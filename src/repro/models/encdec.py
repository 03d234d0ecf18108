"""Encoder-decoder transformer (seamless-m4t family).

The modality frontend (mel-spectrogram + conv feature extractor) is stubbed
per the assignment: the encoder consumes precomputed frame embeddings
``(B, S_enc, d_model)``. Everything downstream — the 12L encoder, 12L
decoder with cross-attention, tied LM head — is fully built.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import attention, nn
from . import remat as remat_lib
from .config import ModelConfig


def _enc_layer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "pre_norm": nn.rmsnorm_init(cfg.d_model),
        "attn": attention.attn_init(ks[0], cfg),
        "pre_ffn_norm": nn.rmsnorm_init(cfg.d_model),
        "ffn": nn.ffn_init(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_kind),
    }


def _dec_layer_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 3)
    return {
        "pre_norm": nn.rmsnorm_init(cfg.d_model),
        "self_attn": attention.attn_init(ks[0], cfg),
        "cross_norm": nn.rmsnorm_init(cfg.d_model),
        "cross_attn": attention.attn_init(ks[1], cfg),
        "pre_ffn_norm": nn.rmsnorm_init(cfg.d_model),
        "ffn": nn.ffn_init(ks[2], cfg.d_model, cfg.d_ff, cfg.ffn_kind),
    }


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    ke, kd, kemb = jax.random.split(key, 3)
    enc = [_enc_layer_init(jax.random.fold_in(ke, i), cfg)
           for i in range(cfg.encoder_layers)]
    dec = [_dec_layer_init(jax.random.fold_in(kd, i), cfg)
           for i in range(cfg.num_layers)]
    return {
        "embed": nn.embed_init(kemb, cfg.vocab_size, cfg.d_model),
        "enc_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc),
        "enc_norm": nn.rmsnorm_init(cfg.d_model),
        "dec_layers": jax.tree.map(lambda *xs: jnp.stack(xs), *dec),
        "final_norm": nn.rmsnorm_init(cfg.d_model),
    }


def encode(params, cfg: ModelConfig, frames, *, dtype=jnp.bfloat16,
           remat: bool = True, remat_policy: Optional[str] = None,
           scan_unroll: int = 1):
    """frames: (B, S_enc, d_model) stubbed frontend embeddings."""
    policy = remat_lib.resolve(remat, remat_policy)
    B, S, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = frames.astype(dtype)

    def attn_part(p, h):
        B_, S_, _ = h.shape
        H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = nn.dense(p["attn"]["wq"], h, dtype).reshape(B_, S_, H, hd)
        k = nn.dense(p["attn"]["wk"], h, dtype).reshape(B_, S_, K, hd)
        v = nn.dense(p["attn"]["wv"], h, dtype).reshape(B_, S_, K, hd)
        q = nn.apply_rope(q, positions, cfg.rope_theta)
        k = nn.apply_rope(k, positions, cfg.rope_theta)
        o = attention.multihead_attention(q, k, v, q_pos=positions,
                                          k_pos=positions, causal=False,
                                          softcap=cfg.attn_softcap)
        return nn.dense(p["attn"]["wo"], o.reshape(B_, S_, H * hd), dtype)

    def layer(x, p):
        h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
        x = x + remat_lib.checkpoint_block(attn_part, policy)(p, h)
        h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
        x = x + remat_lib.checkpoint_block(
            lambda fp, hh: nn.ffn(fp, hh, cfg.ffn_kind, dtype),
            policy)(p["ffn"], h)
        return x, None

    layer = remat_lib.checkpoint_period(layer, policy)
    x, _ = jax.lax.scan(layer, x, params["enc_layers"], unroll=scan_unroll)
    return nn.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def forward(params, cfg: ModelConfig, frames, tgt_tokens, *,
            dtype=jnp.bfloat16, remat: bool = True,
            remat_policy: Optional[str] = None, scan_unroll: int = 1,
            return_hidden: bool = False):
    """Teacher-forced forward. Returns (logits (B, S_dec, V), aux=0), or
    with ``return_hidden`` the decoder's final hidden states (B, S_dec,
    d_model) in place of the logits."""
    policy = remat_lib.resolve(remat, remat_policy)
    enc_out = encode(params, cfg, frames, dtype=dtype, remat_policy=policy,
                     scan_unroll=scan_unroll)
    B, S = tgt_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = nn.embed(params["embed"], tgt_tokens, dtype, scale=cfg.embed_scale)

    def self_part(p, h):
        h, _ = attention.attn_block(p["self_attn"], cfg, h, positions,
                                    compute_dtype=dtype)
        return h

    def cross_part(p, h):
        h, _ = attention.cross_attn_block(p["cross_attn"], cfg, h,
                                          kv_src=enc_out, compute_dtype=dtype)
        return h

    def layer(x, p):
        h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
        x = x + remat_lib.checkpoint_block(self_part, policy)(p, h)
        h = nn.rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        x = x + remat_lib.checkpoint_block(cross_part, policy)(p, h)
        h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
        x = x + remat_lib.checkpoint_block(
            lambda fp, hh: nn.ffn(fp, hh, cfg.ffn_kind, dtype),
            policy)(p["ffn"], h)
        return x, None

    layer = remat_lib.checkpoint_period(layer, policy)
    x, _ = jax.lax.scan(layer, x, params["dec_layers"], unroll=scan_unroll)
    x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, jnp.zeros((), jnp.float32)
    logits = nn.unembed(params["embed"], x, jnp.float32)
    return nn.softcap(logits, cfg.final_softcap), jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_decode_cache(params, cfg: ModelConfig, frames, max_len: int,
                      dtype=jnp.bfloat16):
    """Runs the encoder, precomputes per-layer cross-attn K/V, and allocates
    the self-attn ring cache."""
    enc_out = encode(params, cfg, frames, dtype=dtype, remat=False)
    B = frames.shape[0]
    K, hd = cfg.num_kv_heads, cfg.head_dim
    T = enc_out.shape[1]

    def cross_kv(p):
        k = nn.dense(p["cross_attn"]["wk"], enc_out, dtype).reshape(B, T, K, hd)
        v = nn.dense(p["cross_attn"]["wv"], enc_out, dtype).reshape(B, T, K, hd)
        return {"k": k, "v": v}

    cross = jax.lax.map(cross_kv, params["dec_layers"])
    self_cache = attention.init_kv_cache(cfg, B, max_len, None, dtype)
    self_cache = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape),
        self_cache)
    return {"self": self_cache, "cross": cross}


def decode_step(params, cfg: ModelConfig, token, cache, cur_pos, *,
                dtype=jnp.bfloat16, scan_unroll: int = 1):
    """One decoder token. token: (B,1); cur_pos: (B,)."""
    x = nn.embed(params["embed"], token, dtype, scale=cfg.embed_scale)

    def layer(x, p, c_self, c_cross):
        h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
        h, nc = attention.attn_decode_step(p["self_attn"], cfg, h, c_self,
                                           cur_pos, compute_dtype=dtype)
        x = x + h
        h = nn.rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        h, _ = attention.cross_attn_block(p["cross_attn"], cfg, h,
                                          kv_cache=(c_cross["k"], c_cross["v"]),
                                          compute_dtype=dtype)
        x = x + h
        h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
        x = x + nn.ffn(p["ffn"], h, cfg.ffn_kind, dtype)
        return x, nc

    # fori_loop with in-place cache update (single live cache copy; see
    # transformer.decode_step)
    L = cfg.num_layers
    if scan_unroll >= L:
        new_self = cache["self"]
        for i in range(L):
            p = jax.tree.map(lambda a: a[i], params["dec_layers"])
            cs = jax.tree.map(lambda a: a[i], new_self)
            cc = jax.tree.map(lambda a: a[i], cache["cross"])
            x, nc = layer(x, p, cs, cc)
            new_self = jax.tree.map(
                lambda full, new: full.at[i].set(new.astype(full.dtype)),
                new_self, nc)
    else:
        def loop_body(i, carry):
            x, self_cache = carry
            p = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                params["dec_layers"])
            cs = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                self_cache)
            cc = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                cache["cross"])
            x, nc = layer(x, p, cs, cc)
            self_cache = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), i, 0),
                self_cache, nc)
            return x, self_cache

        x, new_self = jax.lax.fori_loop(0, L, loop_body, (x, cache["self"]))
    x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = nn.unembed(params["embed"], x, jnp.float32)
    return (nn.softcap(logits, cfg.final_softcap),
            {"self": new_self, "cross": cache["cross"]})
