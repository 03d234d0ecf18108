"""Decoder-only transformer assembly covering the dense / MoE / SSM / hybrid
/ VLM families.

Layers are grouped into *periods* (one cycle of ``cfg.layer_pattern``); the
per-slot parameters are stacked over periods and the depth dimension runs
under ``jax.lax.scan`` — this keeps the HLO size O(pattern) instead of
O(num_layers), which matters for the 512-device dry-run compiles, and gives
the natural remat boundary for Micro-Batch Streaming.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .. import spans
from . import attention, moe, nn, recurrent, ssm
from . import remat as remat_lib
from .config import ModelConfig

VISION_EMBED_DIM = 1280  # stubbed ViT output width (qwen2-vl card)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _slot_init(key, cfg: ModelConfig, kind: str):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {}
    if kind in ("global", "local"):
        p["pre_norm"] = nn.rmsnorm_init(cfg.d_model)
        p["attn"] = attention.attn_init(ks[0], cfg)
        if cfg.use_post_norm:
            p["post_norm"] = nn.rmsnorm_init(cfg.d_model)
        p["pre_ffn_norm"] = nn.rmsnorm_init(cfg.d_model)
        if cfg.is_moe:
            p["moe"] = moe.moe_init(ks[1], cfg)
        else:
            p["ffn"] = nn.ffn_init(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_kind)
        if cfg.use_post_norm:
            p["post_ffn_norm"] = nn.rmsnorm_init(cfg.d_model)
    elif kind == "recurrent":
        p["pre_norm"] = nn.rmsnorm_init(cfg.d_model)
        p["rec"] = recurrent.recurrent_init(ks[0], cfg)
        p["pre_ffn_norm"] = nn.rmsnorm_init(cfg.d_model)
        p["ffn"] = nn.ffn_init(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_kind)
    elif kind == "ssm":
        p["pre_norm"] = nn.rmsnorm_init(cfg.d_model)
        p["ssm"] = ssm.ssm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    kemb, kblocks, kvis = jax.random.split(key, 3)
    P = cfg.num_periods
    blocks = []
    for s, kind in enumerate(cfg.layer_pattern):
        per = [_slot_init(jax.random.fold_in(kblocks, s * 1000 + i), cfg, kind)
               for i in range(P)]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
    params = {
        "embed": nn.embed_init(kemb, cfg.vocab_size, cfg.d_model),
        "final_norm": nn.rmsnorm_init(cfg.d_model),
        "blocks": tuple(blocks),
    }
    if cfg.is_vlm:
        params["vision_proj"] = nn.dense_init(kvis, VISION_EMBED_DIM, cfg.d_model)
    if not cfg.tie_embeddings:
        params["unembed"] = nn.dense_init(jax.random.fold_in(kemb, 1),
                                          cfg.d_model, cfg.vocab_size)
    return params


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def _window_for(cfg: ModelConfig, kind: str, global_window: Optional[int]):
    if kind == "local":
        return cfg.sliding_window
    return global_window  # None => full attention


def _theta_for(cfg: ModelConfig, kind: str):
    if kind == "global" and cfg.rope_theta_global is not None:
        return cfg.rope_theta_global
    return cfg.rope_theta


def _apply_slot(p, cfg: ModelConfig, kind: str, x, positions, *, dtype,
                global_window=None, mrope_positions=None,
                want_cache: bool = False, max_len: Optional[int] = None,
                remat_policy: str = "none", lengths=None):
    """Returns (x, aux_loss, cache_entry). Under ``remat_policy="full"``
    each block (attention / FFN / MoE / SSM / RG-LRU) nests its own
    ``jax.checkpoint`` inside the per-period one, so the backward pass
    recomputes one block at a time instead of a whole period."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("global", "local"):
        window = _window_for(cfg, kind, global_window)

        def attn_part(sp, x):
            h = nn.rmsnorm(sp["pre_norm"], x, cfg.norm_eps)
            h, kv = attention.attn_block(
                sp["attn"], cfg, h, positions, window=window,
                rope_theta=_theta_for(cfg, kind), compute_dtype=dtype,
                mrope_positions=mrope_positions)
            if cfg.use_post_norm:
                h = nn.rmsnorm(sp["post_norm"], h, cfg.norm_eps)
            return h, kv

        h, kv = remat_lib.checkpoint_block(attn_part, remat_policy)(p, x)
        x = x + h
        h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
        if cfg.is_moe:
            h, aux = moe.moe_block(p["moe"], cfg, h, compute_dtype=dtype,
                                   remat_policy=remat_policy)
        else:
            h = remat_lib.checkpoint_block(
                lambda fp, hh: nn.ffn(fp, hh, cfg.ffn_kind,
                                      compute_dtype=dtype),
                remat_policy)(p["ffn"], h)
        if cfg.use_post_norm:
            h = nn.rmsnorm(p["post_ffn_norm"], h, cfg.norm_eps)
        x = x + h
        if want_cache:
            kv = attention.ring_cache_from_full(kv[0], kv[1], positions,
                                                window, max_len,
                                                lengths=lengths)
        return x, aux, kv
    if kind == "recurrent":
        h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
        h, final_h = recurrent.recurrent_block(p["rec"], cfg,
                                               nn.seq_gathered(h),
                                               compute_dtype=dtype,
                                               return_cache=want_cache,
                                               remat_policy=remat_policy)
        x = x + nn.seq_sharded(h)
        h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
        x = x + remat_lib.checkpoint_block(
            lambda fp, hh: nn.ffn(fp, hh, cfg.ffn_kind, compute_dtype=dtype),
            remat_policy)(p["ffn"], h)
        return x, aux, final_h
    if kind == "ssm":
        h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
        h, final = ssm.ssm_block(p["ssm"], cfg, nn.seq_gathered(h),
                                 compute_dtype=dtype,
                                 return_cache=want_cache,
                                 remat_policy=remat_policy)
        return x + nn.seq_sharded(h), aux, final
    raise ValueError(kind)


def _embed_inputs(params, cfg: ModelConfig, tokens, vision_embeds, dtype):
    x = nn.embed(params["embed"], tokens, dtype, scale=cfg.embed_scale)
    if cfg.is_vlm and vision_embeds is not None:
        vis = nn.dense(params["vision_proj"], vision_embeds, dtype)
        if cfg.embed_scale:
            vis = vis * jnp.asarray(cfg.d_model ** 0.5, vis.dtype)
        # prefix-image layout: first n_vis positions are image patches
        n_vis = vis.shape[1]
        x = jnp.concatenate([vis, x[:, n_vis:]], axis=1)
    return x


def forward(params, cfg: ModelConfig, tokens, *, positions=None,
            vision_embeds=None, mrope_positions=None, dtype=jnp.bfloat16,
            global_window=None, remat: bool = True,
            remat_policy: Optional[str] = None, return_hidden=False,
            scan_unroll: int = 1):
    """Full-sequence forward (training / prefill). tokens: (B, S) int32.

    ``remat_policy`` grades activation checkpointing (see ``models/remat``);
    when None the legacy ``remat`` bool maps onto the lattice
    (True → "period", False → "none").

    Returns (logits (B,S,V) fp32, aux_loss scalar)."""
    policy = remat_lib.resolve(remat, remat_policy)
    B, S = tokens.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    # sequence parallelism: measured win for dense/hybrid/ssm, regression
    # for MoE (see nn.set_seq_shard) — gate by family
    nn.set_seq_shard(False if cfg.is_moe else None)
    try:
        with jax.named_scope(spans.TRUNK):
            x = nn.seq_sharded(_embed_inputs(params, cfg, tokens,
                                             vision_embeds, dtype))

            def period_fn(x, slot_params):
                aux_total = jnp.zeros((), jnp.float32)
                for kind, p in zip(cfg.layer_pattern, slot_params):
                    x, aux, _ = _apply_slot(p, cfg, kind, x, positions,
                                            dtype=dtype,
                                            global_window=global_window,
                                            mrope_positions=mrope_positions,
                                            remat_policy=policy)
                    aux_total = aux_total + aux
                return x, aux_total

            period_fn = remat_lib.checkpoint_period(period_fn, policy)

            def scan_body(x, slot_params):
                return period_fn(x, slot_params)

            x, aux = jax.lax.scan(scan_body, x, params["blocks"],
                                  unroll=scan_unroll)
            x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if return_hidden:
            return x, jnp.sum(aux)
        logits = _lm_head(params, cfg, x)
        return logits, jnp.sum(aux)
    finally:
        nn.set_seq_shard(None)


@jax.named_scope(spans.HEAD)
def _lm_head(params, cfg: ModelConfig, x):
    if cfg.tie_embeddings:
        logits = nn.unembed(params["embed"], x, jnp.float32)
    else:
        logits = nn.dense(params["unembed"], x, jnp.float32)
    # vocab-sharded logits (Megatron-style): with the embedding table sharded
    # on V, the head emits V/TP-sharded logits (batch stays data-sharded) and
    # the CE reduces shardedly — never materializing (or all-reducing) a
    # full-vocab logits tensor.
    spec = [None] * logits.ndim
    spec[0] = ("pod", "data")
    spec[-1] = "model"
    logits = nn.shard_hint(logits, *spec)
    return nn.softcap(logits, cfg.final_softcap)


def supports_ragged_prefill(cfg: ModelConfig) -> bool:
    """True when a right-padded ragged prompt batch prefills EXACTLY: pure
    attention stacks only. Causal attention never lets a real query row see
    the padding appended after it, but state-carrying blocks (ssm /
    recurrent conv+recurrence) run their scan *through* the padded tail,
    and MoE routing competes padded tokens for expert capacity — both
    change real-token outputs, so those families must prefill exact-length
    groups instead (``engine/serving`` enforces this per family)."""
    return (not cfg.is_moe
            and all(k in ("global", "local") for k in cfg.layer_pattern))


def prefill(params, cfg: ModelConfig, tokens, max_len: int, *,
            positions=None, vision_embeds=None, mrope_positions=None,
            dtype=jnp.bfloat16, global_window=None, scan_unroll: int = 1,
            lengths=None):
    """Serving prefill: full-sequence forward that also builds the decode
    cache (ring layout, matching ``init_cache``). Returns
    (last_token_logits (B, V), cache).

    ``lengths`` (B,) serves a RIGHT-PADDED ragged prompt batch: the logits
    are taken at each row's last real token (``lengths[b] - 1``) and the
    ring cache holds only real tokens (padding never evicts real keys from
    a sliding window). Only valid for configs where padding is exact —
    see :func:`supports_ragged_prefill`."""
    B, S = tokens.shape[:2]
    if lengths is not None and not supports_ragged_prefill(cfg):
        raise ValueError(
            f"{cfg.name}: ragged (right-padded) prefill is only exact for "
            "pure-attention stacks; this config has state-carrying or MoE "
            "blocks — prefill exact-length groups instead "
            "(see transformer.supports_ragged_prefill)")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    nn.set_seq_shard(False if cfg.is_moe else None)
    try:
        x = nn.seq_sharded(_embed_inputs(params, cfg, tokens, vision_embeds,
                                         dtype))

        def scan_body(x, slot_params):
            caches = []
            for kind, p in zip(cfg.layer_pattern, slot_params):
                x, _, c = _apply_slot(p, cfg, kind, x, positions, dtype=dtype,
                                      global_window=global_window,
                                      mrope_positions=mrope_positions,
                                      want_cache=True, max_len=max_len,
                                      lengths=lengths)
                caches.append(c)
            return x, tuple(caches)

        x, cache = jax.lax.scan(scan_body, x, params["blocks"],
                                unroll=scan_unroll)
        if lengths is None:
            x_last = x[:, -1:]
        else:
            idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, S - 1)
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        x = nn.rmsnorm(params["final_norm"], x_last, cfg.norm_eps)
        return _lm_head(params, cfg, x)[:, 0], cache
    finally:
        nn.set_seq_shard(None)


# ---------------------------------------------------------------------------
# serving: prefill -> cache, decode steps
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               global_window: Optional[int] = None):
    """Decode cache pytree: tuple per pattern slot, leaves stacked over
    periods (leading dim P)."""
    P = cfg.num_periods
    caches = []
    for kind in cfg.layer_pattern:
        if kind in ("global", "local"):
            w = _window_for(cfg, kind, global_window)
            c = attention.init_kv_cache(cfg, batch, max_len, w, dtype)
        elif kind == "recurrent":
            c = recurrent.init_recurrent_cache(cfg, batch, dtype)
        elif kind == "ssm":
            c = ssm.init_ssm_cache(cfg, batch, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (P,) + x.shape), c))
    return tuple(caches)


def decode_step(params, cfg: ModelConfig, token, cache, cur_pos, *,
                dtype=jnp.bfloat16, global_window=None, scan_unroll: int = 1):
    """One decode step. token: (B, 1) int32; cur_pos: (B,) absolute position.

    Returns (logits (B, 1, V), new_cache).

    The period loop is a ``fori_loop`` carrying the cache and updating it
    in place with dynamic_update_slice — a scan's xs→ys would hold TWO full
    copies of the KV cache live (new + old), doubling decode HBM."""
    x = nn.embed(params["embed"], token, dtype, scale=cfg.embed_scale)

    def period_body(x, slot_params, slot_cache):
        new_caches = []
        for kind, p, c in zip(cfg.layer_pattern, slot_params, slot_cache):
            if kind in ("global", "local"):
                h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
                h, nc = attention.attn_decode_step(
                    p["attn"], cfg, h, c, cur_pos,
                    window=_window_for(cfg, kind, global_window),
                    rope_theta=_theta_for(cfg, kind), compute_dtype=dtype)
                if cfg.use_post_norm:
                    h = nn.rmsnorm(p["post_norm"], h, cfg.norm_eps)
                x = x + h
                h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
                if cfg.is_moe:
                    h, _ = moe.moe_block(p["moe"], cfg, h, compute_dtype=dtype)
                else:
                    h = nn.ffn(p["ffn"], h, cfg.ffn_kind, compute_dtype=dtype)
                if cfg.use_post_norm:
                    h = nn.rmsnorm(p["post_ffn_norm"], h, cfg.norm_eps)
                x = x + h
            elif kind == "recurrent":
                h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
                h, nc = recurrent.recurrent_decode_step(p["rec"], cfg, h, c,
                                                        compute_dtype=dtype)
                x = x + h
                h = nn.rmsnorm(p["pre_ffn_norm"], x, cfg.norm_eps)
                x = x + nn.ffn(p["ffn"], h, cfg.ffn_kind, compute_dtype=dtype)
            elif kind == "ssm":
                h = nn.rmsnorm(p["pre_norm"], x, cfg.norm_eps)
                h, nc = ssm.ssm_decode_step(p["ssm"], cfg, h, c,
                                            compute_dtype=dtype)
                x = x + h
            new_caches.append(nc)
        return x, tuple(new_caches)

    P = cfg.num_periods
    if scan_unroll >= P:  # fully unrolled (dry-run cost probes)
        new_cache = cache
        for i in range(P):
            sp = jax.tree.map(lambda a: a[i], params["blocks"])
            sc = jax.tree.map(lambda a: a[i], new_cache)
            x, nc = period_body(x, sp, sc)
            new_cache = jax.tree.map(
                lambda full, new: full.at[i].set(new.astype(full.dtype)),
                new_cache, nc)
    else:
        def loop_body(i, carry):
            x, full_cache = carry
            sp = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                params["blocks"])
            sc = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
                full_cache)
            x, nc = period_body(x, sp, sc)
            full_cache = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), i, 0),
                full_cache, nc)
            return x, full_cache

        x, new_cache = jax.lax.fori_loop(0, P, loop_body, (x, cache))
    x = nn.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return _lm_head(params, cfg, x), new_cache
