"""Mamba2 (SSD — state-space duality) block, TPU-adapted.

The SSD computation is implemented in the *chunked* (block) form: within a
chunk all work is dense matmuls (MXU-friendly — this is the TPU adaptation of
the paper's GPU scan), and a short ``lax.scan`` carries the (H, P, N) state
across chunks. Decode is the O(1) recurrent update.

Shapes: d_inner = expand*d_model, P = head_dim, H = d_inner/P heads,
N = ssm_state, single B/C group (G=1) as in mamba2-780m.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from .. import spans
from . import nn
from . import remat as remat_lib
from .config import ModelConfig


# Mamba-2's initialization (mamba_ssm's ``Mamba2``): A ~ U[1, 16], and
# softplus(dt_bias) log-uniform in [DT_MIN, DT_MAX], floored at DT_FLOOR.
A_INIT_RANGE = (1.0, 16.0)
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4


def ssm_init(key, cfg: ModelConfig):
    d, di, N, H = cfg.d_model, cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
    W = cfg.conv_width
    ks = jax.random.split(key, 4)
    k_a, k_dt = jax.random.split(ks[3])
    conv_dim = di + 2 * N
    A = jax.random.uniform(k_a, (H,), jnp.float32, *A_INIT_RANGE)
    dt = jnp.exp(jax.random.uniform(k_dt, (H,), jnp.float32,
                                    math.log(DT_MIN), math.log(DT_MAX)))
    dt = jnp.maximum(dt, DT_FLOOR)
    return {
        # fused input projection: [z, x, B, C, dt]
        "in_proj": nn.dense_init(ks[0], d, 2 * di + 2 * N + H),
        "conv_w": jax.random.normal(ks[1], (W, conv_dim), jnp.float32) / math.sqrt(W),
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(A),  # A = -exp(A_log)
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) = dt
        "out_norm": nn.rmsnorm_init(di),
        "out_proj": nn.dense_init(ks[2], di, d),
    }


def _split_proj(cfg: ModelConfig, zxbcdt):
    di, N, H = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * N]
    dt = zxbcdt[..., 2 * di + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w, conv_b):
    """Depthwise causal conv, width W. xBC: (B, S, Cdim)."""
    W = conv_w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * conv_w[i].astype(xBC.dtype)
              for i in range(W))
    return jax.nn.silu(out + conv_b.astype(xBC.dtype))


@jax.named_scope(spans.SSD)
def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """Chunked SSD. x: (B,S,H,P); dt: (B,S,H); A: (H,) negative;
    Bm, Cm: (B,S,N) (G=1, shared across heads).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    S0 = S
    if S % Q:  # pad tail: dt=0 steps are identity (decay=1, input=0)
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q
    f32 = jnp.float32
    xc = x.astype(f32).reshape(Bsz, nc, Q, H, P)
    dtc = dt.astype(f32).reshape(Bsz, nc, Q, H)
    Bc = Bm.astype(f32).reshape(Bsz, nc, Q, N)
    Cc = Cm.astype(f32).reshape(Bsz, nc, Q, N)

    a = dtc * A  # (B,nc,Q,H) log-decay per step (negative)
    cum = jnp.cumsum(a, axis=2)  # within-chunk inclusive cumsum
    # intra-chunk (diagonal blocks): L[i,j] = exp(cum_i - cum_j) for i>=j.
    # Above the diagonal cum_i - cum_j > 0 and exp overflows, so the mask
    # goes in before exp: exp(-inf) = 0 forms no inf in the forward and no
    # 0 * inf in the backward.
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(mask[None, None, :, :, None], seg, -jnp.inf))
    xdt = xc * dtc[..., None]  # (B,nc,Q,H,P)
    G = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # (B,nc,Q,Q)
    y_diag = jnp.einsum("bcij,bcijh,bcjhp->bcihp", G, L, xdt)

    # cum falls along the chunk, so the exps below have arguments <= 0
    # chunk summary state: S_c = sum_j exp(cum_last - cum_j) B_j (x_j dt_j)^T
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)  # (B,nc,Q,H)
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc, decay_to_end, xdt)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H) total chunk decay

    # carry state across chunks with an associative scan (log-depth, no
    # while loop — keeps the MXU busy and the HLO cost-analyzable)
    s0 = (jnp.zeros((Bsz, H, P, N), f32) if init_state is None
          else init_state.astype(f32))
    dec4 = chunk_decay[..., None, None]  # (B,nc,H,1,1)

    def combine(l, r):
        (dl, sl), (dr, sr) = l, r
        return dl * dr, sl * dr + sr

    _, s_end = jax.lax.associative_scan(combine, (dec4, states), axis=1)
    # state entering chunk c = decayed s0 + inclusive-scan result of chunk c-1
    cumdec = jnp.cumprod(dec4, axis=1)
    s_end = s_end + cumdec * s0[:, None]
    s_in = jnp.concatenate([s0[:, None], s_end[:, :-1]], axis=1)  # (B,nc,H,P,N)
    final = s_end[:, -1]
    # inter-chunk contribution: y_off[i] = exp(cum_i) * C_i . state_in
    y_off = jnp.einsum("bcin,bcih,bchpn->bcihp", Cc, jnp.exp(cum), s_in)
    y = (y_diag + y_off).reshape(Bsz, S, H, P)[:, :S0]
    return y.astype(x.dtype), final


def ssm_block(p, cfg: ModelConfig, x, compute_dtype=None,
              init_state=None, return_cache: bool = False,
              remat_policy: str = "none"
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence Mamba2 block. x: (B, S, D) -> (B, S, D).

    ``remat_policy="full"`` nests a ``jax.checkpoint`` around the block so
    the chunked-scan intermediates are recomputed per block, not per period."""
    fn = remat_lib.checkpoint_block(
        lambda bp, bx: _ssm_block(bp, cfg, bx, compute_dtype, init_state,
                                  return_cache), remat_policy)
    return fn(p, x)


def _ssm_block(p, cfg: ModelConfig, x, compute_dtype=None,
               init_state=None, return_cache: bool = False
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    B, S, D = x.shape
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    zxbcdt = nn.dense(p["in_proj"], x, compute_dtype)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    xBC_raw = xBC
    xBC = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    xs = xBC[..., :di].reshape(B, S, H, P)
    Bm = xBC[..., di:di + N]
    Cm = xBC[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    A = -jnp.exp(p["A_log"])  # (H,)
    y, final = ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk, init_state)
    y = y + xs * p["D"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, S, di)
    y = nn.rmsnorm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = nn.dense(p["out_proj"], y, compute_dtype)
    if return_cache:
        W = cfg.conv_width
        conv_tail = xBC_raw[:, -(W - 1):, :]
        pad = W - 1 - conv_tail.shape[1]
        if pad > 0:
            conv_tail = jnp.pad(conv_tail, ((0, 0), (pad, 0), (0, 0)))
        return out, {"state": final, "conv": conv_tail}
    return out, final


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype):
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    conv_dim = di + 2 * N
    return {
        "state": jnp.zeros((batch, H, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, conv_dim), dtype),
    }


def ssm_decode_step(p, cfg: ModelConfig, x, cache, compute_dtype=None):
    """One-token recurrent update. x: (B, 1, D)."""
    B = x.shape[0]
    di, N, H, P = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads, cfg.ssm_head_dim
    zxbcdt = nn.dense(p["in_proj"], x[:, 0], compute_dtype)  # (B, ...)
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    # conv over the buffered window
    win = jnp.concatenate([cache["conv"].astype(xBC.dtype),
                           xBC[:, None, :]], axis=1)  # (B, W, Cdim)
    conv_out = jnp.einsum("bwc,wc->bc", win, p["conv_w"].astype(xBC.dtype))
    xBC_c = jax.nn.silu(conv_out + p["conv_b"].astype(xBC.dtype))
    xs = xBC_c[..., :di].reshape(B, H, P)
    Bm = xBC_c[..., di:di + N]  # (B, N)
    Cm = xBC_c[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, H)
    A = -jnp.exp(p["A_log"])
    dec = jnp.exp(dt * A)  # (B, H)
    xdt = xs.astype(jnp.float32) * dt[..., None]  # (B,H,P)
    new_state = (cache["state"] * dec[..., None, None]
                 + jnp.einsum("bn,bhp->bhpn", Bm.astype(jnp.float32), xdt))
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), new_state)
    y = y.astype(xs.dtype) + xs * p["D"].astype(xs.dtype)[None, :, None]
    y = y.reshape(B, di)
    y = nn.rmsnorm(p["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = nn.dense(p["out_proj"], y, compute_dtype)[:, None, :]
    new_cache = {"state": new_state, "conv": win[:, 1:, :]}
    return out, new_cache
