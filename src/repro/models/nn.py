"""Minimal functional NN primitives (no flax): params are nested dicts of
jnp arrays; every layer is an ``init_*`` + pure apply function pair.

Master parameters are fp32; compute dtype is configurable (bf16 on TPU).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def auto_axes() -> dict:
    """``{name: size}`` of the ambient mesh's Auto axes (``jax.set_mesh``),
    the axes a sharding hint may name. Empty with no mesh; a ``shard_map``
    body sees its axes as Manual (each body holds its local shard), so
    they are left out too."""
    mesh = jax.sharding.get_abstract_mesh()
    return {n: s for n, s, t in zip(mesh.axis_names, mesh.axis_sizes,
                                    mesh.axis_types)
            if t == jax.sharding.AxisType.Auto}


def shard_hint(x, *axes):
    """Best-effort ``with_sharding_constraint``: applies only when a mesh
    context is active; axis names absent from the mesh are dropped from the
    spec (so the same model code runs on any mesh or none at all)."""
    present_axes = auto_axes()
    if not present_axes:
        return x

    def filt(a):
        if a is None:
            return None
        names = a if isinstance(a, tuple) else (a,)
        present = tuple(n for n in names if n in present_axes)
        if not present:
            return None
        return present if len(present) > 1 else present[0]

    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(
        x, PartitionSpec(*[filt(a) for a in axes]))


def mesh_axis_size(name: str) -> int:
    return auto_axes().get(name, 1)


_SEQ_STATE = {"enabled": None}  # per-trace override (set by forward())


def set_seq_shard(enabled):
    """Trace-scoped override of sequence parallelism (None = env default).
    Measured: big win for dense/hybrid/ssm stacks (gemma2 train: −58%
    collective, −62% compute), a regression for MoE stacks (mixtral: +170%
    collective from dispatch-buffer reshard churn) — so forward() gates it
    by family. Returns the override it replaces."""
    prev, _SEQ_STATE["enabled"] = _SEQ_STATE["enabled"], enabled
    return prev


def _seq_shard_on() -> bool:
    if _SEQ_STATE["enabled"] is not None:
        return _SEQ_STATE["enabled"]
    import os
    return os.environ.get("REPRO_SEQ_SHARD", "1") != "0"


def _seq_ok(x) -> bool:
    m = mesh_axis_size("model")
    return (_seq_shard_on() and m > 1 and x.ndim >= 3
            and x.shape[1] % m == 0 and x.shape[1] >= m)


def seq_sharded(x):
    """Sequence-parallel residual stream (Korthikanti et al.): between
    blocks, activations are sharded over the ``model`` axis on the SEQUENCE
    dim, so the TP boundary is a bf16 reduce-scatter/all-gather pair instead
    of replicating (B, S, D) in fp32 — the dominant collective in the
    baseline roofline. No-op when S is not divisible (e.g. decode, S=1)."""
    if not _seq_ok(x):
        return x
    spec = [("pod", "data"), "model"] + [None] * (x.ndim - 2)
    return shard_hint(x, *spec)


def seq_gathered(x):
    """Gather the sequence dim before cross-token or TP-weight matmuls
    (emitted as a bf16 all-gather when x is bf16)."""
    if not _seq_ok(x):
        return x
    spec = [("pod", "data")] + [None] * (x.ndim - 1)
    return shard_hint(x, *spec)


def vocab_sharded(logits):
    """Vocab-sharded (Megatron-style) logits (B, ..., V): with the embedding
    table sharded on V, the head emits V/TP-sharded logits (the batch stays
    data-sharded) and the reductions over V run shardedly, never
    materializing (or all-reducing) a full-vocab tensor."""
    spec = [None] * logits.ndim
    spec[0] = ("pod", "data")
    spec[-1] = "model"
    return shard_hint(logits, *spec)


def dense_init(key, in_dim: int, out_dim: int, *, bias: bool = False,
               scale: Optional[float] = None, dtype=jnp.float32):
    scale = (1.0 / math.sqrt(in_dim)) if scale is None else scale
    p = {"w": jax.random.normal(key, (in_dim, out_dim), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((out_dim,), dtype)
    return p


def dense(p, x, compute_dtype=None):
    w = p["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ w
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def rmsnorm_init(dim: int):
    return {"scale": jnp.zeros((dim,), jnp.float32)}  # gemma-style (1+scale)


def rmsnorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + p["scale"])).astype(dt)


def layernorm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32),
            "bias": jnp.zeros((dim,), jnp.float32)}


def layernorm(p, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(dt)


def softcap(x, cap: Optional[float]):
    """tanh logit soft-capping (gemma2 / grok)."""
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# RoPE (incl. multi-axis M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                sections: Tuple[int, int, int]) -> jnp.ndarray:
    """Multi-axis RoPE (qwen2-vl): positions (3, B, S) for (t, h, w) axes;
    ``sections`` gives the per-axis number of frequency pairs and must sum to
    head_dim/2."""
    hd = x.shape[-1]
    assert sum(sections) * 2 == hd, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # per-frequency axis selector: frequencies are split into 3 contiguous
    # sections, each rotated by its own position stream.
    sel = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                           for i, s in enumerate(sections)])  # (hd/2,)
    pos = positions.astype(jnp.float32)[sel]  # (hd/2, B, S)
    ang = pos.transpose(1, 2, 0) * freqs  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------

def ffn_init(key, d_model: int, d_ff: int, kind: str):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_up": dense_init(k1, d_model, d_ff),
         "w_down": dense_init(k2, d_ff, d_model)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(k3, d_model, d_ff)
    return p


def _ffn_spec(ndim: int, last):
    spec = [None] * ndim
    spec[0] = ("pod", "data")
    spec[-1] = last
    return spec


def ffn(p, x, kind: str, compute_dtype=None):
    x = seq_gathered(x)  # bf16 all-gather at the TP boundary
    up = dense(p["w_up"], x, compute_dtype)
    if kind == "swiglu":
        h = jax.nn.silu(dense(p["w_gate"], x, compute_dtype)) * up
    elif kind == "geglu":
        h = jax.nn.gelu(dense(p["w_gate"], x, compute_dtype)) * up
    elif kind == "gelu":
        h = jax.nn.gelu(up)
    else:
        raise ValueError(kind)
    # hidden stays TP-sharded on d_ff; output reduce-scatters back to the
    # sequence-sharded residual stream (without hints GSPMD all-gathers the
    # (B, S, d_ff) hidden in fp32 at 32k)
    h = shard_hint(h, *_ffn_spec(h.ndim, "model"))
    out = dense(p["w_down"], h, compute_dtype)
    return seq_sharded(out)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int):
    return {"table": jax.random.normal(key, (vocab, d_model), jnp.float32) * 0.02}


def embed(p, tokens, compute_dtype=None, scale: bool = False):
    t = p["table"]
    if compute_dtype is not None:
        t = t.astype(compute_dtype)
    x = jnp.take(t, tokens, axis=0)
    if scale:
        x = x * jnp.asarray(math.sqrt(t.shape[-1]), x.dtype)
    return x


def unembed(p, x, compute_dtype=None):
    t = p["table"]
    if compute_dtype is not None:
        t = t.astype(compute_dtype)
        x = x.astype(compute_dtype)
    return x @ t.T
