"""Plain float32 reference of a Mamba-2 language model (arXiv:2405.21060),
written from the paper and the configuration file's keys, for one sequence
at a time.

Each layer: RMSNorm, then the Mamba-2 mixer, then a residual add. The
mixer projects to (z, x, B, C, dt); a depthwise causal convolution of
width ``d_conv`` and a SiLU act on (x, B, C); ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; the state-space layer (one B/C group) is
computed in its quadratic "dual" form,

    y_i = sum_{j <= i} (C_i . B_j) exp(sum_{k=j+1..i} dt_k A) dt_j x_j,

plus the skip ``D x``; then ``RMSNorm(y * silu(z))`` and the output
projection. A final RMSNorm and the tied embedding as output head. The
residual stream stays float32. The quadratic form holds a (heads, S, S)
decay matrix, so it runs over groups of heads.

The parameter layout is the program's (see ``qwen2.py``): ``blocks`` is a
one-element tuple of ``{"pre_norm", "ssm"}`` stacked over layers, the
mixer's input projection fused as ``[z, x, B, C, dt]``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rmsnorm, tied_head_nll

HEAD_GROUP = 8  # heads whose (S, S) decay matrices are live at one time


def dims(cfg):
    D = cfg["d_model"]
    di = cfg["expand"] * D
    P = cfg["headdim"]
    if cfg["ngroups"] != 1:
        raise ValueError("the reference has one B/C group")
    return dict(D=D, di=di, N=cfg["d_state"], P=P, H=di // P, W=cfg["d_conv"],
                V=cfg["vocab_size"], L=cfg["n_layer"])


def program_fields(cfg):
    """The program's model-configuration fields this file fixes."""
    d = dims(cfg)
    return dict(num_layers=d["L"], d_model=d["D"], vocab_size=d["V"],
                ssm_state=d["N"], ssm_expand=cfg["expand"],
                ssm_head_dim=d["P"], ssm_chunk=cfg["chunk_size"],
                conv_width=d["W"], norm_eps=cfg["norm_epsilon"],
                tie_embeddings=cfg["tie_embeddings"])


def param_shapes(cfg):
    d = dims(cfg)
    D, di, N, H, W, V, L = (d[k] for k in ("D", "di", "N", "H", "W", "V", "L"))

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, F32)

    block = {
        "pre_norm": {"scale": s(L, D)},
        "ssm": {"in_proj": {"w": s(L, D, 2 * di + 2 * N + H)},
                "conv_w": s(L, W, di + 2 * N), "conv_b": s(L, di + 2 * N),
                "A_log": s(L, H), "D": s(L, H), "dt_bias": s(L, H),
                "out_norm": {"scale": s(L, di)},
                "out_proj": {"w": s(L, di, D)}},
    }
    return {"embed": {"table": s(V, D)}, "final_norm": {"scale": s(D)},
            "blocks": (block,)}


def _leaf(name, shape, key):
    z = jax.random.normal(key, shape, F32)
    u = jax.random.uniform(key, shape, F32)
    if name == "table":
        return 0.02 * z
    if name in ("w", "conv_w"):
        return z / math.sqrt(shape[-2])
    if name == "conv_b":
        return 0.02 * z
    if name == "scale":
        return 0.1 * z
    if name == "A_log":  # A in [-16, -1], the paper's initialization range
        return jnp.log(1.0 + 15.0 * u)
    if name == "D":
        return 1.0 + 0.1 * z
    if name == "dt_bias":  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(math.log(1e-3) + u * (math.log(1e-1) - math.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise KeyError(f"no initializer for leaf {name!r}")


def init(cfg, key):
    """Weights drawn from ``key`` (see ``_leaf``)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))
    out = [_leaf(path[-1].key, sd.shape, jax.random.fold_in(key, i))
           for i, (path, sd) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _ssd(xs, dt, A, B, C, mm):
    """xs (S, H, P), dt (S, H), A (H,), B and C (S, N) -> y (S, H, P)."""
    S, H, P = xs.shape
    cum = jnp.cumsum(dt * A, axis=0).T  # (H, S)
    below = jnp.tril(jnp.ones((S, S), bool))
    cb = mm(C, B.T)  # (S, S)
    g = min(HEAD_GROUP, H) if H % min(HEAD_GROUP, H) == 0 else 1

    @jax.checkpoint
    def group(_, args):
        cum_g, dt_g, x_g = args  # (g, S), (g, S), (g, S, P)
        seg = jnp.where(below[None], cum_g[:, :, None] - cum_g[:, None, :],
                        -jnp.inf)
        m = jnp.exp(seg) * cb[None] * dt_g[:, None, :]
        return None, mm(m, x_g)

    _, y = jax.lax.scan(group, None, (
        cum.reshape(H // g, g, S), dt.T.reshape(H // g, g, S),
        xs.transpose(1, 0, 2).reshape(H // g, g, S, P)))
    return y.reshape(H, S, P).transpose(1, 0, 2)


def sample_loss(params, cfg, tokens, labels, mm):
    """Mean token negative log-likelihood of one sequence."""
    d = dims(cfg)
    di, N, P, H, W = d["di"], d["N"], d["P"], d["H"], d["W"]
    eps = cfg["norm_epsilon"]
    S = tokens.shape[0]
    table = params["embed"]["table"]
    x = table[tokens].astype(F32)

    @jax.checkpoint
    def layer(x, p):
        m = p["ssm"]
        h = rmsnorm(x, 1.0 + p["pre_norm"]["scale"], eps)
        zxbcdt = mm(h, m["in_proj"]["w"])
        z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * N], zxbcdt[:, 2 * di + 2 * N:]
        padded = jnp.pad(xbc, ((W - 1, 0), (0, 0)))
        conv = sum(padded[i:i + S] * m["conv_w"][i] for i in range(W))
        xbc = jax.nn.silu(conv + m["conv_b"])
        xs = xbc[:, :di].reshape(S, H, P)
        B, C = xbc[:, di:di + N], xbc[:, di + N:]
        dt = jax.nn.softplus(dt + m["dt_bias"])
        y = _ssd(xs, dt, -jnp.exp(m["A_log"]), B, C, mm)
        y = (y + xs * m["D"][None, :, None]).reshape(S, di)
        y = rmsnorm(y * jax.nn.silu(z), 1.0 + m["out_norm"]["scale"], eps)
        return x + mm(y, m["out_proj"]["w"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = rmsnorm(x, 1.0 + params["final_norm"]["scale"], eps)
    return tied_head_nll(x, table, labels, mm)


def flops_per_token(cfg, seq):
    """Training FLOPs one token requires, recompute not counted: 6N for the
    weights that multiply activations (input and output projections, the
    depthwise convolution, the tied head), plus three times (forward and
    backward) the forward FLOPs of the chunked SSD equations with chunk
    Q = min(chunk_size, seq), per token and layer: C.B over the chunk
    (2 Q N), the in-chunk output (2 Q H P), the chunk state (2 H P N) and
    its read-out (2 H P N)."""
    d = dims(cfg)
    D, di, N, P, H, W, V, L = (d[k] for k in ("D", "di", "N", "P", "H", "W", "V", "L"))
    per_layer = D * (2 * di + 2 * N + H) + W * (di + 2 * N) + di * D
    n = L * per_layer + V * D
    q = min(cfg["chunk_size"], seq)
    ssd = 2 * q * N + 2 * q * H * P + 4 * H * P * N
    return 6.0 * n + 3.0 * L * ssd
