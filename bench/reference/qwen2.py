"""Plain float32 reference of a Qwen2 decoder (arXiv:2407.10671), written
from the published description and the configuration file's keys, for one
sequence at a time.

Each layer: RMSNorm, grouped-query attention with biased q/k/v projections,
rotary embedding on the first and second halves of each head (theta
``rope_theta``) and a causal softmax, residual add; RMSNorm, SwiGLU MLP,
residual add. A final RMSNorm, then the tied embedding as the output head.
The residual stream stays float32 throughout.

The parameter layout is the program's, so that one set of weights made from
the seed serves both: a dict with ``embed/table``, ``final_norm/scale`` and
``blocks``, a one-element tuple whose dict holds every layer's weights
stacked on a leading layer axis. An RMSNorm weight is stored as ``scale``
and applied as ``1 + scale``; a dense layer is ``{"w": (in, out)}``, with
``"b"`` where it has a bias.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rmsnorm, tied_head_nll


def dims(cfg):
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(D=D, H=H, K=cfg["num_key_value_heads"], hd=D // H,
                F=cfg["intermediate_size"], V=cfg["vocab_size"],
                L=cfg["num_hidden_layers"])


def program_fields(cfg):
    """The program's model-configuration fields this file fixes."""
    d = dims(cfg)
    return dict(num_layers=d["L"], d_model=d["D"], num_heads=d["H"],
                num_kv_heads=d["K"], head_dim=d["hd"], d_ff=d["F"],
                vocab_size=d["V"], norm_eps=cfg["rms_norm_eps"],
                rope_theta=cfg["rope_theta"],
                tie_embeddings=cfg["tie_word_embeddings"])


def param_shapes(cfg):
    d = dims(cfg)
    D, H, K, hd, F, V, L = (d[k] for k in ("D", "H", "K", "hd", "F", "V", "L"))

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, F32)

    def dense(i, o, bias=False):
        p = {"w": s(L, i, o)}
        if bias:
            p["b"] = s(L, o)
        return p

    block = {
        "pre_norm": {"scale": s(L, D)},
        "attn": {"wq": dense(D, H * hd, True), "wk": dense(D, K * hd, True),
                 "wv": dense(D, K * hd, True), "wo": dense(H * hd, D)},
        "pre_ffn_norm": {"scale": s(L, D)},
        "ffn": {"w_up": dense(D, F), "w_down": dense(F, D),
                "w_gate": dense(D, F)},
    }
    return {"embed": {"table": s(V, D)}, "final_norm": {"scale": s(D)},
            "blocks": (block,)}


def _leaf(name, shape, key):
    z = jax.random.normal(key, shape, F32)
    if name == "table":
        return 0.02 * z
    if name == "w":
        return z / math.sqrt(shape[-2])
    if name == "b":
        return 0.02 * z
    if name == "scale":
        return 0.1 * z
    raise KeyError(f"no initializer for leaf {name!r}")


def init(cfg, key):
    """Weights drawn from ``key``: N(0, 1/fan_in) matrices, N(0, 0.02^2)
    embedding and biases, norm weights 1 + N(0, 0.1^2)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(param_shapes(cfg))
    out = [_leaf(path[-1].key, sd.shape, jax.random.fold_in(key, i))
           for i, (path, sd) in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def _rope(t, pos, theta):
    """t: (S, n, hd); rotate (first half, second half) pairs by pos * freq."""
    hd = t.shape[-1]
    freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos[:, None].astype(F32) * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    t1, t2 = t[..., :hd // 2], t[..., hd // 2:]
    return jnp.concatenate([t1 * cos - t2 * sin, t1 * sin + t2 * cos], axis=-1)


def sample_loss(params, cfg, tokens, labels, mm):
    """Mean token negative log-likelihood of one sequence."""
    d = dims(cfg)
    H, K, hd = d["H"], d["K"], d["hd"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = jnp.tril(jnp.ones((S, S), bool))
    table = params["embed"]["table"]
    x = table[tokens].astype(F32)

    def dense(p, h):
        y = mm(h, p["w"])
        return y + p["b"] if "b" in p else y

    @jax.checkpoint
    def layer(x, p):
        a = p["attn"]
        h = rmsnorm(x, 1.0 + p["pre_norm"]["scale"], eps)
        q = _rope(dense(a["wq"], h).reshape(S, H, hd), pos, theta)
        k = _rope(dense(a["wk"], h).reshape(S, K, hd), pos, theta)
        v = dense(a["wv"], h).reshape(S, K, hd)
        # query head i reads key/value head i // (H / K)
        k = jnp.repeat(k, H // K, axis=1).transpose(1, 0, 2)
        v = jnp.repeat(v, H // K, axis=1).transpose(1, 0, 2)
        s = mm(q.transpose(1, 0, 2), k.transpose(0, 2, 1)) / math.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        o = mm(jax.nn.softmax(s, axis=-1), v)
        x = x + mm(o.transpose(1, 0, 2).reshape(S, H * hd), a["wo"]["w"])
        f = p["ffn"]
        h = rmsnorm(x, 1.0 + p["pre_ffn_norm"]["scale"], eps)
        g = jax.nn.silu(mm(h, f["w_gate"]["w"])) * mm(h, f["w_up"]["w"])
        return x + mm(g, f["w_down"]["w"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    x = rmsnorm(x, 1.0 + params["final_norm"]["scale"], eps)
    return tied_head_nll(x, table, labels, mm)


def flops_per_token(cfg, seq):
    """Training FLOPs one token requires, recompute not counted (PaLM,
    arXiv:2204.02311, App. B): 6N + 12 L H Q T, with N the weights that
    multiply activations (q, k, v, o, the three MLP matrices and the tied
    head; biases and norms left out), H heads of size Q, T = seq."""
    d = dims(cfg)
    D, H, K, hd, F, V, L = (d[k] for k in ("D", "H", "K", "hd", "F", "V", "L"))
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F
    n = L * per_layer + V * D
    return 6.0 * n + 12.0 * L * H * hd * seq
