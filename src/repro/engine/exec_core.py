"""Shared normalization / accumulation / update core.

Every executor (compiled scan, eager streaming, Pallas-fused) expresses the
paper's Algorithm 1 through these helpers, so the numerics live in exactly
one place:

  * loss normalization (§3.4, eq. 14): either folded into the micro loss
    before differentiation ("scaled" form — loss/N_Sμ for "paper",
    Σ/N_B_valid for "exact"), or deferred to the accumulate ("raw" form —
    the gradient of the unscaled micro loss is accumulated with the scale
    fused in, paper Fig. 2 step ❹, which is what the Pallas kernel does);
  * gradient accumulation in ``accum_dtype`` (fp32 by default, even when
    micro gradients arrive in bf16);
  * the single optimizer update per mini-batch (step ❺) + shared metrics.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import spans
from ..kernels import fused_update, grad_accum_buckets, grad_accum_tree
from .flat import FlatSpec


def denominators(micro_batches) -> Tuple[int, jnp.ndarray]:
    """(N_Sμ, N_B_valid) of a split batch. N_B_valid is the total sample
    weight when a mask is present — padded tail samples contribute 0 and
    dataset-provided fractional weights contribute their weight (the split
    composes mask × weights, see ``plan.split_minibatch``), so exact-mode
    normalization is the weighted mini-batch mean. Else N_Sμ · N_μ."""
    leaves = jax.tree.leaves(micro_batches)
    n_s = leaves[0].shape[0]
    w = micro_batches.get("sample_weight") if hasattr(micro_batches, "get") else None
    total_valid = (jnp.sum(w) if w is not None
                   else jnp.asarray(n_s * leaves[0].shape[1], jnp.float32))
    return n_s, total_valid


def init_accum(params, dtype):
    """Zero gradient accumulator, shaped like params, in ``accum_dtype``."""
    return jax.tree.map(lambda p: jnp.zeros(p.shape, dtype), params)


def micro_loss_fn(loss_fn: Callable, normalization: str, n_s, total_valid,
                  mb, *, defer_scale: bool = False) -> Callable:
    """The per-micro-batch loss to differentiate.

    Exact-mode contract for ``loss_fn``: with ``exact_denom`` set, micro
    contributions must SUM to the mini-batch loss — per-sample losses are
    divided by ``exact_denom``, and any additive (non-per-sample)
    regularizer must carry the micro-batch's valid-sample share
    ``n_valid/exact_denom`` (see ``launch/steps.make_loss_fn``'s MoE
    router aux term). Otherwise executors would weight it inconsistently.

    ``defer_scale=False``: normalization folded in (Algorithm 1 line 11 for
    "paper"; exact denominator for "exact") — the gradient is accumulated
    with a plain add.

    ``defer_scale=True``: the raw micro loss ("paper": micro mean; "exact":
    Σ valid per-sample losses) — the 1/N_Sμ (resp. 1/N_B_valid) scale is
    applied later, fused into the accumulate (see :func:`deferred_scale`).
    """
    def f(p):
        if normalization == "paper":
            loss, metrics = loss_fn(p, mb)
            return (loss, metrics) if defer_scale else (loss / n_s, metrics)
        if normalization != "exact":
            raise ValueError(f"unknown normalization {normalization!r}")
        denom = 1.0 if defer_scale else total_valid
        loss, metrics = loss_fn(p, mb, exact_denom=denom)
        return loss, metrics
    return f


def deferred_scale(normalization: str, n_s, total_valid):
    """The scale fused into the accumulate when the micro loss was raw."""
    if normalization == "paper":
        return 1.0 / n_s
    return 1.0 / total_valid


@jax.named_scope(spans.ACCUMULATE)
def accumulate(acc, grads, *, scale=None, fused: bool = False,
               interpret: Optional[bool] = None, block: Optional[int] = None):
    """acc ← acc + [scale ·] grads, in the accumulator's dtype.

    ``fused=True`` routes through the Pallas kernel
    (``kernels/grad_accum.py``): scaled accumulate with in-place aliasing on
    the fp32 buffer, so the scaled gradient is never materialized."""
    if fused:
        kw = {"interpret": interpret}
        if block is not None:
            kw["block"] = block
        return grad_accum_tree(acc, grads, 1.0 if scale is None else scale, **kw)
    if scale is None:
        return jax.tree.map(lambda a, g: a + g.astype(a.dtype), acc, grads)
    return jax.tree.map(lambda a, g: a + (g * scale).astype(a.dtype), acc, grads)


def metrics_zeros(loss_fn: Callable, normalization: str, params, mb0):
    """Zero-valued metrics pytree (via eval_shape — no FLOPs) used to seed
    the accumulation carry."""
    probe = micro_loss_fn(loss_fn, normalization, 1, jnp.asarray(1.0), mb0)
    shapes = jax.eval_shape(lambda p: probe(p)[1], params)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


@jax.named_scope(spans.UPDATE)
def apply_update(optimizer, grads, opt_state, params):
    """Paper Fig. 2 step ❺: one optimizer update per mini-batch."""
    updates, new_opt_state = optimizer.update(grads, opt_state, params)
    new_params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                              params, updates)
    return new_params, new_opt_state


@jax.named_scope(spans.ACCUMULATE)
def accumulate_flat(acc_buffers, spec: FlatSpec, grads, *, scale=None,
                    interpret: Optional[bool] = None,
                    block: Optional[int] = None):
    """Bucketed step ❹: route a micro-batch's gradient tree into the flat
    ``accum_dtype`` buffers — one masked Pallas launch per dtype bucket
    (O(num_buckets), vs ``accumulate(fused=True)``'s O(num_leaves))."""
    gbufs = spec.flatten(grads, dtype=acc_buffers[0].dtype)
    kw = {"interpret": interpret}
    if block is not None:
        kw["block"] = block
    return grad_accum_buckets(acc_buffers, gbufs,
                              1.0 if scale is None else scale, **kw)


@jax.named_scope(spans.UPDATE)
def apply_update_flat(optimizer, spec: FlatSpec, acc_buffers, opt_state,
                      params, *, interpret: Optional[bool] = None,
                      block: Optional[int] = None):
    """Step ❺ over flat buffers: one in-place Pallas launch per bucket.

    Reads the fp32 flat accumulator and writes params + optimizer state
    through ``kernels/fused_update.py`` (``input_output_aliases`` on every
    state buffer) — no ``updates`` tree, no fresh momentum/``m``/``v``
    trees, and the global-norm clip scale (``FusedUpdateSpec.clip_norm``)
    is computed from the flat accumulator and carried into the kernel
    instead of materializing a scaled gradient tree. Optimizers without a
    ``fused`` hook fall back to the reference tree update."""
    fs = getattr(optimizer, "fused", None)
    if fs is None:
        return apply_update(optimizer, spec.unflatten(acc_buffers, cast=False),
                            opt_state, params)
    kw = {"interpret": interpret}
    if block is not None:
        kw["block"] = block
    gscale = jnp.asarray(1.0, jnp.float32)
    if fs.clip_norm is not None:
        norm = global_grad_norm(acc_buffers)
        gscale = jnp.minimum(1.0, fs.clip_norm / (norm + 1e-12))
    step = opt_state["step"]
    lr_t = fs.schedule(step)
    flat_p = spec.flatten(params)

    if fs.kind == "sgd":
        if fs.momentum:
            flat_m = spec.flatten(opt_state["mom"])
            outs = [fused_update.fused_sgd(
                p, g, m, lr_t, gscale, momentum=fs.momentum,
                weight_decay=fs.weight_decay, nesterov=fs.nesterov, **kw)
                for p, g, m in zip(flat_p, acc_buffers, flat_m)]
            return (spec.unflatten([o[0] for o in outs]),
                    {"mom": spec.unflatten([o[1] for o in outs]),
                     "step": step + 1})
        new_p = [fused_update.fused_sgd(
            p, g, None, lr_t, gscale, weight_decay=fs.weight_decay, **kw)
            for p, g in zip(flat_p, acc_buffers)]
        return spec.unflatten(new_p), {"mom": None, "step": step + 1}

    if fs.kind == "adam":
        step1 = step + 1
        bc1 = 1 - fs.b1 ** step1.astype(jnp.float32)
        bc2 = 1 - fs.b2 ** step1.astype(jnp.float32)
        flat_m = spec.flatten(opt_state["m"])
        flat_v = spec.flatten(opt_state["v"])
        outs = [fused_update.fused_adam(
            p, g, m, v, lr_t, bc1, bc2, gscale, b1=fs.b1, b2=fs.b2,
            eps=fs.eps, weight_decay=fs.weight_decay,
            decoupled=fs.decoupled, **kw)
            for p, g, m, v in zip(flat_p, acc_buffers, flat_m, flat_v)]
        return (spec.unflatten([o[0] for o in outs]),
                {"m": spec.unflatten([o[1] for o in outs]),
                 "v": spec.unflatten([o[2] for o in outs]),
                 "step": step1})

    raise ValueError(f"unknown fused update kind {fs.kind!r}")


def global_grad_norm(grads) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree.leaves(grads)))


# ---------------------------------------------------------------------------
# numeric guard (engine Layer 9)
# ---------------------------------------------------------------------------

def finite_all(grads) -> jnp.ndarray:
    """On-device scalar: True iff every element of the gradient accumulator
    is finite. Works on a params-shaped tree AND on the flat executor's
    dtype-bucketed buffer list (``jax.tree.leaves`` of a list is the list),
    so the check composes with ``FlatSpec`` — one reduction per leaf fused
    into the step, zero extra host syncs."""
    ok = jnp.asarray(True)
    for g in jax.tree.leaves(grads):
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
    return ok


@jax.named_scope(spans.UPDATE)
def guarded_update(optimizer, grads, opt_state, params):
    """Step ❺ behind the finite-check: if the accumulated gradient has any
    non-finite element, skip the update (params + opt state pass through
    unchanged, including the step counter — the step never happened).
    ``lax.cond`` keeps the skip branch free of update math on device.

    Returns ``(new_params, new_opt_state, ok)`` — ``ok`` is the on-device
    finite flag; readback policy (sync for supervised runs) is the
    caller's choice."""
    ok = finite_all(grads)
    new_params, new_opt_state = jax.lax.cond(
        ok,
        lambda p, s: apply_update(optimizer, grads, s, p),
        lambda p, s: (p, s),
        params, opt_state)
    return new_params, new_opt_state, ok


@jax.named_scope(spans.UPDATE)
def guarded_update_flat(optimizer, spec: FlatSpec, acc_buffers, opt_state,
                        params, *, interpret: Optional[bool] = None,
                        block: Optional[int] = None):
    """Flat-buffer variant of :func:`guarded_update`: the finite-check runs
    directly on the dtype buckets (no unflatten), the fused Pallas update
    only on the taken branch."""
    ok = finite_all(acc_buffers)
    new_params, new_opt_state = jax.lax.cond(
        ok,
        lambda p, s: apply_update_flat(optimizer, spec, acc_buffers, s, p,
                                       interpret=interpret, block=block),
        lambda p, s: (p, s),
        params, opt_state)
    return new_params, new_opt_state, ok


def finalize_metrics(metric_sum: Dict[str, Any], loss, grads) -> Dict[str, Any]:
    out = dict(metric_sum)
    out["loss"] = loss  # Σ normalized micro losses == mini-batch mean loss
    out["grad_norm"] = global_grad_norm(grads)
    return out
