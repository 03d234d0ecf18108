"""Graded rematerialization policies — the compute↔memory axis the planner
trades against the micro-batch size (engine Layer 5, DESIGN.md §Remat
planner).

The paper fits the micro-batch into "the remaining memory after the model
is uploaded" (§4.3.2); remat *creates* memory by trading compute for
activations, so the two knobs must be chosen jointly. The lattice, in
order of increasing memory savings / increasing recompute:

  ``none``    no checkpointing: every intermediate of every period stays
              live for the backward pass (fastest, heaviest).
  ``dots``    ``jax.checkpoint`` per period with
              ``checkpoint_policies.checkpoint_dots``: matmul outputs are
              saved (the expensive-to-recompute part), elementwise ops are
              recomputed.
  ``period``  plain ``jax.checkpoint`` per period (the repo's historical
              ``remat=True``): only the residual stream at each period
              boundary survives the forward; one period is recomputed at a
              time during the backward.
  ``full``    ``period`` plus a nested ``jax.checkpoint`` around every
              block *inside* the period, so the recompute working set is a
              single block rather than a whole period.

Model forwards take ``remat_policy`` (string) next to the legacy
``remat: bool``; :func:`resolve` maps the bool onto the lattice
(True → "period", False → "none") so existing callers are untouched.

Under the policies that recompute per period (:data:`RECOMPUTES_PERIOD`),
:func:`accumulating_scan` runs the period stack with a backward that adds
each period's weight gradient straight into a gradient accumulator.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# Lattice order == escalation order: the planner prefers the leftmost
# (cheapest-recompute) policy whose admitted micro-batch meets the target.
POLICIES = ("none", "dots", "period", "full")
# the policies whose backward recomputes one period at a time from the
# residual stream at its boundary: what :func:`accumulating_scan` keeps
RECOMPUTES_PERIOD = ("period", "full")


def validate(policy: str) -> str:
    if policy not in POLICIES:
        raise ValueError(
            f"unknown remat policy {policy!r}; known: {list(POLICIES)} "
            "(or 'auto' at the planner layer)")
    return policy


def policy_weight(policy: str) -> int:
    """Position on the lattice (0 = no remat). Admission is monotone
    non-decreasing in this weight — the property the planner's escalation
    and the hypothesis tests rely on."""
    return POLICIES.index(validate(policy))


def resolve(remat: Optional[bool] = None,
            remat_policy: Optional[str] = None) -> str:
    """Collapse the (legacy bool, graded policy) pair to one policy.

    An explicit ``remat_policy`` wins; otherwise the bool maps to its
    historical meaning (per-period checkpointing or nothing)."""
    if remat_policy is not None:
        return validate(remat_policy)
    if remat is None or remat:
        return "period"
    return "none"


def checkpoint_period(fn: Callable, policy: str) -> Callable:
    """Wrap a period/scan-body function per the policy (outer level)."""
    validate(policy)
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if policy in ("period", "full"):
        return jax.checkpoint(fn)
    return fn


def checkpoint_block(fn: Callable, policy: str) -> Callable:
    """Wrap a single block inside an already-checkpointed period: only the
    ``full`` policy nests a second checkpoint here, shrinking the backward
    recompute working set from one period to one block."""
    validate(policy)
    if policy == "full":
        return jax.checkpoint(fn)
    return fn


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4))
def accumulating_scan(period_fn: Callable, blocks, accum, x, unroll: int = 1):
    """``lax.scan(period_fn, x, blocks)`` whose gradient for ``blocks`` is
    ``accum`` plus that gradient.

    ``period_fn(x, period_params) -> (x, aux)`` is one period of a stacked
    layer stack, already wrapped by :func:`checkpoint_period` under a
    policy of :data:`RECOMPUTES_PERIOD`; ``blocks`` holds its parameters
    stacked over periods, and ``accum`` a gradient accumulator shaped like
    ``blocks`` (same dtypes). Returns ``(x, aux)`` with ``aux`` stacked
    over periods, as the scan does.

    The forward keeps the residual stream at each period boundary, as the
    checkpointed scan does. The backward is a reverse loop whose state is
    ``(dx, accum)``: each iteration recomputes period ``l`` under
    ``jax.vjp`` and adds its weight gradient into ``accum[l]`` in place.
    The cotangent it returns for ``blocks`` is the updated accumulator;
    ``accum`` gets none. So a micro-batch loop that carries the
    accumulator adds the stack's gradient without a stacked gradient
    buffer of its own, nor a second pass over it."""
    return jax.lax.scan(period_fn, x, blocks, unroll=unroll)


def _accumulating_scan_fwd(period_fn, blocks, accum, x, unroll):
    def body(x, p):
        y, aux = period_fn(x, p)
        return y, (x, aux)

    # traced as the forward of a differentiated scan, as the checkpointed
    # scan's forward is: each period's inputs, the constants it closes over
    # among them, pass through the checkpoint's optimization barrier. Run
    # undifferentiated, the compiler folds constants such as the attention
    # mask out of the loop and keeps them live through the backward.
    (y, (xs, aux)), _ = jax.vjp(
        lambda x, blocks: jax.lax.scan(body, x, blocks, unroll=unroll),
        x, blocks)
    return (y, aux), (blocks, accum, xs)


def _accumulating_scan_bwd(period_fn, unroll, res, cts):
    blocks, accum, xs = res
    dy, daux = cts

    def body(carry, inp):
        dx, acc = carry
        i, x, p, da = inp
        _, vjp = jax.vjp(period_fn, x, p)
        dx, dp = vjp((dx, da))
        # acc[i] += dp, in the accumulator's dtype; the compiler updates
        # the slice of the loop state in place
        acc = jax.tree.map(lambda a, d: a.at[i].add(d.astype(a.dtype)),
                           acc, dp)
        return (dx, acc), None

    (dx, acc), _ = jax.lax.scan(
        body, (dy, accum), (jnp.arange(xs.shape[0]), xs, blocks, daux),
        reverse=True, unroll=unroll)
    return acc, None, dx


accumulating_scan.defvjp(_accumulating_scan_fwd, _accumulating_scan_bwd)
