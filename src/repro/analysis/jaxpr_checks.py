"""Jaxpr-level contract checks (rules JX001–JX004).

Operates on the *traced* train step — ``executor.trace_step(...)`` /
``jax.make_jaxpr`` over ``ShapeDtypeStruct``s — so every check runs
without allocating or executing anything (dryrun-style).

Primitive names are those of the installed JAX (0.9): ``jax.jit`` traces
to ``jit``, ``jax.checkpoint`` to ``remat2``, collectives to ``psum``,
host callbacks to ``debug_callback``/``io_callback``, and a
``shard_map``-wrapped body to a ``shard_map`` equation whose body jaxpr
hangs off ``eqn.params``. A ``lax.scan`` equation carries
``length``/``num_carry``/``num_consts``, so the micro-batch loop is
analyzable structurally — no unrolling needed: a collective *inside* a
scan of length N executes N times.
"""
from __future__ import annotations

from typing import Any, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .findings import Finding, Report, SEVERITY_ERROR, SEVERITY_WARNING

REMAT_PRIMITIVES = frozenset({"remat", "remat2", "checkpoint"})
CALLBACK_PRIMITIVES = frozenset({
    "io_callback", "debug_callback", "pure_callback", "callback",
    "infeed", "outfeed",
})
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "psum2", "all_gather", "all_to_all", "ppermute",
    "reduce_scatter", "pmax", "pmin",
})
#: primitives whose body executes once per enclosing-trip (not multiplied)
_UNKNOWN_TRIP = frozenset({"while"})


def as_jaxpr(obj):
    """Accept a ClosedJaxpr, a Jaxpr, or anything with ``.jaxpr``."""
    if hasattr(obj, "eqns"):
        return obj
    if hasattr(obj, "jaxpr"):
        return as_jaxpr(obj.jaxpr)
    raise TypeError(f"not a jaxpr: {type(obj)!r}")


def _sub_jaxprs(eqn) -> Iterator[Any]:
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for x in items:
            if hasattr(x, "eqns") or hasattr(x, "jaxpr"):
                yield as_jaxpr(x)


def iter_eqns(jaxpr, _path: Tuple[str, ...] = (),
              _trip: Optional[int] = 1
              ) -> Iterator[Tuple[Any, Tuple[str, ...], Optional[int]]]:
    """Yield ``(eqn, path, trip)`` over every equation, recursively.

    ``path`` is the chain of enclosing primitive names (for locations);
    ``trip`` is how many times the equation executes per call of the
    outermost jaxpr — the product of enclosing ``scan`` lengths, or
    ``None`` once inside a ``while`` (statically unknown)."""
    jaxpr = as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        yield eqn, _path, _trip
        if name == "scan":
            length = eqn.params.get("length")
            inner = (None if (_trip is None or length is None)
                     else _trip * int(length))
            tag = f"scan[{length}]"
        elif name in _UNKNOWN_TRIP:
            inner, tag = None, name
        else:
            inner, tag = _trip, name
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, _path + (tag,), inner)


def count_primitive(jaxpr, names) -> int:
    """Number of equations (not executions) matching ``names``."""
    if isinstance(names, str):
        names = {names}
    return sum(1 for eqn, _, _ in iter_eqns(jaxpr)
               if eqn.primitive.name in names)


def _loc(path: Tuple[str, ...], name: str) -> str:
    return "/".join(path + (name,)) or name


def _param_shape_index(params):
    """(set of param shapes, set of plausible flat-bucket sizes, total
    elements) — what a gradient accumulator can look like: a param-shaped
    leaf (tree accumulators), the same with a leading device dim (the
    sharded streaming carry), or a 1-D per-dtype flat bucket / the full
    concatenation (FlatSpec buffers, psum_flat payloads)."""
    leaves = jax.tree.leaves(params)
    shapes = {tuple(l.shape) for l in leaves}
    by_dtype = {}
    for l in leaves:
        by_dtype[jnp.dtype(l.dtype)] = (by_dtype.get(jnp.dtype(l.dtype), 0)
                                        + int(l.size))
    total = sum(int(l.size) for l in leaves)
    bucket_sizes = set(by_dtype.values()) | {total}
    return shapes, bucket_sizes, total


def _looks_like_accumulator(aval, shapes, bucket_sizes) -> bool:
    if not jnp.issubdtype(aval.dtype, jnp.floating) or aval.ndim < 1:
        return False
    shape = tuple(aval.shape)
    if shape in shapes or (aval.ndim >= 2 and shape[1:] in shapes):
        return True
    return aval.ndim == 1 and int(aval.size) in bucket_sizes


# ---------------------------------------------------------------------------
# JX001 — accumulator dtype
# ---------------------------------------------------------------------------

def check_accum_dtype(jaxpr, plan, params) -> List[Finding]:
    """Every micro-gradient accumulator in the traced step carries
    ``plan.accum_dtype``. Accumulators are located structurally: carries
    of the outermost scan(s) whose length is N_Sμ (the micro-batch loop),
    falling back to accumulator-shaped outputs of per-micro ``jit``
    dispatches (the eager streaming pipeline)."""
    expected = jnp.dtype(plan.accum_dtype)
    n_s = int(plan.num_micro_batches)
    shapes, bucket_sizes, _ = _param_shape_index(params)
    findings: List[Finding] = []
    candidates = []

    for eqn, path, _ in iter_eqns(jaxpr):
        if eqn.primitive.name != "scan":
            continue
        if any(p.startswith("scan[") for p in path):
            continue  # only the outermost (micro-batch) scans
        if eqn.params.get("length") != n_s:
            continue
        nc, nk = eqn.params.get("num_consts", 0), eqn.params.get("num_carry", 0)
        for v in eqn.invars[nc:nc + nk]:
            aval = getattr(v, "aval", None)
            if aval is not None and _looks_like_accumulator(
                    aval, shapes, bucket_sizes):
                candidates.append((aval, _loc(path, f"scan[{n_s}].carry")))

    if not candidates:
        # eager streaming: one jitted dispatch per micro-batch, the
        # accumulator is threaded through jit outputs instead of a scan
        for eqn, path, _ in iter_eqns(jaxpr):
            if eqn.primitive.name != "jit":
                continue
            if any(p.startswith("scan[") or p == "jit" for p in path):
                continue  # top-level dispatches only
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and _looks_like_accumulator(
                        aval, shapes, bucket_sizes):
                    candidates.append((aval, _loc(path, "jit.out")))

    for aval, loc in candidates:
        if jnp.dtype(aval.dtype) != expected:
            findings.append(Finding(
                "JX001", SEVERITY_ERROR,
                f"gradient accumulator is {jnp.dtype(aval.dtype).name}, "
                f"plan.accum_dtype is {expected.name} "
                f"(shape {tuple(aval.shape)})",
                location=loc,
                details={"found_dtype": jnp.dtype(aval.dtype).name,
                         "expected_dtype": expected.name,
                         "shape": tuple(aval.shape)}))
    if not candidates and n_s > 1:
        findings.append(Finding(
            "JX001", SEVERITY_WARNING,
            f"no gradient accumulator located in the traced step "
            f"(N_Smu={n_s}) — dtype contract unverifiable",
            details={"num_micro_batches": n_s}))
    return findings


# ---------------------------------------------------------------------------
# JX002 — remat policy applied
# ---------------------------------------------------------------------------

def check_remat_policy(jaxpr, policy: Optional[str], *,
                       micro_remat: bool = False) -> List[Finding]:
    """The planner's remat lattice row is reflected in the trace: policy
    ``"none"`` (and no micro-step checkpoint) means ZERO remat sub-jaxprs;
    any graded policy means the checkpointed forward actually traced to
    >= 1 ``remat2`` equation (a policy that silently fails to apply is
    exactly the OOM-at-scale failure the planner exists to prevent)."""
    count = count_primitive(jaxpr, REMAT_PRIMITIVES)
    expect_any = micro_remat or (policy is not None and policy != "none")
    if expect_any and count == 0:
        return [Finding(
            "JX002", SEVERITY_ERROR,
            f"plan chose remat_policy={policy!r}"
            f"{' (+remat_micro_step)' if micro_remat else ''} but the "
            "traced step contains no remat/checkpoint sub-jaxpr",
            details={"policy": policy, "remat_eqns": count})]
    if not expect_any and count > 0:
        return [Finding(
            "JX002", SEVERITY_ERROR,
            f"plan chose remat_policy='none' but the traced step contains "
            f"{count} remat sub-jaxpr(s) — paying recompute the planner "
            "did not budget",
            details={"policy": policy, "remat_eqns": count})]
    return []


# ---------------------------------------------------------------------------
# JX003 — no host callbacks / host syncs in the step
# ---------------------------------------------------------------------------

def check_host_callbacks(jaxpr) -> List[Finding]:
    out = []
    for eqn, path, _ in iter_eqns(jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMITIVES:
            out.append(Finding(
                "JX003", SEVERITY_ERROR,
                f"host callback primitive {eqn.primitive.name!r} inside "
                "the jitted train step (forces a device->host sync per "
                "dispatch)",
                location=_loc(path, eqn.primitive.name),
                details={"primitive": eqn.primitive.name}))
    return out


# ---------------------------------------------------------------------------
# JX004 — collective census
# ---------------------------------------------------------------------------

def check_collectives(jaxpr, params, *, n_micro: int,
                      expect: str) -> List[Finding]:
    """Gradient-sync census over the traced step.

    ``expect``:
      * ``"none"``      — single-device step: zero collectives at all.
      * ``"deferred"``  — ShardedExecutor contract: exactly ONE psum whose
        payload covers the gradient buffer per mini-batch, outside the
        micro-batch scan.
      * ``"per-micro"`` — the defer_sync=False baseline: >= N_Sμ gradient
        psums per mini-batch (one inside the scan).

    A psum is counted as a *gradient* sync when its payload is at least
    the total parameter element count (``psum_flat`` concatenates grads +
    loss + metrics + valid-count into one fp32 buffer, so payload >=
    total params); smaller collectives (scalar loss syncs) are censused
    separately and allowed."""
    if expect not in ("none", "deferred", "per-micro"):
        raise ValueError(f"bad expect {expect!r}")
    _, _, total = _param_shape_index(params)
    grad_syncs: List[Tuple[Optional[int], str, int]] = []
    small: List[str] = []
    out: List[Finding] = []

    for eqn, path, trip in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            continue
        payload = sum(int(v.aval.size) for v in eqn.invars
                      if getattr(v, "aval", None) is not None)
        loc = _loc(path, name)
        if expect == "none":
            out.append(Finding(
                "JX004", SEVERITY_ERROR,
                f"collective {name!r} (payload {payload} elems) in a "
                "single-device step",
                location=loc, details={"primitive": name,
                                       "payload_elems": payload}))
        elif name in ("psum", "psum2") and payload >= total:
            grad_syncs.append((trip, loc, payload))
        else:
            small.append(loc)

    if expect == "none":
        return out

    unknown = [loc for trip, loc, _ in grad_syncs if trip is None]
    effective = sum(trip for trip, _, _ in grad_syncs if trip is not None)
    details = {"gradient_syncs": [
        {"trip": t, "location": l, "payload_elems": p}
        for t, l, p in grad_syncs],
        "effective_count": effective, "n_micro": n_micro,
        "other_collectives": small}
    if unknown:
        out.append(Finding(
            "JX004", SEVERITY_ERROR,
            "gradient psum under a while-loop — per-mini-batch sync count "
            "not statically provable", location=unknown[0], details=details))
    elif expect == "deferred" and effective != 1:
        out.append(Finding(
            "JX004", SEVERITY_ERROR,
            f"deferred-sync step must issue exactly ONE gradient psum per "
            f"mini-batch, found {effective} "
            f"(N_Smu={n_micro}) — the amortization the sharded engine "
            "promises (DESIGN.md §Mesh execution) is broken",
            details=details))
    elif expect == "per-micro" and effective < n_micro:
        out.append(Finding(
            "JX004", SEVERITY_ERROR,
            f"per-micro baseline expected >= {n_micro} gradient psums per "
            f"mini-batch, found {effective}", details=details))
    return out


# ---------------------------------------------------------------------------
# JX005 — pipelined (1F1B) collective census
# ---------------------------------------------------------------------------

def check_pipeline_collectives(jaxpr, plan, *, stages: int,
                               expect: str = "deferred",
                               data_axes: Tuple[str, ...] = ("pod", "data"),
                               model_axis: str = "model") -> List[Finding]:
    """Collective census of a :class:`engine.PipelinedExecutor` step.

    The 1F1B schedule is closed-form (``engine.schedule_1f1b``), so the
    stage-boundary traffic is exactly predictable at trace level: one
    ``ppermute`` per tick in which ANY stage runs a forward, plus one per
    tick in which any stage runs a backward (the executor host-gates the
    rest away). The psum census is the deferred-sync contract composed
    with pipelining: ONE data-axis psum for the stage-local gradient
    accumulator per mini-batch, plus ONE (data, model) psum carrying
    shared-param grads + loss + metrics + the valid count. The per-micro
    baseline (``defer_sync=False``) instead issues a data-axis psum in
    every backward-active tick (>= N_Smu of them).

    ``expect``: ``"deferred"`` | ``"per-micro"``. FSDP steps replace the
    data-axis gradient psum with per-leaf psum_scatter (not censused
    here — gate FSDP artifacts on numerics + HLO002 instead)."""
    if expect not in ("deferred", "per-micro"):
        raise ValueError(f"bad expect {expect!r}")
    from ..engine.pipelined import schedule_1f1b
    n_micro = int(plan.num_micro_batches)
    fwd_tab, bwd_tab, _, _ = schedule_1f1b(stages, n_micro)
    expected_pp = int((fwd_tab >= 0).any(axis=1).sum()
                      + (bwd_tab >= 0).any(axis=1).sum())

    pp = 0
    unknown_trip: List[str] = []
    data_psums: List[str] = []
    mixed_psums: List[str] = []
    model_psums: List[str] = []
    for eqn, path, trip in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMITIVES:
            continue
        loc = _loc(path, name)
        if trip is None:
            unknown_trip.append(loc)
            continue
        if name == "ppermute":
            pp += trip
            continue
        if name in ("psum", "psum2"):
            axes = eqn.params.get("axes") or ()
            if isinstance(axes, str):
                axes = (axes,)
            has_data = any(a in data_axes for a in axes)
            has_model = model_axis in axes
            if has_data and has_model:
                mixed_psums.extend([loc] * trip)
            elif has_data:
                data_psums.extend([loc] * trip)
            elif has_model:
                model_psums.extend([loc] * trip)

    details = {"expected_ppermutes": expected_pp, "found_ppermutes": pp,
               "data_psums": len(data_psums),
               "data_model_psums": len(mixed_psums),
               "model_psums": len(model_psums),
               "stages": stages, "n_micro": n_micro, "expect": expect}
    out: List[Finding] = []
    if unknown_trip:
        out.append(Finding(
            "JX005", SEVERITY_ERROR,
            "pipeline collective under a while-loop — the schedule census "
            "is not statically provable", location=unknown_trip[0],
            details=details))
        return out
    if pp != expected_pp:
        out.append(Finding(
            "JX005", SEVERITY_ERROR,
            f"stage-boundary ppermute count {pp} != {expected_pp} (the "
            f"1F1B closed-form census for stages={stages}, "
            f"N_Smu={n_micro}) — the executor is shuffling activations "
            "outside the schedule", details=details))
    if expect == "deferred":
        if len(data_psums) != 1:
            out.append(Finding(
                "JX005", SEVERITY_ERROR,
                f"deferred pipelined step must issue exactly ONE "
                f"data-axis gradient psum per mini-batch, found "
                f"{len(data_psums)}", details=details))
        if len(mixed_psums) != 1:
            out.append(Finding(
                "JX005", SEVERITY_ERROR,
                f"deferred pipelined step must issue exactly ONE "
                f"(data, model) psum (shared grads + loss + metrics + "
                f"valid count), found {len(mixed_psums)}", details=details))
    elif len(data_psums) < n_micro:
        out.append(Finding(
            "JX005", SEVERITY_ERROR,
            f"per-micro pipelined baseline expected >= {n_micro} "
            f"data-axis psums, found {len(data_psums)}", details=details))
    return out


# ---------------------------------------------------------------------------
# the bundled jaxpr passes
# ---------------------------------------------------------------------------

def check_pipelined_step(jaxpr, plan, *, stages: int,
                         expect_sync: str = "deferred",
                         policy: Optional[str] = "__from_plan__",
                         micro_remat: Optional[bool] = None) -> Report:
    """The jaxpr contracts that survive the pipelined (1F1B)
    factorization: JX002 + JX003 + JX005.

    JX001 and JX004 are structurally N/A here and deliberately skipped:
    the executor accumulates micro-gradients in per-stage masked buffers
    threaded through the tick scan (no micro-batch-length scan carry for
    JX001 to locate), and JX004's payload heuristic (psum >= total param
    elements) never fires because the pipelined step splits gradient
    traffic into a staged flat bucket and a shared bucket, each smaller
    than the whole tree. JX005's schedule-exact census replaces both the
    sync-count and payload-coverage halves of JX004."""
    if policy == "__from_plan__":
        policy = plan.remat_policy
    if micro_remat is None:
        micro_remat = bool(getattr(plan, "remat_micro_step", False))
    rep = Report(context={"layer": "jaxpr", "expect_sync": expect_sync,
                          "policy": policy, "pipelined": True})
    rep.extend(check_remat_policy(jaxpr, policy, micro_remat=micro_remat),
               "JX002")
    rep.extend(check_host_callbacks(jaxpr), "JX003")
    rep.extend(check_pipeline_collectives(jaxpr, plan, stages=stages,
                                          expect=expect_sync), "JX005")
    return rep


def check_train_step(jaxpr, plan, params, *, expect_sync: str = "none",
                     policy: Optional[str] = "__from_plan__",
                     micro_remat: Optional[bool] = None) -> Report:
    """All four jaxpr contracts over one traced train step."""
    if policy == "__from_plan__":
        policy = plan.remat_policy
    if micro_remat is None:
        micro_remat = bool(getattr(plan, "remat_micro_step", False))
    rep = Report(context={"layer": "jaxpr", "expect_sync": expect_sync,
                          "policy": policy})
    rep.extend(check_accum_dtype(jaxpr, plan, params), "JX001")
    rep.extend(check_remat_policy(jaxpr, policy, micro_remat=micro_remat),
               "JX002")
    rep.extend(check_host_callbacks(jaxpr), "JX003")
    rep.extend(check_collectives(jaxpr, params,
                                 n_micro=int(plan.num_micro_batches),
                                 expect=expect_sync), "JX004")
    return rep
