"""Compiled-HLO contract checks (rules HLO001–HLO005).

Operates on ``jax.jit(step).lower(*abstract).compile()`` artifacts
(``executor.lower_step`` exposes these) — ``memory_analysis()`` for the
byte-level contracts, ``as_text()`` for the collective census. This
module is the single source of truth for HLO text queries: every
collective is read by :func:`collectives`; ``launch/dryrun.py``
re-exports :func:`collective_bytes` from here, and the test suites
assert their collective contracts through this API instead of
hand-parsing HLO strings.
"""
from __future__ import annotations

import math
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

from .findings import Finding, SEVERITY_ERROR

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
#: ``%name = <shape or tuple of shapes> <kind>[-start|-done](...``
#: up to the instruction's ``op_name`` metadata, where it has one
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<out>.+?)\s+"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?P<phase>-start|-done)?\("
    r"(?:.*?op_name=\"(?P<op_name>[^\"]*)\")?", re.M)
_COMMENT_RE = re.compile(r"/\*.*?\*/")


class Collective(NamedTuple):
    """One collective instruction: its kind, the byte size of each operand
    of its (possibly tuple) output, and its ``op_name`` ("" if none)."""
    kind: str
    operand_bytes: Tuple[int, ...]
    op_name: str


def hlo_text(obj) -> str:
    """HLO text from a Compiled / Lowered / already-rendered string."""
    if isinstance(obj, str):
        return obj
    if hasattr(obj, "as_text"):
        return obj.as_text()
    if hasattr(obj, "compile"):  # Lowered
        return obj.compile().as_text()
    raise TypeError(f"cannot extract HLO text from {type(obj)!r}")


def collectives(obj) -> List[Collective]:
    """Every collective instruction in the compiled HLO text, in order.

    ``/*...*/`` comments (XLA prints ``/*index=5*/`` inside long tuples)
    are stripped first; an async ``-start``/``-done`` pair counts once,
    as its ``-start`` (which carries the shape)."""
    out = []
    for m in _COLLECTIVE_RE.finditer(_COMMENT_RE.sub("", hlo_text(obj))):
        if m.group("phase") == "-done":
            continue
        sizes = tuple(
            _DTYPE_BYTES[dt] * math.prod(int(d) for d in dims.split(",") if d)
            for dt, dims in _SHAPE_RE.findall(m.group("out"))
            if dt in _DTYPE_BYTES)
        out.append(Collective(m.group("kind"), sizes,
                              m.group("op_name") or ""))
    return out


def collective_bytes(obj) -> Dict[str, Dict[str, int]]:
    """Per-device output bytes + instruction count of every collective,
    by kind."""
    out: Dict[str, Dict[str, int]] = {}
    for c in collectives(obj):
        d = out.setdefault(c.kind, {"bytes": 0, "count": 0})
        d["bytes"] += sum(c.operand_bytes)
        d["count"] += 1
    return out


def tree_bytes(tree) -> int:
    return sum(int(l.size) * jax.numpy.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def measured_peak_bytes(compiled) -> int:
    """Per-device peak of a compiled executable — the PR-6 estimator:
    arguments + outputs + temps − aliased (donated buffers counted once)."""
    mem = compiled.memory_analysis()
    return int(mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes
               - getattr(mem, "alias_size_in_bytes", 0))


# ---------------------------------------------------------------------------
# HLO001 — donation aliasing coverage
# ---------------------------------------------------------------------------

def check_aliasing(compiled, state_bytes: int, *,
                   context: str = "") -> List[Finding]:
    """The zero-copy update contract: with params/opt-state donated,
    ``input_output_aliases`` must cover at least the full state footprint
    (every donated state buffer reused in place). ``state_bytes`` is the
    params+opt-state byte total (``tree_bytes``); a donated-but-unaliased
    buffer means XLA is round-tripping the update through a copy."""
    mem = compiled.memory_analysis()
    aliased = int(getattr(mem, "alias_size_in_bytes", 0))
    if aliased < state_bytes:
        return [Finding(
            "HLO001", SEVERITY_ERROR,
            f"input_output_aliases covers {aliased} bytes < state "
            f"footprint {state_bytes} bytes — a donated param/opt/"
            "accumulator buffer is not updated in place",
            location=context,
            details={"alias_bytes": aliased, "state_bytes": state_bytes})]
    return []


# ---------------------------------------------------------------------------
# HLO002 — unexpected collectives at stage boundaries
# ---------------------------------------------------------------------------

def check_unexpected_ops(obj, *, expect_gather: bool = False,
                         context: str = "") -> List[Finding]:
    """A replicated-state (pure-DP) step has no business all-gathering:
    params are already whole on every device, so any ``all-gather`` means
    a sharding boundary is materializing state mid-step. (FSDP launch
    paths DO gather — pass ``expect_gather=True`` there.)"""
    if expect_gather:
        return []
    gather = collective_bytes(obj).get("all-gather")
    if not gather:
        return []
    return [Finding(
        "HLO002", SEVERITY_ERROR,
        f"{gather['count']} unexpected all-gather op(s) ({gather['bytes']} "
        "bytes) in a replicated-state step", location=context,
        details={"op": "all-gather", **gather})]


# ---------------------------------------------------------------------------
# HLO003 — memory model cross-check
# ---------------------------------------------------------------------------

def check_memory_model(compiled, modeled_bytes: Optional[int], *,
                       tolerance: float = 16.0,
                       slack_bytes: int = 1 << 30,
                       context: str = "") -> List[Finding]:
    """Tripwire for catastrophic model/compiler divergence: the analytic
    ``core/memory_model`` estimate and the compiled peak must agree within
    ``tolerance``× (plus ``slack_bytes`` absolute headroom for tiny
    configs). The default is deliberately loose — the uncalibrated model
    is conservative by design (PR-6 measured ~4–5× on reduced configs);
    this rule exists to catch order-of-magnitude breaks (a dropped remat,
    a duplicated accumulator), not to re-litigate calibration."""
    if modeled_bytes is None:
        return []
    measured = measured_peak_bytes(compiled)
    hi = modeled_bytes * tolerance + slack_bytes
    lo = max(0.0, modeled_bytes / tolerance - slack_bytes)
    if not (lo <= measured <= hi):
        return [Finding(
            "HLO003", SEVERITY_ERROR,
            f"compiled peak {measured} bytes vs modeled {modeled_bytes} "
            f"bytes — outside {tolerance}x tolerance "
            f"(allowed [{int(lo)}, {int(hi)}])",
            location=context,
            details={"measured_bytes": measured,
                     "modeled_bytes": modeled_bytes,
                     "tolerance": tolerance, "slack_bytes": slack_bytes})]
    return []


# ---------------------------------------------------------------------------
# HLO004 — compiled gradient-sync schedule
# ---------------------------------------------------------------------------

#: all-reduce operands at or under this byte count are treated as
#: scalar/metric traffic (the grad-norm scalar, XLA-introduced scalar
#: syncs from sharding propagation), not gradient syncs
_SCALAR_ALLREDUCE_BYTES = 64


def _allreduce_census(colls: List[Collective]) -> Tuple[int, int]:
    """(all-reduce operands, non-scalar ones). Gradient syncs are counted
    by operand, not by instruction: XLA's all-reduce combiner prints N
    syncs as one tuple all-reduce of N operands, and the chip still moves
    N gradients."""
    ops = [b for c in colls if c.kind == "all-reduce"
           for b in c.operand_bytes]
    return len(ops), sum(b > _SCALAR_ALLREDUCE_BYTES for b in ops)


def check_pipeline_hlo(obj, *, expect: str, n_micro: int,
                       max_ppermutes: int,
                       context: str = "") -> List[Finding]:
    """HLO005 — the compiled pipelined (1F1B) schedule.

    All-reduce operands are classified by payload: non-scalar ones are
    gradient syncs (deferred contract: exactly TWO — the stage-local flat
    data psum and the shared (data, model) psum; per-micro baseline: >=
    N_Smu), scalar ones are metric traffic (the grad-norm scalar plus
    whatever scalar syncs XLA's sharding propagation introduces) and
    exempt. The collective-permute count is bounded, not pinned: XLA
    legitimately merges adjacent permutes of the same source/target
    pairs, so the compiled count must be >= 1 and <= the jaxpr
    schedule census (``max_ppermutes``) — more than the schedule means
    boundary traffic the executor never issued."""
    if expect not in ("deferred", "per-micro"):
        raise ValueError(f"bad expect {expect!r}")
    colls = collectives(obj)
    n_ops, grads = _allreduce_census(colls)
    perms = sum(c.kind == "collective-permute" for c in colls)
    details = {"nonscalar_operands": grads, "scalar_operands": n_ops - grads,
               "collective_permutes": perms, "max_ppermutes": max_ppermutes,
               "n_micro": n_micro, "expect": expect}
    out: List[Finding] = []
    if (grads != 2 if expect == "deferred" else grads < n_micro):
        want = ("exactly 2 (stage-local data psum + shared data-model psum)"
                if expect == "deferred" else f">= {n_micro}")
        out.append(Finding(
            "HLO005", SEVERITY_ERROR,
            f"{expect} pipelined step compiled to {grads} non-scalar "
            f"all-reduce operand(s), contract is {want}", location=context,
            details=details))
    if not (1 <= perms <= max_ppermutes):
        out.append(Finding(
            "HLO005", SEVERITY_ERROR,
            f"{perms} collective-permute(s) in the compiled pipelined "
            f"step, expected between 1 and the jaxpr schedule census "
            f"{max_ppermutes}", location=context, details=details))
    return out


def check_gradient_sync(obj, *, expect: str, n_micro: int,
                        context: str = "") -> List[Finding]:
    """The PR-5 contract at the HLO level, counted in non-scalar
    all-reduce operands: a deferred-sync sharded step syncs exactly ONE
    gradient operand per mini-batch; the per-micro baseline >= N_Sμ
    (however XLA's combiner groups them into instructions); a mesh-free
    step has no all-reduce at all. NOTE the compiled module keeps rolled
    loops rolled — pass an UNROLLED plan (or trust the jaxpr-level
    JX004, which multiplies scan trip counts) when the micro loop is a
    scan."""
    if expect not in ("none", "deferred", "per-micro"):
        raise ValueError(f"bad expect {expect!r}")
    n_ops, grads = _allreduce_census(collectives(obj))
    details = {"allreduce_operands": n_ops, "nonscalar_operands": grads,
               "n_micro": n_micro, "expect": expect}
    if expect == "none":
        bad = n_ops and f"{n_ops} all-reduce operand(s) in a mesh-free step"
    elif expect == "deferred":
        bad = grads != 1 and (
            f"deferred-sync step compiled to {grads} non-scalar all-reduce "
            "operand(s), contract is exactly 1 per mini-batch")
    else:
        bad = grads < n_micro and (
            f"per-micro baseline compiled to {grads} non-scalar all-reduce "
            f"operand(s), expected >= {n_micro}")
    return ([Finding("HLO004", SEVERITY_ERROR, bad, location=context,
                     details=details)] if bad else [])
