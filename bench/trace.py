"""Reduction of a profiler trace to the benchmark's device numbers.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps three things: per device, the operations that ran (the ``XLA Ops``
line of each ``/device:TPU:<n>`` plane) and the collectives in flight
(its ``Async XLA Ops`` line), each named ``"<instruction> <result type>
<opcode>"`` (``short``); and the host spans the harness wrote with
``TraceAnnotation`` (``window``, ``input``, ``dispatch``, ``readback``).
``save``/``load`` keep that in a JSON file, which is how the recorded
traces under ``bench/testdata`` are stored.

``reduce`` works on the traced window, the first ``window`` span:

* busy: the union of the intervals in which an operation ran, per device;
  the idle share is 1 - busy / window, averaged over the devices;
* exposed collective: the time in which a collective ran or was in flight
  and no other operation ran, per device, averaged (an operation is a
  collective by its opcode);
* device ops: time per operation name, summed over devices and divided by
  their number, the largest first;
* idle gaps: the device's idle intervals, longest first, each named by the
  host span that overlaps it most (``host`` where none does).

An operation that encloses another one on the same device (a loop or a
call around its body) is a container and is left out, so only the
operations that did the work count.
"""
from __future__ import annotations

import functools
import json
import re
from typing import Dict, List, Tuple

SPANS = ("window", "input", "dispatch", "readback")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(-start|-done)?$")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")

Interval = Tuple[float, float, str]  # (start_ns, end_ns, name)


@functools.lru_cache(maxsize=None)  # a step repeats its operations' names
def short(text: str) -> str:
    """``"%fusion.7 = f32[8,128]{1,0} fusion(...), kind=..."`` ->
    ``"fusion.7 f32[8,128] fusion"``; a tuple result reads ``tuple``."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text[:120]
    op = _OPCODE.search(" " + rest)
    typ = "tuple" if rest.startswith("(") else re.match(r"[^{\s]*", rest).group(0)
    return f"{name.lstrip('%')} {typ} {op.group(1) if op else '?'}"


def opcode(name: str) -> str:
    return name.rsplit(" ", 1)[-1]


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.match(opcode(name)))


class Trace:
    """``devices``: {ordinal: operations}; ``in_flight``: {ordinal:
    collectives in flight}; ``host``: the harness's spans."""

    def __init__(self, devices: Dict[int, List[Interval]],
                 host: List[Interval], in_flight=None):
        self.devices = {int(k): sorted(tuple(e) for e in v)
                        for k, v in devices.items()}
        self.in_flight = {int(k): sorted(tuple(e) for e in v)
                          for k, v in (in_flight or {}).items()}
        self.host = sorted(tuple(e) for e in host)


def from_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[Interval]] = {}
    in_flight: Dict[int, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, ASYNC_LINE):
                ev = [(e.start_ns, e.start_ns + e.duration_ns, short(e.name))
                      for e in line.events]
                if line.name == ASYNC_LINE:
                    ev = [x for x in ev if is_collective(x[2])]
                dest = devices if line.name == OPS_LINE else in_flight
                dest.setdefault(int(m.group(1)), []).extend(ev)
            elif not m and plane.name.startswith("/host"):
                host.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events if e.name in SPANS)
    if not devices:
        names = sorted({p.name for p in data.planes})
        raise RuntimeError(f"no {OPS_LINE!r} line on a TPU plane in {path}; "
                           f"planes: {names}")
    return Trace(devices, host, in_flight)


def save(trace: Trace, path: str):
    with open(path, "w") as f:
        json.dump({"devices": {str(k): v for k, v in trace.devices.items()},
                   "in_flight": {str(k): v for k, v in trace.in_flight.items()},
                   "host": trace.host}, f, separators=(",", ":"))


def load(path: str) -> Trace:
    with open(path) as f:
        d = json.load(f)
    return Trace(d["devices"], d["host"], d.get("in_flight"))


def union(ivals) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in ivals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(ivals) -> float:
    return sum(e - s for s, e in ivals)


def subtract(a, b) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events: List[Interval]) -> List[Interval]:
    """Drop containers: an event that encloses the next one to start."""
    ev = sorted(events, key=lambda x: (x[0], -x[1]))
    return [a for a, b in zip(ev, ev[1:] + [None])
            if b is None or not (b[0] < a[1] and b[1] <= a[1])]


def clip(events, w0, w1) -> List[Interval]:
    return [(max(s, w0), min(e, w1), n) for s, e, n in events
            if e > w0 and s < w1]


def window(trace: Trace) -> Tuple[float, float]:
    spans = [(s, e) for s, e, n in trace.host if n == "window"]
    if spans:
        return spans[0]
    ev = [x for v in trace.devices.values() for x in v]
    return min(x[0] for x in ev), max(x[1] for x in ev)


def _name_gap(gap, host) -> str:
    best, name = 0.0, "host"
    for s, e, n in host:
        if n == "window":
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def reduce(trace: Trace, top: int = 10) -> dict:
    w0, w1 = window(trace)
    win = w1 - w0
    if win <= 0:
        raise ValueError("empty traced window")
    busy, coll, exposed, gaps = [], [], [], []
    op_time: Dict[str, float] = {}
    for dev in sorted(trace.devices):
        ev = clip(leaves(trace.devices[dev]), w0, w1)
        all_u = union(ev)
        coll_u = union([x for x in ev if is_collective(x[2])]
                       + clip(trace.in_flight.get(dev, []), w0, w1))
        comp_u = union([x for x in ev if not is_collective(x[2])])
        busy.append(length(all_u))
        coll.append(length(coll_u))
        exposed.append(length(subtract(coll_u, comp_u)))
        for s, e, n in ev:
            op_time[n] = op_time.get(n, 0.0) + (e - s)
        gaps.extend(subtract([(w0, w1)], all_u))
    nd = len(busy)
    host = [x for x in trace.host if x[1] > w0 and x[0] < w1]
    named = sorted(((e - s, _name_gap((s, e), host)) for s, e in gaps),
                   reverse=True)
    by_span: Dict[str, float] = {}
    for d, n in named:
        by_span[n] = by_span.get(n, 0.0) + d / nd
    ns = 1e-9
    return {
        "devices": nd,
        "window_s": win * ns,
        "busy_s": sum(busy) / nd * ns,
        "idle_share": sum(1.0 - b / win for b in busy) / nd,
        "collective_s": sum(coll) / nd * ns,
        "exposed_collective_s": sum(exposed) / nd * ns,
        "exposed_share": sum(x / win for x in exposed) / nd,
        "device_ops": [[n, t / nd * ns] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, d * ns] for d, n in named[:top]],
        "idle_by_span": {k: v * ns for k, v in sorted(by_span.items())},
    }
