"""Device time of the training step by phase, from a profiler trace.

The program names its phases with ``jax.named_scope`` (``repro.spans``:
``trunk``, ``head``, ``accumulate``, ``update``, ``grad_sync``), and JAX
adds the direction of differentiation to the same name stack: the
backward's ops carry ``transpose(``, ops recomputed under
``jax.checkpoint`` carry ``rematted_computation`` as well, the first
forward carries neither. Each HLO instruction keeps its name stack as its
``op_name``, and the profiler stores every executable's HLO module with
the trace (the ``/host:metadata`` plane of the ``.xplane.pb``).

Each operation that ran on a device is put in one phase by the
``op_name`` of its instruction (``classify``):

* ``grad_sync``, ``update`` or ``accumulate`` where that scope is in the
  name stack (in that order of precedence);
* else, inside the loss (``trunk`` or ``head``), its direction:
  ``forward``, ``recompute`` or ``backward``;
* else ``other``.

``head`` is a tag beside the phase: an op under it also counts toward
the head's time. A fusion takes the ``op_name`` of its first dot or
convolution where it has one, else its own (which XLA copies from the
fusion's root): the tied head's weight-gradient dot fused with the
accumulate add is backward work, not accumulation.

``reduce`` partitions the busy time of the traced window (``bench/trace``:
the first ``window`` span, else the extent of the device ops; containers
left out) over the phases, per device, and averages over the devices:
where two operations overlap on one device, the one that started first
keeps the time, so the phases sum to the busy time exactly.

  python3 -m bench.phases DIR   # from the checkout's root; DIR holds a
                                # trace, e.g. the launcher's --profile-dir
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys
from typing import Dict, Iterator, List, NamedTuple, Tuple

from bench import trace as trace_lib

PHASES = ("forward", "recompute", "backward", "accumulate", "update",
          "grad_sync", "other")
SCOPED = ("grad_sync", "update", "accumulate")  # precedence, first wins
LOSS = ("trunk", "head")
HEAD = "head"
MATMUL = ("dot", "convolution")
MODULES_LINE = "XLA Modules"  # a device's module runs, beside its ops
_SPLIT = re.compile(r"[/()]")


def classify(op_name: str) -> Tuple[str, bool]:
    """(phase, under ``head``) of one name stack."""
    words = set(_SPLIT.split(op_name))
    for scope in SCOPED:
        if scope in words:
            return scope, False
    if words.isdisjoint(LOSS):
        return "other", False
    if "transpose" in words:
        phase = "recompute" if "rematted_computation" in words else "backward"
    else:
        phase = "forward"
    return phase, HEAD in words


# -- HLO modules ------------------------------------------------------------


class Instr(NamedTuple):
    opcode: str
    op_name: str
    calls: Tuple[str, ...]  # the computations a fusion calls, by name
    operands: Tuple[str, ...] = ()  # instructions, by name


@dataclasses.dataclass
class Module:
    """One HLO module: its instructions by name (unique in a module) and
    its computations as lists of instruction names, root last."""
    name: str
    instrs: Dict[str, Instr]
    comps: Dict[str, List[str]]

    def op_name(self, inst: str, depth: int = 8) -> str:
        """The name stack that decides ``inst``'s phase: a fusion's first
        dot or convolution's, else its own, else its root's; an op the
        compiler added without one (a layout copy or reshape) takes its
        first operand's, up to ``depth`` producers back."""
        ins = self.instrs[inst]
        if ins.opcode == "fusion":
            for sub in self._fused(ins):
                if sub.opcode in MATMUL and sub.op_name:
                    return sub.op_name
            if not ins.op_name and ins.calls:
                root = self.comps.get(ins.calls[0])
                if root and self.instrs[root[-1]].op_name:
                    return self.instrs[root[-1]].op_name
        if ins.op_name or not depth:
            return ins.op_name
        first = ins.operands[0] if ins.operands else None
        return self.op_name(first, depth - 1) if first in self.instrs else ""

    def _fused(self, ins: Instr) -> Iterator[Instr]:
        for comp in ins.calls:
            for name in self.comps.get(comp, ()):
                sub = self.instrs[name]
                yield sub
                if sub.opcode == "fusion":
                    yield from self._fused(sub)

    def phases(self) -> Dict[str, Tuple[str, bool]]:
        return {n: classify(self.op_name(n)) for n in self.instrs}


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protocol buffer message:
    an int for varints, bytes for the rest."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 1:
            v, i = b[i:i + 8], i + 8
        elif kind == 5:
            v, i = b[i:i + 4], i + 4
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protocol buffer wire type {kind} at byte {i}")
        yield key >> 3, v


def _packed(v) -> List[int]:
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


# field numbers of xla/service/hlo.proto and tsl/profiler/protobuf/xplane.proto
def module_from_proto(name: str, data: bytes) -> Module:
    """A ``Module`` from a serialized ``HloModuleProto``."""
    comps_raw = [list(_fields(v)) for k, v in _fields(data) if k == 3]
    comp_names = {}
    inst_names = {}
    for c in comps_raw:
        d = dict(c)
        comp_names[d.get(5, 0)] = d.get(1, b"").decode()
        for k, v in c:
            if k == 2:
                fd = dict(_fields(v))
                inst_names[fd.get(35)] = fd.get(1, b"").decode()
    instrs: Dict[str, Instr] = {}
    comps: Dict[str, List[str]] = {}
    for c in comps_raw:
        d = dict(c)
        order, root = [], None
        for k, v in c:
            if k != 2:
                continue
            f = list(_fields(v))
            fd = dict(f)
            meta = dict(_fields(fd[7])) if 7 in fd else {}
            calls = tuple(comp_names[i] for k2, v2 in f if k2 == 38
                          for i in _packed(v2) if i in comp_names)
            operands = tuple(inst_names[i] for k2, v2 in f if k2 == 36
                             for i in _packed(v2) if i in inst_names)
            iname, opcode = fd.get(1, b"").decode(), fd.get(2, b"").decode()
            instrs[iname] = Instr(opcode, meta.get(2, b"").decode(),
                                  calls if opcode == "fusion" else (),
                                  operands)
            if fd.get(35) == d.get(6):
                root = iname
            else:
                order.append(iname)
        comps[d.get(1, b"").decode()] = order + ([root] if root else [])
    return Module(name, instrs, comps)


_HEADER = re.compile(r"^(?:ENTRY )?%?([^\s(]+)")
_INSTR = re.compile(r"^\s+(ROOT )?%?([^\s=]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(([^)]*)\)")
_OPERAND = re.compile(r"%([^\s,]+)")
_CALLS = re.compile(r"\bcalls=(?:%([^\s,}]+)|\{([^}]*)\})")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def module_from_text(name: str, text: str) -> Module:
    """A ``Module`` from HLO text with metadata (``compiled.as_text()``)."""
    instrs: Dict[str, Instr] = {}
    comps: Dict[str, List[str]] = {}
    order: List[str] = []
    root = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            if line.endswith("{") and not line[:1].isspace():
                order, root = [], None
                comps[_HEADER.match(line).group(1)] = order
            elif line.startswith("}") and root:
                order.append(root)
            continue
        is_root, iname, rest = m.groups()
        op = _OPCODE.search(" " + rest)
        calls = _CALLS.search(rest)
        called = ()
        if calls:
            called = tuple(c.strip().lstrip("%") for c in
                           (calls.group(1) or calls.group(2)).split(","))
        meta = _OP_NAME.search(rest)
        instrs[iname] = Instr(op.group(1) if op else "",
                              meta.group(1) if meta else "", called,
                              tuple(_OPERAND.findall(op.group(2))) if op else ())
        if is_root:
            root = iname
        else:
            order.append(iname)
    return Module(name, instrs, comps)


def modules_from_xplane(path: str) -> Dict[str, Module]:
    """The HLO modules the profiler stored with a trace, keyed as the
    metadata plane names them (``"<module>(<program id>)"``)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for k, plane in _fields(space):
        if k != 1:
            continue
        fields = list(_fields(plane))
        if dict(fields).get(2) != b"/host:metadata":
            continue
        for k2, entry in fields:
            if k2 != 4:  # event_metadata map entry
                continue
            meta = list(_fields(dict(_fields(entry))[2]))
            name = dict(meta).get(2, b"").decode()
            for k3, stat in meta:
                proto = dict(_fields(stat)).get(6) if k3 == 5 else None
                if proto:  # HloProto: field 1 is the HloModuleProto
                    module = dict(_fields(proto)).get(1)
                    if module:
                        out[name] = module_from_proto(name, module)
    return out


# -- reduction --------------------------------------------------------------


def partition(events) -> Dict[object, float]:
    """Time per event name over disjoint shares of the union of the
    intervals: where events overlap, the one that started first keeps
    the time. The values sum to ``trace.length(trace.union(events))``."""
    out: Dict[object, float] = {}
    cursor = float("-inf")
    for s, e, name in sorted(events, key=lambda x: (x[0], x[1])):
        if e > cursor:
            out[name] = out.get(name, 0.0) + e - max(s, cursor)
            cursor = e
    return out


def reduce(trace: trace_lib.Trace, phase_of, top: int = 10) -> dict:
    """Phase times over the trace's window. ``phase_of(name)`` gives a
    device op's (phase, under head) from its name in ``trace``.

    Returns seconds per phase and for the head, averaged over devices,
    ``busy_s`` and ``window_s`` as ``trace.reduce`` counts them, and the
    ops that land in ``other``, the largest first."""
    w0, w1 = trace_lib.window(trace)
    if w1 <= w0:
        raise ValueError("empty traced window")
    phase_s = dict.fromkeys(PHASES, 0.0)
    head_s = busy = 0.0
    other: Dict[str, float] = {}
    for dev in sorted(trace.devices):
        ev = trace_lib.clip(trace_lib.leaves(trace.devices[dev]), w0, w1)
        busy += trace_lib.length(trace_lib.union(ev))
        for name, t in partition(ev).items():
            phase, head = phase_of(name)
            phase_s[phase] += t
            head_s += t * head
            if phase == "other":
                other[name] = other.get(name, 0.0) + t
    nd, ns = len(trace.devices), 1e-9
    return {
        "devices": nd,
        "window_s": (w1 - w0) * ns,
        "busy_s": busy / nd * ns,
        "phase_s": {p: t / nd * ns for p, t in phase_s.items()},
        "head_s": head_s / nd * ns,
        "other_ops": [[n, t / nd * ns] for n, t in
                      sorted(other.items(), key=lambda kv: (-kv[1], kv[0]))[:top]],
    }


def shares(reduced: dict) -> Dict[str, float]:
    """Each phase's and the head's device time over the window, in %."""
    w = reduced["window_s"]
    out = {f"{p}_pct": 100.0 * t / w for p, t in reduced["phase_s"].items()}
    out["head_pct"] = 100.0 * reduced["head_s"] / w
    return out


# -- reading a trace --------------------------------------------------------


def from_xplane(path: str) -> Tuple[trace_lib.Trace, Dict[str, Module]]:
    """The device ops of a ``.xplane.pb``, each named ``"<module>/<instr>"``
    (the module whose run on that device encloses the op, from the
    device's ``XLA Modules`` line), the host spans ``bench/trace`` keeps,
    and the HLO modules stored with the trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, List[trace_lib.Interval]] = {}
    host: List[trace_lib.Interval] = []
    for plane in data.planes:
        m = trace_lib.DEVICE_PLANE.match(plane.name)
        if m is None:
            if plane.name.startswith("/host"):
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for line in plane.lines for e in line.events
                         if e.name in trace_lib.SPANS]
            continue
        lines = {line.name: line for line in plane.lines}
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines[MODULES_LINE].events) \
            if MODULES_LINE in lines else []
        ops = []
        for e in lines[trace_lib.OPS_LINE].events \
                if trace_lib.OPS_LINE in lines else ():
            s = e.start_ns
            inst = trace_lib.short(e.name).split(" ", 1)[0]
            ops.append((s, s + e.duration_ns, f"{_run_at(runs, s)}/{inst}"))
        devices[int(m.group(1))] = ops
    if not any(devices.values()):
        raise RuntimeError(f"no {trace_lib.OPS_LINE!r} events on a TPU plane "
                           f"in {path}")
    return trace_lib.Trace(devices, host), modules_from_xplane(path)


def _run_at(runs, t) -> str:
    """Name of the module run (start, end, name) enclosing time ``t``."""
    i = bisect.bisect_right(runs, (t, float("inf"), "")) - 1
    return runs[i][2] if i >= 0 and runs[i][1] >= t else ""


def phase_table(modules: Dict[str, Module]):
    """``phase_of`` for ``reduce`` over ops named ``"<module>/<instr>"``;
    an op of no stored module is ``other``."""
    tables = {k: m.phases() for k, m in modules.items()}

    def phase_of(name: str) -> Tuple[str, bool]:
        mod, _, inst = name.partition("/")
        return tables.get(mod, {}).get(inst, ("other", False))

    return phase_of


def read(path: str, top: int = 10) -> dict:
    """``reduce`` of the trace at ``path`` with its own modules."""
    trace, modules = from_xplane(path)
    return reduce(trace, phase_table(modules), top)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit("usage: python3 -m bench.phases TRACE_DIR_OR_XPLANE")
    path = argv[0]
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            sys.exit(f"no .xplane.pb under {path}")
        path = found[-1]  # the newest of the profiler's time-named dirs
    r = read(path)
    r["shares_pct"] = shares(r)
    print(json.dumps(r, indent=1))


if __name__ == "__main__":
    main()
