"""Readings for the limits of ``correct``, many seeds in one process.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
      --control-seeds 1,2,3 [--faults half_batch,no_exchange] [--out FILE]

For every seed: the program's set-up steps through the cell's own
trainer (as ``bench/run.py`` drives them, without the timed window), then
the float32 reference, and the gaps between them (``correct.gaps``). For
each control seed also the control, the reference computed in float8 and
put in the program's place, and each planted fault, the float32 reference
put in the program's place with the fault in it:

* ``half_batch``: the second half of every mini-batch left out, the mean
  taken over the rest;
* ``no_exchange``: the gradient sum between chips left out; each chip
  updates with its own share of every micro-batch, and the run reads chip
  0's parameters.

A state that does not change reads 1 as ``update_gap`` and needs no run.
One JSON line per reading goes to stdout and to ``--out``; the last line
sums up: per number the largest program reading (the lower one) and the
smallest control and fault readings. Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import correct, harness  # noqa: E402

FAULTS = ("half_batch", "no_exchange")


def fault_rows(cell, fault):
    t = cell.traffic
    n = t["mini_batch"]
    if fault == "half_batch":
        return list(range(n // 2)) if n >= 2 else None
    if fault == "no_exchange":
        if cell.chips < 2:
            return None
        micro = n // t["num_microbatches"]
        local = micro // cell.chips
        return [i for i in range(n) if i % micro < local]
    raise ValueError(fault)


def calibrate(cell, seeds, control_seeds=(), faults=(), allow_cpu=False,
              emit=print):
    devices = harness.devices_for(cell.chips, allow_cpu)
    if not allow_cpu:
        harness.enable_cache()
    harness.count_compiles()
    prog = harness.Program(cell)
    lines = []

    def out(seed, kind, g, seconds):
        line = {"cell": cell.name, "seed": seed, "kind": kind,
                **{k: g[k] for k in correct.NUMBERS}, "leaf": g["leaf"],
                "seconds": round(seconds, 3)}
        lines.append(line)
        emit(json.dumps(line))

    for seed in seeds:
        t0 = time.perf_counter()
        p, o, got, _ = prog.first_steps(seed)
        harness._free((p, o))
        del p, o
        gc.collect()
        t1 = time.perf_counter()
        ref = harness.reference_readings(cell, seed, devices)
        t2 = time.perf_counter()
        out(seed, "program", correct.gaps(got, ref), t1 - t0)
        emit(json.dumps({"seed": seed, "kind": "reference_seconds",
                         "seconds": round(t2 - t1, 3), "loss": ref["loss"],
                         "program_loss": got["loss"]}))
        if seed not in control_seeds:
            continue
        t0 = time.perf_counter()
        ctl = harness.reference_readings(cell, seed, devices, mode="fp8")
        out(seed, "control", correct.gaps(ctl, ref), time.perf_counter() - t0)
        for fault in faults:
            rows = fault_rows(cell, fault)
            if rows is None:
                continue
            t0 = time.perf_counter()
            bad = harness.reference_readings(cell, seed, devices, rows=rows)
            out(seed, fault, correct.gaps(bad, ref), time.perf_counter() - t0)
    summary = {"cell": cell.name, "kind": "summary"}
    for n in correct.NUMBERS:
        prog_r = [x[n] for x in lines if x["kind"] == "program"]
        summary[n] = {"lower": max(prog_r) if prog_r else None}
        for kind in ("control",) + FAULTS:
            r = [x[n] for x in lines if x["kind"] == kind]
            if r:
                summary[n][kind] = min(r)
    emit(json.dumps(summary))
    return lines, summary


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    ints = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    cell = harness.load_cell(a.workload)
    sink = open(a.out, "a") if a.out else None

    def emit(line):
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()

    try:
        calibrate(cell, ints(a.seeds), set(ints(a.control_seeds)),
                  [f for f in a.faults.split(",") if f], emit=emit)
    except harness.NoChip as e:
        sys.exit(f"bench/calibrate.py: {e}")
    finally:
        if sink:
            sink.close()


if __name__ == "__main__":
    main()
