"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``. The last line of stdout is the result
object; the numbers compared for ``correct`` are also the last lines of
stderr. On a machine without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# import the benchmark as the package ``bench`` from the checkout's root
# (and not this directory, whose trace.py would shadow the standard one)
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit("bench/run.py: the program (src/repro) is not in this checkout")
    cell = harness.load_cell(a.workload)
    try:
        result = harness.run(cell, a.seed, a.seconds, bool(a.trace))
    except harness.NoChip as e:
        sys.exit(f"bench/run.py: {e}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
