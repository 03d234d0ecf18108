"""The comparison that decides ``correct``.

A run's readings and the reference's are both ``{"loss": [l1, l2],
"grad": {leaf: norm}, "update": {leaf: norm}}`` (see
``bench/reference/common.py``). Three numbers are compared, each against
its limit in ``bench/limits/<cell>.json``:

* ``loss_gap``: the largest |loss - reference loss| over the steps;
* ``grad_gap``: over the leaves, the largest gap between the first step's
  gradient norm and the reference's, over the larger of the reference's
  norm of that leaf and of the median leaf;
* ``update_gap``: the same for the norm of the parameters' change over the
  steps, leaving out the leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

A reading that is not finite fails. A limit of ``null`` means the number
is printed and not compared: it has no upper reading (neither the control
nor a planted fault reads three, or ten, times the program), so a limit
could only fail sound runs.
"""
from __future__ import annotations

import json
import math
import os
import statistics

NUMBERS = ("loss_gap", "grad_gap", "update_gap")
MOVES_FLOOR = 1e-3  # of the median leaf's gradient norm


def _worst(got, ref, keys, floor):
    worst, leaf = 0.0, None
    for k in keys:
        g = got.get(k, math.nan)
        gap = abs(g - ref[k]) / max(ref[k], floor)
        if not math.isfinite(gap):
            return math.inf, k
        if gap > worst:
            worst, leaf = gap, k
    return worst, leaf


def gaps(got: dict, ref: dict) -> dict:
    """{number: value} and the leaf that set each, under ``"leaf"``."""
    loss = [abs(a - b) for a, b in zip(got["loss"], ref["loss"])]
    loss_gap = (max(loss) if all(math.isfinite(x) for x in loss)
                and len(loss) == len(ref["loss"]) else math.inf)
    med_g = statistics.median(ref["grad"].values())
    grad_gap, grad_leaf = _worst(got["grad"], ref["grad"], ref["grad"], med_g)
    moving = [k for k, v in ref["grad"].items() if v >= MOVES_FLOOR * med_g]
    med_u = statistics.median(ref["update"][k] for k in moving)
    update_gap, update_leaf = _worst(got["update"], ref["update"], moving, med_u)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "update_gap": update_gap,
            "leaf": {"grad_gap": grad_leaf, "update_gap": update_leaf},
            "left_out": sorted(set(ref["grad"]) - set(moving))}


def limits_path(root: str, cell: str) -> str:
    return os.path.join(root, "bench", "limits", cell + ".json")


def load_limits(root: str, cell: str) -> dict:
    with open(limits_path(root, cell)) as f:
        lim = json.load(f)
    missing = [n for n in NUMBERS if n not in lim]
    if missing:
        raise KeyError(f"limits of {cell} lack {missing}")
    return {n: None if lim[n] is None else float(lim[n]) for n in NUMBERS}


def judge(g: dict, limits: dict):
    """(correct, {number: {"value", "limit"}})."""
    checks = {n: {"value": g[n], "limit": limits[n]} for n in NUMBERS}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values() if c["limit"] is not None)
    return ok, checks
