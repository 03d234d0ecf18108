"""The reduction from a profiler trace to the benchmark's device numbers
(``bench/trace.py``), on a hand-made trace whose numbers are worked out
below and on a short trace recorded on a TPU v5e (``testdata``)."""
from __future__ import annotations

import os

import pytest

from bench import trace as tl

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "trace_1chip.json")


def hand_made():
    """Two devices, times in ns, window [0, 40].

    Device 0: a loop ``while.1`` encloses ``fusion.1`` (a container, left
    out); ops [0, 10], [15, 18], [25, 30] and an all-reduce op [12, 15],
    with an all-reduce in flight [12, 20] on the async line. Busy 21;
    collective 8, of which [12, 15] and [18, 20] (5) run alone; idle gaps
    [10, 12] (host in ``dispatch``), [18, 25] and [30, 40] (host in
    ``readback``).

    Device 1: one op [0, 20]; an all-reduce in flight [18, 30] on the async
    line. Busy 20; collective 12, exposed [20, 30] (10); gap [20, 40].
    """
    dev0 = [(0, 30, "while.1 tuple while"), (0, 10, "fusion.1 f32[8] fusion"),
            (12, 15, "all-reduce.1 f32[8] all-reduce"),
            (15, 18, "fusion.2 f32[8] fusion"), (25, 30, "fusion.3 f32[8] fusion")]
    dev1 = [(0, 20, "fusion.1 f32[8] fusion")]
    flight = {0: [(12, 20, "all-reduce-start.3 tuple all-reduce-start")],
              1: [(18, 30, "all-reduce-start.2 tuple all-reduce-start")]}
    host = [(0, 40, "window"), (9, 13, "dispatch"), (20, 40, "readback")]
    return tl.Trace({0: dev0, 1: dev1}, host, flight)


def test_intervals():
    assert tl.union([(0, 2, "a"), (1, 3, "b"), (5, 6, "c")]) == [(0, 3), (5, 6)]
    assert tl.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tl.leaves([(0, 9, "loop"), (1, 2, "a"), (3, 4, "b")]) == \
        [(1, 2, "a"), (3, 4, "b")]
    assert tl.short("%fusion.7 = f32[8,128]{1,0} fusion(f32[8] %p), kind=kLoop") \
        == "fusion.7 f32[8,128] fusion"
    assert tl.is_collective("all-reduce-start.2 tuple all-reduce-start")
    assert not tl.is_collective("fusion.1 f32[8] fusion")


def test_hand_made_trace():
    r = tl.reduce(hand_made())
    ns = 1e-9
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(40 * ns)
    assert r["busy_s"] == pytest.approx((21 + 20) / 2 * ns)
    assert r["idle_share"] == pytest.approx(((40 - 21) / 40 + (40 - 20) / 40) / 2)
    assert r["collective_s"] == pytest.approx((8 + 12) / 2 * ns)
    assert r["exposed_collective_s"] == pytest.approx((5 + 10) / 2 * ns)
    assert r["exposed_share"] == pytest.approx((5 / 40 + 10 / 40) / 2)
    assert [n for n, _ in r["idle_gaps"]] == ["readback"] * 3 + ["dispatch"]
    assert [d for _, d in r["idle_gaps"]] == pytest.approx(
        [20 * ns, 10 * ns, 7 * ns, 2 * ns])
    assert r["idle_by_span"] == pytest.approx(
        {"dispatch": 1 * ns, "readback": 18.5 * ns})
    ops = dict(r["device_ops"])
    assert "while.1 tuple while" not in ops
    assert ops["fusion.1 f32[8] fusion"] == pytest.approx((10 + 20) / 2 * ns)


def test_recorded_trace():
    """A 24 ms window of the qwen2 MBP step on one TPU v5e chip: the busy
    time is the union of the leaf operations, found here by a plain sweep,
    and the idle gaps add up to the rest of the window."""
    t = tl.load(RECORDED)
    r = tl.reduce(t)
    w0, w1 = tl.window(t)
    ev = sorted((max(s, w0), min(e, w1)) for s, e, _ in tl.leaves(t.devices[0])
                if e > w0 and s < w1)
    busy, end = 0.0, w0
    for s, e in ev:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert r["devices"] == 1 and r["collective_s"] == 0.0
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - busy / (w1 - w0))
    assert 0 < r["idle_share"] < 0.1
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        (w1 - w0 - busy) * 1e-9)
    assert set(r["idle_by_span"]) <= set(tl.SPANS) | {"host"}
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10
    assert not any(n.split()[-1] == "while" for n, _ in r["device_ops"])


def test_trace_round_trip(tmp_path):
    path = tmp_path / "t.json"
    tl.save(hand_made(), str(path))
    assert tl.reduce(tl.load(str(path))) == tl.reduce(hand_made())
