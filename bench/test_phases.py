"""The phase reduction (``bench/phases.py``): the classifier on hand-written
name stacks, the fusion and operand rules on a hand-made module, the
partition of busy time on a hand-made trace whose sums are worked out
below, the HLO module read back from a CPU trace, and a short phased trace
recorded on a TPU v5e (``testdata``)."""
from __future__ import annotations

import glob
import json
import os

import pytest

from bench import phases as ph
from bench import trace as tl

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "testdata", "phases_1chip.json")

STEP = "jit(train_step)/while/body/closed_call"


@pytest.mark.parametrize("op_name,want", [
    (f"{STEP}/jvp(trunk)/while/body/closed_call/dot_general",
     ("forward", False)),
    (f"{STEP}/jvp(head)/dot_general", ("forward", True)),
    (f"{STEP}/transpose(jvp(trunk))/while/body/closed_call/checkpoint/add_any",
     ("backward", False)),
    (f"{STEP}/transpose(jvp(head))/jit(take_along_axis)/scatter-add",
     ("backward", True)),
    (f"{STEP}/transpose(jvp(trunk))/while/body/closed_call/checkpoint/"
     "rematted_computation/jit(silu)/mul", ("recompute", False)),
    ("jit(step)/while/body/closed_call/transpose(jvp(jvp()))/checkpoint/"
     "rematted_computation/head/exp", ("recompute", True)),
    (f"{STEP}/accumulate/add", ("accumulate", False)),
    ("jit(train_step)/update/mul", ("update", False)),
    ("jit(train_step)/shard_map/grad_sync/psum", ("grad_sync", False)),
    # a scope in the name stack wins over the direction
    (f"{STEP}/transpose(jvp(head))/update/add", ("update", False)),
    # outside the loss: JAX's own loop bodies are not the trunk
    ("jit(train_step)/while/body/add", ("other", False)),
    ("jit(train_step)/sqrt", ("other", False)),
    ("", ("other", False)),
    # a scope name inside another word does not count
    ("jit(train_step)/dynamic_update_slice", ("other", False)),
])
def test_classify(op_name, want):
    assert ph.classify(op_name) == want


def test_the_program_writes_the_names_the_reduction_reads():
    from repro import spans
    assert set(ph.SCOPED) | set(ph.LOSS) == set(spans.DEVICE_PHASES)
    assert ph.HEAD == spans.HEAD
    # the benchmark names idle gaps by the program's host spans
    assert set(spans.HOST_SPANS) - {spans.CHECKPOINT} <= set(tl.SPANS)
    assert "window" not in spans.HOST_SPANS


def hand_module():
    """``fusion.1``: a head weight-gradient dot fused under an accumulate
    root; ``fusion.2``: an update fusion with no dot; ``fusion.3``: no
    name of its own, its root's; ``reshape.1``: a layout reshape the
    compiler added, its producer's; ``copy.1``: from a parameter, none."""
    acc = f"{STEP}/accumulate/add"
    dw = f"{STEP}/transpose(jvp(head))/dot_general"
    i = ph.Instr
    instrs = {
        "p.0": i("parameter", "", ()), "p.1": i("parameter", "", ()),
        "dot.1": i("convolution", dw, (), ("p.0", "p.1")),
        "add.1": i("add", acc, (), ("p.0", "dot.1")),
        "fusion.1": i("fusion", acc, ("fused.1",), ("p.0",)),
        "mul.2": i("multiply", "jit(train_step)/update/mul", ()),
        "fusion.2": i("fusion", "jit(train_step)/update/mul", ("fused.2",)),
        "exp.3": i("exponential", f"{STEP}/jvp(head)/exp", ()),
        "fusion.3": i("fusion", "", ("fused.3",)),
        "reshape.1": i("reshape", "", (), ("fusion.1",)),
        "copy.1": i("copy", "", (), ("p.0",)),
    }
    comps = {"fused.1": ["p.0", "p.1", "dot.1", "add.1"],
             "fused.2": ["mul.2"], "fused.3": ["exp.3"],
             "main": ["p.0", "fusion.1", "fusion.2", "fusion.3",
                      "reshape.1", "copy.1"]}
    return ph.Module("jit_train_step(1)", instrs, comps)


def test_fusion_and_operand_rules():
    got = hand_module().phases()
    assert got["fusion.1"] == ("backward", True)  # the dot's, not the root's
    assert got["fusion.2"] == ("update", False)
    assert got["fusion.3"] == ("forward", True)  # its root's
    assert got["reshape.1"] == ("backward", True)  # its producer's
    assert got["copy.1"] == ("other", False)


def test_partition_sums_to_the_union():
    ev = [(0, 10, "a"), (5, 12, "b"), (12, 15, "c"), (20, 30, "a")]
    got = ph.partition(ev)
    assert got == {"a": 20, "b": 2, "c": 3}
    assert sum(got.values()) == tl.length(tl.union(ev))


def hand_trace():
    """Two devices, window [0, 100] ns, ops named ``<module>/<instr>``.

    Device 0: fusion.1 (backward, head) [0, 30]; fusion.2 (update) [30, 40];
    fusion.3 (forward, head) [50, 60] overlapping reshape.1 (backward,
    head) [55, 70], which keeps [60, 70]; copy.1 (other) [80, 90]; a
    ``while`` container [0, 95] left out. Busy 70.
    Device 1: fusion.2 [0, 20]; an op of another module (other) [20, 30].
    Busy 30. Means: busy 50, backward 20, update 15, forward 5,
    other 10 (copy.1 5, the foreign op 5), head 25."""
    m = "jit_train_step(1)"
    dev0 = [(0, 95, f"{m}/while.1"), (0, 30, f"{m}/fusion.1"),
            (30, 40, f"{m}/fusion.2"), (50, 60, f"{m}/fusion.3"),
            (55, 70, f"{m}/reshape.1"), (80, 90, f"{m}/copy.1")]
    dev1 = [(0, 20, f"{m}/fusion.2"), (20, 30, "jit_other(2)/fusion.9")]
    return tl.Trace({0: dev0, 1: dev1}, [(0, 100, "window")])


def test_reduce_hand_made():
    module = hand_module()
    table = module.phases()

    def phase_of(name):
        mod, _, inst = name.partition("/")
        return table[inst] if mod == module.name else ("other", False)

    r = ph.reduce(hand_trace(), phase_of)
    ns = 1e-9
    assert r["busy_s"] == pytest.approx(50 * ns)
    assert r["window_s"] == pytest.approx(100 * ns)
    want = {"forward": 5, "recompute": 0, "backward": 20, "accumulate": 0,
            "update": 15, "grad_sync": 0, "other": 10}
    for k, v in want.items():
        assert r["phase_s"][k] == pytest.approx(v * ns), k
    assert sum(r["phase_s"].values()) == pytest.approx(r["busy_s"])
    assert r["head_s"] == pytest.approx(25 * ns)
    s = ph.shares(r)
    assert s["backward_pct"] == pytest.approx(20.0)
    assert s["head_pct"] == pytest.approx(25.0)
    assert [n for n, _ in r["other_ops"]] == [
        "jit_other(2)/fusion.9", "jit_train_step(1)/copy.1"]


def test_module_read_from_a_cpu_trace_matches_the_compiled_text(tmp_path):
    """The profiler stores the executable's HLO module with the trace; read
    back from the ``.xplane.pb``, it holds what the compiled text holds."""
    import jax
    import jax.numpy as jnp

    def loss(w, xs):
        def body(c, x):
            with jax.named_scope("trunk"):
                y = jax.checkpoint(lambda w, x: jnp.tanh(x @ w))(w, x)
            with jax.named_scope("head"):
                return c + jnp.sum(y * y), None
        return jax.lax.scan(body, 0.0, xs)[0]

    def step(w, xs):
        g = jax.grad(loss)(w, xs)
        with jax.named_scope("update"):
            return w - 0.1 * g

    w, xs = jnp.ones((16, 16)), jnp.ones((3, 4, 16))
    f = jax.jit(step)
    f(w, xs).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        f(w, xs).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    mods = ph.modules_from_xplane(path)
    (name,) = [k for k in mods if k.startswith("jit_step(")]
    got = mods[name]
    want = ph.module_from_text(name, f.lower(w, xs).compile().as_text())
    assert got.instrs == want.instrs
    # the same instructions in each computation, the root last (the text
    # prints them in schedule order)
    assert ({k: (set(v), v[-1]) for k, v in got.comps.items()}
            == {k: (set(v), v[-1]) for k, v in want.comps.items()})
    found = {p for p, _ in got.phases().values()}
    assert {"forward", "recompute", "backward", "update"} <= found


def test_recorded_tpu_step():
    """One step of ``qwen2-8l.oneshot-1x1`` recorded on a TPU v5e (device 0,
    times in ns from the step's start), with the name stack that decides
    each instruction's phase (``Module.op_name``: the fusion and operand
    rules applied to the trace's own HLO module)."""
    with open(RECORDED) as f:
        rec = json.load(f)
    trace = tl.Trace({0: rec["ops"]}, [tuple(rec["window"]) + ("window",)])
    r = ph.reduce(trace, lambda n: ph.classify(rec["op_name"][n]))
    assert r["busy_s"] == pytest.approx(tl.reduce(trace)["busy_s"])
    assert sum(r["phase_s"].values()) == pytest.approx(r["busy_s"])
    for k, v in rec["reduced"]["phase_s"].items():
        assert r["phase_s"][k] == pytest.approx(v), k
    assert r["head_s"] == pytest.approx(rec["reduced"]["head_s"])
    # the step's sums, in ms: 96.578 window, 94.973 busy
    ms = {k: round(v * 1e3, 3) for k, v in r["phase_s"].items()}
    assert ms == {"forward": 18.713, "recompute": 9.243, "backward": 51.201,
                  "accumulate": 0.0, "update": 10.717, "grad_sync": 0.0,
                  "other": 5.099}
    assert round(r["busy_s"] * 1e3, 3) == 94.973
    assert round(r["head_s"] * 1e3, 3) == 30.721
    # the largest ops with no scope: the per-step bf16 casts of the stacked
    # MLP weights, which XLA hoists out of the layer scan without a name
    assert [n for n, _ in r["other_ops"][:3]] == [
        "convert.195", "convert.194", "convert.196"]
