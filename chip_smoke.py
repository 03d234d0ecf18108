"""Training smoke run of the MBP launcher on TPU chips.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # four chips: data-parallel and 1F1B meshes

One chip. qwen2-1.5b at its published widths (d_model 1536, 12 heads with
GQA kv 2, head_dim 128, d_ff 8960, vocab 151,936, QKV bias, tied
embeddings), with depth cut from 28 to ``LAYERS`` layers. A mini-batch of
``MINI`` sequences of ``SEQ`` tokens, too large for the chip in one shot,
is trained as micro-batches of ``MICRO`` under the ``compiled`` (lax.scan)
and the ``flat`` (Pallas grad_accum + fused_update) executors. Both run
through the launcher's own path, ``train.setup`` (``build_plan`` ->
``make_build``) and ``engine.Trainer``, with the Pallas kernels compiled
for the chip (``interpret=False``): one warm-up step, then timed steps that
each end in ``block_until_ready``. The one-shot step (micro-batch = the
whole mini-batch) is compiled too, and the compiler must refuse it.

Four chips (``--chips 4``). The same widths at ``FOUR_CHIP_LAYERS`` layers
and ``FOUR_CHIP_SEQ`` tokens, one global batch on the data-parallel host
mesh ``4:1`` (flat inner executor) and the pipelined mesh ``2:2`` (1F1B),
each compared against the one-device step on ``devices[0]`` in this
process.

Every check that fails raises, and the exit code is then non-zero. The
last line of stdout is ``{"ok": true, "device": {...}}`` and is printed
only after every check has passed, on a TPU. The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` of this
checkout (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import configs, engine  # noqa: E402
from repro.analysis import hlo_checks  # noqa: E402
from repro.launch import compile_cache, train  # noqa: E402
from repro.models import transformer  # noqa: E402

ARCH = "qwen2-1.5b"
# Published depth is 28. The launcher trains with fp32 master params,
# SGD momentum, an fp32 accumulator and an fp32 per-micro gradient, about
# 16 B/param: 24.7 GB at 28 layers. At 8 layers (233.4M embedding +
# 8 x 46.8M) that is 9.7 GB; the micro-batch's activations and its fp32
# logits (2048 x 151,936 x 4 B = 1.24 GB per sample, plus their bf16 copy
# and gradient) take the rest of the 16 GB.
LAYERS = 8
SEQ = 2048
MINI = 8
MICRO = 1  # the largest micro-batch whose compiled flat step fits
# remat "auto" picks "none" here (the memory model undercounts the
# logits), which the chip's compiler refuses at this depth
REMAT = "period"
STEPS = 4  # one warm-up step (compiles) + three timed steps
# Four chips: the 1F1B step at 2:2 keeps four fp32 logits buffers of a
# micro-batch live and updates the recombined state outside its shard_map;
# at 8 layers and seq 2048 its first step needs 11.04G of program memory
# beside the 4.5 GiB state, more than a v5e has. Its compile for v5e at
# 4 layers and seq 1024 needs 7.15 GiB of temporaries beside 3.1 GiB of
# state. All four-chip phases, the one-device reference included, use it.
FOUR_CHIP_LAYERS = 4
FOUR_CHIP_SEQ = 1024

# The compiled and flat executors run the same forward and backward; they
# differ only in where the 1/N_S scale is applied (a power of two here, so
# exact) and in fp32 summation order, so per-step losses agree to ~1e-6.
# 1e-3 leaves room for rare bf16 rounding flips that a parameter
# difference of a few fp32 ulps can cause; a wrong scale would move the
# gradient norm by the factor N_S, far outside GRAD_NORM_RTOL.
LOSS_ATOL = 1e-3
GRAD_NORM_RTOL = 1e-3
# Across layouts (four chips) the micro-batch shapes differ (one device
# runs micro-batches of 1, the 4:1 mesh of 4), so the bf16 matmuls round
# in another order: bf16's unit roundoff is 2^-8 = 3.9e-3. A wrong
# normalization would be off by a factor of 2 or more.
LAYOUT_GRAD_NORM_RTOL = 1e-2
# Random init: the logits are small, so the step-0 loss is ln(V) plus
# about half their variance. The final RMSNorm fixes that variance
# whatever the depth: a one-layer forward at these widths gives
# ln(V) + 0.33.
LOSS0_ATOL = 0.5


def launcher_args(executor: str, *, mesh: str = "1:1",
                  microbatches: int | None = MINI // MICRO,
                  remat: str = REMAT, seq: int = SEQ):
    """The launcher's own parser; ``microbatches=None`` lets the planner
    size the micro-batch against the device's memory limit."""
    pinned = [] if microbatches is None else [
        "--microbatches", str(microbatches)]
    return train.parse_args([
        "--arch", ARCH, "--executor", executor, "--mesh", mesh,
        "--dtype", "bfloat16", "--seq", str(seq), "--mini-batch", str(MINI),
        "--remat-policy", remat, "--calibrate", "off",
        "--steps", str(STEPS), "--log-every", "1", *pinned])


# JAX's own monitoring events: every compile request (a persistent-cache
# hit included) records COMPILE_EVENT; the cache records its hits/misses
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"
EVENTS = collections.Counter()


def _count_event(event, *_, **__):
    EVENTS[event] += 1


def _avals(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)


class TimedStep:
    """Wraps the launcher's step: each call ends in ``block_until_ready``
    and is timed, and the compiles it set off are counted; the last call's
    argument shapes (steady-state shardings) are kept for lowering the
    same step again."""

    def __init__(self, step):
        self.step = step
        self.times = []
        self.compiles = []
        self.avals = None

    def __call__(self, params, opt_state, batch):
        self.avals = _avals((params, opt_state, batch))
        compiles = EVENTS[COMPILE_EVENT]
        t0 = time.perf_counter()
        out = jax.block_until_ready(self.step(params, opt_state, batch))
        self.times.append(time.perf_counter() - t0)
        self.compiles.append(EVENTS[COMPILE_EVENT] - compiles)
        return out


def train_phase(cfg, args, *, interpret=False):
    """Train ``STEPS`` steps of ``cfg`` under ``args`` through the
    launcher's path. Returns the per-step metrics, step times, the final
    params, the timed step and the setup."""
    run = train.setup(cfg, args, interpret=interpret)
    print(f"[{args.executor} mesh {args.mesh}] {run.plan.describe()}",
          flush=True)
    step_fn, pipeline = run.build(run.plan)
    timed = TimedStep(step_fn)
    history = []
    trainer = engine.Trainer(
        timed, pipeline, log_every=1, state_shardings=run.state_shardings,
        log_fn=lambda step, metrics, elapsed: history.append(metrics))
    params, _, _ = trainer.fit(run.params, run.opt_state, args.steps)
    for i, (m, t, c) in enumerate(zip(history, timed.times, timed.compiles)):
        tag = "warm-up" if i == 0 else "timed"
        print(f"  step {i}  loss {m['loss']:.6f}  |g| {m['grad_norm']:.6f}"
              f"  {t:.4f} s ({tag}, {c} compiles)", flush=True)
    for m in history:
        if not all(math.isfinite(v) for v in m.values()):
            raise AssertionError(f"non-finite metrics: {m}")
    return history, timed.times, params, timed, run


def compare(name, got, ref, grad_norm_rtol=GRAD_NORM_RTOL):
    for i, (g, r) in enumerate(zip(got, ref)):
        dl = abs(g["loss"] - r["loss"])
        dg = abs(g["grad_norm"] - r["grad_norm"]) / r["grad_norm"]
        print(f"  {name} step {i}: |d loss| {dl:.3e}  rel |d |g|| {dg:.3e}",
              flush=True)
        if dl > LOSS_ATOL or dg > grad_norm_rtol:
            raise AssertionError(
                f"{name} step {i}: loss {g['loss']} vs {r['loss']}, |g| "
                f"{g['grad_norm']} vs {r['grad_norm']} (atol {LOSS_ATOL}, "
                f"rtol {grad_norm_rtol})")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def one_chip(cfg, device):
    limit = train.budget_bytes(launcher_args("compiled"))
    print(f"device bytes_limit {limit} ({limit / 2**30:.3f} GiB)", flush=True)
    results = {}
    for executor in ("compiled", "flat"):
        args = launcher_args(executor)
        auto = train.build_plan(cfg, launcher_args(
            executor, microbatches=None, remat="auto"))
        history, times, params, timed, run = train_phase(cfg, args)
        if run.plan.num_micro_batches < 2:
            raise AssertionError(f"N_S = {run.plan.num_micro_batches} < 2")
        compiled = timed.step.lower(*timed.avals).compile()
        step_peak = hlo_checks.measured_peak_bytes(compiled)
        print(f"  planner auto admission at bytes_limit: {auto.describe()}",
              flush=True)
        print(f"  compiled step memory_analysis peak {step_peak} "
              f"({step_peak / 2**30:.3f} GiB) for the pinned micro-batch "
              f"{run.plan.micro_batch_size}", flush=True)
        if any(timed.compiles[1:]):
            raise AssertionError(f"timed steps compiled: {timed.compiles}")
        timed_s = times[1:]
        tokens = MINI * SEQ
        print(f"  timed steps {['%.4f' % t for t in timed_s]} s; median "
              f"{sorted(timed_s)[len(timed_s) // 2]:.4f} s "
              f"({tokens / sorted(timed_s)[len(timed_s) // 2]:.0f} tokens/s)",
              flush=True)
        print(f"  peak_bytes_in_use {peak_bytes(device)} "
              f"({peak_bytes(device) / 2**30:.3f} GiB, process so far)",
              flush=True)
        if executor == "flat":
            check_kernels_compiled(compiled)
        results[executor] = history
        del params, compiled
        if executor == "compiled":
            oneshot_refused(cfg, run, timed.avals)
        del run, timed
        gc.collect()

    compare("flat vs compiled", results["flat"], results["compiled"])
    loss0 = results["compiled"][0]["loss"]
    ln_v = math.log(cfg.vocab_size)
    print(f"step-0 loss {loss0:.6f}, ln(vocab) {ln_v:.6f}", flush=True)
    if abs(loss0 - ln_v) > LOSS0_ATOL:
        raise AssertionError(f"step-0 loss {loss0} is not near ln(V) {ln_v}")


def check_kernels_compiled(compiled):
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("flat step has no tpu_custom_call: the Pallas "
                             "kernels are not compiled for the chip")
    print("  flat step HLO contains tpu_custom_call", flush=True)


def oneshot_refused(cfg, run, avals):
    """Compile the step for the whole mini-batch at once (the micro-batch
    run's state shapes ``avals``): the chip's compiler must refuse it for
    lack of memory."""
    plan = train.build_plan(cfg, launcher_args("compiled", microbatches=1))
    step, _ = run.build(plan)
    params, opt_state, split = avals
    batch = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        (1, MINI) + a.shape[2:], a.dtype, sharding=a.sharding), split)
    try:
        compiled = step.lower(params, opt_state, batch).compile()
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        print(f"  one-shot step ({plan.describe()}) refused by the "
              f"compiler: {str(e).splitlines()[0][:200]}", flush=True)
        return
    raise AssertionError(
        "the one-shot mini-batch fits the chip "
        f"({hlo_checks.measured_peak_bytes(compiled)} bytes): the smoke no "
        "longer trains beyond the memory limit")


def shard_devices(tree) -> set:
    return {s.device for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards}


def four_chips(cfg, devices):
    ref = train_phase(cfg, launcher_args("flat", seq=FOUR_CHIP_SEQ))[0]
    gc.collect()
    # data-parallel 4:1: global micro 4, one sample per chip
    dp, _, params, timed, _ = train_phase(
        cfg, launcher_args("flat", mesh="4:1", microbatches=MINI // 4,
                           seq=FOUR_CHIP_SEQ))
    executor = timed.step.__self__
    compiled = executor.lower_step(*timed.avals).compile()
    n_ar = sum(c.kind == "all-reduce"
               for c in hlo_checks.collectives(compiled))
    print(f"  4:1 compiled step: {n_ar} all-reduce per mini-batch "
          "(contract: 1)", flush=True)
    if n_ar != 1:
        raise AssertionError(f"{n_ar} all-reduces in the data-parallel step")
    held = shard_devices(params)
    print(f"  4:1 params on {len(held)} devices", flush=True)
    if held != set(devices):
        raise AssertionError(f"4:1 params live on {held}, not all devices")
    compare("4:1 vs one device", dp, ref, LAYOUT_GRAD_NORM_RTOL)
    del params, timed, executor, compiled
    gc.collect()
    # 1F1B 2:2: two stages x two data shards, global micro 2
    pp, _, params, _, _ = train_phase(
        cfg, launcher_args("compiled", mesh="2:2", microbatches=MINI // 2,
                           seq=FOUR_CHIP_SEQ))
    held = shard_devices(params)
    print(f"  2:2 params on {len(held)} devices", flush=True)
    if held != set(devices):
        raise AssertionError(f"2:2 params live on {held}, not all devices")
    compare("2:2 vs one device", pp, ref, LAYOUT_GRAD_NORM_RTOL)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    cache = compile_cache.enable()
    jax.monitoring.register_event_listener(_count_event)
    jax.monitoring.register_event_duration_secs_listener(_count_event)
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {device.platform}")
    if len(devices) < chips:
        sys.exit(f"--chips {chips} needs {chips} devices; found {len(devices)}")
    entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    print(f"{device.platform} {device.device_kind} x {len(devices)}; compile "
          f"cache {cache} ({entries} entries at start)", flush=True)
    layers, seq = (LAYERS, SEQ) if chips == 1 else (FOUR_CHIP_LAYERS,
                                                    FOUR_CHIP_SEQ)
    cfg = dataclasses.replace(configs.get(ARCH), num_layers=layers)
    n_params = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda k: transformer.init_params(cfg, k),
        jax.random.PRNGKey(0))))
    print(f"{ARCH} at published widths (d_model {cfg.d_model}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}), depth cut 28 -> {layers} "
          f"layers: {n_params} params; seq {seq}, mini-batch {MINI}",
          flush=True)
    if chips == 1:
        one_chip(cfg, device)
    else:
        four_chips(cfg, devices[:4])
    print(f"compile requests {EVENTS[COMPILE_EVENT]}; persistent cache "
          f"{EVENTS[CACHE_HITS]} hits, {EVENTS[CACHE_MISSES]} misses",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
