"""MBS time overhead on the transformer stack (paper §4.3.3): step time at
a fixed global batch as a function of the number of micro-batches. The
paper reports 0.3–5.1% per-epoch overhead; here we measure the compiled
engine step directly, for both the plain-scan and the Pallas fused-
accumulate executors.

``--pipeline`` runs the input-pipeline benchmark instead (paper §3.1 /
Fig. 1): full step-loop time through the synchronous hot loop (inline
``ds.batch`` + blocking per-step metrics readback — what the launcher
used to do) vs. the async ``Pipeline`` + ``Trainer`` path (background
batch synthesis/split, double-buffered device staging, metrics read one
step late). Results land in ``BENCH_pipeline.json`` together with the
pipeline's measured input-wait fraction, so the perf trajectory of the
input path is recorded run over run.

``--update-bench`` benchmarks the update path (paper Fig. 2 steps ❹–❺)
and writes ``BENCH_update.json``: Pallas launches per update (per-leaf
O(num_leaves) vs flat-bucketed O(num_buckets)), step-❺ wall time for the
unfused tree reference vs the fused flat path, and the analytic peak
update-transient bytes each admits into the micro-batch budget.

``--remat-bench`` benchmarks the remat-policy axis (engine Layer 5) and
writes ``BENCH_remat.json``: per policy on the lattice, the measured
compiled-step time (the recompute cost of heavier checkpointing) and the
micro-batch the memory model admits at several HBM budgets — plus the
planner's joint "auto" choice at each budget, showing where escalation
buys batch the cheaper policies cannot.

``--mesh-bench`` benchmarks sharded execution (engine Layer 6) and writes
``BENCH_mesh.json``: at data-parallel 2/4/8 (forced host devices), the
deferred-sync ShardedExecutor step time vs the per-micro-sync baseline,
the all-reduce operands each compiles to on an unrolled scan (1 vs
N_Sμ + 1 — the baseline also pays a scalar loss/valid sync), and the
global batch the mesh-aware planner admits at a fixed per-device budget
as the data axis grows. N_Sμ is recorded per row: the planner's
divisibility rounding can change the schedule as dp grows.

``--tuning-bench`` benchmarks the closed-loop autotuner (engine Layer 7)
and writes ``BENCH_tuning.json``: bucketed grad-accum per-leaf vs legacy
fixed block vs heuristic default vs tuned winner on the 96-leaf config,
plus the admission uplift oracle calibration buys ``plan_mbs`` on reduced
qwen2 at a tight budget (with the XLA-measured peak proving the
calibrated micro still fits).

``--fault-bench`` benchmarks the fault-tolerant runtime (engine Layer 9)
and writes ``BENCH_faults.json``: per injected fault class (OOM at both
degradation rungs, non-finite gradient retry/skip, transient worker,
checkpoint I/O, torn checkpoint write), the supervisor's recovery time,
steps lost/replayed and the plan admission before/after degradation —
plus the steady-state supervision overhead vs the plain Trainer loop.

``--serve-bench`` benchmarks the serving engine (engine Layer 10) and
writes ``BENCH_serve.json``: steady-state decode tokens/s and p50/p99
per-token latency under a synthetic Poisson request stream (warmup/compile
excluded, decode-issued tokens only), TTFT, the admitted-slots-vs-budget
curve from ``plan_serve``, and the XLA-measured decode peak
(``memory_analysis`` on the pool-wide decode step) proving the plan's
admission stays under the budget it was built for.

``--pp-bench`` benchmarks pipeline parallelism (engine Layer 11) and
writes ``BENCH_pp.json``: 1F1B PipelinedExecutor step time on a staged
toy stack at stages 2/4 × dp 1/2 vs the stages=1 baselines
(CompiledScanExecutor / deferred-sync ShardedExecutor), with the
schedule's analytic bubble fraction and tick count per cell — plus the
planner's pipelined admission on reduced qwen2: the local micro-batch
admitted at a fixed per-device budget as the model axis absorbs the
block stack (stage-local activations buy batch the flat layout cannot)."""
from __future__ import annotations

import os
import sys

if ("--mesh-bench" in sys.argv or "--pp-bench" in sys.argv) \
        and "xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    # must land before jax initializes: these benches need >= 8 host devices
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro import configs, engine, optim
from repro.core import memory_model
from repro.data import LMDataset
from repro.engine import exec_core
from repro.kernels import grad_accum_kernels as ga, ref as kref
from repro.launch import steps
from repro.models import transformer

from .common import emit, many_leaf_params, time_fn


def _time_step(step, params, opt_state, split, iters: int) -> float:
    p2, s2, m = step(params, opt_state, split)  # compile
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        p2, s2, m = step(params, opt_state, split)
    jax.block_until_ready(m["loss"])
    return (time.perf_counter() - t0) / iters


def main(quick: bool = True):
    cfg = configs.get_reduced("qwen2-1.5b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = steps.make_loss_fn(cfg, dtype=jnp.float32, remat=False)
    opt = optim.sgd(0.01, momentum=0.9)
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
    global_batch = 16
    mini = ds.batch(global_batch, 0)
    iters = 3 if quick else 10
    rows = []
    for name in ("compiled", "fused", "flat"):
        base_t = None
        for n_micro in (1, 2, 4, 8):
            plan = engine.plan_mbs(global_batch, num_microbatches=n_micro)
            ex = engine.get_executor(name)(loss_fn, opt, plan)
            step = jax.jit(ex.make_train_step())
            split = plan.device_split(mini)
            s = opt.init(params)
            dt = _time_step(step, params, s, split, iters)
            if n_micro == 1:
                base_t = dt
            ov = (dt / base_t - 1) * 100
            rows.append(emit(f"mbs_overhead/{name}/n_micro{n_micro}",
                             dt * 1e6, f"overhead={ov:.1f}%"))
    return rows


def _loop_sync(ex, ds, params, opt_state, mini_batch: int, n_steps: int
               ) -> float:
    """The pre-pipeline launcher hot loop: synchronous batch synthesis,
    host split in the loop, blocking metrics readback every step."""
    p, s = params, opt_state
    t0 = time.perf_counter()
    for i in range(n_steps):
        p, s, m = ex.step(p, s, ds.batch(mini_batch, i))
        float(m["loss"])  # per-step host sync
    jax.block_until_ready(p)
    return (time.perf_counter() - t0) / n_steps


def _loop_overlap(ex, ds, plan, params, opt_state, n_steps: int):
    """Pipeline + Trainer: background synthesis/split, double-buffered
    staging, async metrics readback."""
    device = getattr(ex, "device", None)
    pipeline = engine.Pipeline(ds, plan, prefetch=2, sharding=device)
    trainer = engine.Trainer(ex.step_split, pipeline, log_fn=None)
    t0 = time.perf_counter()
    p, s, _ = trainer.fit(params, opt_state, n_steps)
    jax.block_until_ready(p)
    return (time.perf_counter() - t0) / n_steps, pipeline.stats


def pipeline_main(quick: bool = True, out_path: str = "BENCH_pipeline.json"):
    cfg = configs.get_reduced("qwen2-1.5b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = steps.make_loss_fn(cfg, dtype=jnp.float32, remat=False)
    opt = optim.sgd(0.01, momentum=0.9)
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=64, seed=0)
    mini_batch = 16
    plan = engine.plan_mbs(mini_batch, num_microbatches=4)
    n_steps = 8 if quick else 30

    results = {"benchmark": "pipeline_overlap", "steps": n_steps,
               "mini_batch": mini_batch,
               "num_microbatches": plan.num_micro_batches, "executors": {}}
    for name in ("streaming", "compiled"):
        ex = engine.get_executor(name)(loss_fn, opt, plan)

        def fresh():  # compiled executors donate: never reuse stepped state
            p = jax.tree.map(jnp.copy, params)
            return p, opt.init(p)

        # compile + warm caches outside the timed region
        p, s, m = ex.step(*fresh(), ds.batch(mini_batch, 0))
        jax.block_until_ready(m["loss"])

        sync_s = _loop_sync(ex, ds, *fresh(), mini_batch, n_steps)
        overlap_s, stats = _loop_overlap(ex, ds, plan, *fresh(), n_steps)
        results["executors"][name] = {
            "sync_step_s": sync_s,
            "overlap_step_s": overlap_s,
            "speedup": sync_s / overlap_s,
            "input_wait_fraction": stats.input_wait_fraction,
            "input_wait_s": stats.wait_s,
            "elapsed_s": stats.elapsed_s,
        }
        emit(f"pipeline/{name}/sync", sync_s * 1e6, "per-step, no overlap")
        emit(f"pipeline/{name}/overlap", overlap_s * 1e6,
             f"speedup={sync_s / overlap_s:.2f}x "
             f"input_wait={stats.input_wait_fraction:.3f}")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def _bench_update_path(name: str, params, opt, iters: int) -> dict:
    """Launch counts + step-❹/❺ wall times for one param tree."""
    spec = engine.FlatSpec.for_tree(params)
    grads = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32), params)
    gbufs = spec.flatten(grads, dtype=jnp.float32)
    pbufs = spec.flatten(params)
    state = opt.init(params)
    pbytes = sum(l.size * jnp.dtype(l.dtype).itemsize
                 for l in jax.tree.leaves(params))

    # step ❹: one scaled accumulate over the whole gradient
    t_accum_leaf = time_fn(
        jax.jit(lambda a, g: ga.grad_accum_tree(a, g, 0.125, interpret=True)),
        jax.tree.map(jnp.zeros_like, grads), grads, iters=iters)
    t_accum_bucket = time_fn(
        jax.jit(lambda a, g: ga.grad_accum_buckets(a, g, 0.125,
                                                   interpret=True)),
        spec.zeros(jnp.float32), gbufs, iters=iters)

    # step ❺: unfused tree reference vs the fused flat path. The interpret
    # timing runs the real kernels (dispatch count dominates on this CPU
    # host); the oracle timing is the same one-pass flat arithmetic as a
    # single compiled XLA expression — the compiled-TPU-path proxy.
    t_unfused = time_fn(
        jax.jit(lambda g_, s_, p_: exec_core.apply_update(opt, g_, s_, p_)),
        grads, state, params, iters=iters)
    t_fused = time_fn(
        jax.jit(lambda b_, s_, p_: exec_core.apply_update_flat(
            opt, spec, b_, s_, p_, interpret=True)),
        gbufs, state, params, iters=iters)
    fs = opt.fused
    mbufs = spec.flatten(state["mom"])
    t_fused_oracle = time_fn(
        jax.jit(lambda b_, m_, p_: [kref.fused_sgd_ref(
            p1, g1, m1, 0.01, momentum=fs.momentum,
            weight_decay=fs.weight_decay)
            for p1, g1, m1 in zip(p_, b_, m_)]),
        gbufs, mbufs, pbufs, iters=iters)

    res = {
        "num_leaves": spec.num_leaves,
        "num_buckets": spec.num_buckets,
        "param_bytes": int(pbytes),
        "grad_accum": {
            "per_leaf": {"pallas_launches": spec.num_leaves,
                         "time_s": t_accum_leaf / 1e6},
            "bucketed": {"pallas_launches": spec.num_buckets,
                         "time_s": t_accum_bucket / 1e6},
        },
        "optimizer_update": {
            "unfused": {"pallas_launches": 0,
                        "time_s": t_unfused / 1e6,
                        "transient_bytes": memory_model.update_transient_bytes(
                            int(pbytes))},
            "fused_flat": {"pallas_launches": spec.num_buckets,
                           "time_s_interpret": t_fused / 1e6,
                           "time_s": t_fused_oracle / 1e6,
                           "transient_bytes": 0},
        },
        "step5_speedup_vs_unfused": t_unfused / t_fused_oracle,
        "accum_launch_reduction": spec.num_leaves / spec.num_buckets,
    }
    emit(f"update/{name}/accum_per_leaf", t_accum_leaf,
         f"launches={spec.num_leaves}")
    emit(f"update/{name}/accum_bucketed", t_accum_bucket,
         f"launches={spec.num_buckets}")
    emit(f"update/{name}/step5_unfused", t_unfused,
         f"transient_bytes={res['optimizer_update']['unfused']['transient_bytes']}")
    emit(f"update/{name}/step5_fused_flat", t_fused_oracle,
         f"speedup={res['step5_speedup_vs_unfused']:.2f}x (interpret "
         f"{t_fused:.0f}us)")
    return res


def update_main(quick: bool = True, out_path: str = "BENCH_update.json"):
    """Update-path benchmark (``--update-bench``): per-leaf vs flat-bucketed
    step ❹/❺ on a real (stacked, few-leaf) config and a many-leaf tree,
    plus the memory-model admission delta the fused path buys."""
    iters = 3 if quick else 10
    opt = optim.sgd(0.01, momentum=0.9, weight_decay=5e-4)
    cfg = configs.get_reduced("qwen2-1.5b")
    real = transformer.init_params(cfg, jax.random.PRNGKey(0))
    results = {"benchmark": "update_path", "configs": {}}
    results["configs"]["qwen2-1.5b-reduced"] = _bench_update_path(
        "qwen2-1.5b-reduced", real, opt, iters)
    results["configs"]["synthetic-manyleaf"] = _bench_update_path(
        "synthetic-manyleaf", many_leaf_params(32 if quick else 96),
        opt, iters)

    # what the eliminated transient buys: the largest micro-batch the
    # memory model admits at a budget the unfused update just overflows
    seq, mini = 64, 256
    est = memory_model.estimate(cfg, seq)
    unfused_admit = memory_model.suggest_micro_batch_size(
        cfg, seq, mini, budget_bytes=est.total(8)) or 0
    budget = est.total(2 * max(unfused_admit, 1)) - 1
    results["memory_model"] = {
        "arch": "qwen2-1.5b-reduced", "seq": seq,
        "budget_bytes": int(budget),
        "update_transient_bytes_unfused": est.update_transient_bytes,
        "micro_batch_admitted_unfused": memory_model.suggest_micro_batch_size(
            cfg, seq, mini, budget_bytes=budget) or 0,
        "micro_batch_admitted_fused": memory_model.suggest_micro_batch_size(
            cfg, seq, mini, budget_bytes=budget, fused_update=True) or 0,
    }
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def remat_main(quick: bool = True, out_path: str = "BENCH_remat.json"):
    """Remat-policy benchmark (``--remat-bench``): per-policy compiled step
    time on the reduced transformer stack + per-budget admission table."""
    from repro.models import remat as remat_lib

    cfg = configs.get_reduced("qwen2-1.5b")
    seq = 32
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt = optim.sgd(0.01, momentum=0.9)
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=seq, seed=0)
    mini_batch = 16
    iters = 3 if quick else 10

    results = {"benchmark": "remat_policy", "arch": "qwen2-1.5b-reduced",
               "seq": seq, "mini_batch": mini_batch,
               "policies": {}, "budgets": {}}

    # step time per policy at fixed geometry: the recompute cost axis
    plan = engine.plan_mbs(mini_batch, num_microbatches=4)
    mini = ds.batch(mini_batch, 0)
    split = plan.device_split(mini)
    base_t = None
    for policy in remat_lib.POLICIES:
        loss_fn = steps.make_loss_fn(cfg, dtype=jnp.float32,
                                     remat_policy=policy)
        ex = engine.CompiledScanExecutor(loss_fn, opt, plan)
        step = jax.jit(ex.make_train_step())
        dt = _time_step(step, params, opt.init(params), split, iters)
        if base_t is None:
            base_t = dt
        results["policies"][policy] = {
            "step_time_s": dt,
            "overhead_vs_none": dt / base_t - 1,
            "activation_bytes_per_sample":
                memory_model.activation_bytes_per_sample(
                    cfg, seq, act_bytes=4, remat_policy=policy),
        }
        emit(f"remat/{policy}/step", dt * 1e6,
             f"overhead={100 * (dt / base_t - 1):.1f}%")

    # admission per policy at tight/medium/roomy budgets + the joint choice
    est_none = memory_model.estimate(cfg, seq, act_bytes=4,
                                     remat_policy="none")
    act_none = est_none.activation_bytes_per_sample
    budgets = {
        "tight": est_none.total(0) + 2 * act_none,
        "medium": est_none.total(0) + 6 * act_none,
        "roomy": est_none.total(0) + int(1.5 * mini_batch * act_none),
    }
    for tag, budget in budgets.items():
        admitted = {
            policy: memory_model.suggest_micro_batch_size(
                cfg, seq, mini_batch, budget_bytes=budget, act_bytes=4,
                remat_policy=policy) or 0
            for policy in remat_lib.POLICIES}
        auto_policy, auto_micro = memory_model.suggest_remat_policy_and_micro(
            cfg, seq, mini_batch, budget_bytes=budget, act_bytes=4)
        results["budgets"][tag] = {
            "budget_bytes": int(budget),
            "admitted_micro_batch": admitted,
            "auto": {"policy": auto_policy, "micro_batch": auto_micro or 0},
        }
        emit(f"remat/admission/{tag}", float(auto_micro or 0),
             f"auto={auto_policy} " +
             " ".join(f"{p}:{m}" for p, m in admitted.items()))
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def tuning_main(quick: bool = True, out_path: str = "BENCH_tuning.json",
                cache_path: str = None):
    """Closed-loop autotuner benchmark (``--tuning-bench``), the engine
    Layer 7 acceptance numbers, recorded run over run in
    ``BENCH_tuning.json``:

      * **blocks** — bucketed grad-accum on the synthetic-manyleaf 96-leaf
        tree: per-leaf vs the legacy fixed BUCKET_BLOCK=65536 (the 8.1x
        regression) vs the size-aware heuristic default vs the tuner's
        measured winner; the headline ratio is bucketed-default / per-leaf
        (must stay within 1.5x).
      * **calibration** — reduced qwen2 at a tight budget: the analytic
        plan's admitted micro vs the oracle-calibrated plan's, and XLA
        ``memory_analysis()`` of the step at the calibrated micro proving
        it stays under the budget.
    """
    import tempfile

    from repro.engine import autotune
    from repro.kernels.grad_accum import BUCKET_BLOCK

    cache_path = cache_path or os.path.join(tempfile.mkdtemp(), "tuning.json")
    iters = 3 if quick else 10
    results = {"benchmark": "tuning", "blocks": {}, "calibration": {}}

    # -- half 2: kernel block tuning (96-leaf always: the acceptance config)
    params = many_leaf_params(96)
    spec = engine.FlatSpec.for_tree(params)
    grads = jax.tree.map(lambda p: p * 0.5 + 0.1, params)
    acc_tree = jax.tree.map(jnp.zeros_like, params)
    gbufs = spec.flatten(grads, dtype=jnp.float32)

    t_leaf = time_fn(
        jax.jit(lambda a, g: ga.grad_accum_tree(a, g, 0.125, interpret=True)),
        acc_tree, grads, iters=iters)
    t_legacy = time_fn(
        jax.jit(lambda a, g: ga.grad_accum_buckets(
            a, g, 0.125, block=BUCKET_BLOCK, interpret=True)),
        spec.zeros(jnp.float32), gbufs, iters=iters)
    t_default = time_fn(
        jax.jit(lambda a, g: ga.grad_accum_buckets(a, g, 0.125,
                                                   interpret=True)),
        spec.zeros(jnp.float32), gbufs, iters=iters)

    sweep = autotune.tune_for_params(params, iters=iters,
                                     cache_path=cache_path)
    engine.set_cache_path(cache_path)  # block=None now resolves the winners
    try:
        t_tuned = time_fn(
            jax.jit(lambda a, g: ga.grad_accum_buckets(a, g, 0.125,
                                                       interpret=True)),
            spec.zeros(jnp.float32), gbufs, iters=iters)
    finally:
        engine.set_cache_path(None)

    results["blocks"] = {
        "config": "synthetic-manyleaf", "num_leaves": spec.num_leaves,
        "bucket_elems": [int(n) for n in spec.bucket_sizes],
        "per_leaf_s": t_leaf / 1e6,
        "bucketed_legacy_65536_s": t_legacy / 1e6,
        "bucketed_default_s": t_default / 1e6,
        "bucketed_tuned_s": t_tuned / 1e6,
        "default_blocks": [int(b) for b in spec.bucket_blocks(
            "grad_accum", dtype=jnp.float32, interpret=True)],
        "ratio_default_vs_per_leaf": t_default / t_leaf,
        "ratio_legacy_vs_per_leaf": t_legacy / t_leaf,
        "sweep": {k: {kk: vv for kk, vv in r.items() if kk != "key"}
                  for k, r in sweep.items()},
    }
    emit("tuning/blocks/per_leaf", t_leaf, f"launches={spec.num_leaves}")
    emit("tuning/blocks/bucketed_legacy", t_legacy,
         f"block={BUCKET_BLOCK} "
         f"ratio={results['blocks']['ratio_legacy_vs_per_leaf']:.2f}x")
    emit("tuning/blocks/bucketed_default", t_default,
         f"ratio={results['blocks']['ratio_default_vs_per_leaf']:.2f}x "
         "vs per-leaf (acceptance: <= 1.5x)")
    emit("tuning/blocks/bucketed_tuned", t_tuned,
         f"winners={[r['block'] for r in sweep.values()]}")

    # -- half 1: oracle-calibrated admission on reduced qwen2 --------------
    cfg = configs.get_reduced("qwen2-1.5b")
    seq, mini = 128, 64
    budget = 64 * 1024 ** 2  # tight: analytically even micro 1 overflows
    plan_kw = dict(model_cfg=cfg, seq_len=seq, budget_bytes=budget,
                   remat_policy="period", act_bytes=4)
    analytic = engine.plan_mbs(mini, **plan_kw)
    calibrated = engine.plan_mbs(mini, calibrate="force",
                                 tuning_cache=cache_path, **plan_kw)
    measured = autotune.measured_step_bytes(
        cfg, seq, calibrated.micro_batch_size, remat_policy="period")
    results["calibration"] = {
        "arch": "qwen2-1.5b-reduced", "seq": seq, "mini_batch": mini,
        "budget_bytes": budget,
        "analytic_micro": analytic.micro_batch_size,
        "calibrated_micro": calibrated.micro_batch_size,
        "admission_uplift": (calibrated.micro_batch_size
                             / analytic.micro_batch_size),
        "correction": list(calibrated.correction),
        "measured_bytes_at_calibrated_micro": int(measured),
        "under_budget": bool(measured <= budget),
    }
    emit("tuning/calibration/analytic_micro",
         float(analytic.micro_batch_size), f"budget={budget}")
    emit("tuning/calibration/calibrated_micro",
         float(calibrated.micro_batch_size),
         f"measured={measured} under_budget={measured <= budget} "
         f"uplift={results['calibration']['admission_uplift']:.1f}x")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def faults_main(quick: bool = True, out_path: str = "BENCH_faults.json"):
    """Fault-tolerance benchmark (``--fault-bench``), the engine Layer 9
    acceptance numbers, recorded run over run in ``BENCH_faults.json``:
    per fault class, the supervisor's recovery time, steps lost/replayed,
    restart count and the plan admission before/after degradation — plus
    the steady-state supervision overhead (the synchronous ``nonfinite``
    readback) vs the plain async ``Trainer`` loop."""
    import tempfile

    from repro.checkpoint import checkpoint as ckpt_lib
    from repro.engine import faults

    cfg = configs.get_reduced("qwen2-1.5b")
    params0 = transformer.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = steps.make_loss_fn(cfg, dtype=jnp.float32, remat=False)
    opt = optim.sgd(0.01, momentum=0.9)
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=32, seed=0)
    mini_batch = 8
    n_steps = 6 if quick else 12
    plan = engine.plan_mbs(mini_batch, num_microbatches=2)

    def fresh():
        p = jax.tree.map(jnp.copy, params0)
        return p, opt.init(p)

    def make_build(guard=True):
        def build(pl):
            ex = engine.get_executor("compiled")(loss_fn, opt, pl,
                                                 guard=guard)
            return ex.step_split, engine.Pipeline(ds, pl, prefetch=0)
        return build

    def admission(pl):
        return {"micro_batch_size": pl.micro_batch_size,
                "num_micro_batches": pl.num_micro_batches,
                "remat_policy": pl.remat_policy}

    def run(specs, *, start_plan=None, sup_kw=None, ckpt: bool = True):
        sup = engine.Supervisor(
            make_build(), start_plan or plan,
            config=engine.SupervisorConfig(**(sup_kw or {})),
            ckpt_dir=tempfile.mkdtemp() if ckpt else None,
            ckpt_every=2, ckpt_keep=3, log_fn=None)
        p, s = fresh()
        crash = None
        t0 = time.perf_counter()
        with faults.inject(faults.FaultPlan(*specs)):
            try:
                sup.fit(p, s, n_steps)
            except faults.InjectedCrash as e:
                crash = str(e)
        return sup, time.perf_counter() - t0, crash

    results = {"benchmark": "faults", "arch": "qwen2-1.5b-reduced",
               "steps": n_steps, "plan": admission(plan), "faults": {}}

    # -- steady-state supervision cost (no faults injected) ----------------
    p, s = fresh()
    trainer = engine.Trainer(*make_build(guard=False)(plan), log_fn=None)
    t0 = time.perf_counter()
    trainer.fit(p, s, n_steps)
    t_plain = (time.perf_counter() - t0) / n_steps
    sup, wall, _ = run((), ckpt=False)
    t_sup = wall / n_steps
    results["supervision_overhead"] = {
        "trainer_step_s": t_plain, "supervised_step_s": t_sup,
        "overhead_frac": t_sup / t_plain - 1}
    emit("faults/overhead/supervised_step", t_sup * 1e6,
         f"vs trainer {t_plain * 1e6:.0f}us "
         f"(+{100 * (t_sup / t_plain - 1):.1f}%: sync nonfinite readback)")

    # -- oom: remat escalation rung (geometry preserved) -------------------
    sup, wall, _ = run([faults.oom_at(2)])
    rec = sup.records[-1]
    results["faults"]["oom_remat"] = {
        "recovery_s": rec.recovery_s, "steps_lost": rec.steps_lost,
        "restarts": sup.restarts, "action": rec.action,
        "admission_before": admission(plan),
        "admission_after": admission(sup.plan)}
    emit("faults/oom_remat/recovery", rec.recovery_s * 1e6,
         f"{rec.action}, {rec.steps_lost} steps replayed")

    # -- oom with remat exhausted: micro-shrink rung -----------------------
    import dataclasses as _dc
    full = _dc.replace(plan, remat_policy="full", auto_policy=False)
    sup, wall, _ = run([faults.oom_at(2)], start_plan=full)
    rec = sup.records[-1]
    results["faults"]["oom_shrink"] = {
        "recovery_s": rec.recovery_s, "steps_lost": rec.steps_lost,
        "restarts": sup.restarts, "action": rec.action,
        "admission_before": admission(full),
        "admission_after": admission(sup.plan)}
    emit("faults/oom_shrink/recovery", rec.recovery_s * 1e6,
         f"{rec.action}, {rec.steps_lost} steps replayed")

    # -- non-finite gradient: bounded clean re-draw retry, then skip -------
    sup, wall, _ = run([faults.nan_at(2)])
    rec = sup.records[-1]
    results["faults"]["nan_retry"] = {
        "recovery_s": rec.recovery_s, "steps_lost": rec.steps_lost,
        "action": rec.action}
    emit("faults/nan_retry/recovery", rec.recovery_s * 1e6, rec.action)
    sup, wall, _ = run([faults.nan_at(2)], sup_kw={"nan_retries": 0})
    rec = sup.records[-1]
    results["faults"]["nan_skip"] = {
        "recovery_s": rec.recovery_s, "steps_lost": rec.steps_lost,
        "action": rec.action}
    emit("faults/nan_skip/recovery", rec.recovery_s * 1e6, rec.action)

    # -- transient worker failure: absorbed by the pipeline's retries ------
    sup, wall, _ = run([faults.worker_at(1)])
    results["faults"]["worker_transient"] = {
        "pipeline_retries": sup.pipeline.stats.retries,
        "steps_lost": 0, "restarts": sup.restarts}
    emit("faults/worker/retries", float(sup.pipeline.stats.retries),
         "absorbed in the producer loop, 0 steps lost")

    # -- checkpoint-I/O failure: bounded save retry ------------------------
    sup, wall, _ = run([faults.ckpt_io_at(2)])
    io_recs = [r for r in sup.records if r.kind == "transient"]
    results["faults"]["ckpt_io"] = {
        "save_retries": len(io_recs), "steps_lost": 0,
        "committed": bool(ckpt_lib.committed_steps(sup.ckpt_dir))}
    emit("faults/ckpt_io/save_retries", float(len(io_recs)),
         "save retried then committed, 0 steps lost")

    # -- torn checkpoint write: crash mid-commit, restore skips it ---------
    sup, wall, crash = run([faults.torn_write_at(2)])
    committed = ckpt_lib.committed_steps(sup.ckpt_dir)
    results["faults"]["torn_write"] = {
        "crashed": crash is not None,
        "committed_steps_on_disk": committed,
        "torn_step_invisible": 2 not in committed}
    emit("faults/torn_write/committed", float(len(committed)),
         f"crash at step-2 commit; committed={committed} (torn invisible)")

    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def serve_main(quick: bool = True, out_path: str = "BENCH_serve.json"):
    """Serving benchmark (``--serve-bench``), the engine Layer 10
    acceptance numbers, recorded run over run in ``BENCH_serve.json``."""
    from repro.analysis import serve_checks
    from repro.analysis.hlo_checks import measured_peak_bytes
    from repro.engine import serving

    arch = "qwen2-1.5b"
    cfg = configs.get_reduced(arch)
    max_len = 96
    prefill_micro = 4
    # a budget that admits a bounded slot pool (16 slots exactly) so the
    # admission bound, not the slot cap, shapes the run
    est = memory_model.serve_estimate(cfg, max_len, prefill_len=max_len)
    budget = est.total(16, prefill_micro)
    plan = serving.plan_serve(cfg, budget_bytes=budget, max_len=max_len,
                              prefill_micro=prefill_micro)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = serving.ServingEngine(params, cfg, plan, dtype=jnp.float32)

    n_requests = 24 if quick else 96
    prompt_lens, new_tokens = (8, 16, 32), (4, 8, 16)
    reqs = list(serving.synthetic_traffic(
        n_requests, rate_rps=200.0, prompt_lens=prompt_lens,
        new_tokens=new_tokens, vocab_size=cfg.vocab_size, seed=0))
    eng.run(reqs, warmup_prompt_lens=prompt_lens)
    rep = eng.finished_report(reqs)

    # measured decode peak at the SAME plan geometry, via the analysis layer
    built = serve_checks.build_decode(
        arch, budget_bytes=budget, max_len=max_len,
        max_slots=plan.max_decode_slots, prefill_micro=plan.prefill_micro)
    measured = measured_peak_bytes(built["compiled"])

    # admitted-slots-vs-budget: the serving admission curve
    curve = {}
    for tag, frac in (("half", 0.5), ("planned", 1.0), ("double", 2.0)):
        b = int(budget * frac)
        try:
            p = serving.plan_serve(cfg, budget_bytes=b, max_len=max_len,
                                   prefill_micro=prefill_micro)
            curve[tag] = {"budget_bytes": b, "slots": p.max_decode_slots,
                          "modeled_peak_bytes": p.modeled_peak_bytes()}
        except ValueError as e:
            curve[tag] = {"budget_bytes": b, "slots": 0, "error": str(e)}

    results = {
        "benchmark": "serve", "arch": f"{arch}-reduced",
        "max_len": max_len, "requests": n_requests,
        "prompt_lens": list(prompt_lens), "new_tokens": list(new_tokens),
        "plan": {"budget_bytes": int(budget),
                 "decode_slots": plan.max_decode_slots,
                 "prefill_micro": plan.prefill_micro,
                 "kv_slot_bytes": plan.kv_slot_bytes,
                 "modeled_peak_bytes": plan.modeled_peak_bytes()},
        "report": rep,
        "decode_peak": {"measured_bytes": int(measured),
                        "budget_bytes": int(budget),
                        "under_budget": bool(measured <= budget)},
        "admitted_slots_vs_budget": curve,
    }
    dec = rep["decode"]
    emit("serve/decode/tokens_per_s", dec["tokens_per_s"],
         f"{dec['tokens']} decode-issued tokens over {dec['steps']} steps")
    emit("serve/decode/itl_p50", dec["itl_s"]["p50"] * 1e6,
         f"p99={dec['itl_s']['p99'] * 1e3:.1f}ms")
    emit("serve/prefill/latency_p50", rep["prefill"]["latency_s"]["p50"] * 1e6,
         f"{rep['prefill']['batches']} micro-batches (reported separately "
         "from decode)")
    emit("serve/ttft_p50", rep["ttft_s"]["p50"] * 1e6,
         f"p99={rep['ttft_s']['p99'] * 1e3:.1f}ms")
    emit("serve/slots", float(plan.max_decode_slots),
         f"measured decode peak {measured} <= budget {int(budget)}: "
         f"{measured <= budget}")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def _count_allreduce(jitted, *args) -> int:
    """All-reduce operands of the compiled step (XLA's combiner may print
    the baseline's syncs as one tuple all-reduce)."""
    from repro.analysis.hlo_checks import collectives
    return sum(len(c.operand_bytes)
               for c in collectives(jitted.lower(*args).compile())
               if c.kind == "all-reduce")


def mesh_main(quick: bool = True, out_path: str = "BENCH_mesh.json"):
    """Sharded-execution benchmark (``--mesh-bench``): deferred-sync vs
    per-micro-sync step time + compiled all-reduce counts at data-parallel
    2/4/8, and the mesh-aware planner's admission at a fixed per-device
    budget (the Layer-6 acceptance numbers, recorded run over run)."""
    from repro.launch import mesh as mesh_lib

    cfg = configs.get_reduced("qwen2-1.5b")
    seq = 32
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = steps.make_loss_fn(cfg, dtype=jnp.float32, remat=False)
    opt = optim.sgd(0.01, momentum=0.9)
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=seq, seed=0)
    mini_batch, n_micro = 16, 4
    iters = 3 if quick else 10
    # a per-device budget that admits a handful of local samples: the
    # admission axis shows dp * local_micro (the global batch) growing
    est = memory_model.estimate(cfg, seq, act_bytes=4, remat_policy="none")
    budget = est.total(0) + 4 * est.activation_bytes_per_sample

    results = {"benchmark": "mesh_sharded", "arch": "qwen2-1.5b-reduced",
               "seq": seq, "mini_batch": mini_batch,
               "devices": jax.device_count(),
               "data_parallel": {}}
    mini = ds.batch(mini_batch, 0)
    for dp in (2, 4, 8):
        if jax.device_count() < dp:
            results["data_parallel"][str(dp)] = {
                "skipped": f"needs {dp} devices, have {jax.device_count()}"}
            continue
        mesh = mesh_lib.make_host_mesh(data=dp, model=1)
        # unroll the scan so the per-micro baseline's collectives are
        # visible in the HLO text (a rolled loop body appears once)
        plan = engine.plan_mbs(mini_batch, num_microbatches=n_micro,
                               mesh=mesh, unroll=n_micro)
        split = plan.device_split(mini)
        state = opt.init(params)
        # the plan's ACTUAL schedule: dp-divisibility rounding can change
        # the micro size (and so N_Sμ) as the data axis grows
        row = {"local_micro": plan.local_micro,
               "micro_batch_global": plan.micro_batch_size,
               "num_microbatches": plan.num_micro_batches}
        for tag, defer in (("deferred_sync", True), ("per_micro_sync", False)):
            ex = engine.ShardedExecutor(loss_fn, opt, plan, mesh=mesh,
                                        inner="compiled", defer_sync=defer,
                                        donate=False)
            step = jax.jit(ex.make_train_step())
            row[tag] = {
                "step_time_s": _time_step(step, params, state, split, iters),
                "allreduce_ops": _count_allreduce(step, params, state, split),
            }
        row["speedup_deferred"] = (row["per_micro_sync"]["step_time_s"]
                                   / row["deferred_sync"]["step_time_s"])
        # admission at the fixed per-device budget (mesh-aware planner)
        adm = engine.plan_mbs(256, model_cfg=cfg, seq_len=seq,
                              budget_bytes=budget, act_bytes=4,
                              remat_policy="none", mesh=mesh,
                              fsdp_params=False)
        row["admission"] = {"budget_bytes": int(budget),
                            "global_micro_admitted": adm.micro_batch_size,
                            "local_micro": adm.local_micro}
        results["data_parallel"][str(dp)] = row
        emit(f"mesh/dp{dp}/deferred",
             row["deferred_sync"]["step_time_s"] * 1e6,
             f"allreduce={row['deferred_sync']['allreduce_ops']} "
             f"speedup={row['speedup_deferred']:.2f}x vs per-micro "
             f"({row['per_micro_sync']['allreduce_ops']} allreduce)")
        emit(f"mesh/dp{dp}/admission", float(adm.micro_batch_size),
             f"local={adm.local_micro} at fixed per-device budget")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


def pp_main(quick: bool = True, out_path: str = "BENCH_pp.json"):
    """Pipeline-parallel benchmark (``--pp-bench``), the engine Layer 11
    acceptance numbers, recorded run over run in ``BENCH_pp.json``:

      * **step_times** — the 1F1B PipelinedExecutor on a staged toy stack
        (4 stacked middle layers, the :class:`~repro.engine.StagedLoss`
        contract) at stages 2/4 × dp 1/2, vs the stages=1 baselines at
        the same data parallelism (CompiledScanExecutor at dp=1, the
        deferred-sync ShardedExecutor at dp=2). Each pipelined cell also
        records the closed-form schedule's tick count and bubble fraction
        (S-1)/(M+S-1) — the analytic idle share the measured time should
        track as micro-batches amortize the fill/drain ramps.
      * **admission** — reduced qwen2 at a fixed per-device budget: the
        local micro-batch ``plan_mbs`` admits at stages 1/2 × dp 1/2/4.
        With ``pipeline=True`` the model axis holds stage-LOCAL blocks and
        activations, so the per-device activation term shrinks with the
        stage count and the planner converts the freed bytes into batch.
    """
    from repro.core import losses
    from repro.launch import mesh as mesh_lib

    # staged toy stack: prelude -> NUM_LAYERS stacked tanh blocks ->
    # logits + CE, factored through the StagedLoss contract with a flat
    # single-device twin computing the identical function
    num_layers, d_in, d_h, n_cls = 4, 8, 64, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    params = {
        "w_in": 0.3 * jax.random.normal(ks[0], (d_in, d_h), jnp.float32),
        "mid": 0.3 * jax.random.normal(ks[1], (num_layers, d_h, d_h),
                                       jnp.float32),
        "w_out": 0.3 * jax.random.normal(ks[2], (d_h, n_cls), jnp.float32),
    }
    mini_batch, micro = 16, 4
    batch = {"x": jax.random.normal(ks[3], (mini_batch, d_in), jnp.float32),
             "y": jax.random.randint(ks[4], (mini_batch,), 0, n_cls,
                                     jnp.int32)}

    def flat_loss(p, mb, exact_denom=None):
        x = jnp.tanh(mb["x"] @ p["w_in"])
        for i in range(num_layers):
            x = jnp.tanh(x @ p["mid"][i])
        return losses.cross_entropy(
            x @ p["w_out"], mb["y"], sample_weight=mb.get("sample_weight"),
            exact_denom=exact_denom), {}

    def prelude(shared, mb):
        return jnp.tanh(mb["x"] @ shared["w_in"])

    def stage_fn(stage_p, x):
        def body(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, stage_p)[0]

    def finale(shared, x, mb):
        return losses.cross_entropy(
            x @ shared["w_out"], mb["y"],
            sample_weight=mb.get("sample_weight"), exact_denom=1.0), {}

    staged = engine.StagedLoss(num_layers=num_layers, prelude=prelude,
                               stage_fn=stage_fn, finale=finale,
                               stacked_key="mid")
    opt = optim.sgd(0.01, momentum=0.9)
    iters = 3 if quick else 10

    results = {"benchmark": "pipeline_parallel", "devices": jax.device_count(),
               "mini_batch": mini_batch, "micro_batch": micro,
               "toy": {"num_layers": num_layers, "d_hidden": d_h},
               "step_times": {}, "admission": {}}

    base_by_dp = {}
    for stages in (1, 2, 4):
        for dp in (1, 2):
            key = f"s{stages}xd{dp}"
            if jax.device_count() < stages * dp:
                results["step_times"][key] = {
                    "skipped": f"needs {stages * dp} devices, have "
                               f"{jax.device_count()}"}
                continue
            if stages == 1 and dp == 1:
                plan = engine.plan_mbs(mini_batch, micro_batch_size=micro,
                                       normalization="exact", remat=False)
                ex = engine.CompiledScanExecutor(flat_loss, opt, plan)
                split = plan.device_split(batch)
            elif stages == 1:
                mesh = mesh_lib.make_host_mesh(data=dp, model=1)
                plan = engine.plan_mbs(mini_batch, micro_batch_size=micro,
                                       normalization="exact", remat=False,
                                       mesh=mesh)
                ex = engine.ShardedExecutor(flat_loss, opt, plan, mesh=mesh,
                                            inner="compiled",
                                            defer_sync=True, donate=False)
                split = plan.device_split(batch)
            else:
                mesh = mesh_lib.make_host_mesh(data=dp, model=stages)
                plan = engine.plan_mbs(mini_batch, micro_batch_size=micro,
                                       normalization="exact", remat=False,
                                       mesh=mesh, pipeline=True)
                ex = engine.PipelinedExecutor(staged, opt, plan, mesh=mesh,
                                              defer_sync=True)
                split = ex.stage(plan.split(batch))
            step = jax.jit(ex.make_train_step())
            dt = _time_step(step, params, opt.init(params), split, iters)
            row = {"step_time_s": dt,
                   "num_microbatches": plan.num_micro_batches}
            if stages == 1:
                base_by_dp[dp] = dt
            else:
                n_micro = plan.num_micro_batches
                _, _, _, ticks = engine.schedule_1f1b(stages, n_micro)
                row["ticks"] = int(ticks)
                row["bubble_fraction"] = (stages - 1) / (n_micro + stages - 1)
                row["slowdown_vs_flat"] = dt / base_by_dp[dp]
            results["step_times"][key] = row
            extra = (f"bubble={row['bubble_fraction']:.2f} "
                     f"x{row['slowdown_vs_flat']:.2f} vs flat dp{dp}"
                     if stages > 1 else "flat baseline")
            emit(f"pp/{key}/step", dt * 1e6, extra)

    # pipelined admission on the real reduced stack: fixed per-device
    # budget, growing model axis (stages must divide the block stack —
    # the reduced configs have 2 periods, so stages in {1, 2})
    cfg = configs.get_reduced("qwen2-1.5b")
    seq, mini_adm = 64, 256
    est1 = memory_model.estimate(cfg, seq, act_bytes=4, remat_policy="period")
    budget = est1.total(2)
    results["admission"]["arch"] = "qwen2-1.5b-reduced"
    results["admission"]["seq"] = seq
    results["admission"]["budget_bytes"] = int(budget)
    results["admission"]["grid"] = {}
    for stages in (1, 2):
        for dp in (1, 2, 4):
            mesh = mesh_lib.make_host_mesh(data=dp, model=stages)
            plan = engine.plan_mbs(mini_adm, model_cfg=cfg, seq_len=seq,
                                   budget_bytes=budget, act_bytes=4,
                                   remat_policy="period", mesh=mesh,
                                   pipeline=(stages > 1), fsdp_params=False)
            est = memory_model.estimate(cfg, seq, act_bytes=4,
                                        remat_policy="period", mesh=mesh,
                                        pipeline=(stages > 1))
            key = f"s{stages}xd{dp}"
            results["admission"]["grid"][key] = {
                "local_micro": plan.local_micro,
                "global_micro": plan.micro_batch_size,
                "num_microbatches": plan.num_micro_batches,
                "pipeline_stages": getattr(plan, "pipeline_stages", 1),
                "act_bytes_per_sample": int(est.activation_bytes_per_sample),
            }
            emit(f"pp/admission/{key}", float(plan.micro_batch_size),
                 f"local={plan.local_micro} "
                 f"act/sample={est.activation_bytes_per_sample}")
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2)
    print(f"wrote {out_path}", flush=True)
    return results


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--pipeline", action="store_true",
                    help="run the input-pipeline overlap benchmark and "
                         "write BENCH_pipeline.json")
    ap.add_argument("--update-bench", action="store_true",
                    help="run the update-path benchmark and write "
                         "BENCH_update.json")
    ap.add_argument("--remat-bench", action="store_true",
                    help="run the remat-policy benchmark and write "
                         "BENCH_remat.json")
    ap.add_argument("--mesh-bench", action="store_true",
                    help="run the sharded-execution benchmark (deferred vs "
                         "per-micro gradient sync at data=2/4/8) and write "
                         "BENCH_mesh.json")
    ap.add_argument("--tuning-bench", action="store_true",
                    help="run the closed-loop autotuner benchmark (tuned "
                         "vs default block times + oracle-calibrated "
                         "admission uplift) and write BENCH_tuning.json")
    ap.add_argument("--tuning-cache", default=None,
                    help="tuning-cache path for --tuning-bench (default: "
                         "a throwaway temp file)")
    ap.add_argument("--fault-bench", action="store_true",
                    help="run the fault-tolerance benchmark (per-fault-class "
                         "recovery time / steps lost / admission "
                         "degradation) and write BENCH_faults.json")
    ap.add_argument("--pp-bench", action="store_true",
                    help="run the pipeline-parallel benchmark (1F1B step "
                         "time at stages 2/4 x dp 1/2 vs the flat "
                         "baselines + pipelined planner admission) and "
                         "write BENCH_pp.json")
    ap.add_argument("--serve-bench", action="store_true",
                    help="run the serving benchmark (decode tok/s, p50/p99 "
                         "per-token latency, admitted-slots-vs-budget, "
                         "measured decode peak) and write BENCH_serve.json")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    if a.pipeline:
        pipeline_main(quick=a.quick, out_path=a.out or "BENCH_pipeline.json")
    elif a.update_bench:
        update_main(quick=a.quick, out_path=a.out or "BENCH_update.json")
    elif a.remat_bench:
        remat_main(quick=a.quick, out_path=a.out or "BENCH_remat.json")
    elif a.mesh_bench:
        mesh_main(quick=a.quick, out_path=a.out or "BENCH_mesh.json")
    elif a.tuning_bench:
        tuning_main(quick=a.quick, out_path=a.out or "BENCH_tuning.json",
                    cache_path=a.tuning_cache)
    elif a.fault_bench:
        faults_main(quick=a.quick, out_path=a.out or "BENCH_faults.json")
    elif a.pp_bench:
        pp_main(quick=a.quick, out_path=a.out or "BENCH_pp.json")
    elif a.serve_bench:
        serve_main(quick=a.quick, out_path=a.out or "BENCH_serve.json")
    else:
        main(quick=a.quick)
