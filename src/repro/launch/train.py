"""Production training launcher.

Builds the mesh from the actual device topology (falls back to a host mesh
when run off-cluster), shards params/optimizer via the divisibility policy,
and drives an MBS engine executor through the async input pipeline: the
dataset is batched + plan-split in a background worker (exceptions
propagate), staged host→device with the launcher's batch shardings
(double-buffered at mini-batch granularity), and the ``Trainer`` owns the
step loop — async metrics readback, periodic checkpointing, ``--resume``.

Batch geometry comes from the engine planner: ``--microbatches`` pins
N_Sμ; without it the micro-batch size is derived from the analytic memory
model (``--hbm-budget-gb``). Ragged mini-batches (N_B % N_μ != 0) are
padded + masked, not rejected.

With ``--supervise`` the whole runtime (executor + pipeline) is built
through a rebuild factory and driven by the engine Layer-9
:class:`engine.Supervisor` instead of the bare ``Trainer``: executors run
with the on-device finite-guard, runtime OOM degrades the plan (remat
escalation, then calibrated micro-shrink — the failure is recorded as a
negative bound in the tuning cache) and resumes from the last completed
state, non-finite steps are retried/skipped per ``--on-nan``, and
supervisor give-ups map onto the documented exit codes (40–44,
DESIGN.md §Fault tolerance).

With ``--profile-dir DIR`` the ``Trainer`` writes a ``jax.profiler``
trace of ``--profile-steps`` steps, taken after the first step compiled,
into DIR (``DIR/plugins/profile/<time>/*.xplane.pb``). The trace holds
the host spans and the step's device phases named in ``repro.spans``;
``python3 -m bench.phases DIR`` (from the checkout's root) reduces it to
each phase's share of the device time.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --reduced --steps 20 --mini-batch 16 [--microbatches 4] \
      [--executor compiled|streaming|fused] \
      [--ckpt-dir /tmp/ckpt --ckpt-every 10 --resume] \
      [--supervise --max-restarts 3 --on-nan skip --ckpt-keep 3] \
      [--profile-dir /tmp/prof --profile-steps 5]
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .. import configs, engine, optim
from ..core import memory_model
from ..data import LMDataset
from ..models import encdec, transformer
from . import compile_cache, mesh as mesh_lib, sharding, steps


def build_mesh(args):
    n = len(jax.devices())
    if args.mesh == "production":
        return mesh_lib.make_production_mesh(multi_pod=args.multi_pod)
    if args.mesh == "host":
        # host mesh: all local devices on the data axis
        return mesh_lib.make_host_mesh(data=n, model=1)
    # explicit "DATA:MODEL" axis spec — model > 1 routes the step through
    # the Layer-11 pipelined executor (validated in main() at parse time)
    data, model = mesh_lib.parse_mesh_spec(args.mesh, n)
    return mesh_lib.make_host_mesh(data=data, model=model)


def default_optimizer(args) -> optim.Optimizer:
    return optim.sgd(args.lr, momentum=0.9, weight_decay=5e-4)


def budget_bytes(args) -> Optional[int]:
    """Per-device planning budget: ``--hbm-budget-gb`` when given; on a
    TPU the limit the device itself reports; elsewhere ``None``, which
    plans against ``memory_model.V5E_HBM_BYTES`` (CPU and dry-run
    planning)."""
    if args.hbm_budget_gb:
        return int(args.hbm_budget_gb * 1024 ** 3)
    device = jax.devices()[0]
    if device.platform == "tpu":
        return memory_model.device_bytes_limit(device)
    return None


def build_plan(cfg, args, optimizer=None, mesh=None) -> engine.MBSPlan:
    """The launcher's batch geometry: pinned N_Sμ when given, else the
    memory model picks the micro-batch size (paper §4.3.2, computed) —
    jointly with the remat policy when ``--remat-policy auto`` (the
    default: cheapest recompute that meets the batch target, escalating
    only when the budget forces it). ``optimizer`` (default: the
    launcher's SGD-momentum) feeds the model's state-slot count and
    step-❺ transient: the flat executor updates in place, so its plan
    admits larger auto micro-batches — but only when the optimizer
    actually publishes a fused hook.

    With a ``mesh`` the plan is per-device (engine Layer 6): the budget is
    one worker's HBM, the micro-batch stays divisible by the data axis,
    and the params discount follows the real executor — the host-mesh
    ``ShardedExecutor`` replicates params (``fsdp_params=False``), the
    production GSPMD path FSDP-shards them."""
    dtype_bytes = 4 if args.dtype == "float32" else 2
    optimizer = optimizer or default_optimizer(args)
    return engine.plan_mbs(
        args.mini_batch, num_microbatches=args.microbatches,
        model_cfg=cfg, seq_len=args.seq, budget_bytes=budget_bytes(args),
        normalization=args.normalization,
        act_bytes=dtype_bytes, remat=not args.reduced,
        remat_policy=getattr(args, "remat_policy", None),
        mesh=mesh, fsdp_params=getattr(args, "mesh", "host") == "production",
        pipeline=(mesh is not None
                  and mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS) > 1),
        calibrate=getattr(args, "calibrate", "off"),
        tuning_cache=getattr(args, "tuning_cache", None),
        executor=args.executor,
        **optim.memory_model_kw(optimizer, fused=args.executor == "flat"))


def build_executor(cfg, plan, args, optimizer=None, mesh=None, guard=False,
                   interpret=None):
    """The step path used by main() — also exercised directly by the
    end-to-end ragged-tail test. The loss compiles under the plan's
    chosen remat policy, so the step matches what the planner admitted.
    With a data-parallel ``mesh`` (>1 worker on the batch axes) every
    ``--executor`` routes through the :class:`engine.ShardedExecutor`
    wrapper: per-device accumulation, ONE gradient all-reduce per
    mini-batch. A mesh with a ``model`` axis > 1 routes through the
    Layer-11 :class:`engine.PipelinedExecutor` instead — the block stack
    is split into stages and the plan's micro-batches run 1F1B
    (``--fsdp`` additionally shards params over the data axis with
    just-in-time gathers). ``guard=True`` (the supervised mode) adds the
    on-device finite-check to the update, surfacing a ``nonfinite``
    metric. ``interpret`` reaches the Pallas kernels of the fused and
    flat strategies (``None``: interpret off-TPU only)."""
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    opt = optimizer or default_optimizer(args)
    if mesh is not None and mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS) > 1:
        staged = steps.make_staged_loss(cfg, dtype=dtype,
                                        remat_policy=plan.remat_policy)
        return engine.PipelinedExecutor(
            staged, opt, plan, mesh=mesh,
            fsdp=getattr(args, "fsdp", False), guard=guard), opt
    loss_fn = steps.make_loss_fn(cfg, dtype=dtype,
                                 remat_policy=plan.remat_policy)
    if mesh is not None and mesh_lib.data_parallel_size(mesh) > 1:
        return engine.ShardedExecutor(loss_fn, opt, plan, mesh=mesh,
                                      inner=args.executor, guard=guard,
                                      interpret=interpret), opt
    kw = {} if args.executor == "streaming" else {"interpret": interpret}
    return engine.get_executor(args.executor)(loss_fn, opt, plan,
                                              guard=guard, **kw), opt


def make_build(cfg, args, ds, mesh, host_dp, opt, interpret=None):
    """``plan -> (step_fn, pipeline)``: one factory for all three runtime
    shapes (host-DP sharded, single-device streaming, GSPMD compiled).
    ``main()`` calls it once for the plain ``Trainer``; the Supervisor
    keeps it as the rebuild hook its OOM path re-invokes after degrading
    the plan — everything plan-dependent (executor, jit, pipeline split
    geometry) is reconstructed from scratch for the new plan. The GSPMD
    step also carries ``step.lower(params, opt_state, batch)``, the
    lowering of the very jit it dispatches (for ``memory_analysis`` and
    the compiled HLO)."""
    guard = args.supervise

    def build(plan):
        executor, _ = build_executor(cfg, plan, args, optimizer=opt,
                                     mesh=mesh if host_dp else None,
                                     guard=guard, interpret=interpret)
        if host_dp:
            # data-parallel host mesh (engine Layer 6): per-device
            # accumulation of local_micro samples, ONE deferred gradient
            # all-reduce per mini-batch; the Pipeline stages with the
            # mesh batch shardings
            pipeline = engine.Pipeline(ds, plan, prefetch=args.prefetch,
                                       sharding=executor.batch_shardings)
            return executor.step_split, pipeline
        if args.executor == "streaming":
            # eager paper pipeline: whole split mini-batches staged to the
            # device, micro-batches sliced on device
            pipeline = engine.Pipeline(ds, plan, prefetch=args.prefetch,
                                       sharding=executor.device)
            return executor.step_split, pipeline
        # GSPMD: donate params/opt-state (reused in place) AND the spent
        # split batch (freed for step-❺ temporaries); the loop threads
        # state and never touches a donated buffer again
        donate = not args.no_donate
        jitted = jax.jit(executor.make_train_step(),
                         donate_argnums=(0, 1, 2) if donate else ())

        def step(params, opt_state, batch):
            # tracing is lazy (first call) and the step body resolves
            # PartitionSpecs against the ambient mesh — keep it active at
            # dispatch
            with jax.set_mesh(mesh):
                return jitted(params, opt_state, batch)

        def lower(params, opt_state, batch):
            with jax.set_mesh(mesh):
                return jitted.lower(params, opt_state, batch)

        step.lower = lower
        pipeline = engine.Pipeline(ds, plan, prefetch=args.prefetch,
                                   mesh=mesh)
        return step, pipeline

    return build


def make_plan_ctx(cfg, args, mesh, optimizer):
    """The Supervisor's planning context: everything ``build_plan`` knows,
    so an OOM re-plan goes through the same ``plan_mbs`` the launcher used
    — and the observed failure lands in the same tuning-cache key."""
    dtype_bytes = 4 if args.dtype == "float32" else 2
    return dict(
        model_cfg=cfg, seq_len=args.seq, budget_bytes=budget_bytes(args),
        mesh=mesh,
        executor=args.executor, tuning_cache=args.tuning_cache,
        mm_kw=dict(act_bytes=dtype_bytes, remat=not args.reduced,
                   fsdp_params=args.mesh == "production",
                   pipeline=(mesh is not None and
                             mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS) > 1),
                   **optim.memory_model_kw(
                       optimizer, fused=args.executor == "flat")))


def run_trainer(trainer, params, opt_state, args):
    """Resume (when asked) + fit; shared by both executor paths."""
    start = 0
    if args.resume:
        restored = trainer.restore(params, opt_state)
        if restored is not None:
            params, opt_state, start = restored
            print(f"resumed from step {start}", flush=True)
        else:
            print("no checkpoint to resume from; starting fresh", flush=True)
    params, opt_state, last = trainer.fit(params, opt_state, args.steps,
                                          start_step=start)
    if args.ckpt_dir:
        print(f"checkpointed to {args.ckpt_dir}", flush=True)
    if args.profile_dir:
        print(f"profiled to {args.profile_dir}" if trainer.profile_dir is None
              else "not profiled: the run has fewer than 2 steps", flush=True)
    stats = trainer.pipeline.stats
    print(f"input-wait fraction {stats.input_wait_fraction:.3f} "
          f"({stats.wait_s:.2f}s of {stats.elapsed_s:.2f}s, "
          f"{stats.retries} producer retries)", flush=True)
    return params, opt_state, last


def run_supervised(supervisor, params, opt_state, args):
    """Resume + supervised fit; SupervisorError exit codes (40–44) become
    the process exit status so orchestration can tell "shrink the job"
    (PlanExhausted) from "investigate the data" (NaNCircuitBreaker)."""
    start = 0
    if args.resume:
        restored = supervisor.restore(params, opt_state)
        if restored is not None:
            params, opt_state, start = restored
            print(f"resumed from step {start}", flush=True)
        else:
            print("no checkpoint to resume from; starting fresh", flush=True)
    try:
        params, opt_state, last = supervisor.fit(params, opt_state,
                                                 args.steps, start_step=start)
    except engine.SupervisorError as e:
        print(f"[supervisor] giving up: {e}", flush=True)
        sys.exit(e.exit_code)
    rep = supervisor.report()
    print(f"[supervisor] done: restarts={rep['restarts']} "
          f"steps_lost={rep['steps_lost']} "
          f"plan: micro={rep['plan']['micro_batch_size']} "
          f"remat={rep['plan']['remat_policy']}", flush=True)
    if args.ckpt_dir:
        print(f"checkpointed to {args.ckpt_dir}", flush=True)
    return params, opt_state, last


def parse_args(argv=None) -> argparse.Namespace:
    """The launcher's command line (``argv=None`` reads ``sys.argv``),
    validated at parse time."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mini-batch", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=None,
                    help="pin N_Smu (default: auto micro-batch size from "
                         "the memory model)")
    ap.add_argument("--executor", choices=sorted(engine.EXECUTORS),
                    default="compiled")
    ap.add_argument("--normalization", choices=["paper", "exact"],
                    default="paper")
    ap.add_argument("--remat-policy",
                    choices=["auto", "none", "dots", "period", "full"],
                    default="auto",
                    help="activation-checkpoint grade; auto = planner "
                         "picks it jointly with the micro-batch size "
                         "(cheapest recompute that meets the batch target)")
    ap.add_argument("--hbm-budget-gb", type=float, default=None,
                    help="per-device HBM budget for auto micro-batch sizing")
    ap.add_argument("--calibrate", choices=["off", "auto", "force"],
                    default="auto",
                    help="oracle-calibrated admission (engine.autotune): "
                         "auto = use a cached memory correction when one "
                         "exists (analytic fallback otherwise); force = "
                         "run the probe compiles now and persist the fit; "
                         "off = pure analytic")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="tuning-cache JSON path (default: "
                         "$REPRO_TUNING_CACHE or ~/.cache/repro-tuning/); "
                         "also feeds the kernels' tuned launch blocks")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--mesh", default="host",
                    help="'host' (all devices on the data axis), "
                         "'production', or an explicit 'DATA:MODEL' axis "
                         "spec like '2:4' — MODEL > 1 pipelines the block "
                         "stack over the model axis (1F1B, engine "
                         "Layer 11)")
    ap.add_argument("--fsdp", action="store_true",
                    help="with a pipelined 'DATA:MODEL' mesh, shard "
                         "params over the data axis too (just-in-time "
                         "gathered FSDP forward)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint every N steps (0: only at the end)")
    ap.add_argument("--resume", action="store_true",
                    help="restore params+opt state from the latest "
                         "checkpoint in --ckpt-dir and continue from its step")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the Layer-9 fault-tolerant Supervisor: "
                         "guarded executors, OOM degrade-and-resume, "
                         "bounded retries; give-ups exit 40-44")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="OOM re-plan budget for the whole run "
                         "(--supervise only)")
    ap.add_argument("--on-nan", choices=["skip", "halt"], default="skip",
                    help="non-finite-gradient policy: bounded retry then "
                         "skip behind a circuit breaker, or halt "
                         "immediately (--supervise only)")
    ap.add_argument("--ckpt-keep", type=int, default=None, metavar="K",
                    help="keep only the newest K committed checkpoints "
                         "(default: keep all)")
    ap.add_argument("--no-donate", action="store_true",
                    help="do not donate params/opt-state/batch at the "
                         "step jit boundary (A/B runs that reuse state)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="host batches buffered by the input pipeline "
                         "(0: synchronous)")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="write a jax.profiler trace of --profile-steps "
                         "steps, taken after the first step compiled, "
                         "into DIR")
    ap.add_argument("--profile-steps", type=int, default=5, metavar="N",
                    help="steps traced with --profile-dir (default 5)")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"],
                    default="float32")
    args = ap.parse_args(argv)
    stages = 1
    if args.mesh not in ("host", "production"):
        try:  # validate the DATA:MODEL spec at parse time — fail fast
            stages = mesh_lib.parse_mesh_spec(args.mesh)[1]
        except ValueError as e:
            ap.error(str(e))
    if args.executor == "streaming" and (
            args.mesh == "production" or args.multi_pod or stages > 1):
        # fail fast with the actual contract (not a silent warn-and-ignore):
        # streaming composes with data-parallel HOST meshes through the
        # ShardedExecutor; TP/FSDP production meshes need a compiled
        # executor under GSPMD, pipelined meshes the Layer-11 executor
        ap.error("--executor streaming supports single-device and "
                 "data-parallel host meshes (via the ShardedExecutor); "
                 "production/multi-pod/pipelined meshes need a compiled "
                 "executor")
    if args.fsdp and args.mesh in ("host", "production"):
        ap.error("--fsdp applies to the pipelined path: pass an explicit "
                 "'DATA:MODEL' mesh spec with MODEL > 1")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")
    if args.profile_dir and args.supervise:
        ap.error("--profile-dir traces the plain Trainer loop; the "
                 "--supervise loop is not traced")
    if args.profile_steps < 1:
        ap.error("--profile-steps must be >= 1")
    return args


@dataclasses.dataclass
class Setup:
    """Everything ``main`` builds before the step loop."""
    mesh: Any
    opt: optim.Optimizer
    plan: engine.MBSPlan
    params: Any
    opt_state: Any
    state_shardings: Any
    build: Callable  # plan -> (step_fn, pipeline), see make_build


def setup(cfg, args, interpret=None) -> Setup:
    """Mesh, plan, initial state and the runtime factory for ``cfg`` under
    ``args`` (``interpret``: see :func:`build_executor`)."""
    mesh = build_mesh(args)
    dp = mesh_lib.data_parallel_size(mesh)
    tp = mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS)
    # the shard_map paths (ShardedExecutor DP, PipelinedExecutor 1F1B):
    # executor-owned step_split + plan-split pipeline staging
    host_dp = args.mesh != "production" and (dp > 1 or tp > 1)
    opt = default_optimizer(args)
    plan = build_plan(cfg, args, optimizer=opt, mesh=mesh)

    init = encdec.init_params if cfg.is_encdec else transformer.init_params
    ds = LMDataset(vocab_size=cfg.vocab_size, seq_len=args.seq, seed=0)

    gspmd = not host_dp and args.executor != "streaming"
    if gspmd:
        with jax.set_mesh(mesh):
            pshapes = jax.eval_shape(lambda k: init(cfg, k),
                                     jax.random.PRNGKey(0))
            pspecs = sharding.param_specs(pshapes, mesh)
            params = jax.jit(lambda k: init(cfg, k),
                             out_shardings=sharding.named(pspecs, mesh))(
                jax.random.PRNGKey(0))
            opt_specs = sharding.param_specs(
                jax.eval_shape(opt.init, pshapes), mesh)
            opt_state = jax.jit(opt.init, out_shardings=sharding.named(
                opt_specs, mesh))(params)
        state_shardings = {"params": sharding.named(pspecs, mesh),
                           "opt_state": sharding.named(opt_specs, mesh)}
    elif host_dp:
        # the shard_map executors take the state replicated: create it
        # there, not on one device whose copy would stay resident beside
        # the mesh's during the first step (out of memory at 2:2 on v5e)
        state_shardings = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        params = jax.jit(lambda k: init(cfg, k),
                         out_shardings=state_shardings)(jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init, out_shardings=state_shardings)(params)
    else:
        params = init(cfg, jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        state_shardings = None

    build = make_build(cfg, args, ds, mesh, host_dp, opt, interpret=interpret)
    return Setup(mesh, opt, plan, params, opt_state, state_shardings, build)


def main(argv=None):
    """Train per ``argv``; returns the last step's metrics (host floats)."""
    args = parse_args(argv)
    compile_cache.enable()
    if args.tuning_cache:
        # one cache serves both halves: the planner's memory correction
        # (threaded through build_plan) and the kernels' tuned launch
        # blocks (resolved through the process-wide active cache)
        engine.set_cache_path(args.tuning_cache)

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get(args.arch)
    run = setup(cfg, args)
    print(run.plan.describe(), flush=True)

    if args.supervise:
        supervisor = engine.Supervisor(
            run.build, run.plan,
            config=engine.SupervisorConfig(max_restarts=args.max_restarts,
                                           on_nan=args.on_nan),
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            ckpt_keep=args.ckpt_keep, log_every=args.log_every,
            state_shardings=run.state_shardings,
            plan_ctx=make_plan_ctx(cfg, args, run.mesh, run.opt))
        return run_supervised(supervisor, run.params, run.opt_state, args)[2]

    step_fn, pipeline = run.build(run.plan)
    trainer = engine.Trainer(step_fn, pipeline, ckpt_dir=args.ckpt_dir,
                             ckpt_every=args.ckpt_every,
                             ckpt_keep=args.ckpt_keep,
                             log_every=args.log_every,
                             state_shardings=run.state_shardings,
                             profile_dir=args.profile_dir,
                             profile_steps=args.profile_steps)
    return run_trainer(trainer, run.params, run.opt_state, args)[2]


if __name__ == "__main__":
    main()
