import os
# a CPU-simulated compile by design: 512 host devices, never the TPU (on a
# machine with a chip, the chip belongs to one process at a time)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production mesh, with NO device allocation (ShapeDtypeStruct inputs).

Proves the distribution config is coherent and extracts the roofline inputs:
  * main compile (scan-over-layers): ``memory_analysis()`` (fits HBM?),
    collective schedule, compile proof.
  * cost probes: XLA's cost analysis counts a while-loop body ONCE, so the
    scanned main graph under-reports FLOPs/bytes/collectives by the trip
    counts. We therefore compile two small probes — 1 period and 2 periods
    of the layer pattern, scans fully unrolled, one micro-batch — and
    extrapolate linearly (cost is affine in depth and in the number of
    micro-batches):
        X(P, n) = n * (X1 + (P - 1) * (X2 - X1))
    This is exact for per-layer work; it over-counts the once-per-step
    optimizer update n times (< 0.1% of train FLOPs; noted in
    EXPERIMENTS.md).

Usage:
  python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k \
      [--multi-pod] [--microbatches 8] [--no-probe] [--check] [--json] \
      [--out DIR]

Exit codes (shared with ``python -m repro.analysis`` — see
``repro.analysis.findings``): 0 ok, 1 tool error, 2 budget exceeded
(``--budget``), 3 static-contract findings (``--check``). argparse usage
errors also exit 2 (argparse's own convention; unambiguous in practice
because ``--budget`` is opt-in).
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from .. import configs, engine  # noqa: E402
# the HLO-text census helpers moved to the analysis subsystem (single
# source of truth for dryrun, tests, and the CI contract gate)
from ..analysis.findings import (EXIT_BUDGET, EXIT_CONTRACT,  # noqa: E402
                                 EXIT_OK)
from ..analysis.hlo_checks import collective_bytes  # noqa: E402,F401
from . import mesh as mesh_lib, sharding, steps  # noqa: E402


def _in_specs(bundle, mesh, fsdp_over_pod: bool = False, fsdp: bool = True):
    specs = []
    for i, arg in enumerate(bundle.arg_shapes):
        if bundle.kind == "train":
            spec = (sharding.param_specs(arg, mesh, fsdp=fsdp,
                                         fsdp_over_pod=fsdp_over_pod)
                    if i in (0, 1)
                    else sharding.batch_specs(arg, mesh, batch_dim=1))
        elif bundle.kind == "prefill":
            spec = (sharding.param_specs(arg, mesh) if i == 0
                    else sharding.cache_specs(arg, mesh, stacked=False))
        else:  # decode: (params, token, cache, pos)
            if i == 0:
                spec = sharding.param_specs(arg, mesh)
            elif i == 2:
                spec = sharding.cache_specs(arg, mesh, stacked=True)
            else:
                spec = sharding.cache_specs(arg, mesh, stacked=False)
        specs.append(spec)
    return specs


def _out_specs(bundle, mesh, fsdp_over_pod: bool = False, fsdp: bool = True):
    from jax.sharding import PartitionSpec as P
    out_shapes = jax.eval_shape(bundle.fn, *bundle.arg_shapes)
    if bundle.kind == "train":  # (params, opt_state, metrics)
        return (sharding.param_specs(out_shapes[0], mesh, fsdp=fsdp,
                                     fsdp_over_pod=fsdp_over_pod),
                sharding.param_specs(out_shapes[1], mesh, fsdp=fsdp,
                                     fsdp_over_pod=fsdp_over_pod),
                jax.tree.map(lambda _: P(), out_shapes[2]))
    if isinstance(out_shapes, tuple) and len(out_shapes) == 2:
        logits, cache = out_shapes  # (logits, cache)
        return (sharding.cache_specs(logits, mesh, stacked=False),
                sharding.cache_specs(cache, mesh, stacked=True))
    return sharding.cache_specs(out_shapes, mesh, stacked=False)


def _compile(bundle, mesh, fsdp_over_pod: bool = False, fsdp: bool = True,
             pipelined: bool = False):
    t0 = time.time()
    if pipelined:
        # the Layer-11 step owns its sharding (shard_map over the
        # data x model mesh, specs bound inside) — GSPMD in/out shardings
        # would fight the manual axes, and an ambient mesh context would
        # activate the model's best-effort shard hints INSIDE shard_map
        # (PartitionSpecs naming manual axes are rejected), so lower
        # without either
        jitted = jax.jit(bundle.fn, donate_argnums=bundle.donate_argnums)
        lowered = jitted.lower(*bundle.arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    else:
        with jax.set_mesh(mesh):
            jitted = jax.jit(
                bundle.fn,
                in_shardings=tuple(sharding.named(s, mesh)
                                   for s in _in_specs(bundle, mesh,
                                                      fsdp_over_pod, fsdp)),
                out_shardings=sharding.named(
                    _out_specs(bundle, mesh, fsdp_over_pod, fsdp), mesh),
                donate_argnums=bundle.donate_argnums)
            lowered = jitted.lower(*bundle.arg_shapes)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # older jax returns [dict]
        cost = cost[0] if cost else {}
    cost = dict(cost)
    return compiled, cost, round(t_lower, 2), round(t_compile, 2)


def _probe_cfg(cfg, periods: int):
    kw = {"num_layers": cfg.pattern_len * periods}
    if cfg.is_encdec:
        assert cfg.encoder_layers % cfg.num_periods == 0
        kw["encoder_layers"] = (cfg.encoder_layers // cfg.num_periods) * periods
    return dataclasses.replace(cfg, **kw)


def cost_probes(cfg, shape, mesh, num_microbatches: int, remat: bool = True,
                fsdp: bool = True, executor: str = "compiled",
                remat_policy: str = None):
    """Trip-count-corrected flops/bytes/collective-bytes via two unrolled
    probe compiles (see module docstring)."""
    n = num_microbatches if shape.kind == "train" else 1
    # probe one micro-batch of the planner's (ceil) size — ragged splits pad
    pshape = (dataclasses.replace(
        shape, global_batch=-(-shape.global_batch // num_microbatches))
        if shape.kind == "train" else shape)
    step_kw = ({"remat": remat, "remat_policy": remat_policy,
                "executor": executor}
               if shape.kind == "train" else {})
    probes = {}
    for P in (1, 2):
        bundle = steps.build_step(_probe_cfg(cfg, P), pshape,
                                  num_microbatches=1, scan_unroll=P, **step_kw)
        compiled, cost, tl, tc = _compile(bundle, mesh, fsdp=fsdp)
        probes[P] = {
            "flops": float(cost.get("flops", 0)),
            "bytes": float(cost.get("bytes accessed", 0)),
            "colls": collective_bytes(compiled.as_text()),
            "lower_s": tl, "compile_s": tc,
        }

    P_full = cfg.num_periods

    def extrap(x1, x2):
        return n * (x1 + (P_full - 1) * (x2 - x1))

    kinds = set(probes[1]["colls"]) | set(probes[2]["colls"])
    colls = {k: {
        "bytes": extrap(probes[1]["colls"].get(k, {}).get("bytes", 0),
                        probes[2]["colls"].get(k, {}).get("bytes", 0)),
        "count": extrap(probes[1]["colls"].get(k, {}).get("count", 0),
                        probes[2]["colls"].get(k, {}).get("count", 0)),
    } for k in kinds}
    return {
        "flops_per_device": extrap(probes[1]["flops"], probes[2]["flops"]),
        "bytes_per_device": extrap(probes[1]["bytes"], probes[2]["bytes"]),
        "collectives": colls,
        "collective_bytes_total": sum(d["bytes"] for d in colls.values()),
        "probe_raw": probes,
    }


def run_dryrun(arch: str, shape_name: str, *, multi_pod: bool = False,
               num_microbatches: int = 8, mesh=None, reduced: bool = False,
               probe: bool = True, verbose: bool = True, remat: bool = True,
               remat_policy: str = None, cfg_overrides: dict = None,
               fsdp: bool = True, executor: str = "compiled",
               budget_bytes: int = None, calibrate: str = "off",
               tuning_cache: str = None, check: bool = False,
               mesh_spec: str = None):
    cfg = configs.get_reduced(arch) if reduced else configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = configs.SHAPES[shape_name]
    if not configs.supports_shape(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long_500k requires sub-quadratic attention "
                          "(DESIGN.md §long_500k applicability)"}
    if mesh is None and mesh_spec:
        data, model = mesh_lib.parse_mesh_spec(mesh_spec)
        mesh = mesh_lib.make_host_mesh(data=data, model=model)
    mesh = mesh or mesh_lib.make_production_mesh(multi_pod=multi_pod)
    # an explicit DATA:MODEL spec with MODEL > 1 dry-runs the Layer-11
    # pipelined step (1F1B over the model axis) instead of the GSPMD path
    pipelined = (shape.kind == "train" and mesh_spec is not None
                 and mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS) > 1)
    plan = None
    pinned = None
    if shape.kind == "train":
        # resolve N_Smu through the same planner the step builder uses, so
        # probes/reporting match the compiled step even when the requested
        # count doesn't divide the global batch (<=0 = auto: micro-batch
        # size from the analytic memory model; --remat-policy auto lets
        # the planner pick the checkpoint grade jointly)
        pinned = (num_microbatches if num_microbatches is not None
                  and num_microbatches > 0 else None)
        plan = engine.plan_mbs(shape.global_batch, num_microbatches=pinned,
                               model_cfg=cfg, seq_len=shape.seq_len,
                               remat=remat, remat_policy=remat_policy,
                               mesh=mesh if pipelined else None,
                               pipeline=pipelined)
        num_microbatches = plan.num_micro_batches
        remat_policy = plan.remat_policy  # the chosen grade, for the report
    step_kw = {"remat": remat, "remat_policy": remat_policy,
               "executor": executor} \
        if shape.kind == "train" else {}
    if pipelined:
        step_kw["mesh"] = mesh
    bundle = steps.build_step(cfg, shape, num_microbatches=num_microbatches,
                              **step_kw)
    # multi-pod: extend FSDP over (pod, data) — optimizer-state-bound models
    # (grok-1) only fit per-chip HBM at the 512-chip shard
    compiled, cost, t_lower, t_compile = _compile(bundle, mesh,
                                                  fsdp_over_pod=multi_pod,
                                                  fsdp=fsdp,
                                                  pipelined=pipelined)
    mem = compiled.memory_analysis()
    colls_raw = collective_bytes(compiled.as_text())

    per_device = None
    grad_sync = None
    if shape.kind == "train":
        # engine Layer 6 report: what the mesh-aware planner would run on
        # this mesh (per-device budget, local micro, divisible global
        # micro) and how many all-reduce ops the compiled step actually
        # schedules (a scanned body appears ONCE in the HLO text — the
        # deferred-sync ShardedExecutor keeps the gradient all-reduce
        # outside the scan, so its count is 1 regardless of N_Sμ).
        from ..core import memory_model
        try:
            mesh_plan = engine.plan_mbs(
                shape.global_batch, num_microbatches=pinned,
                model_cfg=cfg, seq_len=shape.seq_len, remat=remat,
                remat_policy=remat_policy, mesh=mesh, pipeline=pipelined)
            est = memory_model.estimate(cfg, shape.seq_len, mesh=mesh,
                                        remat_policy=mesh_plan.remat_policy,
                                        pipeline=pipelined)
            per_device = {
                "data_parallel": mesh_plan.data_parallel,
                "local_micro": mesh_plan.local_micro,
                "micro_batch_global": mesh_plan.micro_batch_size,
                "budget_bytes": memory_model.V5E_HBM_BYTES,
                "analytic_bytes_at_local_micro":
                    est.total(mesh_plan.local_micro),
                "params_bytes": est.params_bytes,
                "activation_bytes_per_local_sample":
                    est.activation_bytes_per_sample,
            }
        except Exception as e:  # report must never sink the compile proof
            per_device = {"error": repr(e)}
        ar = colls_raw.get("all-reduce", {})
        grad_sync = {
            "allreduce_ops_in_hlo": ar.get("count", 0),
            "allreduce_bytes_in_hlo": ar.get("bytes", 0),
            "num_microbatches": num_microbatches,
        }

    pipeline_rep = None
    if pipelined:
        # Layer-11 report: per-stage footprint + the collective census the
        # 1F1B schedule implies. The ppermute count is the schedule's
        # boundary-active tick count (jaxpr-level contract — XLA may merge
        # adjacent collective-permutes in the HLO); the psum census is the
        # deferred-sync contract: ONE data-axis gradient all-reduce per
        # mini-batch + ONE (data, model) psum for shared grads/loss/metrics.
        from ..core import memory_model
        stages = mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS)
        M = plan.num_micro_batches
        fwd_tab, bwd_tab, _, ticks = engine.schedule_1f1b(stages, M)
        ppermutes = int((fwd_tab >= 0).any(axis=1).sum()
                        + (bwd_tab >= 0).any(axis=1).sum())
        try:
            est = memory_model.estimate(cfg, shape.seq_len, mesh=mesh,
                                        remat_policy=plan.remat_policy,
                                        pipeline=True)
            per_stage_bytes = {
                "params_bytes": est.params_bytes,
                "activation_bytes_per_sample":
                    est.activation_bytes_per_sample,
                "bytes_at_local_micro": est.total(plan.local_micro),
            }
        except Exception as e:  # report must never sink the compile proof
            per_stage_bytes = {"error": repr(e)}
        pipeline_rep = {
            "stages": stages,
            "data_parallel": plan.data_parallel,
            "periods_per_stage": cfg.num_periods // stages,
            "num_micro_batches": M,
            "ticks": int(ticks),
            "in_flight_micro_batches": min(stages, M),
            "per_stage": per_stage_bytes,
            "expected_collectives": {
                "ppermute": ppermutes,
                "psum_data_axis": 1,
                "psum_data_model_axis": 1,
            },
        }

    measured_peak = (getattr(mem, "argument_size_in_bytes", 0)
                     + getattr(mem, "output_size_in_bytes", 0)
                     + getattr(mem, "temp_size_in_bytes", 0)
                     - getattr(mem, "alias_size_in_bytes", 0))

    oracle = None
    if shape.kind == "train":
        # modeled vs measured vs corrected, side-by-side (no more diffing
        # two tools by hand): the analytic estimate of the per-device step
        # at the compiled local micro size, XLA's measured peak, and — when
        # a calibration entry exists (or --calibrate force just made one) —
        # the oracle-corrected bytes plus the admission delta it buys.
        from ..core import memory_model
        try:
            dp = mesh_lib.data_parallel_size(mesh)
            micro = -(-shape.global_batch // num_microbatches)
            local = max(1, micro // max(dp, 1))
            est = memory_model.estimate(cfg, shape.seq_len, mesh=mesh,
                                        remat_policy=remat_policy,
                                        act_bytes=4)
            modeled = est.total(local)
            oracle = {
                "local_micro": local,
                "modeled_bytes": modeled,
                "measured_bytes": measured_peak,
                "model_error_pct": (
                    round(100.0 * (modeled - measured_peak) / measured_peak, 2)
                    if measured_peak > 0 else None),
            }
            if calibrate != "off":
                from ..engine import autotune
                corr = autotune.planner_correction(
                    cfg, shape.seq_len, remat_policy=remat_policy,
                    mesh=None, optimizer="sgd", executor=executor,
                    mode=calibrate, cache_path=tuning_cache, act_bytes=4)
                if corr is not None:
                    budget = budget_bytes or memory_model.V5E_HBM_BYTES
                    analytic_admit = memory_model.suggest_micro_batch_size(
                        cfg, shape.seq_len, shape.global_batch,
                        budget_bytes=budget, remat_policy=remat_policy,
                        act_bytes=4) or 1
                    corrected_admit = autotune.corrected_micro_search(
                        cfg, shape.seq_len, shape.global_batch, budget, corr,
                        remat_policy=remat_policy, act_bytes=4) or 1
                    oracle.update({
                        "correction": list(corr),
                        "corrected_bytes": corr[0] * modeled + corr[1],
                        "admission": {
                            "budget_bytes": budget,
                            "analytic_micro": analytic_admit,
                            "calibrated_micro": corrected_admit,
                            "delta": corrected_admit - analytic_admit,
                        },
                    })
        except Exception as e:  # report must never sink the compile proof
            oracle = {"error": repr(e)}

    over_budget = (budget_bytes is not None
                   and measured_peak > budget_bytes)

    contract = None
    if check:
        # static contract gate over THIS run's artifacts (no re-lowering):
        # jaxpr contracts on the pre-GSPMD bundle fn, aliasing + memory
        # cross-check on the compiled step we just built
        from .. import analysis
        modeled = (oracle.get("modeled_bytes")
                   if isinstance(oracle, dict) else None)
        contract = analysis.check_bundle(
            bundle, compiled=compiled, modeled_bytes=modeled,
            devices=int(mesh.devices.size)).to_dict()

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": list(mesh.devices.shape), "axes": list(mesh.axis_names),
        "kind": bundle.kind, "num_devices": int(mesh.devices.size),
        "num_microbatches": num_microbatches if bundle.kind == "train" else None,
        "remat_policy": plan.remat_policy if plan is not None else None,
        "remat_policy_auto": plan.auto_policy if plan is not None else None,
        "per_device": per_device,
        "gradient_sync": grad_sync,
        "pipeline": pipeline_rep,
        "oracle": oracle,
        "budget": ({"budget_bytes": budget_bytes,
                    "measured_peak_bytes": measured_peak,
                    "over_budget": over_budget}
                   if budget_bytes is not None else None),
        "contract": contract,
        "raw_cost_analysis": {k: float(v) for k, v in cost.items()
                              if k in ("flops", "bytes accessed",
                                       "transcendentals", "optimal_seconds")},
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", -1),
            "peak_bytes_est": measured_peak,
        },
        "collectives_raw_once": colls_raw,
        "lower_s": t_lower, "compile_s": t_compile,
        "skipped": False,
    }
    if probe:
        result["corrected"] = cost_probes(cfg, shape, mesh, num_microbatches,
                                          remat=remat, fsdp=fsdp,
                                          executor=executor,
                                          remat_policy=remat_policy)
    if verbose:
        print(json.dumps(result))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--shape", required=True, choices=list(configs.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mesh", default=None, metavar="DATA:MODEL",
                    help="explicit host-mesh axis spec (e.g. '2:4'); "
                         "MODEL > 1 dry-runs the Layer-11 pipelined step "
                         "(1F1B over the model axis) and adds the "
                         "per-stage bytes + collective-census report "
                         "block (default: the production mesh)")
    ap.add_argument("--microbatches", type=int, default=8,
                    help="N_Smu for train shapes; 0 = auto micro-batch "
                         "size from the analytic memory model")
    ap.add_argument("--executor", choices=["compiled", "fused", "flat"],
                    default="compiled",
                    help="compiled scan vs Pallas fused-accumulate vs "
                         "fused flat-buffer update step")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--no-remat", action="store_true",
                    help="perf knob: disable per-period activation remat")
    ap.add_argument("--remat-policy",
                    choices=["auto", "none", "dots", "period", "full"],
                    default=None,
                    help="activation-checkpoint grade (overrides "
                         "--no-remat); auto = planner chooses jointly "
                         "with the micro-batch size")
    ap.add_argument("--no-fsdp", action="store_true",
                    help="perf knob: replicate params over the data axis "
                         "(kills per-micro-batch weight all-gathers; only "
                         "for models whose optimizer state fits)")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="perf knob: MoE capacity factor override")
    ap.add_argument("--budget", type=float, default=None, metavar="GB",
                    help="per-device HBM budget in GB; exits non-zero when "
                         "the MEASURED peak (memory_analysis) exceeds it")
    ap.add_argument("--calibrate", choices=["off", "auto", "force"],
                    default="off",
                    help="oracle block in the report: auto = use a cached "
                         "memory correction when one exists; force = run "
                         "the probe compiles now and persist the fit")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="tuning-cache JSON path (default: "
                         "$REPRO_TUNING_CACHE or ~/.cache/repro-tuning/)")
    ap.add_argument("--check", action="store_true",
                    help="run the static contract checks "
                         "(repro.analysis.check_bundle) over this run's "
                         "traced/compiled step; findings exit 3")
    ap.add_argument("--json", action="store_true",
                    help="print the full JSON report to stdout (also when "
                         "--out is set)")
    ap.add_argument("--out", default=None, help="directory for JSON artifact")
    args = ap.parse_args()

    overrides = {}
    if args.capacity_factor is not None:
        overrides["capacity_factor"] = args.capacity_factor
    budget_bytes = (int(args.budget * 1024 ** 3)
                    if args.budget is not None else None)
    res = run_dryrun(args.arch, args.shape, multi_pod=args.multi_pod,
                     num_microbatches=args.microbatches, reduced=args.reduced,
                     probe=not args.no_probe,
                     verbose=args.out is None or args.json,
                     remat=not args.no_remat,
                     remat_policy=args.remat_policy,
                     cfg_overrides=overrides or None,
                     fsdp=not args.no_fsdp, executor=args.executor,
                     budget_bytes=budget_bytes, calibrate=args.calibrate,
                     tuning_cache=args.tuning_cache, check=args.check,
                     mesh_spec=args.mesh)
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        tag = "multi" if args.multi_pod else "single"
        path = os.path.join(args.out, f"{args.arch}__{args.shape}__{tag}.json")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(f"wrote {path}")

    # repo-wide exit-code contract (shared with ``python -m repro.analysis``,
    # see analysis/findings.py): 0 ok / 1 tool error / 2 budget / 3 contract
    exit_code = EXIT_OK
    b = res.get("budget") if isinstance(res, dict) else None
    if b and b["over_budget"]:
        print(f"BUDGET EXCEEDED: measured peak "
              f"{b['measured_peak_bytes'] / 1024 ** 3:.2f} GiB > budget "
              f"{b['budget_bytes'] / 1024 ** 3:.2f} GiB "
              f"({args.arch} / {args.shape}) — raise --budget, add model "
              f"parallelism, or shrink the micro-batch", file=sys.stderr)
        exit_code = EXIT_BUDGET
    contract = res.get("contract") if isinstance(res, dict) else None
    if contract and contract.get("findings"):
        for f in contract["findings"]:
            print(f"CONTRACT: [{f.get('rule')}] {f.get('message')}",
                  file=sys.stderr)
        if exit_code == EXIT_OK:
            exit_code = EXIT_CONTRACT
    sys.exit(exit_code)


if __name__ == "__main__":
    main()
