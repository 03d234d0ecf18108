"""Memory-planned serving launcher: continuous batching under a synthetic
heavy-traffic stream (engine Layer 10).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --reduced \
      --budget 0.5 --requests 32 --rate 50 --prompt-lens 16,48,96 \
      --new-tokens 8,32 --temperature 0.7

``--budget`` (GiB per device) drives ``engine.plan_serve``: the KV-cache
admission bound (concurrent decode slots) and the prefill micro-batch size
come from ``core/memory_model.serve_estimate``, not from a hand-picked
batch. Prefill latency and steady-state decode throughput are reported
SEPARATELY, after a warmup pass compiles both jits — the old launcher
started its clock before the compiles and counted the prefill-produced
token as decoded, overstating tok/s on both ends.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from .. import configs
from ..core.streaming import prefetch_iterator
from ..engine import serving
from ..models import transformer
from . import compile_cache, mesh as mesh_lib


def _int_list(s: str):
    return tuple(int(x) for x in s.split(",") if x)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--budget", type=float, default=0.5,
                    help="per-device HBM budget in GiB the serve plan is "
                         "admitted against")
    ap.add_argument("--max-len", type=int, default=128,
                    help="context capacity per slot (prompt + generated)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=50.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--prompt-lens", type=_int_list, default=(16, 48, 96),
                    help="comma-separated prompt-length mix")
    ap.add_argument("--new-tokens", type=_int_list, default=(8, 32),
                    help="comma-separated output-budget mix")
    ap.add_argument("--slots", type=int, default=None,
                    help="pin the decode-slot count (default: memory model)")
    ap.add_argument("--prefill-micro", type=int, default=None,
                    help="pin the prefill micro-batch (default: memory model)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy, >0 = temperature sampling")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-donate", action="store_true",
                    help="do not donate the KV pool at the decode jit "
                         "boundary (keeps it readable across calls; costs a "
                         "second full cache copy — see analysis SRV001)")
    ap.add_argument("--dtype", choices=["float32", "bfloat16"],
                    default="float32")
    ap.add_argument("--json", default=None,
                    help="also write the full report to this path")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = configs.get_reduced(args.arch) if args.reduced else configs.get(args.arch)
    try:
        serving.check_servable(cfg)
    except ValueError as e:  # per-family message instead of a shape error
        raise SystemExit(str(e))
    if max(args.prompt_lens) >= args.max_len:
        raise SystemExit(f"largest prompt length {max(args.prompt_lens)} "
                         f"leaves no room to generate at --max-len "
                         f"{args.max_len}")
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    budget = int(args.budget * 2**30)
    mesh = mesh_lib.make_host_mesh(data=len(jax.devices()), model=1)

    with jax.set_mesh(mesh):
        plan = serving.plan_serve(
            cfg, budget_bytes=budget, max_len=args.max_len,
            max_slots=args.slots, prefill_micro=args.prefill_micro,
            mesh=mesh, cache_bytes=2 if args.dtype == "bfloat16" else 4)
        print(plan.describe())
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        engine = serving.ServingEngine(
            params, cfg, plan, dtype=dtype, temperature=args.temperature,
            seed=args.seed, donate=not args.no_donate)
        # Poisson stream, staged through the core prefetcher so prompt
        # synthesis overlaps the serve loop
        stream = prefetch_iterator(
            serving.synthetic_traffic(
                args.requests, rate_rps=args.rate,
                prompt_lens=args.prompt_lens, new_tokens=args.new_tokens,
                vocab_size=cfg.vocab_size, seed=args.seed + 1),
            size=8)
        seen = []

        def tee(it):
            for r in it:
                seen.append(r)
                yield r

        engine.run(tee(stream), warmup_prompt_lens=args.prompt_lens)
        rep = engine.finished_report(seen)

    pf, dec = rep["prefill"], rep["decode"]
    print(f"{cfg.name}: {rep['requests']['finished']}/{len(seen)} requests "
          f"finished (warmup/compile {rep['warmup_s']:.2f}s, excluded)")
    print(f"  prefill: {pf['batches']} micro-batches, "
          f"{pf['prompt_tokens']} prompt tokens, latency "
          f"p50 {pf['latency_s']['p50'] * 1e3:.1f}ms "
          f"max {pf['latency_s']['max'] * 1e3:.1f}ms")
    print(f"  decode (steady-state): {dec['tokens']} tokens in "
          f"{dec['time_s']:.2f}s = {dec['tokens_per_s']:.1f} tok/s over "
          f"{dec['steps']} steps (decode-issued only)")
    print(f"  ITL p50 {dec['itl_s']['p50'] * 1e3:.1f}ms "
          f"p99 {dec['itl_s']['p99'] * 1e3:.1f}ms | "
          f"TTFT p50 {rep['ttft_s']['p50'] * 1e3:.1f}ms "
          f"p99 {rep['ttft_s']['p99'] * 1e3:.1f}ms")
    print(f"  slots: {rep['slots']['max_concurrent']} peak of "
          f"{rep['slots']['planned']} planned "
          f"(mean active {rep['slots']['mean_active_per_step']:.1f})")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"arch": cfg.name, "plan": plan.describe(),
                       "report": rep}, f, indent=2)
        print(f"wrote {args.json}")
    return rep


if __name__ == "__main__":
    main()
