"""One benchmark run of one cell: the launcher's training path on the chip,
timed, optionally traced, and checked against the plain reference.

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
file and a traffic file; the configuration names its reference module
under ``bench/reference``. Nothing here is specific to one cell.

Set-up: ``train.parse_args`` -> ``train.setup`` -> ``run.build(run.plan)``
-> ``engine.Trainer``, with the launcher's default executor. Weights and
data come from ``--seed``: the weights from the reference module's
``init``, made on the device in one jitted call with the shardings
``setup`` chose; the data from ``generator.Tokens``, handed to the
program's input pipeline as its dataset. The trainer runs the first three
steps (the first compiles) and the harness reads, from the program's own
state, the losses of the first two, the first step's gradient as the
optimizer got it (from the momentum after step 1) and the parameters'
change after step 2. Two steps and not three keep the reference's time
near the window's.

Window: one ``Trainer.fit`` over as many further steps as fill
``--seconds`` at the step time seen in set-up, timed from the first
dispatch to the end of the last step. The harness adds spans (``input``,
``dispatch``, ``readback``, ``window``) and no host synchronization. No
program may compile inside the window.

After it: the device memory peak is read, the program's state is freed,
and the reference runs the same two steps in float32 on the cell's chips;
``correct.gaps`` compares the two.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import glob
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
SETUP_STEPS = 3  # run in set-up; the first compiles
REF_STEPS = 2  # of them compared with the reference

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"
EVENTS = collections.Counter()


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _json(path):
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    ref: object  # the configuration's reference module
    end_to_end: list
    per_layer: list
    root: str = ROOT


def load_cell(name: str, root: str = ROOT) -> Cell:
    man = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    cfg = _json(os.path.join(root, conf["file"]))
    traffic = _json(os.path.join(root, "bench", "traffic", w["traffic"] + ".json"))
    e2e = [m for m in man["end_to_end"] if applies(m, name)]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"]
                 if applies(m, name) and m["moves"] in moves]
    ref = importlib.import_module("bench.reference." + cfg["reference"])
    return Cell(name, int(w["chips"]), cfg, traffic, ref, e2e, per_layer, root)


def devices_for(chips: int, allow_cpu: bool = False):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def peak_of(device, root: str = ROOT) -> dict:
    if device.platform != "tpu":
        raise NoChip(f"no peak for platform {device.platform!r}: TPU only")
    kinds = _json(os.path.join(root, "bench", "peaks.json"))["kinds"]
    if device.device_kind not in kinds:
        raise KeyError(f"device kind {device.device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(kinds)})")
    return kinds[device.device_kind]


def enable_cache():
    """The checkout's compile cache (``repro.launch.compile_cache``), every
    program kept, so that only a cell's first run compiles."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def count_compiles():
    import jax
    if not EVENTS.get("_listening"):
        EVENTS["_listening"] = 1
        jax.monitoring.register_event_listener(_count)
        jax.monitoring.register_event_duration_secs_listener(_count)


def _count(event, *_, **__):
    EVENTS[event] += 1


def _process_start_wall() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


PROCESS_START = _process_start_wall()


class Program:
    """The system under test for one cell, built once and driven from any
    number of seeds."""

    def __init__(self, cell: Cell):
        import jax
        from repro import configs, engine
        from repro.launch import train
        from .reference.common import leaf_norms
        self.cell, self.jax, self.engine = cell, jax, engine
        cfg, t = cell.cfg, cell.traffic
        base = configs.get(cfg["registry_arch"])
        fields = cell.ref.program_fields(cfg)
        self.model_cfg = dataclasses.replace(base, **fields)
        self.changed = {k: (getattr(base, k), v) for k, v in fields.items()
                        if getattr(base, k) != v}
        opt = t["optimizer"]
        self.args = train.parse_args([
            "--arch", cfg["registry_arch"], "--mesh", t["mesh"],
            "--dtype", cfg["precision"]["compute"], "--seq", str(t["seq"]),
            "--mini-batch", str(t["mini_batch"]),
            "--microbatches", str(t["num_microbatches"]),
            "--remat-policy", t["remat"], "--lr", repr(float(opt["lr"]))])
        self.run = run = train.setup(self.model_cfg, self.args)
        fs = run.opt.fused
        if (fs is None or fs.kind != opt["kind"] or fs.nesterov
                or fs.momentum != opt["momentum"]
                or fs.weight_decay != opt["weight_decay"]):
            raise ValueError(f"the launcher's optimizer {fs} is not the "
                             f"traffic's {opt}")
        want = cell.ref.param_shapes(cfg)
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           run.params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the program's parameter layout differs from "
                             f"the reference's ({cfg['reference']})")
        pshard = jax.tree.map(lambda a: a.sharding, run.params)
        oshard = jax.tree.map(lambda a: a.sharding, run.opt_state)
        _free((run.params, run.opt_state))
        run.params = run.opt_state = None
        self.devices = list(run.mesh.devices.flat)
        self._init = jax.jit(functools.partial(cell.ref.init, cfg),
                             out_shardings=pshard)
        self._opt_init = jax.jit(run.opt.init, out_shardings=oshard)
        lr, wd = float(opt["lr"]), float(opt["weight_decay"])
        self._grad = jax.jit(lambda p, o: leaf_norms(jax.tree.map(
            lambda m, q: m * (1.0 - lr * wd) - wd * q, o["mom"], p)))
        self._update = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, cell.ref.init(cfg, k))))
        self.step_fn, self.pipeline = run.build(run.plan)
        self.first_dispatch = None
        self.logged = []
        self._spans()
        self.trainer = engine.Trainer(
            self._timed_step, self.pipeline, log_every=self.args.log_every,
            state_shardings=run.state_shardings, log_fn=self._log)
        self.trainer._readback = self._readback

    # -- spans (host, on the profiler's clock when tracing) -------------------

    def _spans(self):
        batches = self.pipeline.batches
        ann = self.jax.profiler.TraceAnnotation

        def annotated(num_batches, start=0):
            it = batches(num_batches, start=start)
            while True:
                with ann("input"):
                    try:
                        b = next(it)
                    except StopIteration:
                        return
                yield b

        self.pipeline.batches = annotated

    def _timed_step(self, params, opt_state, batch):
        if self.first_dispatch is None:
            self.first_dispatch = time.perf_counter()
            self.first_dispatch_wall = time.time()
        with self.jax.profiler.TraceAnnotation("dispatch"):
            return self.step_fn(params, opt_state, batch)

    def _readback(self, metrics):
        with self.jax.profiler.TraceAnnotation("readback"):
            return self.engine.Trainer._readback(metrics)

    def _log(self, step, metrics, elapsed):
        self.logged.append((step, metrics.get("loss", math.nan)))

    # -- driving ------------------------------------------------------------

    @property
    def tokens_per_step(self):
        t = self.cell.traffic
        return t["mini_batch"] * t["seq"]

    def state(self, seed: int):
        from .generator import Tokens, weight_key_data
        key = self.jax.random.PRNGKey(weight_key_data(seed))
        self.pipeline.dataset = Tokens(self.cell.traffic,
                                       self.model_cfg.vocab_size, seed)
        params = self._init(key)
        return params, self._opt_init(params), key

    def first_steps(self, seed: int):
        """The set-up steps through the window's own trainer, feed and
        step. Returns (params, opt_state, readings, step seconds)."""
        params, opt, key = self.state(seed)
        fit = self.trainer.fit
        p, o, m = fit(params, opt, 1)
        losses = [m["loss"]]
        grad = _host(self._grad(p, o))
        times = []
        for step in range(1, SETUP_STEPS):
            t0 = time.perf_counter()
            p, o, m = fit(p, o, step + 1, start_step=step)
            times.append(time.perf_counter() - t0)
            if step < REF_STEPS:
                losses.append(m["loss"])
            if step + 1 == REF_STEPS:
                update = _host(self._update(p, key))
        return p, o, {"loss": losses, "grad": grad, "update": update}, min(times)

    def window(self, p, o, seconds: float, step_s: float, trace_dir=None):
        """Fill ``seconds`` with steps; returns (params, opt_state, steps,
        window seconds, compiles inside it)."""
        jax = self.jax
        n = max(1, math.ceil(seconds / step_s))
        self.first_dispatch = None
        self.logged = []
        compiles = EVENTS[COMPILE_EVENT]
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"):
                p, o, _ = self.trainer.fit(p, o, SETUP_STEPS + n,
                                           start_step=SETUP_STEPS)
                jax.block_until_ready((p, o))
            end = time.perf_counter()
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        return p, o, n, end - self.first_dispatch, EVENTS[COMPILE_EVENT] - compiles

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        return int(max(peaks))


def _host(d):
    import jax
    return {k: float(v) for k, v in jax.device_get(d).items()}


def _free(tree):
    import jax
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


def reference_readings(cell: Cell, seed: int, devices, mode: str = "fp32",
                       rows=None):
    import jax
    from .generator import Tokens, weight_key_data
    from .reference.common import Job
    t = cell.traffic
    tokens = Tokens(t, cell.ref.dims(cell.cfg)["V"], seed)
    batches = [tokens.batch(t["mini_batch"], i) for i in range(REF_STEPS)]
    if len(rows if rows is not None else range(t["mini_batch"])) % len(devices):
        devices = devices[:1]  # the sequences do not split evenly
    job = Job(cell.ref, cell.cfg, t, devices, mode)
    return job.readings(jax.random.PRNGKey(weight_key_data(seed)), batches,
                        rows=rows)


def _metric_reader(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("bench_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        allow_cpu: bool = False, limits: dict = None) -> dict:
    """One run; returns the result object (the last line of stdout)."""
    import jax
    from . import correct
    from . import trace as trace_lib
    devices = devices_for(cell.chips, allow_cpu)
    peak = None if allow_cpu else peak_of(devices[0], cell.root)
    if limits is None:
        limits = correct.load_limits(cell.root, cell.name)
    cache = None if allow_cpu else enable_cache()
    count_compiles()
    prog = Program(cell)
    log(f"[{cell.name}] {devices[0].platform} {devices[0].device_kind} x "
        f"{len(jax.devices())}; cache {cache}; {prog.run.plan.describe()}; "
        f"registry fields changed: {prog.changed}")
    p, o, got, step_s = prog.first_steps(seed)
    log(f"set-up steps: losses {got['loss']}, step {step_s:.4f} s; "
        f"compiles {EVENTS[COMPILE_EVENT]}, cache hits {EVENTS[CACHE_HITS]}, "
        f"misses {EVENTS[CACHE_MISSES]}")
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        p, o, steps, window_s, compiles = prog.window(p, o, seconds, step_s, tdir)
        setup_s = prog.first_dispatch_wall - PROCESS_START
        if compiles:
            raise RuntimeError(f"{compiles} compiles inside the window")
        tokens_per_s = steps * prog.tokens_per_step / window_s
        failed = sum(1 for _, l in prog.logged if not math.isfinite(l))
        mem = prog.memory_peak()
        stats = prog.pipeline.stats
        _free((p, o))
        del p, o
        reduced = None
        if trace:
            xplanes = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                                recursive=True)
            t0 = time.perf_counter()
            reduced = trace_lib.reduce(trace_lib.from_xplane(xplanes[0]))
            log(f"trace: {os.path.getsize(xplanes[0])} bytes read and "
                f"reduced in {time.perf_counter() - t0:.1f} s")
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    log(f"window: {steps} steps in {window_s:.4f} s = {tokens_per_s:.1f} "
        f"tokens/s; set-up {setup_s:.3f} s; input wait {stats.wait_s:.4f} "
        f"of {stats.elapsed_s:.4f} s; memory peak {mem}")
    del prog
    gc.collect()
    ref = reference_readings(cell, seed, devices)
    g = correct.gaps(got, ref)
    ok, checks = correct.judge(g, limits)
    log(f"program losses {got['loss']}; reference {ref['loss']}; worst "
        f"leaves {g['leaf']}; left out of update_gap: {g['left_out']}")
    ctx = SimpleNamespace(
        cell=cell.name, chips=cell.chips, tokens_per_s=tokens_per_s,
        window_s=window_s, setup_s=setup_s, pipeline_stats=stats, peak=peak,
        flops_per_token=cell.ref.flops_per_token(cell.cfg, cell.traffic["seq"]),
        trace=reduced)
    values = {"tokens_per_s": tokens_per_s, "setup_s": setup_s}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = (_metric_reader(cell.root, m["name"])(ctx) if trace
             else values[m["name"]])
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": ok, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        log(f"trace: {json.dumps(reduced)}")
    result["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                        for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    return result
