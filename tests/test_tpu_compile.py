"""Compiles for one TPU v5e chip, described rather than attached.

The kernels of the training path and the flat executor's whole train step
are compiled by the chip's own compiler at qwen2-1.5b's published widths,
with ``interpret=False``, the benchmark's MBP steps (qwen2-1.5b at 8
layers, mamba2-780m at all 48) and one mamba2-780m layer's SSD scan, whose
bytes accessed are bounded. Interpret mode (what every other test runs)
cannot see what this compiler refuses: tiling, fast-memory limits and
programs that do not fit the device. Nothing runs, so these tests say
nothing about results or times. The gradient-sync census (HLO004) is also
read off this compiler's HLO for the tiny test model on a 4:1 mesh.

The memory guards hold two different figures. The qwen2 MBP steps hold
``hlo_checks.measured_peak_bytes`` (arguments + outputs + temporaries -
aliases, here arguments + temporaries) under the chip's bytes limit: the
most their buffers can take, a conservative guard. The mamba2 step holds
the compiler's own ``peak_memory_in_bytes`` under it: while the period
scan's backward wrote each micro-batch's stacked gradient into a buffer of
its own, its arguments + temporaries read 16.85 GiB against the limit's
15.75 GiB, and a v5e chip compiled that step to the same figures
(arguments 6,241,649,664 B, temporaries 11,848,963,584 B, peak
14,507,909,632 B) and ran it, so arguments + temporaries was no bound the
chip holds to. Both MBP steps now add the stack's gradient into the
accumulator inside the backward, and each is held to at most the
arguments + temporaries it had before.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from conftest import (make_sharded_executor, tiny_batch, tiny_loss_fn,
                      tiny_optimizer, tiny_params)
from repro import configs, engine
from repro.analysis import hlo_checks
from repro.kernels import fused_update, grad_accum
from repro.launch import train
from repro.models import ssm, transformer

V5E_HBM = 16 * 1024 ** 3
# ``memory_stats()["bytes_limit"]`` of one TPU v5 lite chip, read on the
# chip (the described topology reports none)
V5E_BYTES_LIMIT = 16_909_336_064
RAGGED = 1_000_003  # not a multiple of any launch block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """``devices[0]`` of the described v5e, with the persistent compile
    cache off: an executable for a chip that is not attached can be
    written to the cache but not read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _qwen_layer_cfg():
    return dataclasses.replace(configs.get("qwen2-1.5b"), num_layers=1)


def _layer_elements() -> int:
    """One qwen2-1.5b layer's flat bucket (46.8M fp32 elements)."""
    p = jax.eval_shape(lambda k: transformer.init_params(_qwen_layer_cfg(), k),
                       jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(p["blocks"]))


def _size(which: str) -> int:
    return _layer_elements() if which == "layer" else RAGGED


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_on_chip(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert hlo_checks.measured_peak_bytes(compiled) < V5E_HBM


def test_layer_bucket_size():
    assert 46_700_000 < _layer_elements() < 46_900_000


@pytest.mark.parametrize("which", ["layer", "ragged"])
@pytest.mark.parametrize("grad_dtype", [jnp.float32, jnp.bfloat16])
def test_grad_accum_compiles(one_chip, which, grad_dtype):
    n = _size(which)
    acc = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((n,), grad_dtype, sharding=one_chip)
    _assert_on_chip(_compile(
        lambda a, b: grad_accum(a, b, 0.125, interpret=False),
        acc, g))


@pytest.mark.parametrize("which", ["layer", "ragged"])
def test_fused_sgd_momentum_compiles(one_chip, which):
    s = jax.ShapeDtypeStruct((_size(which),), jnp.float32, sharding=one_chip)
    _assert_on_chip(_compile(
        lambda p, g, m: fused_update.fused_sgd(
            p, g, m, 0.05, momentum=0.9, weight_decay=5e-4,
            interpret=False),
        s, s, s))


@pytest.mark.parametrize("which", ["layer", "ragged"])
def test_fused_adam_compiles(one_chip, which):
    s = jax.ShapeDtypeStruct((_size(which),), jnp.float32, sharding=one_chip)
    _assert_on_chip(_compile(
        lambda p, g, m, v: fused_update.fused_adam(
            p, g, m, v, 1e-3, 0.1, 0.001, interpret=False),
        s, s, s, s))


def test_flat_train_step_compiles(one_chip):
    """The flat executor's whole step (the launcher's build path) for one
    qwen2-1.5b layer at published widths, seq 2048, micro-batch 1."""
    cfg = _qwen_layer_cfg()
    args = train.parse_args([
        "--arch", "qwen2-1.5b", "--executor", "flat", "--dtype", "bfloat16",
        "--seq", "2048", "--mini-batch", "2", "--microbatches", "2",
        "--remat-policy", "period", "--calibrate", "off"])
    opt = train.default_optimizer(args)
    plan = train.build_plan(cfg, args, optimizer=opt)
    executor, _ = train.build_executor(cfg, plan, args, optimizer=opt,
                                       interpret=False)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    n, micro = plan.num_micro_batches, plan.micro_batch_size
    batch = {"tokens": jax.ShapeDtypeStruct((n, micro, 2048), jnp.int32),
             "labels": jax.ShapeDtypeStruct((n, micro, 2048), jnp.int32),
             "sample_weight": jax.ShapeDtypeStruct((n, micro), jnp.float32)}
    params, opt_state, batch = (jax.tree.map(on_chip, t)
                                for t in (params, opt_state, batch))
    compiled = executor.lower_step(params, opt_state, batch).compile()
    _assert_on_chip(compiled)


def _top_level_arrays(hlo: str):
    """``(dtype, elements, op)`` of every array an instruction of the
    optimized HLO materializes: fusion bodies and aliasing ops (bitcast,
    get-tuple-element, tuple, parameter) are left out."""
    bodies = set(re.findall(r" fusion\(.*calls=%([\w.\-]+)", hlo))
    out, skip = [], False
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            skip = head.group(1) in bodies
            continue
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.+?) ([\w\-]+)\(", line)
        if skip or not m or m.group(2) in (
                "bitcast", "get-tuple-element", "tuple", "parameter"):
            continue
        for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", m.group(1)):
            out.append((dtype, math.prod(int(d) for d in dims.split(",") if d),
                        m.group(2)))
    return out


_COMPILED = {}


def _compile_mbp_step(topo, one_chip, cfg, arch, seq, n_micro):
    """The launcher's MBP step for ``cfg`` (a mini-batch of ``n_micro``
    sequences as ``n_micro`` micro-batches of 1, remat ``period``, bf16
    compute) compiled for one described v5e chip, once per module."""
    key = (cfg, seq, n_micro)
    if key not in _COMPILED:
        _COMPILED[key] = _compile_mbp_step_uncached(topo, one_chip, cfg,
                                                    arch, seq, n_micro)
    return _COMPILED[key]


def _compile_mbp_step_uncached(topo, one_chip, cfg, arch, seq, n_micro):
    args = train.parse_args([
        "--arch", arch, "--dtype", "bfloat16", "--seq", str(seq),
        "--mini-batch", str(n_micro), "--microbatches", str(n_micro),
        "--remat-policy", "period", "--calibrate", "off"])
    opt = train.default_optimizer(args)
    plan = train.build_plan(cfg, args, optimizer=opt)
    executor, _ = train.build_executor(cfg, plan, args, optimizer=opt,
                                       interpret=False)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt_state = jax.eval_shape(opt.init, params)
    n, micro = plan.num_micro_batches, plan.micro_batch_size
    assert (n, micro) == (n_micro, 1)
    batch = {"tokens": jax.ShapeDtypeStruct((n, micro, seq), jnp.int32),
             "labels": jax.ShapeDtypeStruct((n, micro, seq), jnp.int32),
             "sample_weight": jax.ShapeDtypeStruct((n, micro), jnp.float32)}
    params, opt_state, batch = (jax.tree.map(on_chip, t)
                                for t in (params, opt_state, batch))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    jitted = jax.jit(executor.make_train_step(), donate_argnums=(0, 1, 2))
    with jax.set_mesh(mesh):
        return jitted.lower(params, opt_state, batch).compile()


def _qwen2_8l():
    return dataclasses.replace(configs.get("qwen2-1.5b"), num_layers=8)


def _mamba2():
    """mamba2-780m with its vocabulary padded as the benchmark runs it."""
    return dataclasses.replace(configs.get("mamba2-780m"), vocab_size=50_288)


@pytest.mark.parametrize("seq", [2048, 4096])
def test_mbp_step_head_keeps_one_fp32_logits(topo, one_chip, capsys, seq):
    """The ``qwen2-1.5b-8l`` MBP step (8 micro-batches of 1, remat
    ``period``) as the launcher builds it. Its head is one custom-VJP op:
    the optimized program holds one fp32 buffer of the seq x 151,936
    logits (the forward dot's output) and no scatter into one, and the
    compiler's arguments + temporaries stay under the chip's bytes limit
    at seq 4096 too. That figure is a memory guard, not the chip's own
    test, which may admit a program somewhat above it."""
    cfg = _qwen2_8l()
    compiled = _compile_mbp_step(topo, one_chip, cfg, "qwen2-1.5b", seq, 8)
    hlo = compiled.as_text()
    logits = seq * cfg.vocab_size
    arrays = _top_level_arrays(hlo)
    assert [a for a in arrays if a[:2] == ("f32", logits)] == [
        ("f32", logits, "fusion")]
    scattered = re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(", hlo)
    assert logits not in [math.prod(int(d) for d in dims.split(","))
                          for dims in scattered]
    temp = compiled.memory_analysis().temp_size_in_bytes
    peak = hlo_checks.measured_peak_bytes(compiled)
    with capsys.disabled():
        print(f"\nqwen2-1.5b-8l MBP step at seq {seq} for v5e: temp "
              f"{temp / 2 ** 30:.3f} GiB, peak {peak / 2 ** 30:.3f} GiB")
    assert peak < V5E_BYTES_LIMIT


def test_mamba2_mbp_step_fits(topo, one_chip, capsys):
    """mamba2-780m at all 48 published layers (vocabulary 50,277 padded to
    50,288, as the benchmark runs it): seq 2048, mini-batch 16 as 16
    micro-batches of 1, remat ``period``. The compiler's own peak of the
    step (``peak_memory_in_bytes``: fp32 parameters and momentum, the fp32
    accumulator, the fp32 residual stream's period checkpoints, the
    activations) stays under the chip's bytes
    limit. Arguments + temporaries is printed beside it and not asserted
    here (see the module's docstring)."""
    compiled = _compile_mbp_step(topo, one_chip, _mamba2(), "mamba2-780m",
                                 2048, 16)
    mem = compiled.memory_analysis()
    gib = 2 ** 30
    with capsys.disabled():
        print(f"\nmamba2-780m MBP 16 x 1 step at seq 2048 for v5e: args "
              f"{mem.argument_size_in_bytes / gib:.3f} GiB, temp "
              f"{mem.temp_size_in_bytes / gib:.3f} GiB, compiler's peak "
              f"{mem.peak_memory_in_bytes / gib:.3f} GiB")
    assert mem.argument_size_in_bytes < mem.peak_memory_in_bytes
    assert mem.peak_memory_in_bytes < V5E_BYTES_LIMIT


# the compiler's peak of the mamba2 MBP step for v5e while the SSD scan
# passed its chunk states through an associative scan
MAMBA2_MBP_PEAK_BYTES = 12_168_251_904


def test_ssd_scan_bytes_for_v5e(topo, one_chip, capsys):
    """One mamba2-780m layer's ``ssd_chunked`` (seq 2048, 48 heads of 64,
    state 128, chunks of 256; x, B and C in bf16 as the block hands them
    over), forward and backward as the gradient of ``sum(y)``. The chunk
    states cross the chunks in one masked chunks x chunks contraction,
    which reads each state once: the compiler's ``bytes accessed`` stays
    at most 6.0e8, where the associative scan's log-depth tree of pads,
    slices and (P, N) reductions read 1.03e9. Passing the state so takes
    no more memory: the mamba2 MBP step's peak is no higher than it was
    with the scan."""
    cfg = _mamba2()
    H, P, N = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state
    S = 2048

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, dt, A, Bm, Cm):
        y, _ = ssm.ssd_chunked(x, dt, A, Bm, Cm, cfg.ssm_chunk)
        return jnp.sum(y.astype(jnp.float32))

    compiled = _compile(
        jax.grad(loss, argnums=tuple(range(5))),
        spec((1, S, H, P), jnp.bfloat16), spec((1, S, H), jnp.float32),
        spec((H,), jnp.float32), spec((1, S, N), jnp.bfloat16),
        spec((1, S, N), jnp.bfloat16))
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    peak = _compile_mbp_step(topo, one_chip, cfg, "mamba2-780m", 2048,
                             16).memory_analysis().peak_memory_in_bytes
    with capsys.disabled():
        print(f"\nmamba2-780m ssd_chunked forward + backward for v5e: "
              f"{cost['bytes accessed']:.4g} bytes accessed, "
              f"{cost['flops']:.4g} flops; MBP step peak {peak} B")
    assert cost["bytes accessed"] <= 6.0e8
    assert peak <= MAMBA2_MBP_PEAK_BYTES


# arguments + temporaries of the two MBP steps compiled for v5e while the
# period scan's backward wrote each micro-batch's stacked fp32 gradient
# into a buffer of its own, for ``exec_core.accumulate`` to add
PLAIN_ACCUMULATE_BYTES = {"qwen2-1.5b": 4_862_198_784 + 8_945_074_176,
                          "mamba2-780m": 6_241_649_664 + 11_848_963_584}
_WORDS = re.compile(r"[/()]")


def _stacked_accumulate_ops(hlo: str, shapes):
    """``(opcode, dims)`` of each instruction, fusion bodies included, that
    produces an f32 array of one of ``shapes`` under the ``accumulate``
    scope, or copies one."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = f32\[([\d,]*)\]\S* ([\w\-]+)\(",
                     line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        name = re.search(r'op_name="([^"]*)"', line)
        words = set(_WORDS.split(name.group(1))) if name else set()
        if dims in shapes and (m.group(2) == "copy" or "accumulate" in words):
            out.append((m.group(2), dims))
    return out


def _dot_fused_accumulates(hlo: str):
    """Dims of each fused computation whose root updates an f32 slice in
    place from a dot (or convolution) and an add: the weight gradient added
    into the accumulator in the dot's own output fusion."""
    out = set()
    for body in re.split(r"\n(?=(?:ENTRY )?%\S+ \()", hlo):
        root = re.search(
            r"ROOT %\S+ = f32\[([\d,]*)\]\S* dynamic-update-slice\(", body)
        if (root and re.search(r" (convolution|dot)\(", body)
                and re.search(r"= f32\[[\d,]*\]\S* add\(", body)):
            out.add(tuple(int(d) for d in root.group(1).split(",")))
    return out


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-780m"])
def test_mbp_step_accumulates_the_trunk_in_place(topo, one_chip, capsys,
                                                 arch):
    """The benchmark's MBP steps (qwen2-1.5b at 8 layers as 8 x 1,
    mamba2-780m as 16 x 1) add the layer stack's gradient into the fp32
    accumulator inside the backward: no op under the ``accumulate`` scope
    makes an array of a stacked block parameter's shape, none copies one,
    each stacked weight matrix is updated in place by its weight-gradient
    dot's fusion, and arguments + temporaries are no more than the plain
    accumulate's."""
    cfg = _qwen2_8l() if arch == "qwen2-1.5b" else _mamba2()
    n_micro = 8 if arch == "qwen2-1.5b" else 16
    compiled = _compile_mbp_step(topo, one_chip, cfg, arch, 2048, n_micro)
    hlo = compiled.as_text()
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    shapes = {tuple(p.shape) for p in jax.tree.leaves(params["blocks"])}
    assert _stacked_accumulate_ops(hlo, shapes) == []
    weights = {s for s in shapes if len(s) == 3 and min(s[1:]) >= 1024}
    assert weights and weights <= _dot_fused_accumulates(hlo)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    with capsys.disabled():
        print(f"\n{arch} MBP {n_micro} x 1 step for v5e: args + temp "
              f"{used / 2 ** 30:.3f} GiB, with the plain accumulate "
              f"{PLAIN_ACCUMULATE_BYTES[arch] / 2 ** 30:.3f} GiB")
    assert used <= PLAIN_ACCUMULATE_BYTES[arch]


def test_gradient_census_reads_the_chips_hlo(topo, one_chip):
    """The HLO004 census on what the chip's compiler prints (layouts such
    as ``{0:T(256)S(1)}``) for the tiny model on the 4:1 mesh, 8
    micro-batches unrolled: the deferred step syncs one gradient operand,
    and the per-micro baseline's 8 come out combined into one tuple
    all-reduce yet still count as 8, so it fails the deferred census."""
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    opt = tiny_optimizer()
    plan = engine.plan_mbs(64, num_microbatches=8, mesh=mesh, unroll=8)
    params = tiny_params()
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (params, opt.init(params), plan.device_split(tiny_batch(64))))
    for defer, expect in ((True, "deferred"), (False, "per-micro")):
        ex = make_sharded_executor("compiled", tiny_loss_fn, opt, plan, mesh,
                                   donate=False, defer_sync=defer)
        compiled = jax.jit(ex.make_train_step()).lower(*shapes).compile()
        assert sum(c.kind == "all-reduce"
                   for c in hlo_checks.collectives(compiled)) == 1
        assert not hlo_checks.check_gradient_sync(compiled, expect=expect,
                                                  n_micro=8)
    assert hlo_checks.check_gradient_sync(compiled, expect="deferred",
                                          n_micro=8)
