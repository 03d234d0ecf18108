"""The backward that adds the layer stack's gradient into the accumulator
(``remat.accumulating_scan``, used by ``CompiledScanExecutor`` where
``exec_core.inplace_key`` allows) against the plain path: the same loss
over the same split batch, with the stack's gradient formed in its own
buffer and added by ``exec_core.accumulate``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, engine
from repro.launch import steps
from repro.models import transformer

SEQ = 16
MINI = 4
EPS = float(jnp.finfo(jnp.float32).eps)


def _plain(loss_fn):
    """``loss_fn`` without its ``accum_key``: the executors then take the
    plain path for it."""
    def f(params, mb, exact_denom=None):
        return loss_fn(params, mb, exact_denom=exact_denom)
    return f


def _setup(arch, policy, n_micro, normalization):
    cfg = configs.get_reduced(arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = steps.make_loss_fn(cfg, jnp.float32, remat_policy=policy)
    plan = engine.plan_mbs(MINI, num_microbatches=n_micro,
                           normalization=normalization, remat_policy=policy)
    rng = np.random.default_rng(n_micro)
    mb = {k: rng.integers(0, cfg.vocab_size, (MINI, SEQ)).astype(np.int32)
          for k in ("tokens", "labels")}
    return params, loss_fn, plan, plan.device_split(mb)


def _assert_same(got, want):
    """Equal, or apart by an ulp of the leaf's largest magnitude (the
    backward's dots may be fused differently around the add)."""
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype == jnp.float32
        err = float(jnp.max(jnp.abs(g - w)))
        assert err <= EPS * float(jnp.max(jnp.abs(w))), err


@pytest.mark.parametrize("mode", ["paper", "exact", "raw"])
@pytest.mark.parametrize("n_micro", [2, 4])
@pytest.mark.parametrize("policy", ["period", "full"])
@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-780m"])
def test_inplace_matches_plain_accumulate(arch, policy, n_micro, mode):
    params, loss_fn, plan, split = _setup(
        arch, policy, n_micro, "paper" if mode == "paper" else "exact")
    assert loss_fn.accum_key == "blocks"
    inplace = engine.CompiledScanExecutor(loss_fn, None, plan)
    plain = engine.CompiledScanExecutor(_plain(loss_fn), None, plan)
    assert inplace.inplace_accum_share(params) > 0
    assert plain.inplace_accum_share(params) == 0
    if mode == "raw":
        got, want = (jax.jit(ex.raw_accumulate)(params, split)
                     for ex in (inplace, plain))
    else:
        got, want = (ex.gradients(params, split) for ex in (inplace, plain))
    _assert_same(got[0], want[0])
    np.testing.assert_allclose(float(got[1]), float(want[1]), rtol=EPS)


@pytest.mark.parametrize("policy,n_micro", [
    ("period", 1), ("full", 1), ("none", 2), ("dots", 2)])
def test_bypassed_traces_the_plain_step(policy, n_micro):
    """At N_Smu = 1, and under the policies that keep activations instead
    of recomputing per period, the step is the one the plain path traces."""
    params, loss_fn, plan, split = _setup("qwen2-1.5b", policy, n_micro,
                                          "paper")
    inplace = engine.CompiledScanExecutor(loss_fn, None, plan)
    plain = engine.CompiledScanExecutor(_plain(loss_fn), None, plan)
    assert inplace.inplace_accum_share(params) == 0
    assert (str(jax.make_jaxpr(inplace._accumulated)(params, split))
            == str(jax.make_jaxpr(plain._accumulated)(params, split)))


def _abstract_params(cfg):
    return jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                          jax.random.PRNGKey(0))


@pytest.mark.parametrize("arch,change,n_micro,share", [
    # 1.498 GB of trunk of 2.431 GB (the tied 151,936 x 1536 embedding)
    ("qwen2-1.5b", {"num_layers": 8}, 8, 0.616),
    # 2.812 GB of trunk of 3.121 GB, the vocabulary padded as benchmarked
    ("mamba2-780m", {"vocab_size": 50_288}, 16, 0.901),
    ("qwen2-1.5b", {"num_layers": 8}, 1, 0.0),  # one-shot: N_Smu = 1
])
def test_inplace_accum_share(arch, change, n_micro, share):
    """The benchmark's configurations at their published widths: the share
    of the fp32 accumulator's bytes that the backward adds in place."""
    cfg = dataclasses.replace(configs.get(arch), **change)
    plan = engine.plan_mbs(n_micro, num_microbatches=n_micro)
    ex = engine.CompiledScanExecutor(
        steps.make_loss_fn(cfg, remat_policy="period"), None, plan)
    assert ex.inplace_accum_share(_abstract_params(cfg)) == pytest.approx(
        share, abs=1e-3)


def test_sharded_executor_accumulates_in_place():
    """The data-parallel executor's local half (``raw_accumulate`` of its
    compiled inner) takes the in-place path, with the plain path's
    gradient; a flat inner does not."""
    from conftest import host_mesh
    mesh = host_mesh(2)
    cfg = configs.get_reduced("qwen2-1.5b")
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    loss_fn = steps.make_loss_fn(cfg, jnp.float32, remat_policy="period")
    plan = engine.plan_mbs(2 * MINI, num_microbatches=2, mesh=mesh,
                           normalization="exact")
    rng = np.random.default_rng(0)
    split = plan.device_split(
        {k: rng.integers(0, cfg.vocab_size, (2 * MINI, SEQ)).astype(np.int32)
         for k in ("tokens", "labels")})
    sharded = engine.ShardedExecutor(loss_fn, None, plan, mesh=mesh)
    assert sharded.inplace_accum_share(params) == pytest.approx(
        engine.CompiledScanExecutor(loss_fn, None, plan)
        .inplace_accum_share(params))
    assert sharded.inplace_accum_share(params) > 0
    assert engine.ShardedExecutor(loss_fn, None, plan, mesh=mesh,
                                  inner="flat").inplace_accum_share(params) == 0
    got = sharded.gradients(params, split)
    want = engine.ShardedExecutor(_plain(loss_fn), None, plan,
                                  mesh=mesh).gradients(params, split)
    _assert_same(got[0], want[0])
    np.testing.assert_allclose(float(got[1]), float(want[1]), rtol=EPS)


@pytest.mark.parametrize("target", ["qwen2_reduced", "mamba2_reduced"])
def test_jx001_finds_the_inplace_accumulator(target):
    """The contract suite's compiled step takes the in-place path, and
    JX001 still locates the micro-batch scan's fp32 accumulator carry: no
    finding against the plan, an error against a bf16 one."""
    from repro import analysis
    from repro.analysis import suite
    built = suite.TARGETS[target].build("compiled", None, "period")
    plan, params = built["plan"], built["args"][0]
    ex = engine.CompiledScanExecutor(built["loss_fn"], built["optimizer"],
                                     plan)
    assert plan.num_micro_batches > 1 and ex.inplace_accum_share(params) > 0
    jaxpr = ex.trace_step(*built["args"])
    assert analysis.check_accum_dtype(jaxpr, plan, params) == []
    bf16 = dataclasses.replace(plan, accum_dtype=jnp.bfloat16)
    assert {f.rule for f in analysis.check_accum_dtype(jaxpr, bf16, params)
            } == {"JX001"}
