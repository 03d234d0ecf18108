"""Multi-device conformance matrix (engine Layer 6).

Runs on the conftest-forced 8-device CPU host platform and proves, for
every executor in the conformance grid × mesh shapes × split regimes:

  * **equivalence** — sharded execution is semantically invisible: the
    deferred-sync ShardedExecutor reproduces the single-device gradients,
    loss, and full optimizer update (ragged tails + exact normalization +
    global-norm clipping included) within the harness's per-dtype
    tolerances;
  * **deferred sync** — the compiled mini-batch step's HLO contains
    exactly ONE gradient all-reduce, independent of the number of
    micro-batches (asserted against a fully unrolled scan, where the
    per-micro-sync baseline shows one collective per micro-batch);
  * **trajectory** — the 5-step golden loss trajectory pinned in PR 4
    (single device) is reproduced bit-for-tolerance on a (data=4) mesh;
  * **planning** — ``plan_mbs(mesh=...)`` keeps micro sizes divisible by
    the data axis, records ``data_parallel``/``local_micro``, and admits
    a growing global batch at a fixed per-device budget as the data axis
    grows 2 -> 4 -> 8.
"""
import jax
import numpy as np
import pytest

from conftest import (EXECUTOR_GRID, GOLDEN_LOSSES, ToyDataset,
                      assert_scalar_close, assert_trees_close, host_mesh,
                      make_executor, make_sharded_executor, tiny_batch,
                      tiny_loss_fn, tiny_optimizer, tiny_params)
from repro import analysis, configs, engine, optim
from repro.core import memory_model

pytestmark = pytest.mark.mesh

# (label, mini_batch, micro_batch, expected normalization after planning):
# the uniform split keeps Algorithm 1's "paper" mode; the ragged split
# auto-upgrades to "exact" and exercises the zero-weight-padding shards
SPLIT_CASES = {
    "uniform-paper": (16, 8, "paper"),
    "ragged-exact": (10, 4, "exact"),
}


def _plan_and_split(mini, micro, mesh, seed=0):
    plan = engine.plan_mbs(mini, micro_batch_size=micro, mesh=mesh)
    return plan, plan.device_split(tiny_batch(mini, seed))


# ---------------------------------------------------------------------------
# gradient/loss equivalence: executors × mesh shapes × split regimes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", EXECUTOR_GRID)
@pytest.mark.parametrize("data", [2, 4])
@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_sharded_gradients_match_single_device(executor, data, case):
    mini, micro, norm = SPLIT_CASES[case]
    mesh = host_mesh(data)
    plan, split = _plan_and_split(mini, micro, mesh)
    assert plan.normalization == norm
    params, opt = tiny_params(), tiny_optimizer()
    g_ref, l_ref = make_executor(executor, tiny_loss_fn, opt, plan,
                                 donate=False).gradients(params, split)
    g, l = make_sharded_executor(executor, tiny_loss_fn, opt, plan,
                                 mesh).gradients(params, split)
    assert_trees_close(g, g_ref, what=f"{executor}/data={data}/{case} grads")
    assert_scalar_close(l, l_ref, what=f"{executor}/data={data}/{case} loss")


@pytest.mark.parametrize("executor", EXECUTOR_GRID)
def test_sharded_update_matches_single_device_with_clip(executor):
    """Full optimizer step under global-norm clipping on the ragged split:
    params, opt state, loss and grad-norm must all match the single-device
    reference — the clip scale is computed from the globally summed
    gradient, so a wrong sync point shows up here immediately."""
    mesh = host_mesh(4)
    opt = optim.clip_by_global_norm(
        optim.sgd(0.1, momentum=0.9, weight_decay=1e-4), 0.05)
    plan, split = _plan_and_split(10, 4, mesh)
    params = tiny_params()
    ref = make_executor(executor, tiny_loss_fn, opt, plan, donate=False)
    p_ref, s_ref, m_ref = ref.step_split(params, opt.init(params), split)
    ex = make_sharded_executor(executor, tiny_loss_fn, opt, plan, mesh,
                               donate=False)
    p, s, m = ex.step_split(params, opt.init(params), split)
    assert_trees_close(p, p_ref, what=f"{executor} clipped params")
    assert_trees_close(s, s_ref, what=f"{executor} clipped opt state")
    assert_scalar_close(m["loss"], m_ref["loss"], what=f"{executor} loss")
    assert_scalar_close(m["grad_norm"], m_ref["grad_norm"], atol=1e-4,
                        what=f"{executor} grad_norm")


def test_sharded_step_via_host_minibatch():
    """.step() stages the host split with the mesh batch shardings and
    matches .step_split() on pre-staged arrays."""
    mesh = host_mesh(4)
    opt = tiny_optimizer()
    plan = engine.plan_mbs(16, micro_batch_size=8, mesh=mesh)
    params = tiny_params()
    batch = tiny_batch(16)
    ex = make_sharded_executor("compiled", tiny_loss_fn, opt, plan, mesh,
                               donate=False)
    p1, _, m1 = ex.step(params, opt.init(params), dict(batch))
    p2, _, m2 = ex.step_split(params, opt.init(params),
                              plan.device_split(batch))
    assert_trees_close(p1, p2, what="step vs step_split params")
    assert_scalar_close(m1["loss"], m2["loss"])


# ---------------------------------------------------------------------------
# deferred sync: HLO collective counts
# ---------------------------------------------------------------------------

def _compile_step(step_fn, *abstract_args):
    return jax.jit(step_fn).lower(*abstract_args).compile()


@pytest.mark.parametrize("n_micro", [2, 8])
def test_exactly_one_gradient_allreduce_per_minibatch(n_micro):
    """The acceptance criterion: with the scan FULLY UNROLLED (so a rolled
    loop body cannot hide per-iteration collectives) the deferred-sync
    step compiles to exactly one all-reduce regardless of N_Sμ, while the
    per-micro-sync baseline compiles to one per micro-batch plus the
    scalar sync. Both censuses go through the shared analysis rule
    (HLO004) — an empty findings list IS the pass."""
    mesh = host_mesh(4)
    opt = tiny_optimizer()
    plan = engine.plan_mbs(8 * n_micro, num_microbatches=n_micro, mesh=mesh,
                           unroll=n_micro)
    assert plan.num_micro_batches == n_micro
    params = tiny_params()
    split = plan.device_split(tiny_batch(8 * n_micro))
    state = opt.init(params)

    deferred = make_sharded_executor("compiled", tiny_loss_fn, opt, plan,
                                     mesh, donate=False)
    compiled = _compile_step(deferred.make_train_step(), params, state, split)
    findings = analysis.check_gradient_sync(
        compiled, expect="deferred", n_micro=n_micro, context="deferred")
    assert not findings, [f.format() for f in findings]
    assert sum(c.kind == "all-reduce"
               for c in analysis.collectives(compiled)) == 1

    baseline = make_sharded_executor("compiled", tiny_loss_fn, opt, plan,
                                     mesh, donate=False, defer_sync=False)
    compiled = _compile_step(baseline.make_train_step(), params, state, split)
    findings = analysis.check_gradient_sync(
        compiled, expect="per-micro", n_micro=n_micro, context="baseline")
    assert not findings, [f.format() for f in findings]


@pytest.mark.parametrize("executor", [e for e in EXECUTOR_GRID
                                      if e != "streaming"])
def test_one_allreduce_for_every_compiled_inner(executor):
    """The single-collective contract holds for every jittable inner
    strategy (plain scan, Pallas fused accumulate, flat buckets)."""
    mesh = host_mesh(4)
    opt = tiny_optimizer()
    plan = engine.plan_mbs(16, num_microbatches=4, mesh=mesh, unroll=4)
    params = tiny_params()
    split = plan.device_split(tiny_batch(16))
    ex = make_sharded_executor(executor, tiny_loss_fn, opt, plan, mesh,
                               donate=False)
    compiled = _compile_step(ex.make_train_step(), params, opt.init(params),
                             split)
    findings = analysis.check_gradient_sync(
        compiled, expect="deferred", n_micro=4, context=executor)
    assert not findings, [f.format() for f in findings]


# ---------------------------------------------------------------------------
# golden trajectory on a (data=4) mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", EXECUTOR_GRID)
def test_five_step_loss_trajectory_matches_single_device_golden(executor):
    """The PR-4 golden trajectory (recorded on ONE device) must be
    reproduced by sharded execution on a (data=4) mesh — data parallelism
    with deferred sync is a schedule change, never a numerics change."""
    mesh = host_mesh(4)
    plan = engine.plan_mbs(10, micro_batch_size=4, mesh=mesh)
    ds = ToyDataset()
    opt = tiny_optimizer()
    ex = make_sharded_executor(executor, tiny_loss_fn, opt, plan, mesh,
                               donate=False)
    params, state = tiny_params(), opt.init(tiny_params())
    losses = []
    for step in range(5):
        params, state, m = ex.step(params, state, ds.batch(10, step))
        losses.append(float(m["loss"]))
    np.testing.assert_allclose(losses, GOLDEN_LOSSES, atol=5e-4, rtol=0)


# ---------------------------------------------------------------------------
# mesh-aware planning
# ---------------------------------------------------------------------------

def test_plan_records_mesh_geometry_and_divisibility():
    mesh = host_mesh(4)
    plan = engine.plan_mbs(16, micro_batch_size=8, mesh=mesh)
    assert plan.data_parallel == 4
    assert plan.local_micro == 2
    assert plan.micro_batch_size == plan.local_micro * plan.data_parallel
    # pinned sizes that do not divide are rounded UP to the next multiple
    plan = engine.plan_mbs(16, micro_batch_size=6, mesh=mesh)
    assert plan.micro_batch_size == 8 and plan.local_micro == 2
    # ... but never past the largest dp-divisible size <= the mini-batch
    plan = engine.plan_mbs(10, micro_batch_size=7, mesh=mesh)
    assert plan.micro_batch_size == 8 and plan.local_micro == 2
    with pytest.raises(ValueError, match="data-parallel"):
        engine.plan_mbs(3, micro_batch_size=1, mesh=mesh)


def test_sharded_executor_rejects_bad_plans():
    mesh = host_mesh(4)
    opt = tiny_optimizer()
    indivisible = engine.plan_mbs(10, micro_batch_size=5)  # no mesh: 5 % 4
    with pytest.raises(ValueError, match="divide"):
        engine.ShardedExecutor(tiny_loss_fn, opt, indivisible, mesh=mesh)
    ragged_paper = engine.MBSPlan(10, 4, 3, 2, "paper")
    with pytest.raises(ValueError, match="exact"):
        engine.ShardedExecutor(tiny_loss_fn, opt, ragged_paper, mesh=mesh)
    plan = engine.plan_mbs(16, micro_batch_size=8, mesh=mesh)
    with pytest.raises(ValueError, match="defer_sync"):
        engine.ShardedExecutor(tiny_loss_fn, opt, plan, mesh=mesh,
                               inner="flat", defer_sync=False)


def test_admission_grows_with_data_axis():
    """The acceptance criterion: at a FIXED per-device budget the
    mesh-aware planner admits a larger global batch as the data axis
    grows 2 -> 4 -> 8 (local admission is per-device; the global
    micro-batch multiplies it by data_parallel)."""
    cfg = configs.get_reduced("qwen2-1.5b")
    seq = 16
    est = memory_model.estimate(cfg, seq, remat_policy="none")
    budget = est.total(0) + 3 * est.activation_bytes_per_sample
    admitted = []
    for data in (2, 4, 8):
        mesh = host_mesh(data)
        plan = engine.plan_mbs(256, model_cfg=cfg, seq_len=seq,
                               budget_bytes=budget, remat_policy="none",
                               mesh=mesh, fsdp_params=False)
        assert plan.data_parallel == data
        # the plan's own per-device estimate stays inside the budget
        per_dev = memory_model.estimate(cfg, seq, remat_policy="none",
                                        mesh=mesh, fsdp_params=False)
        assert per_dev.total(plan.local_micro) <= budget
        admitted.append(plan.micro_batch_size)
    assert admitted == sorted(admitted)
    assert admitted[-1] > admitted[0], admitted


def test_pipeline_stages_with_mesh_batch_shardings():
    """Pipeline(mesh=...) stages split batches with the mesh's batch
    shardings: the sample dim (dim 1) lands sharded over the data axis,
    the scan dim replicated — the GSPMD launcher path's staging."""
    from jax.sharding import PartitionSpec as P
    mesh = host_mesh(4)
    plan = engine.plan_mbs(16, micro_batch_size=8, mesh=mesh)
    pipe = engine.Pipeline(ToyDataset(), plan, prefetch=0, mesh=mesh)
    batch = next(iter(pipe.batches(1)))
    assert batch["x"].sharding.spec == P(None, "data", None)
    assert batch["sample_weight"].sharding.spec == P(None, "data")
    with pytest.raises(ValueError, match="not both"):
        engine.Pipeline(ToyDataset(), plan, mesh=mesh,
                        sharding=jax.devices()[0])


def test_param_shard_ratio_discounts_fsdp():
    """FSDP sharding discounts the per-device param bytes (divisible dims
    shard; the rest replicate), and the data axis discount disappears for
    a replicating executor (fsdp=False)."""
    cfg = configs.get_reduced("qwen2-1.5b")
    mesh = host_mesh(4)
    r_fsdp = memory_model.param_shard_ratio(cfg, mesh, fsdp=True)
    r_repl = memory_model.param_shard_ratio(cfg, mesh, fsdp=False)
    assert r_fsdp < r_repl <= 1.0
    est_fsdp = memory_model.estimate(cfg, 16, mesh=mesh, fsdp_params=True)
    est_repl = memory_model.estimate(cfg, 16, mesh=mesh, fsdp_params=False)
    assert est_fsdp.params_bytes < est_repl.params_bytes
