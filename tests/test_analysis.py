"""Static-analysis subsystem conformance (ISSUE 7).

Two directions, both mandatory:

  * POSITIVE — the shipped engine produces ZERO findings: every executor
    × mesh × remat combination traces and compiles clean through the
    jaxpr/HLO contract rules, and the repo source is lint-clean.
  * NEGATIVE — every rule actually FIRES on a seeded violation: a
    checker that cannot catch the bug it documents is worse than no
    checker (it certifies broken code).

The matrix uses the tiny conftest model (fast); one real reduced config
exercises the remat lattice (JX002 needs a model with a checkpoint
boundary to apply the policy to).
"""
import jax
import jax.numpy as jnp
import pytest

from conftest import (EXECUTOR_GRID, host_mesh, make_executor,
                      make_sharded_executor, tiny_batch, tiny_loss_fn,
                      tiny_optimizer, tiny_params)
from repro import analysis, engine
from repro.analysis import findings as F


def _setup(n_micro=4, mesh=None, **plan_kw):
    plan = engine.plan_mbs(4 * n_micro, num_microbatches=n_micro,
                           mesh=mesh, **plan_kw)
    opt = tiny_optimizer()
    params = tiny_params()
    return plan, opt, params, opt.init(params), \
        plan.device_split(tiny_batch(4 * n_micro))


# ---------------------------------------------------------------------------
# positive: the shipped engine is contract-clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", EXECUTOR_GRID)
@pytest.mark.parametrize("mesh_mode", ["single", "host"])
def test_zero_findings_matrix(executor, mesh_mode):
    """Every executor × mesh combination traces (and, where jittable,
    compiles) with zero contract findings."""
    mesh = host_mesh(4) if mesh_mode == "host" else None
    plan, opt, params, opt_state, split = _setup(mesh=mesh, unroll=4)
    if mesh is not None:
        ex = make_sharded_executor(executor, tiny_loss_fn, opt, plan, mesh)
    else:
        ex = make_executor(executor, tiny_loss_fn, opt, plan)

    jaxpr = ex.trace_step(params, opt_state, split)
    report = analysis.check_train_step(
        jaxpr, plan, params,
        expect_sync="deferred" if mesh is not None else "none",
        policy="none")
    assert report.ok, report.format()

    if executor == "streaming":
        return  # per-micro dispatch loop: nothing to jit whole
    compiled = ex.lower_step(params, opt_state, split, donate=True).compile()
    state_bytes = analysis.tree_bytes((params, opt_state))
    hlo_findings = (
        analysis.check_aliasing(compiled, state_bytes, context=executor)
        + analysis.check_unexpected_ops(compiled, context=executor)
        + analysis.check_gradient_sync(
            compiled, expect="deferred" if mesh is not None else "none",
            n_micro=plan.num_micro_batches, context=executor))
    assert not hlo_findings, [f.format() for f in hlo_findings]


def test_remat_policy_applied_on_real_model():
    """JX002 positive leg on a REAL reduced config: the traced step under
    remat_policy=period carries checkpoint sub-jaxprs (the tiny model has
    no remat boundary, so this needs the transformer target)."""
    report = analysis.run_suite("qwen2_reduced", executor="compiled",
                                hlo=False, lint=False)
    assert report.ok, report.format()
    assert "JX002" in report.checks_run


def test_repo_is_lint_clean():
    assert analysis.lint_repo() == []


# ---------------------------------------------------------------------------
# negative: each jaxpr rule fires on a seeded violation
# ---------------------------------------------------------------------------

def _rules(findings):
    return {f.rule for f in findings}


def test_jx001_fires_on_bf16_accumulator():
    # executor honestly accumulates in bf16 (plan says so), but the
    # contract under check demands fp32 — the checker must see through it
    plan_bf16, opt, params, opt_state, split = _setup(
        accum_dtype=jnp.bfloat16)
    plan_fp32 = engine.plan_mbs(16, num_microbatches=4)
    ex = make_executor("compiled", tiny_loss_fn, opt, plan_bf16)
    jaxpr = ex.trace_step(params, opt_state, split)
    findings = analysis.check_accum_dtype(jaxpr, plan_fp32, params)
    assert "JX001" in _rules(findings), [f.format() for f in findings]


def test_jx002_fires_on_missing_and_unexpected_remat():
    plan, opt, params, opt_state, split = _setup()
    ex = make_executor("compiled", tiny_loss_fn, opt, plan)
    jaxpr = ex.trace_step(params, opt_state, split)
    # policy says "period" but the trace has no checkpoint sub-jaxpr
    missing = analysis.check_remat_policy(jaxpr, "period")
    assert "JX002" in _rules(missing)

    def remat_loss(p, b, exact_denom=None):
        f = jax.checkpoint(lambda q: tiny_loss_fn(q, b, exact_denom))
        return f(p)

    ex2 = make_executor("compiled", remat_loss, opt, plan)
    jaxpr2 = ex2.trace_step(params, opt_state, split)
    # checkpoint present under policy "none" — remat the planner did not
    # budget for
    unexpected = analysis.check_remat_policy(jaxpr2, "none")
    assert "JX002" in _rules(unexpected)
    # and the matched case is clean
    assert analysis.check_remat_policy(jaxpr2, "period") == []


def test_jx003_fires_on_host_callback():
    plan, opt, params, opt_state, split = _setup()

    def chatty_loss(p, b, exact_denom=None):
        loss, metrics = tiny_loss_fn(p, b, exact_denom)
        jax.debug.callback(lambda x: None, loss)
        return loss, metrics

    ex = make_executor("compiled", chatty_loss, opt, plan)
    jaxpr = ex.trace_step(params, opt_state, split)
    findings = analysis.check_host_callbacks(jaxpr)
    assert "JX003" in _rules(findings)


def test_jx004_fires_on_per_micro_sync():
    mesh = host_mesh(4)
    plan, opt, params, opt_state, split = _setup(mesh=mesh, unroll=4)
    eager = make_sharded_executor("compiled", tiny_loss_fn, opt, plan, mesh,
                                  defer_sync=False)
    jaxpr = eager.trace_step(params, opt_state, split)
    findings = analysis.check_collectives(
        jaxpr, params, n_micro=plan.num_micro_batches, expect="deferred")
    assert "JX004" in _rules(findings), [f.format() for f in findings]
    # the same trace is CORRECT under the per-micro expectation
    assert analysis.check_collectives(
        jaxpr, params, n_micro=plan.num_micro_batches,
        expect="per-micro") == []


# ---------------------------------------------------------------------------
# negative: HLO rules
# ---------------------------------------------------------------------------

def test_hlo001_fires_on_dropped_donation():
    plan, opt, params, opt_state, split = _setup()
    ex = make_executor("compiled", tiny_loss_fn, opt, plan)
    compiled = ex.lower_step(params, opt_state, split, donate=False).compile()
    findings = analysis.check_aliasing(
        compiled, analysis.tree_bytes((params, opt_state)), context="neg")
    assert "HLO001" in _rules(findings)


def test_hlo003_fires_on_wild_memory_model():
    plan, opt, params, opt_state, split = _setup()
    ex = make_executor("compiled", tiny_loss_fn, opt, plan)
    compiled = ex.lower_step(params, opt_state, split, donate=True).compile()
    # model claims 256 GiB for a KB-scale step: outside any sane band
    findings = analysis.check_memory_model(compiled, 1 << 38, context="neg")
    assert "HLO003" in _rules(findings)
    # a model equal to the measurement is inside the band
    measured = analysis.measured_peak_bytes(compiled)
    assert analysis.check_memory_model(compiled, measured,
                                       context="pos") == []


# XLA's all-reduce combiner prints the 8-micro-batch per-micro baseline's
# syncs as ONE tuple all-reduce (the f32[2] loss/metric sync + 8 gradient
# buckets), with a /*index=5*/ comment inside the tuple, and an async
# collective as a -start/-done pair
COMBINED_HLO = """\
  %all-reduce = (f32[2]{0}, f32[192]{0}, f32[192]{0}, f32[192]{0}, \
f32[192]{0}, /*index=5*/f32[192]{0}, f32[192]{0}, f32[192]{0}, f32[192]{0}) \
all-reduce(%f, %f.1, %f.3, %f.5, %f.7, /*index=5*/%f.9, %f.11, %f.13, %f.15), \
channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_49.50, \
metadata={op_name="jit(train_step)/shard_map/grad_sync/psum"}
  %get-tuple-element.8 = f32[192]{0} get-tuple-element(%all-reduce), index=1
  %cp = bf16[4,8]{1,0} collective-permute-start(%x), source_target_pairs={{0,1}}
  ROOT %cp.done = bf16[4,8]{1,0} collective-permute-done(%cp)
"""


def test_reader_counts_operands_of_a_combined_allreduce():
    colls = analysis.collectives(COMBINED_HLO)
    assert [c.kind for c in colls] == ["all-reduce", "collective-permute"]
    assert colls[0].operand_bytes == (8,) + (768,) * 8
    assert colls[0].op_name == "jit(train_step)/shard_map/grad_sync/psum"
    assert colls[1].operand_bytes == (64,) and colls[1].op_name == ""
    assert analysis.collective_bytes(COMBINED_HLO)["all-reduce"] == {
        "bytes": 8 + 8 * 768, "count": 1}
    # one instruction, eight gradient syncs: the census reads eight
    assert not analysis.check_gradient_sync(COMBINED_HLO, expect="per-micro",
                                            n_micro=8)
    assert "HLO004" in _rules(analysis.check_gradient_sync(
        COMBINED_HLO, expect="deferred", n_micro=8))


def test_hlo004_fires_on_per_micro_schedule():
    mesh = host_mesh(4)
    plan, opt, params, opt_state, split = _setup(mesh=mesh, unroll=4)
    eager = make_sharded_executor("compiled", tiny_loss_fn, opt, plan, mesh,
                                  donate=False, defer_sync=False)
    compiled = jax.jit(eager.make_train_step()).lower(
        params, opt_state, split).compile()
    findings = analysis.check_gradient_sync(
        compiled, expect="deferred", n_micro=plan.num_micro_batches,
        context="neg")
    assert "HLO004" in _rules(findings)


# ---------------------------------------------------------------------------
# negative: lint rules + the escape hatch
# ---------------------------------------------------------------------------

LINT_FIXTURES = {
    "LINT001": ("loss_val = float(metrics['loss'])\n", "engine-hot"),
    "LINT002": ("import jax.numpy as jnp\nq = jnp.pad(x, 4)\n", "kernels"),
    "LINT003": ("import jax\nf = jax.jit(step, donate_argnums=(0, 1))\n",
                "general"),
    "LINT004": ("from jax.experimental import pallas as pl\n"
                "out = pl.pallas_call(kernel, out_shape=s)(x)\n", "kernels"),
    "LINT005": ("from repro.kernels.grad_accum import grad_accum\n",
                "general"),
    # one-liner handler so the noqa-waiver fixture lands on the except line
    # (LINT006's waiver must sit there, not anywhere in the handler body)
    "LINT006": ("try: x = 1\nexcept Exception: pass\n", "engine"),
}


@pytest.mark.parametrize("rule", sorted(LINT_FIXTURES))
def test_lint_rule_fires(rule):
    src, category = LINT_FIXTURES[rule]
    findings = analysis.lint_source(src, f"fixture_{rule}.py",
                                    category=category)
    assert rule in _rules(findings), [f.format() for f in findings]


@pytest.mark.parametrize("rule", sorted(LINT_FIXTURES))
def test_lint_noqa_waives(rule):
    src, category = LINT_FIXTURES[rule]
    lines = src.rstrip("\n").split("\n")
    lines[-1] += f"  # repro: noqa({rule})"
    waived = analysis.lint_source("\n".join(lines) + "\n",
                                  f"fixture_{rule}.py", category=category)
    assert rule not in _rules(waived)


def test_lint001_ignores_cold_code():
    src, _ = LINT_FIXTURES["LINT001"]
    assert analysis.lint_source(src, "fixture.py", category="general") == []


def test_lint006_taxonomy_routing_passes():
    src = ("try:\n    x = 1\nexcept Exception as e:\n"
           "    if faults.is_oom(e):\n        raise\n")
    assert analysis.lint_source(src, "fixture.py", category="engine") == []


def test_lint006_ignores_engine_external_code():
    src, _ = LINT_FIXTURES["LINT006"]
    assert analysis.lint_source(src, "fixture.py", category="general") == []


# ---------------------------------------------------------------------------
# findings vocabulary + CLI gate
# ---------------------------------------------------------------------------

def test_finding_rejects_unknown_rule():
    with pytest.raises(ValueError):
        F.Finding(rule="XX999", severity=F.SEVERITY_ERROR, message="?")


def test_report_exit_codes():
    rep = F.Report()
    assert rep.ok and rep.exit_code() == F.EXIT_OK
    rep.extend([F.Finding(rule="LINT001", severity=F.SEVERITY_ERROR,
                          message="seeded")], "LINT")
    assert not rep.ok and rep.exit_code() == F.EXIT_CONTRACT
    assert (F.EXIT_OK, F.EXIT_ERROR, F.EXIT_BUDGET, F.EXIT_CONTRACT) == \
        (0, 1, 2, 3)


def test_cli_lint_only_clean_and_violating(monkeypatch, capsys):
    from repro.analysis import __main__ as cli
    from repro.analysis import lint as lint_mod

    assert cli.main(["--lint-only"]) == F.EXIT_OK

    seeded = [F.Finding(rule="LINT002", severity=F.SEVERITY_ERROR,
                        message="seeded violation", location="x.py:1")]
    monkeypatch.setattr(lint_mod, "lint_repo", lambda root=None: seeded)
    assert cli.main(["--lint-only", "--json"]) == F.EXIT_CONTRACT
    out = capsys.readouterr().out
    assert "seeded violation" in out and '"exit_code": 3' in out
