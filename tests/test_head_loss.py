"""The language-model training loss as one op (``steps.head_loss`` over
``losses.lm_head_cross_entropy``) against the two-step path it replaced:
``transformer._lm_head``'s fp32 logits, then ``losses.cross_entropy``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import assert_scalar_close, assert_trees_close
from repro import configs, engine
from repro.core import losses
from repro.launch import steps
from repro.models import transformer

B, S, D, V = 3, 7, 16, 41


def _cfg(tied, softcap):
    return dataclasses.replace(configs.get_reduced("qwen2-1.5b"), d_model=D,
                               vocab_size=V, tie_embeddings=tied,
                               final_softcap=softcap)


def _inputs(tied, dtype, seed=0):
    rng = np.random.default_rng(seed)
    # logits of tens: a cap of 30 bends them, and the softmax is peaked
    x = jnp.asarray(rng.normal(0, 4.0, (B, S, D)), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.normal(0, 1.0, (V, D) if tied else (D, V)),
                    jnp.float32)
    params = {"embed": {"table": w}} if tied else {"unembed": {"w": w}}
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    return params, x, labels


def _reference(params, cfg, x, labels, **kw):
    return losses.cross_entropy(transformer._lm_head(params, cfg, x), labels,
                                **kw)


WEIGHTS = {
    "none": {},
    # the ragged MBP tail: one padded sample, the global valid count
    "ragged": {"sample_weight": jnp.asarray([1.0, 1.0, 0.0]),
               "exact_denom": 5.0},
    "sample_weight": {"sample_weight": jnp.asarray([1.0, 0.5, 2.0])},
    "exact_denom": {"exact_denom": 4.0},
}


@pytest.mark.parametrize("dtype,weights,softcap,tied", [
    (jnp.float32, w, cap, tied)
    for tied in (True, False) for cap in (None, 30.0) for w in WEIGHTS
] + [(jnp.bfloat16, "ragged", cap, tied)
     for tied in (True, False) for cap in (None, 30.0)])
def test_head_loss_matches_logits_then_cross_entropy(tied, softcap, weights,
                                                     dtype):
    """Loss, d/dx and d/dW against the fp32 logits path. In bf16 the
    reference takes the same bf16-rounded weight, and the op's one bf16
    rounding of the logits' gradient is what separates the two."""
    cfg = _cfg(tied, softcap)
    params, x, labels = _inputs(tied, dtype)
    kw = WEIGHTS[weights]
    if dtype == jnp.bfloat16:
        params = jax.tree.map(lambda a: a.astype(dtype).astype(jnp.float32),
                              params)

    def new(p, x):
        return steps.head_loss(p, cfg, x, labels, **kw)

    def ref(p, x):
        return _reference(p, cfg, x, labels, **kw)

    got, (gp, gx) = jax.value_and_grad(new, (0, 1))(params, x)
    want, (wp, wx) = jax.value_and_grad(ref, (0, 1))(params, x)
    assert_scalar_close(got, want, atol=1e-6 * abs(float(want)))
    rel = 1e-5 if dtype == jnp.float32 else 1e-2
    (dw,), (want_dw,) = (jax.tree.leaves(g["embed" if tied else "unembed"])
                         for g in (gp, wp))
    assert dw.dtype == jnp.float32 and gx.dtype == dtype
    for a, b, what in ((dw, want_dw, "d/dW"), (gx, wx, "d/dx")):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert_trees_close(a, b, atol=rel * float(jnp.max(jnp.abs(b))),
                           what=what)


@pytest.mark.parametrize("softcap", [None, 30.0])
def test_head_loss_primal_alone(softcap):
    """Undifferentiated, the op runs its primal: the same loss as the
    reference, and as the forward rule gives under ``value_and_grad``."""
    cfg = _cfg(True, softcap)
    params, x, labels = _inputs(True, jnp.float32, seed=1)
    kw = WEIGHTS["ragged"]
    primal = jax.jit(lambda p, x: steps.head_loss(p, cfg, x, labels, **kw))
    with_grad = jax.jit(jax.value_and_grad(
        lambda p, x: steps.head_loss(p, cfg, x, labels, **kw)))
    want = _reference(params, cfg, x, labels, **kw)
    assert_scalar_close(primal(params, x), want, atol=1e-5)
    assert_scalar_close(primal(params, x), with_grad(params, x)[0], atol=1e-6)


def test_head_loss_gradient_has_no_scatter():
    """The gold logit is a compare, not a gather: the gradient of the op
    holds no scatter, where the replaced path's did."""
    cfg = _cfg(True, None)
    params, x, labels = _inputs(True, jnp.float32)

    def text(loss):
        return jax.jit(jax.grad(loss, (0, 1))).lower(params, x).as_text()

    assert "scatter" not in text(
        lambda p, x: steps.head_loss(p, cfg, x, labels))
    assert "scatter" in text(lambda p, x: _reference(p, cfg, x, labels))


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma2-9b"])
def test_mbp_executor_matches_one_shot(arch):
    """The compiled MBP executor at N_mu = 4, over a ragged mini-batch of 7
    (one padded sample, exact normalization), against one ``jax.grad`` of
    the training loss over all 7 samples."""
    cfg = configs.get_reduced(arch)
    loss_fn = steps.make_loss_fn(cfg, dtype=jnp.float32, remat=False)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (7, 16)).astype(np.int32),
             "labels": rng.integers(0, cfg.vocab_size, (7, 16)).astype(np.int32)}
    plan = engine.plan_mbs(7, num_microbatches=4, normalization="exact",
                           remat=False)
    assert plan.num_micro_batches == 4 and plan.pad == 1
    ex = engine.CompiledScanExecutor(loss_fn, steps.make_optimizer(cfg), plan)
    grads, loss = ex.gradients(params, plan.device_split(batch))
    one = {k: jnp.asarray(v) for k, v in batch.items()}
    want_loss, want = jax.value_and_grad(
        lambda p: loss_fn(p, one)[0])(params)
    assert_scalar_close(loss, want_loss, atol=5e-6)
    assert_trees_close(grads, want, atol=5e-5)
