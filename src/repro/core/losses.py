"""Loss functions used across the framework.

Every loss returns the *mean per-sample loss over the (micro-)batch* plus a
valid-sample count, which is what the MBS loss-normalization algorithm
(paper §3.4, Algorithm 1) consumes. ``sample_weight`` supports the ragged
tail case (N_B % N_mu != 0): padded samples carry weight 0.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import nn


def _weighted_mean(per_sample: jnp.ndarray, sample_weight, exact_denom):
    """mean over samples; with ``exact_denom`` set, divide the weighted sum
    by that count instead (used by exact-ragged MBS)."""
    if sample_weight is None:
        if exact_denom is not None:
            return jnp.sum(per_sample) / exact_denom
        return jnp.mean(per_sample)
    total = jnp.sum(per_sample * sample_weight)
    denom = exact_denom if exact_denom is not None else jnp.sum(sample_weight)
    return total / denom


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray, *,
                  token_weight: Optional[jnp.ndarray] = None,
                  sample_weight: Optional[jnp.ndarray] = None,
                  exact_denom=None) -> jnp.ndarray:
    """LM / classification CE. logits: (..., V) fp32; labels int.

    Per-sample loss = mean over valid tokens; batch loss = mean over samples.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold  # (..., ) per-token
    if nll.ndim > 1:  # sequence models: mean over tokens per sample
        if token_weight is not None:
            per_sample = (jnp.sum(nll * token_weight, axis=tuple(range(1, nll.ndim)))
                          / jnp.maximum(jnp.sum(token_weight, axis=tuple(range(1, nll.ndim))), 1))
        else:
            per_sample = jnp.mean(nll, axis=tuple(range(1, nll.ndim)))
    else:
        per_sample = nll
    return _weighted_mean(per_sample, sample_weight, exact_denom)


def lm_head_cross_entropy(x: jnp.ndarray, w: jnp.ndarray, labels: jnp.ndarray,
                          *, tied: bool, softcap: Optional[float] = None,
                          sample_weight: Optional[jnp.ndarray] = None,
                          exact_denom=None) -> jnp.ndarray:
    """Language-model loss from the final hidden states, with the output
    head inside: ``cross_entropy(softcap(x @ head), labels, ...)`` as one
    op whose gradient is formed once.

    x: (B, S, d) in the compute dtype; labels: (B, S) int; w: the head
    weight, the embedding table (V, d) when ``tied``, else the projection
    (d, V). The dots take x's dtype as operands and accumulate in fp32;
    the logsumexp and the softmax are fp32. Differentiated, the forward
    keeps one (B, S, V) residual, the scaled ``softmax - one_hot`` in x's
    dtype, and the backward is the two dots on it. The gold logit is
    picked by a compare, so no scatter appears in the gradient. The
    residual is pinned by an optimization barrier: without it XLA fuses
    the residual's pass into both backward dots, which then read the fp32
    logits and keep them live until the backward.
    """
    B, S = labels.shape
    sw = (jnp.ones((B,), jnp.float32) if sample_weight is None
          else sample_weight.astype(jnp.float32))
    if exact_denom is not None:
        denom = exact_denom
    else:
        denom = B if sample_weight is None else jnp.sum(sw)
    # d loss / d nll per token: the mean over tokens, then _weighted_mean
    weight = jnp.broadcast_to((sw / (denom * S))[:, None], (B, S))
    return _head_ce(x, w, labels, jax.lax.stop_gradient(weight), tied,
                    softcap)


def _head_logits(x, w, tied, softcap):
    """fp32 logits after the softcap, and tanh of the capped logits."""
    z = jax.lax.dot_general(
        x, w.astype(x.dtype), (((2,), (1 if tied else 0,)), ((), ())),
        preferred_element_type=jnp.float32)
    z = nn.vocab_sharded(z)
    if softcap is None:
        return z, None
    t = jnp.tanh(z / softcap)
    return t * softcap, t


def _nll_parts(logits, labels):
    """(logsumexp, gold logit, one-hot mask) of fp32 logits."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    hit = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
           == labels[..., None])
    gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    return lse, gold, hit


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _head_ce(x, w, labels, weight, tied, softcap):
    lse, gold, _ = _nll_parts(_head_logits(x, w, tied, softcap)[0], labels)
    return jnp.sum((lse - gold) * weight)


def _head_ce_fwd(x, w, labels, weight, tied, softcap):
    logits, t = _head_logits(x, w, tied, softcap)
    lse, gold, hit = _nll_parts(logits, labels)
    dlogits = (jnp.exp(logits - lse[..., None]) - hit) * weight[..., None]
    if t is not None:
        dlogits = dlogits * (1.0 - t * t)
    residual = jax.lax.optimization_barrier(
        nn.vocab_sharded(dlogits.astype(x.dtype)))
    return jnp.sum((lse - gold) * weight), (residual, x, w)


def _head_ce_bwd(tied, softcap, res, g):
    residual, x, w = res
    dx = jax.lax.dot_general(
        residual, w.astype(x.dtype), (((2,), (0 if tied else 1,)), ((), ())),
        preferred_element_type=jnp.float32)
    tokens = ((0, 1), (0, 1))
    if tied:  # (V, d)
        dw = jax.lax.dot_general(residual, x, (tokens, ((), ())),
                                 preferred_element_type=jnp.float32)
    else:  # (d, V)
        dw = jax.lax.dot_general(x, residual, (tokens, ((), ())),
                                 preferred_element_type=jnp.float32)
    return (g * dx).astype(x.dtype), (g * dw).astype(w.dtype), None, None


_head_ce.defvjp(_head_ce_fwd, _head_ce_bwd)


def bce_with_logits(logits, targets, *, sample_weight=None, exact_denom=None):
    """Binary cross-entropy from logits. logits/targets: (B, H, W, 1)."""
    logits = logits.astype(jnp.float32)
    per_px = jnp.maximum(logits, 0) - logits * targets + jnp.log1p(
        jnp.exp(-jnp.abs(logits)))
    per_sample = jnp.mean(per_px, axis=tuple(range(1, per_px.ndim)))
    return _weighted_mean(per_sample, sample_weight, exact_denom)


def dice_loss(logits, targets, *, sample_weight=None, exact_denom=None,
              eps: float = 1.0):
    """Paper eq. (19): L_dc = 1 - 2|A∩B| / (|A|+|B|), per sample."""
    probs = jax.nn.sigmoid(logits.astype(jnp.float32))
    axes = tuple(range(1, probs.ndim))
    inter = jnp.sum(probs * targets, axis=axes)
    denom = jnp.sum(probs, axis=axes) + jnp.sum(targets, axis=axes)
    per_sample = 1.0 - (2.0 * inter + eps) / (denom + eps)
    return _weighted_mean(per_sample, sample_weight, exact_denom)


def bce_dice_loss(logits, targets, **kw):
    """Paper eq. (20): L_total = L_bce + L_dc (U-Net training loss)."""
    return bce_with_logits(logits, targets, **kw) + dice_loss(logits, targets, **kw)


def iou(logits, targets, thresh: float = 0.5) -> jnp.ndarray:
    """Intersection-over-union metric (paper §4.3.1)."""
    pred = (jax.nn.sigmoid(logits.astype(jnp.float32)) > thresh).astype(jnp.float32)
    axes = tuple(range(1, pred.ndim))
    inter = jnp.sum(pred * targets, axis=axes)
    union = jnp.sum(jnp.maximum(pred, targets), axis=axes)
    return jnp.mean((inter + 1e-6) / (union + 1e-6))


def accuracy(logits, labels) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
