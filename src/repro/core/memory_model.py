"""Analytic device-memory model → automatic micro-batch sizing.

The paper determines the micro-batch size "experimentally ... the maximum
size that can compute on GPU" (§4.3.2). We replace that search with an
analytic model of per-device bytes as a function of the micro-batch size,
and pick the largest power-of-two that fits the HBM budget — the same
quantity the dry-run's ``compiled.memory_analysis()`` verifies.

The model (per device, for the transformer families):
  params           P/ (tp * fsdp)                       * 4 B (fp32 master)
  grads (accum)    same as params                       * 4 B
  optimizer state  k_opt * params bytes (SGD-m: 1, Adam: 2)
  update transient step-❺ peak on top of the steady state: the unfused
                   update materializes the full ``updates`` tree plus
                   fresh momentum/m/v trees that coexist with the old
                   state until the swap — (1 + k_opt) * params bytes.
                   The fused flat path (``kernels/fused_update.py``,
                   in-place aliasing + donation) eliminates it.
  activations      per-period remat boundary + the live working set the
                   remat policy leaves, proportional to micro_batch * seq
                   (the MBS knob). The graded ``remat_policy`` lattice
                   (``models/remat.POLICIES``) scales the working-set term:
                     none    every period's working set stays live
                     dots    matmul outputs of every period stay saved
                             (~half the working set) + one period recompute
                     period  one period's working set (historical remat=True)
                     full    one block's working set (nested per-block remat)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

from ..models import remat as remat_lib
from ..models.config import ModelConfig

#: the planning budget off the chip (CPU tests, dry-run compiles); on a
#: chip the device's own limit is used (:func:`device_bytes_limit`)
V5E_HBM_BYTES = 16 * 1024 ** 3


def device_bytes_limit(device) -> int:
    """The memory the device itself reports it can allocate
    (``memory_stats()["bytes_limit"]``). A device that reports none
    raises: planning against a guess could admit a micro-batch that
    runs out of memory."""
    stats = device.memory_stats() or {}
    if "bytes_limit" not in stats:
        raise RuntimeError(
            f"{device.device_kind} reports no memory limit; pass "
            "--hbm-budget-gb to plan against an explicit budget")
    return int(stats["bytes_limit"])

# lattice order == the planner's escalation order (cheapest recompute first)
POLICY_ORDER = remat_lib.POLICIES

# fraction of a period's working set that checkpoint_dots keeps saved (the
# matmul outputs; elementwise intermediates are recomputed)
DOTS_SAVED_FRACTION = 0.5

# optimizer-state slots per optimizer (momentum / m+v trees)
OPT_SLOTS = {"sgd": 1, "sgd_plain": 0, "adam": 2, "adamw": 2}


def _resolve_slots(optimizer: str, opt_slots: Optional[int]) -> int:
    if opt_slots is not None:
        return opt_slots
    try:
        return OPT_SLOTS[optimizer]
    except KeyError:
        raise ValueError(
            f"unknown optimizer {optimizer!r}; known: {sorted(OPT_SLOTS)} "
            "(or pass opt_slots explicitly)")


def update_transient_bytes(params_bytes: int, optimizer: str = "sgd",
                           fused: bool = False, *,
                           opt_slots: Optional[int] = None) -> int:
    """Peak transient bytes of paper step ❺ beyond the steady state.

    The unfused reference (``optimizer.update`` + ``apply_update``) holds
    the full fp32 ``updates`` tree plus the freshly built optimizer-state
    trees while the old ones are still live. The fused flat update path
    writes params and opt state in place (``input_output_aliases`` +
    donation), leaving only O(kernel block) scratch — counted as zero.

    Fused-path caveat: the flat step still *gathers* the param/opt-state
    trees into contiguous buckets (and scatters them back), which is a
    copy at the XLA level. Those copies are counted as zero because they
    are live only at step ❺, when the donated split batch and the
    micro-batch activations — whose bytes this model already budgets and
    which dominate them at any admitted micro-batch size — have been
    freed for reuse; keeping state flat *across* steps (eliminating the
    gather entirely) is the noted next step in DESIGN.md §Update path."""
    if fused:
        return 0
    return (1 + _resolve_slots(optimizer, opt_slots)) * params_bytes


@dataclasses.dataclass(frozen=True)
class MemoryEstimate:
    params_bytes: int
    grads_bytes: int
    opt_bytes: int
    activation_bytes_per_sample: int  # per micro-batch sample, at given seq
    fixed_bytes: int
    update_transient_bytes: int = 0  # step-❺ peak (0 for the fused path)

    def total(self, micro_batch: int) -> int:
        """Conservative peak-bytes upper bound: sums the forward/backward
        activation peak and the step-❺ update transient even though the
        two phases do not coexist (activations are freed before the
        update). Summing can under-admit a micro-batch but never
        over-admits one — the safe direction for an OOM model."""
        return (self.params_bytes + self.grads_bytes + self.opt_bytes
                + self.fixed_bytes + self.update_transient_bytes
                + self.activation_bytes_per_sample * micro_batch)

    def affine_coeffs(self) -> tuple:
        """(fixed, per_sample) such that total(m) == fixed + per_sample*m.

        The estimate is exactly affine in the micro-batch size — this is
        the property the engine Layer 7 autotuner relies on: a measured
        XLA peak that is also (approximately) affine in m can be mapped
        onto this model by a single per-key affine correction
        (measured ≈ a*total(m) + b), fit from two or three probe
        compiles (`engine.autotune.calibrate_memory`)."""
        return self.total(0), self.activation_bytes_per_sample


def _stream_bytes(cfg: ModelConfig, act_bytes: int) -> int:
    """Bytes of one residual-stream element: fp32 where the model keeps
    its residual stream so (``cfg.residual_in_fp32``), else ``act_bytes``."""
    return 4 if cfg.residual_in_fp32 else act_bytes


def activation_bytes_per_sample(cfg: ModelConfig, seq: int,
                                act_bytes: int = 2,
                                remat: bool = True,
                                remat_policy: Optional[str] = None) -> int:
    """Live activation bytes for ONE sample of length ``seq``.

    Always present: residual-stream checkpoints at every period boundary
    (num_periods * seq * d_model) and the blocked-CE logits slice. The
    policy scales the live working-set term (one period's intermediates,
    ~ c * seq * max(d_model, d_ff, moe_active) * pattern_len):

      none    all ``num_periods`` working sets live simultaneously;
      dots    ``DOTS_SAVED_FRACTION`` of every period's working set stays
              saved (the dot outputs) + one period recomputing;
      period  exactly one period's working set (the recompute unit);
      full    nested per-block checkpoints shrink the recompute unit to a
              single block: one period's working set / pattern_len.

    ``remat_policy`` overrides the legacy ``remat`` bool (True → "period",
    False → "none") — the mapping lives in ``models/remat.resolve``.
    """
    policy = remat_lib.resolve(remat, remat_policy)
    d = cfg.d_model
    boundary = cfg.num_periods * seq * d * _stream_bytes(cfg, act_bytes)
    widths = [d * 6]  # qkv + attn out + residuals
    if cfg.is_moe:
        widths.append(cfg.experts_per_token * cfg.moe_d_ff * 3 * cfg.capacity_factor)
    elif cfg.d_ff:
        widths.append(cfg.d_ff * 3)
    if cfg.ssm_state:
        widths.append(cfg.ssm_d_inner * 4)
    if cfg.lru_width:
        widths.append(cfg.lru_width * 6)
    period_live = seq * int(max(widths)) * act_bytes * cfg.pattern_len
    logits_live = seq * cfg.vocab_size * 4 // 8  # blocked CE kernel: 1/8 vocab
    if policy == "none":
        live = cfg.num_periods * period_live
    elif policy == "dots":
        live = period_live + int(
            DOTS_SAVED_FRACTION * (cfg.num_periods - 1) * period_live)
    elif policy == "period":
        live = period_live
    else:  # "full"
        live = -(-period_live // cfg.pattern_len)
    return boundary + live + logits_live


def pipeline_activation_bytes_per_sample(cfg: ModelConfig, seq: int,
                                         stages: int, act_bytes: int = 2,
                                         remat: bool = True,
                                         remat_policy: Optional[str] = None
                                         ) -> int:
    """Per-device live activation bytes for ONE local sample under the
    1F1B pipelined executor (engine Layer 11) with ``stages`` stages.

    The executor keeps *stage-local activations × the in-flight micro-batch
    count*: 1F1B's warmup depth bounds the number of in-flight micro-batches
    per stage at ``stages``, and each in-flight micro-batch holds exactly
    one stage-INPUT carry (the executor rematerializes the stage forward
    from that carry during the backward tick — stage-level remat). Terms:

      rings        2 depth-``stages`` rings (arriving-activation queue +
                   backward residuals), each slot one residual-stream carry
                   (seq * d_model);
      stage live   ONE stage's forward/backward working set: its share of
                   the period boundaries (num_periods / stages) plus the
                   remat policy's live term — the same lattice scaling as
                   :func:`activation_bytes_per_sample`, with the period
                   count cut to the stage's share;
      logits       the blocked-CE logits slice. Charged on every device:
                   the SPMD-masked schedule traces the (masked) loss head
                   on all stages, so its buffer is live everywhere.
    """
    if stages < 1:
        raise ValueError(f"stages must be >= 1, got {stages}")
    policy = remat_lib.resolve(remat, remat_policy)
    d = cfg.d_model
    carry = seq * d * _stream_bytes(cfg, act_bytes)
    rings = 2 * stages * carry
    per_stage = -(-cfg.num_periods // stages)
    widths = [d * 6]
    if cfg.is_moe:
        widths.append(cfg.experts_per_token * cfg.moe_d_ff * 3
                      * cfg.capacity_factor)
    elif cfg.d_ff:
        widths.append(cfg.d_ff * 3)
    if cfg.ssm_state:
        widths.append(cfg.ssm_d_inner * 4)
    if cfg.lru_width:
        widths.append(cfg.lru_width * 6)
    period_live = seq * int(max(widths)) * act_bytes * cfg.pattern_len
    logits_live = seq * cfg.vocab_size * 4 // 8
    if policy == "none":
        live = per_stage * period_live
    elif policy == "dots":
        live = period_live + int(
            DOTS_SAVED_FRACTION * (per_stage - 1) * period_live)
    elif policy == "period":
        live = period_live
    else:  # "full"
        live = -(-period_live // cfg.pattern_len)
    return rings + per_stage * carry + live + logits_live


# ---------------------------------------------------------------------------
# Serving (engine Layer 10): KV-cache admission terms
# ---------------------------------------------------------------------------

# bytes of the per-slot ring-position bookkeeping (``pos`` int32 per entry)
CACHE_POS_BYTES = 4


def kv_bytes_per_token(cfg: ModelConfig, cache_bytes: int = 2) -> int:
    """Decode-cache bytes ONE cached context token costs, summed over every
    attention layer — the serving mirror of
    :func:`activation_bytes_per_sample`. Each (global|local) layer stores a
    K and a V row (``num_kv_heads * head_dim``) plus the ring slot's
    absolute-position bookkeeping (int32); state-carrying layers
    (ssm / recurrent) contribute nothing here because their decode state is
    O(1) in the context length — see :func:`slot_state_bytes`.

    This is the quantity "The Limit of the Batch Size" turns into decode
    throughput: at a fixed HBM budget the admitted concurrent-request
    count is (budget - params - fixed) / (context * kv_bytes_per_token).
    """
    per_layer = 2 * cfg.num_kv_heads * cfg.head_dim * cache_bytes \
        + CACHE_POS_BYTES
    n_attn = sum(1 for k in cfg.layer_pattern if k in ("global", "local"))
    return cfg.num_periods * n_attn * per_layer


def slot_state_bytes(cfg: ModelConfig, cache_bytes: int = 2) -> int:
    """Context-length-independent decode state per request slot: the SSD
    state + conv tail of ``ssm`` slots and the RG-LRU hidden + conv tail of
    ``recurrent`` slots (matching ``models/{ssm,recurrent}.init_*_cache``)."""
    total = 0
    for kind in cfg.layer_pattern:
        if kind == "ssm" and cfg.ssm_state:
            conv_dim = cfg.ssm_d_inner + 2 * cfg.ssm_state
            total += (cfg.ssm_num_heads * cfg.ssm_head_dim * cfg.ssm_state * 4
                      + (cfg.conv_width - 1) * conv_dim * cache_bytes)
        elif kind == "recurrent" and cfg.lru_width:
            total += (cfg.lru_width * 4
                      + (cfg.conv_width - 1) * cfg.lru_width * cache_bytes)
    return cfg.num_periods * total


def kv_slot_bytes(cfg: ModelConfig, max_len: int, cache_bytes: int = 2,
                  global_window: Optional[int] = None) -> int:
    """Total decode-cache bytes ONE request slot holds at context capacity
    ``max_len``, honoring per-layer ring windows: a ``local`` layer's ring
    is bounded to ``sliding_window`` entries and a ``global`` layer to
    ``global_window`` (when serving a capped long-context variant), so a
    slot costs less than ``max_len * kv_bytes_per_token`` whenever any
    window is tighter than the context."""
    per_entry = 2 * cfg.num_kv_heads * cfg.head_dim * cache_bytes \
        + CACHE_POS_BYTES
    total = 0
    for kind in cfg.layer_pattern:
        if kind in ("global", "local"):
            w = cfg.sliding_window if kind == "local" else global_window
            entries = max_len if w is None else min(w, max_len)
            total += entries * per_entry
    return cfg.num_periods * total + slot_state_bytes(cfg, cache_bytes)


def prefill_activation_bytes_per_sample(cfg: ModelConfig, seq: int,
                                        act_bytes: int = 2) -> int:
    """Forward-only (no backward, no checkpoint boundary) live bytes for
    ONE prefill sample of length ``seq``: the residual stream (x plus one
    block output in flight) and one period's working set — under
    ``lax.scan`` period ``i``'s intermediates are freed before ``i+1``
    runs — plus the last-token logits row. The per-sample KV bytes the
    prefill *builds* are accounted by the caller through
    :func:`kv_slot_bytes` (they persist past the prefill)."""
    d = cfg.d_model
    stream = 2 * seq * d * _stream_bytes(cfg, act_bytes)
    widths = [d * 6]
    if cfg.is_moe:
        widths.append(cfg.experts_per_token * cfg.moe_d_ff * 3
                      * cfg.capacity_factor)
    elif cfg.d_ff:
        widths.append(cfg.d_ff * 3)
    if cfg.ssm_state:
        widths.append(cfg.ssm_d_inner * 4)
    if cfg.lru_width:
        widths.append(cfg.lru_width * 6)
    period_live = seq * int(max(widths)) * act_bytes * cfg.pattern_len
    logits_live = cfg.vocab_size * 4
    return stream + period_live + logits_live


@dataclasses.dataclass(frozen=True)
class ServeMemoryEstimate:
    """Serving twin of :class:`MemoryEstimate` — affine in the number of
    admitted decode slots (at a fixed prefill micro-batch size), which is
    what :func:`engine.serving.plan_serve` binary-searches against."""
    params_bytes: int
    kv_slot_bytes: int  # decode-cache bytes per admitted request slot
    prefill_bytes_per_sample: int  # activations + the cache being built
    fixed_bytes: int

    def total(self, slots: int, prefill_micro: int = 0) -> int:
        """Peak bytes with ``slots`` admitted decode slots and a prefill
        micro-batch of ``prefill_micro`` in flight. Conservative the same
        way :meth:`MemoryEstimate.total` is: the prefill term is charged
        even though admission could time-slice prefill against decode —
        over-counting never over-admits."""
        return (self.params_bytes + self.fixed_bytes
                + self.kv_slot_bytes * slots
                + self.prefill_bytes_per_sample * prefill_micro)

    def affine_coeffs(self, prefill_micro: int = 0) -> tuple:
        """(fixed, per_slot) with total(s) == fixed + per_slot * s."""
        return self.total(0, prefill_micro), self.kv_slot_bytes


def serve_estimate(cfg: ModelConfig, max_len: int, *,
                   prefill_len: Optional[int] = None,
                   cache_bytes: int = 2, act_bytes: int = 2,
                   global_window: Optional[int] = None,
                   mesh=None, fsdp_params: bool = False
                   ) -> ServeMemoryEstimate:
    """Analytic serving-memory model: params (fp32 inference weights, no
    grads / optimizer state / update transient) + per-slot KV bytes at
    ``max_len`` + per-sample prefill cost at ``prefill_len`` (default
    ``max_len``). ``mesh`` switches to the PER-DEVICE estimate the same
    way :func:`estimate` does — params discounted by the real sharding
    ratio (``fsdp_params=False`` models the replicating data-parallel
    serving replica), cache/activation terms budget the *local* slot and
    prefill counts."""
    if mesh is not None:
        p_bytes = int(cfg.param_count() * 4
                      * param_shard_ratio(cfg, mesh, fsdp=fsdp_params))
    else:
        p_bytes = cfg.param_count() * 4
    pf = max_len if prefill_len is None else prefill_len
    slot = kv_slot_bytes(cfg, max_len, cache_bytes, global_window)
    return ServeMemoryEstimate(
        params_bytes=p_bytes,
        kv_slot_bytes=slot,
        prefill_bytes_per_sample=(
            prefill_activation_bytes_per_sample(cfg, pf, act_bytes)
            + slot),
        fixed_bytes=64 * 1024 ** 2,
    )


class _MeshDims:
    """Axis-name → size view of a mesh — the only part of a mesh the
    sharding policy reads, and a hashable cache key for the ratio below."""

    def __init__(self, dims):
        self.shape = dict(dims)
        self.axis_names = tuple(self.shape)


def param_shard_ratio(cfg: ModelConfig, mesh, *, fsdp: bool = True) -> float:
    """Per-device fraction of the parameter bytes under the REAL sharding
    policy (``launch/sharding.param_specs``), mesh axes and divisibility
    included — leaves whose dims do not divide the mesh stay replicated
    and cost full bytes, which a blanket ``/ (tp * fsdp)`` discount would
    understate. Grads and optimizer state shard with the same specs, so
    one ratio covers all three terms. ``fsdp=False`` models a
    data-parallel-only executor that replicates params (the engine's
    ``ShardedExecutor``): only the model axis discounts. Memoized: one
    auto plan calls ``estimate`` once per lattice policy, and the ratio
    only depends on (config, mesh axis sizes, fsdp)."""
    return _param_shard_ratio(cfg, tuple(mesh.shape.items()), fsdp)


@functools.lru_cache(maxsize=256)
def _param_shard_ratio(cfg: ModelConfig, mesh_dims: tuple,
                       fsdp: bool) -> float:
    import jax  # deferred: keep module import light
    from jax.sharding import PartitionSpec as P
    from ..launch import sharding as sharding_lib  # deferred: no cycle
    from ..models import encdec, transformer

    mesh = _MeshDims(mesh_dims)
    init = encdec.init_params if cfg.is_encdec else transformer.init_params
    shapes = jax.eval_shape(lambda k: init(cfg, k), jax.random.PRNGKey(0))
    specs = sharding_lib.param_specs(shapes, mesh, fsdp=fsdp)

    def shard_factor(spec) -> int:
        f = 1
        for entry in spec:
            if entry is None:
                continue
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                f *= mesh.shape[ax]
        return f

    total = sharded = 0
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(x, P))):
        total += leaf.size
        sharded += -(-leaf.size // shard_factor(spec))
    return sharded / total if total else 1.0


def estimate(cfg: ModelConfig, seq: int, *, tp: int = 1, fsdp: int = 1,
             opt_slots: Optional[int] = None, act_bytes: int = 2,
             remat: bool = True, remat_policy: Optional[str] = None,
             optimizer: str = "sgd",
             fused_update: bool = False, mesh=None,
             fsdp_params: bool = True, pipeline: bool = False
             ) -> MemoryEstimate:
    """``optimizer`` names the update rule (state-slot count + step-❺
    transient); ``fused_update=True`` models the flat in-place path
    (``--executor flat``) whose update transient is eliminated. An explicit
    ``opt_slots`` overrides the per-optimizer slot count; ``remat_policy``
    overrides the legacy ``remat`` bool (see
    :func:`activation_bytes_per_sample`).

    ``mesh`` switches to the PER-DEVICE estimate (engine Layer 6): the
    params/grads/opt-state/update-transient terms are discounted by the
    real sharding policy (:func:`param_shard_ratio` — honors divisibility
    and ``fsdp_params``; the manual ``tp``/``fsdp`` divisors are ignored)
    and the activation term is divided by the model axis only — the data
    axis enters through the *local* micro-batch the caller budgets with,
    not through this estimate.

    ``pipeline=True`` (engine Layer 11) reinterprets the mesh's model axis
    as 1F1B pipeline stages: the activation term becomes
    :func:`pipeline_activation_bytes_per_sample` — stage-local activations
    × the in-flight micro-batch count (warmup depth == stages) — instead
    of the tensor-parallel ``// tp`` discount."""
    if mesh is not None:
        from ..launch import mesh as mesh_lib  # deferred: no cycle
        tp = mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS)
        p_bytes = int(cfg.param_count() * 4
                      * param_shard_ratio(cfg, mesh, fsdp=fsdp_params))
    else:
        p_bytes = cfg.param_count() * 4 // (tp * fsdp)
    if pipeline and tp > 1:
        act_per_sample = pipeline_activation_bytes_per_sample(
            cfg, seq, tp, act_bytes, remat, remat_policy)
    else:
        act_per_sample = activation_bytes_per_sample(
            cfg, seq, act_bytes, remat, remat_policy) // tp
    slots = _resolve_slots(optimizer, opt_slots)
    return MemoryEstimate(
        params_bytes=p_bytes,
        grads_bytes=p_bytes,
        opt_bytes=slots * p_bytes,
        activation_bytes_per_sample=act_per_sample,
        fixed_bytes=64 * 1024 ** 2,
        update_transient_bytes=update_transient_bytes(
            p_bytes, optimizer, fused_update, opt_slots=slots),
    )


def suggest_micro_batch_size(cfg: ModelConfig, seq: int, mini_batch: int, *,
                             budget_bytes: int = V5E_HBM_BYTES, tp: int = 1,
                             fsdp: int = 1, opt_slots: Optional[int] = None,
                             act_bytes: int = 2,
                             remat: bool = True,
                             remat_policy: Optional[str] = None,
                             optimizer: str = "sgd",
                             fused_update: bool = False, mesh=None,
                             fsdp_params: bool = True,
                             pipeline: bool = False) -> Optional[int]:
    """Largest power-of-two micro-batch (≤ mini_batch) that fits the budget.
    Returns None if even micro-batch 1 exceeds the budget (the model itself
    does not fit — MBS cannot help; that needs more model parallelism).
    The step-❺ transient term (see :func:`update_transient_bytes`) stops
    this from admitting micro-batches that would OOM at the update; with
    ``fused_update=True`` that headroom is reclaimed for activations.
    With ``mesh`` the estimate is per device and the suggested size is the
    per-device LOCAL micro-batch (``mini_batch`` should then be the local
    share — the planner passes ``mini // data_parallel``)."""
    est = estimate(cfg, seq, tp=tp, fsdp=fsdp, opt_slots=opt_slots,
                   act_bytes=act_bytes, remat=remat,
                   remat_policy=remat_policy, optimizer=optimizer,
                   fused_update=fused_update, mesh=mesh,
                   fsdp_params=fsdp_params, pipeline=pipeline)
    best = None
    m = 1
    while m <= mini_batch:
        if est.total(m) <= budget_bytes:
            best = m
        m *= 2
    return best


def suggest_remat_policy_and_micro(
        cfg: ModelConfig, seq: int, mini_batch: int, *,
        budget_bytes: int = V5E_HBM_BYTES, tp: int = 1, fsdp: int = 1,
        opt_slots: Optional[int] = None, act_bytes: int = 2,
        optimizer: str = "sgd", fused_update: bool = False,
        target_micro: Optional[int] = None, mesh=None,
        fsdp_params: bool = True, pipeline: bool = False
        ) -> Tuple[str, Optional[int]]:
    """Joint (remat policy, micro-batch) choice — engine Layer 5.

    Walks the lattice from cheapest recompute to heaviest, returning the
    FIRST policy whose admitted micro-batch reaches ``target_micro``
    (default: the whole mini-batch — i.e. no gradient accumulation needed).
    When no policy reaches the target the policy admitting the largest
    micro-batch wins, ties broken toward cheaper recompute — heavier remat
    is bought only when it actually converts into batch. Returns
    ``(policy, None)`` with the heaviest policy when even micro-batch 1
    does not fit anywhere (the model needs more parallelism, not MBS).
    """
    target = min(target_micro or mini_batch, mini_batch)
    best_policy, best_micro = POLICY_ORDER[-1], None
    for policy in POLICY_ORDER:
        micro = suggest_micro_batch_size(
            cfg, seq, mini_batch, budget_bytes=budget_bytes, tp=tp,
            fsdp=fsdp, opt_slots=opt_slots, act_bytes=act_bytes,
            remat_policy=policy, optimizer=optimizer,
            fused_update=fused_update, mesh=mesh, fsdp_params=fsdp_params,
            pipeline=pipeline)
        if micro is not None and micro >= target:
            return policy, micro
        if micro is not None and (best_micro is None or micro > best_micro):
            best_policy, best_micro = policy, micro
    return best_policy, best_micro


def max_minibatch_without_mbs(cfg: ModelConfig, seq: int, *,
                              budget_bytes: int = V5E_HBM_BYTES, tp: int = 1,
                              fsdp: int = 1, opt_slots: Optional[int] = None,
                              act_bytes: int = 2,
                              remat: bool = True,
                              remat_policy: Optional[str] = None,
                              optimizer: str = "sgd",
                              fused_update: bool = False) -> int:
    """The paper's "w/o MBS" failure point: the largest mini-batch whose
    whole-batch activations fit (beyond it, the run 'Fails')."""
    est = estimate(cfg, seq, tp=tp, fsdp=fsdp, opt_slots=opt_slots,
                   act_bytes=act_bytes, remat=remat,
                   remat_policy=remat_policy, optimizer=optimizer,
                   fused_update=fused_update)
    m = 0
    while est.total(m + 1) <= budget_bytes:
        m += 1
        if m > 1 << 24:
            break
    return m
