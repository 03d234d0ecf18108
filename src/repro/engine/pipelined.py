"""Pipeline-parallel MBS execution (engine Layer 11): 1F1B over the mesh's
``model`` axis, composed with the Layer-6 data-parallel path.

The paper's micro-batches are exactly the currency of pipeline schedules:
a 1F1B schedule streams the :class:`~.plan.MBSPlan`'s ``num_micro_batches``
through ``stages`` model shards with at most ``stages`` micro-batches in
flight per device — which is why ``plan_mbs(pipeline=True)`` budgets
stage-local activations × warmup depth instead of whole-model activations.

Schedule (closed form, host-side tables — no device control flow):

    t_f(s, i) = s + i                  i <= S-1-s   (warmup)
              = 2 i + s                otherwise    (steady 1F1B)
    t_b(s, j) = 2 S - 1 - s + 2 j
    ticks     T = 2 (M + S - 1)

Forward and backward never collide on one stage (parity: ``2(i-j)`` is
even, ``2S-1-2s`` is odd), each stage's input for micro ``i`` arrives at
least one tick before ``t_f(s, i)``, and a depth-``S`` ring per buffer is
collision-free (the next same-slot write lands after the consumption).

SPMD realization: every device traces the SAME program — per tick one
*masked* forward and one *masked* backward, selected by indexing the
host-side tables with the traced stage id ``lax.axis_index("model")``.
Masked work runs on clamped/stale-but-finite inputs and is discarded
(forward: ring writes gated off; backward: all-zero cotangents make every
gradient contribution exactly zero by linearity of the VJP). This is the
standard SPMD-masking cost: ~2× the FLOPs of a true MIMD 1F1B, traded for
a single jittable program with no per-stage executables.

Stage function contract (:class:`StagedLoss`): ``prelude`` (embedding) is
traced on every stage but a ``where(stage == 0, prelude(mb), x_in)``
select kills its gradient elsewhere; ``finale`` (head + loss) is traced on
every stage but only the LAST stage's loss cotangent is 1 — autodiff then
routes shared-parameter gradients to exactly one stage each, and the
cross-stage sum happens in the one (data+model) psum below.

Collective structure per mini-batch (``defer_sync=True``, no FSDP):

  * 2 ``ppermute`` rings per tick (activations +1, cotangents −1) — the
    point-to-point stage-boundary traffic, 2 T total;
  * exactly ONE data-axis-only psum (the flat stage-gradient reduction —
    "one gradient all-reduce per mini-batch on the DP axis", the same
    amortization :mod:`engine.sharded` proves for pure DP);
  * exactly ONE (data+model) psum (shared-param grads + loss + metrics +
    valid count, masked by ``is_last`` so nothing is counted ×S).

``defer_sync=False`` is the per-micro-sync baseline (one data-axis psum
per backward tick) that the analysis negative-control asserts against.

``fsdp=True`` additionally shards stage-local parameters over the data
axis per ``launch/sharding.param_specs`` (with the ``model`` entries
stripped — the model axis is spent on the stage dim), gathers them
just-in-time inside the step (``all_gather(tiled=True)``) and reduces
gradients with ``psum_scatter`` — a real FSDP forward, proven by the
equivalence tests rather than the exact-psum-count census.

The optimizer update runs OUTSIDE the ``shard_map`` on the recombined
params-shaped gradient tree, so optimizer state never splits across the
(shared, staged) partition and the Layer-9 guard applies unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import spans
from ..launch import mesh as mesh_lib
from ..launch import sharding
from . import exec_core, faults
from .executors import _as_plan
from .plan import MBSPlan
from .sharded import _local_valid_count, batch_partition_specs, psum_flat


def schedule_1f1b(stages: int, micros: int
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side 1F1B tick tables (see module doc for the closed form).

    Returns ``(fwd, bwd, recv, ticks)``: ``fwd[t, s]`` / ``bwd[t, s]`` is
    the micro-batch index stage ``s`` runs forward/backward at tick ``t``
    (−1 = idle); ``recv[t, s]`` is the micro index whose activation stage
    ``s`` receives from ``s−1`` at the END of tick ``t`` (−1 masks the
    ppermute ring wrap into stage 0)."""
    if stages < 1 or micros < 1:
        raise ValueError(f"need stages >= 1 and micros >= 1, got "
                         f"({stages}, {micros})")
    ticks = 2 * (micros + stages - 1)
    fwd = -np.ones((ticks, stages), np.int32)
    bwd = -np.ones((ticks, stages), np.int32)
    for s in range(stages):
        for i in range(micros):
            t = s + i if i <= stages - 1 - s else 2 * i + s
            fwd[t, s] = i
        for j in range(micros):
            bwd[2 * stages - 1 - s + 2 * j, s] = j
    recv = -np.ones((ticks, stages), np.int32)
    recv[:, 1:] = fwd[:, :-1]
    return fwd, bwd, recv, ticks


@dataclasses.dataclass(frozen=True)
class StagedLoss:
    """A loss function split for pipeline execution.

    The params tree must hold ONE subtree (``params[stacked_key]``) whose
    leaves all carry a leading ``num_layers`` scan dim; everything else is
    "shared" (embedding, head, final norm). The three callables factor the
    loss as ``finale(shared, stage_fn^S(.., prelude(shared, mb)), mb)``:

      prelude(shared, mb) -> x        the stage-0 entry (embedding); the
                                      output pytree is the residual carry
                                      every stage maps to itself;
      stage_fn(stage_params, x) -> x  one stage: leaves lead with
                                      ``num_layers // stages`` (scan them);
      finale(shared, x, mb)           -> (raw_loss_sum, metrics): the RAW
                                      per-shard loss SUM (exact_denom=1
                                      semantics — the executor divides by
                                      the global valid count after psum).
    """
    num_layers: int
    prelude: Callable[[Any, Any], Any]
    stage_fn: Callable[[Any, Any], Any]
    finale: Callable[[Any, Any, Any], Tuple[jnp.ndarray, Dict[str, Any]]]
    stacked_key: str = "blocks"

    def partition(self, params, stages: int) -> Tuple[Any, Any]:
        """(shared, staged): staged leaves reshaped (L, ...) ->
        (stages, L/stages, ...) so the stage dim shards over ``model``."""
        if self.num_layers % stages:
            raise ValueError(
                f"pipeline stage count {stages} does not divide the block "
                f"stack ({self.num_layers} layers) — pick a model axis "
                "that divides the layer count evenly")
        per = self.num_layers // stages
        shared = {k: v for k, v in params.items() if k != self.stacked_key}
        staged = jax.tree.map(
            lambda a: a.reshape((stages, per) + a.shape[1:]),
            params[self.stacked_key])
        return shared, staged

    def combine(self, shared, staged):
        """Inverse of :meth:`partition` — rebuilds the params-shaped tree
        (used on gradients, so the optimizer never sees the split)."""
        stacked = jax.tree.map(
            lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]),
            staged)
        out = dict(shared)
        out[self.stacked_key] = stacked
        return out


def _mentions(entry, axis: str) -> bool:
    if entry is None:
        return False
    if isinstance(entry, (tuple, list)):
        return axis in entry
    return entry == axis


def _strip_model(spec: P) -> Tuple:
    """Drop ``model`` mesh-axis entries from a PartitionSpec (the model
    axis is spent on the pipeline stage dim, not tensor parallelism)."""
    out = []
    for e in spec:
        if isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a != mesh_lib.MODEL_AXIS)
            out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
        else:
            out.append(None if e == mesh_lib.MODEL_AXIS else e)
    return tuple(out)


def _map_specs(fn, spec_tree):
    return jax.tree.map(fn, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


class PipelinedExecutor:
    """1F1B pipeline + DP executor (see module doc).

    Implements the :class:`engine.executors.Executor` protocol over a 2-D
    ``data × model`` mesh: the model axis runs ``stages`` pipeline stages,
    the (pod, data) axes replicate the schedule over ``local_micro``
    sample shards. ``fsdp=True`` shards stage-local params over ``data``
    per ``launch/sharding.param_specs`` with just-in-time gathers.
    """
    name = "pipelined"

    def __init__(self, staged: StagedLoss, optimizer, plan, *, mesh,
                 defer_sync: bool = True, fsdp: bool = False,
                 donate: bool = True, guard: bool = False):
        self.staged = staged
        self.optimizer = optimizer
        self.plan: MBSPlan = _as_plan(plan)
        self.mesh = mesh
        self.axes = mesh_lib.batch_axes(mesh)
        self.dp = mesh_lib.data_parallel_size(mesh)
        self.stages = mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS)
        self.defer_sync = defer_sync
        self.fsdp = fsdp
        self.guard = guard
        self._donate = donate
        self._step_jit = None
        self._grads_jit = None
        if self.stages < 2:
            raise ValueError(
                "PipelinedExecutor needs a mesh model axis of >= 2 stages "
                f"(got {self.stages}); for pure data parallelism use "
                "ShardedExecutor")
        if staged.num_layers % self.stages:
            raise ValueError(
                f"pipeline stage count {self.stages} does not divide the "
                f"block stack ({staged.num_layers} layers) — pick a model "
                "axis that divides the layer count evenly")
        if self.plan.pipeline_stages > 1 \
                and self.plan.pipeline_stages != self.stages:
            raise ValueError(
                f"plan was admitted for {self.plan.pipeline_stages} "
                f"pipeline stages but the mesh's model axis is "
                f"{self.stages} — rebuild the plan with this mesh")
        if self.plan.micro_batch_size % self.dp:
            raise ValueError(
                f"micro-batch {self.plan.micro_batch_size} does not divide "
                f"over {self.dp} data-parallel workers — build the plan "
                "with plan_mbs(mesh=...) so sizes stay divisible")
        if self.plan.normalization == "paper" and self.plan.pad:
            raise ValueError(
                'a ragged "paper" plan cannot be pipelined exactly (the '
                "tail pad lands on one worker's shard) — use "
                'normalization="exact" (plan_mbs auto-upgrades ragged plans)')
        if fsdp and not defer_sync:
            raise ValueError(
                "defer_sync=False is the per-micro-sync comparison baseline "
                "and does not compose with fsdp=True (psum_scatter already "
                "replaces the deferred psum)")

    # -- staging ------------------------------------------------------------

    def batch_shardings(self, split):
        specs = batch_partition_specs(split, self.plan.micro_batch_size,
                                      self.axes)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def stage(self, split):
        return jax.device_put(split, self.batch_shardings(split))

    # -- parameter partition specs ------------------------------------------

    def _param_specs(self, shared, staged):
        """(shared_specs, staged_specs) PartitionSpec trees. Non-FSDP:
        staged leaves shard ONLY the leading stage dim over ``model``
        (sharding itself does the stage selection — no dynamic indexing of
        params by stage id); shared params replicate. FSDP: stage-LOCAL
        shapes go through the real ``launch/sharding.param_specs`` policy
        (under a stacked root so the layer scan dim is skipped), with
        ``model`` entries stripped."""
        if not self.fsdp:
            staged_specs = jax.tree.map(
                lambda x: P(mesh_lib.MODEL_AXIS, *([None] * (x.ndim - 1))),
                staged)
            shared_specs = jax.tree.map(lambda x: P(), shared)
            return shared_specs, staged_specs
        stage_view = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), staged)
        policy = sharding.param_specs(
            {"blocks": stage_view, "shared": shared}, self.mesh, fsdp=True)
        staged_specs = _map_specs(
            lambda sp: P(mesh_lib.MODEL_AXIS, *_strip_model(sp)),
            policy["blocks"])
        shared_specs = _map_specs(lambda sp: P(*_strip_model(sp)),
                                  policy["shared"])
        return shared_specs, staged_specs

    def _gather_fsdp(self, tree_, specs):
        """Just-in-time parameter gather: undo the data-axis shards so the
        stage computes on full stage-local params."""
        def g(x, spec):
            for d, e in enumerate(spec):
                if _mentions(e, mesh_lib.DATA_AXIS):
                    x = jax.lax.all_gather(x, mesh_lib.DATA_AXIS, axis=d,
                                           tiled=True)
            return x
        return jax.tree.map(g, tree_, specs)

    @jax.named_scope(spans.GRAD_SYNC)
    def _scatter_grads(self, tree_, specs, *, sum_model: bool):
        """Reduce full gradients back to the FSDP layout: ``psum_scatter``
        on sharded dims, plain data psum on unsharded leaves. ``sum_model``
        first sums the stage contributions (shared params only)."""
        def sfn(g, spec):
            if sum_model:
                g = jax.lax.psum(g, mesh_lib.MODEL_AXIS)
            scattered = False
            for d, e in enumerate(spec):
                if _mentions(e, mesh_lib.DATA_AXIS):
                    g = jax.lax.psum_scatter(
                        g, mesh_lib.DATA_AXIS, scatter_dimension=d,
                        tiled=True)
                    scattered = True
            if not scattered:
                g = jax.lax.psum(g, mesh_lib.DATA_AXIS)
            return g
        return jax.tree.map(sfn, tree_, specs)

    # -- the local (per-device) 1F1B program --------------------------------

    def _local_fn(self, n_s: int, shared_specs, staged_specs):
        """The shard_mapped body: returns NORMALIZED (shared grads, staged
        grads [leading stage dim], loss, metrics) for this device."""
        S = self.stages
        fwd_tab, bwd_tab, recv_tab, ticks = schedule_1f1b(S, n_s)
        spec = self.staged
        perm_f = [(i, (i + 1) % S) for i in range(S)]
        perm_b = [(i, (i - 1) % S) for i in range(S)]

        def take_micro(split, idx):
            safe = jnp.maximum(idx, 0)
            return jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, safe, 0,
                                                       keepdims=False),
                split)

        def ring_read(ring, idx):
            slot = jnp.maximum(idx, 0) % S
            return jax.tree.map(
                lambda r: jax.lax.dynamic_index_in_dim(r, slot, 0,
                                                       keepdims=False),
                ring)

        def ring_write(ring, val, idx, on):
            slot = jnp.maximum(idx, 0) % S

            def wr(r, v):
                new = jax.lax.dynamic_update_index_in_dim(
                    r, v.astype(r.dtype), slot, 0)
                return jnp.where(on, new, r)
            return jax.tree.map(wr, ring, val)

        def ppermute(tree_, perm):
            return jax.tree.map(
                lambda v: jax.lax.ppermute(v, mesh_lib.MODEL_AXIS, perm),
                tree_)

        def local(shared, staged_block, split):
            if self.fsdp:
                shared = self._gather_fsdp(shared, shared_specs)
                # the block keeps its (size-1) stage dim, so the full spec
                # aligns: entry 0 is `model`, which the gather skips
                staged_block = self._gather_fsdp(staged_block, staged_specs)
            stage_p = jax.tree.map(lambda x: x[0], staged_block)
            s_idx = jax.lax.axis_index(mesh_lib.MODEL_AXIS)
            is_first = s_idx == 0
            is_last = s_idx == S - 1

            def full_stage(sp, sh, x_in, mb):
                x0 = spec.prelude(sh, mb)
                x = jax.tree.map(
                    lambda a, b: jnp.where(is_first, a, b), x0, x_in)
                y = spec.stage_fn(sp, x)
                loss_raw, metrics = spec.finale(sh, y, mb)
                return (y, loss_raw), metrics

            def stage_forward(sp, sh, x_in, mb):
                x0 = spec.prelude(sh, mb)
                x = jax.tree.map(
                    lambda a, b: jnp.where(is_first, a, b), x0, x_in)
                return spec.stage_fn(sp, x)

            mb0 = take_micro(split, jnp.asarray(0, jnp.int32))
            x_abs = jax.eval_shape(spec.prelude, shared, mb0)
            zeros = lambda sds: jnp.zeros(sds.shape, sds.dtype)
            queue = jax.tree.map(
                lambda sds: jnp.zeros((S,) + sds.shape, sds.dtype), x_abs)
            resid = jax.tree.map(
                lambda sds: jnp.zeros((S,) + sds.shape, sds.dtype), x_abs)
            cot = jax.tree.map(zeros, x_abs)
            (_, _), metrics_abs = jax.eval_shape(
                full_stage, stage_p, shared, x_abs, mb0)
            metric_acc = jax.tree.map(zeros, metrics_abs)
            acc_stage = exec_core.init_accum(stage_p, self.plan.accum_dtype)
            acc_shared = exec_core.init_accum(shared, self.plan.accum_dtype)
            loss_acc = jnp.zeros((), jnp.float32)

            for t in range(ticks):
                f_i = jnp.asarray(fwd_tab[t])[s_idx]
                b_j = jnp.asarray(bwd_tab[t])[s_idx]
                r_i = jnp.asarray(recv_tab[t])[s_idx]
                f_on = f_i >= 0
                b_on = b_j >= 0

                if (bwd_tab[t] >= 0).any():
                    # masked backward: recompute the stage from its saved
                    # INPUT (stage-level remat) and pull masked cotangents
                    mb_b = take_micro(split, b_j)
                    x_res = ring_read(resid, b_j)
                    (_, loss_raw), vjp_fn, metrics = jax.vjp(
                        lambda sp_, sh_, xi: full_stage(sp_, sh_, xi, mb_b),
                        stage_p, shared, x_res, has_aux=True)
                    dy_on = jnp.logical_and(b_on, jnp.logical_not(is_last))
                    dy = jax.tree.map(
                        lambda c: jnp.where(dy_on, c, jnp.zeros_like(c)),
                        cot)
                    dl = jnp.where(jnp.logical_and(b_on, is_last),
                                   1.0, 0.0).astype(loss_raw.dtype)
                    d_sp, d_sh, dx = vjp_fn((dy, dl))
                    if not self.defer_sync:
                        # per-micro baseline: sync every backward tick
                        d_sp, d_sh = psum_flat((d_sp, d_sh), self.axes)
                    acc_stage = exec_core.accumulate(acc_stage, d_sp)
                    acc_shared = exec_core.accumulate(acc_shared, d_sh)
                    lmask = jnp.where(jnp.logical_and(b_on, is_last),
                                      1.0, 0.0)
                    loss_acc = loss_acc + loss_raw * lmask
                    metric_acc = jax.tree.map(
                        lambda a, m: a + m.astype(a.dtype) * lmask,
                        metric_acc, metrics)
                    # cotangents flow one stage back (depth-1 buffer: the
                    # receiver consumes it exactly next tick)
                    cot = ppermute(dx, perm_b)

                if (fwd_tab[t] >= 0).any():
                    mb_f = take_micro(split, f_i)
                    x_in = ring_read(queue, f_i)
                    y = stage_forward(stage_p, shared, x_in, mb_f)
                    resid = ring_write(resid, x_in, f_i, f_on)
                    y_recv = ppermute(y, perm_f)
                    queue = ring_write(queue, y_recv, r_i, r_i >= 0)

            valid = _local_valid_count(split) * jnp.where(is_last, 1.0, 0.0)
            if self.defer_sync and not self.fsdp:
                # the ONE gradient all-reduce per mini-batch on the DP axis
                acc_stage = psum_flat(acc_stage, self.axes)
            elif self.fsdp:
                acc_stage = self._scatter_grads(
                    acc_stage,
                    _map_specs(lambda sp: P(*sp[1:]), staged_specs),
                    sum_model=False)
            # shared grads + loss + metrics + valid cross stage boundaries:
            # one (data+model) psum (is_last masking stops ×S counting)
            if self.fsdp:
                acc_shared = self._scatter_grads(acc_shared, shared_specs,
                                                 sum_model=True)
                loss_acc, metric_acc, valid = psum_flat(
                    (loss_acc, metric_acc, valid),
                    self.axes + (mesh_lib.MODEL_AXIS,))
            elif self.defer_sync:
                acc_shared, loss_acc, metric_acc, valid = psum_flat(
                    (acc_shared, loss_acc, metric_acc, valid),
                    self.axes + (mesh_lib.MODEL_AXIS,))
            else:
                # per-micro mode already summed grads over data per tick;
                # only the shared stage contributions still need crossing
                acc_shared = psum_flat(acc_shared, (mesh_lib.MODEL_AXIS,))
                loss_acc, metric_acc, valid = psum_flat(
                    (loss_acc, metric_acc, valid),
                    self.axes + (mesh_lib.MODEL_AXIS,))
            scale = 1.0 / valid
            g_sh = jax.tree.map(lambda g: (g * scale).astype(g.dtype),
                                acc_shared)
            g_st = jax.tree.map(lambda g: ((g * scale).astype(g.dtype))[None],
                                acc_stage)
            loss = loss_acc * scale
            metrics = jax.tree.map(lambda m: m / (self.dp * n_s), metric_acc)
            return g_sh, g_st, loss, metrics

        return local

    def _sharded_grads(self, params, split):
        """(params-shaped normalized grads, loss, metrics) via shard_map."""
        shared, staged = self.staged.partition(params, self.stages)
        shared_specs, staged_specs = self._param_specs(shared, staged)
        split_specs = batch_partition_specs(
            split, self.plan.micro_batch_size, self.axes)
        n_s = jax.tree.leaves(split)[0].shape[0]
        local = self._local_fn(n_s, shared_specs, staged_specs)
        g_sh, g_st, loss, metrics = shard_map(
            local, mesh=self.mesh,
            in_specs=(shared_specs, staged_specs, split_specs),
            out_specs=(shared_specs, staged_specs, P(), P()),
            check_vma=False)(shared, staged, split)
        grads = self.staged.combine(g_sh, g_st)
        return grads, loss, metrics

    # -- the Executor surface -----------------------------------------------

    def make_train_step(self) -> Callable:
        """Pure (params, opt_state, split) -> (params, opt_state, metrics).
        The optimizer update runs outside the shard_map on the recombined
        gradient tree — opt state stays params-shaped and replicated."""
        def train_step(params, opt_state, micro_batches):
            grads, loss, metrics = self._sharded_grads(params, micro_batches)
            ok = None
            if self.guard:
                new_params, new_opt, ok = exec_core.guarded_update(
                    self.optimizer, grads, opt_state, params)
            else:
                new_params, new_opt = exec_core.apply_update(
                    self.optimizer, grads, opt_state, params)
            out = exec_core.finalize_metrics(metrics, loss, grads)
            if ok is not None:
                out["nonfinite"] = 1.0 - ok.astype(jnp.float32)
            return new_params, new_opt, out
        return train_step

    def trace_step(self, params, opt_state, micro_batches):
        """ClosedJaxpr of the full pipelined step (inputs may be
        ``ShapeDtypeStruct``s) for the ``repro.analysis`` jaxpr census."""
        return jax.make_jaxpr(self.make_train_step())(
            params, opt_state, micro_batches)

    def state_shardings(self, params, opt_state):
        """(params, opt_state) NamedSharding trees for the step's steady
        state: stacked block leaves (and their optimizer moments) live
        sharded over the ``model`` axis between steps — each stage owns
        its slice, which is exactly the layout the shard_map consumes and
        produces — while shared params and scalars replicate. Lowering
        with these as BOTH in- and out-shardings keeps the donated state
        fully aliased; left unspecified, GSPMD takes replicated inputs
        but emits model-sharded block outputs, and the layout mismatch
        silently costs one full block-stack copy per step (HLO001)."""
        key = self.staged.stacked_key
        n_layers = self.staged.num_layers
        rep = NamedSharding(self.mesh, P())
        staged_sh = NamedSharding(self.mesh, P(mesh_lib.MODEL_AXIS))

        def one(path, x):
            in_blocks = any(
                getattr(p, "key", getattr(p, "name", None)) == key
                for p in path)
            if (in_blocks and getattr(x, "ndim", 0) >= 1
                    and x.shape[0] == n_layers):
                return staged_sh
            return rep

        return (jax.tree_util.tree_map_with_path(one, params),
                jax.tree_util.tree_map_with_path(one, opt_state))

    def donated_state_bytes(self, params, opt_state) -> int:
        """Per-device bytes of the donated (params, opt_state) buffers
        under :meth:`state_shardings` — the HLO001 aliasing floor (block
        leaves count 1/stages, replicated leaves count whole)."""
        key = self.staged.stacked_key
        n_layers = self.staged.num_layers
        total = 0
        for tree_ in (params, opt_state):
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree_)[0]:
                b = int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
                in_blocks = any(
                    getattr(p, "key", getattr(p, "name", None)) == key
                    for p in path)
                if (in_blocks and getattr(leaf, "ndim", 0) >= 1
                        and leaf.shape[0] == n_layers):
                    b //= self.stages
                total += b
        return total

    def lower_step(self, params, opt_state, micro_batches, *,
                   donate: Optional[bool] = None):
        if donate is None:
            donate = self._donate
        p_sh, o_sh = self.state_shardings(params, opt_state)
        return jax.jit(
            self.make_train_step(),
            in_shardings=(p_sh, o_sh, self.batch_shardings(micro_batches)),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1, 2) if donate else (),
        ).lower(params, opt_state, micro_batches)

    def step_split(self, params, opt_state, micro_batches
                   ) -> Tuple[Any, Any, Dict[str, Any]]:
        faults.on_dispatch(self.plan)
        if self._step_jit is None:
            self._step_jit = jax.jit(
                self.make_train_step(),
                donate_argnums=(0, 1, 2) if self._donate else ())
        return self._step_jit(params, opt_state, micro_batches)

    def step(self, params, opt_state, minibatch
             ) -> Tuple[Any, Any, Dict[str, Any]]:
        return self.step_split(params, opt_state,
                               self.stage(self.plan.split(minibatch)))

    def gradients(self, params, micro_batches):
        """Accumulated NORMALIZED gradients + mini-batch loss under the
        1F1B schedule (params-shaped — comparable leaf-for-leaf with the
        single-device executors)."""
        if self._grads_jit is None:
            def run(p, mb):
                g, loss, _ = self._sharded_grads(p, mb)
                return g, loss
            self._grads_jit = jax.jit(run)
        return self._grads_jit(params, micro_batches)
