"""Mesh-aware MBS execution (engine Layer 6): data-parallel micro-batch
accumulation with DEFERRED gradient synchronization.

The paper fits a large global batch into ONE device's memory by splitting
it into micro-batches; data parallelism multiplies that across workers.
The cost to control is the gradient all-reduce: naive DP gradient
accumulation syncs every micro-batch (N_Sμ collectives per step), while
Algorithm 1 only *needs* the sum of all micro gradients — so the sync can
happen once per MINI-batch (``launch/mesh.py``'s amortization promise).

:class:`ShardedExecutor` wraps any executor from ``engine/executors.py``
and runs its accumulation strategy inside ``shard_map``:

  * every batch leaf is sharded on its sample dim over the mesh's batch
    axes ((pod, data)), so each device scans its ``local_micro`` =
    ``micro / data_parallel`` slice of every micro-batch;
  * the inner executor's ``raw_accumulate`` produces UN-normalized local
    sums (gradients, loss, metrics — no 1/N anywhere), using its own
    strategy: ``lax.scan`` (compiled), Pallas fused accumulate (fused),
    flat dtype buckets (flat), or an eager per-micro dispatch loop
    (streaming, see below);
  * all local sums — gradient leaves, loss, metrics, and the local valid-
    sample count — are raveled into ONE fp32 buffer and reduced with a
    single ``lax.psum``: exactly one all-reduce per mini-batch in the
    compiled HLO, independent of N_Sμ (the conformance test asserts this
    against a fully unrolled scan);
  * normalization divides by the GLOBAL valid count after the reduction
    (exact semantics — identical to "paper" mode for the uniform splits
    paper mode is valid for), then the optimizer update runs replicated
    on every device.

``defer_sync=False`` is the comparison baseline (one flat psum per
micro-batch, inner="compiled" only) used by ``--mesh-bench`` and the HLO
conformance test — it is what the deferred path saves.

The streaming inner keeps its eager character: one jitted shard_mapped
dispatch per micro-batch (no collective inside — the local partial sums
carry a leading ``data_parallel`` dim so they stay device-local between
dispatches), then one jitted sync+update dispatch per mini-batch.

Scope: pure data parallelism — params/opt state replicated inside the
step (``plan_mbs(mesh=..., fsdp_params=False)`` budgets accordingly).
TP/FSDP production meshes keep the launcher's GSPMD jit path. MoE router
aux follows the exact-mode contract per *local* micro-batch: router
statistics are per-device (standard DP-MoE semantics), so sharded MoE
losses are not bitwise-comparable to single-device runs.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import spans
from ..launch import mesh as mesh_lib
from . import exec_core, faults, flat as flat_lib
from .executors import EXECUTORS, _as_plan, get_executor
from .plan import MBSPlan


def _axis_entry(axes: Tuple[str, ...]):
    return axes if len(axes) > 1 else axes[0]


@jax.named_scope(spans.GRAD_SYNC)
def psum_flat(tree, axis_names):
    """One collective for a whole pytree: ravel every leaf into a single
    fp32 buffer, ``lax.psum`` it once, unpack. This is why the deferred
    step's HLO contains exactly ONE all-reduce — and it is the bucketing
    optimization (one large collective beats many small ones) for free."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    flat = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])
    flat = jax.lax.psum(flat, axis_names)
    out, off = [], 0
    for l in leaves:
        out.append(flat[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(treedef, out)


def batch_partition_specs(batch, micro: int, axes: Tuple[str, ...],
                          sample_dim_from: int = 1):
    """Per-leaf PartitionSpec sharding the SAMPLE dim — the first dim (at
    index >= ``sample_dim_from``; dim 0 is the scan axis of a split batch)
    whose size equals the global micro-batch size — over the batch axes.
    Every leaf must have such a dim: a replicated leaf inside shard_map
    would be double-counted by every worker's local accumulation."""
    entry = _axis_entry(axes)

    def spec_for(leaf):
        shape = leaf.shape
        for d in range(sample_dim_from, len(shape)):
            if shape[d] == micro:
                spec = [None] * len(shape)
                spec[d] = entry
                return P(*spec)
        raise ValueError(
            f"cannot shard batch leaf of shape {shape}: no dim (>= "
            f"{sample_dim_from}) equals the global micro-batch size {micro}"
            " — ShardedExecutor requires every leaf to carry the sample dim")

    return jax.tree.map(spec_for, batch)


def _local_valid_count(mb, sample_dims: int = 2) -> jnp.ndarray:
    """This shard's valid-sample weight (padding carries 0) — summed into
    the flat psum so the normalization denominator is the GLOBAL count.
    ``sample_dims`` is 2 for a split ``(N_Sμ, N_μ, ...)`` batch, 1 for a
    single micro-batch (the streaming per-micro dispatch)."""
    w = mb.get("sample_weight") if hasattr(mb, "get") else None
    if w is not None:
        return jnp.sum(w).astype(jnp.float32)
    leaf = jax.tree.leaves(mb)[0]
    n = 1.0
    for d in leaf.shape[:sample_dims]:
        n *= d
    return jnp.asarray(n, jnp.float32)


class ShardedExecutor:
    """Data-parallel wrapper around an inner MBS executor (see module doc).

    Implements the :class:`engine.executors.Executor` protocol; the
    ``inner`` name selects the local accumulation strategy ("compiled" |
    "streaming" | "fused" | "flat"). ``donate=False`` for callers that
    reuse params/opt-state across calls (A/B tests, benchmarks).

    ``guard=True`` (engine Layer 9) finite-checks the globally-reduced
    gradient inside ``_finalize`` — after the one psum, so the flag is
    replicated and every device takes the same skip/update branch — and
    surfaces a ``nonfinite`` metric for the supervisor."""
    name = "sharded"

    def __init__(self, loss_fn, optimizer, plan, *, mesh,
                 inner: str = "compiled", defer_sync: bool = True,
                 donate: bool = True, interpret: Optional[bool] = None,
                 block: Optional[int] = None, guard: bool = False):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.plan: MBSPlan = _as_plan(plan)
        self.mesh = mesh
        self.axes = mesh_lib.batch_axes(mesh)
        self.dp = mesh_lib.data_parallel_size(mesh)
        self.defer_sync = defer_sync
        self._donate = donate
        self._interpret = interpret
        self._block = block
        self.guard = guard
        if not self.axes or self.dp < 2:
            raise ValueError(
                "ShardedExecutor needs a mesh with a (pod, data) extent of "
                f">= 2 (got {self.dp}); on one device use the inner "
                "executor directly")
        if self.plan.micro_batch_size % self.dp:
            raise ValueError(
                f"micro-batch {self.plan.micro_batch_size} does not divide "
                f"over {self.dp} data-parallel workers — build the plan "
                "with plan_mbs(mesh=...) so sizes stay divisible")
        if self.plan.normalization == "paper" and self.plan.pad:
            raise ValueError(
                'a ragged "paper" plan cannot be sharded exactly (the tail '
                "pad lands on one worker's shard) — use "
                'normalization="exact" (plan_mbs auto-upgrades ragged plans)')
        if not isinstance(inner, str):
            inner = getattr(inner, "name", inner)
        if inner not in EXECUTORS:
            raise ValueError(
                f"unknown inner executor {inner!r}; available: "
                f"{sorted(EXECUTORS)}")
        if not defer_sync and inner != "compiled":
            raise ValueError(
                "defer_sync=False is the per-micro-sync comparison baseline "
                "and only supports inner='compiled'")
        self.inner_name = inner
        self.inner = (None if inner == "streaming" else
                      get_executor(inner)(loss_fn, optimizer, self.plan,
                                          interpret=interpret, block=block,
                                          donate=False))
        self._step_jit = None
        self._grads_jit = None
        self._stream_micro = None
        self._stream_update = None
        self._stream_grads = None

    # -- staging ------------------------------------------------------------

    def batch_shardings(self, split):
        """NamedSharding tree for a split ``(N_Sμ, N_μ, ...)`` batch — what
        the ``Pipeline`` stages with (``sharding=executor.batch_shardings``)."""
        specs = batch_partition_specs(split, self.plan.micro_batch_size,
                                      self.axes)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def stage(self, split):
        return jax.device_put(split, self.batch_shardings(split))

    def inplace_accum_share(self, params) -> float:
        """The inner strategy's share of the accumulator added in place
        (``CompiledScanExecutor.inplace_accum_share``); 0 where the local
        half is not its ``raw_accumulate``."""
        if self.inner is None or not self.defer_sync:
            return 0.0
        return self.inner.inplace_accum_share(params)

    # -- the local (per-device) halves of the step --------------------------

    def _raw_local(self, params, mb):
        """UN-normalized local sums via the inner executor's own strategy."""
        return self.inner.raw_accumulate(params, mb)

    def _per_micro_synced(self, params, mb):
        """The baseline being amortized away: one flat psum per micro-batch
        inside the scan (N_Sμ collectives per step). Returns grads already
        globally summed; loss/metrics still local."""
        plan = self.plan
        n_s, _ = exec_core.denominators(mb)
        accum0 = exec_core.init_accum(params, plan.accum_dtype)
        mb0 = jax.tree.map(lambda x: x[0], mb)
        metrics0 = exec_core.metrics_zeros(self.loss_fn, "exact", params, mb0)

        def micro_step(carry, m):
            acc, loss_sum, metric_sum = carry
            lfn = exec_core.micro_loss_fn(self.loss_fn, "exact", n_s, 1.0, m,
                                          defer_scale=True)
            (l, metrics), g = jax.value_and_grad(lfn, has_aux=True)(params)
            g = psum_flat(g, self.axes)  # <-- the per-micro sync
            acc = exec_core.accumulate(acc, g)
            metric_sum = jax.tree.map(jnp.add, metric_sum, metrics)
            return (acc, loss_sum + l, metric_sum), None

        (grads, loss, metric_sum), _ = jax.lax.scan(
            micro_step, (accum0, jnp.zeros((), jnp.float32), metrics0),
            mb, unroll=plan.unroll)
        return grads, loss, metric_sum

    def _finalize(self, params, opt_state, grads, loss, metric_sum, valid,
                  n_s: int):
        """Post-sync: normalize by the global valid count, update
        (replicated — identical on every device), package metrics."""
        scale = 1.0 / valid
        grads = jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads)
        loss = loss * scale
        # metrics were summed over every (device, micro-batch) pair
        metrics = jax.tree.map(lambda m: m / (self.dp * n_s), metric_sum)
        ok = None
        if self.inner_name == "flat":
            spec = flat_lib.FlatSpec.for_tree(params)
            bufs = spec.flatten(grads, dtype=jnp.float32)
            if self.guard:
                new_params, new_opt, ok = exec_core.guarded_update_flat(
                    self.optimizer, spec, bufs, opt_state, params,
                    interpret=self._interpret, block=self._block)
            else:
                new_params, new_opt = exec_core.apply_update_flat(
                    self.optimizer, spec, bufs, opt_state, params,
                    interpret=self._interpret, block=self._block)
        elif self.guard:
            new_params, new_opt, ok = exec_core.guarded_update(
                self.optimizer, grads, opt_state, params)
        else:
            new_params, new_opt = exec_core.apply_update(
                self.optimizer, grads, opt_state, params)
        out = exec_core.finalize_metrics(metrics, loss, grads)
        if ok is not None:
            out["nonfinite"] = 1.0 - ok.astype(jnp.float32)
        return new_params, new_opt, out

    # -- compiled path ------------------------------------------------------

    def make_train_step(self) -> Callable:
        """Pure (params, opt_state, split_batch) -> (params, opt_state,
        metrics) with the shard_map applied at trace time — the launcher
        jits it with donation exactly like the single-device executors."""
        if self.inner_name == "streaming":
            raise NotImplementedError(
                "the streaming inner is an eager per-micro pipeline; use "
                ".step()/.step_split() (or a compiled inner for a jittable "
                "train step)")

        def train_step(params, opt_state, micro_batches):
            specs = batch_partition_specs(
                micro_batches, self.plan.micro_batch_size, self.axes)
            n_s = jax.tree.leaves(micro_batches)[0].shape[0]

            def local_step(params, opt_state, mb):
                if self.defer_sync:
                    grads, loss, msum = self._raw_local(params, mb)
                    grads, loss, msum, valid = psum_flat(
                        (grads, loss, msum, _local_valid_count(mb)),
                        self.axes)  # the ONE all-reduce per mini-batch
                else:
                    grads, loss, msum = self._per_micro_synced(params, mb)
                    loss, msum, valid = psum_flat(
                        (loss, msum, _local_valid_count(mb)), self.axes)
                return self._finalize(params, opt_state, grads, loss,
                                      msum, valid, n_s)

            return shard_map(local_step, mesh=self.mesh,
                             in_specs=(P(), P(), specs),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(params, opt_state, micro_batches)

        return train_step

    def trace_step(self, params, opt_state, micro_batches):
        """ClosedJaxpr of the full sharded mini-batch step (traced, never
        run; inputs may be ``ShapeDtypeStruct``s) — the artifact the
        ``repro.analysis`` jaxpr checks (collective census, accumulator
        dtype) consume. For the eager streaming inner the per-micro jitted
        dispatches and the sync+update dispatch are stitched into one
        traceable function (each shows up as a ``jit`` equation)."""
        if self.inner_name != "streaming":
            return jax.make_jaxpr(self.make_train_step())(
                params, opt_state, micro_batches)
        self._ensure_stream_fns()

        def whole(p, o, split):
            n_s = jax.tree.leaves(split)[0].shape[0]
            mb0 = jax.tree.map(lambda x: x[0], split)
            carry = self._carry_zeros(p, mb0)
            for i in range(n_s):
                mb = jax.tree.map(lambda x, i=i: x[i], split)
                carry = self._stream_micro(p, carry, mb)
            return self._stream_update(p, o, carry, n_s)

        return jax.make_jaxpr(whole)(params, opt_state, micro_batches)

    def lower_step(self, params, opt_state, micro_batches, *,
                   donate: Optional[bool] = None):
        """``jax.stages.Lowered`` of the jitted sharded step (donation as
        configured unless overridden) for the HLO-level contract checks —
        one all-reduce per mini-batch, aliasing, ``memory_analysis``."""
        if self.inner_name == "streaming":
            raise NotImplementedError(
                "the streaming inner has no single jittable step to lower; "
                "use trace_step for jaxpr-level analysis")
        if donate is None:
            donate = self._donate
        return jax.jit(
            self.make_train_step(),
            donate_argnums=(0, 1, 2) if donate else (),
        ).lower(params, opt_state, micro_batches)

    def step_split(self, params, opt_state, micro_batches
                   ) -> Tuple[Any, Any, Dict[str, Any]]:
        faults.on_dispatch(self.plan)
        if self.inner_name == "streaming":
            return self._stream_step_split(params, opt_state, micro_batches)
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
        """Accumulated NORMALIZED gradients + mini-batch loss (eq. 15–17's
        quantity) under the deferred-sync sharded schedule."""
        if self.inner_name == "streaming":
            return self._stream_gradients(params, micro_batches)
        if self._grads_jit is None:
            def run(p, mb):
                specs = batch_partition_specs(
                    mb, self.plan.micro_batch_size, self.axes)

                def local(p, mb):
                    g, l, _ = self._raw_local(p, mb)
                    g, l, valid = psum_flat((g, l, _local_valid_count(mb)),
                                            self.axes)
                    scale = 1.0 / valid
                    return (jax.tree.map(
                        lambda x: (x * scale).astype(x.dtype), g), l * scale)

                return shard_map(local, mesh=self.mesh,
                                 in_specs=(P(), specs),
                                 out_specs=(P(), P()),
                                 check_vma=False)(p, mb)
            self._grads_jit = jax.jit(run)
        return self._grads_jit(params, micro_batches)

    # -- streaming path -----------------------------------------------------
    #
    # Local partial sums carry a leading data_parallel dim (sharded over the
    # batch axes) so they stay device-local across eager dispatches — a
    # global array cannot otherwise hold per-device state.

    def _carry_zeros(self, params, mb0):
        dp = self.dp
        acc = jax.tree.map(
            lambda p: jnp.zeros((dp,) + p.shape, self.plan.accum_dtype),
            params)
        mshape = exec_core.metrics_zeros(self.loss_fn, "exact", params, mb0)
        metrics = jax.tree.map(
            lambda m: jnp.zeros((dp,) + m.shape, m.dtype), mshape)
        return (acc, jnp.zeros((dp,), jnp.float32), metrics,
                jnp.zeros((dp,), jnp.float32))

    def _ensure_stream_fns(self):
        if self._stream_micro is not None:
            return
        entry = _axis_entry(self.axes)
        carry_spec = P(entry)
        micro = self.plan.micro_batch_size

        def local_micro(params, carry, mb):
            # one raw grad+accumulate dispatch, NO collective (deferred)
            acc, loss_sum, metric_sum, valid = carry  # local: leading dim 1
            lfn = exec_core.micro_loss_fn(self.loss_fn, "exact", 1, 1.0, mb,
                                          defer_scale=True)
            (l, metrics), g = jax.value_and_grad(lfn, has_aux=True)(params)
            with jax.named_scope(spans.ACCUMULATE):
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(a.dtype)[None], acc, g)
            metric_sum = jax.tree.map(lambda s, m: s + m[None],
                                      metric_sum, metrics)
            return (acc, loss_sum + l[None], metric_sum,
                    valid + _local_valid_count(mb, sample_dims=1)[None])

        def local_update(params, opt_state, carry, n_s):
            local = jax.tree.map(lambda x: x[0], carry)
            grads, loss, msum, valid = psum_flat(local, self.axes)
            return self._finalize(params, opt_state, grads, loss, msum,
                                  valid, n_s)

        def local_grads(carry):
            acc, loss_sum, _, valid = jax.tree.map(lambda x: x[0], carry)
            g, l, v = psum_flat((acc, loss_sum, valid), self.axes)
            scale = 1.0 / v
            return (jax.tree.map(lambda x: (x * scale).astype(x.dtype), g),
                    l * scale)

        def micro_specs(mb):
            return batch_partition_specs(mb, micro, self.axes,
                                         sample_dim_from=0)

        def wrap_micro(params, carry, mb):
            return shard_map(local_micro, mesh=self.mesh,
                             in_specs=(P(), carry_spec, micro_specs(mb)),
                             out_specs=carry_spec,
                             check_vma=False)(params, carry, mb)

        def wrap_update(params, opt_state, carry, n_s):
            return shard_map(lambda p, s, c: local_update(p, s, c, n_s),
                             mesh=self.mesh,
                             in_specs=(P(), P(), carry_spec),
                             out_specs=(P(), P(), P()),
                             check_vma=False)(params, opt_state, carry)

        def wrap_grads(carry):
            return shard_map(local_grads, mesh=self.mesh,
                             in_specs=(carry_spec,), out_specs=(P(), P()),
                             check_vma=False)(carry)

        self._stream_micro = jax.jit(
            wrap_micro, donate_argnums=(1,) if self._donate else ())
        self._stream_update = jax.jit(wrap_update, static_argnums=(3,))
        self._stream_grads = jax.jit(wrap_grads)

    def _stream_accumulate(self, params, micro_batches):
        self._ensure_stream_fns()
        n_s = jax.tree.leaves(micro_batches)[0].shape[0]
        mb0 = jax.tree.map(lambda x: x[0], micro_batches)
        carry = self._carry_zeros(params, mb0)
        for i in range(n_s):
            mb = jax.tree.map(lambda x, i=i: x[i], micro_batches)
            carry = self._stream_micro(params, carry, mb)
        return n_s, carry

    def _stream_step_split(self, params, opt_state, micro_batches):
        n_s, carry = self._stream_accumulate(params, micro_batches)
        return self._stream_update(params, opt_state, carry, n_s)

    def _stream_gradients(self, params, micro_batches):
        _, carry = self._stream_accumulate(params, micro_batches)
        return self._stream_grads(carry)
