"""Pluggable MBS executors behind one interface.

All three run the same Algorithm 1 through the shared core in
``exec_core.py`` — only the execution strategy differs:

  * :class:`CompiledScanExecutor` — the TPU-native production path: a
    ``lax.scan`` over the micro-batch axis inside one jitted step; XLA keeps
    one micro-batch of activations live (DESIGN.md §Hardware adaptation).
  * :class:`StreamingExecutor` — the paper's literal Fig. 1 pipeline:
    host→device transfer of micro-batch i+1 overlaps compute of i (double
    buffering), one jitted gradient per micro-batch, eager accumulate.
  * :class:`FusedAccumExecutor` — the compiled scan with accumulation
    routed through the Pallas kernel ``kernels/grad_accum.py``: the 1/N_Sμ
    loss-normalization scale is fused into the accumulate (paper Fig. 2
    step ❹ + eq. 14) with in-place aliasing on the fp32 accumulator.
  * :class:`FlatFusedExecutor` — the fused flat-buffer update path: the
    accumulator lives in dtype-bucketed contiguous 1-D buffers
    (``engine/flat.py``) for the whole scan, so step ❹ is one masked
    Pallas launch per *bucket* (not per leaf) and step ❺ runs through the
    in-place fused optimizer kernels (``kernels/fused_update.py``) with no
    ``updates``/opt-state transients (DESIGN.md §Update path).

Compiled executors donate params/opt-state/split-batch buffers at the
``step_split`` jit boundary (construct with ``donate=False`` for callers
that must reuse inputs across calls — see DESIGN.md for the contract).

Kernel block sizes are resolved at trace/build time, not hard-coded:
every Pallas call the executors reach (grad-accum, fused update) takes
``block=None`` and resolves it through the kernel-side hook
(``kernels.grad_accum.resolve_block``), which consults the persistent
tuning cache installed by ``engine/autotune.py`` before falling back to
the size-aware heuristic — so a ``tune_for_params`` sweep changes the
launch geometry of all executors without touching their code, and
never their numerics (DESIGN.md §Autotuning).

New strategies (async multi-device, serving) implement the same
:class:`Executor` surface and register in :data:`EXECUTORS`.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Protocol, Tuple, Type, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from . import exec_core, faults, flat
from .plan import MBSConfig, MBSPlan


def _as_plan(plan) -> MBSPlan:
    if isinstance(plan, MBSConfig):
        return MBSPlan.from_config(plan)
    if isinstance(plan, MBSPlan):
        return plan
    raise TypeError(f"expected MBSPlan or MBSConfig, got {type(plan)!r}")


@runtime_checkable
class Executor(Protocol):
    """One mini-batch-update strategy. ``step`` is the host-level entry
    (splits the raw mini-batch per the plan); compiled strategies also
    expose ``make_train_step`` — a pure function over pre-split batches
    that the launcher jits with shardings/donation; ``gradients`` returns
    the accumulated normalized gradients only (eq. 15–17's quantity)."""
    name: str
    plan: MBSPlan

    def make_train_step(self) -> Callable: ...

    def step(self, params, opt_state, minibatch: Dict[str, np.ndarray]
             ) -> Tuple[Any, Any, Dict[str, Any]]: ...

    def step_split(self, params, opt_state, micro_batches
                   ) -> Tuple[Any, Any, Dict[str, Any]]: ...

    def gradients(self, params, micro_batches) -> Tuple[Any, jnp.ndarray]: ...


def _scan_accumulate(loss_fn, plan: MBSPlan, fused: bool, params,
                     micro_batches, interpret=None, block=None,
                     raw: bool = False):
    """Shared compiled core: scan over the micro-batch axis, accumulating
    normalized gradients + loss + metrics. Returns (grads, loss, metric_sum).

    ``raw=True`` (the ShardedExecutor's per-device half of the mini-batch
    step) defers ALL normalization: each micro loss is the raw SUM of valid
    per-sample losses (``exact_denom=1``), gradients/losses/metrics are
    accumulated as plain sums. The caller divides by the GLOBAL valid count
    after the cross-device reduction — the one place the data-parallel
    denominator is known.

    Where the loss can (``exec_core.inplace_key``), the backward adds the
    layer stack's gradient into the accumulator carry itself, and only
    the other leaves go through ``exec_core.accumulate``."""
    n_s, total_valid = exec_core.denominators(micro_batches)
    norm = "exact" if raw else plan.normalization
    accum0 = exec_core.init_accum(params, plan.accum_dtype)
    if raw:
        scale = 1.0 if fused else None  # plain unscaled sums
    else:
        scale = (exec_core.deferred_scale(plan.normalization, n_s, total_valid)
                 if fused else None)
    key = exec_core.inplace_key(loss_fn, params, plan.accum_dtype, n_s,
                                plain_add=scale is None)
    mb0 = jax.tree.map(lambda x: x[0], micro_batches)
    metrics0 = exec_core.metrics_zeros(loss_fn, norm, params, mb0)
    metric_div = 1 if raw else n_s

    def micro_step(carry, mb):
        acc, loss_sum, metric_sum = carry
        lfn = exec_core.micro_loss_fn(loss_fn, norm, n_s, total_valid, mb,
                                      defer_scale=fused or raw,
                                      accum=None if key is None else acc[key])
        grad_fn = jax.value_and_grad(lfn, has_aux=True)
        if plan.remat_micro_step:
            grad_fn = jax.checkpoint(grad_fn)
        (l, metrics), grads = grad_fn(params)
        acc = exec_core.accumulate(acc, grads, scale=scale, fused=fused,
                                   interpret=interpret, block=block,
                                   added=key)
        metric_sum = jax.tree.map(lambda s, m: s + m / metric_div,
                                  metric_sum, metrics)
        return (acc, loss_sum + l, metric_sum), None

    (grads, loss, metric_sum), _ = jax.lax.scan(
        micro_step, (accum0, jnp.zeros((), jnp.float32), metrics0),
        micro_batches, unroll=plan.unroll)
    if fused and not raw:
        loss = loss * scale  # normalization was deferred to the accumulate
    return grads, loss, metric_sum


class _CompiledExecutorBase:
    """Common machinery for scan-based (jit-compiled) executors.

    ``donate=True`` (default) donates params/opt-state/split-batch at the
    ``step_split`` jit boundary: callers must thread the returned state
    (the ``Trainer`` does) and never touch a donated buffer again. Pass
    ``donate=False`` when inputs are reused across calls (A/B comparisons,
    benchmarks timing the same state repeatedly).

    ``guard=True`` (engine Layer 9) puts the optimizer update behind an
    on-device finite-check of the accumulated gradient: a non-finite
    accumulator skips step ❺ (state passes through unchanged) and the
    metrics carry a ``nonfinite`` device scalar for the supervisor's
    skip/retry policy. Guard off (the default) compiles the exact same
    program as before — no cond, no extra metric."""
    name = "base"
    fused = False

    def __init__(self, loss_fn, optimizer, plan, *,
                 interpret: Optional[bool] = None, block: Optional[int] = None,
                 donate: bool = True, guard: bool = False):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.plan = _as_plan(plan)
        self._interpret = interpret
        self._block = block
        self._donate = donate
        self.guard = guard
        self._step_jit = None
        self._grads_jit = None

    def _accumulated(self, params, micro_batches):
        return _scan_accumulate(self.loss_fn, self.plan, self.fused, params,
                                micro_batches, self._interpret, self._block)

    def inplace_accum_share(self, params) -> float:
        """The share of the accumulator's bytes that the backward adds in
        place (``exec_core.inplace_key``); static, from the tree and the
        plan."""
        return exec_core.inplace_share(params, exec_core.inplace_key(
            self.loss_fn, params, self.plan.accum_dtype,
            self.plan.num_micro_batches, plain_add=not self.fused))

    def raw_accumulate(self, params, micro_batches):
        """Traceable UN-normalized accumulation over a (local) split batch:
        (grad sums, loss sum, metric sums) with no 1/N anywhere — the
        per-device half of the ShardedExecutor's deferred-sync step, run
        with this executor's own strategy (scan / Pallas accumulate)."""
        return _scan_accumulate(self.loss_fn, self.plan, self.fused, params,
                                micro_batches, self._interpret, self._block,
                                raw=True)

    def make_train_step(self) -> Callable:
        """(params, opt_state, split_batch) -> (params, opt_state, metrics);
        pure — the launcher jits it with shardings and donation."""
        def train_step(params, opt_state, micro_batches):
            grads, loss, metric_sum = self._accumulated(params, micro_batches)
            if self.guard:
                new_params, new_opt, ok = exec_core.guarded_update(
                    self.optimizer, grads, opt_state, params)
                metrics = exec_core.finalize_metrics(metric_sum, loss, grads)
                metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
                return new_params, new_opt, metrics
            new_params, new_opt = exec_core.apply_update(
                self.optimizer, grads, opt_state, params)
            return new_params, new_opt, exec_core.finalize_metrics(
                metric_sum, loss, grads)
        return train_step

    def gradients(self, params, micro_batches):
        if self._grads_jit is None:
            self._grads_jit = jax.jit(
                lambda p, mb: self._accumulated(p, mb)[:2])
        return self._grads_jit(params, micro_batches)

    def trace_step(self, params, opt_state, micro_batches):
        """ClosedJaxpr of the full mini-batch train step — traced, never
        executed (inputs may be ``ShapeDtypeStruct``s). This is the
        canonical artifact the ``repro.analysis`` jaxpr contract checks
        consume, instead of every caller re-tracing ad hoc."""
        return jax.make_jaxpr(self.make_train_step())(
            params, opt_state, micro_batches)

    def lower_step(self, params, opt_state, micro_batches, *,
                   donate: Optional[bool] = None):
        """``jax.stages.Lowered`` of the jitted step with this executor's
        donation contract (override via ``donate=``); ``.compile()`` it for
        the HLO-level checks (aliasing coverage, ``memory_analysis``)."""
        if donate is None:
            donate = self._donate
        return jax.jit(
            self.make_train_step(),
            donate_argnums=(0, 1, 2) if donate else (),
        ).lower(params, opt_state, micro_batches)

    def step_split(self, params, opt_state, micro_batches):
        """Jitted step over an already-split ``(N_Sμ, N_μ, ...)`` batch —
        the entry used by the ``Trainer``/``Pipeline`` pair (staging done
        upstream). Metrics come back as device scalars (no host sync).
        Inputs are donated (unless constructed with ``donate=False``): the
        params/opt-state buffers are reused in place for the new state and
        the spent split batch is freed for step-❺ temporaries."""
        faults.on_dispatch(self.plan)
        if self._step_jit is None:
            self._step_jit = jax.jit(
                self.make_train_step(),
                donate_argnums=(0, 1, 2) if self._donate else ())
        return self._step_jit(params, opt_state, micro_batches)

    def step(self, params, opt_state, minibatch):
        return self.step_split(params, opt_state,
                               self.plan.device_split(minibatch))


class CompiledScanExecutor(_CompiledExecutorBase):
    """Today's production path: jitted ``lax.scan`` + plain fp32 add."""
    name = "compiled"
    fused = False


class FusedAccumExecutor(_CompiledExecutorBase):
    """Compiled scan with the Pallas fused scaled-accumulate (step ❹).
    ``interpret`` defaults to True off-TPU (set explicitly for tests)."""
    name = "fused"
    fused = True


class FlatFusedExecutor(_CompiledExecutorBase):
    """Fused flat-buffer update path (DESIGN.md §Update path).

    The gradient accumulator is kept as dtype-bucketed contiguous 1-D
    buffers (``engine/flat.py``) across the whole micro-batch scan, so the
    scaled accumulate (step ❹, normalization deferred into the kernel) is
    one masked Pallas launch per *bucket* instead of one per leaf; the
    optimizer update (step ❺) reads the fp32 accumulator and writes params
    + opt state in one in-place pass through ``kernels/fused_update.py``
    (``input_output_aliases`` everywhere, global-norm clip carried in as a
    scalar). Combined with ``step_split``'s donation this eliminates the
    ``updates`` tree and all optimizer-state transients — see
    ``core/memory_model.update_transient_bytes``. ``interpret`` defaults
    to True off-TPU."""
    name = "flat"
    fused = True  # raw micro losses; normalization fused into the accumulate

    def _accumulated_flat(self, params, micro_batches, raw: bool = False):
        """Like ``_scan_accumulate`` but the carry holds flat buckets.
        ``raw=True`` defers all normalization to the caller (sharded
        execution) — unscaled sums, same flat-bucket strategy."""
        plan = self.plan
        norm = "exact" if raw else plan.normalization
        spec = flat.FlatSpec.for_tree(params)  # static at trace time
        n_s, total_valid = exec_core.denominators(micro_batches)
        scale = (1.0 if raw else
                 exec_core.deferred_scale(plan.normalization, n_s, total_valid))
        mb0 = jax.tree.map(lambda x: x[0], micro_batches)
        metrics0 = exec_core.metrics_zeros(self.loss_fn, norm, params, mb0)
        metric_div = 1 if raw else n_s

        def micro_step(carry, mb):
            acc, loss_sum, metric_sum = carry
            lfn = exec_core.micro_loss_fn(self.loss_fn, norm,
                                          n_s, total_valid, mb,
                                          defer_scale=True)
            grad_fn = jax.value_and_grad(lfn, has_aux=True)
            if plan.remat_micro_step:
                grad_fn = jax.checkpoint(grad_fn)
            (l, metrics), grads = grad_fn(params)
            acc = exec_core.accumulate_flat(acc, spec, grads, scale=scale,
                                            interpret=self._interpret,
                                            block=self._block)
            metric_sum = jax.tree.map(lambda s, m: s + m / metric_div,
                                      metric_sum, metrics)
            return (acc, loss_sum + l, metric_sum), None

        (acc, loss, metric_sum), _ = jax.lax.scan(
            micro_step,
            (spec.zeros(plan.accum_dtype), jnp.zeros((), jnp.float32),
             metrics0),
            micro_batches, unroll=plan.unroll)
        return spec, acc, (loss if raw else loss * scale), metric_sum

    def raw_accumulate(self, params, micro_batches):
        """Un-normalized flat-bucket accumulation (see the base class doc);
        returns the gradient sums as a TREE (unflattened, accum dtype)."""
        spec, acc, loss, metric_sum = self._accumulated_flat(
            params, micro_batches, raw=True)
        return spec.unflatten(acc, cast=False), loss, metric_sum

    def make_train_step(self) -> Callable:
        def train_step(params, opt_state, micro_batches):
            spec, acc, loss, metric_sum = self._accumulated_flat(
                params, micro_batches)
            if self.guard:
                # finite-check runs directly on the dtype buckets — the
                # FlatSpec composition the guard contract promises
                new_params, new_opt, ok = exec_core.guarded_update_flat(
                    self.optimizer, spec, acc, opt_state, params,
                    interpret=self._interpret, block=self._block)
                metrics = exec_core.finalize_metrics(metric_sum, loss, acc)
                metrics["nonfinite"] = 1.0 - ok.astype(jnp.float32)
                return new_params, new_opt, metrics
            new_params, new_opt = exec_core.apply_update_flat(
                self.optimizer, spec, acc, opt_state, params,
                interpret=self._interpret, block=self._block)
            # grad_norm straight off the flat buffers (a tuple is a pytree)
            return new_params, new_opt, exec_core.finalize_metrics(
                metric_sum, loss, acc)
        return train_step

    def gradients(self, params, micro_batches):
        if self._grads_jit is None:
            def run(p, mb):
                spec, acc, loss, _ = self._accumulated_flat(p, mb)
                return spec.unflatten(acc, cast=False), loss
            self._grads_jit = jax.jit(run)
        return self._grads_jit(params, micro_batches)


class StreamingExecutor:
    """Eager host→device micro-batch streaming (the paper's Fig. 1
    pipeline): double-buffered transfers, one jitted micro step per
    micro-batch. Honors the full plan — ``normalization="exact"`` and
    ``accum_dtype`` route through the same shared core as the compiled
    executors.

    Loss and metrics stay on device for the whole loop (the jitted micro
    step carries them alongside the gradient accumulator) and the step
    returns device scalars, so nothing forces a host sync between
    micro-batches and the double buffer actually overlaps transfer with
    compute. Callers read metrics back when they need them (the
    ``Trainer`` does so asynchronously, one step late)."""
    name = "streaming"

    def __init__(self, loss_fn, optimizer, plan, device: Optional[Any] = None,
                 *, guard: bool = False):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.plan = _as_plan(plan)
        self.device = device or jax.devices()[0]
        self.guard = guard
        norm = self.plan.normalization

        @jax.jit
        def _micro_grad_accum(params, acc, loss_sum, mb, n_s, total_valid):
            # grad + accumulate in ONE dispatch (the gradients-only analogue
            # of _micro_step; a separate _accumulate launch per micro-batch
            # used to double the dispatch count)
            lfn = exec_core.micro_loss_fn(loss_fn, norm, n_s, total_valid, mb)
            (l, _), g = jax.value_and_grad(lfn, has_aux=True)(params)
            return exec_core.accumulate(acc, g), loss_sum + l

        @jax.jit
        def _micro_step(params, carry, mb, n_s, total_valid):
            # grad + accumulate + on-device loss/metric sums in ONE dispatch
            # (paper Fig. 2 steps ❷–❹); no host value ever materializes here.
            acc, loss_sum, metric_sum = carry
            lfn = exec_core.micro_loss_fn(loss_fn, norm, n_s, total_valid, mb)
            (l, metrics), g = jax.value_and_grad(lfn, has_aux=True)(params)
            acc = exec_core.accumulate(acc, g)
            metric_sum = jax.tree.map(jnp.add, metric_sum, metrics)
            return acc, loss_sum + l, metric_sum

        @jax.jit
        def _update(params, opt_state, acc):  # paper step ❺
            return exec_core.apply_update(optimizer, acc, opt_state, params)

        @jax.jit
        def _guarded_update(params, opt_state, acc):  # step ❺ behind the guard
            return exec_core.guarded_update(optimizer, acc, opt_state, params)

        self._micro_grad_accum = _micro_grad_accum
        self._micro_step = _micro_step
        self._update = _update
        self._guarded_update = _guarded_update

    def make_train_step(self) -> Callable:
        raise NotImplementedError(
            "StreamingExecutor is an eager host pipeline; use .step() "
            "(or a compiled executor for a jittable train step)")

    def trace_step(self, params, opt_state, micro_batches):
        """ClosedJaxpr of one whole mini-batch of the eager pipeline (the
        per-micro jitted dispatches + the update), stitched into a single
        traceable function. Production never compiles this — the pipeline
        stays eager — but it gives ``repro.analysis`` the same step
        semantics to inspect (each jitted dispatch shows up as a ``jit``
        equation)."""
        def whole(p, o, split):
            n_s = jax.tree.leaves(split)[0].shape[0]
            micro_iter = (jax.tree.map(lambda x, i=i: x[i], split)
                          for i in range(n_s))
            return self._run(p, o, micro_iter, n_s, split)
        return jax.make_jaxpr(whole)(params, opt_state, micro_batches)

    def _denoms(self, split) -> Tuple[jnp.ndarray, jnp.ndarray]:
        n_s, total_valid = exec_core.denominators(split)
        return jnp.asarray(n_s, jnp.float32), total_valid

    def gradients(self, params, micro_batches):
        """Eager accumulation over an already-split batch (device arrays) —
        one jitted dispatch per micro-batch (grad + accumulate fused)."""
        n_s = jax.tree.leaves(micro_batches)[0].shape[0]
        n_s_f, total_valid = self._denoms(micro_batches)
        acc = exec_core.init_accum(params, self.plan.accum_dtype)
        loss = jnp.zeros((), jnp.float32)
        for i in range(n_s):
            mb = jax.tree.map(lambda x: x[i], micro_batches)
            acc, loss = self._micro_grad_accum(params, acc, loss, mb,
                                               n_s_f, total_valid)
        return acc, loss

    def _run(self, params, opt_state, micro_iter, n_s: int, split
             ) -> Tuple[Any, Any, Dict[str, Any]]:
        n_s_f, total_valid = self._denoms(split)
        mb0 = jax.tree.map(lambda x: x[0], split)
        carry = (exec_core.init_accum(params, self.plan.accum_dtype),
                 jnp.zeros((), jnp.float32),
                 exec_core.metrics_zeros(self.loss_fn,
                                         self.plan.normalization, params, mb0))
        for cur in micro_iter:
            carry = self._micro_step(params, carry, cur, n_s_f, total_valid)
        acc, loss, metric_sum = carry
        out: Dict[str, Any] = {k: v / n_s for k, v in metric_sum.items()}
        out["loss"] = loss  # Σ normalized micro losses == mini-batch loss
        out["grad_norm"] = exec_core.global_grad_norm(acc)
        if self.guard:
            params, opt_state, ok = self._guarded_update(params, opt_state, acc)
            out["nonfinite"] = 1.0 - ok.astype(jnp.float32)
        else:
            params, opt_state = self._update(params, opt_state, acc)
        return params, opt_state, out

    def step_split(self, params, opt_state, micro_batches
                   ) -> Tuple[Any, Any, Dict[str, Any]]:
        """Streaming update over a pre-split (and typically pre-staged)
        ``(N_Sμ, N_μ, ...)`` batch — the ``Pipeline`` overlaps the
        mini-batch transfer, so micro-batches are sliced on device."""
        faults.on_dispatch(self.plan)
        n_s = jax.tree.leaves(micro_batches)[0].shape[0]
        micro_iter = (jax.tree.map(lambda x, i=i: x[i], micro_batches)
                      for i in range(n_s))
        return self._run(params, opt_state, micro_iter, n_s, micro_batches)

    def step(self, params, opt_state, minibatch: Dict[str, np.ndarray]
             ) -> Tuple[Any, Any, Dict[str, Any]]:
        """One mini-batch update via sequential micro-batch streaming."""
        split = self.plan.split(minibatch)
        n_s = jax.tree.leaves(split)[0].shape[0]

        # double buffer: issue transfer of micro-batch i+1 while i computes
        def put(i):
            return jax.device_put(
                jax.tree.map(lambda x: x[i], split), self.device)

        def micro_iter():
            nxt = put(0)
            for i in range(n_s):
                cur, nxt = nxt, (put(i + 1) if i + 1 < n_s else None)
                yield cur

        return self._run(params, opt_state, micro_iter(), n_s, split)


EXECUTORS: Dict[str, Type] = {
    CompiledScanExecutor.name: CompiledScanExecutor,
    StreamingExecutor.name: StreamingExecutor,
    FusedAccumExecutor.name: FusedAccumExecutor,
    FlatFusedExecutor.name: FlatFusedExecutor,
}


def get_executor(name: str) -> Type:
    try:
        return EXECUTORS[name]
    except KeyError:
        raise ValueError(
            f"unknown executor {name!r}; available: {sorted(EXECUTORS)}")


def accumulate_gradients(loss_fn, params, micro_batches, plan,
                         *, fused: bool = False,
                         interpret: Optional[bool] = None):
    """Eager (python-loop) accumulated, normalized MBS gradients — the
    quantity eq. (15)–(17) proves equal to the mini-batch gradient. Used by
    the equivalence tests, benchmarks and the legacy ``mbs_gradients``."""
    plan = _as_plan(plan)
    n_s, total_valid = exec_core.denominators(micro_batches)
    scale = (exec_core.deferred_scale(plan.normalization, n_s, total_valid)
             if fused else None)
    acc = exec_core.init_accum(params, plan.accum_dtype)
    loss_sum = jnp.zeros((), jnp.float32)
    for i in range(n_s):
        mb = jax.tree.map(lambda x: x[i], micro_batches)
        lfn = exec_core.micro_loss_fn(loss_fn, plan.normalization, n_s,
                                      total_valid, mb, defer_scale=fused)
        (l, _), grads = jax.value_and_grad(lfn, has_aux=True)(params)
        acc = exec_core.accumulate(acc, grads, scale=scale, fused=fused,
                                   interpret=interpret)
        loss_sum = loss_sum + l
    if fused:
        loss_sum = loss_sum * scale
    return acc, loss_sum


def make_baseline_train_step(loss_fn, optimizer) -> Callable:
    """The no-MBS reference: one forward/backward over the whole mini-batch
    (what the paper's "w/o MBS" columns do — and what fails beyond the
    memory limit)."""
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        new_params, new_opt_state = exec_core.apply_update(
            optimizer, grads, opt_state, params)
        return new_params, new_opt_state, exec_core.finalize_metrics(
            metrics, loss, grads)
    return train_step
