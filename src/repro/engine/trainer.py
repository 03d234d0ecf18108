"""Resumable training loop over a (step_fn, Pipeline) pair.

The :class:`Trainer` owns everything the launcher's hot loop used to do
inline, with the synchronization bugs designed out:

  * **async metrics readback** — the step functions return *device*
    scalars; the Trainer holds step i's metrics while dispatching step
    i+1 and only converts to host floats afterwards (and only on log
    steps), so printing a loss never serializes the pipeline;
  * **periodic checkpointing** — ``{"params", "opt_state"}`` saved every
    ``ckpt_every`` steps (plus a final save), tagged with the *next*
    step index so resume knows where to pick up;
  * **resume** — :meth:`restore` reads the latest checkpoint and
    re-applies the run's shardings via ``jax.device_put`` (the launcher
    passes ``sharding.param_specs``-derived NamedShardings) instead of
    handing the step function bare host numpy arrays.

Combined with the Pipeline's step-indexed seeding, a save → resume
round-trip replays the identical data stream and op sequence, so it
matches an uninterrupted run bitwise (the regression test asserts this).

The loop writes the host spans of ``repro.spans`` (``dispatch``,
``readback``, ``checkpoint``; the pipeline writes ``input``) into any
active ``jax.profiler`` trace. With ``profile_dir`` the first ``fit``
traces ``profile_steps`` steps itself, starting after its first step
(which traces and compiles), and waits for them on the device before it
stops the trace.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from .. import spans
from ..checkpoint import checkpoint
from .pipeline import Pipeline


def _default_log(step: int, metrics: Dict[str, float], elapsed: float):
    extra = (f"  |g| {metrics['grad_norm']:.3f}"
             if "grad_norm" in metrics else "")
    print(f"step {step:4d}  loss {metrics['loss']:.4f}{extra}"
          f"  ({elapsed:.1f}s)", flush=True)


class Trainer:
    """Drives ``step_fn(params, opt_state, split_batch)`` over a
    :class:`Pipeline`. ``step_fn`` is an executor's ``step_split`` (or the
    launcher's sharded jit of ``make_train_step``)."""

    def __init__(self, step_fn: Callable, pipeline: Pipeline, *,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
                 ckpt_keep: Optional[int] = None, log_every: int = 5,
                 state_shardings: Any = None, log_fn: Callable = _default_log,
                 profile_dir: Optional[str] = None, profile_steps: int = 5):
        if profile_dir and profile_steps < 1:
            raise ValueError(f"profile_steps must be >= 1, got {profile_steps}")
        self.step_fn = step_fn
        self.pipeline = pipeline
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.ckpt_keep = ckpt_keep
        self.log_every = log_every
        self.state_shardings = state_shardings
        self.log_fn = log_fn
        self.profile_dir = profile_dir
        self.profile_steps = profile_steps

    # -- checkpointing ------------------------------------------------------

    def save(self, step: int, params, opt_state) -> Optional[str]:
        if not self.ckpt_dir:
            return None
        with jax.profiler.TraceAnnotation(spans.CHECKPOINT):
            return checkpoint.save(self.ckpt_dir, step,
                                   {"params": params, "opt_state": opt_state},
                                   keep=self.ckpt_keep)

    def restore(self, params_template, opt_state_template
                ) -> Optional[Tuple[Any, Any, int]]:
        """(params, opt_state, start_step) from the newest *loadable*
        committed checkpoint in ``ckpt_dir``, placed per
        ``state_shardings`` (default device when none) — or ``None`` when
        there is nothing to resume from. Torn writes are invisible
        (uncommitted — no manifest) and checksum-failing checkpoints are
        skipped in favor of the previous committed step."""
        if not self.ckpt_dir:
            return None
        for step in reversed(checkpoint.committed_steps(self.ckpt_dir)):
            try:
                tree = self._restore_step(step, params_template,
                                          opt_state_template)
            except checkpoint.CheckpointCorruptError:
                continue  # fall back to the previous committed step
            if self.state_shardings is None:
                tree = jax.device_put(tree)
            return tree["params"], tree["opt_state"], step
        return None

    def _restore_step(self, step: int, params_template, opt_state_template):
        template = {"params": params_template,
                    "opt_state": opt_state_template}
        try:
            return checkpoint.restore(self.ckpt_dir, template, step,
                                      shardings=self.state_shardings)
        except KeyError:
            # legacy params-only checkpoint: restore what is there and
            # keep the caller's (fresh) optimizer state. A non-dict
            # state_shardings (one sharding for every leaf) applies as-is —
            # dropping it would hand the step bare host numpy arrays and
            # silently re-place them with default sharding.
            pshard = (self.state_shardings.get("params")
                      if isinstance(self.state_shardings, dict)
                      else self.state_shardings)
            params = checkpoint.restore(self.ckpt_dir, params_template,
                                        step, shardings=pshard)
            return {"params": params, "opt_state": opt_state_template}

    # -- the loop -----------------------------------------------------------

    def fit(self, params, opt_state, num_steps: int, *, start_step: int = 0
            ) -> Tuple[Any, Any, Dict[str, float]]:
        """Run steps ``start_step .. num_steps``; returns the final state
        and the last step's metrics (as host floats)."""
        t0 = time.perf_counter()
        pending: Optional[Tuple[int, Dict[str, Any]]] = None
        last: Dict[str, float] = {}
        stream = self.pipeline.batches(num_steps - start_step,
                                       start=start_step)
        # steps [1, 1 + profile_steps) of this pass, once per Trainer
        trace_from = 1 if self.profile_dir else None
        tracing = False
        try:
            # drive iteration from the stream (not a zip'd range) so the
            # generator runs to completion and finalizes pipeline.stats
            for offset, batch in enumerate(stream):
                step = start_step + offset
                if offset == trace_from:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # spans only, not frames
                    jax.profiler.start_trace(self.profile_dir,
                                             profiler_options=opts)
                    tracing = True
                elif tracing and offset == trace_from + self.profile_steps:
                    tracing = self._stop_trace(params, opt_state)
                with jax.profiler.TraceAnnotation(spans.DISPATCH):
                    params, opt_state, metrics = self.step_fn(
                        params, opt_state, batch)
                # read back the PREVIOUS step's metrics now that this step
                # is in flight — the readback overlaps compute instead of
                # gating it
                if pending is not None:
                    self._flush(pending, t0)
                pending = (step, metrics)
                if self.ckpt_every and (step + 1) % self.ckpt_every == 0 \
                        and step + 1 < num_steps:
                    self.save(step + 1, params, opt_state)
            if tracing:
                tracing = self._stop_trace(params, opt_state)
        finally:
            if tracing:  # an exception left the trace open
                jax.profiler.stop_trace()
        if pending is not None:
            last = self._readback(pending[1])
            if self.log_fn:
                self.log_fn(pending[0], last, time.perf_counter() - t0)
        if self.ckpt_dir and num_steps > start_step:
            self.save(num_steps, params, opt_state)
        return params, opt_state, last

    def _flush(self, pending: Tuple[int, Dict[str, Any]], t0: float):
        step, metrics = pending
        if self.log_fn and self.log_every and step % self.log_every == 0:
            self.log_fn(step, self._readback(metrics),
                        time.perf_counter() - t0)

    def _stop_trace(self, params, opt_state) -> bool:
        """Wait for the traced steps on the device, stop the trace and
        profile no later pass; returns the new ``tracing`` flag."""
        jax.block_until_ready((params, opt_state))
        jax.profiler.stop_trace()
        self.profile_dir = None
        return False

    @staticmethod
    def _readback(metrics: Dict[str, Any]) -> Dict[str, float]:
        with jax.profiler.TraceAnnotation(spans.READBACK):
            return {k: float(v) for k, v in metrics.items()}
