"""Plan-aware async input pipeline (paper §3.1, Fig. 1).

The paper's point is that micro-batch *transfer* must overlap *compute*.
On the JAX/TPU stack that overlap happens at two granularities:

  * host work (dataset batch synthesis + the plan's pad-and-mask split,
    Fig. 2 step ❶) runs in a background thread via
    ``core.streaming.prefetch_iterator`` — worker exceptions propagate to
    the consumer instead of truncating the epoch;
  * host→device staging is an async ``jax.device_put`` (with the
    launcher's batch shardings when given), double-buffered at mini-batch
    granularity: batch i+1's transfer is issued before batch i is yielded
    to the step, so it lands while the step computes.

The :class:`Pipeline` also measures how long the consumer was blocked
waiting on input (``stats.input_wait_fraction``), which is the number the
``BENCH_pipeline`` benchmark records — an input-bound step loop shows up
here, not as mysteriously slow device time.
"""
from __future__ import annotations

import dataclasses
import random as _random
import time
from typing import Any, Dict, Iterator, Optional

import jax

from .. import spans
from ..core.streaming import prefetch_iterator
from . import faults
from .plan import MBSPlan


@dataclasses.dataclass
class PipelineStats:
    """Input-side timing of one ``batches()`` pass."""
    batches: int = 0
    wait_s: float = 0.0  # consumer time blocked on host data / staging
    elapsed_s: float = 0.0  # total wall time of the pass
    retries: int = 0  # transient producer failures absorbed by backoff

    @property
    def input_wait_fraction(self) -> float:
        return self.wait_s / self.elapsed_s if self.elapsed_s > 0 else 0.0


class Pipeline:
    """Dataset → pre-split ``(N_Sμ, N_μ, ...)`` batches → device.

    ``sharding`` controls staging:
      * ``None`` — plain ``jax.device_put`` to the default device;
      * a ``jax.sharding.Sharding`` / device — applied to every leaf;
      * a callable ``(split_batch) -> sharding pytree`` — resolved once on
        the first batch (how the launcher passes its mesh batch specs
        without the engine importing the launch layer);
      * with ``stage=False`` no device placement happens at all and the
        pipeline yields host numpy batches (the ``MBSLoader`` facade).

    ``mesh`` is a convenience for the common Layer-6 case: stage every
    split batch with the mesh's batch shardings (``launch/sharding
    .batch_specs`` — dim 0 is the scan axis, the sample dim shards over
    the (pod, data) axes), so the sharded step never reshards its input.
    Mutually exclusive with ``sharding``.

    Batch ``i`` of a pass started at ``start`` is always drawn with seed
    ``seed + start + i``, so a resumed run consumes exactly the stream an
    uninterrupted run would have seen.

    Transient producer failures (the ``faults`` taxonomy's
    ``TransientError`` plus plain ``OSError``) get ``retries`` bounded
    retries with seeded jittered backoff before the existing fail-fast
    propagation; absorbed retries are counted in ``stats.retries`` next to
    ``input_wait_fraction``. The retry re-draws the SAME seeded batch, so
    an absorbed fault never perturbs the data stream.
    """

    def __init__(self, dataset, plan: MBSPlan, *, prefetch: int = 2,
                 stage: bool = True, sharding: Any = None, seed: int = 0,
                 batch_kw: Optional[Dict[str, Any]] = None, mesh: Any = None,
                 retries: int = 2, retry_backoff_s: float = 0.01):
        self.dataset = dataset
        self.plan = plan
        self.prefetch = prefetch
        self.stage = stage
        self.seed = seed
        self.retries = retries
        self.retry_backoff_s = retry_backoff_s
        self.batch_kw = dict(batch_kw or {})
        if mesh is not None:
            if sharding is not None:
                raise ValueError("pass either mesh= or sharding=, not both")

            def sharding(split, _mesh=mesh):
                from ..launch import sharding as sharding_lib  # no cycle
                return sharding_lib.named(
                    sharding_lib.batch_specs(split, _mesh), _mesh)
        self._sharding = sharding
        self._resolved_sharding = None if callable(sharding) else sharding
        self.stats = PipelineStats()

    # -- staging ------------------------------------------------------------

    def _put(self, split):
        if not self.stage:
            return split
        if self._resolved_sharding is None and callable(self._sharding):
            self._resolved_sharding = self._sharding(split)
        if self._resolved_sharding is None:
            return jax.device_put(split)
        return jax.device_put(split, self._resolved_sharding)

    def rebatch(self, step: int):
        """Synthesize, split and stage global step ``step``'s batch again —
        byte-identical to what ``batches()`` would have yielded for it
        (step-indexed seeding), but WITHOUT the fault-injection hooks: this
        is the supervisor's NaN bounded-retry path, re-drawing a poisoned
        batch after the executors' donation already consumed the original
        buffers."""
        mini = self.dataset.batch(self.plan.mini_batch_size,
                                  self.seed + step, **self.batch_kw)
        return self._put(self.plan.split(mini))

    # -- iteration ----------------------------------------------------------

    def batches(self, num_batches: int, start: int = 0
                ) -> Iterator[Dict[str, Any]]:
        """Yield ``num_batches`` staged split batches for global steps
        ``start .. start + num_batches``. Resets ``self.stats``."""
        self.stats = stats = PipelineStats()

        def host_gen():
            rng = _random.Random(self.seed ^ 0x5EED)  # jitter only, not data
            for i in range(start, start + num_batches):
                for attempt in range(self.retries + 1):
                    try:
                        faults.on_host_batch(i)
                        mini = self.dataset.batch(self.plan.mini_batch_size,
                                                  self.seed + i,
                                                  **self.batch_kw)
                        split = self.plan.split(mini)
                        break
                    except (faults.TransientError, OSError):
                        if attempt >= self.retries:
                            raise  # bounded: fail fast like before
                        stats.retries += 1
                        time.sleep(self.retry_backoff_s
                                   * (1 + rng.random()) * (2 ** attempt))
                yield faults.corrupt_batch(split, i)

        it = (prefetch_iterator(host_gen(), self.prefetch)
              if self.prefetch else host_gen())

        def run():
            t_begin = time.perf_counter()
            try:
                nxt = self._next_staged(it, stats)
                while nxt is not _DONE:
                    cur, nxt = nxt, self._next_staged(it, stats)
                    stats.batches += 1
                    yield cur
            finally:
                stats.elapsed_s = time.perf_counter() - t_begin

        return run()

    __call__ = batches  # loader-style invocation

    def _next_staged(self, it, stats: PipelineStats):
        """Pull + stage the next batch, charging the blocked time to
        ``stats.wait_s``. The device_put returns immediately (async
        transfer) — by staging batch i+1 before yielding batch i we get
        the double buffer. The same interval is the ``input`` span of a
        profiler trace."""
        with jax.profiler.TraceAnnotation(spans.INPUT):
            t0 = time.perf_counter()
            try:
                staged = self._put(next(it))
            except StopIteration:
                return _DONE
            finally:
                stats.wait_s += time.perf_counter() - t0
        return staged


_DONE = object()
