"""The names the training step writes into a profiler trace.

This module imports nothing, so that the models and the engine can both
name their work from it. Two kinds, one vocabulary, so that an operator who profiles a run (the
launcher's ``--profile-dir``) and the benchmark read the same names.

Device phases are ``jax.named_scope``s. A scope is metadata: it lands in
every HLO instruction's ``op_name`` (the name stack), and the compiled
program is otherwise the same. Each is placed once, where the work is:

  ``trunk``       the model below the head: embedding, the period scan of
                  the blocks, the final norm (``transformer.forward``,
                  ``steps.make_staged_loss``'s prelude and stages). Not
                  ``body``: JAX writes ``while/body`` into the name stack
                  of every loop, so that word would name every op of the
                  micro-batch scan;
  ``head``        the output head with its softcap and the cross-entropy
                  on its logits: in training one op, ``steps.head_loss``
                  (the loss of ``steps.make_loss_fn`` and of the staged
                  finale); in serving ``transformer._lm_head``;
  ``accumulate``  adding a micro-batch's gradient into the accumulator
                  (``exec_core.accumulate``, ``accumulate_flat``: the plain
                  add and the Pallas kernel alike);
  ``update``      the optimizer step once per mini-batch
                  (``exec_core.apply_update``, ``apply_update_flat`` and
                  both guarded forms);
  ``grad_sync``   the cross-device gradient reduction with its pack and
                  unpack copies (``sharded.psum_flat``, the gradient psums
                  of ``pipelined.py``).

One more scope is a tag inside ``trunk`` and not a phase of its own, as
``head`` is in the loss:

  ``ssd``         Mamba-2's chunked state-space scan (``ssm.ssd_chunked``):
                  the segment sums and their decays, the in-chunk and
                  chunk-state einsums and the associative scan across
                  chunks, in every direction. The block's projections,
                  convolution and gate around it are not under it.

Forward, recompute and backward are not scopes: JAX writes them into the
name stack itself. Under ``jax.grad`` the backward's ops carry
``transpose(``; ops recomputed under ``jax.checkpoint`` carry
``rematted_computation`` below it; the first forward carries neither.

Host spans are ``jax.profiler.TraceAnnotation``s, on the profiler's clock,
so an idle gap on the device lines up with what the host was doing:

  ``input``       the training loop blocked on its next staged batch
                  (``Pipeline._next_staged``: the time
                  ``PipelineStats.wait_s`` counts);
  ``dispatch``    handing one step to the device (``Trainer.fit``: tracing
                  and compiling on the first call, enqueueing after);
  ``readback``    waiting for a step's metrics to reach the host
                  (``Trainer._readback``);
  ``checkpoint``  writing a checkpoint (``Trainer.save``).

No span is named ``window``: the benchmark names its timed window so and
takes the first such span it finds.
"""
from __future__ import annotations

TRUNK = "trunk"
HEAD = "head"
ACCUMULATE = "accumulate"
UPDATE = "update"
GRAD_SYNC = "grad_sync"
DEVICE_PHASES = (TRUNK, HEAD, ACCUMULATE, UPDATE, GRAD_SYNC)
SSD = "ssd"

INPUT = "input"
DISPATCH = "dispatch"
READBACK = "readback"
CHECKPOINT = "checkpoint"
HOST_SPANS = (INPUT, DISPATCH, READBACK, CHECKPOINT)
