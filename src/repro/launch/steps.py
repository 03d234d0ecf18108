"""Step builders + abstract input specs for every (architecture × shape).

  * train:   MBS train step (paper technique, first-class): micro-batch
             scan + loss normalization + single optimizer update.
  * prefill: full-sequence forward building the decode cache.
  * decode:  one new token against a seq_len KV cache.

``input_specs`` returns ShapeDtypeStructs (weak-type-correct, shardable,
no allocation) for everything the step consumes beyond params/opt-state.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import engine, spans
from ..configs.shapes import InputShape
from ..core import losses
from ..models import encdec, nn, transformer
from ..models import remat as remat_lib
from ..models.config import ModelConfig
from . import mesh as mesh_lib
from .. import optim

N_VISION_TOKENS = 256  # stubbed patch embeds per sample (qwen2-vl frontend)
AUDIO_TGT_FRACTION = 4  # decoder length = seq / 4 for enc-dec training


@dataclasses.dataclass(frozen=True)
class StepBundle:
    kind: str
    fn: Callable  # the step function to jit
    arg_shapes: Tuple[Any, ...]  # abstract args (ShapeDtypeStruct trees)
    donate_argnums: Tuple[int, ...] = ()
    # traced-artifact context for ``repro.analysis`` (train steps only):
    # the plan the step was built against, the loss/optimizer it closes
    # over, and the executor name — so contract checks can verify the
    # compiled step against what the planner admitted without rebuilding.
    plan: Optional[Any] = None
    optimizer: Optional[Any] = None
    loss_fn: Optional[Callable] = None
    executor: Optional[str] = None


# ---------------------------------------------------------------------------
# abstract params / optimizer state
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    init = encdec.init_params if cfg.is_encdec else transformer.init_params
    return jax.eval_shape(lambda k: init(cfg, k), jax.random.PRNGKey(0))


def make_optimizer(cfg: ModelConfig, lr: float = 1e-3) -> optim.Optimizer:
    # production default: SGD momentum (the paper's optimizer); examples
    # override with Adam where the paper does (U-Net).
    return optim.sgd(lr, momentum=0.9, weight_decay=5e-4)


def abstract_opt_state(optimizer, params_shapes):
    return jax.eval_shape(optimizer.init, params_shapes)


# ---------------------------------------------------------------------------
# loss / train step
# ---------------------------------------------------------------------------

@jax.named_scope(spans.HEAD)
def head_loss(params, cfg: ModelConfig, x, labels, *, sample_weight=None,
              exact_denom=None):
    """The training loss on the final hidden states ``x``: the output head
    (tied or not, with ``cfg.final_softcap``) and its cross-entropy as one
    op, ``losses.lm_head_cross_entropy``. ``params`` holds ``embed`` and,
    untied, ``unembed`` (the whole parameter tree, or a staged loss's
    shared part)."""
    w = (params["embed"]["table"] if cfg.tie_embeddings
         else params["unembed"]["w"])
    return losses.lm_head_cross_entropy(
        x, w, labels, tied=cfg.tie_embeddings, softcap=cfg.final_softcap,
        sample_weight=sample_weight, exact_denom=exact_denom)


def make_loss_fn(cfg: ModelConfig, dtype=jnp.bfloat16, remat: bool = True,
                 scan_unroll: int = 1,
                 remat_policy: Optional[str] = None):
    """``remat_policy`` grades activation checkpointing (``models/remat``);
    None keeps the legacy ``remat`` bool mapping (True → "period",
    False → "none"). Pass the *plan's* chosen policy here so the compiled
    loss matches what the planner admitted.

    Where the policy recomputes per period (``remat.RECOMPUTES_PERIOD``)
    and the model is decoder-only, the loss takes ``accum=``, an
    accumulator shaped like ``params["blocks"]``: the gradient of
    ``blocks`` then comes back as ``accum`` plus that gradient
    (``transformer.forward``). Such a loss names that key in
    ``loss_fn.accum_key``, where the executors look for it."""
    policy = remat_lib.resolve(remat, remat_policy)

    def loss_fn(params, mb, exact_denom=None, accum=None):
        sw = mb.get("sample_weight")
        if cfg.is_encdec:
            if accum is not None:
                raise ValueError(f"{cfg.name}: enc-dec takes no accum")
            x, aux = encdec.forward(params, cfg, mb["frames"],
                                    mb["tgt_tokens"], dtype=dtype,
                                    remat_policy=policy,
                                    scan_unroll=scan_unroll,
                                    return_hidden=True)
        else:
            x, aux = transformer.forward(
                params, cfg, mb["tokens"],
                vision_embeds=mb.get("vision_embeds"),
                mrope_positions=mb.get("mrope_positions"),
                dtype=dtype, remat_policy=policy, scan_unroll=scan_unroll,
                return_hidden=True, accum=accum)
        loss = head_loss(params, cfg, x, mb["labels"], sample_weight=sw,
                         exact_denom=exact_denom)
        if cfg.is_moe:
            aux_term = cfg.router_aux_coef * aux / cfg.num_layers
            # exact-mode contract: micro contributions SUM to the mini-batch
            # loss, so additive (non-per-sample) regularizers carry this
            # micro-batch's valid-sample share — Σ_i (valid_i/N_B_valid)·aux_i
            # is the weighted mean over micro-batches (== paper mode's
            # mean when the split is uniform), for every executor.
            if exact_denom is not None:
                n_valid = (jnp.sum(sw) if sw is not None
                           else jnp.asarray(float(jax.tree.leaves(mb)[0].shape[0])))
                aux_term = aux_term * (n_valid / exact_denom)
            loss = loss + aux_term
        return loss, {"aux_loss": aux}

    if not cfg.is_encdec and policy in remat_lib.RECOMPUTES_PERIOD:
        loss_fn.accum_key = "blocks"
    return loss_fn


def make_staged_loss(cfg: ModelConfig, dtype=jnp.bfloat16, remat: bool = True,
                     scan_unroll: int = 1,
                     remat_policy: Optional[str] = None) -> engine.StagedLoss:
    """Factor the decoder-only transformer loss into the prelude /
    stage_fn / finale triple that :class:`engine.PipelinedExecutor`
    schedules (engine Layer 11).

    The stage boundary is the period axis: ``params["blocks"]`` leaves
    are stacked ``(num_periods, ...)`` and ``StagedLoss.partition``
    reshapes them to ``(stages, periods_per_stage, ...)``; each stage
    scans its local periods exactly like :func:`transformer.forward`
    scans the whole stack, under the same checkpoint lattice. The finale
    emits the RAW loss sum (``exact_denom=1`` semantics) — the executor
    divides by the global valid count after its cross-mesh psum, which
    is what makes pipelined numerics match the single-device exact path.

    Families whose forward does not cut at period boundaries with only a
    ``(B, S, d_model)`` carry are rejected: MoE (router aux loss
    accumulates across periods into the scalar loss), enc-dec (two
    stacks joined by cross-attention), and VLM (the vision frontend
    feeds extra inputs into the embed prelude).
    """
    if cfg.is_encdec or cfg.is_moe or cfg.is_vlm:
        which = ("enc-dec" if cfg.is_encdec else
                 "MoE" if cfg.is_moe else "VLM")
        raise ValueError(
            f"{cfg.name}: pipeline staging supports dense decoder-only "
            f"stacks; {which} forwards do not factor into "
            "prelude/stage_fn/finale with a (B, S, d_model) carry — run "
            "this family on the data axis (ShardedExecutor) instead")
    policy = remat_lib.resolve(remat, remat_policy)

    def _positions(x):
        B, S = x.shape[:2]
        return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    @jax.named_scope(spans.TRUNK)
    def prelude(shared, mb):
        return transformer._embed_inputs(shared, cfg, mb["tokens"], None,
                                         dtype)

    @jax.named_scope(spans.TRUNK)
    def stage_fn(stage_p, x):
        positions = _positions(x)

        def period_fn(x, slot_params):
            aux = jnp.zeros((), jnp.float32)
            for kind, p in zip(cfg.layer_pattern, slot_params):
                x, a, _ = transformer._apply_slot(p, cfg, kind, x, positions,
                                                  dtype=dtype,
                                                  remat_policy=policy)
                aux = aux + a
            return x, aux

        period_fn = remat_lib.checkpoint_period(period_fn, policy)
        x, _ = jax.lax.scan(period_fn, x, stage_p, unroll=scan_unroll)
        return x

    def finale(shared, x, mb):
        with jax.named_scope(spans.TRUNK):
            x = nn.rmsnorm(shared["final_norm"], x, cfg.norm_eps)
        return head_loss(shared, cfg, x.astype(dtype), mb["labels"],
                         sample_weight=mb.get("sample_weight"),
                         exact_denom=1.0), {}

    return engine.StagedLoss(num_layers=cfg.num_periods, prelude=prelude,
                             stage_fn=stage_fn, finale=finale,
                             stacked_key="blocks")


def abstract_train_batch(cfg: ModelConfig, seq_len: int, plan, *,
                         dtype=jnp.bfloat16) -> Dict[str, Any]:
    """ShapeDtypeStruct tree of a SPLIT ``(N_Sμ, N_μ, ...)`` train batch
    for one (architecture × plan) — what the compiled train step consumes
    beyond params/opt-state. Shared by :func:`build_train_step` and the
    ``repro.analysis`` suite (which traces steps without building data)."""
    s = seq_len
    n, m = plan.num_micro_batches, plan.micro_batch_size
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    if cfg.is_encdec:
        batch = {
            "frames": sds((n, m, s, cfg.d_model), dtype),
            "tgt_tokens": sds((n, m, s // AUDIO_TGT_FRACTION), i32),
            "labels": sds((n, m, s // AUDIO_TGT_FRACTION), i32),
        }
    else:
        batch = {
            "tokens": sds((n, m, s), i32),
            "labels": sds((n, m, s), i32),
        }
        if cfg.is_vlm:
            batch["vision_embeds"] = sds(
                (n, m, N_VISION_TOKENS, transformer.VISION_EMBED_DIM), dtype)
            batch["mrope_positions"] = sds((n, 3, m, s), i32)
    # the plan's pad-and-mask split always emits the sample-weight mask
    batch["sample_weight"] = sds((n, m), f32)
    return batch


def build_train_step(cfg: ModelConfig, shape: InputShape, *,
                     num_microbatches: Optional[int] = None, optimizer=None,
                     dtype=jnp.bfloat16, remat: bool = True,
                     remat_policy: Optional[str] = None,
                     normalization: str = "paper",
                     scan_unroll: int = 1,
                     executor: str = "compiled",
                     mesh=None, fsdp: bool = False) -> StepBundle:
    """Compiled train step via the MBS engine. ``num_microbatches=None``
    auto-sizes the micro-batch from the analytic memory model (the paper's
    experimentally-determined size, computed — §4.3.2); ragged splits are
    padded + masked rather than asserted away. ``remat_policy`` (incl.
    ``"auto"``) goes through the planner; the loss is built with the
    plan's *chosen* policy. ``mesh`` makes the plan mesh-aware (engine
    Layer 6): per-device budget, micro sizes divisible by the data axis —
    pass the mesh the step will be compiled against.

    When the mesh has a ``model`` axis of size > 1 the step routes
    through engine Layer 11 instead: ``plan_mbs(pipeline=True)`` budgets
    stage-local activations × in-flight depth and the
    :class:`engine.PipelinedExecutor` runs the plan's micro-batches
    through the 1F1B schedule (``fsdp=True`` additionally shards params
    over the data axis with just-in-time gathers)."""
    optimizer = optimizer or make_optimizer(cfg)
    pipeline = (mesh is not None
                and mesh_lib.axis_size(mesh, mesh_lib.MODEL_AXIS) > 1)
    plan = engine.plan_mbs(shape.global_batch,
                           num_microbatches=num_microbatches,
                           model_cfg=cfg, seq_len=shape.seq_len,
                           normalization=normalization, unroll=scan_unroll,
                           act_bytes=jnp.dtype(dtype).itemsize, remat=remat,
                           remat_policy=remat_policy, mesh=mesh,
                           pipeline=pipeline,
                           **optim.memory_model_kw(optimizer,
                                                   fused=executor == "flat"))
    if pipeline:
        staged = make_staged_loss(cfg, dtype, scan_unroll=scan_unroll,
                                  remat_policy=plan.remat_policy)
        step = engine.PipelinedExecutor(staged, optimizer, plan, mesh=mesh,
                                        fsdp=fsdp).make_train_step()
        executor = "pipelined"
        loss_fn = None
    else:
        loss_fn = make_loss_fn(cfg, dtype, scan_unroll=scan_unroll,
                               remat_policy=plan.remat_policy)
        step = engine.get_executor(executor)(
            loss_fn, optimizer, plan).make_train_step()

    batch = abstract_train_batch(cfg, shape.seq_len, plan, dtype=dtype)
    params = abstract_params(cfg)
    opt_state = abstract_opt_state(optimizer, params)
    # donate state AND the split batch: the batch is spent after the scan,
    # freeing its buffers for the update step's temporaries
    return StepBundle("train", step, (params, opt_state, batch),
                      donate_argnums=(0, 1, 2), plan=plan,
                      optimizer=optimizer, loss_fn=loss_fn,
                      executor=executor)


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------

def build_prefill_step(cfg: ModelConfig, shape: InputShape, *,
                       dtype=jnp.bfloat16, scan_unroll: int = 1,
                       remat_policy: str = "none") -> StepBundle:
    """``remat_policy`` defaults to "none" (prefill is forward-only, so
    checkpointing buys nothing when serving alone) but is routed through —
    NOT hardcoded — so eval interleaved with training can compile under
    the training policy when memory is tight."""
    s, b = shape.seq_len, shape.global_batch
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    gw = cfg.long_context_global_window if shape.name == "long_500k" else None

    if cfg.is_encdec:
        def fn(params, frames, tokens):
            # encoder over the audio, then teacher-forced decoder prefill;
            # returns last-position logits (cache built by init_decode_cache
            # in the serving loop).
            logits, _ = encdec.forward(params, cfg, frames, tokens,
                                       dtype=dtype,
                                       remat_policy=remat_policy,
                                       scan_unroll=scan_unroll)
            return logits[:, -1]

        args = (abstract_params(cfg), sds((b, s, cfg.d_model), dtype),
                sds((b, s // AUDIO_TGT_FRACTION), i32))
        return StepBundle("prefill", fn, args)

    def fn(params, tokens, vision_embeds=None, mrope_positions=None):
        return transformer.prefill(params, cfg, tokens, max_len=s,
                                   vision_embeds=vision_embeds,
                                   mrope_positions=mrope_positions,
                                   dtype=dtype, global_window=gw,
                                   scan_unroll=scan_unroll)

    args = [abstract_params(cfg), sds((b, s), i32)]
    if cfg.is_vlm:
        args += [sds((b, N_VISION_TOKENS, transformer.VISION_EMBED_DIM), dtype),
                 sds((3, b, s), i32)]
    return StepBundle("prefill", fn, tuple(args))


def abstract_cache(cfg: ModelConfig, shape: InputShape, dtype=jnp.bfloat16):
    gw = cfg.long_context_global_window if shape.name == "long_500k" else None
    if cfg.is_encdec:
        b, s = shape.global_batch, shape.seq_len
        # built abstractly (matches encdec.init_decode_cache's structure)
        K, hd = cfg.num_kv_heads, cfg.head_dim
        L = cfg.num_layers
        sds = jax.ShapeDtypeStruct
        T = s // AUDIO_TGT_FRACTION  # encoder frames feeding cross-attn
        return {
            "self": {
                "k": sds((L, b, s, K, hd), dtype),
                "v": sds((L, b, s, K, hd), dtype),
                "pos": sds((L, b, s), jnp.int32),
            },
            "cross": {
                "k": sds((L, b, T, K, hd), dtype),
                "v": sds((L, b, T, K, hd), dtype),
            },
        }
    cache = jax.eval_shape(
        functools.partial(transformer.init_cache, cfg, shape.global_batch,
                          shape.seq_len, dtype, global_window=gw))
    return cache


def build_decode_step(cfg: ModelConfig, shape: InputShape, *,
                      dtype=jnp.bfloat16, scan_unroll: int = 1) -> StepBundle:
    b = shape.global_batch
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    gw = cfg.long_context_global_window if shape.name == "long_500k" else None
    cache = abstract_cache(cfg, shape, dtype)

    if cfg.is_encdec:
        def fn(params, token, cache, pos):
            return encdec.decode_step(params, cfg, token, cache, pos,
                                      dtype=dtype, scan_unroll=scan_unroll)
    else:
        def fn(params, token, cache, pos):
            return transformer.decode_step(params, cfg, token, cache, pos,
                                           dtype=dtype, global_window=gw,
                                           scan_unroll=scan_unroll)

    args = (abstract_params(cfg), sds((b, 1), i32), cache, sds((b,), i32))
    return StepBundle("decode", fn, args, donate_argnums=(2,))


def build_step(cfg: ModelConfig, shape: InputShape, *, num_microbatches: int = 8,
               dtype=jnp.bfloat16, scan_unroll: int = 1, **kw) -> StepBundle:
    if shape.kind == "train":
        return build_train_step(cfg, shape, num_microbatches=num_microbatches,
                                dtype=dtype, scan_unroll=scan_unroll, **kw)
    if shape.kind == "prefill":
        # eval/serving compiles under the caller's policy (not a hardcoded
        # remat=False); "auto" has no planner here — use the lattice floor
        policy = kw.get("remat_policy") or "none"
        return build_prefill_step(
            cfg, shape, dtype=dtype, scan_unroll=scan_unroll,
            remat_policy="none" if policy == "auto" else policy)
    return build_decode_step(cfg, shape, dtype=dtype, scan_unroll=scan_unroll)
