"""Plain reference of the benchmark's training job, shared by the
per-architecture references beside this file.

It imports nothing of the program under test. An architecture module gives
``param_shapes(cfg)``, ``init(cfg, key)`` and ``sample_loss(params, cfg,
tokens, labels, mm)``; this module runs the job the traffic file states
(SGD with momentum and coupled weight decay, one update per mini-batch,
the mini-batch loss the mean of the per-sequence mean token losses) and
returns the readings that decide ``correct``:

* ``loss``: the mini-batch loss of each step run;
* ``grad``: per leaf, the norm of the first step's gradient;
* ``update``: per leaf, the norm of the parameters' change over the steps.

Every matrix product goes through ``mm``. ``matmul("fp32")`` is float32 at
precision HIGHEST, so the TPU does not round its operands to bfloat16.
``matmul("fp8")`` is the control: operands rounded to float8 e4m3 and the
incoming gradient of each product to float8 e5m2, each with one scale per
tensor, products accumulated in float32. That is the step below the
bfloat16 compute the configurations state.

The mini-batch gradient is accumulated one sequence at a time into the
momentum buffer, so the reference holds parameters, momentum and one
sequence's gradient at most. With several devices each takes an equal
share of the sequences and the partial sums are added once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
FWD8 = jnp.float8_e4m3fn
BWD8 = jnp.float8_e5m2


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


def _mm32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=F32)


def quantize(x, dtype):
    """Round ``x`` to ``dtype`` with one scale for the whole tensor, so its
    largest magnitude maps to the format's largest finite value."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _mm8(a, b):
    return _mm32(quantize(a, FWD8), quantize(b, FWD8))


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    g = quantize(g, BWD8)
    return (_mm32(g, _swap(quantize(b, FWD8))),
            _mm32(_swap(quantize(a, FWD8)), g))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def matmul(mode: str):
    """``mm(a, b)``: ``a @ b`` over the last two axes; ``a`` and ``b`` have
    the same number of axes and the same leading ones."""
    fn = {"fp32": _mm32, "fp8": _mm8}[mode]

    def mm(a, b):
        if a.ndim != b.ndim:
            raise ValueError(f"mm needs operands of one rank: {a.shape} @ {b.shape}")
        return fn(a.astype(F32), b.astype(F32))

    return mm


def rmsnorm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def tied_head_nll(x, table, labels, mm, chunks: int = 4):
    """Mean token negative log-likelihood of ``labels`` under the logits
    ``x @ table.T``, computed over ``chunks`` blocks of positions so that
    one block of float32 logits is live at a time."""
    S = x.shape[0]
    if S % chunks:
        chunks = 1
    xs = x.reshape(chunks, S // chunks, x.shape[-1])
    ls = labels.reshape(chunks, S // chunks)

    @jax.checkpoint
    def block(carry, xl):
        xc, lc = xl
        logits = mm(xc, table.T)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(block, jnp.zeros((), F32), (xs, ls))
    return total / S


def leaf_norms(tree):
    """{leaf path: float32 norm} of a parameter-like tree (device scalars)."""
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _to_host(d):
    return {k: float(v) for k, v in jax.device_get(d).items()}


class Job:
    """The reference job for one (architecture, configuration, traffic) on
    ``devices``; ``mode`` is ``"fp32"`` or ``"fp8"`` (the control)."""

    def __init__(self, arch, cfg, traffic, devices, mode: str = "fp32"):
        self.arch, self.cfg, self.traffic = arch, cfg, traffic
        opt = traffic["optimizer"]
        if opt["kind"] != "sgd":
            raise ValueError(f"the reference runs SGD only, not {opt['kind']!r}")
        self.lr, self.mu, self.wd = opt["lr"], opt["momentum"], opt["weight_decay"]
        self.mesh = Mesh(np.asarray(devices), ("r",))
        self.rep = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("r"))
        self.ndev = len(devices)
        mm = matmul(mode)

        def seq_loss(params, tokens, labels):
            return arch.sample_loss(params, cfg, tokens, labels, mm)

        self._grad = jax.value_and_grad(seq_loss)
        self._init = jax.jit(functools.partial(arch.init, cfg),
                             out_shardings=self.rep)
        self._accumulate = self._build_accumulate()

    def _build_accumulate(self):
        """(params, mom, tokens, labels) -> (mom + sum_s g_s / n, mean loss),
        the donated ``mom`` updated in place."""
        grad, ndev = self._grad, self.ndev

        def local(params, mom, tokens, labels, n):
            def body(carry, tl):
                acc, loss = carry
                l, g = grad(params, *tl)
                acc = jax.tree.map(lambda a, gg: a + gg / n, acc, g)
                return (acc, loss + l / n), None
            return jax.lax.scan(body, (mom, jnp.zeros((), F32)),
                                (tokens, labels))[0]

        if ndev == 1:
            def fn(params, mom, tokens, labels):
                return local(params, mom, tokens, labels, tokens.shape[0])
        else:
            def fn(params, mom, tokens, labels):
                n = tokens.shape[0]

                def shard(params, mom, tokens, labels):
                    # each device starts from mom / ndev (exact for ndev a
                    # power of two), so the one sum restores mom once
                    part = jax.tree.map(lambda m: m / ndev, mom)
                    acc, loss = local(params, part, tokens, labels, n)
                    return jax.lax.psum((acc, loss), "r")

                return jax.shard_map(shard, mesh=self.mesh,
                                     in_specs=(P(), P(), P("r"), P("r")),
                                     out_specs=(P(), P()),
                                     check_vma=False)(params, mom, tokens, labels)
        return jax.jit(fn, donate_argnums=(1,))

    def readings(self, key, batches, rows=None):
        """Run one step per entry of ``batches`` (dicts of host
        ``tokens``/``labels``) from the weights ``init(cfg, key)``.
        ``rows`` restricts every step to those sequences (the planted
        faults). Returns ``{"loss", "grad", "update"}``."""
        mu, wd, lr = self.mu, self.wd, self.lr
        params = self._init(key)
        mom = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))(params)
        decay = jax.jit(lambda m, p: jax.tree.map(lambda a, b: mu * a + wd * b, m, p),
                        donate_argnums=(0,))
        grad_of = jax.jit(lambda m, p: leaf_norms(
            jax.tree.map(lambda a, b: a - wd * b, m, p)))
        descend = jax.jit(lambda p, m: jax.tree.map(lambda a, b: a - lr * b, p, m),
                          donate_argnums=(0,))
        losses, grad = [], None
        for step, batch in enumerate(batches):
            tokens, labels = batch["tokens"], batch["labels"]
            if rows is not None:
                tokens, labels = tokens[rows], labels[rows]
            tokens = jax.device_put(np.asarray(tokens, np.int32), self.rows)
            labels = jax.device_put(np.asarray(labels, np.int32), self.rows)
            mom = decay(mom, params)
            mom, loss = self._accumulate(params, mom, tokens, labels)
            losses.append(float(loss))
            if step == 0:
                grad = _to_host(grad_of(mom, params))
            params = descend(params, mom)
        del mom
        update = _to_host(jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            jnp.subtract, p, self.arch.init(self.cfg, k))))(params, key))
        del params
        return {"loss": losses, "grad": grad, "update": update}
