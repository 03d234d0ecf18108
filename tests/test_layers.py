"""Layer-level unit tests: RoPE/M-RoPE, softcap, chunked attention vs naive,
SSD chunk invariance, RG-LRU scan vs sequential recurrence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import ModelConfig, attention, nn, recurrent, ssm


def test_rope_rotation_preserves_norm():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 8, 4, 16))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    y = nn.apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(jnp.linalg.norm(x, axis=-1),
                               jnp.linalg.norm(y, axis=-1), rtol=1e-5)
    # position 0 is identity
    y0 = nn.apply_rope(x, jnp.zeros((2, 8), jnp.int32), 10_000.0)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(x), atol=1e-6)


def test_rope_relative_property():
    """<rope(q,m), rope(k,n)> depends only on m-n."""
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (1, 1, 1, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 32))

    def dot(m, n):
        qm = nn.apply_rope(q, jnp.full((1, 1), m, jnp.int32), 1e4)
        kn = nn.apply_rope(k, jnp.full((1, 1), n, jnp.int32), 1e4)
        return float(jnp.sum(qm * kn))

    assert abs(dot(5, 3) - dot(12, 10)) < 1e-4


def test_mrope_equals_rope_when_positions_equal():
    """M-RoPE with identical t/h/w position streams == plain RoPE."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (2, 6, 2, 24))
    pos = jnp.broadcast_to(jnp.arange(6)[None], (2, 6))
    mpos = jnp.broadcast_to(pos[None], (3, 2, 6))
    a = nn.apply_rope(x, pos, 1e4)
    b = nn.apply_mrope(x, mpos, 1e4, (4, 4, 4))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_softcap_bounds_and_identity():
    x = jnp.asarray([-100.0, -1.0, 0.0, 1.0, 100.0])
    y = nn.softcap(x, 30.0)
    assert float(jnp.max(jnp.abs(y))) <= 30.0
    np.testing.assert_allclose(np.asarray(nn.softcap(x, None)), np.asarray(x))
    # small values pass ~unchanged
    assert abs(float(nn.softcap(jnp.asarray(1.0), 30.0)) - 1.0) < 1e-3


@pytest.mark.parametrize("S,chunk", [(32, 8), (33, 8), (16, 16), (40, 13)])
def test_ssd_chunk_size_invariance(S, chunk):
    key = jax.random.PRNGKey(3)
    B, H, P, N = 2, 3, 8, 4
    x = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (H,)) * 0.1)
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N))
    y1, f1 = ssm.ssd_chunked(x, dt, A, Bm, Cm, chunk)
    y2, f2 = ssm.ssd_chunked(x, dt, A, Bm, Cm, S)  # single chunk
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=1e-4)


def test_ssd_matches_sequential_recurrence():
    key = jax.random.PRNGKey(4)
    B, S, H, P, N = 1, 12, 2, 4, 3
    x = jax.random.normal(key, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (B, S, H)))
    A = -jnp.exp(jnp.zeros((H,)))
    Bm = jax.random.normal(jax.random.fold_in(key, 3), (B, S, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 4), (B, S, N))
    y, final = ssm.ssd_chunked(x, dt, A, Bm, Cm, 4)
    # sequential reference: h_t = exp(dt*A) h_{t-1} + dt * B x
    h = np.zeros((B, H, P, N))
    for t in range(S):
        dec = np.exp(np.asarray(dt[:, t]) * np.asarray(A))  # (B,H)
        xdt = np.asarray(x[:, t]) * np.asarray(dt[:, t])[..., None]  # (B,H,P)
        h = h * dec[..., None, None] + np.einsum("bn,bhp->bhpn",
                                                 np.asarray(Bm[:, t]), xdt)
        yt = np.einsum("bn,bhpn->bhp", np.asarray(Cm[:, t]), h)
        np.testing.assert_allclose(np.asarray(y[:, t]), yt, atol=1e-4)
    np.testing.assert_allclose(np.asarray(final), h, atol=1e-4)


def _recurrent_ssd(x, dt, A, Bm, Cm, init_state):
    """The SSD one step at a time, in float32: h_t = exp(dt_t A) h_{t-1} +
    dt_t B_t x_t^T, y_t = C_t . h_t. Returns (y, final state)."""
    def step(h, t):
        xt, dtt, bt, ct = t
        h = (h * jnp.exp(dtt * A)[..., None, None]
             + jnp.einsum("bn,bhp->bhpn", bt, xt * dtt[..., None]))
        return h, jnp.einsum("bn,bhpn->bhp", ct, h)
    seq = [jnp.moveaxis(v, 1, 0) for v in (x, dt, Bm, Cm)]
    final, y = jax.lax.scan(step, init_state, seq)
    return jnp.moveaxis(y, 0, 1), final


def _ssd_with_state_loss(ssd, w, w_final):
    def loss(x, dt, A, Bm, Cm, s0):
        y, final = ssd(x, dt, A, Bm, Cm, s0)
        return jnp.sum(y * w) + jnp.sum(final * w_final), (y, final)
    return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(6)),
                                      has_aux=True))


def _rel_gap(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("init", ["zero", "given"])
@pytest.mark.parametrize("S,chunk", [(8, 8), (7, 4), (61, 4)],
                         ids=["nc1", "nc2-ragged", "nc16-ragged"])
def test_ssd_state_passing_matches_recurrence(S, chunk, init):
    """The state passed between chunks (one masked chunks x chunks decay
    contraction) at 1, 2 and 16 chunks, with a ragged tail where S is no
    multiple of the chunk, from a zero and from a given initial state:
    y, the final state and the gradients of both with respect to x, dt, A,
    B, C and the initial state agree with the step-by-step recurrence.
    Both sides are float32 and differ in summation order only: the gaps
    read at most 2e-6 against 1e-4 here; a wrong decay or a lost chunk
    gives gaps of order 1."""
    ks = jax.random.split(jax.random.PRNGKey(S), 8)
    B, H, P, N = 2, 3, 4, 5
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = 0.5 * jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    s0 = (jnp.zeros((B, H, P, N)) if init == "zero"
          else jax.random.normal(ks[5], (B, H, P, N)))
    w = jax.random.normal(ks[6], (B, S, H, P))
    w_final = jax.random.normal(ks[7], (B, H, P, N))
    chunked = (lambda *a: ssm.ssd_chunked(*a[:5], chunk, a[5])
               if init == "given" else ssm.ssd_chunked(*a[:5], chunk))
    (_, got), grads = _ssd_with_state_loss(chunked, w, w_final)(
        x, dt, A, Bm, Cm, s0)
    (_, ref), grads_ref = _ssd_with_state_loss(_recurrent_ssd, w, w_final)(
        x, dt, A, Bm, Cm, s0)
    assert got[0].shape == (B, S, H, P) and got[1].shape == (B, H, P, N)
    for name, a, b in zip(("y", "final"), got, ref):
        assert _rel_gap(a, b) < 1e-4, name
    names = ("x", "dt", "A", "B", "C", "init_state")
    for name, g, g_ref in zip(names, grads, grads_ref):
        if name == "init_state" and init == "zero":
            continue  # the chunked form was not handed the state
        assert _rel_gap(g, g_ref) < 1e-4, name


@pytest.mark.parametrize("strong_chunks", [16, 12])
def test_ssd_at_the_strongest_initial_decay_is_finite(strong_chunks):
    """A = -16 and dt = 0.1, the strongest decay ``ssm_init`` draws, over
    16 chunks of 256 (at 12, the last 4 chunks take the weakest dt, 1e-4):
    every decay between chunks of the strong part underflows to 0, and in
    a chunk the log-decays above the diagonal reach +408, whose exp
    overflows unless masked first. Every gradient, the initial state's
    included, is finite, and y and the final state match the recurrence.
    At 12 the running log-decay reaches -4,915 while each weak chunk adds
    only -0.41: an exponent taken as the difference of two running sums
    loses about 5e-4 there (the final state's gap then reads 2.7e-4), the
    segment sum keeps it (gaps at most 6e-6 on both counts), against 5e-5
    here."""
    ks = jax.random.split(jax.random.PRNGKey(16), 7)
    B, S, H, P, N = 1, 16 * 256, 2, 4, 3
    x = jax.random.normal(ks[0], (B, S, H, P))
    strong = jnp.arange(S)[None, :, None] < strong_chunks * 256
    dt = jnp.where(strong, 0.1, 1e-4) * jnp.ones((B, S, H))
    A = jnp.full((H,), -16.0)
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    s0 = jax.random.normal(ks[3], (B, H, P, N))
    w = jax.random.normal(ks[4], (B, S, H, P))
    w_final = jax.random.normal(ks[5], (B, H, P, N))
    (_, (y, final)), grads = _ssd_with_state_loss(
        lambda *a: ssm.ssd_chunked(*a[:5], 256, a[5]), w, w_final)(
        x, dt, A, Bm, Cm, s0)
    y_ref, final_ref = jax.jit(_recurrent_ssd)(x, dt, A, Bm, Cm, s0)
    for name, g in zip(("x", "dt", "A", "B", "C", "init_state"), grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
    assert _rel_gap(final, final_ref) < 5e-5
    assert _rel_gap(y, y_ref) < 5e-5


def _quadratic_ssd(x, dt, A, Bm, Cm):
    """The SSD's quadratic ("dual") form over the whole sequence, as in the
    benchmark's float32 reference: y_i = sum_{j<=i} (C_i . B_j)
    exp(sum_{k=j+1..i} dt_k A) dt_j x_j."""
    S = x.shape[1]
    cum = jnp.cumsum(dt * A, axis=1)  # (B, S, H)
    seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B, i, j, H)
    below = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    L = jnp.exp(jnp.where(below, seg, -jnp.inf))
    G = jnp.einsum("bin,bjn->bij", Cm, Bm)
    return jnp.einsum("bij,bijh,bjh,bjhp->bihp", G, L, dt, x)


def _ssd_inputs(seed, init, S=512, H=4, P=8, N=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (1, S, H, P))
    dt_raw = jax.random.normal(ks[1], (1, S, H))
    Bm = jax.random.normal(ks[2], (1, S, N)) / N ** 0.5
    Cm = jax.random.normal(ks[3], (1, S, N)) / N ** 0.5
    if init == "program_old":  # A = -1, dt_bias 0: what ssm_init drew before
        A_log, dt_bias = jnp.zeros((H,)), jnp.zeros((H,))
    else:  # Mamba-2's, as ssm_init draws it now
        cfg = ModelConfig(name="s", family="ssm", num_layers=1, d_model=H * P // 2,
                          num_heads=0, num_kv_heads=0, head_dim=0, d_ff=0,
                          vocab_size=8, layer_pattern=("ssm",), ssm_state=N,
                          ssm_head_dim=P, ssm_chunk=256)
        p = ssm.ssm_init(ks[4], cfg)
        A_log, dt_bias = p["A_log"], p["dt_bias"]
    w = jax.random.normal(ks[5], (1, S, H, P))  # the output's cotangent
    return (x, dt_raw, A_log, Bm, Cm, dt_bias), w


def _ssd_loss(ssd, w):
    def loss(x, dt_raw, A_log, Bm, Cm, dt_bias):
        dt = jax.nn.softplus(dt_raw + dt_bias)
        y = ssd(x, dt, -jnp.exp(A_log), Bm, Cm)
        return jnp.sum(y * w), y
    return jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)


@pytest.mark.parametrize("init", ["program_old", "mamba2"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ssd_value_and_grad_at_chunk_256_match_quadratic_form(seed, init):
    """At chunk 256 the in-chunk log-decays above the diagonal reach +100s
    and exp overflows to inf there. The chunked scan's outputs and every
    gradient (x, dt through softplus, A through A_log, B, C, dt_bias) are
    finite and agree with the quadratic form. Tolerances: both sides are
    float32 and differ only in summation order and in the chunked form's
    factoring of each decay across chunks. Over these seeds and inits the
    largest relative gap of y and of the per-element gradients (x, dt, B,
    C) is 2e-5, against 1e-4 here. The per-head gradients (A_log, dt_bias)
    sum S^2 decay terms of both signs, whose cancellation raises the
    relative round-off: at most 9e-5, against 5e-4 here. A wrong mask or
    decay gives gaps of order 1."""
    args, w = _ssd_inputs(seed, init)
    (_, y), grads = _ssd_loss(
        lambda *a: ssm.ssd_chunked(*a, chunk=256)[0], w)(*args)
    (_, y_ref), grads_ref = _ssd_loss(_quadratic_ssd, w)(*args)
    names = ("x", "dt", "A_log", "B", "C", "dt_bias")
    for name, g in zip(names, grads):
        assert bool(jnp.all(jnp.isfinite(g))), name
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(y_ref))))
    for name, g, g_ref in zip(names, grads, grads_ref):
        gap = float(jnp.linalg.norm(g - g_ref) / jnp.linalg.norm(g_ref))
        assert gap < (5e-4 if name in ("A_log", "dt_bias") else 1e-4), (name, gap)


def test_ssm_init_is_mamba2s():
    """A = -exp(A_log) in [-16, -1]; softplus(dt_bias) log-uniform in
    [1e-3, 1e-1]; D = 1."""
    cfg = ModelConfig(name="s", family="ssm", num_layers=1, d_model=1024,
                      num_heads=0, num_kv_heads=0, head_dim=0, d_ff=0,
                      vocab_size=8, layer_pattern=("ssm",), ssm_state=4,
                      ssm_head_dim=2, ssm_chunk=8)
    p = ssm.ssm_init(jax.random.PRNGKey(0), cfg)
    H = cfg.ssm_num_heads
    A = np.asarray(jnp.exp(p["A_log"]))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert A.shape == dt.shape == (H,) == (1024,)
    assert A.min() >= 1.0 and A.max() <= 16.0
    assert A.max() - A.min() > 14.0  # spread over the range, not a constant
    np.testing.assert_allclose([dt.min(), dt.max()], [1e-3, 1e-1], rtol=0.1)
    # log-uniform: the median sits near the geometric mean 1e-2
    assert 0.6e-2 < float(np.median(dt)) < 1.6e-2
    np.testing.assert_array_equal(np.asarray(p["D"]), np.ones(H))


@pytest.mark.parametrize("arch, stream", [("mamba2-780m", jnp.float32),
                                          ("qwen2-1.5b", jnp.bfloat16)])
def test_residual_stream_dtype_follows_the_config(arch, stream):
    """mamba2-780m's published config keeps the residual stream in fp32
    (``residual_in_fp32``): the embedding and the period scan's carry are
    fp32 under bf16 compute, and the hidden states handed to the training
    head come back in the compute dtype. qwen2 keeps it in bf16. The
    activation model counts the stream's period checkpoints at its width."""
    from repro import configs
    from repro.core import memory_model
    from repro.models import transformer
    cfg = configs.get_reduced(arch)
    params = jax.eval_shape(lambda k: transformer.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t: transformer.forward(
        p, cfg, t, dtype=jnp.bfloat16, return_hidden=True))(params, tokens)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    assert scans[0].outvars[0].aval.dtype == stream
    assert jaxpr.out_avals[0].dtype == jnp.bfloat16
    base = dataclasses.replace(cfg, residual_in_fp32=False)
    extra = (memory_model.activation_bytes_per_sample(cfg, 64)
             - memory_model.activation_bytes_per_sample(base, 64))
    assert extra == (cfg.num_periods * 64 * cfg.d_model * 2
                     if stream == jnp.float32 else 0)


def test_rg_lru_scan_matches_sequential():
    cfg = ModelConfig(name="r", family="h", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=1, head_dim=8, d_ff=32,
                      vocab_size=8, lru_width=16)
    p = recurrent.recurrent_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 16))
    out_full, h_full = recurrent.recurrent_block(p, cfg, x)
    # sequential: feed one token at a time through the decode path
    cache = recurrent.init_recurrent_cache(cfg, 2, jnp.float32)
    outs = []
    for t in range(10):
        o, cache = recurrent.recurrent_decode_step(p, cfg, x[:, t:t + 1], cache)
        outs.append(o)
    seq = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(seq),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(cache["h"]),
                               atol=1e-4)


def test_chunked_attention_kvalid_ring():
    """Decode against a partially-filled ring cache masks empty slots."""
    cfg = ModelConfig(name="a", family="d", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
                      vocab_size=8, sliding_window=4)
    p = attention.attn_init(jax.random.PRNGKey(0), cfg)
    cache = attention.init_kv_cache(cfg, 1, 8, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 32))
    out, cache = attention.attn_decode_step(p, cfg, x, cache,
                                            jnp.zeros((1,), jnp.int32),
                                            window=4)
    assert not bool(jnp.isnan(out).any())
    assert int((cache["pos"] >= 0).sum()) == 1
