"""xLSTM: mLSTM chunked parallel form vs sequential; sLSTM scan; decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.nn import xlstm

KEY = jax.random.PRNGKey(0)


def _naive_mlstm(q, k, v, logf, logi):
    B, S, H, P = q.shape
    q = np.asarray(q, np.float64) * P ** -0.5
    k, v = np.asarray(k, np.float64), np.asarray(v, np.float64)
    f = np.exp(np.asarray(logf, np.float64))
    i = np.exp(np.asarray(logi, np.float64))
    C = np.zeros((B, H, P, P))
    n = np.zeros((B, H, P))
    ys = []
    for t in range(S):
        C = f[:, t, :, None, None] * C + i[:, t, :, None, None] * np.einsum(
            "bhp,bhn->bhpn", v[:, t], k[:, t])
        n = f[:, t, :, None] * n + i[:, t, :, None] * k[:, t]
        num = np.einsum("bhn,bhpn->bhp", q[:, t], C)
        den = np.abs(np.einsum("bhn,bhn->bh", q[:, t], n))
        ys.append(num / np.maximum(den, 1.0)[:, :, None])
    return np.stack(ys, 1)


@pytest.mark.parametrize("Q", [4, 16])
def test_mlstm_chunked_matches_naive(Q):
    B, S, H, P = 2, 16, 2, 4
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, S, H, P))
    k = jax.random.normal(ks[1], (B, S, H, P))
    v = jax.random.normal(ks[2], (B, S, H, P))
    logf = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, S, H)))
    logi = jax.random.normal(ks[4], (B, S, H)) * 0.3
    y, _ = xlstm.mlstm_chunked(q, k, v, logf, logi, Q)
    want = _naive_mlstm(q, k, v, logf, logi)
    np.testing.assert_allclose(y, want, atol=1e-4, rtol=1e-4)


def test_mlstm_chunked_grad_finite_on_long_chunks():
    # xlstm-125m's chunk (256): the decay exponent above the diagonal
    # passes exp's f32 range, which must not leak NaN into the gradient
    B, S, H, P = 1, 512, 2, 8
    ks = jax.random.split(KEY, 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, P)) for i in range(3))
    fpre = jax.random.normal(ks[3], (B, S, H))
    logi = jax.random.normal(ks[4], (B, S, H))

    def loss(*a):
        q, k, v, fpre, logi = a
        y, _ = xlstm.mlstm_chunked(q, k, v, jax.nn.log_sigmoid(fpre), logi,
                                   256)
        return jnp.sum(y)

    grads = jax.grad(loss, argnums=range(5))(q, k, v, fpre, logi)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_mlstm_block_decode_continues_prefill():
    cfg = get_arch("xlstm-125m").reduced(num_layers=1, d_model=64)
    p = xlstm.init_mlstm(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    y_full = xlstm.mlstm_block(p, cfg, x)
    _, cache = xlstm.mlstm_block(p, cfg, x[:, :8], return_cache=True)
    y_dec, _ = xlstm.mlstm_block(p, cfg, x[:, 8:9], cache=cache, decode=True)
    np.testing.assert_allclose(y_dec[:, 0], y_full[:, 8], atol=1e-3,
                               rtol=1e-3)


def test_slstm_normalizer_bounds_state():
    cfg = get_arch("xlstm-125m").reduced(num_layers=1, d_model=64)
    p = xlstm.init_slstm(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model)) * 3
    y = xlstm.slstm_block(p, cfg, x)
    assert np.all(np.isfinite(np.asarray(y)))


def test_slstm_decode_continues_prefill():
    cfg = get_arch("xlstm-125m").reduced(num_layers=1, d_model=64)
    p = xlstm.init_slstm(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    y_full = xlstm.slstm_block(p, cfg, x)
    _, state = xlstm.slstm_block(p, cfg, x[:, :8], return_cache=True)
    y_dec, _ = xlstm.slstm_block(p, cfg, x[:, 8:9], cache=state, decode=True)
    np.testing.assert_allclose(y_dec[:, 0], y_full[:, 8], atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# the published blocks (xlstm-7x1-125m)
# ---------------------------------------------------------------------------
def _published(**kw):
    return get_arch("xlstm-7x1-125m").reduced(num_layers=1, d_model=64, **kw)


def test_published_mlstm_chunked_form_is_the_recurrence():
    """The published mLSTM block over a whole sequence, chunked, equals the
    same block stepped one token at a time through its decode cache."""
    cfg = _published()
    p = xlstm.init_mlstm(KEY, cfg, jnp.float32)
    p["w_gates"] = jax.random.normal(KEY, p["w_gates"].shape) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_full = xlstm.mlstm_block(p, cfg, x)
    H, di = cfg.num_heads, 2 * cfg.d_model
    cache = (jnp.zeros((2, cfg.ssm_conv - 1, di)),
             jnp.zeros((2, H, di // H + 1, di // H)))
    ys = []
    for t in range(x.shape[1]):
        y, cache = xlstm.mlstm_block(p, cfg, x[:, t:t + 1], cache=cache,
                                     decode=True)
        ys.append(y[:, 0])
    np.testing.assert_allclose(np.stack(ys, 1), y_full, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("kind", ["mlstm", "slstm"])
def test_published_block_decode_continues_prefill(kind):
    cfg = _published()
    init = xlstm.init_mlstm if kind == "mlstm" else xlstm.init_slstm
    block = xlstm.mlstm_block if kind == "mlstm" else xlstm.slstm_block
    p = init(KEY, cfg, jnp.float32)
    if kind == "slstm":     # published init: recurrent kernels start at 0
        p["r_gates"] = jax.random.normal(KEY, p["r_gates"].shape) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    y_full = block(p, cfg, x)
    _, cache = block(p, cfg, x[:, :8], return_cache=True)
    y_dec, _ = block(p, cfg, x[:, 8:9], cache=cache, decode=True)
    np.testing.assert_allclose(y_dec[:, 0], y_full[:, 8], atol=1e-4,
                               rtol=1e-4)


def test_published_mlstm_block_kernel_is_the_einsum(monkeypatch):
    """The published mLSTM block with its q/k/v projections through the
    block-diagonal kernel (forced, interpret mode) against the einsum
    block: output and gradient, under jax.checkpoint as the training scan
    runs it; d_model 64, so di = 128 is one lane tile."""
    import functools

    from repro import obs
    from repro.kernels import ops
    cfg = _published()
    p = xlstm.init_mlstm(KEY, cfg, jnp.float32)
    p["w_gates"] = jax.random.normal(KEY, p["w_gates"].shape) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, cfg.d_model))
    g = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def run(p, x):
        block = jax.checkpoint(lambda p, x: xlstm.mlstm_block(p, cfg, x))
        return jax.value_and_grad(
            lambda p, x: jnp.vdot(block(p, x), g), (0, 1))(p, x)

    want = run(p, x)
    monkeypatch.setattr(ops, "blockdiag", functools.partial(
        ops.blockdiag, interpret=True))
    obs.trace.start()
    try:
        got = run(p, x)
        assert set(obs.trace.counters()) == {"blockdiag_kernel"}
    finally:
        obs.trace.stop()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * np.max(np.abs(b)))


def test_published_parameter_count_is_the_hand_count():
    """3,605,768 per mLSTM block (up 768x3072, q/k/v 3x384x4x4, conv 4x1536
    + 1536, gates 4608x8 + 8, head-wise norm 1536, skip 1536, down
    1536x768, pre-norm 768) and 3,548,160 per sLSTM block (conv 4x768 +
    768, gates and recurrent kernels 2x4x4x192x192, biases 4x768, norm
    768, pre-norms 2x768, feed-forward 3x768x1024), 21 and 3 of them, plus
    the untied embedding and head 2x50304x768 and the final norm."""
    from repro.launch import roofline
    from repro.models import lm
    cfg = get_arch("xlstm-7x1-125m")
    shapes = jax.eval_shape(lambda k: lm.init_lm(k, cfg), KEY)
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert n == 21 * 3_605_768 + 3 * 3_548_160 + 77_266_944 + 768
    assert 163.6e6 <= n <= 163.8e6
    assert [i for i, k in enumerate(cfg.block_pattern) if k == "slstm"] == [
        3, 11, 19]
    # the roofline's FLOP terms: the weights that multiply every token
    assert roofline.xlstm_matmul_params(cfg, "mlstm") == (
        768 * 3072 + 3 * 1536 * 4 + 4608 * 8 + 1536 * 768)
    assert roofline.xlstm_matmul_params(cfg, "slstm") == (
        2 * 4 * 768 * 192 + 3 * 768 * 1024)


def test_published_gate_bias_init():
    cfg = get_arch("xlstm-7x1-125m")
    pm = xlstm.init_mlstm(KEY, cfg, jnp.float32)
    np.testing.assert_allclose(pm["b_gates"][4:], [3.0, 4.0, 5.0, 6.0])
    assert np.all(np.asarray(pm["w_gates"]) == 0)
    ps = xlstm.init_slstm(KEY, cfg, jnp.float32, index=11)
    f = np.asarray(ps["b_gates"]).reshape(4, 4, 192)
    j = np.arange(192) / 191
    want = -(-5.0 + 12.0 * j ** (0.3 + 1.3 * 11 / 23))
    np.testing.assert_allclose(f[1], np.broadcast_to(want, (4, 192)),
                               rtol=1e-5, atol=1e-6)
    assert np.all(f[[0, 2, 3]] == 0) and np.all(np.asarray(ps["r_gates"]) == 0)
