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
