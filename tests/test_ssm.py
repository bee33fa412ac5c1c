"""Mamba2 SSD: chunked == sequential recurrence; decode continuity."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.nn import ssm

KEY = jax.random.PRNGKey(0)


def _naive_ssd(xh, Bm, Cm, dt, A):
    """Direct per-step recurrence (f32)."""
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    h = np.zeros((Bsz, H, P, N), np.float64)
    ys = []
    xh, Bm, Cm, dt = map(lambda a: np.asarray(a, np.float64),
                         (xh, Bm, Cm, dt))
    A = np.asarray(A, np.float64)
    for t in range(S):
        decay = np.exp(dt[:, t] * A[None, :])               # (B,H)
        h = decay[:, :, None, None] * h + np.einsum(
            "bh,bn,bhp->bhpn", dt[:, t], Bm[:, t], xh[:, t])
        ys.append(np.einsum("bn,bhpn->bhp", Cm[:, t], h))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("Q", [4, 8, 16])
def test_ssd_chunked_matches_naive(Q):
    B, S, H, P, N = 2, 16, 3, 4, 5
    ks = jax.random.split(KEY, 4)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A = -jnp.exp(jnp.linspace(-1, 0.5, H))
    y, h = ssm.ssd_chunked(xh, Bm, Cm, dt, A, Q)
    y_ref, h_ref = _naive_ssd(xh, Bm, Cm, dt, A)
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h, h_ref, atol=1e-4, rtol=1e-4)


def test_ssd_chunk_size_invariance():
    B, S, H, P, N = 1, 32, 2, 4, 4
    ks = jax.random.split(KEY, 4)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A = -jnp.ones((H,))
    y8, _ = ssm.ssd_chunked(xh, Bm, Cm, dt, A, 8)
    y32, _ = ssm.ssd_chunked(xh, Bm, Cm, dt, A, 32)
    np.testing.assert_allclose(y8, y32, atol=1e-4, rtol=1e-4)


def test_ssd_chunked_grad_finite_on_long_chunks():
    # a 256-step chunk puts the decay exponent above the diagonal past
    # exp's f32 range, which must not leak NaN into the gradient
    B, S, H, P, N = 1, 512, 2, 4, 4
    ks = jax.random.split(KEY, 4)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    Bm = jax.random.normal(ks[1], (B, S, N))
    Cm = jax.random.normal(ks[2], (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)))
    A = -jnp.ones((H,))

    def loss(*a):
        return jnp.sum(ssm.ssd_chunked(*a, A, 256)[0])

    grads = jax.grad(loss, argnums=range(4))(xh, Bm, Cm, dt)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_mamba2_decode_continues_prefill():
    cfg = get_arch("zamba2-1.2b").reduced(num_layers=1, d_model=64)
    cfg = dataclasses.replace(cfg, shared_attn_period=0,
                              block_pattern=("mamba",))
    p = ssm.init_mamba2(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    # full pass over 9 tokens
    y_full = ssm.mamba2_block(p, cfg, x)
    # prefill 8 then decode the 9th
    _, cache = ssm.mamba2_block(p, cfg, x[:, :8], return_cache=True)
    y_dec, _ = ssm.mamba2_decode(p, cfg, x[:, 8:9], cache)
    np.testing.assert_allclose(y_dec[:, 0], y_full[:, 8], atol=1e-3,
                               rtol=1e-3)
