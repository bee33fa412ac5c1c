"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import blockdiag as bd
from repro.kernels import disc_loss as dl
from repro.kernels import flash_attention as fa
from repro.kernels import proto_accum as pa
from repro.kernels import ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,Sq,Sk,H,G,hd", [
    (2, 128, 128, 4, 2, 64),
    (1, 256, 256, 8, 8, 128),
    (2, 128, 128, 4, 1, 32),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Sk, H, G, hd, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, Sk, G, hd), dtype)
    v = jax.random.normal(ks[2], (B, Sk, G, hd), dtype)
    out = fa.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                             interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("n,d,C", [(100, 84, 10), (512, 128, 256),
                                   (1000, 64, 300), (7, 16, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_proto_accum(n, d, C, dtype):
    ks = jax.random.split(KEY, 2)
    f = jax.random.normal(ks[0], (n, d), dtype)
    l = jax.random.randint(ks[1], (n,), 0, C)
    s, c = pa.proto_accum(f, l, C, block_n=128, block_c=64, interpret=True)
    rs, rc = ref.proto_accum(f, l, C)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(s, rs, atol=tol, rtol=tol)
    np.testing.assert_allclose(c, rc, atol=1e-6)


@pytest.mark.parametrize("di,rows", [(128, 512), (128, 1536), (1536, 512),
                                     (1536, 1024)])
@pytest.mark.parametrize("m", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blockdiag(di, rows, m, dtype):
    """The kernel's values, dx and dW against the einsum, for one
    projection and for q and k fused (against two separate einsums).
    Random blocks, so a transposed block fails. float32: tight; bfloat16:
    within one rounding of the float32-accumulated einsum of the same
    bf16 inputs, which the kernel rounds once."""
    from repro.kernels import ops
    ks = jax.random.split(KEY, 2 + 2 * m)
    x = jax.random.normal(ks[0], (2, rows // 2, di), dtype)
    ws = [jax.random.normal(k, (di // 4, 4, 4), dtype) for k in ks[1:1 + m]]
    gs = [jax.random.normal(k, x.shape, dtype) for k in ks[1 + m:]]

    def loss(project, x, ws):
        return sum(jnp.vdot(y.astype(jnp.float32), g.astype(jnp.float32))
                   for y, g in zip(project(x, ws), gs))

    kernel = lambda x, ws: ops.blockdiag(x, *ws, interpret=True)
    oracle = lambda x, ws: [ref.blockdiag(x, w) for w in ws]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    got = (kernel(x, ws), jax.grad(loss, (1, 2))(kernel, x, ws))
    want = (oracle(*f32((x, ws))), jax.grad(loss, (1, 2))(oracle,
                                                           *f32((x, ws))))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == dtype
        a, b = np.asarray(a, np.float32), np.asarray(b)
        scale = np.max(np.abs(b))
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=2e-6 * scale)
        else:
            np.testing.assert_allclose(a, b, rtol=2 ** -8, atol=1e-6 * scale)


def test_blockdiag_dispatch():
    """The kernel where its shapes fit, the einsum for one token or a width
    off the 128 lanes; on the CPU `ops.blockdiag` is the einsum, and the
    span store counts the path each projection took."""
    from repro.kernels import ops
    assert bd.supports(16 * 2048, 1536, 4)          # lm-xlstm125m-silo
    assert not bd.supports(16, 1536, 4)              # decode: one token
    assert not bd.supports(16 * 2048, 1000, 4)       # di % 128 != 0
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (2, 256, 128))
    wq, wk = (jax.random.normal(k, (32, 4, 4)) for k in ks[1:])
    obs.trace.start()
    try:
        q, k = ops.blockdiag(x, wq, wk)
        assert obs.trace.counters() == {"blockdiag_ref": 2}
        np.testing.assert_array_equal(q, ref.blockdiag(x, wq))
        np.testing.assert_array_equal(k, ref.blockdiag(x, wk))
        (v,) = ops.blockdiag(x, wq, interpret=True)
        assert obs.trace.counters() == {"blockdiag_ref": 2,
                                        "blockdiag_kernel": 1}
        np.testing.assert_allclose(v, q, rtol=1e-6, atol=1e-5)
        ops.blockdiag(x[:, :1], wq, interpret=True)
        assert obs.trace.counters()["blockdiag_ref"] == 3
    finally:
        obs.trace.stop()


@pytest.mark.parametrize("B,C,M", [(32, 10, 10), (64, 1000, 10),
                                   (100, 777, 33), (256, 2048, 128)])
def test_disc_loss(B, C, M):
    ks = jax.random.split(KEY, 3)
    s_log = jax.random.normal(ks[0], (B, C)) * 2
    q = jax.nn.softmax(jax.random.normal(ks[1], (M, C)) * 2, axis=-1)
    y = jax.random.randint(ks[2], (B,), 0, M)
    out = dl.disc_loss(s_log, q, y, jnp.ones((M,), bool), block_b=32,
                       block_c=256, interpret=True)
    want = ref.disc_loss(s_log, q, y, None)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


def test_disc_loss_valid_mask():
    ks = jax.random.split(KEY, 3)
    B, C, M = 16, 64, 8
    s_log = jax.random.normal(ks[0], (B, C))
    q = jax.nn.softmax(jax.random.normal(ks[1], (M, C)), axis=-1)
    y = jax.random.randint(ks[2], (B,), 0, M)
    valid = (jnp.arange(M) % 2 == 0)
    out = dl.disc_loss(s_log, q, y, valid, block_b=16, block_c=64,
                       interpret=True)
    want = ref.disc_loss(s_log, q, y, valid)
    np.testing.assert_allclose(out, want, atol=2e-4, rtol=2e-4)


def test_ref_disc_equals_core_loss():
    """ref.disc_loss (per-sample) must agree with core.losses.disc_loss
    (mean over valid samples) for full-validity inputs."""
    from repro.core import losses
    ks = jax.random.split(KEY, 3)
    B, C, d = 12, 10, 8
    feats = jax.random.normal(ks[0], (B, d))
    obs = jax.random.normal(ks[1], (C, d))
    y = jax.random.randint(ks[2], (B,), 0, C)
    w = jax.random.normal(jax.random.PRNGKey(9), (d, C))
    core = float(losses.disc_loss(feats, obs, y, w))
    q = jax.nn.softmax(obs @ w, axis=-1)
    per = ref.disc_loss(feats @ w, q, y)
    np.testing.assert_allclose(core, float(per.mean()), rtol=1e-5)


def test_ops_wrappers_dispatch():
    from repro.kernels import ops
    q = jax.random.normal(KEY, (1, 128, 4, 32))
    k = jax.random.normal(KEY, (1, 128, 2, 32))
    v = jax.random.normal(KEY, (1, 128, 2, 32))
    a = ops.flash_attention(q, k, v, causal=True)               # ref on CPU
    b = ops.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
