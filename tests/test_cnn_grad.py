"""LeNet5's convolution backward (`cnn._conv2d`'s custom VJP) against
autodiff through the plain `lax.conv_general_dilated`, and its forward
against the plain forward.

The custom VJP changes only how a one-channel input's weight gradient is
computed, so every gradient must match the plain convolution's to float32
rounding (compared at HIGHEST matmul precision on the CPU), and the lowered
forward must be the plain one byte for byte: eval and the upload phase run
the forward alone.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import client as client_lib
from repro.models import cnn
from repro.optim import adam_init
from repro.types import CollabConfig, TrainConfig

RTOL = 1e-5
CLIENTS = 3


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-30))


def _conv_grads(shape_x, shape_w):
    """(x, w) -> d(sum(conv(x, w) * ct))/d(x, w) for a fixed cotangent."""
    k = shape_w[0]
    out = (shape_x[0], shape_x[1] - k + 1, shape_x[2] - k + 1, shape_w[3])
    ct = jax.random.normal(jax.random.PRNGKey(7), out)

    def loss(x, w):
        return jnp.sum(cnn._conv2d(x, w) * ct)

    def args(key):
        kx, kw = jax.random.split(key)
        return (jax.random.normal(kx, shape_x), jax.random.normal(kw, shape_w))

    return jax.grad(loss, argnums=(0, 1)), args


def _lenet_grads(width):
    def loss(p, x):
        s, lg = cnn.apply(p, x)
        return jnp.sum(lg ** 2) + jnp.sum(s)

    def args(key):
        kp, kx = jax.random.split(key)
        return (cnn.init_cnn(kp, width=width),
                jax.random.normal(kx, (8, 28, 28, 1)))

    return jax.grad(loss), args


def _local_update():
    """Two Adam steps of the CoRS local update (the fleet's scan)."""
    spec = client_lib.ClientSpec(apply=cnn.apply,
                                 head=lambda p: (p["head_w"], p["head_b"]))
    ccfg = CollabConfig(mode="cors", num_classes=10, d_feature=84)
    run = client_lib.make_local_update_fn(spec, ccfg, TrainConfig())
    teacher = client_lib.empty_teacher(ccfg)

    def update(p, batches, key):
        p, o, _ = run(p, adam_init(p), batches, teacher, key)
        return p, o.m, o.v

    def args(key):
        kp, kx, ky, kk = jax.random.split(key, 4)
        return (cnn.init_cnn(kp),
                {"x": jax.random.normal(kx, (2, 8, 28, 28, 1)),
                 "y": jax.random.randint(ky, (2, 8), 0, 10)},
                kk)

    return update, args


CASES = {
    "conv1": lambda: _conv_grads((8, 28, 28, 1), (5, 5, 1, 6)),
    "conv2": lambda: _conv_grads((8, 12, 12, 6), (5, 5, 6, 16)),
    "lenet5": lambda: _lenet_grads(1),
    "lenet5-width2": lambda: _lenet_grads(2),
    "local-update-adam2": _local_update,
}


@pytest.mark.parametrize("vmapped", [False, True], ids=["one", "vmap3"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_grad_matches_plain_conv(monkeypatch, case, vmapped):
    def run():
        # a fresh function per side: jax caches traces by function, so a
        # reused one would not see the patched convolution
        fn, make_args = CASES[case]()
        if vmapped:
            keys = jax.random.split(jax.random.PRNGKey(0), CLIENTS)
            return jax.jit(jax.vmap(fn))(*jax.vmap(make_args)(keys))
        return jax.jit(fn)(*make_args(jax.random.PRNGKey(0)))

    with jax.default_matmul_precision("highest"):
        got = run()
        with monkeypatch.context() as m:
            m.setattr(cnn, "_conv2d", cnn._valid_conv)
            want = run()
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for a, b in zip(flat_got, flat_want):
        assert _rel(a, b) < RTOL


def test_forward_lowers_as_plain_conv(monkeypatch):
    p = cnn.init_cnn(jax.random.PRNGKey(0))
    x = jnp.zeros((32, 28, 28, 1))

    def lowered():
        return jax.jit(lambda p, x: cnn.apply(p, x)).lower(p, x).as_text()

    got = lowered()
    monkeypatch.setattr(cnn, "_conv2d", cnn._valid_conv)
    assert lowered() == got
