"""Compile the main path's kernels and the fleet's update step for a TPU
v5e chip that is described, not attached.

The TPU compiler refuses what interpret mode accepts: tiles not aligned to
the chip's layout, more fast memory than a kernel may use, a program that
does not fit the device. These compiles run at the real widths (LeNet5
fleet of 32 clients, xlstm-125m's d_model and vocabulary, a 4k-token
attention) and cost no chip time. Nothing here executes.

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro import relay as relay_lib
from repro.core import client as client_lib, vec_collab
from repro.kernels import disc_loss as dl
from repro.kernels import flash_attention as fa
from repro.kernels import proto_accum as pa
from repro.models import cnn
from repro.optim import adam_init
from repro.types import CollabConfig, TrainConfig


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip, so keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    """Shapes of `tree` (arrays or ShapeDtypeStructs) placed on `sharding`."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("n,d,C,dtype", [
    (32, 84, 10, jnp.float32),            # LeNet5 client batch: d'=84, C=10
    (8192, 768, 50304, jnp.bfloat16),     # xlstm-125m: 8k tokens, full vocab
])
def test_proto_accum_compiles(one_chip, n, d, C, dtype):
    fn = jax.jit(lambda f, y: pa.proto_accum(f, y, C))
    compiled = fn.lower(_sds(one_chip, (n, d), dtype),
                        _sds(one_chip, (n,), jnp.int32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("B,C,M", [
    (256, 50304, 64),                     # LM: full-vocab student logits
    (32, 10, 10),                         # LeNet5 fleet: C = M = 10
])
def test_disc_loss_compiles(one_chip, B, C, M):
    compiled = jax.jit(dl.disc_loss).lower(
        _sds(one_chip, (B, C), jnp.float32),
        _sds(one_chip, (M, C), jnp.float32),
        _sds(one_chip, (B,), jnp.int32),
        _sds(one_chip, (M,), jnp.bool_)).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles(one_chip):
    # 4k tokens, 32 query heads over 8 kv heads of width 128, bf16
    q = _sds(one_chip, (1, 4096, 32, 128), jnp.bfloat16)
    kv = _sds(one_chip, (1, 4096, 8, 128), jnp.bfloat16)
    compiled = jax.jit(fa.flash_attention).lower(q, kv, kv).compile()
    _assert_kernel(compiled)


def test_fleet_bucket_update_step_compiles(one_chip):
    """One bucket of 32 LeNet5 clients (three local batches of 32) against
    a flat relay: downlink, vmapped local updates and upload payloads."""
    N, nb, bs = 32, 3, 32
    ccfg = CollabConfig(mode="cors", num_classes=10, d_feature=84)
    policy = relay_lib.get_policy("flat")
    spec = client_lib.ClientSpec(apply=lambda p, x: cnn.apply(p, x),
                                 head=lambda p: (p["head_w"], p["head_b"]))
    step = vec_collab.make_bucket_update_step(spec, ccfg, TrainConfig(),
                                              policy)

    def stacked_init():
        p = cnn.init_cnn(jax.random.PRNGKey(0))
        stack = lambda t: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (N,) + a.shape), t)
        return stack(p), stack(adam_init(p))

    params, opt = _on(one_chip, jax.eval_shape(stacked_init))
    rstate = _on(one_chip, jax.eval_shape(
        lambda: policy.init_state(ccfg, ccfg.d_feature, 0, n_clients=N)))
    batches = {"x": _sds(one_chip, (N, nb, bs, 28, 28, 1), jnp.float32),
               "y": _sds(one_chip, (N, nb, bs), jnp.int32)}
    data_x = _sds(one_chip, (N, nb * bs, 28, 28, 1), jnp.float32)
    data_y = _sds(one_chip, (N, nb * bs), jnp.int32)
    ids = _sds(one_chip, (N,), jnp.int32)
    keys = _sds(one_chip, (N, 2), jnp.uint32)
    mask = _sds(one_chip, (N,), jnp.bool_)
    compiled = step.lower(params, opt, rstate, batches, data_x, data_y, ids,
                          keys, keys, keys, mask).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
