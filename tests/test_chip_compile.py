"""Compile the main path's kernels and the fleet's round step for a TPU
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
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro import relay as relay_lib
from repro.core import client as client_lib, vec_collab
from repro.kernels import blockdiag as bd
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


@pytest.mark.parametrize("m", [1, 2])
def test_blockdiag_compiles(one_chip, m):
    """lm-xlstm125m-silo's q/k/v projections, 16 x 2048 rows of 1536, v
    alone and q and k fused: forward and both gradients, as kernels with
    no activation-sized copy around them."""
    n, di = 16 * 2048, 1536

    def loss(x, ws):
        ys = bd.project(x, bd.tiles(ws), False)
        return sum(jnp.sum(y.astype(jnp.float32)) for y in ys)

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        _sds(one_chip, (n, di), jnp.bfloat16),
        _sds(one_chip, (m, di // 4, 4, 4), jnp.bfloat16)).compile()
    text = compiled.as_text()
    _assert_kernel(compiled)
    assert not re.search(rf"= \S+\[{n},{di}\]\S* copy\(", text)


def _compile_round_step(sharding, n_clients=32):
    """The fleet's round step for n_clients LeNet5 clients (three local
    batches of 32) against a flat relay, as one bucket at full
    participation: downlink, vmapped local updates, upload payloads and
    the relay commit."""
    N, nb, bs = n_clients, 3, 32
    ccfg = CollabConfig(mode="cors", num_classes=10, d_feature=84)
    policy = relay_lib.get_policy("flat")
    spec = client_lib.ClientSpec(apply=lambda p, x: cnn.apply(p, x),
                                 head=lambda p: (p["head_w"], p["head_b"]))
    step = vec_collab.make_round_step([spec], [np.arange(N)], ccfg,
                                      TrainConfig(), policy)

    def stacked_init():
        p = cnn.init_cnn(jax.random.PRNGKey(0))
        stack = lambda t: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (N,) + a.shape), t)
        return stack(p), stack(adam_init(p))

    params, opt = _on(sharding, jax.eval_shape(stacked_init))
    rstate = _on(sharding, jax.eval_shape(
        lambda: policy.init_state(ccfg, ccfg.d_feature, 0, n_clients=N)))
    batches = {"x": _sds(sharding, (N, nb, bs, 28, 28, 1), jnp.float32),
               "y": _sds(sharding, (N, nb, bs), jnp.int32)}
    data_x = _sds(sharding, (N, nb * bs, 28, 28, 1), jnp.float32)
    data_y = _sds(sharding, (N, nb * bs), jnp.int32)
    ids = _sds(sharding, (N,), jnp.int32)
    keys = _sds(sharding, (N, 2), jnp.uint32)
    mask = _sds(sharding, (N,), jnp.bool_)
    # idx, delays, round_idx, hist, dl: None for a synchronous, unlagged,
    # full-participation fleet
    return step.lower((params,), (opt,), rstate, None, (batches,),
                      (data_x,), (data_y,), ids, keys, keys, keys, mask,
                      None, None, None, None, None).compile()


def test_fleet_bucket_update_step_compiles(one_chip):
    mem = _compile_round_step(one_chip).memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def _conv_windows(compiled):
    """(window sizes, sorted result dims) of every convolution."""
    out = []
    for line in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* convolution\(.*"
                      r"window=\{size=(\S+)", line)
        if m:
            out.append((m.group(2),
                        sorted(int(d) for d in m.group(1).split(","))))
    return out


def test_fleet_update_conv1_weight_grad_has_no_output_map_window(
        one_chip, monkeypatch):
    """At the benchmark fleet's 256 clients. Vmapped over clients,
    autodiff's weight gradient of conv1 is a convolution whose window is
    the whole 24x24 output map; the custom VJP computes it as per-tap
    reductions instead. Conv2's weight gradient keeps the transposed
    convolution, window its 8x8 output map (the explicit forms measured
    slower on a TPU v5e). Temp memory stays within a quarter of the plain
    convolution's program."""
    N = 256
    step = _compile_round_step(one_chip, N)
    windows = _conv_windows(step)
    assert not [w for w in windows if w[0].startswith("24x24x")]
    assert [w[1] for w in windows if w[0].startswith("8x8x")] == \
        [sorted((N, 5, 5, 6, 16))]
    monkeypatch.setattr(cnn, "_conv2d", cnn._valid_conv)
    plain = _compile_round_step(one_chip, N)
    assert [w for w in _conv_windows(plain) if w[0].startswith("24x24x")]
    assert (step.memory_analysis().temp_size_in_bytes
            <= 1.25 * plain.memory_analysis().temp_size_in_bytes)


def test_mlstm_core_grad_holds_no_chunk_state_stacks(one_chip):
    """lm-xlstm125m-silo's mLSTM core (16 x 2048 tokens, 4 heads of 384,
    chunks of 256), its gradient under jax.checkpoint as the training step
    takes it. The hand-written backward saves each chunk's entry state
    once and carries dC back chunk by chunk, where autodiff's formulation
    materialises several (8, 16, 4, 385, 384) f32 stacks of 302 MB (the
    states, their scan's copy, the cotangents): temp memory stays under
    1.5e9 bytes (autodiff's formulation: 1.90e9), and no f32 array is 385
    wide (the normaliser appended to v). XLA's bytes accessed stay under
    7.5e9 (autodiff's formulation: 1.58e10); the cost model counts a loop
    body once."""
    from repro.nn import xlstm
    B, S, H, P = 16, 2048, 4, 384
    core = jax.checkpoint(
        lambda q, k, v, lf, li: xlstm.mlstm_chunked(q, k, v, lf, li, 256)[0])
    qkv = _sds(one_chip, (B, S, H, P), jnp.bfloat16)
    gates = _sds(one_chip, (B, S, H), jnp.float32)
    compiled = jax.jit(lambda *a: jax.vjp(core, *a[:5])[1](a[5])).lower(
        qkv, qkv, qkv, gates, gates,
        _sds(one_chip, (B, S, H, P), jnp.float32)).compile()
    assert not re.search(rf"f32\[[\d,]*\b{P + 1}\b", compiled.as_text())
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 7.5e9
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
