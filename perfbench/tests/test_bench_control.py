"""The control -- the plain reference put in the program's place and
computed with float8 matmul operands, the precision below the one each
configuration states -- fails the cell's limits; the reference against
itself passes them. At a size a CPU test can hold; the same readings at
the cells' own sizes come from `calibrate.py` on the chip."""
import pytest

import benchtest_util
import calibrate
from benchlib import compare


@pytest.mark.parametrize("cell", ["fleet-lenet5-n256",
                                  "lm-xlstm125m-4x2048"])
def test_control_fails_and_reference_passes(cell):
    w = benchtest_util.small_workload(cell)
    cfg, mod = benchtest_util.small_config(w["config"])
    for line in calibrate.readings(mod, cfg, w["traffic"], w["limits"],
                                   [2 ** 35 + 11]):
        failed = [k for k, v in line["control"].items()
                  if w["limits"][k] is not None and v > w["limits"][k]]
        assert failed, line["control"]
    data = mod.Data(cfg, w["traffic"], 7)
    ref = mod.Reference(cfg).run(data)
    checks = mod.compare_summaries(ref, ref, w["limits"])
    assert compare.all_within(checks)


@pytest.mark.parametrize("dtype", ["bfloat16", "float8_e4m3fn"])
def test_roundings_are_the_astype_round_trips(dtype):
    """Where the compiler keeps an `astype` round trip, as this host's
    does, the control's roundings give the same values, bit for bit, over
    the whole range: subnormals, ties and the largest value included."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(5)
    mag = np.exp(rng.uniform(-40, 40, 1 << 16)) * rng.choice([-1, 1], 1 << 16)
    x = jnp.asarray(mag, jnp.float32)
    if dtype == "bfloat16":
        want = x.astype(jnp.bfloat16).astype(jnp.float32)
        got = jax.jit(compare.round_to(jnp.bfloat16))(x)
    else:
        s = jnp.max(jnp.abs(x)) / compare.E4M3_MAX
        want = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        # half-way ties between e4m3 values, below and above the subnormals
        ties = jnp.asarray([2.5, 3.5, 17.0, 19.0, 0.5 ** 9 * 1.5,
                            0.5 ** 9 * 2.5], jnp.float32) * s
        x = jnp.concatenate([x, ties])
        want = jnp.concatenate(
            [want, (ties / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
             * s])
        got = jax.jit(compare.e4m3_round)(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
