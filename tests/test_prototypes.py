"""Prototype statistics: accumulation, merging, observations (Alg. 1)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.core import prototypes

KEY = jax.random.PRNGKey(0)


def test_accumulate_matches_manual():
    f = jax.random.normal(KEY, (20, 6))
    y = jax.random.randint(jax.random.PRNGKey(1), (20,), 0, 4)
    st = prototypes.accumulate(prototypes.init_state(4, 6), f, y)
    for c in range(4):
        mask = np.asarray(y) == c
        np.testing.assert_allclose(st.sum[c], np.asarray(f)[mask].sum(0),
                                   atol=1e-5)
        assert float(st.count[c]) == mask.sum()


def test_merge_equals_joint_accumulation():
    f = jax.random.normal(KEY, (30, 5))
    y = jax.random.randint(jax.random.PRNGKey(1), (30,), 0, 3)
    a = prototypes.accumulate(prototypes.init_state(3, 5), f[:15], y[:15])
    b = prototypes.accumulate(prototypes.init_state(3, 5), f[15:], y[15:])
    joint = prototypes.accumulate(prototypes.init_state(3, 5), f, y)
    m = prototypes.merge(a, b)
    np.testing.assert_allclose(m.sum, joint.sum, atol=1e-4)
    np.testing.assert_allclose(m.count, joint.count)


def test_means_fallback_for_empty_class():
    st = prototypes.init_state(3, 2)
    st = prototypes.accumulate(st, jnp.ones((2, 2)), jnp.array([0, 0]))
    fb = jnp.full((3, 2), 7.0)
    m = prototypes.means(st, fallback=fb)
    np.testing.assert_allclose(m[0], [1, 1])
    np.testing.assert_allclose(m[1], [7, 7])


def test_observations_average_n_avg_samples():
    # class 0 has exactly 3 identical samples -> observation == the sample
    f = jnp.concatenate([jnp.full((3, 4), 2.0),
                         jax.random.normal(KEY, (10, 4))])
    y = jnp.concatenate([jnp.zeros(3, jnp.int32),
                         jnp.ones(10, jnp.int32)])
    obs, valid = prototypes.observations(KEY, f, y, 3, n_avg=3, m_up=2)
    assert obs.shape == (2, 3, 4)
    np.testing.assert_allclose(obs[:, 0], 2.0, atol=1e-5)
    assert bool(valid[0]) and bool(valid[1]) and not bool(valid[2])


def test_observations_concentrate_with_n_avg():
    # variance of the observation decreases with n_avg (paper §3, Eq. 2)
    f = jax.random.normal(KEY, (400, 8))
    y = jnp.zeros((400,), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(5), 30)
    def spread(n_avg):
        os = jnp.stack([prototypes.observations(k, f, y, 1, n_avg)[0][0, 0]
                        for k in keys])
        return float(jnp.mean(jnp.var(os, axis=0)))
    assert spread(50) < spread(2)


def test_psum_merge_single_device():
    st = prototypes.accumulate(prototypes.init_state(2, 3),
                               jnp.ones((4, 3)), jnp.zeros(4, jnp.int32))
    def f(s):
        return prototypes.psum_merge(s, "i")
    out = jax.shard_map(f, mesh=sharding.auto_mesh((1,), ("i",)),
                        in_specs=jax.sharding.PartitionSpec(),
                        out_specs=jax.sharding.PartitionSpec())(st)
    np.testing.assert_allclose(out.sum, st.sum)
