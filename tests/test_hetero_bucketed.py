"""Mixed-architecture fleets in the vectorized engine (core/vec_collab.py)
vs the sequential oracle.

The tentpole invariant: for a MIXED-spec fleet (≥2 stackable buckets,
interleaved client ids) the vectorized engine and the sequential
oracle evolve identical relay ring bookkeeping (exact ptr/owner/valid/age)
and the same per-client eval metrics, across relay policies × participation
schedules — because both write uploads in the same bucket order
(client_lib.bucketize) under the same per-round key schedule. Plus bucket
construction mechanics and the no-retrace guarantee of the one round step
over all buckets.
"""
import jax
import numpy as np
import pytest

from oracles import assert_states_match as _assert_states_match
from repro import relay as relay_lib
from repro.core import client as client_lib, collab, vec_collab
from repro.data import partition, synthetic
from repro.models import cnn, mlp
from repro.types import CollabConfig, FleetConfig, TrainConfig

# Two distinct spec OBJECTS (identical callables hash apart on purpose) +
# two MLP widths: widths alone would already split buckets by param shape,
# the distinct objects make this the documented usage.
MLP_A = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))
MLP_B = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))
CNN_SPEC = client_lib.ClientSpec(
    apply=lambda p, x: cnn.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))


def _fleet(n_clients=4, seed=0, with_cnn=False):
    """Interleaved mixed fleet: even ids -> MLP_A(h=64), odd -> MLP_B(h=96),
    optionally the last client a CNN (third bucket)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), n_clients)
    specs, params = [], []
    for i, k in enumerate(keys):
        if with_cnn and i == n_clients - 1:
            specs.append(CNN_SPEC)
            params.append(cnn.init_cnn(k))
        elif i % 2 == 0:
            specs.append(MLP_A)
            params.append(mlp.init_mlp(k, hidden=64))
        else:
            specs.append(MLP_B)
            params.append(mlp.init_mlp(k, hidden=96))
    return specs, params


def _build(engine, policy, schedule, mode="cors", n_clients=4, n=256,
           seed=0, with_cnn=False):
    x, y = synthetic.class_images(n, seed=0, noise=0.4)
    tx, ty = synthetic.class_images(128, seed=9, noise=0.4)
    parts = partition.uniform_split(x, y, n_clients, seed=1)
    ccfg = CollabConfig(mode=mode, num_classes=10, d_feature=84,
                        lambda_kd=2.0,
                        lambda_disc=1.0 if mode == "cors" else 0.0)
    tcfg = TrainConfig(batch_size=16)
    specs, params = _fleet(n_clients, seed, with_cnn)
    cls = (collab.CollabTrainer if engine == "seq"
           else vec_collab.VectorizedCollabTrainer)
    return cls(specs, params, parts, (tx, ty), ccfg, tcfg, seed=seed,
               fleet=FleetConfig(policy=policy, participation=schedule))


# ---------------------------------------------------------------------------
# tentpole: seq/vec equivalence for mixed-spec fleets
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["flat", "per_class", "staleness"])
@pytest.mark.parametrize("schedule", ["full", "uniform_k:2", "bernoulli:0.5"])
def test_hetero_seq_vec_equivalence(policy, schedule):
    seq = _build("seq", policy, schedule)
    vec = _build("vec", policy, schedule)
    assert vec.hetero and len(vec.buckets) == 2
    assert [list(b.ids) for b in vec.buckets] == [[0, 2], [1, 3]]
    for _ in range(2):
        rs, rv = seq.run_round(), vec.run_round()
        assert rs["participants"] == rv["participants"]
        np.testing.assert_allclose(rs["accs"], rv["accs"], atol=2e-2)
        for a, b in zip(rs["metrics"], rv["metrics"]):
            assert sorted(a) == sorted(b)
            for k in a:
                np.testing.assert_allclose(a[k], b[k], rtol=1e-3, atol=1e-4)
    assert seq.ledger.by_round == vec.ledger.by_round
    assert seq.ledger.total_bytes == vec.ledger.total_bytes
    _assert_states_match(seq.server.state, vec.relay_state)


def test_hetero_three_buckets_fd_mode():
    """FD mode (logit prototypes) + a third CNN bucket: the cross-bucket
    proto AND logit-proto merges must both match the oracle."""
    seq = _build("seq", "flat", "full", mode="fd", with_cnn=True)
    vec = _build("vec", "flat", "full", mode="fd", with_cnn=True)
    assert len(vec.buckets) == 3
    for _ in range(2):
        rs, rv = seq.run_round(), vec.run_round()
        np.testing.assert_allclose(rs["accs"], rv["accs"], atol=2e-2)
    np.testing.assert_allclose(np.asarray(seq.server.state.mean_logits),
                               np.asarray(vec.relay_state.mean_logits),
                               atol=5e-3)
    _assert_states_match(seq.server.state, vec.relay_state)


def test_hetero_zero_participant_round_is_relay_noop():
    class NoShow(relay_lib.ParticipationSchedule):
        name = "noshow"

        def mask(self, round_idx, n_clients):
            return np.zeros((n_clients,), bool)

    vec = _build("vec", "staleness", NoShow(), n_clients=2, n=128)
    state0 = jax.tree.map(np.asarray, vec.relay_state)
    rec = vec.run_round()
    assert rec["participants"] == []
    assert rec["comm_up"] == rec["comm_down"] == 0.0
    jax.tree.map(np.testing.assert_array_equal, state0,
                 jax.tree.map(np.asarray, vec.relay_state))


# ---------------------------------------------------------------------------
# bucket construction + compile-once mechanics
# ---------------------------------------------------------------------------
def test_bucketize_groups_by_spec_and_shape():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    # same spec object, two widths -> shape split; order = first appearance
    specs = [MLP_A, MLP_A, MLP_A, MLP_A]
    params = [mlp.init_mlp(k, hidden=64 if i in (0, 3) else 96)
              for i, k in enumerate(keys)]
    buckets = client_lib.bucketize(specs, params)
    assert [ids for _, ids in buckets] == [[0, 3], [1, 2]]
    # homogeneous fleet -> ONE bucket, identity order
    params64 = [mlp.init_mlp(k, hidden=64) for k in keys]
    buckets = client_lib.bucketize(specs, params64)
    assert [ids for _, ids in buckets] == [[0, 1, 2, 3]]


def test_hetero_upload_order_is_bucket_order():
    seq = _build("seq", "flat", "full")
    assert seq._upload_order == [0, 2, 1, 3]


def test_hetero_steps_compile_once():
    """A mixed fleet's round is ONE compiled step over all its buckets, and
    participation never retraces it: 3 rounds under a varying-k schedule =
    1 trace."""
    vec = _build("vec", "per_class", "bernoulli:0.7")
    assert len(vec.buckets) > 1
    for _ in range(3):
        vec.run_round()
    assert vec._round_step._cache_size() == 1


def test_hetero_client_params_roundtrip():
    vec = _build("vec", "flat", "full")
    p1 = vec.client_params(1)                     # bucket B, slot 0
    assert p1["w1"].shape[-1] == 96
    p0 = vec.client_params(0)
    assert p0["w1"].shape[-1] == 64
