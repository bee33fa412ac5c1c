"""Download-lag relay history (src/repro/relay/history.py + sim download
clocks).

The tentpole invariant: when clients read STALE relay snapshots — teacher
pools and global prototypes as of round `t − d(client, t)` — the
vectorized engine's in-step history ring and the sequential oracle's
host-side snapshot list evolve IDENTICAL relay state, commit lists and
comm ledgers, across every relay policy × download clock, with and
without event-ordered upload lag on top. Plus: the `H_max = 1` (and
all-delay-0) machinery is bit-identical to the history-free engines, the
ring itself matches the oracle's snapshots slot by slot, downlink billing
is invariant under the delay map (billed at read), the lagged step never
retraces, and the LM-path `make_download_lag_round_sync` serves exactly
the prototypes of round `t − d`.

The full policy × download-clock × upload-clock cross products live behind
the `slow` marker (separate non-blocking CI job); tier-1 runs a diagonal.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracles import assert_ledgers_equal, assert_states_match, run_matched
from repro import sim
from repro.core import client as client_lib, collab, prototypes, vec_collab
from repro.data import partition, synthetic
from repro.launch import train
from repro.models import mlp
from repro.relay import history
from repro.types import CollabConfig, FleetConfig, TrainConfig

SPEC = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))
SPEC_B = client_lib.ClientSpec(
    apply=lambda p, x: mlp.apply(p, x),
    head=lambda p: (p["head_w"], p["head_b"]))

POLICIES = ["flat", "per_class", "staleness"]
DL_CLOCKS = ["homogeneous:1", "lognormal:2", "periodic:2,3"]


def _build(engine, policy, dl_clock, clock=None, schedule=None, mode="cors",
           n_clients=4, n=192, seed=0, hetero=False, mesh=None):
    x, y = synthetic.class_images(n, seed=0, noise=0.4)
    tx, ty = synthetic.class_images(96, seed=9, noise=0.4)
    parts = partition.uniform_split(x, y, n_clients, seed=1)
    ccfg = CollabConfig(mode=mode, num_classes=10, d_feature=84,
                        lambda_kd=2.0,
                        lambda_disc=1.0 if mode == "cors" else 0.0)
    tcfg = TrainConfig(batch_size=16)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_clients)
    if hetero:
        specs = [SPEC if i % 2 == 0 else SPEC_B for i in range(n_clients)]
        params = [mlp.init_mlp(k, hidden=64 if i % 2 == 0 else 96)
                  for i, k in enumerate(keys)]
    else:
        specs = [SPEC] * n_clients
        params = [mlp.init_mlp(k) for k in keys]
    cls = (collab.CollabTrainer if engine == "seq"
           else vec_collab.VectorizedCollabTrainer)
    return cls(specs, params, parts, (tx, ty), ccfg, tcfg, seed=seed,
               fleet=FleetConfig(policy=policy, participation=schedule,
                                 clock=clock, download_clock=dl_clock,
                                 mesh=mesh))


# ---------------------------------------------------------------------------
# tentpole: seq host-replayed snapshots == vec history ring
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy,dl_clock", list(zip(POLICIES, DL_CLOCKS)))
def test_download_lag_seq_vec_equivalence(policy, dl_clock):
    """Tier-1 diagonal of the policy × download-clock matrix (the full
    cross product runs under -m slow)."""
    run_matched(_build("seq", policy, dl_clock),
                _build("vec", policy, dl_clock))


@pytest.mark.slow
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("dl_clock", DL_CLOCKS)
@pytest.mark.parametrize("up_clock", [None, "lognormal:2"])
def test_download_lag_full_matrix(policy, dl_clock, up_clock):
    """Every relay policy × download clock × {upload lag off, on}."""
    run_matched(_build("seq", policy, dl_clock, clock=up_clock),
                _build("vec", policy, dl_clock, clock=up_clock))


def test_upload_and_download_lag_compose():
    """Event-ordered late commits + stale snapshot reads in ONE run: the
    two clock axes must not interfere (a client can distill against an
    old snapshot while its own upload is still in flight)."""
    run_matched(_build("seq", "staleness", "lognormal:2",
                       clock="lognormal:2"),
                _build("vec", "staleness", "lognormal:2",
                       clock="lognormal:2"), rounds=4)


def test_download_lag_partial_participation_and_fd():
    """Variable-count schedule (incl. possible zero-participant rounds,
    which must still advance the ring) + FD-mode logit protos."""
    run_matched(_build("seq", "flat", "periodic:2,3", schedule="bernoulli:0.5",
                       mode="fd"),
                _build("vec", "flat", "periodic:2,3", schedule="bernoulli:0.5",
                       mode="fd"), rounds=4)


def test_download_lag_static_k_compaction():
    """Unlike upload lag, download lag composes with static-k compaction:
    only participants read, so the gathered (k, ...) block covers every
    stale read. The compacted engine must still match the oracle."""
    seq = _build("seq", "flat", "lognormal:2", schedule="uniform_k:2")
    vec = _build("vec", "flat", "lognormal:2", schedule="uniform_k:2")
    assert vec._k_active == 2                    # compaction stays ON
    run_matched(seq, vec)


def test_download_lag_hetero_buckets():
    """Two interleaved buckets read from ONE shared history ring."""
    run_matched(_build("seq", "staleness", "periodic:2,3", hetero=True),
                _build("vec", "staleness", "periodic:2,3", hetero=True))


@pytest.mark.slow
@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("dl_clock", DL_CLOCKS)
def test_async_hetero_download_lag_matrix(policy, dl_clock):
    """The heaviest cross product: mixed fleets × event-ordered upload
    lag × download lag, per policy × download clock."""
    run_matched(
        _build("seq", policy, dl_clock, clock="lognormal:2", hetero=True),
        _build("vec", policy, dl_clock, clock="lognormal:2", hetero=True))


# ---------------------------------------------------------------------------
# H_max = 1 / all-delay-0 machinery is bit-identical to today's engines
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d_max", [0, 2])
def test_delay0_machinery_bit_identical(d_max):
    """A homogeneous delay-0 download clock forces the history machinery
    (H_max = d_max + 1 ring, per-client gathers, in-step push) with every
    read at delay 0: both engines must match their download_clock=None
    selves bit-for-bit — the acceptance anchor for H_max = 1 (d_max=0)
    and for deeper rings whose stale slots are never read (d_max=2)."""
    for engine in ("seq", "vec"):
        a = _build(engine, "staleness", sim.HomogeneousClock(0, d_max=d_max),
                   n_clients=3)
        b = _build(engine, "staleness", None, n_clients=3)
        if engine == "vec":
            assert a._lagged and not b._lagged
        for _ in range(2):
            ra, rb = a.run_round(), b.run_round()
            assert ra["commits"] == rb["commits"]
            assert ra["accs"] == rb["accs"]
        sa = a.server.state if engine == "seq" else a.relay_state
        sb = b.server.state if engine == "seq" else b.relay_state
        jax.tree.map(lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)), sa, sb)
        assert_ledgers_equal(a.ledger, b.ledger)


def test_history_ring_matches_oracle_snapshots():
    """Slot-by-slot ring equality: after matched runs, the vectorized
    ring's snapshot at every depth d equals the oracle's host-side
    _snaps[d] — and each snapshot's clock is the merge count as of that
    round (the stale global prototypes a depth-d reader is served)."""
    seq = _build("seq", "per_class", "homogeneous:2")
    vec = _build("vec", "per_class", "homogeneous:2")
    rounds = 5
    run_matched(seq, vec, rounds=rounds)
    h_max = vec._h_max
    assert h_max == 3 and seq._h_max == 3
    for d in range(h_max):
        snap_s = seq._snapshot(d)
        snap_v = history.read_at(vec.hist, d)
        assert_states_match(snap_s, snap_v)
        # full participation + delay-0 uploads: one merge per round
        assert int(np.asarray(snap_v.clock)) == rounds - d
    # reads deeper than the ring clamp to the oldest retained snapshot
    deep = history.read_at(vec.hist, h_max + 3)
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)),
        history.read_at(vec.hist, h_max - 1), deep)


def test_download_lag_composes_with_mesh():
    """download-lag × mesh used to raise ("history ring is an off-mesh
    construct"); under the placement API the ring is REPLICATED
    (history.out_spec) so the per-client stale gathers stay local — it
    runs, matches the oracle exactly, and still compiles once."""
    from repro import sharding
    seq = _build("seq", "flat", "lognormal:2")
    vec = _build("vec", "flat", "lognormal:2", mesh=sharding.client_mesh(1))
    run_matched(seq, vec)
    assert vec._round_step._cache_size() == 1


def test_download_lag_step_compiles_once():
    """H_max is static, per-round delays are traced: 3 rounds = 1 compile,
    for both the sync-lagged and the async×lagged round steps."""
    vec = _build("vec", "per_class", "lognormal:2")
    for _ in range(3):
        vec.run_round()
    assert vec._round_step._cache_size() == 1
    vec = _build("vec", "flat", "periodic:2,3", clock="lognormal:2")
    for _ in range(3):
        vec.run_round()
    assert vec._round_step._cache_size() == 1


# ---------------------------------------------------------------------------
# billing: downlink at read — invariant under the delay map
# ---------------------------------------------------------------------------
def test_downlink_billed_at_read_invariant_to_delay_map():
    """A stale read still crosses the wire at read time, so the ledger of
    a lagged run equals the round-fresh run's bit-for-bit under the same
    schedule — the delay map can shift WHAT is read, never what is
    billed."""
    a = _build("seq", "flat", "lognormal:3", n_clients=4)
    b = _build("seq", "flat", None, n_clients=4)
    for _ in range(4):
        a.run_round()
        b.run_round()
    assert_ledgers_equal(a.ledger, b.ledger)


# ---------------------------------------------------------------------------
# sim: download clocks
# ---------------------------------------------------------------------------
def test_download_clock_decorrelated_but_deterministic():
    up = sim.get_clock("lognormal:3", seed=4)
    dl = sim.get_download_clock("lognormal:3", seed=4)
    dl2 = sim.get_download_clock("lognormal:3", seed=4)
    assert dl.d_max == 3
    for r in range(5):
        np.testing.assert_array_equal(dl.delays(r, 8), dl2.delays(r, 8))
        assert (dl.delays(r, 8) <= 3).all() and (dl.delays(r, 8) >= 0).all()
    # same spec + same seed must NOT alias the upload clock's draws
    assert any(not np.array_equal(up.delays(r, 32), dl.delays(r, 32))
               for r in range(5))
    assert sim.get_download_clock(None) is None
    assert sim.get_download_clock("none") is None
    c = sim.HomogeneousClock(1)
    assert sim.get_download_clock(c, seed=9) is c


def test_periodic_download_clock_ages_forward():
    """A duty-cycled downloader's snapshot age must GROW between syncs
    (rounds SINCE its last window) and reset at the next one — the
    time-forward mirror of PeriodicClock's rounds-UNTIL-next-window
    upload delay, which would make observed history run backwards."""
    dl = sim.get_download_clock("periodic:4,3")
    assert isinstance(dl, sim.PeriodicSyncClock)
    ages = np.array([dl.delays(t, 1)[0] for t in range(7)])
    np.testing.assert_array_equal(ages, [0, 1, 2, 0, 1, 2, 0])
    d6 = [dl.delays(t, 6) for t in range(12)]
    for t in range(1, 12):
        step = d6[t] - d6[t - 1]
        assert ((step == 1) | (d6[t] == 0)).all()    # +1 or fresh sync


# ---------------------------------------------------------------------------
# LM-scale download-lag round sync (launch/train.py)
# ---------------------------------------------------------------------------
def test_download_lag_round_sync_serves_stale_protos():
    ccfg = CollabConfig(num_classes=4, d_feature=3)
    init_h, rs_lag, read_at = train.make_download_lag_round_sync(ccfg,
                                                                 h_max=3)
    rs_sync = train.make_round_sync(ccfg)
    mk_state = lambda: train.TrainState(None, None,
                                        prototypes.init_state(4, 3),
                                        jnp.zeros((), jnp.int32))
    state, state_s = mk_state(), mk_state()
    hist = init_h(4, 3)
    rng = np.random.default_rng(0)
    per_round = []
    for r in range(5):
        stats = prototypes.ProtoState(
            jnp.asarray(rng.normal(size=(3, 4, 3)), jnp.float32),
            jnp.asarray(rng.random((3, 4)), jnp.float32))
        state, hist = rs_lag(state, hist, stats)
        state_s = rs_sync(state_s, stats)
        per_round.append(state.proto)
    # the merge itself is untouched by the ring
    np.testing.assert_allclose(np.asarray(state.proto.sum),
                               np.asarray(state_s.proto.sum), atol=1e-6)
    # read_at(d) is the post-merge proto of d rounds ago; deeper reads
    # clamp to the oldest retained snapshot
    for d in range(3):
        got = read_at(hist, jnp.asarray(d, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got.sum),
                                      np.asarray(per_round[4 - d].sum))
    # per-client vectorized reads (one stale proto per client)
    got = read_at(hist, jnp.asarray([0, 2, 1], jnp.int32))
    for j, d in enumerate([0, 2, 1]):
        np.testing.assert_array_equal(np.asarray(got.sum[j]),
                                      np.asarray(per_round[4 - d].sum))

    # h_max=1 degenerates to make_round_sync exactly
    init1, rs1, read1 = train.make_download_lag_round_sync(ccfg, h_max=1)
    stats = prototypes.ProtoState(jnp.ones((3, 4, 3)), jnp.ones((3, 4)))
    st1, h1 = rs1(state_s, init1(4, 3), stats)
    st2 = rs_sync(state_s, stats)
    np.testing.assert_array_equal(np.asarray(st1.proto.sum),
                                  np.asarray(st2.proto.sum))
    np.testing.assert_array_equal(
        np.asarray(read1(h1, jnp.zeros((), jnp.int32)).sum),
        np.asarray(st1.proto.sum))
