"""Multi-client simulation trainer — the paper's experimental harness.

Runs CoRS and all baselines (CL / IL / FD / FedAvg) with identical data
partitions, optimizers and round accounting, so benchmarks/table1_utility.py
reproduces the paper's Table 1 comparison semantics. Clients may have
heterogeneous architectures in CoRS/FD modes (a selling point of the paper);
FedAvg requires homogeneous models and asserts so.

This sequential trainer is the ORACLE: it steps clients one-by-one, for any
mix of client architectures. Rounds are synchronous (paper Algorithm 1
cadence): every client downloads from the relay state of the PREVIOUS
round, then all upload — so the vectorized engine (core/vec_collab.py),
which runs each spec-bucket of clients in one vmapped step, evolves the
exact same relay state given the same seeds (see `round_keys` for the
shared per-round key schedule).

Upload ordering: uploads happen in BUCKET order (client_lib.bucketize —
clients grouped by stackable (spec, param-shape) key in first-appearance
order, client-id order within a bucket), because that is the order in which
the bucketed engine writes each bucket's observation rows into the shared
relay ring. For a homogeneous fleet this degenerates to plain client-id
order, i.e. exactly the pre-bucketing behavior. Downloads are order-free
(every present client reads the same round-start state) and the per-client
key schedule is indexed by client id, so ordering changes nothing else.

Server behavior is pluggable via `policy` (a repro.relay RelayPolicy spec:
"flat" | "per_class" | "staleness") and `schedule` (a participation
schedule: "full" | "uniform_k:K" | "cyclic:K" | "bernoulli:P" |
"adaptive:P[,BOOST]"); absent clients are skipped entirely — no download,
no update, no upload, no comm billed — which is the reference semantics
the vectorized engine's masked client axis is tested against
(tests/test_relay_policies.py).

Asynchrony: pass `clock` (a repro.sim ClockModel spec, e.g.
"lognormal:4") and uploads commit LATE — a round-r upload with commit
delay d is parked in the event queue and appended in round r+d, in event
order (birth round, then upload position; see relay/events.py). This
trainer is the EVENT-REPLAY ORACLE: it replays the identical commit order
the vectorized engine's pending buffer produces, one host-side event at a
time, and therefore stays the bit-exact ring/stamp bookkeeping reference
under any clock model. A client's teachers always come from the committed
state at its sync (round start) — in-flight uploads are invisible, which
is exactly what distinguishes the relay from SplitFed's synchronous
server. `clock=None` (or D_max=0) is today's synchronous behavior,
bit-identical.

Download lag: pass `download_clock` (same `repro.sim` spec machinery,
independent seed fold) and a client training in round t reads its teachers
AND global prototypes from a snapshot `d(client, t)` rounds STALER than
its round-start sync — the state its round-`t − d` self would have read
fresh, i.e. the post-merge state of round `t − d − 1` (d = 0 is the
round-start state itself) — the stale-sync half that the event log's late
uploads don't model. This trainer keeps the last
`H_max = d_max + 1` post-merge states in a host-side most-recent-first
list, the exact replay of the vectorized engine's relay/history.py ring
(every ring slot starts as the init state, so early deep reads see the
Algorithm-1 init in both engines). Downlink is billed at READ — the bytes
cross the wire when the snapshot is served, however stale it is — so the
ledger is invariant under the delay map. `download_clock=None` (or
d_max=0, delay 0 everywhere) is today's round-fresh download,
bit-identical.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, relay as relay_lib, sim
from repro.core import baselines, client as client_lib, comm
from repro.optim import adam_init
from repro.relay import events
from repro.types import CollabConfig, TrainConfig, resolve_fleet


def round_keys(key, n: int):
    """Canonical per-round key schedule, shared with the vectorized engine:
    one relay, one update and one upload key per client, drawn from three
    independent folds of the round key. Returns (next_key, relay (n,2),
    update (n,2), upload (n,2))."""
    key, kr, ku, ko = jax.random.split(key, 4)
    return (key, jax.random.split(kr, n), jax.random.split(ku, n),
            jax.random.split(ko, n))


@dataclass
class ClientState:
    spec: client_lib.ClientSpec
    params: Any
    opt_state: Any
    data_x: jax.Array
    data_y: jax.Array


class CollabTrainer:
    def __init__(self, specs: Sequence[client_lib.ClientSpec],
                 params_list: Sequence[Any],
                 client_data: Sequence[Tuple[jax.Array, jax.Array]],
                 test_data: Tuple[jax.Array, jax.Array],
                 ccfg: CollabConfig, tcfg: TrainConfig, seed: int = 0,
                 fleet=None, policy=None, schedule=None, clock=None,
                 download_clock=None, telemetry=None):
        fleet = resolve_fleet(fleet, policy=policy, schedule=schedule,
                              clock=clock, download_clock=download_clock)
        if fleet.mesh is not None:
            raise ValueError(
                "the sequential oracle steps clients host-side and holds "
                "no stacked client axis to shard; FleetConfig.mesh only "
                "applies to the vectorized engine (core/vec_collab.py)")
        assert len(specs) == len(params_list) == len(client_data)
        self.ccfg, self.tcfg = ccfg, tcfg
        self.clients = [
            ClientState(spec=s, params=p, opt_state=adam_init(p),
                        data_x=x, data_y=y)
            for s, p, (x, y) in zip(specs, params_list, client_data)]
        self.test_x, self.test_y = test_data
        # Relay-write order shared with the bucketed vectorized engine:
        # bucket by bucket, client-id order within a bucket (identity for
        # homogeneous fleets). See the module docstring.
        buckets = client_lib.bucketize(specs, params_list)
        self._upload_order = [i for _, ids in buckets for i in ids]
        # Telemetry (repro.obs): the oracle computes the SAME jitted
        # telemetry function over its bit-equal ring state, so run_matched
        # can pin the integer leaves across engines; the event-log
        # quantities it already tracks host-side (commit lags, queue depth)
        # go in as small arrays.
        self.telemetry = obs.resolve(telemetry)
        self._telem = self.telemetry is not None and self.telemetry.metrics
        self._bucket_ids = [np.asarray(ids, np.int64) for _, ids in buckets]
        self._telem_fn = (obs.metrics.make_host_telemetry_fn(len(specs))
                          if self._telem else None)
        self._sink = (obs.JsonlWriter(self.telemetry.jsonl)
                      if self.telemetry and self.telemetry.jsonl else None)
        self.clock = sim.get_clock(fleet.clock, seed=seed)
        self._queue = events.HostEventQueue()
        self.policy = relay_lib.get_policy(fleet.policy)
        # Streaming population (repro.sim.population): the cohort table
        # OWNS participation, ring rows are tagged with EXTERNAL ids, and
        # the table's LRU evictions hit the relay at round start. The
        # compositions below are rejected, not silently wrong: the async
        # pending buffer and the history ring key state by a STATIC id
        # space (upload position / snapshot owner), which seat turnover
        # invalidates — re-filed as ROADMAP follow-ons.
        self.arrivals = sim.get_arrivals(fleet.arrivals)
        self._streaming = self.arrivals is not None
        if self._streaming:
            if fleet.participation is not None:
                raise ValueError(
                    "streaming arrivals own participation (the cohort "
                    "table picks k active seats per round); leave "
                    "FleetConfig.participation unset")
            if self.clock is not None and self.clock.d_max > 0:
                raise ValueError(
                    "streaming arrivals do not compose with an async "
                    "upload clock yet: the pending buffer is indexed by "
                    "upload position, which seat turnover reuses")
            if fleet.download_clock is not None:
                raise ValueError(
                    "streaming arrivals do not compose with download lag "
                    "yet: history snapshots hold evicted owners' rows")
            if ccfg.mode not in ("cors", "fd"):
                raise ValueError(
                    "streaming arrivals need a relay mode (cors | fd); "
                    f"mode={ccfg.mode!r} has no server to stream through")
            if len(buckets) > 1:
                raise ValueError(
                    "streaming arrivals currently require a homogeneous "
                    "fleet (seats are interchangeable); got "
                    f"{len(buckets)} client buckets")
            self._cohort = self.arrivals.table(len(specs))
            self.schedule = None
        else:
            self._cohort = None
            self.schedule = relay_lib.get_schedule(fleet.participation,
                                                   seed=seed,
                                                   clock=self.clock)
        self.server = relay_lib.RelayServer(ccfg, ccfg.d_feature, seed,
                                            n_clients=len(specs),
                                            policy=self.policy)
        # Download lag (relay/history.py semantics, replayed host-side):
        # `_snaps` is the bounded most-recent-first ring of post-merge
        # relay states; a round-t client with download delay d reads
        # _snaps[d] = the state as of round t − d. Only relay modes
        # download, so only they carry the ring.
        self.dl_clock = sim.get_download_clock(fleet.download_clock, seed=seed)
        self._lagged = (self.dl_clock is not None
                        and ccfg.mode in ("cors", "fd"))
        self._h_max = (self.dl_clock.d_max + 1) if self._lagged else 1
        self._snaps = [self.server.state] if self._lagged else None
        self.ledger = comm.CommLedger()
        self.key = jax.random.PRNGKey(seed)
        self._updaters = [client_lib.make_local_update(c.spec, ccfg, tcfg)
                          for c in self.clients]
        # one jitted fn per distinct spec, NOT per call/round: re-jitting a
        # fresh lambda each time recompiled every round, and the eager
        # compute_uploads paid ~20 ms dispatch per client per round.
        self._eval_cache: Dict[client_lib.ClientSpec, Callable] = {}
        self._upload_cache: Dict[client_lib.ClientSpec, Callable] = {}
        self.history: List[Dict] = []

    # ------------------------------------------------------------------
    def _batches(self, c: ClientState):
        bs = self.tcfg.batch_size
        n = (c.data_x.shape[0] // bs) * bs
        xs = c.data_x[:n].reshape(-1, bs, *c.data_x.shape[1:])
        ys = c.data_y[:n].reshape(-1, bs)
        return {"x": xs, "y": ys}

    # ------------------------------------------------------------------
    def run_round(self) -> Dict:
        with obs.trace.span("run_round", round=len(self.history)):
            return self._run_round()

    def _run_round(self) -> Dict:
        ccfg = self.ccfg
        mode = ccfg.mode
        N = len(self.clients)
        # Keys are drawn for ALL N clients regardless of participation, so
        # present clients consume the same per-client keys under every
        # schedule (and as in the vectorized engine); absent clients simply
        # never use theirs.
        r = len(self.history)
        self.key, relay_ks, upd_ks, upl_ks = round_keys(self.key, N)
        if self._streaming:
            # Cohort table view: participation mask over SEATS, external
            # ids per seat, and the owners LRU-evicted at admission time —
            # their ring slots are invalidated BEFORE any read this round.
            view = self._cohort.round(r)
            mask = view.mask.copy()
            ext_ids = view.seat_ids
            if view.evicted.size:
                with obs.trace.span("evict"):
                    self.server.state = self.policy.evict_owners(
                        self.server.state,
                        jnp.asarray(view.evicted, jnp.int32))
        else:
            mask = np.asarray(self.schedule.mask(r, N), bool)
            ext_ids = None
        present = np.nonzero(mask)[0]
        # Ring owner tags use the EXTERNAL id under streaming arrivals;
        # seat index i doubles as the id for a static fleet.
        owner_of = ((lambda i: int(ext_ids[i])) if self._streaming
                    else (lambda i: int(i)))
        delays = (self.clock.delays(r, N) if self.clock is not None
                  else np.zeros((N,), np.int64))

        # phase 1 — downlink: every PRESENT client sees last round's state,
        # or — under a download clock — the post-merge snapshot from
        # d(client, r) rounds before that (its last completed sync).
        dl = (self.dl_clock.delays(r, N) if self._lagged
              else np.zeros((N,), np.int64))
        prev_state = self.server.state
        teachers: Dict[int, Dict] = {}
        with obs.trace.span("teacher_read"):
            for i in present:
                teachers[i] = (self.server.relay(
                    owner_of(i), max(1, ccfg.m_down), relay_ks[i],
                    state=self._snapshot(int(dl[i])))
                    if mode in ("cors", "fd")
                    else client_lib.empty_teacher(ccfg))

        # phase 2 — local updates (Algorithm 2); absent clients are frozen
        metrics_all = [jax.tree.map(float, client_lib.zero_metrics(ccfg))
                       for _ in range(N)]
        with obs.trace.span("update"):
            for i in present:
                c = self.clients[i]
                c.params, c.opt_state, m = self._updaters[i](
                    c.params, c.opt_state, self._batches(c), teachers[i],
                    upd_ks[i])
                metrics_all[i] = jax.tree.map(float, m)

        # phase 3 — uplink + server merge (Algorithm 1). Present clients'
        # fresh uploads enter the event queue with their clock-model commit
        # delay; the relay then commits round r's DUE events in event order
        # (birth round, upload position — relay/events.py), each stamped
        # with its birth clock. With no clock (or D_max=0) every upload is
        # due at birth and this replays today's synchronous upload loop
        # bit-for-bit. A round with zero commits leaves the relay state
        # untouched (no merge, no clock tick).
        commits: List[Tuple[int, int]] = [(r, int(i)) for i in present]
        if mode in ("cors", "fd"):
            # Birth stamps are policy-resolved: the flat clock for single
            # relays (identical to the old int(state.clock) broadcast), the
            # OWNER's shard clock for the sharded relay.
            order_owners = [owner_of(i) for i in self._upload_order]
            birth_stamps = self.policy.host_stamps(self.server.state,
                                                   order_owners)
            with obs.trace.span("upload"):
                for pos, i in enumerate(self._upload_order):
                    if not mask[i]:
                        continue
                    c = self.clients[i]
                    payload = self._upload_fn(c.spec)(c.params, c.data_x,
                                                      c.data_y, upl_ks[i])
                    self._queue.push(birth=r, pos=pos, client_id=i,
                                     stamp=int(birth_stamps[pos]),
                                     payload=payload,
                                     delay=int(delays[i]))
            with obs.trace.span("commit"):
                due = self._queue.pop_due(r)
                self.server.begin_round()
                for birth, pos, cid, stamp, payload, _ in due:
                    # Streaming is sync-only (guarded above), so every due
                    # event was pushed THIS round and the seat -> external
                    # id map is the current view's.
                    self.server.upload(owner_of(cid), payload, stamp=stamp)
                if due:
                    self.server.end_round()
            commits = [(birth, cid) for birth, pos, cid, *_ in due]

        if mode == "fedavg" and len(present):
            avg = baselines.fedavg_aggregate(
                [self.clients[i].params for i in present])
            for i in present:
                self.clients[i].params = avg

        # download-lag ring: snapshot the post-merge state EVERY round
        # (unchanged on no-commit rounds — the snapshot still represents
        # "the state as of round r"), exactly like the vectorized engine's
        # unconditional history.push inside its round step.
        if self._lagged:
            self._snaps.insert(0, self.server.state)
            del self._snaps[self._h_max:]

        up, down = comm.round_floats(
            mode, n_present=len(present), n_commit=len(commits),
            n_read=len(present) if self._lagged else None,
            C=ccfg.num_classes,
            d=ccfg.d_feature, m_up=ccfg.m_up, m_down=ccfg.m_down,
            model_size=(baselines.num_params(self.clients[0].params)
                        if mode == "fedavg" else 0))
        self.ledger.log_round(up, down)

        with obs.trace.span("eval"):
            accs = [self.evaluate(c) for c in self.clients]
        rec = {"round": len(self.history) + 1,
               "acc_mean": float(np.mean(accs)),
               "acc_std": float(np.std(accs)),
               "accs": accs,
               "metrics": metrics_all,
               "participants": present.tolist(),
               "commits": [[b, c] for b, c in commits],
               "comm_up": up, "comm_down": down}
        if self._telem:
            # host-counted event-log quantities: this round's commit lags
            # (commit round − birth round, clipped like the in-jit bins)
            # and the uploads still parked in the queue after pop_due
            chist = np.zeros((obs.STALE_BINS,), np.int32)
            for birth, _cid in commits:
                chist[min(r - birth, obs.STALE_BINS - 1)] += 1
            mask_parts = tuple(jnp.asarray(mask[ids])
                               for ids in self._bucket_ids)
            loss_parts = tuple(
                np.asarray([metrics_all[i]["total"] for i in ids],
                           np.float32) for ids in self._bucket_ids)
            gnorm_parts = tuple(
                np.asarray([metrics_all[i]["grad_norm"] for i in ids],
                           np.float32) for ids in self._bucket_ids)
            telem = self._telem_fn(
                prev_state, self.server.state, jnp.asarray(mask),
                mask_parts, loss_parts, gnorm_parts, jnp.asarray(chist),
                jnp.asarray(len(self._queue), jnp.int32),
                jnp.asarray(dl, jnp.int32))
            rec["telemetry"] = obs.to_record(telem)
        self.history.append(rec)
        if self._sink is not None:
            self._sink.write(rec)
        return rec

    def run(self, rounds: int, log_every: int = 0) -> List[Dict]:
        with obs.trace.record(self.telemetry.trace if self.telemetry
                              else None):
            for r in range(rounds):
                rec = self.run_round()
                if log_every and (r + 1) % log_every == 0:
                    print(f"  round {rec['round']:3d} acc "
                          f"{rec['acc_mean']:.4f} ±{rec['acc_std']:.4f}")
        return self.history

    # ------------------------------------------------------------------
    def _snapshot(self, d: int):
        """Relay state as of `d` rounds ago (None = live state when no
        download clock is bound). Clamped to the ring depth; entries past
        the pushes performed so far resolve to the init state, which is
        what the vectorized ring's never-written slots hold."""
        if not self._lagged:
            return None
        return self._snaps[min(d, self._h_max - 1, len(self._snaps) - 1)]

    # ------------------------------------------------------------------
    def _eval_fn(self, spec: client_lib.ClientSpec):
        fn = self._eval_cache.get(spec)
        if fn is None:
            fn = jax.jit(lambda p, x: spec.apply(p, x)[1])
            self._eval_cache[spec] = fn
        return fn

    def _upload_fn(self, spec: client_lib.ClientSpec):
        fn = self._upload_cache.get(spec)
        if fn is None:
            fn = client_lib.make_compute_uploads(spec, self.ccfg)
            self._upload_cache[spec] = fn
        return fn

    def evaluate(self, c: ClientState, batch: int = 512) -> float:
        n = self.test_x.shape[0]
        correct = 0
        apply = self._eval_fn(c.spec)
        for i in range(0, n, batch):
            lg = apply(c.params, self.test_x[i:i + batch])
            correct += int(jnp.sum(jnp.argmax(lg, -1)
                                   == self.test_y[i:i + batch]))
        return correct / n
