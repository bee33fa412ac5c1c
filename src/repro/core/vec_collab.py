"""Vectorized multi-client engine: all clients advance in ONE jitted step.

The paper's headline claim is that CoRS "is scalable with the number of
clients"; the sequential `CollabTrainer` oracle steps clients in a Python
loop (cost linear in N, one dispatch per client per phase). This engine
stacks homogeneous clients' params / Adam moments / data along a leading
client axis and runs the whole round — relay sampling, local updates,
uploads, server merge — as a single `jax.vmap`'d jitted function over that
axis, against the same fixed-shape relay state the sequential path uses.
Given the same seeds and equal-size partitions the two engines evolve
identical relay state and near-identical weights (see
tests/test_vec_collab.py and tests/test_relay_policies.py), but the
vectorized round is one XLA program instead of O(N) Python dispatches.

Relay policy: the server side is pluggable (`repro.relay`): `flat` (the
seed ring, bit-compatible), `per_class` (the paper's exact per-class buffer
layout) or `staleness` (exp(-λ·age) Gumbel-top-k sampling). The policy's
pure functions are closed over by the jitted round step, so swapping
policies swaps ONE compiled program, not the engine.

Participation: a `ParticipationSchedule` (repro.relay.participation) emits
a per-round boolean client mask. Schedules with a static participant count
k (uniform_k, cyclic) run COMPACTED: the step gathers the k participants
into a (k, ...) block, so a k=N/4 round costs ~1/4 of a full round —
real savings, not just masking. Variable-count schedules (bernoulli_p) and
the mesh path run full-width and mask: absent clients' params/opt are
frozen via `where`, their uploads zero-weighted, and the ring append drops
their rows without consuming slots. Either way there is exactly one jitted
round step per (policy, schedule) — the mask and gather indices are traced
arguments of fixed shape, so participation never retraces.

Device scaling is PLACEMENT-DRIVEN (repro.relay.placement): pass a mesh
with a "clients" axis (`sharding.client_mesh`, via `FleetConfig.mesh`) and
the SAME traced round body is jitted with in/out shardings resolved from
the state classes' placement declarations — client-resident leaves
(params, opt, data, pending uploads) are CLIENT_SHARDED over the mesh
axis, relay/history state is REPLICATED per `policy.out_spec` /
`events.out_spec` / `history.out_spec` — and GSPMD inserts the
collectives. The one cross-device exchange per round is
`placement.exchange` on the upload payload (the CLIENT_SHARDED ->
REPLICATED constraint right before the relay append/merge, which lowers
to the observation all-gather + the paper's O(C·d') prototype
all-reduce). There are no mesh branches in the round body, so every fleet
composition — async event log, download-lag history, hetero buckets,
static-k compaction — runs on the mesh through the same code path that
runs off it, and off-mesh bit-compatibility is structural.

Heterogeneous-architecture fleets (different client models, a CoRS selling
point) run BUCKETED: clients are grouped into stackable buckets by
`client_lib.bucketize` (same ClientSpec AND same param shapes), each bucket
gets its own jitted vmapped step (`make_bucket_update_step`), and all
buckets share ONE relay state. CoRS only couples clients through the
(C, d') representation pool — no weights cross the boundary — so the relay
is the only cross-bucket synchronization point. The round is synchronous:

  phase 1-3a  every bucket's downlink samples teachers from the SAME
              round-start relay state, then updates + computes uploads,
              independently per bucket (one dispatch per bucket, not per
              client);
  phase 3b    `make_relay_commit` appends all buckets' observation rows in
              bucket order (= the order the sequential oracle uploads in,
              see core/collab.py) and runs ONE prototype merge.

The per-round key schedule is the oracle's `collab.round_keys`, indexed by
ORIGINAL client id and sliced per bucket, so the sequential oracle remains
the bit-exact reference for ring bookkeeping under any bucket mix
(tests/test_hetero_bucketed.py). On a mesh, each bucket's stack is
CLIENT_SHARDED over the same client axis (GSPMD pads non-divisible bucket
sizes) and the shared commit is the exchange point. Static-k compaction
stays homogeneous-only: bucket participant counts vary per round even
under fixed-k schedules, and per-bucket stacks have different shapes.

Asynchrony: pass `clock` (a repro.sim ClockModel spec) and uploads commit
LATE through the event-ordered relay log (repro.relay.events): a round-r
upload with commit delay d <= D_max parks in a fixed-shape pending buffer
(N, D_max, ...) and is appended — in event order, stamped with its birth
clock — in round r+d, all inside ONE jitted async round step (homogeneous)
or the shared jitted async commit (bucketed). Teachers are always sampled
from the round-start COMMITTED state (the client's last sync; in-flight
uploads are invisible). The commit set decouples from the participant set,
so the async path runs full-width (on a mesh the pending buffer is
CLIENT_SHARDED and the commit payload is the round's one exchange);
`D_max = 0` keeps today's synchronous fast paths bit-identically. The
sequential oracle replays the identical event order host-side and stays
the bit-exact reference (tests/test_async_relay.py).

Download lag: pass `download_clock` (the same `repro.sim` spec machinery,
independent seed fold) and every client reads its teachers AND global
prototypes from a snapshot `d(client, t)` rounds staler than its
round-start sync — what its round-`t − d` self would have read fresh
(d = 0 is the round-start state) — the stale-sync half of asynchrony,
modeled by a bounded history ring of the last `H_max = d_max + 1` relay
states (repro.relay.history). The ring
is threaded through the SAME jitted round step: per-client snapshot reads
are dynamic indices into the history axis (one batched gather, fused with
the teacher-row gather) and the post-merge push happens at the end of the
step, with `H_max` static and the per-round delay vector traced — one
compile per (policy, schedule, clock spec), ever. Upload lag composes:
under both clocks a client can distill against a stale snapshot while its
own upload is still in flight, and because slot age is clock-derived
(`clock − stamp`), the ages it sees are the snapshot's own — a stale
download is automatically older by the time it is read. `H_max = 1` (or
no download clock) is bit-identical to today's engines; the sequential
oracle replays the ring host-side (tests/test_download_lag.py). On a
mesh the ring is REPLICATED (history.out_spec) and the per-client stale
reads stay local gathers — no extra collective.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs, relay as relay_lib, sim
from repro.core import baselines, client as client_lib, collab, comm, \
    prototypes
from repro.optim import adam_init
from repro.relay import placement
from repro.relay.participation import bcast_mask as _bcast, freeze_absent
from repro.types import CollabConfig, TrainConfig, resolve_fleet


def _stack(trees: Sequence[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# Reusable round-phase builders, parameterized by ClientSpec. Both the fused
# homogeneous round step and the per-bucket heterogeneous steps are composed
# from these, so the phase semantics exist in exactly one place.
# ---------------------------------------------------------------------------
def make_teacher_phase(policy: relay_lib.RelayPolicy, ccfg: CollabConfig,
                       lagged: bool = False):
    """Phase 1 (downlink): vmapped teacher sampling from the relay buffers
    for relay modes, a broadcast no-op teacher otherwise. Returns
    `teachers(rstate, ids, relay_ks) -> teacher pytree (k, ...)`.

    `lagged=True` is the download-lag variant: `teachers(hist, ids,
    relay_ks, dl) `samples client i's teachers (and global prototypes)
    from `history.read_at(hist, dl[i])` — its own post-merge snapshot from
    dl[i] rounds ago. The per-client dynamic index into the history axis
    happens INSIDE the vmapped sample, so it lowers to one batched gather
    that XLA fuses with the teacher-row gather (no per-client state
    copies), and `dl` is a traced argument — lag patterns never retrace."""
    mode = ccfg.mode
    m_down = max(1, ccfg.m_down)

    if lagged:
        assert mode in ("cors", "fd"), mode

        def teachers_lagged(hist, ids, relay_ks, dl):
            return jax.vmap(
                lambda i, k, d: policy.sample_teacher(
                    relay_lib.history.read_at(hist, d), i, m_down, k))(
                        ids, relay_ks, dl)

        return teachers_lagged

    def teachers(rstate, ids, relay_ks):
        if mode in ("cors", "fd"):
            return jax.vmap(
                lambda i, k: policy.sample_teacher(
                    rstate, i, m_down, k))(ids, relay_ks)
        et = client_lib.empty_teacher(ccfg)
        k_loc = ids.shape[0]
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (k_loc,) + a.shape), et)

    return teachers


def make_client_upload_phase(spec: client_lib.ClientSpec,
                             ccfg: CollabConfig):
    """Phase 3a, per-client form: vmapped `compute_uploads` with NO
    cross-client reduction — the pieces the async event log parks and
    commits per upload (relay/events.py). Returns `uploads_of(params,
    data_x, data_y, upl_ks, ids) -> dict(obs (k, m, C, d'), valid (k, C),
    psum (k, C, d'), pcnt (k, C), [lsum (k, C, C), lcnt (k, C) in FD
    mode], owner (k,) int32)`."""
    mode = ccfg.mode

    def uploads_of(p_s, dx, dy, upl_ks, ids_s):
        uploads = jax.vmap(
            lambda p, x, y, k: client_lib.compute_uploads(
                spec, p, x, y, ccfg, k))(p_s, dx, dy, upl_ks)
        out = {"obs": uploads["obs"], "valid": uploads["valid"],
               "psum": uploads["proto"].sum,
               "pcnt": uploads["proto"].count,
               "owner": ids_s.astype(jnp.int32)}
        if mode == "fd":
            out["lsum"] = uploads["logit_proto"].sum
            out["lcnt"] = uploads["logit_proto"].count
        return out

    return uploads_of


def make_upload_phase(spec: client_lib.ClientSpec, ccfg: CollabConfig,
                      policy: relay_lib.RelayPolicy = None):
    """Phase 3a (uplink, compute side): the per-client pieces reduced into
    relay-ready synchronous-append form. Returns `uploads_of(params,
    data_x, data_y, upl_ks, ids, mask) -> (proto, logit|None, obs_rows,
    valid_rows, owner_rows, row_mask)` where absent clients' prototype
    sums are zero-weighted and their observation rows masked out (dropped
    by the relay append WITHOUT consuming ring slots).

    A `policy` defining `reduce_uploads` (e.g. the sharded relay) owns the
    reduction instead: the same mask weights and per-client sums are
    segmented by owner rather than summed over the client axis. Policies
    without the hook (and `policy=None`) keep the traced program
    unchanged."""
    mode = ccfg.mode
    per_client = make_client_upload_phase(spec, ccfg)
    reduce = policy.reduce_uploads if policy is not None else None

    def uploads_of(p_s, dx, dy, upl_ks, ids_s, sub_mask):
        wf = sub_mask.astype(jnp.float32)
        u = per_client(p_s, dx, dy, upl_ks, ids_s)
        if reduce is None:
            proto = prototypes.ProtoState(
                jnp.sum(u["psum"] * wf[:, None, None], axis=0),
                jnp.sum(u["pcnt"] * wf[:, None], axis=0))
        else:
            proto = reduce(u["psum"], u["pcnt"], wf, u["owner"])
        logit = None
        if mode == "fd":
            logit = (prototypes.ProtoState(
                jnp.sum(u["lsum"] * wf[:, None, None], axis=0),
                jnp.sum(u["lcnt"] * wf[:, None], axis=0))
                if reduce is None
                else reduce(u["lsum"], u["lcnt"], wf, u["owner"]))
        m_real = u["obs"].shape[1]           # 0 when m_up == 0
        obs_rows = u["obs"].reshape(-1, *u["obs"].shape[2:])
        valid_rows = jnp.repeat(u["valid"], m_real, axis=0)
        owner_rows = jnp.repeat(ids_s, m_real)
        row_mask = jnp.repeat(sub_mask, m_real)
        return proto, logit, obs_rows, valid_rows, owner_rows, row_mask

    return uploads_of


def make_relay_commit(policy: relay_lib.RelayPolicy, lagged: bool = False,
                      mesh=None):
    """Phase 3b: the round's single relay write. `commit(rstate, payloads)`
    takes the per-bucket upload payloads (in bucket order), concatenates
    their observation rows, sums their prototype contributions, appends and
    runs ONE prototype merge. Appending the concatenation equals appending
    bucket-by-bucket: every policy's append writes rows in order and masked
    rows consume no slots, so per-bucket uploads COMPOSE. The bucket count
    and per-bucket row counts are fixed, so jitting this gives one trace —
    and zero per-round eager concat/merge dispatches — for the whole run.

    `lagged=True`: `commit(rstate, payloads, hist)` additionally pushes the
    post-merge state into the download-lag history ring and returns
    `(rstate, hist)` (the zero-participant round, which skips this commit
    entirely, pushes via a bare `history.push` in the engine instead).

    `mesh`: the concatenated payload is THE round's cross-device exchange
    (placement.exchange) — the buckets' client-sharded rows and summed
    prototypes become replicated right before the append/merge."""

    def commit(rstate, payloads, *lag):
        cat = lambda k: jnp.concatenate([p[k] for p in payloads])
        proto = prototypes.merge(*[p["proto"] for p in payloads])
        logit = (prototypes.merge(*[p["logit"] for p in payloads])
                 if payloads[0]["logit"] is not None else None)
        (proto, logit, obs_rows, valid_rows, owner_rows, row_mask) = \
            placement.exchange(
                (proto, logit, cat("obs_rows"), cat("valid_rows"),
                 cat("owner_rows"), cat("row_mask")), mesh)
        new = policy.append(rstate, obs_rows, valid_rows,
                            owner_rows, row_mask)
        new = policy.merge_round(new, proto, logit)
        if lagged:
            return new, relay_lib.history.push(lag[0], new)
        return new

    return commit


def _client_rep(mesh):
    """The two resolved shardings of the placement alphabet on `mesh`."""
    return (placement.resolve(placement.CLIENT_SHARDED, mesh),
            placement.resolve(placement.REPLICATED, mesh))


def make_async_round_step(spec: client_lib.ClientSpec, ccfg: CollabConfig,
                          tcfg: TrainConfig, policy: relay_lib.RelayPolicy,
                          lagged: bool = False, mesh=None, templates=None,
                          telemetry: bool = False):
    """The homogeneous ASYNC round step (bounded-delay uploads,
    relay/events.py): phases 1-2 exactly as the synchronous step, then ONE
    `events.commit_and_park` — commit every due event (pending uploads
    whose clock says "now" + this round's delay-0 uploads) in event order,
    park the rest. Full-width only: lateness decouples who trains from
    whose upload commits, so the static-k participant gather does not
    cover the commit set. `round_idx` and `delays` are traced arguments —
    one compile, ever.

    Returns a jitted `step(params, opt, rstate, pending, batches, data_x,
    data_y, ids, relay_ks, upd_ks, upl_ks, mask, delays, round_idx) ->
    (params, opt, rstate, pending, metrics)`.

    `lagged=True` composes upload lag with DOWNLOAD lag: the step takes
    two trailing args `(hist, dl)`, samples teachers from each client's
    `t − dl[i]` snapshot, pushes the post-merge state into the ring, and
    additionally returns the new history — so a stale download of a
    delayed commit is exactly as old as the two clocks say.

    `mesh` + `templates` (dict with "rstate"/"pending"[/"hist"] state
    examples): jit the SAME traced body with in/out shardings resolved
    from the placement declarations — client state and the pending buffer
    CLIENT_SHARDED, relay/history REPLICATED — and mark the commit payload
    as the round's one exchange (`commit_and_park(..., mesh=mesh)`).

    `telemetry=True` (a STATIC build flag, so the telemetry-off program is
    byte-identical to a telemetry-free build): append an in-jit
    `obs.RoundTelemetry` — REPLICATED on a mesh (obs.metrics.out_spec) —
    as the step's last output, computed from state the step already holds
    (round-start vs post-commit relay state, the pre-commit pending
    buffer's due events, this round's mask/delays)."""
    mode = ccfg.mode
    assert mode in ("cors", "fd"), mode
    local_update = client_lib.make_local_update_fn(spec, ccfg, tcfg)
    teachers = make_teacher_phase(policy, ccfg, lagged=lagged)
    per_client = make_client_upload_phase(spec, ccfg)

    def step(params, opt, rstate, pending, batches, data_x, data_y, ids,
             relay_ks, upd_ks, upl_ks, mask, delays, round_idx, *lag):
        obs.trace.count("traces")     # the body runs only when jax traces
        rstate0, pending0 = rstate, pending
        # phases 1-2 — downlink from the COMMITTED state of the client's
        # last sync (round start, or dl[i] rounds earlier under download
        # lag; in-flight uploads are invisible either way) + local
        # updates; absent clients freeze
        with jax.named_scope("teacher_read"):
            teacher = (teachers(lag[0], ids, relay_ks, lag[1]) if lagged
                       else teachers(rstate, ids, relay_ks))
        with jax.named_scope("update"):
            new_p, new_o, metrics = jax.vmap(local_update)(
                params, opt, batches, teacher, upd_ks)
            p_s = freeze_absent(mask, new_p, params)
            o_s = freeze_absent(mask, new_o, opt)
            metrics = jax.tree.map(
                lambda m: jnp.where(_bcast(mask, m), m, 0.0), metrics)
        # phase 3 — the event log's single relay write (and, on a mesh,
        # the round's single cross-device exchange)
        with jax.named_scope("upload"):
            fresh = per_client(p_s, data_x, data_y, upl_ks, ids)
        with jax.named_scope("commit"):
            rstate, pending = relay_lib.events.commit_and_park(
                policy, rstate, pending, fresh, round_idx, delays, mask,
                mesh=mesh)
        tail = ()
        if telemetry:
            with jax.named_scope("telemetry"):
                tail = (obs.round_telemetry(
                    rstate0, rstate, mask.shape[0], mask=mask,
                    loss_parts=(metrics["total"],),
                    gnorm_parts=(metrics["grad_norm"],),
                    mask_parts=(mask,), pending=pending,
                    pending_pre=pending0, round_idx=round_idx,
                    delays=delays, dl=lag[1] if lagged else None),)
        if lagged:
            hist = relay_lib.history.push(lag[0], rstate)
            return (p_s, o_s, rstate, pending, hist, metrics) + tail
        return (p_s, o_s, rstate, pending, metrics) + tail

    if mesh is None:
        return jax.jit(step)
    cl, rep = _client_rep(mesh)
    rspec = placement.resolve(policy.out_spec(templates["rstate"]), mesh)
    pspec = placement.resolve(
        relay_lib.events.out_spec(templates["pending"]), mesh)
    in_sh = (cl, cl, rspec, pspec, cl, cl, cl, cl, cl, cl, cl, cl, cl, rep)
    out_sh = (cl, cl, rspec, pspec)
    if lagged:
        hspec = placement.resolve(
            relay_lib.history.out_spec(templates["hist"]), mesh)
        in_sh += (hspec, cl)
        out_sh += (hspec,)
    out_sh += (cl,) + ((rep,) if telemetry else ())
    return jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)


def make_async_relay_commit(policy: relay_lib.RelayPolicy,
                            lagged: bool = False, mesh=None):
    """Heterogeneous counterpart of `make_relay_commit` for the async
    engine: concatenate the buckets' PER-CLIENT payloads in bucket (=
    upload/event) order and run ONE `events.commit_and_park`. `delays` and
    `mask` arrive permuted to upload order, matching the concatenation and
    the pending buffer's upload-position indexing. `lagged=True` takes a
    trailing history arg, pushes the post-merge state (this commit runs
    EVERY round, so the ring advances even on no-commit rounds) and
    returns it. `mesh` marks the commit payload as the round's one
    cross-device exchange (see `events.commit_and_park`)."""

    def commit(rstate, pending, payloads, round_idx, delays, mask, *lag):
        keys = [k for k in payloads[0] if payloads[0][k] is not None]
        fresh = {k: jnp.concatenate([p[k] for p in payloads]) for k in keys}
        rstate, pending = relay_lib.events.commit_and_park(
            policy, rstate, pending, fresh, round_idx, delays, mask,
            mesh=mesh)
        if lagged:
            return rstate, pending, relay_lib.history.push(lag[0], rstate)
        return rstate, pending

    return commit


def make_bucket_update_step(spec: client_lib.ClientSpec, ccfg: CollabConfig,
                            tcfg: TrainConfig,
                            policy: relay_lib.RelayPolicy,
                            per_client_payload: bool = False,
                            lagged: bool = False):
    """One bucket's full-width masked round step against a FIXED relay
    state: downlink + local updates + upload payloads (phases 1-3a). The
    relay write (3b) is deliberately NOT here — the bucketed engine lets
    every bucket read the same round-start state and then commits all
    buckets' uploads in bucket order via `make_relay_commit` (synchronous)
    or `make_async_relay_commit` (bounded-delay event log, which needs the
    UNREDUCED per-client pieces — `per_client_payload=True`).

    Returns a jitted `step(params, opt, rstate, batches, data_x, data_y,
    ids, relay_ks, upd_ks, upl_ks, mask) -> (params, opt, metrics,
    payload)`; `payload` is None outside relay modes. The mask is a traced
    argument, so participation never retraces; one trace per bucket, ever.

    `lagged=True` (download lag): the `rstate` slot receives the shared
    history ring instead, plus a trailing `dl` arg — the bucket's clients
    read their own `t − dl[j]` snapshots. History is read-only here; the
    shared commit owns the push.
    """
    mode = ccfg.mode
    local_update = client_lib.make_local_update_fn(spec, ccfg, tcfg)
    teachers = make_teacher_phase(policy, ccfg, lagged=lagged)
    uploads_of = make_upload_phase(spec, ccfg, policy)
    uploads_per_client = make_client_upload_phase(spec, ccfg)

    def step(params, opt, rstate, batches, data_x, data_y, ids,
             relay_ks, upd_ks, upl_ks, mask, *lag):
        obs.trace.count("traces")     # the body runs only when jax traces
        teacher = (teachers(rstate, ids, relay_ks, lag[0]) if lagged
                   else teachers(rstate, ids, relay_ks))
        new_p, new_o, metrics = jax.vmap(local_update)(
            params, opt, batches, teacher, upd_ks)
        p_s = freeze_absent(mask, new_p, params)
        o_s = freeze_absent(mask, new_o, opt)
        metrics = jax.tree.map(
            lambda m: jnp.where(_bcast(mask, m), m, 0.0), metrics)
        payload = None
        if mode in ("cors", "fd"):
            if per_client_payload:
                payload = uploads_per_client(p_s, data_x, data_y, upl_ks,
                                             ids)
            else:
                proto, logit, obs_rows, valid_rows, owner_rows, row_mask = \
                    uploads_of(p_s, data_x, data_y, upl_ks, ids, mask)
                payload = {"proto": proto, "logit": logit,
                           "obs_rows": obs_rows, "valid_rows": valid_rows,
                           "owner_rows": owner_rows, "row_mask": row_mask}
        return p_s, o_s, metrics, payload

    return jax.jit(step)


def make_eval_hits(spec: client_lib.ClientSpec):
    """Jitted stacked-client eval: logits for the whole client stack plus
    argmax/compare/reduce INSIDE the jit, so one test chunk costs one
    dispatch and returns a (k,) per-client hit-count vector (no eager
    argmax ops, no host sync per chunk)."""

    def hits(P, x, y):
        obs.trace.count("traces")     # the body runs only when jax traces
        lg = jax.vmap(lambda p: spec.apply(p, x)[1])(P)
        return jnp.sum(jnp.argmax(lg, -1) == y[None], axis=-1)

    return jax.jit(hits)


@dataclass
class ClientBucket:
    """One stackable group of clients inside the bucketed engine: shared
    ClientSpec + param shapes, params/opt/data stacked along a leading axis
    of size len(ids), and the bucket's own jitted step/eval functions.
    `ids` are ORIGINAL client ids (ascending), used for relay owner tags,
    key-schedule slicing and participation-mask slicing."""
    spec: client_lib.ClientSpec
    ids: np.ndarray
    params: Any
    opt: Any
    batches: Dict
    data_x: jax.Array
    data_y: jax.Array
    step: Callable
    eval_fn: Callable


class VectorizedCollabTrainer:
    """Drop-in counterpart of `CollabTrainer` for any client fleet.

    Same constructor shape, `run_round` record schema, `ledger` accounting
    and `history`; `specs` may be a single ClientSpec or a sequence. Clients
    are grouped into stackable buckets (`client_lib.bucketize`); a
    homogeneous fleet is ONE bucket and runs the fused single-step fast
    path (static-k compaction, optional placement-sharded mesh), a mixed
    fleet runs one vmapped step per bucket around a shared relay. Client
    datasets are trimmed to the shortest partition within each bucket so
    they stack; pass equal-size partitions for exact parity with the
    oracle.

    The fleet (relay policy, participation schedule, upload/download
    clocks, mesh) is ONE `FleetConfig` passed as `fleet=`; the loose
    legacy kwargs still work for a release via the `resolve_fleet`
    deprecation shim.
    """

    def __init__(self,
                 specs: Union[client_lib.ClientSpec,
                              Sequence[client_lib.ClientSpec]],
                 params_list: Sequence[Any],
                 client_data: Sequence[Tuple[jax.Array, jax.Array]],
                 test_data: Tuple[jax.Array, jax.Array],
                 ccfg: CollabConfig, tcfg: TrainConfig, seed: int = 0,
                 fleet=None, mesh=None, policy=None, schedule=None,
                 clock=None, download_clock=None, telemetry=None):
        fleet = resolve_fleet(fleet, mesh=mesh, policy=policy,
                              schedule=schedule, clock=clock,
                              download_clock=download_clock)
        # Observability (repro.obs): in-jit RoundTelemetry is a STATIC
        # build flag on the round steps (off -> traced program unchanged);
        # the sink and the span store are host-side.
        self.telemetry = obs.resolve(telemetry)
        self._telem = self.telemetry is not None and self.telemetry.metrics
        self._sink = (obs.JsonlWriter(self.telemetry.jsonl)
                      if self.telemetry and self.telemetry.jsonl else None)
        if isinstance(specs, client_lib.ClientSpec):
            specs = [specs] * len(params_list)
        assert len(specs) == len(params_list) == len(client_data)
        self.ccfg, self.tcfg = ccfg, tcfg
        self.n_clients = N = len(params_list)
        self.mesh = mesh = fleet.mesh
        self.policy = relay_lib.get_policy(fleet.policy)
        self.clock = sim.get_clock(fleet.clock, seed=seed)
        # Streaming population (repro.sim.population): the cohort table
        # OWNS participation and seat indices carry EXTERNAL client ids.
        # Composition guards mirror the sequential oracle (core/collab.py)
        # exactly — rejected, not silently wrong.
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
            self._cohort = self.arrivals.table(N)
            self.schedule = None
            self._evict = jax.jit(self.policy.evict_owners)
        else:
            self._cohort = None
            self.schedule = relay_lib.get_schedule(fleet.participation,
                                                   seed=seed,
                                                   clock=self.clock)
        # Asynchrony (bounded-delay uploads, relay/events.py) only touches
        # relay commits, so only relay modes run the async path; a D_max=0
        # clock IS the synchronous fleet and keeps today's fast paths
        # (static-k compaction) — and composes with a mesh either way: the
        # pending buffer is CLIENT_SHARDED (events.out_spec) and the
        # commit payload is the round's one exchange.
        self._async = (self.clock is not None and self.clock.d_max > 0
                       and ccfg.mode in ("cors", "fd"))
        # Download lag (relay/history.py): only relay modes download, so
        # only they carry the snapshot ring. Binding ANY download clock
        # (even d_max=0, i.e. H_max=1) routes through the history
        # machinery — the bit-compat probe the tests use. The ring is
        # REPLICATED on a mesh (history.out_spec); stale reads stay local.
        self.dl_clock = sim.get_download_clock(fleet.download_clock,
                                               seed=seed)
        self._lagged = (self.dl_clock is not None
                        and ccfg.mode in ("cors", "fd"))
        buckets = client_lib.bucketize(specs, params_list)
        self.bucket_ids: List[List[int]] = [ids for _, ids in buckets]
        self.hetero = len(buckets) > 1
        if self._streaming and self.hetero:
            raise ValueError(
                "streaming arrivals currently require a homogeneous "
                "fleet (seats are interchangeable); got "
                f"{len(buckets)} client buckets")
        if self.hetero and ccfg.mode == "fedavg":
            raise ValueError(
                "FedAvg averages whole weight vectors, which needs one "
                f"shared architecture; got {len(buckets)} distinct "
                "(spec, param-shape) buckets. Heterogeneous fleets only "
                "make sense in representation-coupled modes "
                "('cors'/'fd') or independently ('il').")

        if mesh is not None and N % mesh.shape[placement.CLIENT_AXIS]:
            raise ValueError(
                f"FleetConfig.mesh: the fleet's client axis (N={N}) must "
                f"divide the mesh's '{placement.CLIENT_AXIS}' axis "
                f"({mesh.shape[placement.CLIENT_AXIS]} devices). "
                "CLIENT_SHARDED state at rest (the client stacks, the "
                "async pending buffer) must materialize its sharding, and "
                "jax arrays cannot hold an uneven NamedSharding — GSPMD "
                "only pads values internal to a jit (which is why an "
                "uneven static-k block or hetero BUCKET is fine). Pad the "
                "fleet or use a device count that divides it.")
        self.relay_state = self.policy.init_state(
            ccfg, ccfg.d_feature, seed, n_clients=N)
        if mesh is not None:
            # commit the initial state to its declared placement so the
            # first round starts where every later round ends
            self.relay_state = jax.device_put(
                self.relay_state,
                placement.resolve(self.policy.out_spec(self.relay_state),
                                  mesh))
        self.test_x, self.test_y = (jnp.asarray(test_data[0]),
                                    jnp.asarray(test_data[1]))
        self.ledger = comm.CommLedger()
        self.key = jax.random.PRNGKey(seed)
        self.history: List[Dict] = []
        # Relay-write (= event) order: upload position u -> client id.
        # Bucket by bucket, client-id order within — identity for
        # homogeneous fleets. The pending buffer is indexed by u.
        self._upload_order = [i for _, ids in buckets for i in ids]
        if self._async:
            self.pending = relay_lib.events.init_pending(
                N, self.clock.d_max, ccfg.m_up, ccfg.num_classes,
                ccfg.d_feature, fd=(ccfg.mode == "fd"))
            if mesh is not None:
                self.pending = jax.device_put(
                    self.pending,
                    placement.resolve(
                        relay_lib.events.out_spec(self.pending), mesh))
            self._commit_mirror = relay_lib.events.CommitMirror()
        if self._lagged:
            self._h_max = self.dl_clock.d_max + 1
            self.hist = relay_lib.history.init(self.relay_state, self._h_max)
            if mesh is not None:
                self.hist = jax.device_put(
                    self.hist,
                    placement.resolve(
                        relay_lib.history.out_spec(self.hist), mesh))
            # bare push for rounds whose relay commit is skipped entirely
            # (zero-participant synchronous bucketed rounds): the ring
            # still advances with the (unchanged) post-round state.
            self._hist_push = jax.jit(relay_lib.history.push)

        if self.hetero:
            self._init_bucketed(buckets, params_list, client_data)
            return

        # -- homogeneous fast path: ONE bucket, fused round step ----------
        self.spec = specs[0]
        self.data_x, self.data_y, self.batches, self.params, self.opt_state \
            = self._stack_clients(params_list, client_data)
        if mesh is not None:
            # commit the client stacks to their placement up front: round 0
            # then presents the same (sharding, committed) signature as
            # every later round, keeping the jit fastpath single-entry
            # (the compile-once contract the tests pin)
            cl = placement.resolve(placement.CLIENT_SHARDED, mesh)
            (self.data_x, self.data_y, self.batches, self.params,
             self.opt_state) = jax.device_put(
                (self.data_x, self.data_y, self.batches, self.params,
                 self.opt_state), cl)

        # Compaction: only when the schedule's per-round count is static,
        # and only synchronously (lateness decouples who trains from whose
        # upload commits, so the participant gather does not cover the
        # commit set — the async step runs full-width). On a mesh the
        # compacted (k, ...) block is client-sharded like the full stack;
        # GSPMD pads non-divisible k.
        # Streaming cohorts run full-width: the seat-id vector is traced
        # and participation varies with the active-seat count.
        fixed_k = (self.schedule.fixed_k if self.schedule is not None
                   else None)
        self._k_active = (fixed_k if (fixed_k is not None
                                      and not self._async)
                          else N)
        self._round_step = (
            make_async_round_step(
                self.spec, ccfg, tcfg, self.policy, lagged=self._lagged,
                mesh=mesh,
                templates={"rstate": self.relay_state,
                           "pending": self.pending,
                           "hist": self.hist if self._lagged else None},
                telemetry=self._telem)
            if self._async else self._make_round_step())
        self._eval_hits = make_eval_hits(self.spec)

    # ------------------------------------------------------------------
    def _stack_clients(self, params_list, client_data):
        """Stack a stackable client group: trimmed data, batched views,
        params and fresh Adam state, all with a leading client axis."""
        n_common = min(x.shape[0] for x, _ in client_data)
        data_x = jnp.stack([jnp.asarray(x[:n_common])
                            for x, _ in client_data])
        data_y = jnp.stack([jnp.asarray(y[:n_common])
                            for _, y in client_data])
        k = len(params_list)
        bs = self.tcfg.batch_size
        nb = n_common // bs
        batches = {
            "x": data_x[:, :nb * bs].reshape(
                k, nb, bs, *data_x.shape[2:]),
            "y": data_y[:, :nb * bs].reshape(k, nb, bs)}
        params = _stack(params_list)
        opt = _stack([adam_init(p) for p in params_list])
        return data_x, data_y, batches, params, opt

    def _init_bucketed(self, buckets, params_list, client_data):
        """Build the per-bucket engine: one ClientBucket (stacked state +
        jitted step) per stackable group, a shared jitted relay commit, and
        the client-id -> (bucket, slot) map."""
        self.spec = None
        self.buckets: List[ClientBucket] = []
        self._client_slot: Dict[int, Tuple[int, int]] = {}
        for b, (spec, ids) in enumerate(buckets):
            data_x, data_y, batches, params, opt = self._stack_clients(
                [params_list[i] for i in ids],
                [client_data[i] for i in ids])
            if self.mesh is not None:
                # each bucket's stack is client-sharded over the SAME mesh
                # axis; a bucket whose size does not divide the axis falls
                # back to replicated (an array at rest cannot hold the
                # uneven sharding GSPMD would pad inside a jit).
                # Committing the inputs here lets the per-bucket jit infer
                # its shardings — the shared commit is the exchange point
                even = len(ids) % self.mesh.shape[placement.CLIENT_AXIS] == 0
                sh = placement.resolve(
                    placement.CLIENT_SHARDED if even else placement.REPLICATED,
                    self.mesh)
                data_x, data_y, batches, params, opt = jax.device_put(
                    (data_x, data_y, batches, params, opt), sh)
            self.buckets.append(ClientBucket(
                spec=spec, ids=np.asarray(ids, np.int64), params=params,
                opt=opt, batches=batches, data_x=data_x, data_y=data_y,
                step=make_bucket_update_step(
                    spec, self.ccfg, self.tcfg, self.policy,
                    per_client_payload=self._async,
                    lagged=self._lagged),
                eval_fn=make_eval_hits(spec)))
            for j, i in enumerate(ids):
                self._client_slot[i] = (b, j)
        self._relay_commit = jax.jit(
            make_async_relay_commit(self.policy, lagged=self._lagged,
                                    mesh=self.mesh)
            if self._async
            else make_relay_commit(self.policy, lagged=self._lagged,
                                   mesh=self.mesh))
        if self._telem:
            # the bucketed round has no single step to fuse telemetry
            # into (one jit per bucket + the shared commit), so it runs
            # one extra small jitted summary after the commit
            self._telem_fn = obs.metrics.make_telemetry_fn(
                self.n_clients, asynchronous=self._async,
                lagged=self._lagged)

    # ------------------------------------------------------------------
    def client_params(self, i: int):
        """Unstacked view of client i's params (checkpointing / inspection)."""
        if self.hetero:
            b, j = self._client_slot[i]
            return jax.tree.map(lambda p: p[j], self.buckets[b].params)
        return jax.tree.map(lambda p: p[i], self.params)

    # ------------------------------------------------------------------
    def _make_round_step(self):
        spec, ccfg, tcfg = self.spec, self.ccfg, self.tcfg
        N, mesh, policy = self.n_clients, self.mesh, self.policy
        mode = ccfg.mode
        lagged = self._lagged
        telem = self._telem        # static: off -> the trace is unchanged
        local_update = client_lib.make_local_update_fn(spec, ccfg, tcfg)
        teachers = make_teacher_phase(policy, ccfg, lagged=lagged)
        uploads_of = make_upload_phase(spec, ccfg, policy)
        # Gather/scatter the participant block ONLY when it is a strict
        # subset: with k == N the idx is a runtime arange XLA cannot elide,
        # and the full-size gather + scatter-back of params/opt/batches
        # would tax every full-participation round for nothing.
        compact = self._k_active < N

        def round_core(params, opt, rstate, batches, data_x, data_y, ids,
                       relay_ks, upd_ks, upl_ks, mask, idx, *lag):
            # `lag` = (hist, dl) under a download clock: the snapshot ring
            # (REPLICATED) and this round's (N,) download delays, both
            # traced. The body is mesh-free — with a mesh, the SAME trace
            # is jitted under the placement-resolved shardings below and
            # GSPMD inserts the collectives at the exchange.
            obs.trace.count("traces")  # the body runs only when jax traces
            hist, dl = lag if lagged else (None, None)
            rstate0 = rstate
            # phase 0 — participant gather: the round runs on the
            # idx-selected (k, ...) block (identity permutation under full
            # participation).
            if compact:
                take = lambda t: jax.tree.map(lambda a: a[idx], t)
                p_s, o_s, b_s = take(params), take(opt), take(batches)
                dx, dy, ids_s = data_x[idx], data_y[idx], ids[idx]
                rk, uk, ok = relay_ks[idx], upd_ks[idx], upl_ks[idx]
                sub_mask = mask[idx]
                dl_s = dl[idx] if lagged else None
            else:
                p_s, o_s, b_s = params, opt, batches
                dx, dy, ids_s = data_x, data_y, ids
                rk, uk, ok = relay_ks, upd_ks, upl_ks
                sub_mask = mask
                dl_s = dl
            wf = sub_mask.astype(jnp.float32)
            n_present = jnp.sum(wf)
            any_present = n_present > 0

            keep = lambda new, old: freeze_absent(sub_mask, new, old)

            # phase 1 — downlink (vmapped relay sampling from the buffers;
            # under download lag, from each client's own stale snapshot)
            with jax.named_scope("teacher_read"):
                teacher = (teachers(hist, ids_s, rk, dl_s) if lagged
                           else teachers(rstate, ids_s, rk))

            # phase 2 — all local updates in one vmap (Algorithm 2 × k)
            with jax.named_scope("update"):
                new_p, new_o, metrics = jax.vmap(local_update)(
                    p_s, o_s, b_s, teacher, uk)
                p_s, o_s = keep(new_p, p_s), keep(new_o, o_s)
                metrics = jax.tree.map(
                    lambda m: jnp.where(_bcast(sub_mask, m), m, 0.0),
                    metrics)

            # phase 3 — uplink + merge (Algorithm 1): absent clients'
            # prototype sums are zero-weighted and their observation rows
            # dropped from the ring WITHOUT consuming slots; a round with
            # zero participants leaves the relay state untouched.
            if mode in ("cors", "fd"):
                with jax.named_scope("upload"):
                    (proto, logit, obs_rows, valid_rows, owner_rows,
                     row_mask) = uploads_of(p_s, dx, dy, ok, ids_s,
                                            sub_mask)
                # THE cross-device exchange (relay/placement.py): the
                # upload payload becomes replicated here — GSPMD lowers it
                # to the observation all-gather + the paper's O(C·d')
                # prototype all-reduce. No-op off-mesh.
                with jax.named_scope("exchange"):
                    (proto, logit, obs_rows, valid_rows, owner_rows,
                     row_mask) = placement.exchange(
                        (proto, logit, obs_rows, valid_rows, owner_rows,
                         row_mask), mesh)
                with jax.named_scope("commit"):
                    new_rstate = policy.append(rstate, obs_rows,
                                               valid_rows, owner_rows,
                                               row_mask)
                    new_rstate = policy.merge_round(new_rstate, proto,
                                                    logit)
                    rstate = jax.tree.map(
                        lambda n, o: jnp.where(any_present, n, o),
                        new_rstate, rstate)

            if mode == "fedavg":
                denom = jnp.maximum(n_present, 1.0)

                def avg(p):
                    # the weight average is fedavg's exchange: summing over
                    # the (sharded) client axis and constraining the result
                    # replicated lowers to the model-size all-reduce
                    s = jnp.sum(p.astype(jnp.float32) * _bcast(wf, p), axis=0)
                    s = placement.exchange(s, mesh)
                    a = (s / denom).astype(p.dtype)
                    return jnp.where(_bcast(sub_mask, p),
                                     jnp.broadcast_to(a, p.shape), p)
                p_s = jax.tree.map(avg, p_s)

            # phase 4 — scatter the compacted block back into the stack
            if compact:
                put = lambda full, s: jax.tree.map(
                    lambda f, v: f.at[idx].set(v), full, s)
                params, opt = put(params, p_s), put(opt, o_s)
                metrics_full = jax.tree.map(
                    lambda m: jnp.zeros((N,) + m.shape[1:],
                                        m.dtype).at[idx].set(m), metrics)
            else:
                params, opt, metrics_full = p_s, o_s, metrics
            tail = ()
            if telem:
                # synchronous commit lag is always 0, so the commit hist
                # collapses to bin 0 = n_present (the oracle's commit-list
                # length); stale reads come from the full-width dl vector.
                with jax.named_scope("telemetry"):
                    tail = (obs.round_telemetry(
                        rstate0, rstate, N, mask=mask,
                        loss_parts=(metrics_full["total"],),
                        gnorm_parts=(metrics_full["grad_norm"],),
                        mask_parts=(mask,), dl=dl),)
            if lagged:
                # ring advance is UNCONDITIONAL (unlike the relay write):
                # a zero-participant round still snapshots the unchanged
                # state, so "d rounds ago" always means rounds, not merges.
                hist = relay_lib.history.push(hist, rstate)
                return (params, opt, rstate, hist, metrics_full) + tail
            return (params, opt, rstate, metrics_full) + tail

        if mesh is None:
            return jax.jit(round_core)

        # Placement-resolved shardings: the SAME round_core trace, jitted
        # with client state CLIENT_SHARDED and relay/history state at the
        # policy's declared placement. GSPMD partitions the body and the
        # only collectives are the ones the exchange implies.
        cl, rep = _client_rep(mesh)
        rspec = placement.resolve(
            policy.out_spec(self.relay_state), mesh)
        in_sh = (cl, cl, rspec, cl, cl, cl, cl, cl, cl, cl, cl, rep)
        out_sh = (cl, cl, rspec)
        if lagged:
            hspec = placement.resolve(
                relay_lib.history.out_spec(self.hist), mesh)
            in_sh += (hspec, cl)
            out_sh += (hspec,)
        out_sh += (cl,) + ((rep,) if telem else ())
        return jax.jit(round_core, in_shardings=in_sh,
                       out_shardings=out_sh)

    # ------------------------------------------------------------------
    def _round_commits(self, r: int, mask_np, delays_np):
        """The round's commit list [(birth, client), ...] — event order,
        identical to the sequential oracle's replay (host-side mirror of
        the device pending buffer for records and comm billing)."""
        mode = self.ccfg.mode
        if mode not in ("cors", "fd"):
            return [(r, int(i)) for i in np.nonzero(mask_np)[0]]
        if self._async:
            return self._commit_mirror.step(r, mask_np, delays_np,
                                            self._upload_order)
        return [(r, int(i)) for i in self._upload_order if mask_np[i]]

    def run_round(self) -> Dict:
        """One round. With the span store on (`obs.trace.start()`), its
        spans are `run_round` > `inputs`, `round_step` (the bucketed
        engine: `bucket_steps`, `commit`), `fetch`, `records`, `eval` >
        `eval_fetch`, `log`; the counters `host_syncs` (arrays pulled to
        the host) and `traces` land on `run_round`'s args."""
        r = len(self.history)
        with obs.trace.span("run_round", round=r):
            if self.hetero:
                return self._run_round_bucketed(r)
            return self._run_round_fused(r)

    def _run_round_fused(self, r: int) -> Dict:
        ccfg, N = self.ccfg, self.n_clients
        mode = ccfg.mode
        with obs.trace.span("inputs"):
            # Same key schedule as the sequential oracle: keys for ALL N
            # clients regardless of participation (absent clients just
            # never consume theirs), so seq and vec stay
            # equivalence-testable under every schedule.
            self.key, relay_ks, upd_ks, upl_ks = collab.round_keys(self.key,
                                                                   N)
            if self._streaming:
                # Cohort view: mask over SEATS, external ids per seat (the
                # traced `ids` arg — seat turnover never retraces).
                # LRU-evicted owners' ring slots are invalidated BEFORE any
                # read this round, same order as the sequential oracle.
                view = self._cohort.round(r)
                mask_np = view.mask.copy()
                ids = jnp.asarray(view.seat_ids, jnp.int32)
                if view.evicted.size:
                    with obs.trace.span("evict"):
                        self.relay_state = self._evict(
                            self.relay_state,
                            jnp.asarray(view.evicted, jnp.int32))
            else:
                mask_np = np.asarray(self.schedule.mask(r, N), bool)
                ids = jnp.arange(N, dtype=jnp.int32)
            present = np.nonzero(mask_np)[0]
            delays_np = (self.clock.delays(r, N) if self.clock is not None
                         else np.zeros((N,), np.int64))
            commits = self._round_commits(r, mask_np, delays_np)
            mask = jnp.asarray(mask_np)
            # Download lag: this round's (N,) snapshot ages, traced like
            # the upload delays — the lag pattern never retraces the step.
            lag = ((self.hist,
                    jnp.asarray(self.dl_clock.delays(r, N), jnp.int32))
                   if self._lagged else ())
            if self._async:
                # Full-width async step: round_idx/delays are traced, so
                # the event timeline never retraces; the pending buffer
                # threads through like the relay state.
                args = (self.params, self.opt_state, self.relay_state,
                        self.pending, self.batches, self.data_x,
                        self.data_y, ids, relay_ks, upd_ks, upl_ks, mask,
                        jnp.asarray(delays_np, jnp.int32),
                        jnp.asarray(r, jnp.int32), *lag)
            else:
                if self._k_active < N:
                    idx_np = present             # static-k compaction
                    assert idx_np.size == self._k_active, (
                        "schedule emitted a mask inconsistent with its "
                        "fixed_k", idx_np.size, self._k_active)
                else:
                    idx_np = np.arange(N)
                args = (self.params, self.opt_state, self.relay_state,
                        self.batches, self.data_x, self.data_y,
                        ids, relay_ks, upd_ks, upl_ks, mask,
                        jnp.asarray(idx_np, jnp.int32), *lag)
        with obs.trace.span("round_step"):
            out = self._round_step(*args)
        telem = None
        if self._telem:
            *out, telem = out
        if self._async and self._lagged:
            (self.params, self.opt_state, self.relay_state,
             self.pending, self.hist, metrics) = out
        elif self._async:
            (self.params, self.opt_state, self.relay_state,
             self.pending, metrics) = out
        elif self._lagged:
            (self.params, self.opt_state, self.relay_state, self.hist,
             metrics) = out
        else:
            self.params, self.opt_state, self.relay_state, metrics = out

        with obs.trace.span("fetch"):
            metrics_np = jax.tree.map(np.asarray, metrics)
            if obs.trace.recording():
                obs.trace.count("host_syncs",
                                len(jax.tree.leaves(metrics_np)))
        with obs.trace.span("records"):
            up, down = comm.round_floats(
                mode, n_present=int(present.size), n_commit=len(commits),
                n_read=int(present.size) if self._lagged else None,
                C=ccfg.num_classes,
                d=ccfg.d_feature, m_up=ccfg.m_up, m_down=ccfg.m_down,
                model_size=(baselines.num_params(self.client_params(0))
                            if mode == "fedavg" else 0))
            self.ledger.log_round(up, down)
            metrics_all = [jax.tree.map(lambda v: float(v[i]), metrics_np)
                           for i in range(N)]
        return self._log_round(present, up, down, metrics_all, commits,
                               telemetry=telem)

    def _run_round_bucketed(self, r: int) -> Dict:
        """One synchronous round across all buckets: every bucket's step
        reads the SAME round-start relay state (downloads), then the shared
        commit writes all uploads in bucket order and merges once."""
        ccfg, N = self.ccfg, self.n_clients
        mode = ccfg.mode
        with obs.trace.span("inputs"):
            # The oracle's key schedule, indexed by ORIGINAL client id and
            # sliced per bucket — bucketing changes execution grouping,
            # never which randomness a client consumes.
            self.key, relay_ks, upd_ks, upl_ks = collab.round_keys(self.key,
                                                                   N)
            mask_np = np.asarray(self.schedule.mask(r, N), bool)
            present = np.nonzero(mask_np)[0]
            delays_np = (self.clock.delays(r, N) if self.clock is not None
                         else np.zeros((N,), np.int64))
            commits = self._round_commits(r, mask_np, delays_np)
            rstate0 = self.relay_state
            # Download lag: every bucket reads from the SAME shared history
            # ring, each client indexing its own stale snapshot; delays
            # sliced per bucket like the keys and the participation mask.
            dl_np = (np.asarray(self.dl_clock.delays(r, N), np.int64)
                     if self._lagged else None)
            pending0 = self.pending if self._async else None
        payloads, metrics_parts = [], []
        with obs.trace.span("bucket_steps"):
            for b in self.buckets:
                ids_j = jnp.asarray(b.ids, jnp.int32)
                lag_b = ((jnp.asarray(dl_np[b.ids], jnp.int32),)
                         if self._lagged else ())
                b.params, b.opt, metrics, payload = b.step(
                    b.params, b.opt,
                    self.hist if self._lagged else rstate0,
                    b.batches, b.data_x, b.data_y,
                    ids_j, relay_ks[b.ids], upd_ks[b.ids], upl_ks[b.ids],
                    jnp.asarray(mask_np[b.ids]), *lag_b)
                metrics_parts.append(metrics)
                payloads.append(payload)

        hist_lag = (self.hist,) if self._lagged else ()
        with obs.trace.span("commit"):
            if self._async:
                # The shared commit runs EVERY round: pending uploads can
                # be due even when nobody trains (and it no-ops when the
                # commit set is empty). mask/delays permuted to upload
                # order, like the concatenated payloads and the pending
                # buffer.
                perm = self._upload_order
                out = self._relay_commit(
                    rstate0, self.pending, tuple(payloads),
                    jnp.asarray(r, jnp.int32),
                    jnp.asarray(delays_np[perm], jnp.int32),
                    jnp.asarray(mask_np[perm]), *hist_lag)
                if self._lagged:
                    self.relay_state, self.pending, self.hist = out
                else:
                    self.relay_state, self.pending = out
            elif mode in ("cors", "fd") and present.size:
                out = self._relay_commit(rstate0, tuple(payloads),
                                         *hist_lag)
                if self._lagged:
                    self.relay_state, self.hist = out
                else:
                    self.relay_state = out
            elif self._lagged:
                # relay untouched this round, but the ring still advances
                self.hist = self._hist_push(self.hist, rstate0)

        telem = None
        if self._telem:
            # per-bucket loss/grad-norm parts in bucket order; the commit
            # quantities are permutation-invariant counts, so mask/delays
            # go in ORIGINAL client-id order (the pending buffer's due
            # events carry their own birth rounds)
            mask_parts = tuple(jnp.asarray(mask_np[b.ids])
                               for b in self.buckets)
            loss_parts = tuple(m["total"] for m in metrics_parts)
            gnorm_parts = tuple(m["grad_norm"] for m in metrics_parts)
            rest = ()
            if self._async:
                rest += (pending0, self.pending,
                         jnp.asarray(r, jnp.int32),
                         jnp.asarray(delays_np, jnp.int32))
            if self._lagged:
                rest += (jnp.asarray(dl_np, jnp.int32),)
            telem = self._telem_fn(
                rstate0, self.relay_state, jnp.asarray(mask_np),
                mask_parts, loss_parts, gnorm_parts, *rest)

        with obs.trace.span("fetch"):
            parts_np = [jax.tree.map(np.asarray, m) for m in metrics_parts]
            if obs.trace.recording():
                obs.trace.count("host_syncs", len(jax.tree.leaves(parts_np)))
        with obs.trace.span("records"):
            up, down = comm.round_floats(
                mode, n_present=int(present.size), n_commit=len(commits),
                n_read=int(present.size) if self._lagged else None,
                C=ccfg.num_classes,
                d=ccfg.d_feature, m_up=ccfg.m_up, m_down=ccfg.m_down)
            self.ledger.log_round(up, down)
            metrics_all: List[Dict] = [None] * N
            for b, m_np in zip(self.buckets, parts_np):
                for j, i in enumerate(b.ids):
                    metrics_all[int(i)] = jax.tree.map(
                        lambda v: float(v[j]), m_np)
        return self._log_round(present, up, down, metrics_all, commits,
                               telemetry=telem)

    def _log_round(self, present, up, down, metrics_all, commits,
                   telemetry=None) -> Dict:
        with obs.trace.span("eval"):
            accs = self.evaluate_all()
        with obs.trace.span("log"):
            rec = {"round": len(self.history) + 1,
                   "acc_mean": float(np.mean(accs)),
                   "acc_std": float(np.std(accs)),
                   "accs": accs,
                   "metrics": metrics_all,
                   "participants": present.tolist(),
                   "commits": [[b, c] for b, c in commits],
                   "comm_up": up, "comm_down": down}
            if telemetry is not None:
                rec["telemetry"] = obs.to_record(telemetry)
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
    def evaluate_all(self, batch: int = 512) -> List[float]:
        """Per-client test accuracy, all of a bucket's clients per test
        chunk in one call (homogeneous fleets are one bucket)."""
        n = self.test_x.shape[0]

        def stack_hits(fn, P):
            correct = 0                          # accumulate ON device —
            for i in range(0, n, batch):         # one sync per stack, not
                correct = correct + fn(          # one per chunk
                    P, self.test_x[i:i + batch], self.test_y[i:i + batch])
            with obs.trace.span("eval_fetch"):
                obs.trace.count("host_syncs")
                return np.asarray(correct)

        if not self.hetero:
            return (stack_hits(self._eval_hits, self.params) / n).tolist()
        accs = np.zeros((self.n_clients,))
        for b in self.buckets:
            accs[b.ids] = stack_hits(b.eval_fn, b.params) / n
        return accs.tolist()
