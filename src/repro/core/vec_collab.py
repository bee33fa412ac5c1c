"""Vectorized multi-client engine: a whole fleet round is ONE jitted step.

The paper's headline claim is that CoRS "is scalable with the number of
clients"; the sequential `CollabTrainer` oracle steps clients in a Python
loop (cost linear in N, one dispatch per client per phase). This engine
stacks clients' params / Adam moments / data along a leading client axis
and runs the whole round — relay sampling, local updates, uploads, server
merge — as ONE jitted `round_core` (`make_round_step`) against the same
fixed-shape relay state the sequential path uses. Given the same seeds and
equal-size partitions the two engines evolve identical relay state and
near-identical weights (see tests/test_vec_collab.py and
tests/test_relay_policies.py), but the vectorized round is one XLA program
instead of O(N) Python dispatches.

The round body. Clients are grouped into stackable buckets by
`client_lib.bucketize` (same ClientSpec AND same param shapes): a
homogeneous fleet is one bucket, a mixed-architecture fleet (a CoRS
selling point) several. CoRS only couples clients through the (C, d')
representation pool — no weights cross a bucket boundary — so
`round_core` takes every client-state argument as a tuple over buckets
and runs

  per bucket  (a static Python loop) `teacher_read` — every bucket samples
              from the SAME round-start relay state —, `update` (vmapped
              local updates, absent clients frozen) and `upload`;
  once        `exchange` + `commit` of all buckets' uploads in bucket
              order (= the order the sequential oracle uploads in, see
              core/collab.py): one append and ONE prototype merge, or,
              under an upload clock, one `events.commit_and_park`; then
              the download-lag history push and the telemetry tail.

Appending the bucket-order concatenation equals appending bucket by bucket
(every policy's append writes rows in order and masked rows consume no
slots), and the per-round key schedule is the oracle's `collab.round_keys`,
indexed by ORIGINAL client id and sliced per bucket, so the sequential
oracle remains the bit-exact reference for ring bookkeeping under any
bucket mix (tests/test_hetero_bucketed.py). Nothing is sliced or permuted
when there is one bucket: a homogeneous fleet traces the same program as a
body written for it alone. Bucket count, sizes and order are static, so a
fleet compiles one round step, ever.

Relay policy: the server side is pluggable (`repro.relay`): `flat` (the
seed ring, bit-compatible), `per_class` (the paper's exact per-class buffer
layout) or `staleness` (exp(-λ·age) Gumbel-top-k sampling). The policy's
pure functions are closed over by the jitted round step, so swapping
policies swaps ONE compiled program, not the engine.

Participation: a `ParticipationSchedule` (repro.relay.participation) emits
a per-round boolean client mask. On a one-bucket synchronous fleet,
schedules with a static participant count k (uniform_k, cyclic) run
COMPACTED: the step gathers the k participants into a (k, ...) block, so a
k=N/4 round costs ~1/4 of a full round — real savings, not just masking.
Everything else runs full-width and masks: absent clients' params/opt are
frozen via `where`, their uploads zero-weighted, and the ring append drops
their rows without consuming slots; a round nobody trains in leaves the
relay state untouched. The mask and gather indices are traced arguments
of fixed shape, so participation never retraces.

Device scaling is PLACEMENT-DRIVEN (repro.relay.placement): pass a mesh
with a "clients" axis (`sharding.client_mesh`, via `FleetConfig.mesh`) and
the SAME traced round body is jitted with in/out shardings resolved from
the state classes' placement declarations (`_round_shardings`) —
client-resident leaves (bucket stacks, per-client inputs, pending uploads)
are CLIENT_SHARDED over the mesh axis, relay/history state is REPLICATED
per `policy.out_spec` / `events.out_spec` / `history.out_spec` — and GSPMD
inserts the collectives. A bucket whose size does not divide the client
axis keeps its stack REPLICATED (an array at rest cannot hold an uneven
sharding). The one cross-device exchange per round is `placement.exchange`
on the upload payload (the CLIENT_SHARDED -> REPLICATED constraint right
before the relay append/merge, which lowers to the observation all-gather
+ the paper's O(C·d') prototype all-reduce). There are no mesh branches in
the round body, so every fleet composition runs on the mesh through the
same code that runs off it, and off-mesh bit-compatibility is structural.

Asynchrony: pass an upload clock (a repro.sim ClockModel spec) and uploads
commit LATE through the event-ordered relay log (repro.relay.events): a
round-r upload with commit delay d <= D_max parks in a fixed-shape pending
buffer (N, D_max, ...) indexed by upload position and is appended — in
event order, stamped with its birth clock — in round r+d, inside the same
round step. Teachers are always sampled from the round-start COMMITTED
state (the client's last sync; in-flight uploads are invisible). The
commit set decouples from the participant set, so the async form runs
full-width (on a mesh the pending buffer is CLIENT_SHARDED and the commit
payload is the round's one exchange); `D_max = 0` keeps the synchronous
form bit-identically. The sequential oracle replays the identical event
order host-side and stays the bit-exact reference
(tests/test_async_relay.py).

Download lag: pass a download clock (the same `repro.sim` spec machinery,
independent seed fold) and every client reads its teachers AND global
prototypes from a snapshot `d(client, t)` rounds staler than its
round-start sync — what its round-`t − d` self would have read fresh
(d = 0 is the round-start state) — the stale-sync half of asynchrony,
modeled by a bounded history ring of the last `H_max = d_max + 1` relay
states (repro.relay.history). The ring is threaded through the round step:
per-client snapshot reads are dynamic indices into the history axis (one
batched gather, fused with the teacher-row gather) and the post-merge push
happens at the end of the step, every round, with `H_max` static and the
per-round delay vector traced. Upload lag composes: under both clocks a
client can distill against a stale snapshot while its own upload is still
in flight, and because slot age is clock-derived (`clock − stamp`), the
ages it sees are the snapshot's own. `H_max = 1` (or no download clock) is
bit-identical to the unlagged step; the sequential oracle replays the ring
host-side (tests/test_download_lag.py). On a mesh the ring is REPLICATED
(history.out_spec) and the per-client stale reads stay local gathers — no
extra collective.
"""
from __future__ import annotations

import functools
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
from repro.types import CollabConfig, FleetConfig, TrainConfig


def _stack(trees: Sequence[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


# ---------------------------------------------------------------------------
# Round-phase builders, parameterized by ClientSpec: `round_core` composes
# one set of them per bucket, so the phase semantics exist in one place.
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


def _stack_placement(n: int, mesh) -> str:
    """Where a bucket's stack of `n` clients lives on `mesh`:
    CLIENT_SHARDED when `n` divides the client axis, else REPLICATED — an
    array at rest cannot hold the uneven sharding GSPMD pads inside a jit.
    (A one-bucket fleet always divides: the trainer checks N.)"""
    even = n % mesh.shape[placement.CLIENT_AXIS] == 0
    return placement.CLIENT_SHARDED if even else placement.REPLICATED


def _round_shardings(mesh, policy: relay_lib.RelayPolicy, bucket_ids,
                     rstate, pending=None, hist=None):
    """`round_core`'s (in_shardings, out_shardings) on `mesh`, resolved
    from the placement declarations: bucket stacks per `_stack_placement`,
    the (N,) per-client inputs CLIENT_SHARDED, relay / pending / history
    state per `policy.out_spec` / `events.out_spec` / `history.out_spec`,
    scalars and telemetry REPLICATED. State a fleet does not carry (None)
    has no leaves to place."""
    cl = placement.resolve(placement.CLIENT_SHARDED, mesh)
    rep = placement.resolve(placement.REPLICATED, mesh)
    st = tuple(placement.resolve(_stack_placement(len(ids), mesh), mesh)
               for ids in bucket_ids)
    rs = placement.resolve(policy.out_spec(rstate), mesh)
    ps = (None if pending is None else
          placement.resolve(relay_lib.events.out_spec(pending), mesh))
    hs = (None if hist is None else
          placement.resolve(relay_lib.history.out_spec(hist), mesh))
    # params, opt, rstate, pending, batches, data_x, data_y, ids, relay_ks,
    # upd_ks, upl_ks, mask, idx, delays, round_idx, hist, dl
    in_sh = (st, st, rs, ps, st, st, st, cl, cl, cl, cl, cl, rep, cl, rep,
             hs, cl)
    # params, opt, rstate, pending, hist, metrics, telemetry
    out_sh = (st, st, rs, ps, hs, st, rep)
    return in_sh, out_sh


def make_round_step(specs: Sequence[client_lib.ClientSpec],
                    bucket_ids: Sequence[np.ndarray], ccfg: CollabConfig,
                    tcfg: TrainConfig, policy: relay_lib.RelayPolicy, *,
                    compact: bool = False, asynchronous: bool = False,
                    lagged: bool = False, telemetry: bool = False,
                    mesh=None, states=None):
    """The fleet's ONE jitted round step, `round_core`, over the buckets
    `specs[b]` / `bucket_ids[b]` (ORIGINAL client ids, ascending; bucket
    order is upload order).

    Returns `step(params, opt, rstate, pending, batches, data_x, data_y,
    ids, relay_ks, upd_ks, upl_ks, mask, idx, delays, round_idx, hist, dl)
    -> (params, opt, rstate, pending, hist, metrics, telemetry)`. `params`,
    `opt`, `batches`, `data_x`, `data_y` and the returned `params`, `opt`
    and `metrics` are tuples over buckets; `ids`, the keys, `mask`,
    `delays` and `dl` are (N,) in client-id order. An argument a form does
    not use is None, and so is an output it does not make:

      compact       (one bucket, synchronous) `idx`: the (k,) participant
                    ids; the round runs on that gathered block;
      asynchronous  `pending`, `delays`, `round_idx`: the event log
                    (relay/events.py) commits due uploads and parks the
                    rest;
      lagged        `hist`, `dl`: the download-lag ring and this round's
                    (N,) snapshot ages (relay/history.py);
      telemetry     a STATIC build flag (off: the traced program is the
                    telemetry-free one): an in-jit `obs.RoundTelemetry`
                    from state the step already holds.

    Every per-round value is traced, so neither participation nor the
    clocks' timelines retrace. `mesh` + `states` (the `(rstate, pending,
    hist)` the step will be fed): jit the same body with
    `_round_shardings`; the upload payload is the round's one exchange."""
    mode = ccfg.mode
    relay = mode in ("cors", "fd")
    N = sum(len(ids) for ids in bucket_ids)
    one = len(specs) == 1
    assert one or not compact, "static-k compaction needs one bucket"
    updates = [client_lib.make_local_update_fn(s, ccfg, tcfg) for s in specs]
    uploads = [make_client_upload_phase(s, ccfg) if asynchronous
               else make_upload_phase(s, ccfg, policy) for s in specs]
    teachers = make_teacher_phase(policy, ccfg, lagged=lagged)
    # Static row selections: each bucket's clients, and the upload-order
    # permutation — traced only where they are not the identity, so a
    # one-bucket fleet slices and permutes nothing.
    sels = [None] if one else [np.asarray(ids) for ids in bucket_ids]
    order = np.concatenate(bucket_ids)
    perm = None if np.array_equal(order, np.arange(N)) else order

    def round_core(params, opt, rstate, pending, batches, data_x, data_y,
                   ids, relay_ks, upd_ks, upl_ks, mask, idx, delays,
                   round_idx, hist, dl):
        obs.trace.count("traces")  # the body runs only when jax traces
        rstate0, pending0 = rstate, pending
        outs = []
        for b, sel in enumerate(sels):
            # phase 0 — the bucket's rows of the (N,) inputs; under
            # compaction the idx-selected (k, ...) block of the one stack.
            # Gather ONLY when it is a strict subset: with k == N the idx
            # is a runtime arange XLA cannot elide, and the full-size
            # gather + scatter-back of params/opt/batches would tax every
            # full-participation round for nothing.
            rows = idx if compact else sel
            take = lambda t: (t if rows is None
                              else jax.tree.map(lambda a: a[rows], t))
            p_s, o_s, b_s = params[b], opt[b], batches[b]
            dx, dy = data_x[b], data_y[b]
            if compact:
                p_s, o_s, b_s, dx, dy = take((p_s, o_s, b_s, dx, dy))
            ids_s, rk, uk, ok, sub_mask, dl_s = take(
                (ids, relay_ks, upd_ks, upl_ks, mask, dl))
            keep = lambda new, old: freeze_absent(sub_mask, new, old)

            # phase 1 — downlink (vmapped relay sampling from the
            # round-start buffers; under download lag, from each client's
            # own stale snapshot)
            with jax.named_scope("teacher_read"):
                teacher = (teachers(hist, ids_s, rk, dl_s) if lagged
                           else teachers(rstate, ids_s, rk))

            # phase 2 — all local updates in one vmap (Algorithm 2 × k)
            with jax.named_scope("update"):
                new_p, new_o, metrics = jax.vmap(updates[b])(
                    p_s, o_s, b_s, teacher, uk)
                p_s, o_s = keep(new_p, p_s), keep(new_o, o_s)
                metrics = jax.tree.map(
                    lambda m: jnp.where(_bcast(sub_mask, m), m, 0.0),
                    metrics)

            # phase 3a — uplink, compute side: the unreduced per-client
            # pieces the event log parks, or the synchronous payload with
            # absent clients' prototype sums zero-weighted and their
            # observation rows masked out
            up = None
            if relay:
                with jax.named_scope("upload"):
                    up = (uploads[b](p_s, dx, dy, ok, ids_s)
                          if asynchronous
                          else uploads[b](p_s, dx, dy, ok, ids_s, sub_mask))

            if mode == "fedavg":               # one bucket: see the trainer
                wf = sub_mask.astype(jnp.float32)
                denom = jnp.maximum(jnp.sum(wf), 1.0)

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
                p_s, o_s = put(params[b], p_s), put(opt[b], o_s)
                metrics = jax.tree.map(
                    lambda m: jnp.zeros((N,) + m.shape[1:],
                                        m.dtype).at[idx].set(m), metrics)
            outs.append((p_s, o_s, metrics, up, sub_mask))
        params, opt, metrics, ups, masks = zip(*outs)

        # phase 3b — the round's single relay write (and, on a mesh, its
        # single cross-device exchange), all buckets in bucket order
        if relay and asynchronous:
            # commit every due event (pending uploads whose clock says
            # "now" + this round's delay-0 uploads) in event order, park
            # the rest; it runs every round, since pending uploads can be
            # due when nobody trains. The pending buffer, the payload and
            # mask/delays are indexed by upload position.
            fresh = ups[0] if one else {
                k: jnp.concatenate([u[k] for u in ups]) for k in ups[0]}
            mask_u, delays_u = ((mask, delays) if perm is None
                                else (mask[perm], delays[perm]))
            with jax.named_scope("commit"):
                rstate, pending = relay_lib.events.commit_and_park(
                    policy, rstate, pending, fresh, round_idx, delays_u,
                    mask_u, mesh=mesh)
        elif relay:
            # a round with zero participants leaves the relay untouched
            any_present = functools.reduce(
                jnp.add, [jnp.sum(m.astype(jnp.float32)) for m in masks]) > 0
            payload = ups[0]
            if not one:
                cols = list(zip(*ups))
                payload = (prototypes.merge(*cols[0]),
                           None if cols[1][0] is None
                           else prototypes.merge(*cols[1]),
                           *[jnp.concatenate(c) for c in cols[2:]])
            # THE cross-device exchange (relay/placement.py): the upload
            # payload becomes replicated here — GSPMD lowers it to the
            # observation all-gather + the paper's O(C·d') prototype
            # all-reduce. No-op off-mesh.
            with jax.named_scope("exchange"):
                (proto, logit, obs_rows, valid_rows, owner_rows,
                 row_mask) = placement.exchange(payload, mesh)
            with jax.named_scope("commit"):
                new_rstate = policy.append(rstate, obs_rows, valid_rows,
                                           owner_rows, row_mask)
                new_rstate = policy.merge_round(new_rstate, proto, logit)
                rstate = jax.tree.map(
                    lambda n, o: jnp.where(any_present, n, o),
                    new_rstate, rstate)

        telem = None
        if telemetry:
            # per-bucket loss/grad-norm parts in bucket order; the commit
            # counts are permutation-invariant, so mask/delays/dl go in
            # client-id order (synchronous commit lag is always 0; async
            # lags come from the pre-commit pending buffer's due events)
            with jax.named_scope("telemetry"):
                telem = obs.round_telemetry(
                    rstate0, rstate, N, mask=mask,
                    loss_parts=tuple(m["total"] for m in metrics),
                    gnorm_parts=tuple(m["grad_norm"] for m in metrics),
                    mask_parts=(mask,) if one else masks, pending=pending,
                    pending_pre=pending0, round_idx=round_idx,
                    delays=delays, dl=dl)
        if lagged:
            # ring advance is UNCONDITIONAL (unlike the relay write): a
            # round without commits still snapshots the unchanged state,
            # so "d rounds ago" always means rounds, not merges.
            hist = relay_lib.history.push(hist, rstate)
        return params, opt, rstate, pending, hist, metrics, telem

    shardings = {}
    if mesh is not None:
        in_sh, out_sh = _round_shardings(mesh, policy, bucket_ids, *states)
        shardings = dict(in_shardings=in_sh, out_shardings=out_sh)
    return jax.jit(round_core, **shardings)


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
    """One stackable group of clients: shared ClientSpec + param shapes,
    params/opt/data stacked along a leading axis of size len(ids), and the
    bucket's jitted eval. `ids` are ORIGINAL client ids (ascending), used
    for relay owner tags, key-schedule slicing and participation-mask
    slicing."""
    spec: client_lib.ClientSpec
    ids: np.ndarray
    params: Any
    opt: Any
    batches: Dict
    data_x: jax.Array
    data_y: jax.Array
    eval_fn: Callable


class VectorizedCollabTrainer:
    """Drop-in counterpart of `CollabTrainer` for any client fleet.

    Same constructor shape, `run_round` record schema, `ledger` accounting
    and `history`; `specs` may be a single ClientSpec or a sequence. Clients
    are grouped into stackable buckets (`client_lib.bucketize`) and every
    round is one call of the fleet's jitted round step (`make_round_step`);
    a homogeneous fleet is ONE bucket, and only it runs static-k
    compaction. Client datasets are trimmed to the shortest partition
    within each bucket so they stack; pass equal-size partitions for exact
    parity with the oracle.

    The fleet (relay policy, participation schedule, upload/download
    clocks, mesh, arrivals) is ONE `FleetConfig`, passed as `fleet=`.
    """

    def __init__(self,
                 specs: Union[client_lib.ClientSpec,
                              Sequence[client_lib.ClientSpec]],
                 params_list: Sequence[Any],
                 client_data: Sequence[Tuple[jax.Array, jax.Array]],
                 test_data: Tuple[jax.Array, jax.Array],
                 ccfg: CollabConfig, tcfg: TrainConfig, seed: int = 0,
                 fleet: FleetConfig = None, telemetry=None):
        fleet = fleet if fleet is not None else FleetConfig()
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
        # event-log and history state, threaded through the round step
        # when the fleet has the clock that needs it (None otherwise)
        self.pending = self.hist = None
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

        # Client stacks, one per bucket. On a mesh each is committed to its
        # placement up front: round 0 then presents the same (sharding,
        # committed) signature as every later round, keeping the jit
        # fastpath single-entry (the compile-once contract the tests pin).
        self.buckets: List[ClientBucket] = []
        self._client_slot: Dict[int, Tuple[int, int]] = {}
        for b, (spec, ids) in enumerate(buckets):
            stacks = self._stack_clients([params_list[i] for i in ids],
                                         [client_data[i] for i in ids])
            if mesh is not None:
                stacks = jax.device_put(stacks, placement.resolve(
                    _stack_placement(len(ids), mesh), mesh))
            data_x, data_y, batches, params, opt = stacks
            self.buckets.append(ClientBucket(
                spec=spec, ids=np.asarray(ids, np.int64), params=params,
                opt=opt, batches=batches, data_x=data_x, data_y=data_y,
                eval_fn=make_eval_hits(spec)))
            self._client_slot.update({i: (b, j) for j, i in enumerate(ids)})

        # Compaction: only when the schedule's per-round count is static,
        # only for one bucket (bucket participant counts vary per round
        # even under fixed-k schedules), and only synchronously (lateness
        # decouples who trains from whose upload commits, so the
        # participant gather does not cover the commit set). On a mesh the
        # compacted (k, ...) block is client-sharded like the full stack;
        # GSPMD pads non-divisible k. Streaming cohorts run full-width: the
        # seat-id vector is traced and participation varies with the
        # active-seat count.
        fixed_k = (self.schedule.fixed_k if self.schedule is not None
                   else None)
        self._k_active = (fixed_k if (fixed_k is not None and not self.hetero
                                      and not self._async)
                          else N)
        self._round_step = make_round_step(
            [b.spec for b in self.buckets], [b.ids for b in self.buckets],
            ccfg, tcfg, self.policy, compact=self._k_active < N,
            asynchronous=self._async, lagged=self._lagged,
            telemetry=self._telem, mesh=mesh,
            states=(self.relay_state, self.pending, self.hist))

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

    # ------------------------------------------------------------------
    @property
    def params(self):
        """A homogeneous fleet's stacked client params (its one bucket's);
        a mixed fleet's are per bucket, in `buckets`."""
        (bucket,) = self.buckets
        return bucket.params

    @property
    def opt_state(self):
        """A homogeneous fleet's stacked Adam state (its one bucket's)."""
        (bucket,) = self.buckets
        return bucket.opt

    def client_params(self, i: int):
        """Unstacked view of client i's params (checkpointing / inspection)."""
        b, j = self._client_slot[i]
        return jax.tree.map(lambda p: p[j], self.buckets[b].params)

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
        """One round: build its inputs, call the round step once, pull the
        metrics, bill, evaluate and log. With the span store on
        (`obs.trace.start()`), its spans are `run_round` > `inputs`,
        `round_step`, `fetch`, `records`, `eval` > `eval_fetch` (one per
        bucket), `log`; the counters `host_syncs` (arrays pulled to the
        host) and `traces` land on `run_round`'s args."""
        r = len(self.history)
        ccfg, N = self.ccfg, self.n_clients
        mode = ccfg.mode
        with obs.trace.span("run_round", round=r):
            with obs.trace.span("inputs"):
                # Same key schedule as the sequential oracle: keys for ALL
                # N clients regardless of participation (absent clients
                # just never consume theirs), indexed by ORIGINAL client id
                # — bucketing changes execution grouping, never which
                # randomness a client consumes.
                self.key, relay_ks, upd_ks, upl_ks = collab.round_keys(
                    self.key, N)
                if self._streaming:
                    # Cohort view: mask over SEATS, external ids per seat
                    # (the traced `ids` arg — seat turnover never
                    # retraces). LRU-evicted owners' ring slots are
                    # invalidated BEFORE any read this round, same order as
                    # the sequential oracle.
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
                delays_np = (self.clock.delays(r, N)
                             if self.clock is not None
                             else np.zeros((N,), np.int64))
                commits = self._round_commits(r, mask_np, delays_np)
                idx = None
                if self._k_active < N:           # static-k compaction
                    assert present.size == self._k_active, (
                        "schedule emitted a mask inconsistent with its "
                        "fixed_k", present.size, self._k_active)
                    idx = jnp.asarray(present, jnp.int32)
                # round_idx/delays (async) and the (N,) snapshot ages
                # (download lag) are traced like the mask: the event
                # timeline and the lag pattern never retrace the step.
                asy = self._async
                args = (
                    tuple(b.params for b in self.buckets),
                    tuple(b.opt for b in self.buckets), self.relay_state,
                    self.pending, tuple(b.batches for b in self.buckets),
                    tuple(b.data_x for b in self.buckets),
                    tuple(b.data_y for b in self.buckets), ids, relay_ks,
                    upd_ks, upl_ks, jnp.asarray(mask_np), idx,
                    jnp.asarray(delays_np, jnp.int32) if asy else None,
                    jnp.asarray(r, jnp.int32) if asy else None, self.hist,
                    (jnp.asarray(self.dl_clock.delays(r, N), jnp.int32)
                     if self._lagged else None))
            with obs.trace.span("round_step"):
                (params, opt, self.relay_state, self.pending, self.hist,
                 metrics, telem) = self._round_step(*args)
            for b, p, o in zip(self.buckets, params, opt):
                b.params, b.opt = p, o

            with obs.trace.span("fetch"):
                metrics_np = jax.tree.map(np.asarray, metrics)
                if obs.trace.recording():
                    obs.trace.count("host_syncs",
                                    len(jax.tree.leaves(metrics_np)))
            with obs.trace.span("records"):
                up, down = comm.round_floats(
                    mode, n_present=int(present.size),
                    n_commit=len(commits),
                    n_read=int(present.size) if self._lagged else None,
                    C=ccfg.num_classes, d=ccfg.d_feature, m_up=ccfg.m_up,
                    m_down=ccfg.m_down,
                    model_size=(baselines.num_params(self.client_params(0))
                                if mode == "fedavg" else 0))
                self.ledger.log_round(up, down)
                metrics_all: List[Dict] = [None] * N
                for b, m_np in zip(self.buckets, metrics_np):
                    for j, i in enumerate(b.ids.tolist()):
                        metrics_all[i] = jax.tree.map(
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
        accs = np.zeros((self.n_clients,))
        for b in self.buckets:
            correct = 0                          # accumulate ON device —
            for i in range(0, n, batch):         # one sync per bucket, not
                correct = correct + b.eval_fn(   # one per chunk
                    b.params, self.test_x[i:i + batch],
                    self.test_y[i:i + batch])
            with obs.trace.span("eval_fetch"):
                obs.trace.count("host_syncs")
                accs[b.ids] = np.asarray(correct) / n
        return accs.tolist()
