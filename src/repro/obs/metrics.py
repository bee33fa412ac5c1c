"""In-jit round telemetry: one fixed-shape `RoundTelemetry` pytree per round.

The headline claims of the paper — flat communication, scalability in N,
utility under staleness — are measured claims, and the engines that
simulate them (async event log, download-lag history, hetero buckets,
meshes) were invisible outside ad-hoc prints. This module computes the
round's observability surface INSIDE the existing jitted round step, so
telemetry is free when off (a static flag — the traced program is
unchanged) and cheap when on (a handful of reductions over state the step
already holds; the CI `telemetry` gate bounds the overhead).

Every leaf is fixed-shape, mesh-ready (all leaves REPLICATED — telemetry
summarizes the shared relay, never per-client state; see `out_spec`), and
oracle-checked: the sequential trainer computes the SAME function over its
bit-equal ring state (plus host-side pending/commit counters), so the
integer bookkeeping leaves are bit-identical across engines while the
float leaves (drift, per-bucket losses) carry the same vmap-association
tolerance as the weights themselves (tests/oracles.assert_telemetry_match).

Leaf semantics (C = num_classes, B = STALE_BINS, n_b = bucket count):

  occupancy       ()   int32  live ring slots (owner != EMPTY_OWNER)
  fill            (C,) int32  valid observations per class across the ring
  owner_diversity ()   int32  distinct real clients (owner >= 0) owning
                              at least one live slot — seeds excluded
  stale_hist      (B,) int32  age histogram of live slots in the
                              POST-round state, age = clock − stamp
                              clipped into bin B−1 (what a round-fresh
                              teacher read next round would see)
  pending_depth   ()   int32  in-flight uploads still parked after the
                              round (0 for synchronous fleets)
  commit_hist     (B,) int32  this round's commits binned by commit lag
                              (commit round − birth round); bin 0 is the
                              fresh delay-0 uploads, so late commits =
                              commit_hist[1:].sum()
  stale_reads     ()   int32  present clients whose downlink came from a
                              stale snapshot (download delay > 0)
  proto_drift     ()   f32    ||global_protos − previous round's||₂
  bucket_loss     (n_b,) f32  mean last-batch total loss over the
                              bucket's PRESENT clients
  bucket_grad_norm (n_b,) f32 same reduction over the global grad norm
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.relay import placement
from repro.relay.base import EMPTY_OWNER

# Fixed histogram width shared by stale_hist and commit_hist: ages/lags
# 0..STALE_BINS-2 get their own bin, everything older clips into the last.
STALE_BINS = 8


class RoundTelemetry(NamedTuple):
    occupancy: jax.Array          # () int32
    fill: jax.Array               # (C,) int32
    owner_diversity: jax.Array    # () int32
    stale_hist: jax.Array         # (STALE_BINS,) int32
    pending_depth: jax.Array      # () int32
    commit_hist: jax.Array        # (STALE_BINS,) int32
    stale_reads: jax.Array        # () int32
    proto_drift: jax.Array        # () f32
    bucket_loss: jax.Array        # (n_buckets,) f32
    bucket_grad_norm: jax.Array   # (n_buckets,) f32


# Integer leaves are derived from the exactly-matched ring/clock/pending
# bookkeeping and must agree bit-for-bit across engines; float leaves
# inherit the engines' vmap-association tolerance.
EXACT_LEAVES = ("occupancy", "fill", "owner_diversity", "stale_hist",
                "pending_depth", "commit_hist", "stale_reads")
FLOAT_LEAVES = ("proto_drift", "bucket_loss", "bucket_grad_norm")


def out_spec(telem: RoundTelemetry):
    """Placement declaration (relay/placement.py): every telemetry leaf is
    a fleet-wide summary of REPLICATED relay/pending reductions — nothing
    is per-client-resident, so the whole pytree replicates on a mesh."""
    return placement.like(telem, placement.REPLICATED)


def _single_summary(state, n_clients: int):
    """(occupancy, fill, owner_diversity, stale_hist) of ONE relay state.

    Layout-generic across the policy states: flat/staleness rings carry
    `valid (cap, C)` / `owner (cap,)`, the per-class layout carries
    `valid (C, cap_c)` / `owner (C, cap_c)` — discriminated by the ptr
    rank (per_class keeps one write pointer per class)."""
    per_class = state.ptr.ndim == 1
    owner = state.owner.reshape(-1)
    stamp = state.stamp.reshape(-1)
    vi = state.valid.astype(jnp.int32)
    fill = jnp.sum(vi, axis=1) if per_class else jnp.sum(vi, axis=0)
    live = owner != EMPTY_OWNER
    li = live.astype(jnp.int32)
    occupancy = jnp.sum(li)
    # distinct real owners (seeds' owner=-1 excluded): sort-based exact
    # count — dead slots sort to a sentinel, a live owner counts where it
    # differs from its sorted predecessor. Unlike a scatter onto an (N,)
    # count vector this is id-space independent, which streaming arrivals
    # need: external ids are unbounded while seats stay few.
    sentinel = jnp.iinfo(jnp.int32).max
    key = jnp.sort(jnp.where(live & (owner >= 0), owner, sentinel))
    isreal = key != sentinel
    distinct = isreal & jnp.concatenate(
        [jnp.ones((1,), bool), key[1:] != key[:-1]])
    owner_diversity = jnp.sum(distinct.astype(jnp.int32))
    age = jnp.clip(state.clock - stamp, 0, STALE_BINS - 1)
    stale_hist = jnp.zeros((STALE_BINS,), jnp.int32).at[age].add(li)
    return occupancy, fill, owner_diversity, stale_hist


def relay_summary(state, n_clients: int):
    """(occupancy, fill, owner_diversity, stale_hist) of a relay state.

    Sharded relay states (relay/shards.py — every inner leaf stacked on a
    leading (S,) axis) summarize per shard and reduce: occupancy/fill/
    stale_hist sum, and because a client hashes to exactly ONE shard,
    distinct owners across shards is the sum of per-shard counts too."""
    if hasattr(state, "shards"):
        occ, fill, div, hist = jax.vmap(
            lambda s: _single_summary(s, n_clients))(state.shards)
        return (jnp.sum(occ), jnp.sum(fill, axis=0), jnp.sum(div),
                jnp.sum(hist, axis=0))
    return _single_summary(state, n_clients)


def shard_summary(state, n_clients: int = 0) -> dict:
    """Host-side PER-SHARD summary — the population sweep's report surface
    (occupancy, owner diversity and the age histogram per relay shard).
    Single-relay states report themselves as one shard."""
    if hasattr(state, "shards"):
        occ, fill, div, hist = jax.vmap(
            lambda s: _single_summary(s, n_clients))(state.shards)
    else:
        o, f, d, h = _single_summary(state, n_clients)
        occ, fill, div, hist = o[None], f[None], d[None], h[None]
    occ, div, hist = jax.device_get((occ, div, hist))
    return {"occupancy": np.asarray(occ).tolist(),
            "owner_diversity": np.asarray(div).tolist(),
            "stale_hist": np.asarray(hist).tolist()}


def round_telemetry(prev_state, new_state, n_clients: int, *, mask,
                    loss_parts, gnorm_parts, mask_parts,
                    pending=None, pending_pre=None, round_idx=None,
                    delays=None, dl=None,
                    commit_hist=None, pending_depth=None) -> RoundTelemetry:
    """The one telemetry computation, shared by every engine path.

    prev/new_state: the relay state at round start / end (drift + summary).
    mask: (N,) bool participation. loss/gnorm/mask_parts: per-bucket tuples
    of (k_b,) arrays in bucket order, absent clients zeroed/masked — one
    entry for homogeneous fleets.

    The commit-lag histogram has two sources: the vectorized async step
    passes the PRE-commit pending buffer (`pending_pre`, `round_idx`,
    `delays`) and the lags are recomputed in-jit from the same due-event
    predicate `commit_and_park` uses; the sequential oracle (which replays
    events host-side and holds no PendingState) passes its host-counted
    `commit_hist` / `pending_depth` directly. Both reduce the identical
    event multiset, which is what run_matched pins bit-for-bit."""
    occupancy, fill, owner_diversity, stale_hist = relay_summary(
        new_state, n_clients)

    if pending_depth is None:
        pending_depth = (jnp.sum(pending.live.astype(jnp.int32))
                         if pending is not None
                         else jnp.zeros((), jnp.int32))
    else:
        pending_depth = jnp.asarray(pending_depth, jnp.int32).reshape(())

    if commit_hist is None:
        fresh = mask & (delays == 0) if delays is not None else mask
        commit_hist = jnp.zeros((STALE_BINS,), jnp.int32).at[0].add(
            jnp.sum(fresh.astype(jnp.int32)))
        if pending_pre is not None and pending_pre.d_max > 0:
            due = (pending_pre.live
                   & (pending_pre.commit == round_idx)).astype(jnp.int32)
            lag = jnp.clip(round_idx - pending_pre.birth, 0, STALE_BINS - 1)
            commit_hist = commit_hist.at[lag.reshape(-1)].add(
                due.reshape(-1))
    else:
        commit_hist = jnp.asarray(commit_hist, jnp.int32)

    stale_reads = (jnp.sum((mask & (dl > 0)).astype(jnp.int32))
                   if dl is not None else jnp.zeros((), jnp.int32))

    dp = new_state.global_protos - prev_state.global_protos
    proto_drift = jnp.sqrt(jnp.sum(jnp.square(dp))).astype(jnp.float32)

    bl, bg = [], []
    for lp, gp, mp in zip(loss_parts, gnorm_parts, mask_parts):
        n_b = jnp.maximum(jnp.sum(mp.astype(jnp.float32)), 1.0)
        bl.append(jnp.sum(lp) / n_b)
        bg.append(jnp.sum(gp) / n_b)
    return RoundTelemetry(
        occupancy=occupancy, fill=fill, owner_diversity=owner_diversity,
        stale_hist=stale_hist, pending_depth=pending_depth,
        commit_hist=commit_hist, stale_reads=stale_reads,
        proto_drift=proto_drift,
        bucket_loss=jnp.stack(bl).astype(jnp.float32),
        bucket_grad_norm=jnp.stack(bg).astype(jnp.float32))


def make_host_telemetry_fn(n_clients: int):
    """Jitted round_telemetry for the SEQUENTIAL oracle: same relay-state
    reductions over its bit-equal ring, with the event-log quantities the
    oracle already tracks host-side (commit list lags, queue depth,
    download delays) passed in as small arrays. One trace per trainer."""

    def fn(prev_state, new_state, mask, mask_parts, loss_parts,
           gnorm_parts, commit_hist, pending_depth, dl):
        return round_telemetry(
            prev_state, new_state, n_clients, mask=mask,
            loss_parts=loss_parts, gnorm_parts=gnorm_parts,
            mask_parts=mask_parts, commit_hist=commit_hist,
            pending_depth=pending_depth, dl=dl)

    return jax.jit(fn)


def to_record(telem: RoundTelemetry) -> dict:
    """JSON-safe host dict of one round's telemetry: scalars become python
    int/float, vectors become lists — the `rec["telemetry"]` entry in both
    engines' round records and the JSONL sink payload. One device_get for
    the whole pytree (not one sync per leaf — this runs every round)."""
    host = jax.device_get(tuple(telem))
    out = {}
    for name, leaf in zip(RoundTelemetry._fields, host):
        a = np.asarray(leaf)
        out[name] = a.item() if a.ndim == 0 else a.tolist()
    return out
