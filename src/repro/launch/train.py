"""Distributed CoRS training step (the paper's technique on the mesh).

Client semantics on the mesh: parameters carry a leading `clients` axis
sharded over "pod"; `jax.vmap` over that axis gives every pod its own
client — per-client forward/backward/Adam with NO cross-pod gradient
traffic. The ONLY cross-pod collective in CoRS mode is the prototype
merge (mean of per-client per-class feature sums: an all-reduce of
(C, d'+1) floats), which is exactly the paper's communication claim, now
visible in the compiled HLO and measured by launch/roofline.py.

Baselines compile from the same builder:
  mode="fedavg": adds a per-step parameter all-reduce over clients (O(D)).
  mode="il"    : no cross-client collective at all.

Single-pod mesh: clients=1, same code (the vmap axis is size 1).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding
from repro.core import losses, prototypes
from repro.relay import history as relay_history, placement
from repro.relay.participation import bcast_mask, freeze_absent
from repro.models import encdec, lm
from repro.optim import adam_init, adam_update
from repro.types import CollabConfig, ModelConfig, ShapeConfig


class TrainState(NamedTuple):
    params: Any
    opt: Any
    proto: prototypes.ProtoState
    step: jax.Array


# ---------------------------------------------------------------------------
# per-client loss
# ---------------------------------------------------------------------------
def _lm_outputs(cfg: ModelConfig, params, batch):
    if cfg.is_encoder_decoder:
        enc = encdec.encode(params, cfg, batch["frames"])
        out = encdec.decode_forward(params, cfg, batch["tokens"], enc,
                                    mode="train")
    else:
        out = lm.forward(params, cfg, batch, mode="train")
    return out


def _head_w(cfg: ModelConfig, params):
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    return w


def make_loss_fn(cfg: ModelConfig, ccfg: CollabConfig, *,
                 disc_tokens: int = 8192):
    """Per-client loss: Eq. (6) adapted to LM classification (class = next
    token). L_disc uses K sampled negatives on a token subsample (LM-scale
    adaptation, DESIGN.md §3)."""

    def loss_fn(params, batch, proto_means, key):
        out = _lm_outputs(cfg, params, batch)
        with jax.named_scope("cors_loss"):
            return objective(params, batch, proto_means, key, out)

    def objective(params, batch, proto_means, key, out):
        feats, logits = out["features"], out["logits"]
        labels = batch["labels"]
        l_ce = losses.ce_loss(logits, labels)
        metrics = {"ce": l_ce}
        total = l_ce + 0.01 * out["aux"]
        if ccfg.mode == "cors":
            l_kd = losses.kd_loss(feats, proto_means, labels)
            d = feats.shape[-1]
            f_flat = feats.reshape(-1, d)
            y_flat = labels.reshape(-1)
            T = min(disc_tokens, f_flat.shape[0])
            k1, _ = jax.random.split(key)
            l_disc = losses.disc_loss_sampled(
                k1, f_flat[:T], proto_means, y_flat[:T],
                _head_w(cfg, params), None,
                num_negatives=min(ccfg.num_negatives or 1023,
                                  cfg.vocab_size - 1),
                student_logits=logits.reshape(-1, cfg.vocab_size)[:T])
            total = total + ccfg.lambda_kd * l_kd + ccfg.lambda_disc * l_disc
            metrics.update(kd=l_kd, disc=l_disc)
        metrics["total"] = total
        return total, (metrics, feats, labels)

    return loss_fn


# ---------------------------------------------------------------------------
# the train step
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, ccfg: CollabConfig, *,
                    n_clients: int = 1, lr: float = 1e-3,
                    disc_tokens: int = 8192, client_axis: str = "pod",
                    sync_in_step: bool = True):
    """sync_in_step=False is the paper-faithful cadence: Algorithm 1
    exchanges prototypes once per ROUND, not per step — the step then only
    accumulates local stats and `make_round_sync()` does the merge. The
    default True folds the exchange into every step (worst case; what the
    naive port of the algorithm to synchronous SPMD would do).

    Named scopes for the device trace: the model's blocks (`mlstm`,
    `slstm`, ...) and LM head (`head`), the objective (`cors_loss`), the
    optimizer (`adam`) and the per-class statistics (`proto_stats`)."""
    loss_fn = make_loss_fn(cfg, ccfg, disc_tokens=disc_tokens)
    C = cfg.vocab_size

    def client_step(params, opt, batch, proto_means, key):
        (_, (metrics, feats, labels)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, proto_means, key)
        with jax.named_scope("adam"):
            params, opt = adam_update(params, grads, opt, lr=lr)
        # per-class feature stats of this client's batch (paper's uplink)
        with jax.named_scope("proto_stats"):
            stats = prototypes.accumulate(
                prototypes.init_state(C, feats.shape[-1]),
                feats.reshape(-1, feats.shape[-1]), labels.reshape(-1))
        return params, opt, stats, metrics

    def train_step(state: TrainState, batch, key, participation=None):
        """`participation`: optional (n_clients,) bool mask (see
        repro.relay.participation) — absent clients' params/opt freeze for
        the step, their per-class stats are zero-weighted in the merge, and
        the FedAvg baseline averages over present clients only. None (the
        default) is full participation and traces the identical program as
        before the mask existed."""
        proto_means = prototypes.means(state.proto)
        keys = jax.random.split(key, n_clients)
        params, opt, stats, metrics = jax.vmap(
            client_step, in_axes=(0, 0, 0, None, 0))(
                state.params, state.opt, batch, proto_means, keys)
        if participation is not None:
            wf = participation.astype(jnp.float32)
            params = freeze_absent(participation, params, state.params)
            opt = freeze_absent(participation, opt, state.opt)
            stats = prototypes.ProtoState(stats.sum * wf[:, None, None],
                                          stats.count * wf[:, None])
        if ccfg.mode == "fedavg":
            # baseline: per-step O(D) weight averaging across clients
            if participation is None:
                params = jax.tree.map(
                    lambda p: jnp.broadcast_to(jnp.mean(p, axis=0,
                                                        dtype=jnp.float32)
                                               .astype(p.dtype), p.shape),
                    params)
            else:
                n_eff = jnp.maximum(jnp.sum(wf), 1.0)

                def avg(p):
                    s = jnp.sum(p.astype(jnp.float32) * bcast_mask(wf, p),
                                axis=0) / n_eff
                    return jnp.broadcast_to(s.astype(p.dtype), p.shape)
                params = freeze_absent(participation,
                                       jax.tree.map(avg, params), params)
        if ccfg.mode in ("cors", "fd") and sync_in_step:
            # the paper's exchange: inter-client merge of per-class stats
            with jax.named_scope("proto_stats"):
                merged = prototypes.ProtoState(
                    jnp.sum(stats.sum, axis=0), jnp.sum(stats.count, axis=0))
                decay = ccfg.proto_momentum or 1.0
                proto = prototypes.ProtoState(
                    decay * state.proto.sum + merged.sum,
                    decay * state.proto.count + merged.count)
        else:
            proto = state.proto
        if participation is None:
            metrics = jax.tree.map(lambda m: jnp.mean(m), metrics)
        else:
            # mean over PRESENT clients only — absent clients' updates were
            # discarded above, so their losses must not pollute the record
            metrics = jax.tree.map(
                lambda m: jnp.sum(m * wf) / jnp.maximum(jnp.sum(wf), 1.0),
                metrics)
        return TrainState(params, opt, proto, state.step + 1), metrics

    return train_step


def make_round_sync(ccfg: CollabConfig):
    """Per-round prototype exchange (paper Algorithm 1 cadence): merge the
    clients' accumulated stats into the shared ProtoState. Run once per
    round when the step was built with sync_in_step=False.

    Accepts one stats pytree per client-architecture BUCKET (each with its
    own leading client axis, as in core/vec_collab.py's client buckets):
    the proto state is the only thing heterogeneous buckets share, so a
    mixed fleet at LM scale is N_buckets `train_step`s + ONE round_sync
    over all their stats. A single homogeneous stack is the 1-bucket case.
    For straggler fleets whose stats commit LATE, use
    `make_async_round_sync` instead — it carries the clock state."""
    def round_sync(state: TrainState,
                   *bucket_stats: prototypes.ProtoState):
        merged = prototypes.merge(*[
            prototypes.ProtoState(jnp.sum(s.sum, axis=0),
                                  jnp.sum(s.count, axis=0))
            for s in bucket_stats])
        decay = ccfg.proto_momentum or 1.0
        return state._replace(proto=prototypes.ProtoState(
            decay * state.proto.sum + merged.sum,
            decay * state.proto.count + merged.count))
    return round_sync


def make_async_round_sync(ccfg: CollabConfig, d_max: int):
    """`make_round_sync` for a bounded-delay fleet (repro.sim clocks): a
    client's round-r stats with commit delay d join the SHARED prototype
    state in round r+d, not round r — the LM-scale counterpart of the
    collaborative engines' event-ordered relay (relay/events.py). The
    prototype merge is a sum, so late contributions are order-free; what
    must be carried across rounds is the clock state: a fixed-shape
    pending ProtoState of d_max future slots (slot j = stats due j+1
    rounds from now).

    Returns (init_pending, round_sync):
      init_pending(C, d')               -> pending ProtoState (d_max, C, ·)
      round_sync(state, pending, delays_and_stats...) -> (state, pending)
        where the varargs alternate (delays_b, stats_b) per bucket:
        delays_b (k_b,) int32 commit delays, stats_b the bucket's stacked
        per-client ProtoState. Pure/jittable; delays are traced, so
        straggler patterns never retrace. d_max = 0 degenerates to
        `make_round_sync` exactly (empty pending, everything commits now).
    """
    assert d_max >= 0, d_max

    def init_pending(C: int, d_feature: int) -> prototypes.ProtoState:
        return prototypes.ProtoState(
            jnp.zeros((d_max, C, d_feature), jnp.float32),
            jnp.zeros((d_max, C), jnp.float32))

    def round_sync(state: TrainState, pending: prototypes.ProtoState,
                   *delays_and_stats):
        assert len(delays_and_stats) % 2 == 0, \
            "pass (delays, stats) per bucket"
        # scatter every client's stats into its commit-delay slot:
        # sums[j] = sum of stats committing j rounds from now (j=0: now)
        C, d = state.proto.sum.shape
        sums = prototypes.ProtoState(jnp.zeros((d_max + 1, C, d)),
                                     jnp.zeros((d_max + 1, C)))
        for b in range(0, len(delays_and_stats), 2):
            delays = delays_and_stats[b].astype(jnp.int32)
            stats = delays_and_stats[b + 1]
            sums = prototypes.ProtoState(
                sums.sum.at[delays].add(stats.sum, mode="drop"),
                sums.count.at[delays].add(stats.count, mode="drop"))
        commit = prototypes.ProtoState(sums.sum[0], sums.count[0])
        if d_max > 0:
            commit = prototypes.ProtoState(commit.sum + pending.sum[0],
                                           commit.count + pending.count[0])
            # new_pending[j] (due j+1 rounds from now) = what was due j+2
            # rounds ago-relative (old pending[j+1]) + fresh stats with
            # delay j+1 (sums[j+1])
            shift = lambda a, fresh: jnp.concatenate(
                [a[1:], jnp.zeros_like(a[:1])]) + fresh[1:]
            pending = prototypes.ProtoState(
                shift(pending.sum, sums.sum),
                shift(pending.count, sums.count))
        decay = ccfg.proto_momentum or 1.0
        state = state._replace(proto=prototypes.ProtoState(
            decay * state.proto.sum + commit.sum,
            decay * state.proto.count + commit.count))
        return state, pending

    return init_pending, round_sync


def make_download_lag_round_sync(ccfg: CollabConfig, h_max: int):
    """`make_round_sync` for a fleet whose clients READ stale prototypes
    (repro.sim download clocks): the LM-scale counterpart of the relay
    history ring (relay/history.py). The merge itself is unchanged — what
    download lag needs is a bounded ring of the last `h_max` POST-MERGE
    ProtoStates, so a client syncing in round t with download delay d can
    be served the global prototypes as of round `t − d` instead of the
    fresh ones.

    Returns (init_history, round_sync, read_at):
      init_history(C, d')        -> History ring seeded with the empty
                                    ProtoState in every slot
      round_sync(state, hist, *bucket_stats) -> (state, hist): the plain
                                    merge, then push the post-merge proto
      read_at(hist, delays)      -> ProtoState(s) as of `delays` rounds
                                    ago; `delays` may be a scalar or — via
                                    vmap — a per-client vector, traced
                                    either way
    Pure/jittable below init. `h_max = 1` retains only the current
    post-merge proto, so delay-0 reads are bit-identical to
    `make_round_sync` alone."""
    assert h_max >= 1, h_max
    sync = make_round_sync(ccfg)

    def init_history(C: int, d_feature: int) -> relay_history.History:
        return relay_history.init(prototypes.init_state(C, d_feature),
                                  h_max)

    def round_sync(state: TrainState, hist: relay_history.History,
                   *bucket_stats: prototypes.ProtoState):
        state = sync(state, *bucket_stats)
        return state, relay_history.push(hist, state.proto)

    def read_at(hist: relay_history.History, delays) -> prototypes.ProtoState:
        if hasattr(delays, "ndim") and getattr(delays, "ndim", 0) > 0:
            return jax.vmap(lambda d: relay_history.read_at(hist, d))(delays)
        return relay_history.read_at(hist, delays)

    return init_history, round_sync, read_at


def proto_round_telemetry(prev: prototypes.ProtoState,
                          new: prototypes.ProtoState) -> Dict[str, Any]:
    """One round's prototype-level observability for the LM-scale path,
    which shares no relay ring with the collaborative engines and so gets
    the ProtoState-reducible subset of repro.obs's RoundTelemetry: the
    drift of the class means across the round's merge, the total absorbed
    stat mass, and class coverage. Host-side (a few (C, d') reductions per
    ROUND, not per step); JSON-safe for the same JSONL sink/report."""
    dm = prototypes.means(new) - prototypes.means(prev)
    return {
        "proto_drift": float(jnp.sqrt(jnp.sum(jnp.square(dm)))),
        "proto_mass": float(jnp.sum(new.count)),
        "classes_seen": int(jnp.sum(new.count > 0)),
    }


# ---------------------------------------------------------------------------
# state/batch construction (real arrays or ShapeDtypeStructs)
# ---------------------------------------------------------------------------
def init_state_shapes(cfg: ModelConfig, n_clients: int = 1):
    """abstract TrainState via eval_shape (no allocation — dry-run path)."""
    def init():
        key = jax.random.PRNGKey(0)
        if cfg.is_encoder_decoder:
            p = encdec.init_encdec(key, cfg)
        else:
            p = lm.init_lm(key, cfg)
        opt = adam_init(p)
        bc = lambda a: jnp.broadcast_to(a[None], (n_clients,) + a.shape)
        return TrainState(jax.tree.map(bc, p), jax.tree.map(bc, opt),
                          prototypes.init_state(cfg.vocab_size,
                                                cfg.d_feature),
                          jnp.zeros((), jnp.int32))
    return jax.eval_shape(init)


def init_state(cfg: ModelConfig, key, n_clients: int = 1) -> TrainState:
    if cfg.is_encoder_decoder:
        ps = [encdec.init_encdec(k, cfg)
              for k in jax.random.split(key, n_clients)]
    else:
        ps = [lm.init_lm(k, cfg) for k in jax.random.split(key, n_clients)]
    p = jax.tree.map(lambda *xs: jnp.stack(xs), *ps)
    opt = jax.tree.map(lambda *xs: jnp.stack(xs),
                       *[adam_init(pp) for pp in ps])
    return TrainState(p, opt,
                      prototypes.init_state(cfg.vocab_size, cfg.d_feature),
                      jnp.zeros((), jnp.int32))


def train_batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                      n_clients: int = 1):
    """ShapeDtypeStructs for one global train batch."""
    Bc = shape.global_batch // n_clients
    S = shape.seq_len
    N = n_clients
    sds = jax.ShapeDtypeStruct
    batch: Dict[str, Any] = {
        "labels": sds((N, Bc, S), jnp.int32)}
    if cfg.input_kind == "tokens":
        batch["tokens"] = sds((N, Bc, S), jnp.int32)
    else:
        batch["embeddings"] = sds((N, Bc, S, cfg.d_model), jnp.dtype(cfg.dtype))
        if cfg.rope_kind == "mrope":
            batch["positions"] = sds((N, Bc, S, 3), jnp.int32)
    if cfg.is_encoder_decoder:
        batch["tokens"] = sds((N, Bc, S), jnp.int32)
        batch["frames"] = sds((N, Bc, cfg.encoder_seq, cfg.d_model),
                              jnp.dtype(cfg.dtype))
    return batch


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------
def _client_lead(mesh, n_clients: int):
    return "pod" if (n_clients > 1 and "pod" in mesh.axis_names) else None


def state_shardings(state_shapes, cfg: ModelConfig, mesh, n_clients: int = 1,
                    *, strategy: str = "tp"):
    """strategy:
      "tp"      (default) model axis = tensor parallel
      "dp_only" params replicated; the model axis becomes extra data
                parallelism — no per-layer activation all-reduces
      "zero1"   dp_only + Adam moments sharded over the flattened
                (data, model) axes (ZeRO-1: replicated-params memory without
                replicated-optimizer memory)"""
    lead = _client_lead(mesh, n_clients)
    flat_dp = sharding.dp_size(mesh) * sharding.axis_size(mesh, "model")

    def param_leaf(path, leaf):
        if strategy in ("dp_only", "zero1"):
            inner = [None] * (len(leaf.shape) - 1)
        else:
            inner = sharding.param_spec(path, leaf.shape[1:], mesh,
                                        fsdp=cfg.fsdp)
        return NamedSharding(mesh, P(lead, *inner))

    def opt_leaf(path, leaf):
        if strategy == "zero1":
            dims = leaf.shape[1:]
            spec = [None] * len(dims)
            for i, dsz in enumerate(dims):
                if dsz % flat_dp == 0 and dsz >= flat_dp:
                    axes = tuple(a for a in ("data", "model")
                                 if a in mesh.axis_names)
                    spec[i] = axes if len(axes) > 1 else axes[0]
                    break
            return NamedSharding(mesh, P(lead, *spec))
        return param_leaf(path, leaf)

    def spec_tree(tree, leaf_fn):
        flat = jax.tree_util.tree_flatten_with_path(tree)
        leaves = []
        for kp, leaf in flat[0]:
            path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                            for k in kp)
            leaves.append(leaf_fn(path, leaf))
        return jax.tree_util.tree_unflatten(flat[1], leaves)

    params_sh = spec_tree(state_shapes.params, param_leaf)
    opt_sh = type(state_shapes.opt)(
        NamedSharding(mesh, P(lead)),
        spec_tree(state_shapes.opt.m, opt_leaf),
        spec_tree(state_shapes.opt.v, opt_leaf))
    tp = sharding.axis_size(mesh, "model")
    shard_v = strategy != "dp_only" and cfg.vocab_size % tp == 0
    proto_spec = P("model", None) if shard_v else P(None, None)
    cnt_spec = P("model") if shard_v else P(None)
    proto_sh = prototypes.ProtoState(NamedSharding(mesh, proto_spec),
                                     NamedSharding(mesh, cnt_spec))
    return TrainState(params_sh, opt_sh, proto_sh,
                      NamedSharding(mesh, P()))


def round_sync_shardings(mesh, n_clients: int = 1):
    """Placement-resolved shardings for the per-round prototype exchange
    (`make_round_sync` / `make_async_round_sync` /
    `make_download_lag_round_sync`), via the SAME declarations the
    collaborative engines use (repro.relay.placement), resolved against
    this path's "pod" client axis:

      - the shared / pending / history ProtoStates are REPLICATED — the
        pending buffer here is delay-slot-indexed (not client-indexed,
        unlike relay/events.py) and the ring snapshots a replicated state,
        so there is nothing to shard;
      - per-client bucket stats (leading client axis) are CLIENT_SHARDED
        over "pod" — their sum inside round_sync is then the round's one
        CLIENT_SHARDED -> REPLICATED exchange, exactly like
        `placement.exchange` in core/vec_collab.py.

    Returns (replicated, stats) NamedShardings for jit in/out_shardings;
    on a single-client or pod-less mesh both are replicated (the identity
    placement)."""
    lead = _client_lead(mesh, n_clients)
    rep = placement.resolve(placement.REPLICATED, mesh)
    stats = (placement.resolve(placement.CLIENT_SHARDED, mesh, axis=lead)
             if lead else rep)
    return rep, stats


def batch_shardings(batch_shapes, mesh, n_clients: int = 1, *,
                    strategy: str = "tp"):
    lead = _client_lead(mesh, n_clients)
    baxes = ("data", "model") if strategy in ("dp_only", "zero1") else "data"

    def leaf(l):
        # (N, Bc, ...) -> client over pod, batch over data (+model: dp_only)
        rest = (None,) * (len(l.shape) - 2)
        return NamedSharding(mesh, P(lead, baxes, *rest))
    return jax.tree.map(leaf, batch_shapes)
