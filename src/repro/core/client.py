"""LOCALUPDATE (paper Algorithm 2), model-agnostic.

A client is (apply, head): `apply(params, x) -> (features, logits)` and
`head(params) -> (W, b)` exposing the linear classifier τ_u used by the
discriminator. Works for the paper's CNNs and for LM adapters alike.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import losses, prototypes
from repro.optim import adam_update
from repro.types import CollabConfig, TrainConfig


@dataclass(frozen=True)
class ClientSpec:
    apply: Callable  # (params, x) -> (features (B,d'), logits (B,C))
    head: Callable   # params -> (W (d',C), b (C,) | None)


def bucket_key(spec: ClientSpec, params) -> Tuple:
    """Stackability key of one client: clients can share a vmapped round
    step iff they share BOTH the ClientSpec (same apply/head callables) and
    the exact param pytree structure + leaf shapes/dtypes. Two clients with
    the same spec but e.g. different hidden widths land in different
    buckets — their param stacks cannot be concatenated."""
    leaves, treedef = jax.tree.flatten(params)
    return (spec, treedef,
            tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves))


def bucketize(specs: Sequence[ClientSpec],
              params_list: Sequence) -> List[Tuple[ClientSpec, List[int]]]:
    """Group clients into stackable buckets: (spec, client-id list) pairs in
    FIRST-APPEARANCE order, client-id order within a bucket.

    This ordering is load-bearing: it is the order in which the vectorized
    engine's round step (core/vec_collab.py) appends the buckets' uploads
    to the shared relay, and the sequential oracle (core/collab.py) uploads
    in the same order so the two engines evolve identical ring state. For a
    homogeneous fleet there is one bucket and the order degenerates to plain
    client-id order — bit-compatible with the pre-bucketing engines.

    Distinct-but-identical ClientSpec objects (e.g. two lambdas with the
    same body) intentionally hash apart: callers that want clients stacked
    together must share ONE spec object across them, which is also what
    makes the per-spec jit caches effective."""
    assert len(specs) == len(params_list)
    buckets: Dict[Tuple, List[int]] = {}
    order: List[Tuple] = []
    for i, (s, p) in enumerate(zip(specs, params_list)):
        k = bucket_key(s, p)
        if k not in buckets:
            buckets[k] = []
            order.append(k)
        buckets[k].append(i)
    return [(k[0], buckets[k]) for k in order]


def loss_fn(spec: ClientSpec, params, batch, teacher, ccfg: CollabConfig,
            key=None):
    """One mini-batch of Algorithm 2's inner loop.

    teacher: dict(global_protos (C,d'), valid_g (C,), obs (M,C,d'),
    valid_o (C,), obs_pick (int32 scalar: which m to use)) — or None entries
    for IL/CL/FD modes.
    """
    x, y = batch["x"], batch["y"]
    feats, logits = spec.apply(params, x)
    l_ce = losses.ce_loss(logits, y)
    metrics = {"ce": l_ce}
    total = l_ce
    if ccfg.mode == "cors":
        w, b = spec.head(params)
        l_kd = losses.kd_loss(feats, teacher["global_protos"], y,
                              valid=teacher["valid_g"])
        m = teacher.get("obs_pick", 0)
        obs_m = teacher["obs"][m]                            # (C, d')
        l_disc = losses.disc_loss(feats, obs_m, y, w, b,
                                  valid=teacher["valid_o"],
                                  student_logits=logits)
        total = total + ccfg.lambda_kd * l_kd + ccfg.lambda_disc * l_disc
        metrics.update(kd=l_kd, disc=l_disc,
                       mi_bound=losses.mi_lower_bound(
                           l_disc, ccfg.num_classes - 1))
    elif ccfg.mode == "fd":
        l_fd = losses.fd_loss(logits, teacher["mean_logits"], y,
                              valid=teacher["valid_g"])
        total = total + ccfg.lambda_kd * l_fd
        metrics["fd"] = l_fd
    metrics["total"] = total
    return total, metrics


def empty_teacher(ccfg: CollabConfig) -> Dict:
    """A no-op teacher pytree (IL/CL/FedAvg modes, round-0 defaults).

    Same keys/shapes as `server.sample_teacher` so the jitted update traces
    once regardless of mode."""
    C, d = ccfg.num_classes, ccfg.d_feature
    return {"global_protos": jnp.zeros((C, d), jnp.float32),
            "valid_g": jnp.zeros((C,), bool),
            "obs": jnp.zeros((max(1, ccfg.m_down), C, d), jnp.float32),
            "valid_o": jnp.zeros((C,), bool),
            "obs_pick": jnp.asarray(0, jnp.int32),
            "mean_logits": jnp.zeros((C, C), jnp.float32)}


def make_local_update_fn(spec: ClientSpec, ccfg: CollabConfig,
                         tcfg: TrainConfig):
    """Un-jitted fn(params, opt_state, batches, teacher, key) ->
    (params, opt_state, metrics). `batches` is a stacked pytree
    (n_batches, bs, ...) scanned E local epochs (Algorithm 2).

    The sequential trainer jits this per client (`make_local_update`); the
    vectorized engine vmaps it over a stacked client axis inside one jitted
    round step (core/vec_collab.py)."""

    grad_fn = jax.value_and_grad(
        lambda p, b, t, k: loss_fn(spec, p, b, t, ccfg, k), has_aux=True)

    def run(params, opt_state, batches, teacher, key):
        n = jax.tree.leaves(batches)[0].shape[0]
        keys = jax.random.split(key, n * tcfg.local_epochs).reshape(
            tcfg.local_epochs, n, 2)

        def step(carry, batch_and_key):
            p, o = carry
            batch, k = batch_and_key
            (_, metrics), grads = grad_fn(p, batch, teacher, k)
            # global grad norm, from the grads the step already computed —
            # the per-bucket health signal the telemetry layer aggregates
            metrics["grad_norm"] = jnp.sqrt(sum(
                jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
            p, o = adam_update(p, grads, o, lr=tcfg.learning_rate,
                               b1=tcfg.beta1, b2=tcfg.beta2, eps=tcfg.eps)
            return (p, o), metrics

        def epoch(carry, ek):
            return jax.lax.scan(step, carry, (batches, ek))

        (params, opt_state), metrics = jax.lax.scan(
            epoch, (params, opt_state), keys)
        metrics = jax.tree.map(lambda m: m[-1, -1], metrics)  # last batch
        return params, opt_state, metrics

    return run


def make_local_update(spec: ClientSpec, ccfg: CollabConfig,
                      tcfg: TrainConfig):
    """Jitted `make_local_update_fn` (the per-client sequential path)."""
    return jax.jit(make_local_update_fn(spec, ccfg, tcfg))


def zero_metrics(ccfg: CollabConfig) -> Dict:
    """The metrics record of a client that SKIPPED the round (partial
    participation): all-zero floats with exactly the keys `loss_fn` emits
    for this mode, so per-round records keep one entry per client."""
    m = {"ce": 0.0, "total": 0.0, "grad_norm": 0.0}
    if ccfg.mode == "cors":
        m.update(kd=0.0, disc=0.0, mi_bound=0.0)
    elif ccfg.mode == "fd":
        m["fd"] = 0.0
    return m


def compute_uploads(spec: ClientSpec, params, data_x, data_y,
                    ccfg: CollabConfig, key):
    """End-of-round uploads (Algorithm 1): the client's per-class averaged
    representations (for t̄) and M_↑ observations (for the L_disc buffers).
    For FD mode, per-class mean logits instead."""
    feats, logits = spec.apply(params, data_x)
    state = prototypes.accumulate(
        prototypes.init_state(ccfg.num_classes, feats.shape[-1]),
        feats, data_y)
    obs, valid = prototypes.observations(key, feats, data_y,
                                         ccfg.num_classes, ccfg.n_avg,
                                         ccfg.m_up)
    out = {"proto": state, "obs": obs, "valid": valid}
    if ccfg.mode == "fd":
        lstate = prototypes.accumulate(
            prototypes.init_state(ccfg.num_classes, logits.shape[-1]),
            logits, data_y)
        out["logit_proto"] = lstate
    return out


def make_compute_uploads(spec: ClientSpec, ccfg: CollabConfig):
    """Jitted `compute_uploads` with spec/ccfg closed over (they are static
    config, not data). The sequential trainer caches ONE of these per
    distinct ClientSpec: the eager version cost ~20 ms/client/round of pure
    dispatch, dominant at small per-client data; jitted it traces once per
    data shape and never again (tests assert the cache stays at one entry
    across rounds)."""
    return jax.jit(lambda params, x, y, key: compute_uploads(
        spec, params, x, y, ccfg, key))
