"""LeNet5 clients in a CoRS fleet, driven round by round.

The timed step is the program's `VectorizedCollabTrainer.run_round`: every
participant's local update (Adam, batch 32, E=1), its uploads to the flat
relay, the relay's merge, and the per-round eval of every client on the
shared test set. Set-up builds the fleet from the seed and runs its first
CHECK_ROUNDS rounds through that same call; those rounds compile every
program the window uses, and their outputs are what `check` compares.

The reference below is a plain jax.numpy implementation of the same
rounds, written from the paper's Algorithms 1 and 2 and the relay's
documented semantics, computing in float32 at the highest matmul
precision. It imports nothing of the program and starts from the weights
and data this module made. It draws its randomness as the program's
documented key schedule does (one relay, one update and one upload key per
client per round), so both sides sample the same teachers and observations.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchlib import compare, gen

LEAVES = ("conv1", "b1", "conv2", "b2", "fc1", "fb1", "fc2", "fb2",
          "head_w", "head_b")
CHECK_ROUNDS = 3
CLIENT_BLOCK = 64
LOSS_TERMS = ("ce", "kd", "disc", "total")
HIGHEST = lax.Precision.HIGHEST
EMPTY_OWNER, SEED_OWNER = -2, -1


# ---------------------------------------------------------------------------
# sizes, weights, operations
# ---------------------------------------------------------------------------
def leaf_shapes(cfg: dict) -> Dict[str, tuple]:
    k, c0, c1, c2 = cfg["kernel"], cfg["channels"], cfg["conv1"], cfg["conv2"]
    s2 = ((cfg["image"] - k + 1) // 2 - k + 1) // 2
    flat = c2 * s2 * s2
    return {"conv1": (k, k, c0, c1), "b1": (c1,),
            "conv2": (k, k, c1, c2), "b2": (c2,),
            "fc1": (flat, cfg["fc1"]), "fb1": (cfg["fc1"],),
            "fc2": (cfg["fc1"], cfg["d_feature"]), "fb2": (cfg["d_feature"],),
            "head_w": (cfg["d_feature"], cfg["num_classes"]),
            "head_b": (cfg["num_classes"],)}


def init_params(key, cfg: dict, n: int):
    """Stacked (n, ...) float32 weights of n clients, in one jitted call:
    He-normal convs, 1/sqrt(fan_in) dense layers, zero biases."""
    shapes = leaf_shapes(cfg)

    def init(key):
        ks = jax.random.split(key, len(LEAVES))
        out = {}
        for k, name in zip(ks, LEAVES):
            shp = (n,) + shapes[name]
            if name.startswith("conv"):
                fan_in = shapes[name][0] * shapes[name][1] * shapes[name][2]
                out[name] = jax.random.normal(k, shp) * math.sqrt(2.0 / fan_in)
            elif len(shapes[name]) == 2:
                out[name] = jax.random.normal(k, shp) / math.sqrt(
                    shapes[name][0])
            else:
                out[name] = jnp.zeros(shp, jnp.float32)
        return out

    return jax.jit(init)(key)


def forward_flops(cfg: dict) -> int:
    """Multiply-adds x 2 of one image's forward pass: the two valid 5x5
    convolutions, the two dense layers and the head (pooling, biases and
    activations not counted)."""
    k, c0, c1, c2 = cfg["kernel"], cfg["channels"], cfg["conv1"], cfg["conv2"]
    o1 = cfg["image"] - k + 1
    o2 = o1 // 2 - k + 1
    flat = c2 * (o2 // 2) ** 2
    return 2 * (o1 * o1 * c1 * k * k * c0 + o2 * o2 * c2 * k * k * c1
                + flat * cfg["fc1"] + cfg["fc1"] * cfg["d_feature"]
                + cfg["d_feature"] * cfg["num_classes"])


def train_flops_per_sample(cfg: dict) -> int:
    """Forward and backward of one training sample: 3 x forward."""
    return 3 * forward_flops(cfg)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
class Reference:
    """CoRS rounds of a LeNet5 fleet in plain jax.numpy.

    `q` rounds every matmul operand and `store` every parameter after each
    update (by default: float32 at the highest precision; `control` lowers
    both). `half_batch` leaves out the second half of every local batch
    and takes the mean over the rest (one of the faults the check must
    catch)."""

    def __init__(self, cfg: dict, q=compare.identity,
                 store=compare.identity, half_batch: bool = False):
        self.cfg, self.q, self.store, self.half = cfg, q, store, half_batch
        self._round = jax.jit(self._round_fn)
        self._acc = jax.jit(jax.vmap(self._acc_fn, in_axes=(0, None, None)))

    # -- model ------------------------------------------------------------
    def _mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def _conv_relu(self, x, w, b):
        # a valid 5x5 convolution as one matmul over the k*k shifted views
        # (im2col), which compiles far faster than a convolution vmapped
        # over clients at the highest precision
        k, _, C, O = w.shape
        Ho, Wo = x.shape[1] - k + 1, x.shape[2] - k + 1
        cols = jnp.stack([x[:, i:i + Ho, j:j + Wo, :] for i in range(k)
                          for j in range(k)], axis=3)
        y = jnp.einsum("bhwpc,pco->bhwo", self.q(cols),
                       self.q(w.reshape(k * k, C, O)), precision=HIGHEST)
        return jnp.maximum(y + b, 0.0)

    @staticmethod
    def _pool(x):
        B, H, W, C = x.shape
        return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))

    def features(self, p, x):
        h = self._pool(self._conv_relu(x, p["conv1"], p["b1"]))
        h = self._pool(self._conv_relu(h, p["conv2"], p["b2"]))
        h = h.reshape(h.shape[0], -1)
        h = jnp.maximum(self._mm(h, p["fc1"]) + p["fb1"], 0.0)
        return jnp.tanh(self._mm(h, p["fc2"]) + p["fb2"])

    def logits(self, p, s):
        return self._mm(s, p["head_w"]) + p["head_b"]

    # -- Algorithm 2: the local objective ---------------------------------
    def _loss(self, p, x, y, t):
        cfg, C = self.cfg, self.cfg["num_classes"]
        if self.half:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        s = self.features(p, x)
        z = self.logits(p, s)
        ce = -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(z), y[:, None],
                                           axis=1))
        w = t["valid_g"].astype(jnp.float32)[y]
        d2 = jnp.mean((s - t["global_protos"][y]) ** 2, axis=-1)
        kd = jnp.sum(d2 * w) / jnp.maximum(jnp.sum(w), 1.0)
        zt = self.logits(p, t["obs"])
        h = jnp.clip(self._mm(jax.nn.softmax(z), jax.nn.softmax(zt).T),
                     1e-7, 1.0 - 1e-7)
        pos = jax.nn.one_hot(y, C)
        v = t["valid_o"].astype(jnp.float32)
        per_pair = -(pos * jnp.log(h) + (1 - pos) * jnp.log1p(-h)) * v[None]
        sv = v[y]
        disc = jnp.sum(per_pair * sv[:, None]) / jnp.maximum(jnp.sum(sv), 1.0)
        total = ce + cfg["lambda_kd"] * kd + cfg["lambda_disc"] * disc
        return total, jnp.stack([ce, kd, disc, total])

    def _local_update(self, p, m, v, t, bx, by, teacher):
        cfg = self.cfg
        b1, b2, lr, eps = (cfg["beta1"], cfg["beta2"], cfg["learning_rate"],
                           cfg["eps"])
        grad = jax.grad(self._loss, has_aux=True)

        def step(carry, batch):
            p, m, v, t = carry
            g, terms = grad(p, batch[0], batch[1], teacher)
            t = t + 1
            m = {k: b1 * m[k] + (1 - b1) * g[k] for k in p}
            v = {k: b2 * v[k] + (1 - b2) * g[k] ** 2 for k in p}
            tf = t.astype(jnp.float32)
            p = {k: self.store(p[k] - lr * (m[k] / (1 - b1 ** tf))
                               / (jnp.sqrt(v[k] / (1 - b2 ** tf)) + eps))
                 for k in p}
            return (p, m, v, t), terms

        carry = (p, m, v, t)
        for _ in range(cfg["local_epochs"]):
            carry, terms = lax.scan(step, carry, (bx, by))
        return carry + (terms[-1],)

    # -- Algorithm 1: the relay -------------------------------------------
    @staticmethod
    def _teacher(ring, cid, key):
        usable = ring["owner"] != EMPTY_OWNER
        others = usable & (ring["owner"] != cid)
        pool = jnp.where(jnp.any(others), others, usable)
        k_sample, _ = jax.random.split(key)
        idx = jax.random.categorical(k_sample, jnp.where(pool, 0.0, -jnp.inf),
                                     shape=(1,))
        return {"global_protos": ring["global_protos"],
                "valid_g": ring["valid_g"],
                "obs": ring["obs"][idx[0]],
                "valid_o": jnp.all(ring["valid"][idx], axis=0)}

    def _uploads(self, p, x, y, key):
        C, n_avg = self.cfg["num_classes"], self.cfg["n_avg"]
        s = self.features(p, x)
        psum = jax.ops.segment_sum(s, y, num_segments=C)
        pcnt = jax.ops.segment_sum(jnp.ones_like(y, jnp.float32), y,
                                   num_segments=C)
        # one observation per class: the mean of n_avg same-class samples
        # picked by descending random priority
        prio = jax.random.uniform(jax.random.split(key, 1)[0], (x.shape[0],))
        order = jnp.argsort(-prio)
        ys, ss = y[order], s[order]
        onehot = (ys[:, None] == jnp.arange(C)[None]).astype(jnp.float32)
        rank = jnp.cumsum(onehot, axis=0) * onehot
        keep = ((rank > 0) & (rank <= n_avg)).astype(jnp.float32)
        osum = jnp.sum(keep[:, :, None] * ss[:, None, :], axis=0)
        obs = osum / jnp.maximum(jnp.sum(keep, axis=0), 1.0)[:, None]
        return psum, pcnt, obs, pcnt > 0

    def _round_fn(self, P, M, V, T, bx, by, dx, dy, ring, ids, mask,
                  relay_ks, upl_ks):
        """One round over the participant block (k, ...)."""
        teachers = jax.vmap(self._teacher, in_axes=(None, 0, 0))(
            ring, ids, relay_ks)

        def client(args):
            p, m, v, t, x, y, data_x, data_y, teacher, key = args
            p, m, v, t, terms = self._local_update(p, m, v, t, x, y, teacher)
            return (p, m, v, t, terms) + self._uploads(p, data_x, data_y, key)

        # clients in blocks, so that the reference fits beside what is left
        (P, M, V, T, terms, psum, pcnt, obs, valid) = lax.map(
            client, (P, M, V, T, bx, by, dx, dy, teachers, upl_ks),
            batch_size=CLIENT_BLOCK)
        cap = ring["obs"].shape[0]
        w = mask.astype(jnp.int32)
        slot = jnp.where(mask, (ring["ptr"] + jnp.cumsum(w) - 1) % cap, cap)
        total_sum = jnp.sum(psum * w[:, None, None], axis=0)
        total_cnt = jnp.sum(pcnt * w[:, None], axis=0)
        ring = dict(ring)
        ring["obs"] = ring["obs"].at[slot].set(obs, mode="drop")
        ring["valid"] = ring["valid"].at[slot].set(valid, mode="drop")
        ring["owner"] = ring["owner"].at[slot].set(ids, mode="drop")
        ring["stamp"] = ring["stamp"].at[slot].set(
            jnp.full(ids.shape, ring["clock"]), mode="drop")
        ring["ptr"] = (ring["ptr"] + jnp.sum(w)) % cap
        ring["global_protos"] = total_sum / jnp.maximum(total_cnt, 1.0)[:, None]
        ring["valid_g"] = total_cnt > 0
        ring["clock"] = ring["clock"] + 1
        return P, M, V, T, terms, ring

    def _acc_fn(self, p, tx, ty):
        z = self.logits(p, self.features(p, tx))
        return jnp.mean((jnp.argmax(z, axis=-1) == ty).astype(jnp.float32))

    def accuracies(self, P, tx, ty, block: int = 128):
        n = jax.tree.leaves(P)[0].shape[0]
        out = [np.asarray(self._acc(jax.tree.map(lambda a: a[i:i + block], P),
                                    tx, ty)) for i in range(0, n, block)]
        return np.concatenate(out)

    # -- a whole check run --------------------------------------------------
    def run(self, fleet: "Data", rounds: int = CHECK_ROUNDS) -> dict:
        cfg = self.cfg
        N = fleet.x.shape[0]
        P = {k: jnp.asarray(v) for k, v in fleet.params0.items()}
        P0 = P
        M = {k: jnp.zeros_like(v) for k, v in P.items()}
        V = {k: jnp.zeros_like(v) for k, v in P.items()}
        T = jnp.zeros((N,), jnp.int32)
        ring = {k: jnp.asarray(v) for k, v in fleet.ring0().items()}
        bs = cfg["batch_size"]
        nb = fleet.x.shape[1] // bs
        bx = jnp.asarray(fleet.x[:, :nb * bs].reshape(
            N, nb, bs, *fleet.x.shape[2:]))
        by = jnp.asarray(fleet.y[:, :nb * bs].reshape(N, nb, bs))
        dx, dy = jnp.asarray(fleet.x), jnp.asarray(fleet.y)
        tx, ty = jnp.asarray(fleet.tx), jnp.asarray(fleet.ty)
        key = jax.random.PRNGKey(fleet.trainer_seed)
        losses, accs = [], []
        m_norm = None
        for r in range(rounds):
            key, kr, _, ko = jax.random.split(key, 4)
            relay_ks, upl_ks = jax.random.split(kr, N), jax.random.split(ko, N)
            mask = fleet.mask(r)
            idx = np.nonzero(mask)[0]
            take = lambda t: jax.tree.map(lambda a: a[idx], t)
            out = self._round(take(P), take(M), take(V), T[idx], bx[idx],
                              by[idx], dx[idx], dy[idx], ring,
                              jnp.asarray(idx, jnp.int32),
                              jnp.ones((idx.size,), bool), relay_ks[idx],
                              upl_ks[idx])
            Pk, Mk, Vk, Tk, terms, ring = out
            put = lambda full, part: jax.tree.map(
                lambda f, s: f.at[idx].set(s), full, part)
            P, M, V, T = put(P, Pk), put(M, Mk), put(V, Vk), T.at[idx].set(Tk)
            losses.append(np.asarray(jnp.mean(terms, axis=0), np.float64))
            accs.append(self.accuracies(P, tx, ty))
            if r == 0:
                m_norm = adam_mean_norms(M, T, cfg["beta1"])
        return summary(losses, m_norm, P, P0, ring, accs)


def control(cfg: dict) -> Reference:
    """The reference one precision below what the configuration states
    (float32 parameters, bfloat16 matmul operands): bfloat16 parameters and
    float8 e4m3 matmul operands, per-tensor scaled."""
    return Reference(cfg, q=compare.quant_e4m3,
                     store=compare.round_to("bfloat16"))


# ---------------------------------------------------------------------------
# what both sides produce, and the comparison
# ---------------------------------------------------------------------------
def adam_mean_norms(M, T, b1) -> Dict[str, float]:
    """Per-leaf norm of Adam's bias-corrected first moment, which after
    one round is the gradient the optimizer got, averaged over the round's
    steps with Adam's weights."""
    t = jnp.asarray(T, jnp.float32)
    corr = jnp.where(t > 0, 1.0 / (1.0 - b1 ** jnp.maximum(t, 1.0)), 0.0)
    return {k: float(jnp.linalg.norm(
        (M[k] * corr.reshape((-1,) + (1,) * (M[k].ndim - 1))).ravel()))
        for k in M}


def summary(losses, m_norm, P, P0, ring, accs) -> dict:
    return {"loss": np.stack(losses),
            "m_norm": m_norm,
            "dp_norm": {k: float(jnp.linalg.norm((P[k] - P0[k]).ravel()))
                        for k in P},
            "ring": {k: np.asarray(ring[k]) for k in
                     ("owner", "valid", "stamp", "ptr", "clock")},
            "protos": np.asarray(ring["global_protos"], np.float64),
            "obs": np.asarray(ring["obs"], np.float64),
            "accs": np.stack(accs)}


def _rel_l2(a, b) -> float:
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def compare_summaries(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers compared, each with its limit."""
    skip = compare.quiet_leaves(ref["m_norm"])
    mism = sum(int(np.sum(np.asarray(prog["ring"][k]) != ref["ring"][k]))
               for k in ref["ring"])
    values = {
        "loss_gap": compare.rel_gap(prog["loss"], ref["loss"]),
        "grad_gap": compare.worst(compare.norm_gaps(prog["m_norm"],
                                                    ref["m_norm"])),
        "update_gap": compare.worst(compare.norm_gaps(
            prog["dp_norm"], ref["dp_norm"], skip)),
        "ring_mismatch": float(mism),
        "proto_gap": max(_rel_l2(prog["protos"], ref["protos"]),
                         _rel_l2(prog["obs"], ref["obs"])),
        "acc_gap": float(np.max(np.abs(prog["accs"] - ref["accs"]))),
    }
    return [compare.check_line(k, v, limits[k]) for k, v in values.items()]


# ---------------------------------------------------------------------------
# the fleet's data, made from the seed
# ---------------------------------------------------------------------------
class Data:
    """Client data (N, S, ...), test set, initial weights and the relay's
    and trainer's seeds of one cell, all made from the run's seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        N, S = traffic["clients"], traffic["samples_per_client"]
        s_data, s_test, s_split, s_w, s_tr = gen.sub_seeds(seed, 5)
        img = dict(num_classes=cfg["num_classes"], image=cfg["image"],
                   channels=cfg["channels"], noise=cfg["image_noise"])
        x, y = gen.class_images(N * S, seed=s_data, **img)
        self.tx, self.ty = gen.class_images(traffic["test_images"],
                                            seed=s_test, **img)
        parts = gen.uniform_split(x, y, N, seed=s_split)
        self.x = np.stack([p[0] for p in parts])
        self.y = np.stack([p[1] for p in parts])
        self.params0 = jax.tree.map(np.asarray, init_params(
            jax.random.PRNGKey(s_w), cfg, N))
        self.trainer_seed = s_tr
        self.cfg, self.traffic = cfg, traffic

    def mask(self, r: int) -> np.ndarray:
        """Round r's participants: everyone, or k drawn uniformly without
        replacement from the round's own generator."""
        N, part = self.traffic["clients"], self.traffic["participation"]
        if part == "full":
            return np.ones((N,), bool)
        k = int(part.split(":")[1])
        rng = np.random.default_rng([self.trainer_seed, r])
        m = np.zeros((N,), bool)
        m[rng.choice(N, k, replace=False)] = True
        return m

    def ring0(self) -> dict:
        """The relay's initial state: random prototypes, one seeded
        observation slot, the rest empty (32 slots per client)."""
        cfg = self.cfg
        C, d = cfg["num_classes"], cfg["d_feature"]
        cap = 32 * self.traffic["clients"] * cfg["m_up"]
        rng = np.random.default_rng(self.trainer_seed)
        protos = rng.normal(size=(C, d)).astype(np.float32) * 0.01
        obs = np.zeros((cap, C, d), np.float32)
        obs[:1] = rng.normal(size=(1, C, d)).astype(np.float32) * 0.01
        valid = np.zeros((cap, C), bool)
        valid[:1] = True
        owner = np.full((cap,), EMPTY_OWNER, np.int32)
        owner[:1] = SEED_OWNER
        return {"obs": obs, "valid": valid, "owner": owner,
                "ptr": np.int32(1), "global_protos": protos,
                "valid_g": np.ones((C,), bool),
                "stamp": np.zeros((cap,), np.int32), "clock": np.int32(0)}


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
class Cell:
    unit = "samples"

    def __init__(self, cfg, traffic, seed, devices, limits, log=print):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices, self.limits, self.log = devices, limits, log
        self.flops_per_unit = train_flops_per_sample(cfg)
        nb = traffic["samples_per_client"] // cfg["batch_size"]
        self.samples_per_client = nb * cfg["batch_size"] * cfg["local_epochs"]
        self.trainer = None

    def _build(self):
        from repro.core import client as client_lib, vec_collab
        from repro.models import cnn
        from repro.types import CollabConfig, FleetConfig, TrainConfig
        cfg, tr, d = self.cfg, self.traffic, self.data
        spec = client_lib.ClientSpec(
            apply=cnn.apply, head=lambda p: (p["head_w"], p["head_b"]))
        N = tr["clients"]
        params = [{k: v[i] for k, v in d.params0.items()} for i in range(N)]
        parts = [(d.x[i], d.y[i]) for i in range(N)]
        ccfg = CollabConfig(mode=cfg["mode"], num_classes=cfg["num_classes"],
                            d_feature=cfg["d_feature"],
                            lambda_kd=cfg["lambda_kd"],
                            lambda_disc=cfg["lambda_disc"],
                            n_avg=cfg["n_avg"], m_up=cfg["m_up"],
                            m_down=cfg["m_down"])
        tcfg = TrainConfig(learning_rate=cfg["learning_rate"],
                           beta1=cfg["beta1"], beta2=cfg["beta2"],
                           eps=cfg["eps"], batch_size=cfg["batch_size"],
                           local_epochs=cfg["local_epochs"])
        mesh = None
        if len(self.devices) > 1:
            from repro import sharding
            mesh = sharding.client_mesh(len(self.devices))
        fleet = FleetConfig(policy=cfg["relay"], mesh=mesh,
                            participation=(None if tr["participation"] == "full"
                                           else tr["participation"]))
        return vec_collab.VectorizedCollabTrainer(
            spec, params, parts, (d.tx, d.ty), ccfg, tcfg,
            seed=d.trainer_seed, fleet=fleet)

    def setup(self):
        self.data = Data(self.cfg, self.traffic, self.seed)
        self.log("fleet data and weights made")
        self.trainer = self._build()
        self.log("trainer built")
        tr = self.trainer
        P0 = tr.params
        losses, accs = [], []
        for r in range(CHECK_ROUNDS):
            rec = tr.run_round()
            jax.block_until_ready(tr.params)
            present = rec["participants"]
            losses.append(np.array([[rec["metrics"][i][t] for t in LOSS_TERMS]
                                    for i in present]).mean(axis=0))
            accs.append(np.asarray(rec["accs"]))
            if r == 0:
                m_norm = adam_mean_norms(tr.opt_state.m, tr.opt_state.step,
                                         self.cfg["beta1"])
            self.log(f"check round {r + 1} done")
        ring = dict(tr.relay_state._asdict())
        self.prog = summary(losses, m_norm, tr.params, P0, ring, accs)

    def step(self):
        rec = self.trainer.run_round()
        jax.block_until_ready(self.trainer.params)
        ok = bool(np.all(np.isfinite(rec["accs"])))
        return len(rec["participants"]) * self.samples_per_client, ok

    def release(self):
        self.trainer = None

    def check(self):
        ref = Reference(self.cfg).run(self.data)
        return compare_summaries(self.prog, ref, self.limits)
