"""xLSTM-125M as one CoRS client, driven step by step.

The timed step is the program's jitted `launch.train.make_train_step`
(donated state): forward and backward through the whole model, the CoRS
objective (cross-entropy, the KD pull to the running class prototypes and
the discriminator over K sampled negative classes), Adam, and the per-class
feature statistics of the batch. Set-up makes the weights on the device in
one jitted call from the seed, prepares `distinct_batches` batches from the
seeded token stream, and runs the first CHECK_STEPS steps through that same
step; they compile everything the window uses, and what they produce is
what `check` compares.

The reference below is a plain jax.numpy implementation of the same
model and objective in float32 at the highest matmul precision, with its
parameters stored at the configuration's precision. It imports nothing of
the program. Its mLSTM is the quadratic parallel form of the
recurrence, where the program runs a chunked one; its sLSTM is the same
sequential scan. It runs once the window has closed, layer by layer under
rematerialisation and over the tokens in blocks, so that it fits.
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchlib import compare, gen

CHECK_STEPS = 3
LOSS_TERMS = ("ce", "kd", "disc", "total")
HIGHEST = lax.Precision.HIGHEST
STREAM_TOKENS = 1 << 17
LOSS_BLOCK = 2048
_SEGMENT = re.compile(r"\['segments'\]\[(\d+)\]")


# ---------------------------------------------------------------------------
# sizes, weights, operations
# ---------------------------------------------------------------------------
def segments(cfg: dict) -> List[Tuple[str, int]]:
    """Consecutive blocks of one kind, as the program stacks them."""
    out: List[Tuple[str, int]] = []
    for kind in cfg["block_pattern"]:
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1] + 1)
        else:
            out.append((kind, 1))
    return out


def _block_shapes(cfg: dict, kind: str) -> Dict[str, tuple]:
    d, H = cfg["d_model"], cfg["num_heads"]
    if kind == "mlstm":
        di = 2 * d
        return {"w_up": (d, 2 * di), "conv_w": (cfg["ssm_conv"], di),
                "conv_b": (di,), "wq": (di, di), "wk": (di, di),
                "wv": (di, di), "w_gates": (di, 2 * H),
                "out_norm": (di,), "w_down": (di, d)}
    P = d // H
    return {"w_gates": (d, 4 * d), "r_gates": (4, H, P, P),
            "out_norm": (d,), "w_up": (d, 2 * d), "w_down": (d, d)}


def init_params(key, cfg: dict):
    """The model's weights in the program's layout and type, in one jitted
    call: 0.02-normal embedding, 1/sqrt(fan_in) dense layers, 0.2-normal
    conv taps, 1/sqrt(P) recurrent blocks, unit norms, zero biases."""
    dt = jnp.dtype(cfg["dtype"])
    d, V = cfg["d_model"], cfg["vocab_size"]

    def leaf(k, name, shp):
        if name in ("out_norm", "norm1"):
            return jnp.ones(shp, dt)
        if name == "conv_b":
            return jnp.zeros(shp, dt)
        if name == "conv_w":
            return (jax.random.normal(k, shp) * 0.2).astype(dt)
        if name == "r_gates":
            return (jax.random.normal(k, shp) / math.sqrt(shp[-1])).astype(dt)
        return (jax.random.normal(k, shp) / math.sqrt(shp[-2])).astype(dt)

    def init(key):
        k_emb, k_seg, k_head = jax.random.split(key, 3)
        segs = []
        for (kind, n), ks in zip(segments(cfg),
                                 jax.random.split(k_seg, len(segments(cfg)))):
            shapes = _block_shapes(cfg, kind)
            lk = jax.random.split(ks, len(shapes))
            body = {}
            for k, (name, shp) in zip(lk, shapes.items()):
                v = leaf(k, name, (n,) + shp)
                body[name] = {"scale": v} if name == "out_norm" else v
            segs.append({"norm1": {"scale": jnp.ones((n, d), dt)},
                         kind: body})
        return {"embed": (jax.random.normal(k_emb, (V, d)) * 0.02).astype(dt),
                "segments": segs,
                "final_norm": {"scale": jnp.ones((d,), dt)},
                "lm_head": (jax.random.normal(k_head, (d, V))
                            / math.sqrt(d)).astype(dt)}

    return jax.jit(init)(key)


def forward_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 per token of one forward pass: every block's
    projections, the mLSTM's chunked terms (causal part of the in-chunk
    products, the chunk states and their read-out), the sLSTM's recurrent
    products, and the LM head. The embedding lookup is not counted."""
    d, H, V, Q = (cfg["d_model"], cfg["num_heads"], cfg["vocab_size"],
                  cfg["ssm_chunk"])
    di, Pm, Ps = 2 * d, 2 * d // H, d // H
    causal = (Q + 1) / 2
    mlstm = (2 * d * 2 * di + 3 * 2 * di * di + 2 * di * 2 * H + 2 * di * d
             + 2 * H * Pm * causal + 2 * H * (Pm + 1) * causal
             + 2 * 2 * H * (Pm + 1) * Pm)
    slstm = (2 * d * 4 * d + 2 * 4 * H * Ps * Ps + 2 * d * 2 * d
             + 2 * d * d)
    n_m = sum(k == "mlstm" for k in cfg["block_pattern"])
    n_s = sum(k == "slstm" for k in cfg["block_pattern"])
    return n_m * mlstm + n_s * slstm + 2 * d * V


def train_flops_per_token(cfg: dict, tokens_per_step: int) -> float:
    """Forward and backward per token: 3 x the model's forward, plus the
    discriminator over K sampled negatives on T tokens, whose prototype
    projections need weight gradients only (2 x forward) and whose
    student-teacher product needs both (3 x)."""
    d, V = cfg["d_model"], cfg["vocab_size"]
    T = min(cfg["disc_tokens"], tokens_per_step)
    K = min(cfg["num_negatives"], V - 1)
    disc = 2 * (2 * T * d * V + 2 * K * d * V) + 3 * 2 * T * V * K
    return 3 * forward_flops_per_token(cfg) + disc / tokens_per_step


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
class Reference:
    """The xLSTM LM and its CoRS objective in plain jax.numpy, float32;
    the parameters are held at the configuration's `dtype` between steps,
    as the program holds them: a bfloat16 norm scale of 1 does not move by
    Adam's first steps of the learning rate, 1e-3.

    `q` rounds every matmul operand and `store` every parameter between
    steps (by default: float32 operands at the highest precision, and
    `compare.round_to` the configuration's `dtype`; `control` lowers both).
    `half_batch` leaves out the second half of the sequences and takes the
    mean over the rest.
    """

    def __init__(self, cfg: dict, q=compare.identity, store=None,
                 half_batch: bool = False):
        self.cfg, self.q, self.half = cfg, q, half_batch
        self.store = store or compare.round_to(cfg["dtype"])
        self._step = jax.jit(self._step_fn)

    def _mm(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HIGHEST)

    def _rms(self, scale, x):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(var + self.cfg["norm_eps"]) * scale

    def _mlstm(self, p, x):
        B, S, d = x.shape
        H = self.cfg["num_heads"]
        di = 2 * d
        P = di // H
        up = self._mm("bsd,de->bse", x, p["w_up"])
        xm, z = up[..., :di], up[..., di:]
        cw = p["conv_w"].shape[0]
        pad = jnp.pad(xm, ((0, 0), (cw - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(cw))
        conv = jax.nn.silu(conv + p["conv_b"])
        q = self._mm("bse,ef->bsf", conv, p["wq"]).reshape(B, S, H, P)
        k = self._mm("bse,ef->bsf", conv, p["wk"]).reshape(B, S, H, P)
        v = self._mm("bse,ef->bsf", xm, p["wv"]).reshape(B, S, H, P)
        gates = self._mm("bse,eh->bsh", conv, p["w_gates"])
        logi, logf = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])
        # parallel form: y_t = sum_{j<=t} D_tj (q_t.k_j) v_j / max(|n_t|, 1),
        # D_tj = exp(sum_{j<l<=t} log f_l + log i_j)
        cum = jnp.cumsum(logf, axis=1)
        logd = cum[:, :, None, :] - cum[:, None, :, :] + logi[:, None, :, :]
        causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
        dmat = jnp.exp(jnp.where(causal, logd, -jnp.inf))
        w = self._mm("bthp,bjhp->btjh", q * P ** -0.5, k) * dmat
        num = self._mm("btjh,bjhp->bthp", w, v)
        den = jnp.sum(w, axis=2)[..., None]
        y = (num / jnp.maximum(jnp.abs(den), 1.0)).reshape(B, S, di)
        y = self._rms(p["out_norm"]["scale"], y * jax.nn.silu(z))
        return self._mm("bse,ed->bsd", y, p["w_down"])

    def _slstm(self, p, x):
        B, S, d = x.shape
        H = self.cfg["num_heads"]
        P = d // H
        gx = self._mm("bsd,de->bse", x, p["w_gates"]).reshape(B, S, 4, d)
        r = p["r_gates"]

        def cell(carry, g_t):
            c, n, m, h = carry
            rec = self._mm("ghpq,bhq->bghp", r, h.reshape(B, H, P))
            g = g_t + rec.reshape(B, 4, d)
            i_pre, f_pre, z_pre, o_pre = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
            m_new = jnp.maximum(f_pre + m, i_pre)
            i = jnp.exp(i_pre - m_new)
            f = jnp.exp(f_pre + m - m_new)
            c = f * c + i * jnp.tanh(z_pre)
            n = f * n + i
            h = jax.nn.sigmoid(o_pre) * c / jnp.maximum(n, 1e-6)
            return (c, n, m_new, h), h

        zero = jnp.zeros((B, d), jnp.float32)
        init = (zero, zero, jnp.full((B, d), -30.0), zero)
        _, hs = lax.scan(cell, init, gx.transpose(1, 0, 2, 3))
        hs = self._rms(p["out_norm"]["scale"], hs.transpose(1, 0, 2))
        up = self._mm("bsd,de->bse", hs, p["w_up"])
        gate, u = up[..., :d], up[..., d:]
        return self._mm("bsd,de->bse", jax.nn.silu(gate) * u, p["w_down"])

    def features(self, params, tokens):
        x = params["embed"][tokens]
        for (kind, _), seg in zip(segments(self.cfg), params["segments"]):
            f = self._mlstm if kind == "mlstm" else self._slstm

            @jax.checkpoint
            def block(x, lp, f=f, kind=kind):
                return x + f(lp[kind], self._rms(lp["norm1"]["scale"], x)), None

            x, _ = lax.scan(block, x, seg)
        return self._rms(params["final_norm"]["scale"], x)

    def loss(self, params, batch, proto_sum, proto_cnt, key):
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        if self.half:
            tokens, labels = tokens[: tokens.shape[0] // 2], \
                labels[: labels.shape[0] // 2]
        feats = self.features(params, tokens)
        d, V = feats.shape[-1], cfg["vocab_size"]
        f = feats.reshape(-1, d)
        y = labels.reshape(-1)
        n_tok = f.shape[0]
        T = min(cfg["disc_tokens"], n_tok)
        K = min(cfg["num_negatives"], V - 1)
        means = proto_sum / jnp.maximum(proto_cnt, 1.0)[:, None]
        W = params["lm_head"]
        neg = jax.random.randint(jax.random.split(key)[0], (K,), 0, V)
        qneg = jax.nn.softmax(self._mm("kd,dv->kv", means[neg], W), axis=-1)

        @jax.checkpoint
        def block_terms(fb, yb, disc_w):
            z = self._mm("nd,dv->nv", fb, W)
            logp = jax.nn.log_softmax(z, axis=-1)
            ce = -jnp.sum(jnp.take_along_axis(logp, yb[:, None], axis=1))
            kd = jnp.sum(jnp.mean((fb - means[yb]) ** 2, axis=-1))
            p = jnp.exp(logp)
            zpos = jax.nn.softmax(self._mm("nd,dv->nv", means[yb], W), axis=-1)
            hpos = jnp.clip(jnp.sum(p * zpos, axis=-1), 1e-7, 1 - 1e-7)
            hneg = jnp.clip(self._mm("nv,kv->nk", p, qneg), 1e-7, 1 - 1e-7)
            not_self = (neg[None, :] != yb[:, None]).astype(jnp.float32)
            per_tok = -jnp.log(hpos) - jnp.sum(jnp.log1p(-hneg) * not_self,
                                               axis=-1)
            return ce, kd, jnp.sum(per_tok * disc_w)

        ce = kd = disc = 0.0
        for a in range(0, n_tok, LOSS_BLOCK):
            b = min(a + LOSS_BLOCK, n_tok)
            disc_w = (jnp.arange(a, b) < T).astype(jnp.float32)
            c_, k_, d_ = block_terms(f[a:b], y[a:b], disc_w)
            ce, kd, disc = ce + c_, kd + k_, disc + d_
        ce, kd, disc = ce / n_tok, kd / n_tok, disc / T
        total = ce + cfg["lambda_kd"] * kd + cfg["lambda_disc"] * disc
        stats = (jax.ops.segment_sum(f, y, num_segments=V),
                 jax.ops.segment_sum(jnp.ones_like(y, jnp.float32), y,
                                     num_segments=V))
        return total, (jnp.stack([ce, kd, disc, total]), stats)

    def _step_fn(self, params, m, v, t, batch, proto_sum, proto_cnt, key):
        cfg = self.cfg
        b1, b2, lr, eps = (cfg["beta1"], cfg["beta2"], cfg["learning_rate"],
                           cfg["eps"])
        (_, (terms, (s, c))), g = jax.value_and_grad(self.loss, has_aux=True)(
            params, batch, proto_sum, proto_cnt, key)
        t = t + 1.0
        m = jax.tree.map(lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
        v = jax.tree.map(lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
        # each update is computed in float32 and the result rounded to the
        # parameters' stored precision
        params = jax.tree.map(
            lambda p, mm, vv: self.store(
                p - lr * (mm / (1 - b1 ** t))
                / (jnp.sqrt(vv / (1 - b2 ** t)) + eps)), params, m, v)
        return params, m, v, t, terms, g, proto_sum + s, proto_cnt + c

    def run(self, data: "Data") -> dict:
        p = jax.tree.map(lambda a: a.astype(jnp.float32), data.params0())
        P0 = p
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        t = jnp.zeros((), jnp.float32)
        V, d = self.cfg["vocab_size"], self.cfg["d_model"]
        ps, pc = jnp.zeros((V, d), jnp.float32), jnp.zeros((V,), jnp.float32)
        losses, g_norm = [], None
        for i in range(CHECK_STEPS):
            batch = {k: jnp.asarray(a) for k, a in data.batches[i].items()}
            key = jax.random.split(data.step_key(i), 1)[0]
            p, m, v, t, terms, g, ps, pc = self._step(p, m, v, t, batch, ps,
                                                      pc, key)
            losses.append(np.asarray(terms, np.float64))
            if i == 0:
                g_norm = leaf_norms(g)
                proto1 = ps
            del g
        return summary(losses, g_norm, leaf_norms(p, P0), proto1, pc)


def control(cfg: dict) -> Reference:
    """The reference one precision below what the configuration states
    (bfloat16 parameters, activations and matmul operands): float8 e4m3,
    per-tensor scaled, for the matmul operands and the stored parameters.
    Its operands do not separate it from the program (the sLSTM's chaos
    swamps them, `compare_summaries`); its stored parameters do: they
    change by whole e4m3 steps, which `update_gap` reads."""
    return Reference(cfg, q=compare.quant_e4m3, store=compare.e4m3_round)


# ---------------------------------------------------------------------------
# what both sides produce, and the comparison
# ---------------------------------------------------------------------------
def leaf_norms(tree, minus=None) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    other = (jax.tree.leaves(minus) if minus is not None
             else [None] * len(flat))
    out = {}
    for (path, a), b in zip(flat, other):
        a = a.astype(jnp.float32)
        if b is not None:
            a = a - b.astype(jnp.float32)
        out[jax.tree_util.keystr(path)] = float(jnp.linalg.norm(a.ravel()))
    return out


def summary(losses, g_norm, dp_norm, proto_sum1, proto_cnt) -> dict:
    """What a check run produced: each step's loss terms, the per-leaf
    norms of the first gradient and of the change after CHECK_STEPS steps,
    the per-class feature sums after the first step and the per-class
    token counts after the last."""
    return {"loss": np.stack(losses), "g_norm": g_norm, "dp_norm": dp_norm,
            "proto_sum1": np.asarray(proto_sum1, np.float64),
            "proto_cnt": np.asarray(proto_cnt, np.float64)}


def above_last_slstm(keys) -> List[str]:
    """The leaves whose first gradient does not pass back through an
    sLSTM's recurrence: the blocks after the last sLSTM, the final norm and
    the LM head."""
    seg = {k: int(m.group(1)) for k in keys if (m := _SEGMENT.match(k))}
    last = max((i for k, i in seg.items() if "['slstm']" in k), default=-1)
    return [k for k in keys
            if k != "['embed']" and seg.get(k, last + 1) > last]


def compare_summaries(prog: dict, ref: dict, limits: dict) -> list:
    """The numbers compared, each with its limit.

    - `loss_gap`: every loss term of each of the CHECK_STEPS steps,
      relative, the worst.
    - `grad_gap`: the first gradient by the worst leaf among
      `above_last_slstm`, its norm gap over the larger of that leaf's
      reference norm and the median of those leaves'.
    - `update_gap`: the change after CHECK_STEPS steps by the worst leaf,
      leaving out the leaves whose reference gradient is nought to rounding
      (`compare.quiet_leaves`).
    - `count_mismatch`: the per-class token counts after the last step,
      which no rounding moves: exact.
    - `proto_gap`: the per-class feature sums after the first step,
      relative L2; read, not compared.

    At this init the sLSTM's recurrence over 2048 tokens is chaotic: the
    gradient reaching the blocks below it is over a thousand times the
    head's, and any change of rounding decorrelates the features of later
    tokens. On the chip (12 seeds at the cell's size) the worst leaf of the
    whole first gradient reads 0.24-1.02 for the program, 0.14-1.03 for
    this reference with bfloat16 operands and parameters, and 0.35-1.96
    for the control; the feature sums read 0.57-0.68, 0.54-0.64 and
    1.01-1.09. The program in float32 at HIGHEST reads 0.007-0.045 and
    0.0012-0.0017: the program is sound, and these numbers measure the
    chaos. So the gradient is read above the last sLSTM, where the program
    reads at most 0.016 and the half-batch fault at least 0.40 (the control
    reads as the program there), and the feature sums are not compared:
    the control reads them under twice the program. The control fails
    `update_gap`, the change after the last step (program 0.020-0.115 over
    24 seeds)."""
    skip = compare.quiet_leaves(ref["g_norm"])
    top = above_last_slstm(ref["g_norm"])
    ps, rs = prog["proto_sum1"], ref["proto_sum1"]
    values = {
        "loss_gap": compare.rel_gap(prog["loss"], ref["loss"]),
        "grad_gap": compare.worst(compare.norm_gaps(
            {k: prog["g_norm"][k] for k in top},
            {k: ref["g_norm"][k] for k in top})),
        "update_gap": compare.worst(compare.norm_gaps(
            prog["dp_norm"], ref["dp_norm"], skip)),
        "count_mismatch": float(np.sum(prog["proto_cnt"] != ref["proto_cnt"])),
        "proto_gap": float(np.linalg.norm(ps - rs)
                           / max(np.linalg.norm(rs), 1e-30)),
    }
    return [compare.check_line(k, v, limits[k]) for k, v in values.items()]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
def model_config(cfg: dict):
    """The program's ModelConfig with this file's sizes."""
    from repro.configs import get_arch
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "d_ff",
            "vocab_size", "ssm_conv", "ssm_chunk", "norm_eps", "d_feature",
            "dtype")
    return dataclasses.replace(get_arch(cfg["arch"]),
                               block_pattern=tuple(cfg["block_pattern"]),
                               **{k: cfg[k] for k in keys})


class Data:
    """The cell's token batches, weights key and step keys, all made from
    the run's seed."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        s_tok, s_batch, s_w, s_key = gen.sub_seeds(seed, 4)
        toks = gen.token_stream(STREAM_TOKENS, vocab=cfg["vocab_size"],
                                seed=s_tok)
        self.batches = list(gen.lm_batches(
            toks, traffic["batch"], traffic["seq_len"],
            traffic["distinct_batches"], seed=s_batch))
        self.w_key = jax.random.PRNGKey(s_w)
        self.key = jax.random.PRNGKey(s_key)
        self.cfg = cfg

    def params0(self):
        return init_params(self.w_key, self.cfg)

    def step_key(self, i: int):
        return jax.random.fold_in(self.key, i)


class Cell:
    unit = "tokens"

    def __init__(self, cfg, traffic, seed, devices, limits, log=print):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.devices, self.limits, self.log = devices, limits, log
        B, S = traffic["batch"], traffic["seq_len"]
        self.tokens_per_step = B * S
        self.flops_per_unit = train_flops_per_token(cfg, B * S)
        self.state = None

    def setup(self):
        from repro.core import prototypes
        from repro.launch import train as train_lib
        from repro.models import lm
        from repro.optim import adam_init
        from repro.types import CollabConfig
        cfg = self.cfg
        self.data = d = Data(cfg, self.traffic, self.seed)
        mcfg = model_config(cfg)
        params = d.params0()
        want = jax.eval_shape(lambda k: lm.init_lm(k, mcfg), d.w_key)
        got = jax.eval_shape(lambda: params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype)
                for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter layout")
        self.dev_batches = [{k: jnp.asarray(v)[None] for k, v in b.items()}
                            for b in d.batches]
        ccfg = CollabConfig(mode=cfg["mode"], num_classes=cfg["vocab_size"],
                            d_feature=cfg["d_feature"],
                            lambda_kd=cfg["lambda_kd"],
                            lambda_disc=cfg["lambda_disc"],
                            num_negatives=cfg["num_negatives"])
        self.step_fn = jax.jit(train_lib.make_train_step(
            mcfg, ccfg, n_clients=1, lr=cfg["learning_rate"],
            disc_tokens=cfg["disc_tokens"]), donate_argnums=0)
        stacked = jax.tree.map(lambda a: a[None], params)
        self.state = train_lib.TrainState(
            stacked, jax.tree.map(lambda a: a[None], adam_init(params)),
            prototypes.init_state(cfg["vocab_size"], cfg["d_feature"]),
            jnp.zeros((), jnp.int32))
        self.log("weights, batches and state made")
        self.i = 0
        losses = []
        for i in range(CHECK_STEPS):
            metrics = self._advance()
            losses.append(np.array([float(metrics[t]) for t in LOSS_TERMS]))
            if i == 0:
                b1 = cfg["beta1"]
                g_norm = leaf_norms(jax.tree.map(
                    lambda a: a[0] / (1 - b1), self.state.opt.m))
                proto1 = np.asarray(self.state.proto.sum)
            self.log(f"check step {i + 1} done")
        dp_norm = leaf_norms(jax.tree.map(lambda a: a[0], self.state.params),
                             params)
        del params
        self.prog = summary(losses, g_norm, dp_norm, proto1,
                            self.state.proto.count)

    def _advance(self):
        b = self.dev_batches[self.i % len(self.dev_batches)]
        self.state, metrics = self.step_fn(self.state, b,
                                           self.data.step_key(self.i))
        self.i += 1
        jax.block_until_ready(self.state)
        return metrics

    def step(self):
        metrics = self._advance()
        return self.tokens_per_step, bool(np.isfinite(float(metrics["total"])))

    def release(self):
        self.state = None
        self.step_fn = None
        self.dev_batches = None

    def check(self):
        ref = Reference(self.cfg).run(self.data)
        return compare_summaries(self.prog, ref, self.limits)
