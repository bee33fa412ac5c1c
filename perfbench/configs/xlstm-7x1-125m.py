"""xLSTM[7:1] 125M, the paper's published blocks, as one CoRS client driven
step by step.

The timed step is the program's jitted `launch.train.make_train_step`
(donated state), as for the staged 12-block `xlstm-125m` configuration:
forward and backward through the whole model, the CoRS objective
(cross-entropy, the KD pull to the running class prototypes and the
discriminator over K sampled negative classes), Adam, and the per-class
feature statistics of the batch. One sample is one training sequence.

What this model shares with the staged configuration is loaded from its
module and not copied: the seeded token batches and keys (`Data`), the
CoRS loss terms, Adam and the three check steps (`Reference.loss`,
`_step_fn` and `run`), `summary` and `compare_summaries`. What is new here
is the published block stack, in the program's layout (`init_params`) and
in the plain reference (`Reference.features`): a plain jax.numpy model in
float32 at the highest matmul precision that imports nothing of the
program, with the mLSTM in its quadratic parallel form (the program runs
it chunked) and the sLSTM as a sequential scan, checkpointed per block,
with the loss over the tokens in blocks, so that it fits.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchlib import cells, compare

_, staged = cells.config("xlstm-125m")

CHECK_STEPS = staged.CHECK_STEPS
segments = staged.segments
summary = staged.summary
leaf_norms = staged.leaf_norms

# Departures from the model that a correct run must be told apart from:
# the reference with one part left out or wired wrong, put in the
# program's place (`calibrate_faults.py` reads them on the chip; the tests
# on the CPU). A recurrent kernel applied transposed, or with its gates'
# kernels swapped, is no departure: with the kernels starting at 0 and
# Adam acting entry by entry, it is the same model with its entries
# stored elsewhere (`test_bench_xlstm_7x1.py` shows it reads as the
# reference). The cross-head recurrence (each head's kernel reading the
# next head's state) is a different model that no limit fails: it departs
# only through the recurrent kernels, which start at 0, so in three steps
# it moves their gradients' directions and hardly their norms, which are
# all the limits read.
FAULTS = {
    "slstm_r_dropped": {"depart": ("r",)},
    "slstm_conv_dropped": {"depart": ("slstm_conv",)},
    "mlstm_skip_dropped": {"depart": ("skip",)},
    "slstm_r_crosshead": {"depart": ("r_crosshead",)},
}

# The reference at the program's own precision (bfloat16 matmul operands;
# the parameters are stored in bfloat16 either way): what rounding alone
# does to the compared numbers (`calibrate_faults.py` reads it).
AT_DTYPE = {"q": compare.round_to("bfloat16")}


# ---------------------------------------------------------------------------
# sizes, weights, operations
# ---------------------------------------------------------------------------
def _block_shapes(cfg: dict, kind: str) -> Dict[str, tuple]:
    d, H, bs = cfg["d_model"], cfg["num_heads"], cfg["qkv_blocksize"]
    if kind == "mlstm":
        di = 2 * d
        qkv = (di // bs, bs, bs)
        return {"w_up": (d, 2 * di), "conv_w": (cfg["ssm_conv"], di),
                "conv_b": (di,), "wq": qkv, "wk": qkv, "wv": qkv,
                "w_gates": (3 * di, 2 * H), "b_gates": (2 * H,),
                "out_norm": (di,), "skip": (di,), "w_down": (di, d)}
    P = d // H
    return {"conv_w": (cfg["ssm_conv"], d), "conv_b": (d,),
            "w_gates": (4, H, P, P), "r_gates": (4, H, P, P),
            "b_gates": (4, d), "out_norm": (d,)}


def _ffn_shapes(cfg: dict) -> Dict[str, tuple]:
    d, f = cfg["d_model"], cfg["d_ff"]
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def slstm_forget_bias(cfg: dict, index: int):
    """The published power-law forget-gate bias of the sLSTM at block
    `index`, the same for each head: -(-5 + 12 (j/(P-1))^(0.3 + 1.3 r)),
    r = index / (blocks - 1)."""
    P = cfg["d_model"] // cfg["num_heads"]
    r = index / (cfg["num_layers"] - 1)
    return -(-5.0 + 12.0 * (jnp.arange(P) / (P - 1)) ** (0.3 + 1.3 * r))


def init_params(key, cfg: dict):
    """The model's weights in the program's layout and type, in one jitted
    call, by the laws `assumed.init` states."""
    dt = jnp.dtype(cfg["dtype"])
    d, V, H = cfg["d_model"], cfg["vocab_size"], cfg["num_heads"]

    def normal(k, shp, std):
        return (jax.random.normal(k, shp) * std).astype(dt)

    def mlstm_leaf(k, name, shp, n):
        if name in ("out_norm", "skip"):
            return jnp.ones((n,) + shp, dt)
        if name in ("conv_b", "w_gates"):
            return jnp.zeros((n,) + shp, dt)
        if name == "b_gates":
            i_bias = jax.random.normal(k, (n, H)) * 0.1
            f_bias = jnp.broadcast_to(jnp.linspace(3.0, 6.0, H), (n, H))
            return jnp.concatenate([i_bias, f_bias], axis=1).astype(dt)
        if name == "conv_w":
            return normal(k, (n,) + shp, 0.2)
        return normal(k, (n,) + shp, 1 / math.sqrt(shp[-2]))

    def slstm_leaf(k, name, shp, first, n):
        if name == "out_norm":
            return jnp.ones((n,) + shp, dt)
        if name in ("conv_b", "r_gates"):
            return jnp.zeros((n,) + shp, dt)
        if name == "b_gates":
            P = d // H
            f = jnp.stack([slstm_forget_bias(cfg, first + i)
                           for i in range(n)])
            b = jnp.zeros((n, 4, H, P)).at[:, 1].set(f[:, None, :])
            return b.reshape((n,) + shp).astype(dt)
        if name == "conv_w":
            return normal(k, (n,) + shp, 0.2)
        return normal(k, (n,) + shp, 1 / math.sqrt(shp[-1]))

    def init(key):
        k_emb, k_seg, k_head = jax.random.split(key, 3)
        segs, first = [], 0
        for (kind, n), ks in zip(segments(cfg),
                                 jax.random.split(k_seg, len(segments(cfg)))):
            shapes = _block_shapes(cfg, kind)
            k_blk, k_ffn = jax.random.split(ks)
            body = {}
            for k, (name, shp) in zip(jax.random.split(k_blk, len(shapes)),
                                      shapes.items()):
                v = (mlstm_leaf(k, name, shp, n) if kind == "mlstm"
                     else slstm_leaf(k, name, shp, first, n))
                body[name] = {"scale": v} if name == "out_norm" else v
            seg = {"norm1": {"scale": jnp.ones((n, d), dt)}, kind: body}
            if kind == "slstm":
                fs = _ffn_shapes(cfg)
                seg["norm2"] = {"scale": jnp.ones((n, d), dt)}
                seg["mlp"] = {name: normal(k, (n,) + shp, 1 / math.sqrt(shp[0]))
                              for k, (name, shp) in zip(
                                  jax.random.split(k_ffn, len(fs)), fs.items())}
            segs.append(seg)
            first += n
        return {"embed": normal(k_emb, (V, d), 0.02), "segments": segs,
                "final_norm": {"scale": jnp.ones((d,), dt)},
                "lm_head": normal(k_head, (d, V), 1 / math.sqrt(d))}

    return jax.jit(init)(key)


def block_params(cfg: dict, kind: str) -> int:
    """Parameters of one block of `kind`, its pre-norms included."""
    shapes = dict(_block_shapes(cfg, kind), norm1=(cfg["d_model"],))
    if kind == "slstm":
        shapes.update(_ffn_shapes(cfg), norm2=(cfg["d_model"],))
    return sum(math.prod(s) for s in shapes.values())


def forward_flops_per_token(cfg: dict) -> float:
    """Multiply-adds x 2 per token of one forward pass: every block's
    projections (block-diagonal ones at their block size), the mLSTM's
    chunked terms (causal part of the in-chunk products, the chunk states
    and their read-out), the sLSTM's head-wise gate and recurrent products
    and its feed-forward, and the LM head. Convolutions, norms, gates'
    pointwise work and the embedding lookup are not counted."""
    d, H, V, Q = (cfg["d_model"], cfg["num_heads"], cfg["vocab_size"],
                  cfg["ssm_chunk"])
    di, bs, f = 2 * d, cfg["qkv_blocksize"], cfg["d_ff"]
    Pm, Ps = di // H, d // H
    causal = (Q + 1) / 2
    mlstm = (2 * d * 2 * di + 3 * 2 * di * bs + 2 * 3 * di * 2 * H
             + 2 * di * d + 2 * H * Pm * causal + 2 * H * (Pm + 1) * causal
             + 2 * 2 * H * (Pm + 1) * Pm)
    slstm = 2 * 4 * d * Ps + 2 * 4 * d * Ps + 2 * 3 * d * f
    n_m = sum(k == "mlstm" for k in cfg["block_pattern"])
    n_s = sum(k == "slstm" for k in cfg["block_pattern"])
    return n_m * mlstm + n_s * slstm + 2 * d * V


def train_flops_per_sample(cfg: dict, batch: int, seq_len: int) -> float:
    """Forward and backward per training sequence: 3 x the model's forward
    over its tokens, plus its share of the discriminator over K sampled
    negatives on T tokens a step (the staged configuration's count)."""
    d, V = cfg["d_model"], cfg["vocab_size"]
    T = min(cfg["disc_tokens"], batch * seq_len)
    K = min(cfg["num_negatives"], V - 1)
    disc = 2 * (2 * T * d * V + 2 * K * d * V) + 3 * 2 * T * V * K
    return 3 * forward_flops_per_token(cfg) * seq_len + disc / batch


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
class Reference(staged.Reference):
    """The published xLSTM[7:1] stack in plain jax.numpy, float32, under the
    staged configuration's CoRS objective, Adam and check steps.

    `q`, `store` and `half_batch` as there. `depart` names departures
    from the model, for planted faults (`FAULTS`): parts left out, "r" the
    sLSTM's recurrent term, "slstm_conv" the sLSTM's conv (its i and f
    gates then read the block input), "skip" the mLSTM's learnable skip;
    and "r_crosshead", the sLSTM's recurrence reading the previous state
    of the next head, or "r_transposed", each head's recurrent kernel
    applied transposed.
    """

    def __init__(self, cfg: dict, q=compare.identity, store=None,
                 half_batch: bool = False, depart=()):
        self.depart = frozenset(depart)
        super().__init__(cfg, q=q, store=store, half_batch=half_batch)

    def _ln(self, scale, x):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
        return (x - mu) * lax.rsqrt(var + self.cfg["norm_eps"]) * scale

    def _headwise(self, scale, y):
        B, S, H, P = y.shape
        return self._ln(scale.reshape(H, P), y).reshape(B, S, H * P)

    def _conv(self, p, x):
        cw, S = p["conv_w"].shape[0], x.shape[1]
        pad = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
        conv = sum(pad[:, i:i + S] * p["conv_w"][i] for i in range(cw))
        return jax.nn.silu(conv + p["conv_b"])

    def _blockdiag(self, x, w):
        B, S, e = x.shape
        nb, bs, _ = w.shape
        return self._mm("bsnp,npq->bsnq", x.reshape(B, S, nb, bs),
                        w).reshape(B, S, e)

    def _mlstm(self, p, x):
        B, S, d = x.shape
        H = self.cfg["num_heads"]
        di = 2 * d
        P = di // H
        up = self._mm("bsd,de->bse", x, p["w_up"])
        xm, z = up[..., :di], up[..., di:]
        conv = self._conv(p, xm)
        q = self._blockdiag(conv, p["wq"])
        k = self._blockdiag(conv, p["wk"])
        v = self._blockdiag(xm, p["wv"])
        gates = self._mm("bse,eh->bsh", jnp.concatenate([q, k, v], -1),
                         p["w_gates"]) + p["b_gates"]
        logi, logf = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])
        causal = jnp.tril(jnp.ones((S, S), bool))

        def head(a):
            # parallel form: y_t = sum_{j<=t} D_tj (q_t.k_j) v_j
            # / max(|n_t|, 1), D_tj = exp(sum_{j<l<=t} log f_l + log i_j);
            # one head at a time, so that one (B, S, S) array is the largest
            q, k, v, logi, cum = a
            logd = cum[:, :, None] - cum[:, None, :] + logi[:, None, :]
            dmat = jnp.exp(jnp.where(causal, logd, -jnp.inf))
            w = self._mm("btp,bjp->btj", q * P ** -0.5, k) * dmat
            num = self._mm("btj,bjp->btp", w, v)
            den = jnp.sum(w, axis=2)[..., None]
            return num / jnp.maximum(jnp.abs(den), 1.0)

        per_head = [a.reshape(B, S, H, P) for a in (q, k, v)]
        per_head += [logi, jnp.cumsum(logf, axis=1)]
        y = lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in per_head))
        y = self._headwise(p["out_norm"]["scale"], jnp.moveaxis(y, 0, 2))
        if "skip" not in self.depart:
            y = y + p["skip"] * conv
        return self._mm("bse,ed->bsd", y * jax.nn.silu(z), p["w_down"])

    def _slstm(self, p, x):
        B, S, d = x.shape
        H = self.cfg["num_heads"]
        P = d // H
        conv = x if "slstm_conv" in self.depart else self._conv(p, x)
        u = jnp.stack([conv, conv, x, x], axis=2).reshape(B, S, 4, H, P)
        gx = (self._mm("bsghq,ghpq->bsghp", u, p["w_gates"])
              .reshape(B, S, 4, d) + p["b_gates"])
        r = p["r_gates"]
        eq = ("ghqp,bhq->bghp" if "r_transposed" in self.depart
              else "ghpq,bhq->bghp")

        def cell(carry, g):
            c, n, m, h = carry
            if "r" not in self.depart:
                h = h.reshape(B, H, P)
                if "r_crosshead" in self.depart:
                    h = jnp.roll(h, -1, axis=1)
                g = g + self._mm(eq, r, h).reshape(B, 4, d)
            i_pre, f_pre, z_pre, o_pre = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
            logf = jax.nn.log_sigmoid(f_pre)
            m_new = jnp.maximum(logf + m, i_pre)
            i = jnp.exp(i_pre - m_new)
            f = jnp.exp(logf + m - m_new)
            c = f * c + i * jnp.tanh(z_pre)
            n = f * n + i
            h = jax.nn.sigmoid(o_pre) * c / jnp.maximum(n, 1e-6)
            return (c, n, m_new, h), h

        zero = jnp.zeros((B, d), jnp.float32)
        init = (zero, zero, jnp.full((B, d), -30.0), zero)
        _, hs = lax.scan(cell, init, gx.transpose(1, 0, 2, 3))
        return self._headwise(p["out_norm"]["scale"],
                              hs.transpose(1, 0, 2).reshape(B, S, H, P))

    def _ffn(self, p, x):
        g = self._mm("bsd,df->bsf", x, p["w_gate"])
        u = self._mm("bsd,df->bsf", x, p["w_up"])
        return self._mm("bsf,fd->bsd", jax.nn.gelu(g, approximate=False) * u,
                        p["w_down"])

    def features(self, params, tokens):
        x = params["embed"][tokens]
        for (kind, _), seg in zip(segments(self.cfg), params["segments"]):

            @jax.checkpoint
            def block(x, lp, kind=kind):
                h = self._ln(lp["norm1"]["scale"], x)
                if kind == "mlstm":
                    return x + self._mlstm(lp["mlstm"], h), None
                x = x + self._slstm(lp["slstm"], h)
                return x + self._ffn(lp["mlp"],
                                     self._ln(lp["norm2"]["scale"], x)), None

            x, _ = lax.scan(block, x, seg)
        return self._ln(params["final_norm"]["scale"], x)


def control(cfg: dict) -> Reference:
    """The reference one precision below what the configuration states
    (bfloat16 matmul operands): float8 e4m3 matmul operands, per-tensor
    scaled, with the parameters stored in bfloat16 as the program stores
    them."""
    return Reference(cfg, q=compare.quant_e4m3)


# ---------------------------------------------------------------------------
# what both sides produce, and the comparison
# ---------------------------------------------------------------------------
def compare_summaries(prog: dict, ref: dict, limits: dict) -> list:
    """The staged configuration's numbers (`loss_gap`, `grad_gap` over the
    leaves above the last sLSTM, `update_gap`, `count_mismatch`,
    `proto_gap`), and `grad_gap_all`: the first gradient by the worst leaf
    of the whole model, as `grad_gap` reads its leaves, leaving out those
    whose reference gradient is nought to rounding.

    `grad_gap_all` is read, not compared: below the last sLSTM the first
    gradient departs from the float32 reference by rounding alone. On the
    chip at the cell's size (16 x 2048) the program reads 0.041-0.55 over
    11 seeds, and on one of them, where the program reads 0.22, this
    reference at the program's precision (`AT_DTYPE`: bfloat16 operands
    and parameters) reads 0.32, as it reads the compared numbers as the
    program does. So the gradient is compared above the last sLSTM, as
    for the staged configuration."""
    checks = staged.compare_summaries(prog, ref, limits)
    skip = compare.quiet_leaves(ref["g_norm"])
    gap = compare.worst(compare.norm_gaps(prog["g_norm"], ref["g_norm"], skip))
    return checks + [compare.check_line("grad_gap_all", gap,
                                        limits["grad_gap_all"])]


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
def model_config(cfg: dict):
    """The program's ModelConfig with this file's sizes."""
    from repro.configs import get_arch
    keys = ("num_layers", "d_model", "num_heads", "d_ff", "vocab_size",
            "ssm_conv", "ssm_chunk", "norm_eps",
            "d_feature", "dtype")
    return dataclasses.replace(get_arch(cfg["arch"]),
                               num_kv_heads=cfg["num_heads"],
                               block_pattern=tuple(cfg["block_pattern"]),
                               **{k: cfg[k] for k in keys})


class Data(staged.Data):
    """The staged configuration's seeded batches and keys, with this
    model's weights."""

    def params0(self):
        return init_params(self.w_key, self.cfg)


class Cell(staged.Cell):
    unit = "samples"

    def __init__(self, cfg, traffic, seed, devices, limits, log=print):
        super().__init__(cfg, traffic, seed, devices, limits, log)
        self.batch = traffic["batch"]
        self.flops_per_unit = train_flops_per_sample(cfg, traffic["batch"],
                                                     traffic["seq_len"])

    def setup(self):
        mcfg = model_config(self.cfg)   # first: an unknown arch fails at once
        from repro.core import prototypes
        from repro.launch import train as train_lib
        from repro.models import lm
        from repro.optim import adam_init
        from repro.types import CollabConfig
        cfg = self.cfg
        self.data = d = Data(cfg, self.traffic, self.seed)
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
        self.state = train_lib.TrainState(
            jax.tree.map(lambda a: a[None], params),
            jax.tree.map(lambda a: a[None], adam_init(params)),
            prototypes.init_state(cfg["vocab_size"], cfg["d_feature"]),
            jnp.zeros((), jnp.int32))
        self.log("weights, batches and state made")
        self.i = 0
        losses = []
        for i in range(CHECK_STEPS):
            metrics = self._advance()
            losses.append(np.array([float(metrics[t])
                                    for t in staged.LOSS_TERMS]))
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

    def step(self):
        metrics = self._advance()
        return self.batch, bool(np.isfinite(float(metrics["total"])))

    def check(self):
        ref = Reference(self.cfg).run(self.data)
        return compare_summaries(self.prog, ref, self.limits)
