"""xLSTM blocks: mLSTM (matrix memory, chunk-parallel) and sLSTM (scalar
memory, sequential lax.scan).

mLSTM recurrence (per head, state C: (P_v, P_k), normalizer n: (P_k,)):
    C_t = f_t C_{t-1} + i_t v_t k_t^T
    n_t = f_t n_{t-1} + i_t k_t
    y_t = (C_t q_t) / max(|n_t · q_t|, 1)
This is the SSD recurrence with (dt,B,C,x) := (i,k,q,v) and per-head k/q, so
we run the same chunked block-decomposition (dense MXU matmuls intra-chunk,
short scan inter-chunk); the normalizer rides along as an appended ones
column of v. sLSTM's stabilized exponential gating is inherently sequential
(running max m_t), so it uses lax.scan over time — faithful to the paper,
and the reason the xLSTM LMs keep sLSTM layers sparse.

Two block forms, selected by `cfg.xlstm_form`: "compact" (the repo's
12-block xlstm-125m) and "published" (arXiv:2405.04517's blocks, as
xlstm-7x1-125m runs them): see `init_mlstm` and `init_slstm`. The blocks'
work runs under the named scopes `mlstm` and `slstm`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.nn import layers

QKV_BLOCK = 4   # the published mLSTM's q/k/v block-diagonal block size


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def init_mlstm(key, cfg, dtype):
    if cfg.xlstm_form == "published":
        return _init_mlstm_published(key, cfg, dtype)
    d = cfg.d_model
    di = 2 * d                      # projection factor 2 (xLSTM paper)
    ks = layers.split(key, 8)
    return {
        "w_up": layers.dense_init(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, di)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "wq": layers.dense_init(ks[2], di, di, dtype),
        "wk": layers.dense_init(ks[3], di, di, dtype),
        "wv": layers.dense_init(ks[4], di, di, dtype),
        "w_gates": layers.dense_init(ks[5], di, 2 * cfg.num_heads, dtype),
        "out_norm": layers.init_rmsnorm(di, dtype),
        "w_down": layers.dense_init(ks[6], di, d, dtype),
    }


def _init_mlstm_published(key, cfg, dtype):
    """The paper's mLSTM layer: up-projection by 2 into (x_m, z), causal
    conv and SiLU on x_m, q/k from the conv and v from x_m through
    block-diagonal projections (blocks of `QKV_BLOCK`), input and
    forget gates from concat(q, k, v) with biases (weights 0, input bias
    N(0, 0.1), forget bias linspace(3, 6) over the heads), a head-wise
    LayerNorm without bias, a learnable skip from the conv output (ones),
    the SiLU(z) output gate and the down-projection."""
    d, H = cfg.d_model, cfg.num_heads
    di = 2 * d
    bs = QKV_BLOCK
    ks = layers.split(key, 7)

    def qkv(k):
        return (jax.random.normal(k, (di // bs, bs, bs)) / math.sqrt(bs)
                ).astype(dtype)

    b_gates = jnp.concatenate([jax.random.normal(ks[5], (H,)) * 0.1,
                               jnp.linspace(3.0, 6.0, H)])
    return {
        "w_up": layers.dense_init(ks[0], d, 2 * di, dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, di)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "wq": qkv(ks[2]), "wk": qkv(ks[3]), "wv": qkv(ks[4]),
        "w_gates": jnp.zeros((3 * di, 2 * H), dtype),
        "b_gates": b_gates.astype(dtype),
        "out_norm": layers.init_rmsnorm(di, dtype),
        "skip": jnp.ones((di,), dtype),
        "w_down": layers.dense_init(ks[6], di, d, dtype),
    }


def headwise_norm(scale, y, eps: float):
    """LayerNorm without bias over each head's features: y (B,S,H,P) ->
    (B,S,H*P) float32, scaled by `scale` (H*P,)."""
    B, S, H, P = y.shape
    y = y.astype(jnp.float32)
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.var(y, axis=-1, keepdims=True)
    y = (y - mu) * jax.lax.rsqrt(var + eps)
    return y.reshape(B, S, H * P) * scale.astype(jnp.float32)


def _causal_conv(p, x, cache_conv, decode: bool):
    """SiLU(causal depthwise conv) of x (B,S,C) -> (conv, new conv state);
    in decode, x is one token and `cache_conv` the last width-1 inputs."""
    if decode:
        window = jnp.concatenate([cache_conv, x], axis=1)
        conv = jax.nn.silu(jnp.einsum("bwc,wc->bc", window, p["conv_w"])
                           + p["conv_b"])[:, None, :]
        return conv, window[:, 1:, :]
    return _conv_silu(p["conv_w"], p["conv_b"], x), None


def _mlstm_published(p, cfg, x, *, return_cache: bool, cache, decode: bool):
    B, S, d = x.shape
    H = cfg.num_heads
    di = 2 * d
    P = di // H
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    xm, z = up[..., :di], up[..., di:]
    conv, new_conv_state = _causal_conv(p, xm, cache[0] if decode else None,
                                        decode)
    q, k = ops.blockdiag(conv, p["wq"], p["wk"])
    (v,) = ops.blockdiag(xm, p["wv"])
    gates = (jnp.einsum("bse,eh->bsh", jnp.concatenate([q, k, v], axis=-1),
                        p["w_gates"]).astype(jnp.float32)
             + p["b_gates"].astype(jnp.float32))
    logi, logf = gates[..., :H], jax.nn.log_sigmoid(gates[..., H:])
    q, k, v = (a.reshape(B, S, H, P) for a in (q, k, v))
    if decode:
        y, C = _mlstm_recurrent_step(q, k, v, logf, logi, cache[1])
        new_cache = (new_conv_state, C)
    else:
        y, C = mlstm_chunked(q, k, v, logf, logi, cfg.ssm_chunk)
        new_cache = ((xm[:, -(cfg.ssm_conv - 1):, :], C) if return_cache
                     else None)
    y = headwise_norm(p["out_norm"]["scale"], y, cfg.norm_eps)
    y = (y + p["skip"].astype(jnp.float32) * conv.astype(jnp.float32)
         ).astype(x.dtype) * jax.nn.silu(z)
    return jnp.einsum("bse,ed->bsd", y, p["w_down"]), new_cache


def _mlstm_recurrent_step(q, k, v, logf, logi, C):
    """One token of the mLSTM recurrence: q,k,v (B,1,H,P); logf,logi
    (B,1,H); C (B,H,P+1,P) with the normalizer as row P. -> y (B,1,H,P)
    float32, the new C."""
    f32 = jnp.float32
    P = q.shape[-1]
    ones = jnp.ones(v.shape[:-1] + (1,), f32)
    va = jnp.concatenate([v.astype(f32), ones], axis=-1)[:, 0]  # (B,H,P+1)
    C_new = (jnp.exp(logf[:, 0])[:, :, None, None] * C
             + jnp.exp(logi[:, 0])[:, :, None, None]
             * jnp.einsum("bhp,bhn->bhpn", va, k.astype(f32)[:, 0]))
    qs = q.astype(f32)[:, 0] * P ** -0.5
    ya = jnp.einsum("bhn,bhpn->bhp", qs, C_new)
    y = ya[..., :P] / jnp.maximum(jnp.abs(ya[..., P:]), 1.0)
    return y[:, None], C_new


def _conv_silu(w, b, x):
    cw = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(cw))
    return jax.nn.silu(out + b[None, None, :])


def mlstm_chunked(q, k, v, logf, logi, Q: int, state=None):
    """q,k,v (B,S,H,P); logf,logi (B,S,H). Returns y (B,S,H,P) f32, state.

    state = (C (B,H,Pv+1,Pk)) where row P_v is the normalizer.
    """
    B, S, H, P = q.shape
    f32 = jnp.float32
    Q = min(Q, S)
    assert S % Q == 0
    nc = S // Q
    ones = jnp.ones((B, S, H, 1), f32)
    va = jnp.concatenate([v.astype(f32), ones], axis=-1)     # (B,S,H,P+1)
    scale = P ** -0.5

    qc = (q.astype(f32) * scale).reshape(B, nc, Q, H, P)
    kc = k.astype(f32).reshape(B, nc, Q, H, P)
    vc = va.reshape(B, nc, Q, H, P + 1)
    ic = logi.astype(f32).reshape(B, nc, Q, H)
    fc = logf.astype(f32).reshape(B, nc, Q, H)
    cums = jnp.cumsum(fc, axis=2)
    total = cums[:, :, -1, :]

    Gm = jnp.einsum("bcihp,bcjhp->bcijh", qc, kc)            # (B,nc,Q,Q,H)
    Ld = cums[:, :, :, None, :] - cums[:, :, None, :, :] + ic[:, :, None, :, :]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # Mask before the exp: above the diagonal Ld grows with the chunk
    # (~0.7 per step at f = sigmoid(0)) and exp overflows past Q ~ 128; a
    # where() after the exp then sends 0 * inf = NaN into the gradient.
    W = Gm * jnp.exp(jnp.where(causal[None, None, :, :, None], Ld, -jnp.inf))
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, vc)

    decay_to_end = jnp.exp(total[:, :, None, :] - cums + ic)  # (B,nc,Q,H)
    Sc = jnp.einsum("bcjh,bcjhp,bcjhn->bchpn", decay_to_end, vc, kc)

    if state is None:
        state = jnp.zeros((B, H, P + 1, P), f32)

    def body(C, inp):
        tot_c, S_c = inp
        return jnp.exp(tot_c)[:, :, None, None] * C + S_c, C

    C_final, C_enter = jax.lax.scan(
        body, state, (total.transpose(1, 0, 2), Sc.transpose(1, 0, 2, 3, 4)))
    C_enter = C_enter.transpose(1, 0, 2, 3, 4)               # (B,nc,H,P+1,P)
    y_inter = jnp.einsum("bcihn,bcih,bchpn->bcihp",
                         qc, jnp.exp(cums), C_enter)
    ya = (y_intra + y_inter).reshape(B, S, H, P + 1)
    y = ya[..., :P] / jnp.maximum(jnp.abs(ya[..., P:]), 1.0)
    return y, C_final


def mlstm_block(p, cfg, x, *, return_cache: bool = False, cache=None,
                decode: bool = False):
    with jax.named_scope("mlstm"):
        if cfg.xlstm_form == "published":
            y, new_cache = _mlstm_published(p, cfg, x,
                                            return_cache=return_cache,
                                            cache=cache, decode=decode)
            return (y, new_cache) if return_cache or decode else y
        return _mlstm_compact(p, cfg, x, return_cache=return_cache,
                              cache=cache, decode=decode)


def _mlstm_compact(p, cfg, x, *, return_cache: bool, cache, decode: bool):
    B, S, d = x.shape
    H = cfg.num_heads
    di = 2 * d
    P = di // H
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    xm, z = up[..., :di], up[..., di:]
    conv, new_conv_state = _causal_conv(p, xm, cache[0] if decode else None,
                                        decode)
    q = jnp.einsum("bse,ef->bsf", conv, p["wq"]).reshape(B, S, H, P)
    k = jnp.einsum("bse,ef->bsf", conv, p["wk"]).reshape(B, S, H, P)
    v = jnp.einsum("bse,ef->bsf", xm, p["wv"]).reshape(B, S, H, P)
    gates = jnp.einsum("bse,eh->bsh", conv, p["w_gates"]).astype(jnp.float32)
    logi, fpre = gates[..., :H], gates[..., H:]
    logf = jax.nn.log_sigmoid(fpre)

    if decode:
        y, C_new = _mlstm_recurrent_step(q, k, v, logf, logi, cache[1])
        new_cache = (new_conv_state, C_new)
    else:
        y, C_final = mlstm_chunked(q, k, v, logf, logi, cfg.ssm_chunk)
        new_cache = None
        if return_cache:
            conv_state = xm[:, -(cfg.ssm_conv - 1):, :]
            new_cache = (conv_state, C_final)
    y = y.reshape(B, S, di).astype(x.dtype) * jax.nn.silu(z)
    y = layers.rmsnorm(p["out_norm"], y, cfg.norm_eps)
    y = jnp.einsum("bse,ed->bsd", y, p["w_down"])
    if return_cache or decode:
        return y, new_cache
    return y


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def init_slstm(key, cfg, dtype, index: int = 0):
    """`index` is the block's place in the stack, which the published
    form's forget-gate bias depends on."""
    if cfg.xlstm_form == "published":
        return _init_slstm_published(key, cfg, dtype, index)
    d = cfg.d_model
    H = cfg.num_heads
    P = d // H
    ks = layers.split(key, 4)
    return {
        "w_gates": layers.dense_init(ks[0], d, 4 * d, dtype),   # i,f,z,o per cell
        "r_gates": (jax.random.normal(ks[1], (4, H, P, P))
                    / math.sqrt(P)).astype(dtype),              # block-diag recurrence
        "out_norm": layers.init_rmsnorm(d, dtype),
        "w_up": layers.dense_init(ks[2], d, 2 * d, dtype),      # GLU ffn
        "w_down": layers.dense_init(ks[3], d, d, dtype),
    }


def _init_slstm_published(key, cfg, dtype, index: int):
    """The paper's sLSTM layer: a causal conv and SiLU feed the i and f
    gates, the raw input the z and o gates, each through a head-wise
    (block-diagonal) projection; recurrent head-wise kernels start at 0;
    the gate biases are 0 but the forget gate's power law over each
    head's units, -(-5 + 12 (j/(P-1))^(0.3 + 1.3 index/(L-1))); the cell's
    output goes through a head-wise LayerNorm without bias. The block's
    feed-forward lives in models/blocks.py."""
    d, H, L = cfg.d_model, cfg.num_heads, cfg.num_layers
    P = d // H
    ks = layers.split(key, 2)
    ratio = index / (L - 1) if L > 1 else 0.0
    f_bias = -(-5.0 + 12.0 * (jnp.arange(P) / (P - 1)) ** (0.3 + 1.3 * ratio))
    b_gates = jnp.zeros((4, H, P)).at[1].set(f_bias).reshape(4, d)
    return {
        "conv_w": (jax.random.normal(ks[0], (cfg.ssm_conv, d)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((d,), dtype),
        "w_gates": (jax.random.normal(ks[1], (4, H, P, P))
                    / math.sqrt(P)).astype(dtype),              # i,f,z,o per head
        "r_gates": jnp.zeros((4, H, P, P), dtype),
        "b_gates": b_gates.astype(dtype),
        "out_norm": layers.init_rmsnorm(d, dtype),
    }


def slstm_scan(gx, r, state, sigmoid_forget: bool = False):
    """gx (B,S,4,d) input gate pre-activations; r (4,H,P,P) recurrence.
    The forget gate is exp(f) (compact form) or, with `sigmoid_forget`,
    sigmoid(f) (the published form), both in log space.

    state = (c, n, m, h): c,n,h (B,d); m (B,d). Returns h_seq (B,S,d), state.
    """
    B, S, four, d = gx.shape
    H, P = r.shape[1], r.shape[2]

    def step(carry, g_t):
        c, n, m, h = carry
        hh = h.reshape(B, H, P)
        rec = jnp.einsum("ghpq,bhq->bghp", r.astype(jnp.float32), hh)
        rec = rec.reshape(B, four, d)
        g = g_t.astype(jnp.float32) + rec
        i_pre, f_pre, z_pre, o_pre = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
        if sigmoid_forget:
            f_pre = jax.nn.log_sigmoid(f_pre)
        m_new = jnp.maximum(f_pre + m, i_pre)
        i = jnp.exp(i_pre - m_new)
        f = jnp.exp(f_pre + m - m_new)
        c_new = f * c + i * jnp.tanh(z_pre)
        n_new = f * n + i
        h_new = jax.nn.sigmoid(o_pre) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    (c, n, m, h), hs = jax.lax.scan(step, state, gx.transpose(1, 0, 2, 3))
    return hs.transpose(1, 0, 2), (c, n, m, h)


def _zero_state(B, d):
    z = jnp.zeros((B, d), jnp.float32)
    return (z, z, jnp.full((B, d), -30.0, jnp.float32), z)


def slstm_block(p, cfg, x, *, return_cache: bool = False, cache=None,
                decode: bool = False):
    with jax.named_scope("slstm"):
        if cfg.xlstm_form == "published":
            y, new_cache = _slstm_published(p, cfg, x,
                                            return_cache=return_cache,
                                            cache=cache, decode=decode)
        else:
            y, new_cache = _slstm_compact(p, cfg, x, cache)
        return (y, new_cache) if return_cache or decode else y


def _slstm_published(p, cfg, x, *, return_cache: bool, cache, decode: bool):
    """-> (y, cache); the cache is (conv state, (c, n, m, h))."""
    B, S, d = x.shape
    H = cfg.num_heads
    P = d // H
    conv, new_conv_state = _causal_conv(p, x, cache[0] if decode else None,
                                        decode)
    u = jnp.stack([conv, conv, x, x], axis=2).reshape(B, S, 4, H, P)
    gx = (jnp.einsum("bsghq,ghpq->bsghp", u, p["w_gates"]).reshape(B, S, 4, d)
          .astype(jnp.float32) + p["b_gates"].astype(jnp.float32))
    state = cache[1] if cache is not None else _zero_state(B, d)
    hs, state = slstm_scan(gx, p["r_gates"], state, sigmoid_forget=True)
    y = headwise_norm(p["out_norm"]["scale"], hs.reshape(B, S, H, P),
                      cfg.norm_eps).astype(x.dtype)
    if not decode:
        new_conv_state = x[:, -(cfg.ssm_conv - 1):, :]
    return y, (new_conv_state, state)


def _slstm_compact(p, cfg, x, cache):
    B, S, d = x.shape
    gx = jnp.einsum("bsd,de->bse", x, p["w_gates"]).reshape(B, S, 4, d)
    state = _zero_state(B, d) if cache is None else cache
    hs, state = slstm_scan(gx, p["r_gates"], state)
    hs = layers.rmsnorm(p["out_norm"], hs.astype(x.dtype), cfg.norm_eps)
    up = jnp.einsum("bsd,de->bse", hs, p["w_up"])
    g, u = up[..., :d], up[..., d:]
    y = jnp.einsum("bsd,de->bse", jax.nn.silu(g) * u, p["w_down"])
    return y, state
