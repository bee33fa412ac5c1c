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
and the reason xLSTM-125m keeps sLSTM layers sparse (1-in-6 here).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.nn import layers


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def init_mlstm(key, cfg, dtype):
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
    B, S, d = x.shape
    H = cfg.num_heads
    di = 2 * d
    P = di // H
    up = jnp.einsum("bsd,de->bse", x, p["w_up"])
    xm, z = up[..., :di], up[..., di:]
    if decode:
        conv_state, C = cache
        window = jnp.concatenate([conv_state, xm], axis=1)
        cw = p["conv_w"].shape[0]
        conv = jax.nn.silu(jnp.einsum("bwc,wc->bc", window, p["conv_w"])
                           + p["conv_b"])[:, None, :]
        new_conv_state = window[:, 1:, :]
    else:
        conv = _conv_silu(p["conv_w"], p["conv_b"], xm)
    q = jnp.einsum("bse,ef->bsf", conv, p["wq"]).reshape(B, S, H, P)
    k = jnp.einsum("bse,ef->bsf", conv, p["wk"]).reshape(B, S, H, P)
    v = jnp.einsum("bse,ef->bsf", xm, p["wv"]).reshape(B, S, H, P)
    gates = jnp.einsum("bse,eh->bsh", conv, p["w_gates"]).astype(jnp.float32)
    logi, fpre = gates[..., :H], gates[..., H:]
    logf = jax.nn.log_sigmoid(fpre)

    if decode:
        # O(1) recurrent update
        f32 = jnp.float32
        scale = P ** -0.5
        ones = jnp.ones((B, 1, H, 1), f32)
        va = jnp.concatenate([v.astype(f32), ones], axis=-1)[:, 0]  # (B,H,P+1)
        C_new = (jnp.exp(logf[:, 0])[:, :, None, None] * C
                 + jnp.exp(logi[:, 0])[:, :, None, None]
                 * jnp.einsum("bhp,bhn->bhpn", va, k.astype(f32)[:, 0]))
        qs = q.astype(f32)[:, 0] * scale
        ya = jnp.einsum("bhn,bhpn->bhp", qs, C_new)
        y = ya[..., :P] / jnp.maximum(jnp.abs(ya[..., P:]), 1.0)
        y = y[:, None]                                       # (B,1,H,P)
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
def init_slstm(key, cfg, dtype):
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


def slstm_scan(gx, r, state):
    """gx (B,S,4,d) input gate pre-activations; r (4,H,P,P) recurrence.

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
        m_new = jnp.maximum(f_pre + m, i_pre)
        i = jnp.exp(i_pre - m_new)
        f = jnp.exp(f_pre + m - m_new)
        c_new = f * c + i * jnp.tanh(z_pre)
        n_new = f * n + i
        h_new = jax.nn.sigmoid(o_pre) * c_new / jnp.maximum(n_new, 1e-6)
        return (c_new, n_new, m_new, h_new), h_new

    (c, n, m, h), hs = jax.lax.scan(step, state, gx.transpose(1, 0, 2, 3))
    return hs.transpose(1, 0, 2), (c, n, m, h)


def slstm_block(p, cfg, x, *, return_cache: bool = False, cache=None,
                decode: bool = False):
    B, S, d = x.shape
    gx = jnp.einsum("bsd,de->bse", x, p["w_gates"]).reshape(B, S, 4, d)
    if cache is None:
        z = jnp.zeros((B, d), jnp.float32)
        state = (z, z, jnp.full((B, d), -30.0, jnp.float32), z)
    else:
        state = cache
    hs, state = slstm_scan(gx, p["r_gates"], state)
    hs = layers.rmsnorm(p["out_norm"], hs.astype(x.dtype), cfg.norm_eps)
    up = jnp.einsum("bsd,de->bse", hs, p["w_up"])
    g, u = up[..., :d], up[..., d:]
    y = jnp.einsum("bsd,de->bse", jax.nn.silu(g) * u, p["w_down"])
    if return_cache or decode:
        return y, state
    return y
