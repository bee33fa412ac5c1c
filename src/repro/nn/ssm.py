"""Mamba2 block via the chunked SSD algorithm (TPU-native form).

The GPU Mamba2 kernel is a fused warp-level scan; the TPU-idiomatic
equivalent is the SSD block-decomposition: intra-chunk work becomes dense
(Q×Q)·(Q×P) matmuls on the MXU, inter-chunk state is a short lax.scan over
S/Q affine steps. Recurrence (per head h, scalar A):

    h_t = exp(A·dt_t) h_{t-1} + dt_t · B_t ⊗ x_t      (state: (P, N))
    y_t = C_t · h_t + D ⊙ x_t

Decode keeps (conv_state, ssm_state) in the cache and does the O(1) update.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn import layers


def init_mamba2(key, cfg, dtype):
    d, di, N = cfg.d_model, cfg.d_inner, cfg.ssm_state
    H, P, cw = cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_conv
    conv_ch = di + 2 * N
    ks = layers.split(key, 4)
    return {
        "w_in": layers.dense_init(ks[0], d, 2 * di + 2 * N + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (cw, conv_ch)) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "out_norm": layers.init_rmsnorm(di, dtype),
        "w_out": layers.dense_init(ks[2], di, d, dtype),
    }


def _split_proj(p, cfg, x):
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.mamba_heads
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["w_in"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., di + di + 2 * N:]                       # (B,S,H)
    return z, xbc, dt


def _causal_conv(w, b, x):
    """Depthwise causal conv. x (B,S,C); w (cw,C)."""
    cw = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(cw))
    return jax.nn.silu(out + b[None, None, :])


def ssd_chunked(xh, Bm, Cm, dt, A, Q: int, h0=None):
    """Chunked SSD scan.

    xh (B,S,H,P); Bm/Cm (B,S,N); dt (B,S,H) (post-softplus); A (H,) negative.
    Returns y (B,S,H,P) float32 and final state (B,H,P,N).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(Q, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    f32 = jnp.float32

    a = (dt.astype(f32) * A[None, None, :])                  # (B,S,H) negative
    xh = xh.astype(f32).reshape(Bsz, nc, Q, H, P)
    Bc = Bm.astype(f32).reshape(Bsz, nc, Q, N)
    Cc = Cm.astype(f32).reshape(Bsz, nc, Q, N)
    dtc = dt.astype(f32).reshape(Bsz, nc, Q, H)
    ac = a.reshape(Bsz, nc, Q, H)
    cums = jnp.cumsum(ac, axis=2)                            # inclusive
    total = cums[:, :, -1, :]                                # (B,nc,H)

    # --- intra-chunk (dense, MXU) ---
    Gm = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)               # (B,nc,Q,Q)
    Ld = cums[:, :, :, None, :] - cums[:, :, None, :, :]     # (B,nc,Q,Q,H) i,j
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    # mask before the exp: above the diagonal exp(Ld) overflows on long
    # chunks, and 0 * inf in the where()'s gradient would be NaN
    L = jnp.exp(jnp.where(causal[None, None, :, :, None], Ld, -jnp.inf))
    W = Gm[..., None] * L * dtc[:, :, None, :, :]            # weight (i,j,h)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, xh)

    # --- chunk summary states ---
    decay_to_end = jnp.exp(total[:, :, None, :] - cums)      # (B,nc,Q,H)
    Sc = jnp.einsum("bcjh,bcjn,bcjhp->bchpn",
                    dtc * decay_to_end, Bc, xh)              # (B,nc,H,P,N)

    # --- inter-chunk scan ---
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), f32)

    def body(h, inp):
        tot_c, S_c = inp                                     # (B,H), (B,H,P,N)
        h_next = jnp.exp(tot_c)[:, :, None, None] * h + S_c
        return h_next, h                                     # emit state *entering* chunk

    (h_final, h_enter) = jax.lax.scan(
        body, h0, (total.transpose(1, 0, 2), Sc.transpose(1, 0, 2, 3, 4)))
    h_enter = h_enter.transpose(1, 0, 2, 3, 4)               # (B,nc,H,P,N)

    y_inter = jnp.einsum("bcin,bcih,bchpn->bcihp",
                         Cc, jnp.exp(cums), h_enter)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P)
    return y, h_final


def mamba2_block(p, cfg, x, *, return_cache: bool = False):
    """x (B,S,d) -> y (B,S,d) [, cache=(conv_state, ssm_state)]."""
    B, S, d = x.shape
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.mamba_heads, cfg.mamba_head_dim
    z, xbc, dt = _split_proj(p, cfg, x)
    xbc_conv = _causal_conv(p["conv_w"], p["conv_b"], xbc)
    xs = xbc_conv[..., :di].reshape(B, S, H, P)
    Bm = xbc_conv[..., di:di + N]
    Cm = xbc_conv[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])
    y, h_final = ssd_chunked(xs, Bm, Cm, dt, A, cfg.ssm_chunk)
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, di).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = layers.rmsnorm(p["out_norm"], y, cfg.norm_eps)
    y = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    if return_cache:
        conv_state = xbc[:, -(cfg.ssm_conv - 1):, :]         # last cw-1 inputs
        return y, (conv_state, h_final)
    return y


def mamba2_decode(p, cfg, x, cache):
    """One-token decode. x (B,1,d); cache=(conv_state (B,cw-1,C), h (B,H,P,N))."""
    B = x.shape[0]
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.mamba_heads, cfg.mamba_head_dim
    conv_state, h = cache
    z, xbc, dt = _split_proj(p, cfg, x)                      # (B,1,·)
    window = jnp.concatenate([conv_state, xbc], axis=1)      # (B,cw,C)
    conv = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    conv = jax.nn.silu(conv)[:, None, :]                     # (B,1,C)
    xs = conv[..., :di].reshape(B, H, P)
    Bm = conv[:, 0, di:di + N]
    Cm = conv[:, 0, di + N:]
    dt = jax.nn.softplus(dt[:, 0, :].astype(jnp.float32)
                         + p["dt_bias"][None, :])            # (B,H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dt * A[None, :])                         # (B,H)
    h_new = (decay[:, :, None, None] * h
             + jnp.einsum("bh,bn,bhp->bhpn", dt, Bm.astype(jnp.float32),
                          xs.astype(jnp.float32)))
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), h_new)
    y = y + p["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, 1, di).astype(x.dtype) * jax.nn.silu(z)
    y = layers.rmsnorm(p["out_norm"], y, cfg.norm_eps)
    y = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    new_conv_state = window[:, 1:, :]
    return y, (new_conv_state, h_new)
