"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

Each function mirrors the kernel's contract exactly; kernel tests sweep
shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_EPS = 1e-7


def flash_attention(q, k, v, *, causal: bool = True):
    """q (B,Sq,H,hd); k,v (B,Sk,G,hd); GQA-aware naive attention, f32 math."""
    B, Sq, H, hd = q.shape
    G = k.shape[2]
    Hr = H // G
    qf = (q.reshape(B, Sq, G, Hr, hd) * (hd ** -0.5)).astype(jnp.float32)
    s = jnp.einsum("bqghd,bkgd->bgqhk", qf, k.astype(jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((Sq, k.shape[1]), bool))
        s = jnp.where(mask[None, None, :, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgqhk,bkgd->bgqhd", p, v.astype(jnp.float32))
    return o.transpose(0, 2, 1, 3, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def proto_accum(features, labels, num_classes: int):
    """features (n, d) -> per-class sums (C, d) f32 and counts (C,) f32."""
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    sums = jnp.einsum("nc,nd->cd", onehot, features.astype(jnp.float32))
    counts = jnp.sum(onehot, axis=0)
    return sums, counts


def disc_loss(student_logits, teacher_probs, labels, valid=None):
    """Per-sample CoRS discriminator loss (Eq. 7).

    student_logits (B, C); teacher_probs (M, C) already softmaxed;
    labels (B,) index into the M axis (observation m of class c sits at
    row c, so M == C in the paper's layout). Returns (B,) f32.
    """
    p = jax.nn.softmax(student_logits.astype(jnp.float32), axis=-1)
    h = jnp.clip(p @ teacher_probs.astype(jnp.float32).T, _EPS, 1.0 - _EPS)
    M = teacher_probs.shape[0]
    pos = jax.nn.one_hot(labels, M, dtype=jnp.float32)
    v = jnp.ones((M,), jnp.float32) if valid is None else valid.astype(jnp.float32)
    per_pair = -(pos * jnp.log(h) + (1.0 - pos) * jnp.log1p(-h)) * v[None, :]
    return jnp.sum(per_pair, axis=-1)


def blockdiag(x, w):
    """x (..., nb*bs) through the block-diagonal w (nb, bs, bs)."""
    nb, bs, _ = w.shape
    y = jnp.einsum("...np,npq->...nq", x.reshape(x.shape[:-1] + (nb, bs)), w)
    return y.reshape(x.shape)
