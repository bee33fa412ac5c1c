"""LeNet5-style CNN — the paper's own MNIST model (≈30K params, d'=84).

f_u = τ_u ∘ φ_u: `features` returns the d'-dim last-hidden representation
(the thing CoRS shares); `classify` is the linear head τ_u. A `wide` variant
(ResNet9-ish capacity stand-in, still cheap on CPU) exercises the paper's
"larger model" regime for the benchmarks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.nn import layers


def init_cnn(key, *, num_classes: int = 10, d_feature: int = 84,
             in_ch: int = 1, width: int = 1, image: int = 28):
    ks = layers.split(key, 6)
    c1, c2 = 6 * width, 16 * width
    # image -> conv5 -> pool2 -> conv5 -> pool2
    s1 = (image - 4) // 2
    s2 = (s1 - 4) // 2
    flat = c2 * s2 * s2
    conv = lambda k, ci, co: (jax.random.normal(k, (5, 5, ci, co))
                              * math.sqrt(2.0 / (25 * ci))).astype(jnp.float32)
    return {
        "conv1": conv(ks[0], in_ch, c1), "b1": jnp.zeros((c1,)),
        "conv2": conv(ks[1], c1, c2), "b2": jnp.zeros((c2,)),
        "fc1": layers.dense_init(ks[2], flat, 120 * width, jnp.float32),
        "fb1": jnp.zeros((120 * width,)),
        "fc2": layers.dense_init(ks[3], 120 * width, d_feature, jnp.float32),
        "fb2": jnp.zeros((d_feature,)),
        # τ_u — the linear classifier (W_u, b_u) of the paper
        "head_w": layers.dense_init(ks[4], d_feature, num_classes, jnp.float32),
        "head_b": jnp.zeros((num_classes,)),
    }


def _valid_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@jax.custom_vjp
def _conv2d(x, w):
    """`_valid_conv` with autodiff's backward, except for the weight
    gradient of a one-channel input (`_one_channel_weight_grad`)."""
    return _valid_conv(x, w)


def _conv2d_fwd(x, w):
    return _valid_conv(x, w), (x, w)


def _conv2d_bwd(res, g):
    x, w = res
    dx, = jax.linear_transpose(lambda x: _valid_conv(x, w), x)(g)
    if x.shape[-1] == 1:
        dw = _one_channel_weight_grad(x, g, w.shape[0])
    else:
        # wider inputs keep the transposed conv: for LeNet5's conv2 it beat
        # every explicit form tried on a TPU v5e (PERF.md, section 6)
        dw, = jax.linear_transpose(lambda w: _valid_conv(x, w), w)(g)
    return dx, dw


_conv2d.defvjp(_conv2d_fwd, _conv2d_bwd)


def _one_channel_weight_grad(x, g, k):
    """dL/dw of `_valid_conv` for x (B, H, W, 1), g (B, Ho, Wo, Co): per
    kernel tap, the input window times g, summed over (image, h, w) in
    float32.

    Transposing the conv instead gives a convolution whose window is the
    whole Ho x Wo output map, with the images as its features; vmapped
    over a fleet's clients, a TPU v5e runs LeNet5's 24x24 one-channel case
    at about 5% of its memory bandwidth. Here each tap is a plain
    reduction, and a vmapped client axis stays a batch axis."""
    ho, wo = g.shape[1:3]
    x = x[..., 0]
    taps = [jnp.sum(x[:, i:i + ho, j:j + wo, None] * g, axis=(0, 1, 2),
                    dtype=jnp.float32)
            for i in range(k) for j in range(k)]
    return jnp.stack(taps).reshape(k, k, 1, g.shape[-1]).astype(g.dtype)


def _conv(x, w, b):
    return jax.nn.relu(_conv2d(x, w) + b[None, None, None, :])


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def features(params, x):
    """φ_u: x (B, H, W, C) -> s (B, d').

    The feature layer is tanh (as in LeNet5's F6): CoRS shares and regresses
    onto these representations (L_KD), and a bounded feature space keeps
    ‖s − t̄‖² well-scaled at the paper's λ_KD = 10 — with unbounded ReLU
    features the KD pull dominates CE and collapses training (see
    EXPERIMENTS.md §Paper-claims notes)."""
    h = _pool(_conv(x, params["conv1"], params["b1"]))
    h = _pool(_conv(h, params["conv2"], params["b2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ params["fc1"] + params["fb1"])
    h = jnp.tanh(h @ params["fc2"] + params["fb2"])
    return h


def classify(params, s):
    """τ_u: s (B, d') -> logits (B, C)."""
    return s @ params["head_w"] + params["head_b"]


def apply(params, x):
    s = features(params, x)
    return s, classify(params, s)


def num_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))
