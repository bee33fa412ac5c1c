"""Basic functional layers: init helpers, norms, linear, embedding, MLPs.

Everything is a pair of functions: `init_*` returning a dict-of-arrays param
tree, and an apply function taking (params, inputs). No module objects — the
pytrees compose naturally with jax.jit / scan / grad and keep the sharding
rules (sharding.py) path-addressable.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)
            * 0.02).astype(dtype)


def split(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype=dtype),
            "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm(params, x, eps: float = 1e-5):
    """LayerNorm over the last axis; without a "bias" leaf it has none."""
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(dt)


def init_norm(kind: str, d: int, dtype):
    if kind in ("rmsnorm", "layernorm_nobias"):     # one scale leaf
        return init_rmsnorm(d, dtype)
    return init_layernorm(d, dtype)


def apply_norm(kind: str, params, x, eps: float):
    return rmsnorm(params, x, eps) if kind == "rmsnorm" else layernorm(params, x, eps)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_swiglu(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = split(key, 3)
    return {"w_gate": dense_init(k1, d_model, d_ff, dtype),
            "w_up": dense_init(k2, d_model, d_ff, dtype),
            "w_down": dense_init(k3, d_ff, d_model, dtype)}


def swiglu(params, x):
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, params["w_down"])


def init_gelu_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2 = split(key, 2)
    return {"w_up": dense_init(k1, d_model, d_ff, dtype),
            "b_up": jnp.zeros((d_ff,), dtype=dtype),
            "w_down": dense_init(k2, d_ff, d_model, dtype),
            "b_down": jnp.zeros((d_model,), dtype=dtype)}


def gelu_mlp(params, x):
    h = jnp.einsum("...d,df->...f", x, params["w_up"]) + params["b_up"]
    h = jax.nn.gelu(h)
    return jnp.einsum("...f,fd->...d", h, params["w_down"]) + params["b_down"]


def geglu(params, x):
    """The gated feed-forward with an exact (erf) GeLU gate, in the swiglu
    layout: w_down(gelu(x w_gate) * x w_up)."""
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    return jnp.einsum("...f,fd->...d", jax.nn.gelu(g, approximate=False) * u,
                      params["w_down"])


def init_mlp(kind: str, key, d_model: int, d_ff: int, dtype):
    return (init_swiglu(key, d_model, d_ff, dtype) if kind in ("swiglu", "geglu")
            else init_gelu_mlp(key, d_model, d_ff, dtype))


def apply_mlp(kind: str, params, x):
    return {"swiglu": swiglu, "geglu": geglu}.get(kind, gelu_mlp)(params, x)
