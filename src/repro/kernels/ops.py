"""Jit'd public wrappers for the Pallas kernels.

Backend dispatch: on TPU the Pallas kernels run compiled; everywhere else
(this CPU container, dry-run lowering) they run via the pure-jnp oracles in
ref.py (identical math), or in interpret mode when `interpret=True` is forced
(kernel correctness tests). This keeps `use_kernel=True` call sites portable.
`blockdiag` also dispatches by shape, and counts the path each projection
takes (`obs.trace` counters `blockdiag_kernel`, `blockdiag_ref`) while it
is traced.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import blockdiag as _bd
from repro.kernels import disc_loss as _dl
from repro.kernels import flash_attention as _fa
from repro.kernels import proto_accum as _pa
from repro.kernels import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False):
    if interpret or _on_tpu():
        return _fa.flash_attention(q, k, v, causal=causal,
                                   interpret=interpret or not _on_tpu())
    return ref.flash_attention(q, k, v, causal=causal)


@partial(jax.jit, static_argnames=("num_classes", "interpret"))
def proto_accum(features, labels, num_classes: int, *,
                interpret: bool = False):
    if interpret or _on_tpu():
        return _pa.proto_accum(features, labels, num_classes,
                               interpret=interpret or not _on_tpu())
    return ref.proto_accum(features, labels, num_classes)


@partial(jax.jit, static_argnames=("interpret",))
def disc_loss(student_logits, teacher_probs, labels, valid=None, *,
              interpret: bool = False):
    if interpret or _on_tpu():
        return _dl.disc_loss(student_logits, teacher_probs, labels, valid,
                             interpret=interpret or not _on_tpu())
    return ref.disc_loss(student_logits, teacher_probs, labels, valid)


def blockdiag(x, *ws, interpret: bool = False):
    """x (..., di) through each block-diagonal w (di/bs, bs, bs) -> a tuple
    with one (..., di) per w. The kernel reads x once for all the ws; it
    runs where its shapes fit (`blockdiag.supports`: whole row blocks and
    128-lane tiles), compiled on TPU or interpreted when forced, and the
    einsum everywhere else (one token of decode, the CPU)."""
    di, bs = x.shape[-1], ws[0].shape[-1]
    rows = math.prod(x.shape[:-1])
    if (interpret or _on_tpu()) and _bd.supports(rows, di, bs):
        obs.trace.count("blockdiag_kernel", len(ws))
        ys = _bd.project(x.reshape(rows, di), _bd.tiles(jnp.stack(ws)),
                         interpret or not _on_tpu())
        return tuple(y.reshape(x.shape) for y in ys)
    obs.trace.count("blockdiag_ref", len(ws))
    return tuple(ref.blockdiag(x, w) for w in ws)
