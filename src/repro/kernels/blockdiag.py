"""Pallas TPU kernel: a block-diagonal projection (the published mLSTM's
q/k/v, blocks of 4).

x (n, di) times a block-diagonal matrix of di/bs blocks (bs, bs). XLA
lowers the einsum over a (..., di/bs, bs) view as a grouped convolution
with the sequence on the lanes, and copies every activation into that
layout and back out of it. Here the matrix is cut into 128-lane tiles:
tile t is the (128, 128) block-diagonal matrix of blocks 128t/bs ..
128(t+1)/bs - 1, zero elsewhere. A grid step loads a lane-dense
(BLOCK_ROWS, di) block of rows and multiplies each 128-lane slice on the
MXU by its tile, accumulating in float32 and rounding once to x's dtype.
The off-diagonal products are exact zeros, so the result is the einsum's.

`project(x, tiles)` reads x once and writes one output per tile set (q
and k from the conv output share a call). Its VJP:
  dx       = sum_m dy_m @ tiles_m^T: the same kernel on the transposed
             tiles, with the cotangents summed in the kernel;
  dtiles_m = x_t^T dy_m,t for each 128-lane slice t, accumulated in
             float32 over the row blocks.
`tiles` assembles the tiles from the (di/bs, bs, bs) parameters; autodiff
of that assembly takes the diagonal blocks of dtiles, which is exact.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 512


def supports(rows: int, di: int, bs: int) -> bool:
    """Whether the kernel takes `rows` rows of width `di` in blocks of
    `bs`: whole row blocks and whole 128-lane tiles."""
    return rows % BLOCK_ROWS == 0 and di % LANES == 0 and LANES % bs == 0


def tiles(ws):
    """ws (m, di/bs, bs, bs) -> (m, di/128, 128, 128) block-diagonal
    tiles, zero off the (bs, bs) blocks; elementwise, so exact."""
    m, nb, bs, _ = ws.shape
    g = LANES // bs
    w = ws.reshape(m, nb // g, g, bs, 1, bs)
    eye = jnp.eye(g, dtype=ws.dtype)[:, None, :, None]       # (g,1,g,1)
    return (w * eye).reshape(m, nb // g, LANES, LANES)


def _lanes(j):
    return slice(j * LANES, (j + 1) * LANES)


def _fan_out_kernel(x_ref, t_ref, *y_refs):
    """y_m = x @ blockdiag(t_m) for each m."""
    for j in range(t_ref.shape[1]):
        xs = x_ref[:, _lanes(j)]
        for m, y_ref in enumerate(y_refs):
            y_ref[:, _lanes(j)] = jnp.dot(
                xs, t_ref[m, j], preferred_element_type=jnp.float32
            ).astype(y_ref.dtype)


def _fan_in_kernel(t_ref, *refs):
    """dx = sum_m dy_m @ blockdiag(t_m), summed in float32."""
    *dy_refs, dx_ref = refs
    for j in range(t_ref.shape[1]):
        acc = sum(jnp.dot(dy_ref[:, _lanes(j)], t_ref[m, j],
                          preferred_element_type=jnp.float32)
                  for m, dy_ref in enumerate(dy_refs))
        dx_ref[:, _lanes(j)] = acc.astype(dx_ref.dtype)


def _grad_kernel(x_ref, *refs):
    """dt_m,j += x_j^T dy_m,j over the row blocks, in float32."""
    *dy_refs, dt_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dt_ref[...] = jnp.zeros_like(dt_ref)

    for j in range(dt_ref.shape[1]):
        xs = x_ref[:, _lanes(j)]
        for m, dy_ref in enumerate(dy_refs):
            dt_ref[m, j] += jax.lax.dot_general(
                xs, dy_ref[:, _lanes(j)], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def _run(kernel, args, in_specs, out_specs, out_shape, *, rows, sequential,
         interpret):
    """One pallas_call over the row blocks. VMEM holds each block twice
    (double-buffered) plus a float32 slice and room for the compiler."""
    blocks = sum(math.prod(s.block_shape) * a.dtype.itemsize
                 for s, a in zip(in_specs + out_specs, (*args, *out_shape)))
    return pl.pallas_call(
        kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential else "parallel",),
            vmem_limit_bytes=2 * blocks + BLOCK_ROWS * LANES * 4 + (8 << 20)),
        interpret=interpret,
    )(*args)


def _specs(di, t_shape):
    """(a block of rows, the whole tile array)"""
    return (pl.BlockSpec((BLOCK_ROWS, di), lambda i: (i, 0)),
            pl.BlockSpec(t_shape, lambda i: (0,) * len(t_shape)))


def _fan_out(x, t, *, interpret):
    """x (n, di), t (m, di/128, 128, 128) -> m outputs (n, di) x.dtype."""
    (n, di), m = x.shape, t.shape[0]
    blk, whole = _specs(di, t.shape)
    return _run(_fan_out_kernel, (x, t), [blk, whole], [blk] * m,
                [jax.ShapeDtypeStruct((n, di), x.dtype)] * m, rows=n,
                sequential=False, interpret=interpret)


def _fan_in(dys, t, *, interpret):
    """m cotangents (n, di), t (m, di/128, 128, 128) -> one (n, di)."""
    n, di = dys[0].shape
    blk, whole = _specs(di, t.shape)
    (dx,) = _run(_fan_in_kernel, (t, *dys), [whole] + [blk] * len(dys),
                 [blk], [jax.ShapeDtypeStruct((n, di), dys[0].dtype)],
                 rows=n, sequential=False, interpret=interpret)
    return dx


def _grad(x, dys, *, interpret):
    """x and m cotangents (n, di) -> (m, di/128, 128, 128) float32."""
    n, di = x.shape
    shape = (len(dys), di // LANES, LANES, LANES)
    blk, whole = _specs(di, shape)
    (dt,) = _run(_grad_kernel, (x, *dys), [blk] * (1 + len(dys)), [whole],
                 [jax.ShapeDtypeStruct(shape, jnp.float32)], rows=n,
                 sequential=True, interpret=interpret)
    return dt


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def project(x, t, interpret: bool):
    """x (n, di) through each tile set of t (m, di/128, 128, 128) -> a
    tuple of m arrays (n, di) in x's dtype. n % BLOCK_ROWS == 0."""
    return tuple(_fan_out(x, t, interpret=interpret))


def _project_fwd(x, t, interpret):
    return project(x, t, interpret), (x, t)


def _project_bwd(interpret, res, dys):
    x, t = res
    dx = _fan_in(dys, jnp.swapaxes(t, -1, -2), interpret=interpret)
    dt = _grad(x, dys, interpret=interpret)
    return dx.astype(x.dtype), dt.astype(t.dtype)


project.defvjp(_project_fwd, _project_bwd)
