"""The local SGD step's fused axpy, θ_half − η·g (two reads, one write),
which every strategy's ``_sgd_step`` runs under ``use_pallas``.

One VMEM-resident pass over its operands: arithmetic intensity is tiny
(<1 flop/byte), so all it saves is HBM traffic.  It runs on each leaf in
the leaf's own shape and layout: the block tiles the last two dimensions,
the grid runs over the leading ones one index at a time, and Pallas masks
the edge blocks.  So a stacked leaf such as ``(layers, d_model, d_ff)``
reaches the kernel with no re-layout copy on the TPU, where an array is
stored in (8, 128) tiles of its last two dimensions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128
AXPY_BLOCK_COLS = 1024    # the axpy block's last dim: 8 lane tiles
AXPY_BLOCK_ELEMS = 512 * AXPY_BLOCK_COLS   # 2 MiB of fp32 per operand


def _axpy_kernel(x_ref, y_ref, o_ref, *, a):
    o_ref[...] = x_ref[...] + a * y_ref[...]


def _axpy_block(shape, dtype):
    """The (rows, cols) block of a leaf's last two dims: whole where a
    dim fits, else cols 1024 and as many rows as fill AXPY_BLOCK_ELEMS
    of VMEM (lanes padded to 128) in whole sublane tiles (8 rows of fp32,
    16 of bf16).  Three operands, double-buffered, stay under 16 MiB."""
    rows, cols = shape[-2:]
    bc = min(cols, AXPY_BLOCK_COLS)
    sub = 32 // jnp.dtype(dtype).itemsize
    cap = max(sub, AXPY_BLOCK_ELEMS // (pl.cdiv(bc, LANE) * LANE) // sub * sub)
    return min(rows, cap), bc


def fused_axpy(x, y, a, interpret=False):
    """x + a·y for same-shape, same-dtype x and y, in their own layout.
    A 0-D or 1-D leaf is viewed as one row."""
    shape = x.shape
    view = (1,) * (2 - x.ndim) + shape
    x, y = x.reshape(view), y.reshape(view)
    *lead, rows, cols = view
    br, bc = _axpy_block(view, x.dtype)
    spec = pl.BlockSpec((*[pl.squeezed] * len(lead), br, bc), lambda *i: i)
    out = pl.pallas_call(
        functools.partial(_axpy_kernel, a=a),
        grid=(*lead, pl.cdiv(rows, br), pl.cdiv(cols, bc)),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(view, x.dtype),
        interpret=interpret,
    )(x, y)
    return out.reshape(shape)
