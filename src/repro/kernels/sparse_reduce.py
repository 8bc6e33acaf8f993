"""Pallas scatter-accumulate kernel: the sparse server aggregate.

The sparse top-k uplink (transport.SparseTopKCodec) ships each client's
delta as per-leaf ``(values, indices)`` pairs, but until this kernel the
server decoded every client to dense before ``weighted_delta_reduce`` —
aggregation FLOPs and memory traffic scaled with the parameter count d
even at ``topk_frac = 0.01``.  This kernel segment-sums the K stacked
wire pairs straight into one dense output leaf:

    out[idx_{k,j}] += w_k · values_{k,j}      (K · k adds per block)

Tiling: the output is cut into row blocks of ``BLOCK_ROWS`` × 128 and
the grid is (row block, client, pair chunk) with the client and chunk axes
innermost, so each output block stays resident while every client's
pairs stream past it.  The fp32 output ref IS the accumulator, so
accumulation is fp32 whatever the wire dtype (the cast-on-write precision
contract: the ops.py wrapper casts to the wire dtype exactly once, on the
final write).  Each step adds the pairs whose index falls in its block;
the others add an exact +0.0 at the block's first element.  Per output
element the adds therefore run in client-major, pair order — the same
order as the segment-sum oracle in kernels/ref.py, bit for bit, duplicate
indices included (scatter-add).

Every block shape obeys the 8 × 128 tiling: pairs arrive as
(K, rows, 128) chunks of ``CHUNK_ROWS`` rows, and the per-client weights
ride in SMEM as scalar-prefetch operands.  Each block visits every pair,
so the work is (blocks × K · k); binning the pairs by block first is the
k-cost version.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
BLOCK_ROWS = 512          # output rows per block: 512×128 fp32 = 256 KiB
CHUNK_ROWS = 64           # wire rows per step: 64×128 pairs


def _sparse_reduce_kernel(w_ref, v_ref, i_ref, o_ref, *, block_elems):
    # w (K,) fp32 in SMEM; v/i (1, chunk, LANE) — one chunk of one
    # client's pairs; o (block, LANE) fp32, revisited across the client
    # and chunk axes: the ref is the accumulator.
    r, k, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((k == 0) & (c == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    local = i_ref[0].reshape(-1) - r * block_elems
    inb = (local >= 0) & (local < block_elems)
    wv = jnp.where(inb, w_ref[k] * v_ref[0].reshape(-1).astype(jnp.float32),
                   0.0)
    flat = o_ref[...].reshape(-1)
    flat = flat.at[jnp.where(inb, local, 0)].add(wv)
    o_ref[...] = flat.reshape(o_ref.shape)


def sparse_reduce_2d(values, indices, weights, rows, interpret=False):
    """values (K, kp), indices (K, kp) int32 into the flattened (rows·LANE,)
    output, weights (K,) -> (rows, LANE) fp32 = Σ_k w_k · scatter(v_k @ i_k).

    kp and rows·LANE are lane-aligned by the ops.py wrapper (k-padding uses
    (value 0, index 0) pairs — weighted zeros accumulate as exact +0.0).
    The caller casts the fp32 result to the wire dtype (cast-on-write)."""
    k_clients, kp = values.shape
    krows = kp // LANE
    chunk = min(krows, CHUNK_ROWS)
    pad = (-krows) % chunk
    if pad:
        # more (value 0, index 0) filler rows: exact +0.0 adds
        values = jnp.pad(values, ((0, 0), (0, pad * LANE)))
        indices = jnp.pad(indices, ((0, 0), (0, pad * LANE)))
        krows += pad
    block = min(rows, BLOCK_ROWS)
    pair_spec = pl.BlockSpec((1, chunk, LANE), lambda r, k, c, w: (k, c, 0))
    return pl.pallas_call(
        functools.partial(_sparse_reduce_kernel, block_elems=block * LANE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, block), k_clients, krows // chunk),
            in_specs=[pair_spec, pair_spec],
            out_specs=pl.BlockSpec((block, LANE),
                                   lambda r, k, c, w: (r, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(weights.astype(jnp.float32),
      values.reshape(k_clients, krows, LANE),
      indices.reshape(k_clients, krows, LANE))
