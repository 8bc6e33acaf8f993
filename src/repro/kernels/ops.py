"""jit'd public wrappers around the Pallas kernels.

On non-TPU backends every kernel runs in ``interpret=True`` mode (the body
executes as plain JAX on CPU) so the whole framework stays runnable and
testable in this container; on TPU the same call sites compile to Mosaic.

A Mosaic kernel cannot be partitioned by the compiler.  Under a
multi-device mesh declared with ``jax.set_mesh``, each wrapper therefore
runs its kernel in ``shard_map``: every device applies the kernel to its
own shard.  Per-element kernels over parameter trees take each leaf's
own layout from ``sharding.specs`` (no gather); flash attention keeps its
batch and head sharding and gathers the sequence.  Without such a mesh
(one device, or the vmapped simulator rounds) the kernel is called as is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels import compress as _cp
from repro.kernels import fedadc_update as _fu
from repro.kernels import flash_attention as _fa
from repro.kernels import kd_loss as _kd
from repro.kernels import ref as _ref
from repro.kernels import sparse_reduce as _sr
from repro.kernels import ssd_scan as _ssd
from repro.kernels import weighted_reduce as _wr

LANE = _fu.LANE


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _mesh():
    """The multi-device mesh declared with ``jax.set_mesh``, else None."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or mesh.size == 1 else mesh


def _on_shards(fn, in_specs, out_specs, *args):
    """``fn(*args)``, run by each device on its own shards under a declared
    multi-device mesh (the partitioner reshards operands to `in_specs`)."""
    if _mesh() is None:
        return fn(*args)
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)(*args)


def leaf_spec(path, shape):
    """The partition spec the pod engine gives a parameter-shaped leaf (a
    stacked leaf passes its per-client shape)."""
    mesh = _mesh()
    if mesh is None:
        return P()
    from repro.sharding.specs import spec_for_param  # lazy: layering
    return spec_for_param(path, shape, mesh)


# ---------------------------------------------------------------------------
# elementwise fused updates — applied leaf-wise over parameter pytrees
# ---------------------------------------------------------------------------
def _as_tiles(x):
    flat = x.reshape(-1)
    pad = (-flat.size) % LANE
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANE), pad


def _from_tiles(t, pad, shape, dtype):
    flat = t.reshape(-1)
    if pad:
        flat = flat[:flat.size - pad]
    return flat.reshape(shape).astype(dtype)


def fused_axpy(x, y, a, spec=P()):
    """x + a·y on a single leaf laid out as `spec` under a mesh; the
    kernel takes each (shard of a) leaf in its own shape and layout."""
    leaf = functools.partial(_fu.fused_axpy, a=a, interpret=_interpret())
    return _on_shards(leaf, (spec, spec), spec, x, y.astype(x.dtype))


def tree_fused_axpy(xs, ys, a):
    """x + a·y over a parameter-shaped pytree, each leaf in its own
    layout."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x, y: fused_axpy(x, y, a, leaf_spec(path, x.shape)),
        xs, ys)


def weighted_delta_reduce(stacked, weights):
    """Σ_k w_k·Δ_k over a stacked pytree (leading axis K on every leaf).
    Weights are applied as given (normalise upstream for a weighted mean)."""
    def leaf(d, w):
        k = d.shape[0]
        flat = d.reshape(k, -1)
        pad = (-flat.shape[1]) % LANE
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        tiles = flat.reshape(k, -1, LANE)
        out = _wr.weighted_reduce_2d(tiles, w, interpret=_interpret())
        return _from_tiles(out, pad, d.shape[1:], d.dtype)

    def reduce(path, d):
        # the reduced K axis stays whole on every device
        spec = leaf_spec(path, d.shape[1:])
        return _on_shards(leaf, (P(None, *spec), P(None)), spec, d, weights)
    return jax.tree_util.tree_map_with_path(reduce, stacked)


# ---------------------------------------------------------------------------
# delta compression — single-leaf quantise/sparsify round trips
# ---------------------------------------------------------------------------
def qsgd_compress_leaf(v, u, scale, s, spec=P()):
    """Stochastic uniform quantise-dequantise on one leaf laid out as
    `spec` under a mesh.  `u` uniform draw (v's shape), `scale` per-leaf
    scalar, `s` static level count.
    -> (dequantised q, residual v − q), both v's shape/dtype."""
    def leaf(v, u, scale):
        vt, pad = _as_tiles(v)
        ut, _ = _as_tiles(u)
        q, r = _cp.qsgd_2d(vt, ut, scale, s, interpret=_interpret())
        return (_from_tiles(q, pad, v.shape, v.dtype),
                _from_tiles(r, pad, v.shape, v.dtype))
    return _on_shards(leaf, (spec, spec, P()), (spec, spec), v,
                      u.astype(v.dtype), scale)


def topk_compress_leaf(v, thresh, spec=P()):
    """Magnitude-threshold select on one leaf laid out as `spec` under a
    mesh (top-k with τ precomputed).  -> (selected q, residual v − q)."""
    def leaf(v, thresh):
        vt, pad = _as_tiles(v)
        q, r = _cp.threshold_select_2d(vt, thresh, interpret=_interpret())
        return (_from_tiles(q, pad, v.shape, v.dtype),
                _from_tiles(r, pad, v.shape, v.dtype))
    return _on_shards(leaf, (spec, P()), (spec, spec), v, thresh)


def topk_sparse_leaf(v, k):
    """True sparse top-k select on one leaf: the k largest-|v| entries leave
    as (values, flat indices) — the actual wire representation — and the
    residual keeps everything else (DESIGN.md §Transport).

    -> (values (k,), indices (k,) int32, residual of v's shape/dtype).

    Selection and residual are exact complements by construction (the
    residual zeroes exactly the gathered indices), so
    ``sparse_scatter_leaf(values, indices) + residual == v`` bitwise.  No
    Pallas kernel: top-k and gather/scatter lower to XLA's sort/dynamic-
    gather, which are memory-bound and already single-pass — the fused
    threshold kernel only pays off on the dense path where the select is an
    elementwise mask over the full tensor.
    """
    flat = v.reshape(-1)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    idx = idx.astype(jnp.int32)
    values = flat[idx]
    residual = flat.at[idx].set(0).reshape(v.shape)
    return values, idx, residual


def sparse_scatter_leaf(values, indices, shape, dtype):
    """Server-side decode of one sparse leaf: scatter (values, indices) into
    a dense zero tensor — one scatter per client instead of re-running the
    dense threshold pass."""
    n = int(np.prod(shape)) if shape else 1
    return jnp.zeros((n,), dtype).at[indices].set(values).reshape(shape)


@functools.partial(jax.jit, static_argnums=(3, 4))
def sparse_weighted_delta_reduce(values, indices, weights, shape, dtype):
    """Σ_k w_k · scatter(values_k @ indices_k) for one leaf: the sparse
    server aggregate at K·k cost instead of K·d (kernels/sparse_reduce.py).
    `values`/`indices` are the stacked (K, k) wire pairs of K clients
    (duplicate indices accumulate), `shape`/`dtype` the dense leaf
    template.  Accumulation is fp32 inside the kernel's revisited output
    ref; the single cast to `dtype` happens on the final write
    (cast-on-write precision contract)."""
    _, k = values.shape
    n = 1
    for dim in shape:      # static python ints — no host sync in the trace
        n *= dim
    if k == 0:
        # an empty wire contributes nothing — and a zero-width Pallas
        # block is not a thing, so short-circuit before the kernel
        return jnp.zeros(shape, dtype)
    kpad = (-k) % LANE
    if kpad:
        # (value 0, index 0) filler pairs: the weighted zeros land on
        # index 0 as exact +0.0 adds, which never perturb the sum
        values = jnp.pad(values, ((0, 0), (0, kpad)))
        indices = jnp.pad(indices, ((0, 0), (0, kpad)))
    rows = (n + LANE - 1) // LANE
    out = _sr.sparse_reduce_2d(values, indices.astype(jnp.int32), weights,
                               rows, interpret=_interpret())
    return out.reshape(-1)[:n].astype(dtype).reshape(shape)


# ---------------------------------------------------------------------------
# attention / ssd / kd
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=True, window=0, block_q=None,
                    block_k=None):
    """q (B,L,H,D) model layout -> (B,L,H,D).

    Differentiable: the forward pass is the Pallas kernel, the backward
    pass is the VJP of the float32 oracle (``ref.flash_attention``),
    recomputed from (q, k, v) — so a training step through this op keeps
    no (L, L) residual between its passes."""
    def kernel(q, k, v):
        out = _fa.flash_attention(
            jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
            jnp.moveaxis(v, 1, 2), causal=causal, window=window,
            block_q=block_q, block_k=block_k, interpret=_interpret())
        return jnp.moveaxis(out, 1, 2)

    spec = P()
    mesh = _mesh()
    if mesh is not None:
        # batch over "data", heads over "model" where they divide; q and kv
        # heads split alike, so each shard keeps whole GQA groups
        def axis(name, dim):
            n = mesh.shape.get(name, 1)
            return name if n > 1 and dim % n == 0 else None
        spec = P(axis("data", q.shape[0]), None, axis("model", k.shape[2]),
                 None)
    return _on_shards(kernel, (spec,) * 3, spec, q, k, v)


def _flash_attention_fwd(q, k, v, causal, window, block_q, block_k):
    return flash_attention(q, k, v, causal, window, block_q, block_k), \
        (q, k, v)


def _flash_attention_bwd(causal, window, block_q, block_k, res, g):
    def oracle(q, k, v):
        out = _ref.flash_attention(jnp.moveaxis(q, 1, 2),
                                   jnp.moveaxis(k, 1, 2),
                                   jnp.moveaxis(v, 1, 2),
                                   causal=causal, window=window)
        return jnp.moveaxis(out, 1, 2)
    return jax.vjp(oracle, *res)[1](g)


flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


def ssd_scan(x, dt, A_log, B, C, D, chunk=256):
    return _ssd.ssd_scan(x, dt, A_log, B, C, D, chunk=chunk,
                         interpret=_interpret())


def kd_loss(student_logits, teacher_logits, labels, rho, lam, tau):
    return _kd.kd_loss(student_logits, teacher_logits, labels, rho, lam, tau,
                       interpret=_interpret())
