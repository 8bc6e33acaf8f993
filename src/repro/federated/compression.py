"""Pluggable uplink delta compressors with per-client error feedback.

The paper claims acceleration + drift control with *no additional
communication load* (Sec. II-A); this module makes the uplink side of that
claim measurable instead of analytic.  Each client compresses its round
delta before transport; the server aggregates and runs the FedADC momentum
recursion on the *decompressed* reconstruction, so drift control composes
with a lossy uplink (DESIGN.md §Compression).

Compressors (``FedConfig.compressor``):

* ``none``     — the hook is bypassed entirely (pre-compression code path).
* ``identity`` — goes through the hook but is lossless; engine runs are
  bit-identical to ``none`` (tested), which pins the hook's placement.
* ``topk``     — top-k magnitude sparsification: per leaf, the k =
  ⌈topk_frac·n⌉ largest-|v| entries survive; the wire carries (value, index)
  pairs, ⌈log₂ n⌉ bits per index.
* ``qsgd``     — QSGD-style stochastic uniform quantisation: magnitudes are
  scaled by the per-leaf max into ``2^qsgd_bits − 1`` levels and
  stochastically rounded (unbiased given the scale); the wire carries
  ``qsgd_bits``+sign per entry plus one f32 scale per leaf.

Error feedback (``FedConfig.error_feedback``): the client quantises
``v_t = Δ_t + e_{t-1}`` and keeps ``e_t = v_t − q(v_t)`` — the *exact*
compression residual — to re-inject next round, so systematic quantisation
bias cannot accumulate in the server trajectory.  The per-client ``e``
state lives in the round protocol's ``ClientStore`` (DESIGN.md
§Transport): host-backed in the simulator/async engines, mesh-sharded
inside the pod engine's train state.

These compressors are the *codecs'* arithmetic: engines drive them through
``repro.federated.transport.Transport`` (uplink round trips, downlink
broadcast, measured-byte accounting for both directions; the old
``strategy.compress_delta`` hook survives as a deprecation shim).
``compress`` is jit/vmap-friendly: it returns the decompressed delta (what
the server reconstructs from the wire) plus the new EF state; the dense
codecs never materialise the wire format inside the round, while
``FedConfig.sparse_uplink`` swaps in the true (value, index)
representation (transport.SparseTopKCodec).  ``wire_nbytes`` is the
host-side accounting of that wire format — exact byte counts from leaf
shapes (works on ShapeDtypeStructs, so pod-scale archs need no allocation).
With ``fed.use_pallas`` the quantise-dequant round trips run as fused
single-pass VMEM kernels (kernels/compress.py); otherwise as the pure-jnp
oracles in kernels/ref.py — both parity-tested against each other.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tree as T
from repro.kernels import ref

KNOWN_COMPRESSORS = ("none", "identity", "topk", "qsgd")


class SparseLeaf(NamedTuple):
    """One leaf's sparse wire format: the k surviving (value, index) pairs.
    A NamedTuple, so it is a pytree — it vmaps over clients and crosses jit
    boundaries like any other array pair.  Lives here (not transport) so
    the aggregation layer can consume the wire without importing the
    codec machinery; transport re-exports it."""
    values: jax.Array     # (k,) — or (K, k) once stacked over clients
    indices: jax.Array    # same shape, int32 flat index into the leaf


def is_sparse_leaf(x) -> bool:
    return isinstance(x, SparseLeaf)


def is_sparse_tree(tree) -> bool:
    """True when the pytree's aggregation-level leaves are SparseLeaf wires
    (the sparse-native uplink); False for dense trees.  Mixed trees don't
    occur: SparseTopKCodec encodes every leaf."""
    return any(is_sparse_leaf(l)
               for l in jax.tree.leaves(tree, is_leaf=is_sparse_leaf))


def _leaf_elems(leaf) -> int:
    """Element count of an array OR a ShapeDtypeStruct."""
    return int(np.prod(leaf.shape)) if leaf.shape else 1


def _leaf_itembits(leaf) -> int:
    return jnp.dtype(leaf.dtype).itemsize * 8


def raw_nbytes(tree) -> int:
    """Uncompressed wire size of a pytree (arrays or ShapeDtypeStructs)."""
    return sum(_leaf_elems(l) * jnp.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


class Compressor:
    """compress() runs inside jit (per client, vmap-safe); wire_nbytes()
    runs on the host for byte accounting."""
    name = "base"
    lossy = True

    def compress(self, delta, ef, key):
        """(delta, ef pytrees, PRNG key) -> (decompressed q, new ef).
        q is what the server reconstructs from the wire; new ef is the
        exact residual (delta + ef) − q."""
        raise NotImplementedError

    def wire_nbytes(self, tree) -> int:
        raise NotImplementedError


class IdentityCompressor(Compressor):
    name = "identity"
    lossy = False

    def compress(self, delta, ef, key):
        # pure passthrough — no arithmetic, so engine trajectories are
        # bit-identical to compressor="none" (tested)
        return delta, ef

    def wire_nbytes(self, tree) -> int:
        return raw_nbytes(tree)


class TopKCompressor(Compressor):
    """Top-k magnitude sparsification, k per leaf, exact threshold via
    lax.top_k; the select itself is a streaming per-block threshold pass
    (kernels/compress.py) so only the (cheap) threshold scan depends on k."""
    name = "topk"

    def __init__(self, frac: float, use_pallas: bool = False):
        if not 0.0 < frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1]; got {frac}")
        self.frac = frac
        self.use_pallas = use_pallas

    def _k(self, n: int) -> int:
        return max(1, int(math.ceil(self.frac * n)))

    def compress(self, delta, ef, key):
        v = T.add(delta, ef)
        # flatten/unflatten rather than unzipping an is_leaf-on-tuples map:
        # the input pytree may contain tuple internal nodes a tuple
        # heuristic would mistake for (q, residual) pairs
        leaves, treedef = jax.tree_util.tree_flatten_with_path(v)
        pairs = []
        for path, x in leaves:
            flat = jnp.abs(x.reshape(-1))
            thresh = jax.lax.top_k(flat, self._k(flat.size))[0][-1]
            if self.use_pallas:
                from repro.kernels import ops
                pairs.append(ops.topk_compress_leaf(
                    x, thresh, ops.leaf_spec(path, x.shape)))
            else:
                pairs.append(ref.topk_threshold_select(x, thresh))
        return (jax.tree.unflatten(treedef, [p[0] for p in pairs]),
                jax.tree.unflatten(treedef, [p[1] for p in pairs]))

    def wire_nbytes(self, tree) -> int:
        bits = 0
        for l in jax.tree.leaves(tree):
            n = _leaf_elems(l)
            idx_bits = max(1, math.ceil(math.log2(n))) if n > 1 else 1
            bits += self._k(n) * (_leaf_itembits(l) + idx_bits) + 32
        return (bits + 7) // 8


class QSGDCompressor(Compressor):
    """QSGD-style stochastic uniform quantisation, per-leaf max scale."""
    name = "qsgd"

    def __init__(self, bits: int, use_pallas: bool = False):
        if bits < 1:
            raise ValueError(f"qsgd_bits must be >= 1; got {bits}")
        self.bits = bits
        self.levels = (1 << bits) - 1     # magnitude levels; sign is separate
        self.use_pallas = use_pallas

    def compress(self, delta, ef, key):
        v = T.add(delta, ef)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(v)
        keys = jax.random.split(key, len(leaves))
        pairs = []
        for (path, x), k in zip(leaves, keys):
            u = jax.random.uniform(k, x.shape, dtype=x.dtype)
            scale = jnp.max(jnp.abs(x))
            if self.use_pallas:
                from repro.kernels import ops
                pairs.append(ops.qsgd_compress_leaf(
                    x, u, scale, self.levels, ops.leaf_spec(path, x.shape)))
            else:
                pairs.append(ref.qsgd_quantize(x, u, scale, self.levels))
        return (jax.tree.unflatten(treedef, [p[0] for p in pairs]),
                jax.tree.unflatten(treedef, [p[1] for p in pairs]))

    def wire_nbytes(self, tree) -> int:
        bits = sum(_leaf_elems(l) * (self.bits + 1) + 32
                   for l in jax.tree.leaves(tree))
        return (bits + 7) // 8


@functools.lru_cache(maxsize=None)
def _get_compressor(name: str, topk_frac: float, qsgd_bits: int,
                    use_pallas: bool) -> Optional[Compressor]:
    if name == "none":
        return None
    if name == "identity":
        return IdentityCompressor()
    if name == "topk":
        return TopKCompressor(topk_frac, use_pallas)
    if name == "qsgd":
        return QSGDCompressor(qsgd_bits, use_pallas)
    raise ValueError(f"unknown compressor {name!r}; "
                     f"known: {', '.join(KNOWN_COMPRESSORS)}")


def get_compressor(fed) -> Optional[Compressor]:
    """FedConfig -> Compressor instance (None when compressor='none', i.e.
    the hook is bypassed and the round runs the pre-compression code path).
    Cached on the wire-relevant knobs only (not the whole config), so jit
    tracing reuses one instance per codec instead of one per config."""
    return _get_compressor(fed.compressor, fed.topk_frac, fed.qsgd_bits,
                           fed.use_pallas)


def uplink_nbytes(fed, params) -> int:
    """Measured bytes one client uploads per round under fed's compressor
    (raw delta bytes when compression is off)."""
    comp = get_compressor(fed)
    return raw_nbytes(params) if comp is None else comp.wire_nbytes(params)
