"""Per-kernel validation: interpret=True Pallas vs the pure-jnp oracle in
ref.py, swept over shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.base import FedConfig
from repro.core.strategies import FedADC
from repro.kernels import fedadc_update as FU
from repro.kernels import flash_attention as FA
from repro.kernels import kd_loss as KD
from repro.kernels import ops, ref
from repro.kernels import ssd_scan as SSD


def rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,H,Hk,L,D", [
    (1, 2, 2, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA group 2
    (1, 8, 1, 128, 128),    # MQA
    (1, 4, 4, 192, 64),     # L not multiple of block
    (1, 8, 2, 512, 64),     # GQA group 4, L 512
    (1, 5, 1, 1024, 64),    # GQA group 5 (a 14B shard's), L 1024
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, H, Hk, L, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, H, L, D), dtype)
    k = rand(ks[1], (B, Hk, L, D), dtype)
    v = rand(ks[2], (B, Hk, L, D), dtype)
    out = FA.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                             interpret=True)
    expect = ref.flash_attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,Hk,L,D", [
    (1, 4, 1, 1024, 128),   # group 4: 4 q blocks, causal within one k block
    (1, 5, 1, 1024, 128),   # group 5: rows not a power of two
    (2, 8, 2, 512, 64),     # one k block
    (1, 4, 4, 192, 64),     # L under one block
    (1, 8, 1, 128, 128),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_chosen_blocks(B, H, Hk, L, D, dtype):
    """The blocks the kernel picks from the shapes, against the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (B, H, L, D), dtype)
    k = rand(ks[1], (B, Hk, L, D), dtype)
    v = rand(ks[2], (B, Hk, L, D), dtype)
    out = FA.flash_attention(q, k, v, causal=True, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("L,block_q,block_k,causal,window", [
    (1024, 256, 512, True, 0),
    (1024, 128, 128, True, 0),
    (1024, 512, 256, True, 0),
    (192, 64, 128, True, 0),     # ragged last k block
    (256, 64, 64, True, 32),
    (256, 128, 64, True, 100),
    (512, 128, 128, False, 0),
])
def test_flash_band_is_the_visible_blocks(L, block_q, block_k, causal,
                                          window):
    """A q block computes, and fetches K/V for, exactly the k blocks that
    hold a (query, key) pair its mask lets through; every other step keeps
    the index of a visible block, so the pipeline fetches nothing new."""
    n_kb = -(-L // block_k)
    for qi in range(-(-L // block_q)):
        lo, hi = FA._band(qi, block_q=block_q, block_k=block_k, n_kb=n_kb,
                          causal=causal, window=window)
        qpos = np.arange(qi * block_q, min((qi + 1) * block_q, L))[:, None]
        kpos = np.arange(L)[None, :]
        seen = np.ones((len(qpos), L), bool)
        if causal:
            seen &= kpos <= qpos
        if window > 0:
            seen &= kpos > qpos - window
        visible = sorted({int(c) // block_k for c in np.nonzero(seen)[1]})
        assert visible == list(range(int(lo), int(hi) + 1))
        fetched = [int(np.clip(ki, lo, hi)) for ki in range(n_kb)]
        assert sorted(set(fetched)) == visible
        assert fetched == sorted(fetched)   # no block fetched twice


@pytest.mark.parametrize("L,block_q,block_k,causal,window", [
    (1024, 256, 512, True, 0),
    (1024, 128, 128, True, 0),
    (1024, 512, 256, True, 0),
    (192, 128, 64, True, 0),     # ragged last q block
    (256, 64, 64, True, 32),
    (256, 64, 128, True, 100),
    (512, 128, 128, False, 0),
])
def test_flash_q_band_is_the_visible_blocks(L, block_q, block_k, causal,
                                            window):
    """The backward's mirror of ``_band``: a k block computes with, and
    fetches q, dO and statistics for, exactly the q blocks that hold a
    (query, key) pair the mask lets through; every other step keeps the
    index of a visible block, so the pipeline fetches nothing new."""
    n_qb = -(-L // block_q)
    for ki in range(-(-L // block_k)):
        lo, hi = FA._q_band(ki, block_q=block_q, block_k=block_k, n_qb=n_qb,
                            causal=causal, window=window)
        kpos = np.arange(ki * block_k, min((ki + 1) * block_k, L))[:, None]
        qpos = np.arange(L)[None, :]
        seen = np.ones((len(kpos), L), bool)
        if causal:
            seen &= kpos <= qpos
        if window > 0:
            seen &= kpos > qpos - window
        visible = sorted({int(c) // block_q for c in np.nonzero(seen)[1]})
        assert visible == list(range(int(lo), int(hi) + 1))
        fetched = [int(np.clip(qi, lo, hi)) for qi in range(n_qb)]
        assert sorted(set(fetched)) == visible
        assert fetched == sorted(fetched)   # no block fetched twice


@pytest.mark.parametrize("B,H,Hk,L,D,window,block_q,block_k", [
    (1, 2, 2, 256, 64, 0, 128, 128),    # MHA, exact blocks
    (1, 4, 1, 256, 64, 0, 128, 256),    # GQA group 4, one k block
    (2, 8, 2, 256, 64, 0, None, None),  # group 4, the chosen blocks
    (1, 5, 1, 384, 64, 0, 128, 256),    # group 5, ragged last k block
    (1, 5, 1, 192, 64, 0, 128, 128),    # group 5, ragged q and k blocks
    (1, 4, 4, 256, 64, 32, 64, 64),     # sliding window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_backward_kernels(B, H, Hk, L, D, window, block_q,
                                          block_k, dtype):
    """dq from ``_flash_dq_kernel`` and dk, dv from ``_flash_dkv_kernel``,
    given the forward's log-sum-exp, against the VJP of the oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = rand(ks[0], (B, H, L, D), dtype)
    k = rand(ks[1], (B, Hk, L, D), dtype)
    v = rand(ks[2], (B, Hk, L, D), dtype)
    do = rand(ks[3], (B, H, L, D), dtype)
    o, lse = FA.flash_attention(q, k, v, causal=True, window=window,
                                interpret=True, residuals=True)
    np.testing.assert_array_equal(o, FA.flash_attention(
        q, k, v, causal=True, window=window, interpret=True))
    got = FA.flash_attention_bwd(q, k, v, o, lse, do, causal=True,
                                 window=window, block_q=block_q,
                                 block_k=block_k, interpret=True)
    _, pullback = jax.vjp(lambda *a: ref.flash_attention(
        *a, causal=True, window=window), q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for a, b in zip(got, pullback(do)):
        assert a.shape == b.shape and a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 2, 256, 64), jnp.float32)
    k = rand(ks[1], (1, 2, 256, 64), jnp.float32)
    v = rand(ks[2], (1, 2, 256, 64), jnp.float32)
    out = FA.flash_attention(q, k, v, causal=True, window=window,
                             block_q=64, block_k=64, interpret=True)
    expect = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_flash_attention_model_layout_matches_sdpa():
    """ops.flash_attention (B,L,H,D layout) vs attention._sdpa."""
    from repro.models.attention import _sdpa, causal_window_mask
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, L, H, Hk, D = 2, 128, 4, 2, 64
    q = rand(ks[0], (B, L, H, D), jnp.float32)
    k = rand(ks[1], (B, L, Hk, D), jnp.float32)
    v = rand(ks[2], (B, L, Hk, D), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True)
    expect = _sdpa(q, k, v, causal_window_mask(L, L, 0))
    np.testing.assert_allclose(out, expect, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,L,H,P,N,chunk", [
    (1, 64, 2, 16, 8, 16),
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 64, 64),     # zamba2-like state size
    (2, 96, 3, 16, 8, 32),       # L not multiple of 2*chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_shapes(b, L, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = rand(ks[0], (b, L, H, P), dtype)
    dt = jax.nn.softplus(rand(ks[1], (b, L, H), jnp.float32))
    A_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    B = rand(ks[2], (b, L, H, N), dtype)
    C = rand(ks[3], (b, L, H, N), dtype)
    D = jnp.ones((H,))
    out = SSD.ssd_scan(x, dt, A_log, B, C, D, chunk=chunk, interpret=True)
    expect = ref.ssd_scan(x, dt, A_log, B, C, D)
    scale = float(jnp.abs(expect).max()) + 1e-6
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32) / scale,
                               np.asarray(expect, np.float32) / scale,
                               atol=tol)


def test_ssd_kernel_matches_chunked_jnp():
    """The model's jnp chunked path and the kernel agree (same math)."""
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    b, L, H, P, N = 2, 128, 4, 32, 16
    x = rand(ks[0], (b, L, H, P), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (b, L, H), jnp.float32))
    A_log = jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32))
    B = rand(ks[2], (b, L, H, N), jnp.float32)
    C = rand(ks[3], (b, L, H, N), jnp.float32)
    D = jnp.ones((H,))
    a = SSD.ssd_scan(x, dt, A_log, B, C, D, chunk=32, interpret=True)
    c = ssd_chunked(x, dt, A_log, B, C, D, chunk=32)
    np.testing.assert_allclose(a, c, atol=3e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# FedADC updates: the server step and the local step's fused axpy
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 3000), eta=st.floats(1e-4, 1.0),
       beta_global=st.floats(0.0, 1.0), beta_local=st.floats(0.0, 1.0))
def test_property_server_update(n, eta, beta_global, beta_local):
    """FedADC's server update (Alg. 3 lines 16-19) is the closed form
    m' = (β_g − β_l)·m + Δ̄/η, θ' = θ − αη·m'."""
    fed = FedConfig(strategy="fedadc", eta=eta, beta_global=beta_global,
                    beta_local=beta_local, alpha=1.5)
    rng = np.random.RandomState(n)
    theta, m, d = (rng.randn(n).astype(np.float32) for _ in range(3))
    t2, s2 = FedADC().server_update({"m": {"p": jnp.asarray(m)}},
                                    {"p": jnp.asarray(theta)},
                                    {"p": jnp.asarray(d)}, fed)
    me = (beta_global - beta_local) * m.astype(np.float64) + d / eta
    te = theta - 1.5 * eta * me
    np.testing.assert_allclose(s2["m"]["p"], me, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(t2["p"], te, atol=1e-5, rtol=1e-4)


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, jnp.uint16 if x.dtype == jnp.bfloat16 else jnp.uint32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(3, 256, 384), (3, 128), (2560,), (7, 13),
                                   (5,), (2, 3, 3, 40), (128,), (1000,),
                                   (4097,), (65536,)])
def test_fused_axpy_pytree_shapes(shape, dtype):
    """The axpy runs on each leaf in its own shape; x + a·y is element by
    element, so it is bitwise the compiled x + a·y (compiled too: the
    compiler may contract a·y + x into one multiply-add)."""
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    x, y = rand(ks[0], shape, dtype), rand(ks[1], shape, dtype)
    out = ops.fused_axpy(x, y, -0.05)
    assert out.shape == shape and out.dtype == dtype
    expect = jax.jit(lambda x, y: x + -0.05 * y)(x, y)
    np.testing.assert_array_equal(_bits(out), _bits(expect))
    zeros = ops.tree_fused_axpy({"p": x}, {"p": x * 2.0}, -0.5)["p"]
    np.testing.assert_array_equal(zeros, jnp.zeros_like(x))


def test_tree_fused_axpy_keeps_leaf_layout():
    """An aligned stacked leaf reaches the kernel as it is: no flatten,
    pad, slice or re-tiling around the call, each of which is an HBM
    copy on the TPU."""
    leaf = jax.ShapeDtypeStruct((3, 256, 384), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, y: ops.tree_fused_axpy(
        {"w": x}, {"w": y}, -0.05))(leaf, leaf)
    prims = {e.primitive.name for e in jaxpr.jaxpr.eqns}
    assert "pallas_call" in prims
    assert not prims & {"reshape", "pad", "slice", "dynamic_slice",
                        "concatenate"}, prims


# ---------------------------------------------------------------------------
# KD loss
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,C", [(8, 10), (64, 37), (128, 100), (31, 257)])
def test_kd_loss_sweep(B, C):
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    s = rand(ks[0], (B, C), jnp.float32)
    t = rand(ks[1], (B, C), jnp.float32)
    y = jax.random.randint(ks[2], (B,), 0, C)
    rho = jax.random.uniform(ks[3], (C,))
    out = KD.kd_loss(s, t, y, rho, 0.35, 2.0, interpret=True)
    expect = ref.kd_loss(s, t, y, rho, 0.35, 2.0)
    np.testing.assert_allclose(out, expect, atol=1e-5, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(lam=st.floats(0.0, 1.0), tau=st.floats(0.5, 4.0))
def test_property_kd_loss_hparams(lam, tau):
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    B, C = 16, 12
    s = rand(ks[0], (B, C), jnp.float32)
    t = rand(ks[1], (B, C), jnp.float32)
    y = jax.random.randint(ks[2], (B,), 0, C)
    rho = jax.random.uniform(ks[3], (C,))
    out = KD.kd_loss(s, t, y, rho, lam, tau, interpret=True)
    expect = ref.kd_loss(s, t, y, rho, lam, tau)
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=1e-3)
    assert bool(jnp.all(jnp.isfinite(out)))


# ---------------------------------------------------------------------------
# delta-compression kernels (uplink quantise/sparsify round trips)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [128, 1000, 4097, 65536])
@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qsgd_kernel_sweep(n, bits, dtype):
    ks = jax.random.split(jax.random.PRNGKey(8), 2)
    v = rand(ks[0], (n,), dtype)
    u = jax.random.uniform(ks[1], (n,), dtype=dtype)
    scale = jnp.max(jnp.abs(v))
    s = (1 << bits) - 1
    q, r = ops.qsgd_compress_leaf(v, u, scale, s)
    qe, re = ref.qsgd_quantize(v, u, scale, s)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(q, np.float32),
                               np.asarray(qe, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(r, np.float32),
                               np.asarray(re, np.float32), atol=tol, rtol=tol)
    # reconstruction error bounded by one quantisation step (plus dtype
    # rounding: bf16's 8-bit mantissa cannot represent 255 levels exactly)
    step = float(scale) / s
    eps = 2.0 ** -8 if dtype == jnp.bfloat16 else 2.0 ** -23
    bound = step * (1 + 1e-3) + 2 * float(scale) * eps + 1e-6
    np.testing.assert_array_less(np.abs(np.asarray(v - q, np.float32)), bound)


def test_qsgd_kernel_zero_leaf_and_padding():
    v = jnp.zeros((131,))                        # forces lane padding + scale 0
    u = jax.random.uniform(jax.random.PRNGKey(0), (131,))
    q, r = ops.qsgd_compress_leaf(v, u, jnp.max(jnp.abs(v)), 15)
    np.testing.assert_array_equal(np.asarray(q), 0.0)
    np.testing.assert_array_equal(np.asarray(r), 0.0)


@pytest.mark.parametrize("n,k", [(128, 13), (1000, 100), (4097, 1),
                                 (65536, 6554)])
def test_topk_threshold_kernel_sweep(n, k):
    v = rand(jax.random.PRNGKey(9), (n,), jnp.float32)
    thresh = jax.lax.top_k(jnp.abs(v), k)[0][-1]
    q, r = ops.topk_compress_leaf(v, thresh)
    qe, re = ref.topk_threshold_select(v, thresh)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qe))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(re))
    # exactly k survivors for distinct magnitudes, and r is the exact
    # complement: q + r == v bitwise (select is pure masking)
    assert int(jnp.sum(q != 0)) == k
    np.testing.assert_array_equal(np.asarray(q + r), np.asarray(v))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 3000), frac=st.floats(0.01, 1.0))
def test_property_topk_select_conserves(n, frac):
    rng = np.random.RandomState(n)
    v = jnp.asarray(rng.randn(n).astype(np.float32))
    k = max(1, int(np.ceil(frac * n)))
    thresh = jax.lax.top_k(jnp.abs(v), k)[0][-1]
    q, r = ops.topk_compress_leaf(v, thresh)
    np.testing.assert_array_equal(np.asarray(q + r), np.asarray(v))
    assert int(jnp.sum(q != 0)) >= min(k, int(jnp.sum(v != 0)))


# ---------------------------------------------------------------------------
# weighted-delta-reduce: fp32 accumulation at bf16 (Pallas ↔ ref ↔ fp64
# oracle).  Summing K bf16 deltas in bf16 loses the aggregate to rounding
# once the partial sum's ulp outgrows the increments; both the ref path and
# the kernel must accumulate in fp32 and cast on write.
# ---------------------------------------------------------------------------
class TestWeightedReduceFp32Accumulation:
    K, N = 96, 4096          # K ≥ 64: bf16 running sums visibly drown here

    def _operands(self):
        rng = np.random.RandomState(7)
        # positive values ~1.0 so the partial sum grows monotonically —
        # the adversarial regime for low-precision accumulation
        d64 = 1.0 + 0.05 * rng.randn(self.K, self.N)
        d_bf16 = jnp.asarray(d64, jnp.bfloat16)
        w = jnp.asarray(rng.uniform(0.2, 1.0, self.K), jnp.float32)
        # the fp64 oracle consumes the bf16-rounded inputs (the wire dtype
        # is given; the accumulation precision is what is under test)
        return d_bf16, w, np.asarray(d_bf16, np.float64), np.asarray(
            w, np.float64)

    def test_ref_and_pallas_match_fp64_oracle(self):
        d, w, d64, w64 = self._operands()
        oracle = np.tensordot(w64, d64, axes=([0], [0]))
        got_ref = np.asarray(ref.weighted_delta_reduce(d, w), np.float64)
        got_pal = np.asarray(
            ops.weighted_delta_reduce({"x": d}, w)["x"], np.float64)
        # fp32 accumulation + one final bf16 rounding: within 1 bf16 ulp
        bound = np.abs(oracle) * 2.0 ** -8
        assert np.all(np.abs(got_ref - oracle) <= bound)
        assert np.all(np.abs(got_pal - oracle) <= bound)
        # and Pallas agrees with the ref path to the same resolution
        np.testing.assert_allclose(got_pal, got_ref, rtol=2.0 ** -8, atol=0)

    def test_bf16_accumulation_would_fail_this_bound(self):
        """The regression the fp32 fix closes: an in-dtype (bf16) running
        sum violates the 1-ulp bound the fixed paths satisfy."""
        d, w, d64, w64 = self._operands()
        oracle = np.tensordot(w64, d64, axes=([0], [0]))
        acc = jnp.zeros((self.N,), jnp.bfloat16)
        for i in range(self.K):                      # the old semantics
            acc = acc + w[i].astype(jnp.bfloat16) * d[i]
        bad = np.asarray(acc, np.float64)
        bound = np.abs(oracle) * 2.0 ** -8
        assert np.mean(np.abs(bad - oracle) > bound) > 0.5

    def test_weighted_mean_bf16_matches_fp64_oracle(self):
        """The aggregation entry point (both backends) at bf16."""
        from repro.federated import aggregation as A
        d, w, d64, w64 = self._operands()
        wn64 = w64 / w64.sum()
        oracle = np.tensordot(wn64, d64, axes=([0], [0]))
        bound = np.abs(oracle) * 2.0 ** -8 + 1e-7
        for use_pallas in (False, True):
            got = np.asarray(
                A.weighted_mean({"x": d}, w, use_pallas=use_pallas)["x"],
                np.float64)
            assert np.all(np.abs(got - oracle) <= bound), use_pallas

    def test_steady_state_transfer_guard(self, steady_state_guard):
        """Kernel parity under the transfer guard: after one warmup call
        (compile + H2D of operands) both the Pallas and the ref reduction
        run on device-resident operands with no implicit transfer, and
        still agree."""
        d, w, _, _ = self._operands()
        ops.weighted_delta_reduce({"x": d}, w)
        ref.weighted_delta_reduce(d, w)
        with steady_state_guard():
            got_pal = ops.weighted_delta_reduce({"x": d}, w)["x"]
            got_ref = ref.weighted_delta_reduce(d, w)
        np.testing.assert_allclose(np.asarray(got_pal, np.float64),
                                   np.asarray(got_ref, np.float64),
                                   rtol=2.0 ** -8, atol=0)

    def test_fp32_inputs_unchanged(self):
        """The fix must not perturb the existing fp32 path."""
        rng = np.random.RandomState(3)
        d = jnp.asarray(rng.randn(8, 513), jnp.float32)
        w = jnp.asarray(rng.uniform(size=8), jnp.float32)
        got = ops.weighted_delta_reduce({"x": d}, w)["x"]
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref.weighted_delta_reduce(d, w)),
            rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# sparse_weighted_delta_reduce: the scatter-accumulate server aggregate
# (kernels/sparse_reduce.py) vs the jnp segment-sum oracle and an fp64
# dense oracle — the sparse-native path's precision and collision contracts.
# ---------------------------------------------------------------------------
class TestSparseReduce:
    K, N = 96, 4096
    TOPK = 409               # ceil(0.1 · N)

    def _wire(self, dtype=jnp.bfloat16, k=None, n=None, K=None, seed=7):
        k = self.TOPK if k is None else k
        n = self.N if n is None else n
        K = self.K if K is None else K
        rng = np.random.RandomState(seed)
        # positive ~1.0 values: the adversarial regime for low-precision
        # accumulation (partial sums grow monotonically)
        vals = jnp.asarray(1.0 + 0.05 * rng.randn(K, k), dtype)
        # unique-per-client indices, as the top-k wire guarantees
        idx = jnp.asarray(
            np.stack([rng.choice(n, size=k, replace=False)
                      for _ in range(K)]), jnp.int32)
        w = jnp.asarray(rng.uniform(0.2, 1.0, K), jnp.float32)
        return vals, idx, w

    @pytest.mark.parametrize("shape,dtype,K,k", [
        ((64, 32), jnp.float32, 6, 97),
        ((4096,), jnp.bfloat16, 96, 409),
        ((17,), jnp.float32, 3, 5),        # k-pad + n-pad, tiny leaf
        ((), jnp.float32, 4, 1),           # scalar leaf
        ((70000,), jnp.float32, 3, 9000),  # 2 output row blocks, 2 chunks
    ])
    def test_pallas_matches_ref_bitwise(self, shape, dtype, K, k):
        """Kernel and oracle apply the weighted updates in the same
        client-major order onto an fp32 zero buffer — bitwise equal."""
        n = int(np.prod(shape)) if shape else 1
        rng = np.random.RandomState(K * 1000 + k)
        vals = jnp.asarray(rng.randn(K, k), dtype)
        idx = jnp.asarray(rng.randint(0, n, (K, k)), jnp.int32)
        w = jnp.asarray(rng.uniform(0.2, 1.0, K), jnp.float32)
        got = ops.sparse_weighted_delta_reduce(vals, idx, w, shape, dtype)
        exp = ref.sparse_weighted_delta_reduce(vals, idx, w, shape, dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))

    def test_bf16_values_fp32_accumulate_vs_fp64_oracle(self):
        """K=96 bf16 wires: fp32 accumulation keeps the aggregate within
        one bf16 ulp of the fp64 dense oracle (an in-dtype running sum
        would drown the late clients, as the dense reduce class pins)."""
        vals, idx, w = self._wire()
        oracle = np.zeros(self.N)
        wv = np.asarray(w, np.float64)[:, None] * np.asarray(vals, np.float64)
        np.add.at(oracle, np.asarray(idx).reshape(-1), wv.reshape(-1))
        bound = np.abs(oracle) * 2.0 ** -8 + 1e-7
        for fn in (ops.sparse_weighted_delta_reduce,
                   ref.sparse_weighted_delta_reduce):
            got = np.asarray(fn(vals, idx, w, (self.N,), jnp.float32),
                             np.float64)
            assert np.all(np.abs(got - oracle) <= bound), fn.__module__

    def test_duplicate_index_collisions_accumulate(self):
        """Duplicated indices within a client must scatter-ADD (the
        segment-sum semantics), not last-write-wins like decode's .set."""
        vals = jnp.asarray([[1.0, 2.0, 4.0], [8.0, 16.0, 32.0]], jnp.float32)
        idx = jnp.asarray([[5, 5, 5], [5, 5, 2]], jnp.int32)
        w = jnp.asarray([1.0, 1.0], jnp.float32)
        for fn in (ops.sparse_weighted_delta_reduce,
                   ref.sparse_weighted_delta_reduce):
            got = np.asarray(fn(vals, idx, w, (8,), jnp.float32))
            # weights applied as given (normalisation happens upstream)
            assert got[5] == 1 + 2 + 4 + 8 + 16, fn.__module__
            assert got[2] == 32.0, fn.__module__
            assert got[[0, 1, 3, 4, 6, 7]].sum() == 0.0

    def test_empty_k_edge(self):
        """A zero-width wire contributes exactly zeros (no Pallas call —
        a zero-size block cannot be tiled)."""
        w = jnp.ones((2,), jnp.float32)
        for fn in (ops.sparse_weighted_delta_reduce,
                   ref.sparse_weighted_delta_reduce):
            out = fn(jnp.zeros((2, 0)), jnp.zeros((2, 0), jnp.int32), w,
                     (8,), jnp.float32)
            np.testing.assert_array_equal(np.asarray(out), 0.0)
            assert out.shape == (8,) and out.dtype == jnp.float32

    def test_matches_dense_decode_fold(self):
        """The end-to-end contract: segment-summing the wire equals
        decoding each client dense and folding in client order (the
        off-support adds are exact +0.0 no-ops) — bitwise."""
        vals, idx, w = self._wire(dtype=jnp.float32, seed=11)
        acc = np.zeros(self.N, np.float32)
        for i in range(self.K):
            dense = np.asarray(ops.sparse_scatter_leaf(
                vals[i], idx[i], (self.N,), jnp.float32))
            acc = acc + np.float32(w[i]) * dense
        got = ops.sparse_weighted_delta_reduce(vals, idx, w, (self.N,),
                                               jnp.float32)
        np.testing.assert_array_equal(np.asarray(got), acc)

    def test_steady_state_transfer_guard(self, steady_state_guard):
        """After one warmup call, both backends aggregate device-resident
        wires with zero implicit host<->device transfers, and agree."""
        vals, idx, w = self._wire()
        args = (vals, idx, w, (self.N,), jnp.float32)
        ops.sparse_weighted_delta_reduce(*args)
        ref.sparse_weighted_delta_reduce(*args)
        with steady_state_guard():
            got_pal = ops.sparse_weighted_delta_reduce(*args)
            got_ref = ref.sparse_weighted_delta_reduce(*args)
        np.testing.assert_array_equal(np.asarray(got_pal),
                                      np.asarray(got_ref))
