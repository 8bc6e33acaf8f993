"""Pure-jnp oracles for every Pallas kernel.  Tests assert_allclose each
kernel (interpret=True on CPU) against these references across shape/dtype
sweeps."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# fedadc_update
# ---------------------------------------------------------------------------
def fused_axpy(x, y, a):
    """x + a*y."""
    return x + a * y


def weighted_delta_reduce(deltas, weights):
    """Σ_k w_k·Δ_k for a single stacked array (K, ...); accumulates in at
    least fp32 (bf16 partial sums drown the late terms as K grows; f64
    inputs keep f64), cast back to the delta dtype on write."""
    acc_t = jnp.promote_types(deltas.dtype, jnp.float32)
    out = jnp.tensordot(weights.astype(acc_t), deltas.astype(acc_t),
                        axes=([0], [0]))
    return out.astype(deltas.dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def sparse_weighted_delta_reduce(values, indices, weights, shape, dtype):
    """Σ_k w_k · scatter(values_k @ indices_k) for one leaf without ever
    materialising the K dense reconstructions: a weighted segment-sum over
    the stacked (K, k) wire pairs into the dense `shape` template.
    Accumulates in at least fp32 (same contract as weighted_delta_reduce),
    cast to the leaf dtype on the final write.  Duplicate indices within a
    client accumulate (scatter-add semantics)."""
    n = 1
    for d in shape:        # static python ints — no host sync in the trace
        n *= d
    acc_t = jnp.promote_types(values.dtype, jnp.float32)
    wv = (weights.astype(acc_t)[:, None] * values.astype(acc_t)).reshape(-1)
    out = jax.ops.segment_sum(wv, indices.reshape(-1).astype(jnp.int32),
                              num_segments=n)
    return out.astype(dtype).reshape(shape)


# ---------------------------------------------------------------------------
# delta compression (uplink quantise/sparsify round trips)
# ---------------------------------------------------------------------------
def qsgd_quantize(v, u, scale, s):
    """QSGD stochastic uniform quantise-dequantise.  `u` is the uniform
    draw (same shape as v), `scale` the per-leaf max magnitude, `s` the
    number of magnitude levels.  -> (dequantised q, residual v − q)."""
    dtype = v.dtype
    inv = jnp.where(scale > 0,
                    jnp.asarray(float(s), dtype) / jnp.maximum(scale, 1e-30),
                    jnp.zeros((), dtype))
    y = jnp.abs(v) * inv
    lower = jnp.floor(y)
    level = lower + (u < (y - lower)).astype(dtype)
    q = jnp.sign(v) * level * (scale / jnp.asarray(float(s), dtype))
    return q, v - q


def topk_threshold_select(v, thresh):
    """Magnitude-threshold select (top-k with τ = k-th largest |v|).
    -> (selected q, residual v − q)."""
    q = jnp.where(jnp.abs(v) >= thresh, v, jnp.zeros_like(v))
    return q, v - q


# ---------------------------------------------------------------------------
# flash attention (causal, GQA, optional sliding window)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, causal=True, window=0):
    """q (B,H,L,D), k/v (B,Hk,L,D) -> (B,H,L,D).  float32 math."""
    B, H, Lq, D = q.shape
    Hk = k.shape[1]
    g = H // Hk
    qf = q.astype(jnp.float32) * (D ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, g, axis=1)
    vf = jnp.repeat(vf, g, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qf, kf)
    qpos = jnp.arange(Lq)[:, None]
    kpos = jnp.arange(Lq)[None, :]
    mask = jnp.ones((Lq, Lq), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vf).astype(q.dtype)


# ---------------------------------------------------------------------------
# SSD scan (sequential recurrence oracle)
# ---------------------------------------------------------------------------
def ssd_scan(x, dt, A_log, B, C, D, chunk=None):
    """Sequential SSD recurrence.  x (b,L,H,P); dt (b,L,H); B/C (b,L,H,N);
    A_log (H,); D (H,).  Matches mamba2.ssd_chunked (x pre-scaled by dt
    inside, as in the chunked version)."""
    b, L, H, P = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    xdt = x.astype(f32) * dt[..., None].astype(f32)
    decay = jnp.exp(-jnp.exp(A_log.astype(f32))[None, None] * dt.astype(f32))

    def step(h, inp):
        xt, Bt, Ct, dect = inp                      # (b,H,P),(b,H,N),(b,H,N),(b,H)
        h = h * dect[..., None, None] + jnp.einsum("bhn,bhp->bhnp", Bt, xt)
        y = jnp.einsum("bhn,bhnp->bhp", Ct, h)
        return h, y
    h0 = jnp.zeros((b, H, N, P), f32)
    _, ys = jax.lax.scan(step, h0, (jnp.moveaxis(xdt, 1, 0),
                                    jnp.moveaxis(B.astype(f32), 1, 0),
                                    jnp.moveaxis(C.astype(f32), 1, 0),
                                    jnp.moveaxis(decay, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1)
    return y + D.astype(f32)[None, None, :, None] * xdt


# ---------------------------------------------------------------------------
# self-confidence KD loss (eq. 7-9)
# ---------------------------------------------------------------------------
def kd_loss(student_logits, teacher_logits, labels, rho, lam, tau):
    """-> per-sample loss (B,)."""
    s = student_logits.astype(jnp.float32)
    t = teacher_logits.astype(jnp.float32)
    C = s.shape[-1]
    p_t = jax.nn.softmax(t / tau, -1)
    onehot = jax.nn.one_hot(labels, C, dtype=jnp.float32)
    damp = (1.0 - rho)[None, :] * p_t
    non_true = damp * (1.0 - onehot)
    true_mass = 1.0 - non_true.sum(-1, keepdims=True)
    target = non_true + onehot * true_mass
    # CE
    lse = jax.nn.logsumexp(s, -1)
    gold = jnp.sum(s * onehot, -1)
    ce = lse - gold
    # KL(target ‖ student_T)
    logp = jax.nn.log_softmax(s / tau, -1)
    tgt = jnp.clip(target, 1e-9, 1.0)
    kl = jnp.sum(tgt * (jnp.log(tgt) - logp), -1) * tau ** 2
    return (1 - lam) * ce + lam * kl
