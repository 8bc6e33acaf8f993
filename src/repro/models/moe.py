"""Mixture-of-Experts FFN (DeepSeek-V3 256e top-8 + shared; Llama-4 16e top-1
+ shared).

TPU adaptation: dispatch uses the capacity-based scatter/gather formulation —
``expert_inputs (E, C, d) = scatter(x)`` followed by a batched expert einsum
``ecd,edf->ecf``.  The expert dimension E shards cleanly over the "model"
mesh axis (expert parallelism); under pjit the scatter/gather lowers to an
all-to-all pair, which is exactly the communication pattern the roofline
analysis tracks.  No (T, E, C) one-hot dispatch tensor is ever materialised.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L


def _constrain(x, *spec):
    """Pin `x` to `spec` on the mesh declared with ``jax.set_mesh`` (the
    dry-run, a sharded pod round).  Without a declared mesh there is
    nothing to pin to and `x` passes through; under one, a spec the mesh
    cannot take raises."""
    if jax.sharding.get_abstract_mesh().empty:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def moe_init(key, cfg, dtype=jnp.float32):
    m = cfg.moe
    d = cfg.d_model
    kr, ke, ks = jax.random.split(key, 3)
    keg, keu, ked = jax.random.split(ke, 3)
    p = {
        "router": L.linear_init(kr, d, m.n_experts, dtype=jnp.float32),
        "experts": {
            "gate": L._dense_init(keg, (m.n_experts, d, m.d_ff_expert), in_axis=1, dtype=dtype),
            "up": L._dense_init(keu, (m.n_experts, d, m.d_ff_expert), in_axis=1, dtype=dtype),
            "down": L._dense_init(ked, (m.n_experts, m.d_ff_expert, d), in_axis=1, dtype=dtype),
        },
    }
    if m.n_shared_experts > 0:
        p["shared"] = L.mlp_init(ks, d, m.d_ff_expert * m.n_shared_experts,
                                 dtype=dtype)
    return p


def moe_apply(p, x, cfg):
    """x (B, L, d) -> (y, aux_loss)."""
    m = cfg.moe
    B, Lq, d = x.shape
    T = B * Lq
    xt = x.reshape(T, d)

    logits = L.linear(p["router"], xt.astype(jnp.float32))      # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, m.top_k)                  # (T, k)
    topw = topw / jnp.clip(topw.sum(-1, keepdims=True), 1e-9)

    # flatten (token, k-choice) assignments
    flat_e = topi.reshape(-1)                                    # (T*k,)
    flat_w = topw.reshape(-1)
    cap = int(max(1, (T * m.top_k * m.capacity_factor) // m.n_experts))

    # per-expert counts — also feeds the load-balance aux loss without ever
    # materialising a (T·k, E) one-hot (§Perf iteration 5: the cumsum-based
    # position assignment read/wrote a (T·k, E) int tensor per MoE layer;
    # the sort-based ranking below is O(T·k) memory)
    counts = jnp.zeros((m.n_experts,), jnp.int32).at[flat_e].add(1)
    me = probs.mean(0)                                           # (E,)
    ce = counts.astype(jnp.float32) / (T * m.top_k)
    aux = m.router_aux_coef * m.n_experts * jnp.sum(me * ce)

    # position of each assignment within its expert via stable sort:
    # identical ordering to the cumsum formulation (token order preserved)
    starts = jnp.cumsum(counts) - counts                         # exclusive
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    pos_sorted = jnp.arange(flat_e.shape[0], dtype=jnp.int32) \
        - starts[sorted_e].astype(jnp.int32)
    pos_in_e = jnp.zeros_like(flat_e).at[order].set(pos_sorted)
    keep = pos_in_e < cap
    pos_in_e = jnp.where(keep, pos_in_e, cap)                    # overflow slot

    xin = jnp.repeat(xt, m.top_k, axis=0)                        # (T*k, d)
    xin = _constrain(xin, "data", None)
    # dispatch: scatter into the expert-parallel buffer.  The constraints
    # pin token tensors to "data" and expert buffers to "model" so GSPMD
    # lowers the dispatch/return as data↔expert all-to-alls instead of
    # replicating the (E, C, d) buffers (§Perf iteration 3: 17.4 TB → see
    # EXPERIMENTS.md).
    buf = jnp.zeros((m.n_experts, cap + 1, d), x.dtype)
    buf = buf.at[flat_e, pos_in_e].add(xin * keep[:, None].astype(x.dtype))
    buf = _constrain(buf[:, :cap], cfg.moe_dispatch_axis, None, None)

    ew = p["experts"]
    h = jnp.einsum("ecd,edf->ecf", buf, ew["gate"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, ew["up"].astype(x.dtype))
    out = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u,
                     ew["down"].astype(x.dtype))                 # (E, C, d)
    out = _constrain(out, cfg.moe_dispatch_axis, None, None)

    out = jnp.pad(out, ((0, 0), (0, 1), (0, 0)))                 # overflow row
    gathered = out[flat_e, pos_in_e]                             # (T*k, d)
    gathered = _constrain(gathered, "data", None)
    gathered = gathered * (flat_w * keep)[:, None].astype(x.dtype)
    y = gathered.reshape(T, m.top_k, d).sum(1)

    if "shared" in p:
        y = y + L.mlp(p["shared"], xt)
    return y.reshape(B, Lq, d), aux
