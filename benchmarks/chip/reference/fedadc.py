"""Plain FedADC round (Nesterov variant) over a reference model's loss.

One communication round, as the FedADC paper's Algorithm 3 states it:

* the server broadcasts θ_t and m̄ = β_local · m_t / H;
* each client runs H local steps from θ_t:
  θ½ = θ − η·m̄, g = ∇loss(θ½), θ = θ½ − η·g;
* the server averages the client deltas Δ_i = θ_t − θ_i^H uniformly,
  m_{t+1} = (β_global − β_local)·m_t + Δ̄/η and θ_{t+1} = θ_t − α·η·m_{t+1}.

Everything is float32.

Clients run one after another, so the round fits beside nothing else on
the device; ``block_rows`` sums each local step's gradient over blocks of
that many rows where a step's rows at once would not fit.
``fault`` plants one of the faults the correctness check must catch:
``"half_clients"`` leaves out the second half of the clients and averages
over the rest; ``"half_rows"`` takes each local step's gradient over the
first half of its rows only, as a data-parallel step that skips the
exchange between its shards would.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


FAULTS = ("half_clients", "half_rows")


def _loss_and_grad(model, params, tokens, cfg, block_rows):
    """Mean loss and gradient over the rows of `tokens` (b, L), summed
    block by block (every block holds as many rows, so the mean of the
    block means is the mean)."""
    b = tokens.shape[0]
    blocks = tokens.reshape(b // block_rows, block_rows, tokens.shape[1])
    vg = jax.value_and_grad(model.loss)

    def body(carry, blk):
        lsum, gsum = carry
        l, g = vg(params, blk, cfg)
        return (lsum + l, jax.tree.map(jnp.add, gsum, g)), None
    zeros = jax.tree.map(jnp.zeros_like, params)
    (lsum, gsum), _ = jax.lax.scan(body, (jnp.zeros(()), zeros), blocks)
    n = blocks.shape[0]
    return lsum / n, jax.tree.map(lambda g: g / n, gsum)


@functools.partial(jax.jit, static_argnames=("model", "cfg_items",
                                             "block_rows", "eta"))
def _client_delta(theta_t, m_bar, tokens, *, model, cfg_items, block_rows,
                  eta):
    """Δ = θ_t − θ^H and the mean local loss for one client's (H, b, L)
    tokens."""
    cfg = dict(cfg_items)

    def step(theta, toks):
        half = jax.tree.map(lambda t, m: t - eta * m, theta, m_bar)
        l, g = _loss_and_grad(model, half, toks, cfg, block_rows)
        return jax.tree.map(lambda t, gi: t - eta * gi, half, g), l
    theta_h, losses = jax.lax.scan(step, theta_t, tokens)
    return jax.tree.map(jnp.subtract, theta_t, theta_h), jnp.mean(losses)


@jax.jit
def _accumulate(acc, delta):
    return jax.tree.map(jnp.add, acc, delta)


@functools.partial(jax.jit, static_argnames=("n", "eta", "alpha", "gamma"))
def _server_update(params, m, acc, *, n, eta, alpha, gamma):
    m_new = jax.tree.map(lambda mi, a: gamma * mi + (a / n) / eta, m, acc)
    theta = jax.tree.map(lambda p, mi: p - alpha * eta * mi, params, m_new)
    return theta, m_new


@functools.partial(jax.jit, static_argnames=("scale",))
def _scale(m, *, scale):
    return jax.tree.map(lambda x: scale * x, m)


def fedadc_round(model, params, m, tokens, cfg, fed, block_rows=None,
                 fault=None):
    """One round of `model` (a module with ``loss(params, tokens, cfg)``).
    params, m: float32 trees; tokens (C, H, b, L); cfg: the model's sizes
    (hashable values); fed: dict with eta, alpha, beta_global, beta_local;
    block_rows: rows a gradient block (default: all of a step's rows).
    -> (params', m', mean client loss as a device scalar)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    C, H, b, _ = tokens.shape
    if fault == "half_clients":
        C = C // 2
        tokens = tokens[:C]
    if fault == "half_rows":
        b = b // 2
        tokens = tokens[:, :, :b]
    m_bar = _scale(m, scale=fed["beta_local"] / H)
    client = functools.partial(
        _client_delta, model=model, cfg_items=tuple(sorted(cfg.items())),
        block_rows=min(block_rows or b, b), eta=fed["eta"])
    acc = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for c in range(C):
        delta, loss = client(params, m_bar, tokens[c])
        acc = _accumulate(acc, delta)
        losses.append(loss)
    params, m = _server_update(
        params, m, acc, n=C, eta=fed["eta"], alpha=fed["alpha"],
        gamma=fed["beta_global"] - fed["beta_local"])
    return params, m, jnp.mean(jnp.stack(losses))
