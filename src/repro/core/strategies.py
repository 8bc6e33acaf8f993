"""FL strategy algebra — the paper's contribution (FedADC, Alg. 3/4) plus
every baseline it compares against, expressed over opaque parameter pytrees
so the same code drives both the paper-scale simulator (CNN/ResNet on
CIFAR-like data) and the pod-scale engine (the 10 assigned architectures).

Interface (all pure functions, jit/scan friendly):
  server_init(params)              -> server_state dict
  client_setup(server_state, fed)  -> ctx broadcast to clients (e.g. m̄_t)
  local_step(theta, ctx, grad_fn, batch, fed, extra) -> (theta', extra')
       `extra` carries per-local-step state (double-momentum EMA, step idx).
  server_aggregate(deltas, weights, fed) -> mean_delta
       deltas stacked over clients (leading axis K); weights (K,) from the
       pluggable aggregator (repro.federated.aggregation) — uniform,
       example-weighted, or DRAG divergence-adaptive.
  server_update(server_state, theta_t, mean_delta, fed)
       -> (theta_{t+1}, server_state')
  mean_delta is Σ_i w_i (θ_t - θ_i^H) / Σ_i w_i  (the *pseudo gradient × η*;
  the paper's 1/|S| mean under uniform weights).

Strategies whose clients carry cross-round state (SCAFFOLD c_i, FedDyn h_i,
MOON previous model) additionally implement client_state_* hooks used by the
simulator; the pod engine restricts itself to stateless-client strategies
(see DESIGN.md §Engines).

The wire (uplink compression, downlink broadcast codecs, byte accounting)
is NOT a strategy concern: engines compose a strategy with a Transport and
a ClientStore through repro.federated.protocol.RoundProtocol (DESIGN.md
§Transport).  The old ``compress_delta`` hook remains as a deprecation
shim only.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core import tree as T

# hooks that have already fired their deprecation warning this process —
# keyed by hook name so a shim warns once, not once per call site or (worse)
# once per jit re-trace of the round function
_DEPRECATION_WARNED: set = set()


def _warn_deprecated(hook: str, replacement: str) -> None:
    if hook in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(hook)
    warnings.warn(f"{hook} is deprecated; use {replacement} "
                  f"(DESIGN.md §Transport migration table)",
                  DeprecationWarning, stacklevel=3)


def _maybe_clip(g, fed: FedConfig):
    if fed.grad_clip > 0:
        g = T.clip_by_global_norm(g, fed.grad_clip)
    return g


def _wd(theta, g, fed: FedConfig):
    if fed.weight_decay > 0:
        g = T.axpy(fed.weight_decay, theta, g)
    return g


def _sgd_step(theta, g, eta, fed):
    g = _wd(theta, _maybe_clip(g, fed), fed)
    if fed.use_pallas:
        from repro.kernels import ops
        return ops.tree_fused_axpy(theta, g, -eta)
    return jax.tree.map(lambda t, gi: t - eta * gi, theta, g)


# ---------------------------------------------------------------------------
# FedAvg (Alg. 1)
# ---------------------------------------------------------------------------
class FedAvg:
    name = "fedavg"
    stateless_clients = True

    def server_init(self, params):
        return {}

    def client_setup(self, server_state, params, fed):
        return {}

    def init_extra(self, params, fed):
        return None

    def local_step(self, theta, ctx, grad_fn, batch, fed, extra):
        g, aux = grad_fn(theta, batch)
        return _sgd_step(theta, g, fed.eta, fed), extra, aux

    def compress_delta(self, delta, ef, key, fed):
        """DEPRECATED shim — the uplink hook moved off the strategy and into
        the wire layer: use ``repro.federated.transport.Transport.uplink``
        (engines drive it through ``RoundProtocol.uplink``).  Kept for one
        release so external callers migrate gracefully; warns once per
        process, then delegates to a cached stateless Transport with the
        exact pre-redesign semantics."""
        _warn_deprecated("strategy.compress_delta",
                         "RoundProtocol.uplink / Transport.uplink")
        from repro.federated.transport import shim_transport  # lazy: layering
        return shim_transport(fed).uplink(delta, ef, key)

    def server_aggregate(self, deltas, weights, fed):
        """Δ̄ = Σ_i w_i·Δ_i / Σ_i w_i over client-stacked deltas.  Shared by
        every strategy; with fed.use_pallas the reduction runs as one fused
        VMEM pass (kernels/weighted_reduce.py)."""
        from repro.federated.aggregation import weighted_mean  # lazy: layering
        return weighted_mean(deltas, weights, use_pallas=fed.use_pallas)

    def server_update(self, server_state, theta_t, mean_delta, fed):
        # θ_{t+1} = mean(θ_i^H) = θ_t - mean_delta
        return T.sub(theta_t, mean_delta), server_state


def _theta_step(theta_t, m, fed):
    """θ_{t+1} = θ_t − α·η·m, computed in fp32 and cast back to the
    parameter dtype (the fp32 momentum must not promote bf16 parameters)."""
    theta = T.axpy(-fed.alpha * fed.eta, m, T.cast(theta_t, jnp.float32))
    return jax.tree.map(lambda nt, t: nt.astype(t.dtype), theta, theta_t)


# ---------------------------------------------------------------------------
# SlowMo (Alg. 2) — server momentum over pseudo gradients.
# ---------------------------------------------------------------------------
class SlowMo(FedAvg):
    name = "slowmo"

    def server_init(self, params):
        # the momentum accumulates Δ̄ across rounds: it is held in fp32
        # regardless of the parameter/wire dtype (a bf16 m loses small
        # late-round pseudo-gradients — the fp32 cast-on-write contract,
        # server side; checked by the trace-accumulation-dtype audit)
        return {"m": T.cast(T.zeros_like(params), jnp.float32)}

    def server_update(self, server_state, theta_t, mean_delta, fed):
        g_bar = T.scale(T.cast(mean_delta, jnp.float32),
                        1.0 / fed.eta)                      # line 12
        m = T.axpy(fed.beta_global, server_state["m"], g_bar)  # line 14
        theta = _theta_step(theta_t, m, fed)                # line 16
        return theta, {"m": m}


# ---------------------------------------------------------------------------
# FedADC (Alg. 3) — THE PAPER'S CONTRIBUTION.
# The global momentum m_t is normalised (m̄_t = β_local · m_t / H) and
# embedded into every local iteration; the server applies the small
# correction (β_global − β_local)·m_t when rebuilding the pseudo momentum.
# ---------------------------------------------------------------------------
class FedADC(FedAvg):
    name = "fedadc"

    def server_init(self, params):
        # fp32 momentum independent of the parameter/wire dtype — see
        # SlowMo.server_init
        return {"m": T.cast(T.zeros_like(params), jnp.float32)}

    def client_setup(self, server_state, params, fed):
        # line 5: m̄_t = β_local · m_t / H, broadcast in the params dtype
        # (the fp32 momentum must not promote a bf16 wire)
        m_bar = T.scale(server_state["m"],
                        fed.beta_local / fed.local_steps)
        return {"m_bar": jax.tree.map(lambda m, p: m.astype(p.dtype),
                                      m_bar, params)}

    # ctx broadcast leaves are an exact scalar image of the θ-delta
    # (server_update: Δθ_t = −α·η·m_t while m̄_t = β_l/H · m_t), so the
    # delta-coded downlink derives the ctx from the θ wire instead of
    # transporting it — the momentum-aware 0-byte ctx (DESIGN.md
    # §Transport).  `delta_params` is the decoded θ-delta the clients
    # received; the scale is config-derived, never transmitted.
    def _ctx_scale(self, fed):
        return -fed.beta_local / (fed.local_steps * fed.alpha * fed.eta)

    def ctx_from_broadcast_delta(self, delta_params, fed):
        return {"m_bar": T.scale(delta_params, self._ctx_scale(fed))}

    def local_step(self, theta, ctx, grad_fn, batch, fed, extra):
        m_bar = ctx["m_bar"]
        if fed.variant == "nesterov":
            # red: θ^{τ-1/2} = θ − η·m̄ ; g at θ^{τ-1/2}; θ = θ^{τ-1/2} − η·g
            theta_half = jax.tree.map(lambda t, m: t - fed.eta * m,
                                      theta, m_bar)
            g, aux = grad_fn(theta_half, batch)
            theta_new = _sgd_step(theta_half, g, fed.eta, fed)
        else:
            # blue (heavy-ball): θ = θ − η·(g + m̄)
            g, aux = grad_fn(theta, batch)
            g_total = T.add(_maybe_clip(g, fed), m_bar)
            theta_new = jax.tree.map(lambda t, gt: t - fed.eta * gt,
                                     theta, _wd(theta, g_total, fed))
        return theta_new, extra, aux

    def server_update(self, server_state, theta_t, mean_delta, fed):
        delta_bar = T.scale(T.cast(mean_delta, jnp.float32),
                            1.0 / fed.eta)                  # line 16
        m = T.axpy(fed.beta_global - fed.beta_local,
                   server_state["m"], delta_bar)            # line 17
        theta = _theta_step(theta_t, m, fed)                # line 19
        return theta, {"m": m}


# ---------------------------------------------------------------------------
# FedADC with double momentum (Alg. 4).
# ---------------------------------------------------------------------------
class FedADCDouble(FedADC):
    name = "fedadc_double"

    def client_setup(self, server_state, params, fed):
        m_bar = T.scale(server_state["m"],
                        fed.beta_global / fed.local_steps)
        return {"m_bar": jax.tree.map(lambda m, p: m.astype(p.dtype),
                                      m_bar, params)}

    def _ctx_scale(self, fed):
        # Alg. 4 broadcasts m̄_t = β_g/H · m_t against the same Δθ = −αη·m_t
        return -fed.beta_global / (fed.local_steps * fed.alpha * fed.eta)

    def init_extra(self, params, fed):
        return {"m_local": T.zeros_like(params), "tau": jnp.zeros((), jnp.int32)}

    def local_step(self, theta, ctx, grad_fn, batch, fed, extra):
        g, aux = grad_fn(theta, batch)
        g = _maybe_clip(g, fed)
        is_first = (extra["tau"] == 0)
        m_local = jax.tree.map(
            lambda ml, gi: jnp.where(is_first, gi,
                                     fed.phi * ml + (1 - fed.phi) * gi),
            extra["m_local"], g)                             # lines 9-12
        upd = T.add(ctx["m_bar"], m_local)                   # line 14
        theta_new = jax.tree.map(lambda t, u: t - fed.eta * u, theta,
                                 _wd(theta, upd, fed))
        return theta_new, {"m_local": m_local, "tau": extra["tau"] + 1}, aux

    def server_update(self, server_state, theta_t, mean_delta, fed):
        m = T.scale(T.cast(mean_delta, jnp.float32),
                    1.0 / fed.eta)                           # line 21 (no carry)
        theta = _theta_step(theta_t, m, fed)                 # line 23
        return theta, {"m": m}


# ---------------------------------------------------------------------------
# FedProx — proximal term μ/2‖θ − θ_t‖² added to the local objective.
# ---------------------------------------------------------------------------
class FedProx(FedAvg):
    name = "fedprox"

    def client_setup(self, server_state, params, fed):
        return {"theta_t": params}

    def local_step(self, theta, ctx, grad_fn, batch, fed, extra):
        g, aux = grad_fn(theta, batch)
        g = T.add(g, T.scale(T.sub(theta, ctx["theta_t"]), fed.mu_prox))
        return _sgd_step(theta, g, fed.eta, fed), extra, aux


# ---------------------------------------------------------------------------
# SCAFFOLD — control variates (stateful clients; simulator only).
# ---------------------------------------------------------------------------
class Scaffold(FedAvg):
    name = "scaffold"
    stateless_clients = False

    def server_init(self, params):
        return {"c": T.zeros_like(params)}

    def client_state_init(self, params):
        return {"c_i": T.zeros_like(params)}

    def client_setup(self, server_state, params, fed):
        return {"c": server_state["c"]}

    def local_step(self, theta, ctx, grad_fn, batch, fed, extra):
        g, aux = grad_fn(theta, batch)
        g = T.add(T.sub(g, extra["c_i"]), ctx["c"])
        return _sgd_step(theta, g, fed.eta, fed), extra, aux

    def client_state_update(self, client_state, ctx, theta_t, theta_H, fed):
        # option II: c_i' = c_i − c + (θ_t − θ_H)/(H·η)
        c_new = T.add(T.sub(client_state["c_i"], ctx["c"]),
                      T.scale(T.sub(theta_t, theta_H),
                              1.0 / (fed.local_steps * fed.eta)))
        return {"c_i": c_new}

    def server_update_scaffold(self, server_state, theta_t, mean_delta,
                               mean_dc, fed, part_frac):
        theta = T.sub(theta_t, mean_delta)
        c = T.add(server_state["c"], T.scale(mean_dc, part_frac))
        return theta, {"c": c}


# ---------------------------------------------------------------------------
# FedDyn — dynamic regularisation (stateful clients; simulator only).
# ---------------------------------------------------------------------------
class FedDyn(FedAvg):
    name = "feddyn"
    stateless_clients = False

    def server_init(self, params):
        return {"h": T.zeros_like(params)}

    def client_state_init(self, params):
        return {"grad_corr": T.zeros_like(params)}

    def client_setup(self, server_state, params, fed):
        return {"theta_t": params}

    def local_step(self, theta, ctx, grad_fn, batch, fed, extra):
        g, aux = grad_fn(theta, batch)
        # ∇ [ f_i(θ) − <∇̂_i, θ> + α/2 ‖θ − θ_t‖² ]
        g = T.sub(g, extra["grad_corr"])
        g = T.add(g, T.scale(T.sub(theta, ctx["theta_t"]), fed.feddyn_alpha))
        return _sgd_step(theta, g, fed.eta, fed), extra, aux

    def client_state_update(self, client_state, ctx, theta_t, theta_H, fed):
        gc = T.sub(client_state["grad_corr"],
                   T.scale(T.sub(theta_H, theta_t), fed.feddyn_alpha))
        return {"grad_corr": gc}

    def server_update_feddyn(self, server_state, theta_t, mean_theta_H,
                             mean_drift_all, fed):
        # h ← h − α · (1/N) Σ_i (θ_i^H − θ_t);  θ ← mean(θ^H) − h/α
        h = T.sub(server_state["h"], T.scale(mean_drift_all, fed.feddyn_alpha))
        theta = T.sub(mean_theta_H, T.scale(h, 1.0 / fed.feddyn_alpha))
        return theta, {"h": h}


STRATEGIES: Dict[str, Any] = {
    s.name: s for s in
    (FedAvg(), SlowMo(), FedADC(), FedADCDouble(), FedProx(), Scaffold(),
     FedDyn())
}
# loss-modifier strategies reuse FedAvg/FedADC update algebra:
for alias in ("moon", "fedgkd", "fedntd", "fedrs"):
    STRATEGIES[alias] = FedAvg()


def get_strategy(name: str):
    if name == "fedadc+":
        return STRATEGIES["fedadc"]
    if name not in STRATEGIES:
        raise KeyError(f"unknown strategy {name!r}; known {sorted(STRATEGIES)}")
    return STRATEGIES[name]
