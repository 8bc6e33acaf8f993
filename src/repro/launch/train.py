"""Pod-scale federated round engine.

Maps one FedADC communication round onto the production mesh:

* the model is FSDP-sharded over "data" and tensor-parallel over "model";
* each client's H local steps run as an inner ``lax.scan`` (local batch
  sharded over "data");
* clients are processed client-serially per pod (``lax.scan``, delta
  accumulation — linearity of the FedADC aggregation makes waves exact),
  and client-parallel across the "pod" axis (``vmap``; the Δ̄/momentum
  all-reduce over pods is the ONLY cross-pod collective per round, which is
  the FL communication pattern);
* the server update (pseudo-momentum + model update) is sharded pointwise;
* both wire directions ride the round protocol's ``Transport`` (DESIGN.md
  §Transport): the (θ_t, ctx) broadcast through the downlink codec, each
  client delta through the uplink codec inside the client-serial scan;
* per-client error-feedback residuals live in a mesh-resident
  ``sharded_*`` client store inside the train state (``state["clients"]``,
  leading axis ``fed.n_clients``; parameter dims shard like the parameter
  they mirror) — this engine is no longer stateless-client for EF, which
  lifts the old "lossy compression + error_feedback rejected on the pod
  engine" restriction.

``train_step(state, batch)`` is one full communication round:
batch["tokens"]: (CP, CS, H, b, L) where CP·CS = clients_per_round and
H = fed.local_steps.  When the EF store is active, batch["client_ids"]
(CP, CS) int32 names the round's clients; it defaults to slots 0..R−1.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FedConfig, ModelConfig, RunConfig
from repro.core import distillation as D
from repro.core import tree as T
from repro.core.strategies import get_strategy
from repro.federated import aggregation as A
from repro.federated import store as CS
from repro.federated.fleet import hierarchy as FH
from repro.federated.reference import ReferenceStore
from repro.federated.transport import Transport
from repro.models.registry import get_model
from repro.telemetry import drift as drift_metrics

POD_SUPPORTED = ("fedavg", "slowmo", "fedadc", "fedadc_double", "fedprox",
                 "fedadc+")


def _wire_dtype(run: RunConfig):
    """The dtype client deltas (and hence EF residuals) live in: the
    compute dtype under the mixed-precision round, else the param dtype."""
    mixed = (jnp.dtype(run.param_dtype) == jnp.float32
             and jnp.dtype(run.compute_dtype) == jnp.bfloat16)
    return jnp.dtype(run.compute_dtype) if mixed else jnp.dtype(run.param_dtype)


def _broadcast_inputs(strategy, params, server, fed: FedConfig,
                      run: RunConfig):
    """(θ_t, server view, ctx) in the wire dtype: the mixed-precision round
    broadcasts bf16 (§Perf iteration 7) — shared by ``init_state`` (the
    delta codec's round-0 reference must match the round-0 broadcast
    bitwise) and ``train_step``."""
    compute_dtype = jnp.dtype(run.compute_dtype)
    mixed = (jnp.dtype(run.param_dtype) == jnp.float32
             and compute_dtype == jnp.bfloat16)
    theta_t = T.cast(params, compute_dtype) if mixed else params
    server_view = server
    if mixed and "m" in server:
        server_view = dict(server, m=T.cast(server["m"], compute_dtype))
    ctx = strategy.client_setup(server_view, theta_t, fed)
    return theta_t, server_view, ctx, mixed


def init_state(rng, mcfg: ModelConfig, fed: FedConfig, run: RunConfig):
    model = get_model(mcfg)
    dtype = jnp.dtype(run.param_dtype)
    params = model.init(rng, mcfg, dtype=dtype)
    strategy = get_strategy(fed.strategy)
    state = {"params": params,
             "server": strategy.server_init(params),
             "round": jnp.zeros((), jnp.int32)}
    transport = Transport(fed)
    if transport.ef_enabled:
        # mesh-resident per-client EF store (leading axis n_clients); dtype
        # matches the wire the residual is the complement of
        ef_template = T.cast(params, _wire_dtype(run))
        state["clients"] = {"ef": CS.sharded_init(ef_template, fed.n_clients)}
    if transport.stateful_downlink:
        # only the *lossy* delta codec is stateful: its broadcast reference
        # lives in the train state (sharded like the parameters it mirrors)
        # so it survives jit and rides the pod mesh; the round-0 reference
        # is the initial sync.  The lossless delta downlink derives its
        # reference from θ_t itself, so the train state carries none.
        theta_w, _, ctx0, _ = _broadcast_inputs(strategy, params,
                                                state["server"], fed, run)
        state["refs"] = {
            "downlink": transport.init_downlink_ref(theta_w, ctx0)}
    return state


def state_shapes(mcfg: ModelConfig, fed: FedConfig, run: RunConfig):
    """abstract state (no allocation) for the dry-run."""
    rng = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda r: init_state(r, mcfg, fed, run), rng)


def _token_histogram(tokens, vocab: int, valid=None):
    """Client token statistics for the FedADC+ ρ vector; positions with
    `valid` False (padding) are excluded."""
    flat = tokens.reshape(-1)
    w = jnp.ones_like(flat, jnp.float32) if valid is None \
        else valid.reshape(-1).astype(jnp.float32)
    return jnp.zeros((vocab,), jnp.float32).at[flat].add(w)


def _local_objective(model, mcfg: ModelConfig, fed: FedConfig,
                     run: RunConfig):
    """Builds loss(theta, step_batch, theta_t, rho) for one local step."""
    use_pallas = fed.use_pallas

    def loss(theta, sb, theta_t, rho):
        if not fed.distill:
            l, aux = model.loss_fn(theta, sb, mcfg, use_pallas, run.remat)
            return l
        # FedADC+ self-confidence KD: teacher = global model θ_t (eq. 7-9),
        # ρ from the client's token statistics.
        s_logits, aux_l = model.forward(theta, sb, mcfg, use_pallas, run.remat)
        t_logits, _ = model.forward(jax.lax.stop_gradient(theta_t), sb, mcfg,
                                    use_pallas, run.remat)
        if mcfg.n_patch_tokens > 0 and "patch_embeds" in sb:
            np_ = sb["patch_embeds"].shape[1]
            s_logits, t_logits = s_logits[:, np_:], t_logits[:, np_:]
        labels = sb["labels"][:, 1:]
        s_l, t_l = s_logits[:, :-1], t_logits[:, :-1]
        mask = (labels >= 0)
        V = s_l.shape[-1]
        flat_s = s_l.reshape(-1, V)
        flat_t = t_l.reshape(-1, V)
        flat_y = jnp.clip(labels.reshape(-1), 0)
        kd, _ = D.masked_self_confidence_kd_loss(
            flat_s, flat_t, flat_y, rho, fed.distill_lambda, fed.distill_tau,
            mask.reshape(-1))
        return kd + 0.0 * aux_l
    return loss


def make_train_step(mcfg: ModelConfig, fed: FedConfig, run: RunConfig,
                    client_parallel: int = 1, telemetry=None):
    """-> train_step(state, batch).  One communication round.

    With an enabled ``telemetry``, the aux dict gains a ``"telemetry"``
    sub-dict of in-jit drift scalars (streaming weighted dispersion,
    ``||Δ̄||``, momentum alignment, EF-residual norm); with telemetry off
    (the default) the returned program is bit-identical to the
    pre-telemetry one — the gate is a static Python fact, never a traced
    value."""
    with_metrics = telemetry is not None and telemetry.enabled
    if fed.strategy not in POD_SUPPORTED:
        raise ValueError(
            f"pod engine supports stateless-client strategies {POD_SUPPORTED};"
            f" use the simulator for {fed.strategy} (per-client state).")
    if fed.aggregator == "drag" and fed.strategy in ("fedavg", "fedprox"):
        raise ValueError(
            "drag aggregation in the pod engine needs a server-momentum "
            "reference (slowmo/fedadc/fedadc_double); the client-serial "
            "scan has no round mean to fall back on.")
    transport = Transport(fed)
    transported = transport.up is not None
    sparse_native = transport.sparse_native
    ef_enabled = transport.ef_enabled
    lossy_down = transport.down is not None and transport.down.lossy
    model = get_model(mcfg)
    strategy = get_strategy(fed.strategy)
    loss_fn = _local_objective(model, mcfg, fed, run)

    def client_delta(theta_t, ctx, cb):
        """cb: dict with leading (H, b) -> (delta, mean loss)."""
        rho = None
        if fed.distill:
            hist = _token_histogram(cb["tokens"], mcfg.vocab_size,
                                    valid=(cb["labels"] >= 0))
            rho = hist / jnp.maximum(hist.max(), 1.0)

        def local(carry, sb):
            theta, extra = carry

            def grad_fn(th, _):
                with jax.named_scope("fedadc.fwd_bwd"):
                    l, g = jax.value_and_grad(loss_fn)(th, sb, theta_t, rho)
                return g, l
            theta, extra, l = strategy.local_step(theta, ctx, grad_fn, None,
                                                  fed, extra)
            return (theta, extra), l

        extra0 = strategy.init_extra(theta_t, fed)
        # the local steps' loop is the local update's phase, so the ops the
        # compiler derives from the loop body land in it too
        with jax.named_scope("fedadc.local_update"):
            (theta_H, _), ls = jax.lax.scan(local, (theta_t, extra0), cb)
        with jax.named_scope("fedadc.uplink"):
            delta = T.sub(theta_t, theta_H)
        return delta, jnp.mean(ls)

    def per_group(theta_t, ctx, ref, cbs, gkey, efs=None):
        """cbs: dict with leading (CS, H, b) — serial clients, weighted
        Δ-accumulation.  The aggregator weight for each client is computed in
        streaming form (repro.federated.aggregation.streaming_weight) against
        the server-momentum reference direction, so DRAG-style adaptive
        weighting works without materialising the CS deltas.  Each client's
        delta rides the transport's uplink round trip against its gathered
        EF residual (`efs`, leading CS; zeros when EF is off) before
        weighting/accumulation, so the aggregate is built from the server's
        wire reconstructions and the updated residuals flow back out for the
        scatter into the sharded client store.  `efs` is None when the EF
        store is off — each client then compresses against a zero residual
        (the pre-store behaviour) and a scalar dummy rides the scan ys."""
        cs = jax.tree.leaves(cbs)[0].shape[0]
        ckeys = jax.random.split(gkey, cs)

        def serial(carry, inp):
            cb, ck = inp[:2]
            ef = inp[2] if efs is not None else None
            if with_metrics:
                acc, wsum, sqsum = carry
            else:
                acc, wsum = carry
            d, l = client_delta(theta_t, ctx, cb)
            with jax.named_scope("fedadc.uplink"):
                new_ef = ef if efs is not None else jnp.zeros(())
                if transported:
                    # sparse-native: encode only — the (values, indices)
                    # wire is scatter-accumulated below at k-cost, and the
                    # EF residual from encode is the exact complement the
                    # roundtrip would return (the scan carry stays
                    # dense-output/sparse-input)
                    up = transport.uplink_encode if sparse_native \
                        else transport.uplink
                    d, new_ef = up(d, T.zeros_like(d) if ef is None else ef,
                                   ck)
                    if efs is None:
                        new_ef = jnp.zeros(())   # residual not carried
            with jax.named_scope("fedadc.accumulate"):
                w = A.streaming_weight(d, ref, fed.aggregator,
                                       fed.drag_lambda)
                # Σ w·Δ accumulates in fp32 regardless of the wire dtype: a
                # bf16 running sum loses the late clients to rounding once
                # the partial sum's ulp outgrows the increments; cast on
                # write happens after the cross-pod aggregation below
                if sparse_native:
                    # per coordinate this is the same client-ordered fp32
                    # add chain as the dense decode path (whose off-support
                    # adds are exact +0.0 no-ops), so the two are
                    # bit-identical
                    acc = jax.tree.map(
                        lambda wl, a: a.reshape(-1).at[wl.indices].add(
                            w * wl.values.astype(jnp.float32)
                        ).reshape(a.shape),
                        d, acc, is_leaf=A.is_sparse_leaf)
                else:
                    acc = jax.tree.map(
                        lambda a, di: a + w * di.astype(jnp.float32), acc, d)
                wsum = wsum + w
                if with_metrics:
                    # the only telemetry cost in the scan: one fp32 scalar,
                    # Σ w·||Δ||², for the streaming-dispersion identity
                    sqsum = sqsum + drift_metrics.streaming_sq_norm(d, w)
            if with_metrics:
                return (acc, wsum, sqsum), (l, new_ef)
            return (acc, wsum), (l, new_ef)
        acc0 = (T.cast(T.zeros_like(theta_t), jnp.float32), jnp.zeros(()))
        if with_metrics:
            acc0 = acc0 + (jnp.zeros(()),)
        xs = (cbs, ckeys) if efs is None else (cbs, ckeys, efs)
        carry_out, (ls, new_efs) = jax.lax.scan(serial, acc0, xs)
        acc, wsum = carry_out[:2]
        sqsum = carry_out[2] if with_metrics else jnp.zeros(())
        return acc, wsum, jnp.mean(ls), new_efs, sqsum

    compute_dtype = jnp.dtype(run.compute_dtype)

    def train_step(state: Dict, batch: Dict):
        batch = dict(batch)
        client_ids = batch.pop("client_ids", None)
        theta_master = state["params"]
        # mixed-precision round (§Perf iteration 7): the server keeps the
        # master θ/m in param_dtype; the per-round broadcast, local steps,
        # and Δ accumulation run in compute_dtype (bf16) — halves the param
        # all-gathers and activation traffic; Δ̄ is upcast before the f32
        # server update, which preserves the momentum-accumulation
        # precision the FedADC recursion needs.
        with jax.named_scope("fedadc.broadcast"):
            theta_t, server_ctx_state, ctx, mixed = _broadcast_inputs(
                strategy, theta_master, state["server"], fed, run)
            ref = A.reference_direction(server_ctx_state) \
                if fed.aggregator == "drag" else None
            CP, CSn = batch["tokens"].shape[:2]
            # per-round compression randomness, deterministic in (run
            # seed, round index) so replicate experiments draw independent
            # noise
            round_key = jax.random.fold_in(jax.random.PRNGKey(run.seed),
                                           state["round"])
            pod_keys = jax.random.split(round_key, CP)
            new_dref = None
            if transport.down is not None:
                # clients everywhere train on the broadcast reconstruction;
                # only the lossy delta codec keeps reference state, and it
                # rides state["refs"] ("refs" membership is a static Python
                # fact — the lossless config traces the ref-free graph)
                dkey = jax.random.fold_in(round_key, 0xD0) if lossy_down \
                    else None
                dref = state["refs"]["downlink"] if "refs" in state \
                    else None
                theta_t, ctx, new_dref = transport.broadcast(
                    theta_t, ctx, dkey, dref)
            if ef_enabled:
                if client_ids is None:
                    # default identification: slot i of the round is
                    # client i
                    client_ids = jnp.arange(
                        CP * CSn, dtype=jnp.int32).reshape(CP, CSn)
                efs = jax.tree.map(
                    lambda x: x.reshape((CP, CSn) + x.shape[1:]),
                    CS.sharded_gather(state["clients"]["ef"],
                                      client_ids.reshape(-1)))
            else:
                efs = None
        if CP == 1:
            squeezed = jax.tree.map(lambda x: x[0], batch)
            efs0 = None if efs is None else jax.tree.map(lambda x: x[0], efs)
            acc, wsum, loss, new_efs, sqsum = per_group(
                theta_t, ctx, ref, squeezed, pod_keys[0], efs0)
            with jax.named_scope("fedadc.aggregate"):
                group_means = jax.tree.map(
                    lambda a: (a / wsum.astype(a.dtype))[None], acc)
                gweights = wsum[None]
                sq_total, w_total = sqsum, wsum
                if efs is not None:
                    new_efs = jax.tree.map(lambda x: x[None], new_efs)
        else:
            if efs is None:
                accs, wsums, losses, new_efs, sqsums = jax.vmap(
                    lambda cbs, gk: per_group(theta_t, ctx, ref, cbs, gk)
                )(batch, pod_keys)
            else:
                accs, wsums, losses, new_efs, sqsums = jax.vmap(
                    lambda cbs, gk, e: per_group(theta_t, ctx, ref, cbs,
                                                 gk, e)
                )(batch, pod_keys, efs)
            with jax.named_scope("fedadc.aggregate"):
                group_means = jax.tree.map(
                    lambda a: a / wsums.reshape(
                        (-1,) + (1,) * (a.ndim - 1)).astype(a.dtype), accs)
                gweights = wsums
                sq_total, w_total = jnp.sum(sqsums), jnp.sum(wsums)
                loss = jnp.mean(losses)
        # per-pod weighted means recombine exactly through the shared hook:
        # Δ̄ = Σ_p W_p·Δ̄_p / Σ_p W_p = Σ_i w_i·Δ_i / Σ_i w_i by linearity.
        # The per-group sums arrive as fp32 accumulators; the mixed round
        # keeps Δ̄ in f32 for the server update, a pure-low-precision run
        # casts back to the param dtype on write.  Under the two-tier fleet
        # topology the CP pod partials chunk into fleet_regions regional
        # partials before the global combine (identity at R=1 — DESIGN.md
        # §Fleet); each pod is already a stage-1 unit, so nothing changes
        # inside the client-serial scan.
        with jax.named_scope("fedadc.aggregate"):
            if fed.fleet_regions > 0:
                mean_delta = FH.hierarchical_combine(group_means, gweights,
                                                     fed, strategy)
            else:
                mean_delta = strategy.server_aggregate(group_means, gweights,
                                                       fed)
            mean_delta = T.cast(mean_delta,
                                jnp.float32 if mixed else jnp.dtype(
                                    run.param_dtype))
        with jax.named_scope("fedadc.server_update"):
            new_params, new_server = strategy.server_update(
                state["server"], theta_master, mean_delta, fed)
            new_state = {"params": new_params, "server": new_server,
                         "round": state["round"] + 1}
            if "refs" in state:
                new_state["refs"] = {"downlink": new_dref}
            if ef_enabled:
                flat_new = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), new_efs)
                new_state["clients"] = {"ef": CS.sharded_scatter(
                    state["clients"]["ef"], client_ids.reshape(-1),
                    flat_new)}
            aux = {"loss": loss}
            if with_metrics:
                metrics = {
                    "delta_dispersion": drift_metrics.streaming_dispersion(
                        sq_total, w_total, mean_delta),
                    "update_norm": drift_metrics.update_norm(mean_delta),
                }
                if "m" in state["server"]:
                    metrics["momentum_alignment"] = \
                        drift_metrics.momentum_alignment(
                            state["server"]["m"], mean_delta)
                if ef_enabled:
                    metrics["ef_residual_norm"] = \
                        drift_metrics.ef_residual_norm(jax.tree.map(
                            lambda x: x.reshape((-1,) + x.shape[2:]),
                            new_efs))
                aux["telemetry"] = metrics
        return new_state, aux

    # measured-byte accounting (bugfix): the pod engine drives real wire
    # traffic through `transport` but used to leave the byte counters at
    # zero — the only tree a consumer could size was the dense master-dtype
    # reconstruction the decode side materialises (fp32 under the mixed
    # round: ~2× the actual bf16 sparse wire).  Templates come from
    # eval_shape (no allocation) on the WIRE trees: the uplink delta and
    # the broadcast both live in the wire dtype (_wire_dtype).
    state_t = state_shapes(mcfg, fed, run)
    theta_w_t, _, ctx_t = jax.eval_shape(
        lambda p, s: _broadcast_inputs(strategy, p, s, fed, run)[:3],
        state_t["params"], state_t["server"])
    transport.set_wire_templates(theta_w_t, (theta_w_t, ctx_t))

    # the pod engine's downlink reference layer: multicast accounting and
    # (when fed.downlink_unicast) per-client catch-up/resync bookkeeping —
    # host-side by design, mirroring the counters
    refs = ReferenceStore(fed, transport, telemetry=telemetry)

    def account_round(n_clients: Optional[int] = None, resync: bool = False,
                      client_ids=None):
        """Advance the measured-byte counters by one round's traffic.
        Host-side by design: callers jit train_step themselves, so the
        counters cannot advance inside it — call once per executed round.
        Multicast (default): `n_clients` dispatched clients, resync=True
        for the delta downlink's round-0 initial sync.  Unicast
        (fed.downlink_unicast): pass `client_ids` and each client is
        classified fresh/catch-up/resync against the last round it saw."""
        if client_ids is not None:
            ids = [int(c) for c in np.asarray(client_ids).reshape(-1)]
            refs.dispatch(ids, account_round.round_no)
            account_round.round_no += 1
            transport.account_uplink(len(ids))
            return
        transport.account_downlink(n_clients, resync=resync)
        transport.account_uplink(n_clients)

    account_round.round_no = 0
    train_step.transport = transport
    train_step.refs = refs
    train_step.account_round = account_round
    return train_step
