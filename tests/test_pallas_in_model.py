"""Integration: the use_pallas code paths inside the models produce the same
numerics as the default jnp paths (interpret mode on CPU), and the pod
engine runs the paper's variants end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import FedConfig, RunConfig
from repro.launch.train import init_state, make_train_step
from repro.models.registry import get_model


def _mk_batch(cfg, B=2, L=128):
    tokens = (jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                                 cfg.vocab_size)).astype(jnp.int32)
    return {"tokens": tokens, "labels": tokens}


def test_mamba2_pallas_path_matches_jnp():
    cfg = ARCHS["zamba2-1.2b"].reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    batch = _mk_batch(cfg)
    a, _ = model.forward(params, batch, cfg, use_pallas=False)
    b, _ = model.forward(params, batch, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-3,
                               rtol=5e-3)


def test_attention_pallas_path_matches_jnp():
    cfg = ARCHS["mistral-large-123b"].reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    batch = _mk_batch(cfg)
    a, _ = model.forward(params, batch, cfg, use_pallas=False)
    b, _ = model.forward(params, batch, cfg, use_pallas=True)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-3,
                               rtol=5e-3)


@pytest.mark.parametrize("strategy", ["fedadc_double", "fedprox", "slowmo"])
def test_pod_engine_strategy_variants(strategy):
    cfg = ARCHS["qwen3-4b"].reduced()
    fed = FedConfig(strategy=strategy, clients_per_round=2, local_steps=2,
                    eta=0.01)
    run = RunConfig(remat="none")
    state = init_state(jax.random.PRNGKey(0), cfg, fed, run)
    step = jax.jit(make_train_step(cfg, fed, run))
    batch1 = _mk_batch(cfg, 2, 32)
    batch = jax.tree.map(lambda x: jnp.broadcast_to(x, (1, 2, 2) + x.shape),
                         batch1)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_pod_engine_fedadc_plus_distill():
    """FedADC+ on the pod engine: self-confidence KD with token-frequency ρ."""
    cfg = ARCHS["qwen3-4b"].reduced()
    fed = FedConfig(strategy="fedadc", clients_per_round=2, local_steps=2,
                    eta=0.01, distill=True, distill_lambda=0.35)
    run = RunConfig(remat="none")
    state = init_state(jax.random.PRNGKey(0), cfg, fed, run)
    step = jax.jit(make_train_step(cfg, fed, run))
    batch1 = _mk_batch(cfg, 2, 32)
    batch = jax.tree.map(lambda x: jnp.broadcast_to(x, (1, 2, 2) + x.shape),
                         batch1)
    new_state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_pod_engine_rejects_stateful_strategies():
    cfg = ARCHS["qwen3-4b"].reduced()
    fed = FedConfig(strategy="scaffold")
    with pytest.raises(ValueError):
        make_train_step(cfg, fed, RunConfig())


def test_mixed_precision_round_preserves_master_dtype():
    cfg = ARCHS["qwen3-4b"].reduced()
    fed = FedConfig(strategy="fedadc", clients_per_round=2, local_steps=2,
                    eta=0.01)
    run = RunConfig(param_dtype="float32", compute_dtype="bfloat16")
    state = init_state(jax.random.PRNGKey(0), cfg, fed, run)
    step = jax.jit(make_train_step(cfg, fed, run))
    batch1 = _mk_batch(cfg, 2, 32)
    batch = jax.tree.map(lambda x: jnp.broadcast_to(x, (1, 2, 2) + x.shape),
                         batch1)
    new_state, _ = step(state, batch)
    for leaf in jax.tree.leaves(new_state["params"]):
        assert leaf.dtype == jnp.float32      # f32 master survives
    for leaf in jax.tree.leaves(new_state["server"]["m"]):
        assert leaf.dtype == jnp.float32


@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention_gradients_match_oracle(window):
    """The kernel's custom VJP is the float32 oracle's: gradients through
    ops.flash_attention equal those through ref.flash_attention."""
    from repro.kernels import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, 256, 4, 64))
    k = jax.random.normal(ks[1], (2, 256, 2, 64))
    v = jax.random.normal(ks[2], (2, 256, 2, 64))
    g = jax.random.normal(ks[3], q.shape)

    def kernel(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True,
                                           window=window) * g)

    def oracle(q, k, v):
        out = ref.flash_attention(*(jnp.moveaxis(t, 1, 2) for t in (q, k, v)),
                                  causal=True, window=window)
        return jnp.sum(jnp.moveaxis(out, 1, 2) * g)
    got = jax.grad(kernel, (0, 1, 2))(q, k, v)
    exp = jax.grad(oracle, (0, 1, 2))(q, k, v)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_model_gradients_with_kernels_match_jnp():
    """Gradients of the LM loss through the flash-attention kernel match
    the jnp attention path."""
    cfg = ARCHS["qwen3-4b"].reduced()
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    batch = _mk_batch(cfg)

    def grads(use_pallas):
        return jax.grad(lambda p: model.loss_fn(p, batch, cfg,
                                                use_pallas)[0])(params)
    a, b = grads(False), grads(True)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-4,
                                   atol=1e-6)


def test_pod_round_with_kernels_matches_jnp_round():
    """A use_pallas=True pod round traces (the flash-attention kernel is
    differentiated through its custom VJP) and moves θ as the jnp round
    does, in float32."""
    cfg = ARCHS["qwen3-4b"].reduced()
    run = RunConfig(remat="none", param_dtype="float32",
                    compute_dtype="float32")
    batch = jax.tree.map(lambda x: jnp.broadcast_to(x, (1, 2, 2) + x.shape),
                         _mk_batch(cfg, 2, 128))
    deltas, losses = [], []
    for use_pallas in (False, True):
        fed = FedConfig(strategy="fedadc", clients_per_round=2,
                        local_steps=2, eta=0.05, use_pallas=use_pallas)
        state = init_state(jax.random.PRNGKey(0), cfg, fed, run)
        new_state, metrics = jax.jit(make_train_step(cfg, fed, run))(
            state, batch)
        losses.append(float(metrics["loss"]))
        deltas.append(jax.tree.map(lambda a, b: np.asarray(a - b),
                                   new_state["params"], state["params"]))
    np.testing.assert_allclose(losses[1], losses[0], rtol=1e-5)
    num = sum(np.sum((a - b) ** 2) for a, b in
              zip(jax.tree.leaves(deltas[1]), jax.tree.leaves(deltas[0])))
    den = sum(np.sum(a ** 2) for a in jax.tree.leaves(deltas[0]))
    assert np.sqrt(num / den) < 1e-4
