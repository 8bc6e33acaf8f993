"""The benchmark's plain float32 reference round against the program's
round with the kernels off and float32 compute, at a tiny size."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_util import TINY_CONFIG

from reference import fedadc, qwen3
from repro.configs.base import FedConfig, ModelConfig, RunConfig
from repro.launch.train import make_train_step, state_shapes


@pytest.mark.parametrize("tied", [True, False])
def test_reference_round_matches_program(tied):
    cfg = dict(TINY_CONFIG, tie_word_embeddings=tied)
    sizes = qwen3.sizes(cfg)
    mcfg = ModelConfig(**dict(cfg["program"]["ModelConfig"],
                              tie_embeddings=tied))
    C, H, b, L = 3, 2, 2, 16
    fed = FedConfig(strategy="fedadc", variant="nesterov", local_steps=H,
                    clients_per_round=C, eta=0.5, beta_global=0.8,
                    beta_local=0.8)
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    remat="full")
    params = qwen3.init_params(jax.random.PRNGKey(3), sizes)
    expected = state_shapes(mcfg, fed, run)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(expected)
    assert [x.shape for x in jax.tree.leaves(params)] == \
        [x.shape for x in jax.tree.leaves(expected)]
    state = {"params": params,
             "server": {"m": jax.tree.map(jnp.zeros_like, params)},
             "round": jnp.zeros((), jnp.int32)}
    step = jax.jit(make_train_step(mcfg, fed, run))
    toks = np.random.RandomState(0).randint(
        0, sizes["vocab_size"], (3, 1, C, H, b, L)).astype(np.int32)
    fd = {"eta": 0.5, "alpha": 1.0, "beta_global": 0.8, "beta_local": 0.8}
    p, m = params, state["server"]["m"]
    for r in range(3):
        state, aux = step(state, {"tokens": toks[r], "labels": toks[r]})
        p, m, loss = fedadc.fedadc_round(qwen3, p, m, toks[r][0], sizes, fd,
                                         block_rows=1)
        np.testing.assert_allclose(float(aux["loss"]), float(loss),
                                   rtol=1e-5)
        for a, c in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(p)):
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=2e-6)
        for a, c in zip(jax.tree.leaves(state["server"]["m"]),
                        jax.tree.leaves(m)):
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=2e-5)


def test_blocks_of_rows_give_the_whole_batch_gradient():
    sizes = qwen3.sizes(TINY_CONFIG)
    params = qwen3.init_params(jax.random.PRNGKey(1), sizes)
    toks = jnp.asarray(np.random.RandomState(1).randint(
        0, sizes["vocab_size"], (4, 16)), jnp.int32)
    whole = jax.value_and_grad(qwen3.loss)(params, toks, sizes)
    blocks = fedadc._loss_and_grad(qwen3, params, toks, sizes, 1)
    np.testing.assert_allclose(whole[0], blocks[0], rtol=1e-6)
    for a, c in zip(jax.tree.leaves(whole[1]), jax.tree.leaves(blocks[1])):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-7)
