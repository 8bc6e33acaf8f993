"""Regression tests for the dry-run lowering machinery on the 1×1 host mesh
(the 512-device production lowering is exercised by launch/dryrun.py; these
pin the ShapeDtypeStruct/sharding plumbing so it cannot rot)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.configs.base import FedConfig, RunConfig, ShapeConfig
from repro.launch import inputs as I
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import make_prefill_step, make_serve_step
from repro.launch.train import make_train_step

SMALL_TRAIN = ShapeConfig("train_small", seq_len=64, global_batch=16,
                          kind="train")
SMALL_PREFILL = ShapeConfig("prefill_small", seq_len=128, global_batch=2,
                            kind="prefill")
SMALL_DECODE = ShapeConfig("decode_small", seq_len=128, global_batch=2,
                           kind="decode")

FED = FedConfig(strategy="fedadc", clients_per_round=2, local_steps=2,
                eta=0.05)
RUN = RunConfig(remat="none")


@pytest.mark.parametrize("arch", ["qwen3-4b", "zamba2-1.2b",
                                  "llama4-scout-17b-a16e", "whisper-small"])
def test_train_step_lowers_on_host_mesh(arch):
    mcfg = ARCHS[arch].reduced()
    mesh = make_host_mesh()
    with mesh:
        state_sds = I.state_inputs(mcfg, FED, RUN, mesh)
        batch_sds = I.train_inputs(mcfg, SMALL_TRAIN, FED, mesh, False)
        step = make_train_step(mcfg, FED, RUN)
        compiled = jax.jit(step).lower(state_sds, batch_sds).compile()
        assert compiled.cost_analysis() is not None


@pytest.mark.parametrize("arch", ["qwen3-4b", "internvl2-26b"])
def test_prefill_lowers_on_host_mesh(arch):
    mcfg = ARCHS[arch].reduced()
    mesh = make_host_mesh()
    with mesh:
        state_sds = I.state_inputs(mcfg, FED, RUN, mesh, mode="serve")
        batch_sds = I.prefill_inputs(mcfg, SMALL_PREFILL, mesh, False)
        step = make_prefill_step(mcfg)
        compiled = jax.jit(step).lower(state_sds["params"],
                                       batch_sds).compile()
        assert compiled is not None


@pytest.mark.parametrize("arch", ["qwen3-4b", "xlstm-350m",
                                  "deepseek-v3-671b"])
def test_serve_step_lowers_on_host_mesh(arch):
    mcfg = ARCHS[arch].reduced()
    mesh = make_host_mesh()
    with mesh:
        state_sds = I.state_inputs(mcfg, FED, RUN, mesh, mode="serve")
        cache_sds, tokens, cur_pos, active = I.decode_inputs(
            mcfg, SMALL_DECODE, mesh, False, cache_dtype=jnp.float32)
        step = make_serve_step(mcfg)
        compiled = jax.jit(step).lower(state_sds["params"], cache_sds,
                                       tokens, cur_pos, active).compile()
        assert compiled is not None


def test_kd_loss_ignores_padding_tokens():
    """FedADC+ KD regression: positions with label == -100 must contribute to
    neither the CE/KD terms nor the ρ token statistics — junk content at
    padded tail positions cannot change the round."""
    import numpy as np
    mcfg = ARCHS["qwen3-4b"].reduced()
    fed = FedConfig(strategy="fedadc", clients_per_round=1, local_steps=2,
                    eta=0.05, distill=True, distill_lambda=0.35)
    run = RunConfig(remat="none", param_dtype="float32",
                    compute_dtype="float32")
    mesh = make_host_mesh()
    with mesh:
        from repro.launch.train import init_state
        state = init_state(jax.random.PRNGKey(0), mcfg, fed, run)
        step = make_train_step(mcfg, fed, run)
        rng = np.random.RandomState(0)
        b, L, pad_from = 2, 32, 20
        toks = rng.randint(0, mcfg.vocab_size, size=(1, 1, 2, b, L))
        labels = toks.copy()
        labels[..., pad_from:] = -100
        batch_a = {"tokens": jnp.asarray(toks, jnp.int32),
                   "labels": jnp.asarray(labels, jnp.int32)}
        junk = toks.copy()
        junk[..., pad_from:] = rng.randint(0, mcfg.vocab_size,
                                           size=junk[..., pad_from:].shape)
        batch_b = {"tokens": jnp.asarray(junk, jnp.int32),
                   "labels": jnp.asarray(labels, jnp.int32)}
        sa, ma = step(state, batch_a)
        sb, mb = step(state, batch_b)
        assert jnp.allclose(ma["loss"], mb["loss"], rtol=1e-6)
        for x, y in zip(jax.tree.leaves(sa["params"]),
                        jax.tree.leaves(sb["params"])):
            assert jnp.allclose(x, y, rtol=1e-5, atol=1e-7), \
                "padding tokens leaked into the KD round"


def test_round_decomposition_exact():
    from repro.launch.inputs import round_decomposition
    mesh = make_host_mesh()
    fed = FedConfig(clients_per_round=4, local_steps=4)
    from repro.configs.base import SHAPES
    CP, CS, H, b = round_decomposition(SHAPES["train_4k"], fed, mesh, False)
    assert CP * CS == 4 and H == 4 and CP * CS * H * b == 256


def test_meshes_use_auto_axes():
    """Every mesh builder makes Auto axes: under explicit axes the
    embedding gather over a sharded table is a type error."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_mesh
    for mesh in (make_host_mesh(), make_mesh((1, 1), ("data", "model"))):
        assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_moe_constraint_only_under_a_declared_mesh():
    """Without a declared mesh the MoE dispatch constraint passes its input
    through; under one, a spec naming an axis the mesh lacks raises
    instead of silently un-sharding."""
    from repro.models.moe import _constrain
    x = jnp.ones((4, 8))
    assert _constrain(x, "no_such_axis", None) is x
    with jax.set_mesh(make_host_mesh()):
        y = jax.jit(lambda a: _constrain(a, "data", None))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        with pytest.raises(Exception, match="no_such_axis"):
            jax.jit(lambda a: _constrain(a, "no_such_axis", None))(x)
