"""Sharding rules (divisibility guards, TP/FSDP placement) and the HLO
collective parser behind the roofline analysis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ARCHS
from repro.launch import roofline as R
from repro.models.registry import get_model
from repro.sharding import specs as S

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _specs_for(arch):
    cfg = ARCHS[arch]
    model = get_model(cfg)
    shapes = jax.eval_shape(lambda r: model.init(r, cfg),
                            jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    return [(path, leaf, S.spec_for_param(path, leaf.shape, MESH))
            for path, leaf in flat]


class TestParamSpecs:
    @pytest.mark.parametrize("arch", sorted(ARCHS))
    def test_all_specs_divisible(self, arch):
        """Guarded specs: every sharded dim divides its mesh axis."""
        for path, leaf, spec in _specs_for(arch):
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                size = MESH.shape[ax]
                assert leaf.shape[dim] % size == 0, (path, leaf.shape, spec)

    @pytest.mark.parametrize("arch", ["mistral-large-123b", "qwen3-14b"])
    def test_dense_majority_params_sharded(self, arch):
        """≥95% of parameter bytes must be sharded over BOTH axes (FSDP×TP)
        for the big dense archs — replicated big tensors blow HBM."""
        tot, both = 0, 0
        for path, leaf, spec in _specs_for(arch):
            n = int(np.prod(leaf.shape))
            tot += n
            axes = {a for a in spec if a is not None}
            if {"data", "model"} <= axes:
                both += n
        assert both / tot > 0.95, f"only {both/tot:.1%} fully sharded"

    def test_moe_experts_expert_parallel(self):
        for path, leaf, spec in _specs_for("deepseek-v3-671b"):
            keys = "/".join(str(getattr(p, "key", p)) for p in path)
            if "experts" in keys:
                assert spec[1] == "model", (keys, spec)  # E dim (after layer stack)

    def test_row_vs_column_parallel(self):
        cfg = ARCHS["qwen3-14b"]
        model = get_model(cfg)
        shapes = jax.eval_shape(lambda r: model.init(r, cfg),
                                jax.random.PRNGKey(0))
        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        by_name = {}
        for path, leaf in flat:
            keys = [str(getattr(p, "key", p)) for p in path]
            if len(keys) >= 2 and keys[-1] == "w":
                by_name[keys[-2]] = S.spec_for_param(path, leaf.shape, MESH)
        # column-parallel: output dim on "model"; row-parallel: input dim
        assert by_name["wq"][-1] == "model"
        assert by_name["wo"][-2] == "model"
        assert by_name["gate"][-1] == "model"
        assert by_name["down"][-2] == "model"


class TestCacheSpecs:
    def test_kv_cache_divisibility(self):
        cfg = ARCHS["qwen3-4b"]
        model = get_model(cfg)
        cache = jax.eval_shape(lambda: model.init_cache(cfg, 128, 1024,
                                                        jnp.bfloat16))
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        for path, leaf in flat:
            spec = S.spec_for_cache(path, leaf.shape, MESH)
            for dim, ax in enumerate(spec):
                if ax is not None:
                    assert leaf.shape[dim] % MESH.shape[ax] == 0

    def test_batch1_long_context_never_shards_batch(self):
        cfg = ARCHS["zamba2-1.2b"]
        model = get_model(cfg)
        cache = jax.eval_shape(lambda: model.init_cache(cfg, 1, 4096,
                                                        jnp.bfloat16))
        flat = jax.tree_util.tree_flatten_with_path(cache)[0]
        for path, leaf in flat:
            spec = S.spec_for_cache(path, leaf.shape, MESH)
            for dim, ax in enumerate(spec):
                if ax is not None:
                    assert leaf.shape[dim] >= MESH.shape[ax]


class TestCollectiveParser:
    HLO = """
  ENTRY %main {
    %ag = bf16[32,4096]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
    %ar = f32[1024]{0} all-reduce(%p1), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
    %rs = f32[128]{0} reduce-scatter(%p2), replica_groups={{0,1}}, dimensions={0}
    %cp = bf16[64,64]{1,0} collective-permute(%p3), source_target_pairs={{0,1},{1,0}}
    %a2a = (f32[16]{0}, f32[16]{0}) all-to-all(%p4, %p5), replica_groups={{0,1}}
    %mm = f32[256,256]{1,0} dot(%a, %b)
  }
  """

    def test_counts(self):
        st = R.parse_collectives(self.HLO)
        assert st.counts["all-gather"] == 1
        assert st.counts["all-reduce"] == 1
        assert st.counts["reduce-scatter"] == 1
        assert st.counts["collective-permute"] == 1
        assert st.counts["all-to-all"] == 1

    def test_bytes(self):
        st = R.parse_collectives(self.HLO)
        assert st.result_bytes["all-gather"] == 32 * 4096 * 2
        assert st.result_bytes["all-reduce"] == 1024 * 4
        assert st.result_bytes["all-to-all"] == 2 * 16 * 4  # tuple result

    def test_wire_factors(self):
        st = R.parse_collectives(self.HLO)
        # ar: 2*(8-1)/8 × 4096B; ag: (4-1)/4 × 262144B; rs: 1/2×512B;
        # cp: 1×8192B; a2a: 1/2×128B
        expect = (2 * 7 / 8) * 4096 + (3 / 4) * 262144 + 0.5 * 512 \
            + 8192 + 0.5 * 128
        np.testing.assert_allclose(st.wire_bytes, expect)

    def test_ignores_non_collectives(self):
        st = R.parse_collectives("%x = f32[8]{0} add(%a, %b)")
        assert st.wire_bytes == 0

    def test_as_dict_reports_counts_not_seconds(self):
        st = R.parse_collectives(self.HLO)
        rl = R.Roofline(flops=197e12, bytes_accessed=819e9 * 3,
                        wire_bytes=st.wire_bytes, chips=256, collectives=st,
                        per_device_hbm=2 * 2**30)
        d = rl.as_dict()
        assert d == {"flops": 197e12, "bytes": 819e9 * 3,
                     "wire_bytes": st.wire_bytes, "per_device_hbm_gb": 2.0,
                     "collective_counts": st.counts}
        assert not [k for k in d if k.endswith("_s")]


class TestModelFlops:
    def test_dense_train(self):
        from repro.configs.base import SHAPES
        cfg = ARCHS["qwen3-14b"]
        f = R.model_flops_per_round(cfg, SHAPES["train_4k"])
        expect = 6 * cfg.param_count() * 256 * 4096
        np.testing.assert_allclose(f, expect)

    def test_moe_uses_active(self):
        from repro.configs.base import SHAPES
        cfg = ARCHS["deepseek-v3-671b"]
        f = R.model_flops_per_round(cfg, SHAPES["prefill_32k"])
        expect = 2 * cfg.active_param_count() * 32 * 32768
        np.testing.assert_allclose(f, expect)
