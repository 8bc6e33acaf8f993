"""The registered ``qwen3-14b`` is the published Qwen3-14B, and the
benchmark's configuration of it differs only by the keys it lists as
reduced."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import pytest

from repro.configs import ARCHS
from repro.configs.base import ModelConfig

CONFIG_FILE = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
               / "configs" / "qwen3-14b.json")
# the benchmark's reduced Hugging Face keys, by ModelConfig field
REDUCED = {"n_layers": "num_hidden_layers", "vocab_size": "vocab_size"}
# ModelConfig field -> the published config.json key it is read from
PUBLISHED = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
             "n_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "d_ff": "intermediate_size", "rope_theta": "rope_theta",
             "norm_eps": "rms_norm_eps",
             "tie_embeddings": "tie_word_embeddings",
             "max_seq_len": "max_position_embeddings"}


@pytest.fixture(scope="module")
def bench_config():
    return json.loads(CONFIG_FILE.read_text())


def test_registered_config_equals_the_benchmarks_but_for_reduced_keys(
        bench_config):
    registered = ARCHS["qwen3-14b"]
    program = ModelConfig(**bench_config["program"]["ModelConfig"])
    assert sorted(bench_config["reduced"]) == sorted(REDUCED.values())
    for f in dataclasses.fields(ModelConfig):
        if f.name in REDUCED or f.name == "source":
            continue
        assert getattr(registered, f.name) == getattr(program, f.name), \
            f.name


@pytest.mark.parametrize("field, key", sorted(REDUCED.items()))
def test_reduced_keys_are_published_in_the_registry(bench_config, field,
                                                    key):
    reduced = bench_config["reduced"][key]
    assert getattr(ARCHS["qwen3-14b"], field) == reduced["published"]
    assert bench_config["program"]["ModelConfig"][field] == reduced["here"]


@pytest.mark.parametrize("field, key", sorted(PUBLISHED.items()))
def test_registered_config_reads_the_published_keys(bench_config, field,
                                                    key):
    assert getattr(ARCHS["qwen3-14b"], field) == bench_config[key]


def test_registered_config_cites_qwen3_14b(bench_config):
    assert ARCHS["qwen3-14b"].source == "hf:Qwen/Qwen3-14B"
    assert "/Qwen/Qwen3-14B/" in bench_config["source"]
