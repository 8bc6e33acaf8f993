"""qwen3-14b [dense] — qk_norm + GQA, untied head [hf:Qwen/Qwen3-14B]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    arch_id="qwen3-14b",
    family="dense",
    source="hf:Qwen/Qwen3-14B",
    n_layers=40,
    d_model=5120,
    n_heads=40,
    n_kv_heads=8,
    d_ff=17408,
    vocab_size=151936,
    head_dim=128,
    qk_norm=True,
    rope_theta=1_000_000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
    max_seq_len=40_960,
)
