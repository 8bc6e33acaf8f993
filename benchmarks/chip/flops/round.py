"""Model operations of a decoder-only transformer's training step.

Per token: 6 x the parameters that take part in a matrix product
(every projection of every layer and the output head; not the embedding
gather, not the norms), plus causal attention: its two products,
2·2·heads·head_dim operations for each (query, key) pair forward and
twice that backward, over the (L + 1) / 2 keys a query sees on average.
Recomputation (remat) is not counted: these are the operations the
forward and backward passes require.
"""
from __future__ import annotations


def matmul_params(cfg) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def flops_per_token(cfg, seq_len: int) -> float:
    attn = (12 * cfg["num_attention_heads"] * cfg["head_dim"]
            * (seq_len + 1) / 2)
    return 6.0 * matmul_params(cfg) + cfg["num_hidden_layers"] * attn
