"""Bytes and operations a kernel has to move or do, from shapes alone. Kept
with the benchmark, so that a PR which claims a kernel gain cannot change
what the kernel is held against.
"""

from __future__ import annotations

BF16 = 2


def head_dim(config: dict) -> int:
    return config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]


def kv_bytes_per_token_per_layer(config: dict, dtype_bytes: int = BF16) -> int:
    """K and V of one token in one layer."""
    return 2 * config["num_key_value_heads"] * head_dim(config) * dtype_bytes


def decode_attention_bytes(config: dict, context_tokens: float, batch: float,
                           dtype_bytes: int = BF16) -> float:
    """What ONE call of paged decode attention (one layer, one step) has to
    read and write at the least: the K and V of every context token of every
    sequence in the batch once, one query row and one output row per sequence
    and head. `context_tokens` is the sum over the batch."""
    kv = context_tokens * kv_bytes_per_token_per_layer(config, dtype_bytes)
    qo = 2 * batch * config["num_attention_heads"] * head_dim(config) * dtype_bytes
    return kv + qo


def weight_bytes(config: dict, dtype_bytes: int = BF16) -> int:
    """Parameters a decode step streams once: every layer's matrices and the
    output head (the tied embedding is read as the head; the embedding lookup
    itself reads one row per token)."""
    D, I, V = config["hidden_size"], config["intermediate_size"], config["vocab_size"]
    hd, Hq, Hkv = head_dim(config), config["num_attention_heads"], config["num_key_value_heads"]
    layer = D * hd * (Hq + 2 * Hkv) + hd * (Hq + 2 * Hkv) + Hq * hd * D + 3 * D * I + 2 * D
    return (config["num_hidden_layers"] * layer + V * D + D) * dtype_bytes
