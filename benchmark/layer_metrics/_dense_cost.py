"""Operations a packed prefill of a dense decoder has to do, from the
configuration's keys as `costs.py` takes them and from what the dispatch span
says the pack holds. Kept with the benchmark and beside its reader, not in
`costs.py`, which is a file that was here before the span said it.

USEFUL operations only: the real rows, never the padding; the head is left
out (it runs on the rows that end a prompt alone). So a share of the peak
computed from this can read low and never high.
"""

from __future__ import annotations

import costs


def layer_matrix_params(config: dict) -> int:
    """The parameters of ONE layer's matrices: q, k, v and output projections,
    gate, up and down (the biases and norms add no product)."""
    D, I = config["hidden_size"], config["intermediate_size"]
    hd, Hq, Hkv = costs.head_dim(config), config["num_attention_heads"], config["num_key_value_heads"]
    return D * hd * (Hq + 2 * Hkv) + Hq * hd * D + 3 * D * I


def attended_pairs_at_least(rows: int, ctx: int, lanes: int) -> float:
    """A lower bound on the (query row, key) pairs a pack attends over, from
    the pack's SUMS alone. Sequence j brings r_j rows on top of s_j tokens of
    context: row i of it attends to s_j + i + 1 keys, so the pack's pairs are
    sum_j (r_j * s_j + r_j * (r_j + 1) / 2). The span gives rows = sum r_j,
    ctx = sum s_j and lanes >= the number of sequences, not the products.
    Every r_j is at least 1, so sum r_j * s_j >= ctx; and by convexity
    sum r_j * (r_j + 1) / 2 >= rows * (rows / lanes + 1) / 2. ("Every row
    attends to its pack's mean context", rows * ctx / lanes, is an estimate
    and no bound: the long context may belong to the short chunk.)"""
    return ctx + rows * (rows / max(1, lanes) + 1) / 2


def prefill_useful_flops(config: dict, rows: int, ctx: int, lanes: int) -> float:
    """2 x rows x the parameters of every layer's matrices, plus the two
    attention products (Q K^T and P V: 2 x 2 x heads x head_dim a pair) over
    at least the pairs the rows attend to."""
    layers = config["num_hidden_layers"]
    matrices = 2.0 * rows * layers * layer_matrix_params(config)
    attention = 4.0 * config["num_attention_heads"] * costs.head_dim(config) \
        * attended_pairs_at_least(rows, ctx, lanes) * layers
    return matrices + attention
