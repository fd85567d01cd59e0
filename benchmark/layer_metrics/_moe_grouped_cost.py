"""Bytes the grouped expert products of a SwiGLU expert layer have to move,
from shapes alone (`ops/pallas/grouped_matmul.py`, kernel
`moe_grouped_matmul`). Kept with the benchmark and beside its reader, not in
`costs.py`, which is a file that was here before the reader.
"""

from __future__ import annotations

BF16 = 2


def expert_bytes(config: dict, dtype_bytes: int = BF16) -> int:
    """The three matrices of ONE SwiGLU expert (`w1`, `w3`: hidden x
    moe_intermediate; `w2` back)."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] * dtype_bytes


def grouped_bytes(config: dict, experts_touched: float, rows: float,
                  dtype_bytes: int = BF16) -> float:
    """What the three products of a decode step's expert layers have to read
    and write at the least: the matrices of every (layer, expert) pair that
    received a row, once; each assigned row in at hidden width (twice: `w1`
    and `w3`), the two intermediates out, their product in, the result out.
    An expert that no row chose costs nothing: the kernel skips it."""
    D, F = config["hidden_size"], config["moe_intermediate_size"]
    return experts_touched * expert_bytes(config, dtype_bytes) + rows * (3 * D + 3 * F) * dtype_bytes
