"""The grouped matrix product of the dropless expert dispatch as a TPU kernel
whose work follows the REAL rows.

``rows [M, K]`` are sorted by group; ``group_sizes [G]`` says how many rows
each of the bank's ``G`` matrices ``[K, N]`` meets, in order from row 0; rows
past ``sum(group_sizes)`` belong to nobody (assignments to experts held on
another chip sort there: three quarters of ``M`` in an expert-parallel share).

The kernel walks a list of VISITS, one per (group, row tile) pair in which the
group has a row, in group order. The list is made outside from ``group_sizes``
and scalar-prefetched; its length is the grid's extent (a traced number), so

- a group with no row is never visited: its matrix is not read;
- consecutive visits of one group name the same bank block, which the
  pipeline then keeps: a matrix streams from HBM once a call (once per column
  block where ``N`` is split), in blocks of up to `BANK_BLOCK_BYTES` (a whole
  [1024, 2688] bf16 matrix is one 5.25 MiB block and one DMA);
- consecutive visits of one row tile keep its output block in VMEM; each visit
  stores only the rows of its own group (a select against what the block
  holds), and the block is written back when the walk leaves the tile;
- row tiles past the last real row are never visited: no read, no MXU pass, no
  write. What the output holds there, and in the rows of a visited tile that
  lie past the last group, is UNDEFINED (stale memory, possibly NaN): the
  dispatch selects them away (`ops/moe.moe_dispatch`).

The contraction is never split: one ``dot`` over all of ``K`` with float32
accumulation, rounded once to the rows' dtype, as ``jax.lax.ragged_dot``.

The row tile comes from the static shapes (`row_tile`): ``M / G`` bounds the
rows a group is expected to hold. A visit of a tile under the MXU's 128 rows
costs the pass of a whole one (the bank block is what the MXU loads), so the
tile is never below that; where groups would hold more it is 256, so that a
visit carries more rows against its fixed cost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the most one block of the bank may take (it is double-buffered): a whole
#: expert matrix of the published Nemotron-3 widths (1024 x 2688 bf16) fits
BANK_BLOCK_BYTES = 6 << 20


def row_tile(M: int, G: int) -> int:
    """Rows a tile, from the static shapes alone: the MXU's 128, or 256 where
    a group would hold more than 128 rows were every row real and the routing
    even (``M / G``). On the v5e (PERF.md section 5, PR 30) tiles of 16 to 128
    rows cost the same at 4 rows a group, 128 and 256 the same at 44, and 512
    two thirds more there: a tile that several groups share pays a whole
    tile's pass for each."""
    return 256 if M // max(1, G) > 128 else 128


def column_block(K: int, N: int, itemsize: int) -> int | None:
    """Columns of the bank a block: all ``N`` where a [K, N] matrix fits
    `BANK_BLOCK_BYTES`, else the largest multiple of 128 that divides ``N`` and
    fits; None where no such block exists (the caller keeps ``ragged_dot``)."""
    if K * N * itemsize <= BANK_BLOCK_BYTES:
        return N
    if N % 128:
        return None
    fits = [n for n in range(128, N, 128)
            if N % n == 0 and K * n * itemsize <= BANK_BLOCK_BYTES]
    return max(fits, default=None)


def visit_lists(group_sizes: jnp.ndarray, tm: int, tiles_m: int):
    """(group of each visit [V], row tile of each visit [V], row offsets of the
    groups [G + 1], number of visits): V = tiles_m + G - 1 is the most there
    can be (every tile once, and once more for each group that starts inside
    one); entries past the number of visits repeat the last one."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    num = visit_ends[-1]
    v = jnp.minimum(jnp.arange(tiles_m + G - 1, dtype=jnp.int32), jnp.maximum(num - 1, 0))
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"), G - 1).astype(jnp.int32)
    tile = first[group] + v - (visit_ends[group] - tiles[group])
    tile = jnp.clip(tile, 0, tiles_m - 1).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return group, tile, offsets, num


def _kernel(group_ref, tile_ref, offsets_ref, x_ref, w_ref, o_ref, *, tm: int):
    v = pl.program_id(1)
    g = group_ref[v]
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def walk(body, name: str, rows, bank, group_sizes, tile_m=None, block_n=None, interpret=False):
    """``body(group_ref, tile_ref, offsets_ref, x_ref, w_ref, o_ref, tm=)`` run
    once per visit (and per column block) over the pipeline described at the
    top: the kernel below, and the null stream of `tools/profile_moe.py`."""
    M, K = rows.shape
    G, _, N = bank.shape
    tm = tile_m or row_tile(M, G)
    tn = block_n or column_block(K, N, bank.dtype.itemsize)
    if tn is None or N % tn:
        raise ValueError(f"no column block for a bank of [{K}, {N}] {bank.dtype}")
    tiles_m = pl.cdiv(M, tm)
    if tiles_m * tm != M:
        rows = jnp.pad(rows, ((0, tiles_m * tm - M), (0, 0)))
    group, tile, offsets, num = visit_lists(group_sizes, tm, tiles_m)

    item = rows.dtype.itemsize
    vmem = 2 * (K * tn * bank.dtype.itemsize + tm * K * item + tm * tn * item) + 2 * tm * tn * 4
    out = pl.pallas_call(
        functools.partial(body, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # column blocks outermost: a row tile's visits stay consecutive
            grid=(N // tn, jnp.maximum(num, 1)),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, group, tile, offsets: (tile[v], 0)),
                pl.BlockSpec((None, K, tn), lambda n, v, group, tile, offsets: (group[v], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n, v, group, tile, offsets: (tile[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((tiles_m * tm, N), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20)),
        ),
        name=name,
        interpret=interpret,
    )(group, tile, offsets, rows, bank)
    return out[:M]


@functools.partial(jax.jit, static_argnames=("tile_m", "block_n", "interpret"))
def grouped_matmul_pallas(
    rows: jnp.ndarray,  # [M, K], sorted by group
    bank: jnp.ndarray,  # [G, K, N]
    group_sizes: jnp.ndarray,  # [G] int
    *,
    tile_m: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``[M, N]`` in the rows' dtype: row r times the matrix of its group.
    Rows past the last group are undefined."""
    return walk(_kernel, "moe_grouped_matmul", rows, bank, group_sizes, tile_m, block_n, interpret)
