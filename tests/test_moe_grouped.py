"""The grouped matrix product of the expert dispatch (`moe_grouped_matmul`,
ops/pallas/grouped_matmul.py) in interpret mode at tiny widths: against a
per-row dense product in float32, for every way the groups can lie over the
row tiles; through `grouped_matmul` and `moe_dispatch` with a held share; and
what the walk visits. The chip's compiler sees the served shapes in
tests/test_tpu_compile.py, and the chip itself in chip_smoke.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops import moe
from dynamo_tpu.ops.pallas.grouped_matmul import (
    column_block, grouped_matmul_pallas, row_tile, visit_lists,
)
from dynamo_tpu.quant import QuantizedLinear

TM = 16  # the row tile of these cases


def dense_rows(rows, bank, sizes):
    """Row by row in float32: (the product of the rows inside groups, how many)."""
    rows, bank = np.asarray(rows, np.float32), np.asarray(bank, np.float32)
    group_of = np.repeat(np.arange(len(sizes)), sizes)
    return np.einsum("rk,rkn->rn", rows[: len(group_of)], bank[group_of]), len(group_of)


def draw(seed, M, K, N, G, dtype=jnp.float32):
    kr, kb = jax.random.split(jax.random.key(seed))
    rows = jax.random.normal(kr, (M, K), jnp.float32).astype(dtype)
    bank = (jax.random.normal(kb, (G, K, N), jnp.float32) / np.sqrt(K)).astype(dtype)
    return rows, bank


def nan_tail(rows, real):
    """Rows past the last group hold NaN on the way in: none may reach a real row."""
    return rows.at[real:].set(jnp.nan)


#: name -> (static rows M, in K, out N, group sizes, kernel arguments)
KERNEL_CASES = {
    "ragged": (64, 32, 48, [3, 7, 1, 12, 5, 9], {}),
    "empty_groups": (64, 32, 48, [0, 5, 0, 0, 20, 0, 3, 0], {}),
    "no_row_at_all": (32, 32, 48, [0, 0, 0], {}),
    "one_group_holds_every_row": (64, 32, 48, [0, 64, 0], {}),
    "ends_inside_a_tile": (64, 32, 48, [5, 20, 14], {}),
    "ends_on_a_tile_boundary": (64, 32, 48, [16, 16, 32], {}),
    "a_group_over_three_tiles": (64, 32, 48, [7, 40, 2], {}),
    "tail_past_the_last_group": (96, 32, 48, [4, 9, 6], {}),
    "rows_not_a_multiple_of_the_tile": (50, 32, 48, [11, 0, 23, 9], {}),
    # the cell's two banks, 1024 -> 2688 and back, scaled down 8 : 21
    "bank_w1_8_to_21": (88, 32, 84, [4, 6, 0, 5, 3, 7], {}),
    "bank_w2_21_to_8": (88, 84, 32, [4, 6, 0, 5, 3, 7], {}),
    "columns_in_blocks": (64, 32, 384, [3, 0, 30, 9], {"block_n": 128}),
    "bfloat16": (64, 128, 256, [5, 0, 17, 20], {"dtype": jnp.bfloat16}),
    "default_tile_from_shapes": (300, 32, 48, [100, 0, 150, 7], {"tile_m": None}),
}


@pytest.mark.parametrize("name", KERNEL_CASES)
def test_kernel_matches_dense_rows(name):
    M, K, N, sizes, kw = KERNEL_CASES[name]
    kw = dict({"tile_m": TM}, **kw)
    dtype = kw.pop("dtype", jnp.float32)
    rows, bank = draw(len(name), M, K, N, len(sizes), dtype)
    want, real = dense_rows(rows, bank, sizes)
    got = grouped_matmul_pallas(
        nan_tail(rows, real), bank, jnp.asarray(sizes, jnp.int32), interpret=True, **kw
    )
    assert got.shape == (M, N) and got.dtype == dtype
    got = np.asarray(got[:real], np.float32)
    assert np.isfinite(got).all(), "a row past the last group reached a real row"
    atol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, atol=atol, rtol=atol)


#: name -> (group sizes, row tiles M / TM, visits as (group, tile))
WALKS = {
    "a group with no row is never visited": (
        [0, 5, 0, 20, 0], 4, [(1, 0), (3, 0), (3, 1)]),
    "the walk stops at the last real row": (
        [3, 2], 8, [(0, 0), (1, 0)]),
    "a boundary on a tile's edge costs no second visit": (
        [16, 16, 1], 4, [(0, 0), (1, 1), (2, 2)]),
    "nothing held": ([0, 0, 0], 2, []),
}


@pytest.mark.parametrize("name", WALKS)
def test_walk_visits_real_rows_only(name):
    sizes, tiles_m, want = WALKS[name]
    group, tile, offsets, num = visit_lists(jnp.asarray(sizes, jnp.int32), TM, tiles_m)
    assert group.shape == tile.shape == (tiles_m + len(sizes) - 1,)
    assert list(zip(np.asarray(group)[: int(num)], np.asarray(tile)[: int(num)])) == want
    assert np.asarray(offsets).tolist() == [0, *np.cumsum(sizes)]
    # entries past the count repeat the last visit: nothing new is fetched
    assert (np.asarray(tile) < tiles_m).all() and (np.asarray(group) < len(sizes)).all()


def test_tiles_come_from_the_static_shapes():
    # the cell: 22 and 176 static rows a group; Mixtral's prefill: thousands
    assert row_tile(2816, 128) == 128 and row_tile(22528, 128) == 256
    assert row_tile(16384, 8) == 256 and row_tile(10, 4) == 128
    # a whole matrix of the cell is one block, either way round
    assert column_block(1024, 2688, 2) == 2688 and column_block(2688, 1024, 2) == 1024
    # Mixtral-8x7B: [4096, 14336] and back, in column blocks that divide
    assert column_block(4096, 14336, 2) == 512 and column_block(14336, 4096, 2) == 128
    assert column_block(1 << 20, 4096, 2) is None  # no block fits: ragged_dot's


def _dispatch(hidden, weights, idx, w1, w2, num_held, offset):
    def ffn(rows, group_sizes):
        mid = moe.relu2(moe.grouped_matmul(rows, w1, group_sizes))
        return moe.grouped_matmul(mid, w2, group_sizes)

    return moe.moe_dispatch(hidden, weights, idx, ffn, num_held=num_held, offset=offset)


@pytest.mark.parametrize("offset", [0, 4], ids=["first_share", "second_share"])
def test_held_share_through_the_dispatch(monkeypatch, offset):
    """4 of 8 experts held from `offset`: the kernel's undefined tail is
    selected away, and the result is the reference path's."""
    T, K, D, F, held, routed = 24, 3, 32, 84, 4, 8
    keys = jax.random.split(jax.random.key(7), 4)
    hidden = jax.random.normal(keys[0], (T, D), jnp.float32)
    w1 = jax.random.normal(keys[1], (held, D, F), jnp.float32) / np.sqrt(D)
    w2 = jax.random.normal(keys[2], (held, F, D), jnp.float32) / np.sqrt(F)
    logits = jax.random.normal(keys[3], (T, routed), jnp.float32)
    weights, idx = moe.topk_routing(logits, K)
    monkeypatch.setenv("DYNTPU_PALLAS", "0")
    want, want_counts = _dispatch(hidden, weights, idx, w1, w2, held, offset)
    monkeypatch.setenv("DYNTPU_PALLAS", "1")  # interpret mode, asked for by name
    got, counts = _dispatch(hidden, weights, idx, w1, w2, held, offset)
    assert 0 < int(counts.sum()) < T * K, "the case must hold some assignments and not all"
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(want_counts))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


#: which product `grouped_matmul` takes: (bank kind, mesh given) -> the kernel?
PATHS = {
    "plain bank on one device": ("plain", False, True),
    "int8 bank": ("int8", False, False),
    "under a mesh of several devices": ("plain", True, False),
    "too wide for a block": ("wide", False, False),
}


@pytest.mark.parametrize("name", PATHS)
def test_which_product_is_taken(monkeypatch, name):
    kind, with_mesh, want_kernel = PATHS[name]
    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    sizes = [3, 0, 9, 4]
    rows, bank = draw(3, 32, 32, 48, len(sizes))
    want, real = dense_rows(rows, bank, sizes)
    if kind == "int8":
        scale = jnp.max(jnp.abs(bank), axis=1) / 127.0  # [G, out]
        q = jnp.round(bank / scale[:, None, :]).astype(jnp.int8)
        want, _ = dense_rows(rows, q.astype(jnp.float32) * scale[:, None, :], sizes)
        bank = QuantizedLinear(q, scale)
    if kind == "wide":
        monkeypatch.setattr("dynamo_tpu.ops.pallas.grouped_matmul.BANK_BLOCK_BYTES", 1024)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("ep",)) if with_mesh else None
    jaxpr = str(jax.make_jaxpr(lambda r, b, s: moe.grouped_matmul(r, b, s, mesh))(
        rows, bank, jnp.asarray(sizes, jnp.int32)
    ))
    assert ("pallas_call" in jaxpr) == want_kernel and ("ragged_dot" in jaxpr) != want_kernel
    got = moe.grouped_matmul(rows, bank, jnp.asarray(sizes, jnp.int32), mesh)
    np.testing.assert_allclose(np.asarray(got[:real], np.float32), want, atol=1e-4, rtol=1e-4)
