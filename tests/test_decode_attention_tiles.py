"""The tiled lookahead decode kernel (interpret mode) against the gather
reference, at the boundaries its loop structure has: a tile of pages per
iteration, a cross-program window of W tiles, a double-buffered tail; over
pools of head_dim 128 and, through the same walk, over folded pools."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_decode_attention
from dynamo_tpu.ops.pallas.paged_attention import (
    decode_tile_pages,
    lookahead_window,
    paged_decode_attention_pallas_folded,
    paged_decode_attention_pallas_lookahead,
)
from dynamo_tpu.quant.kv import QuantizedPages, quantize_kv_rows

PS, D = 16, 128
TP = decode_tile_pages(PS, 2, D, 4)  # pages per tile
W = lookahead_window(PS, 2, D, 4)  # tiles per window
TILE = TP * PS  # tokens per tile
W128 = lookahead_window(128, 2, D, 2)  # where a page is a tile, the window it had before

#: name -> (Hq, Hkv, page size, pool dtype, int8, lengths of the first rows;
#: 0 = a padded row, as every row after them is)
CASES = {
    "length_1": (4, 2, PS, "float32", False, [1]),
    "one_tile": (4, 2, PS, "float32", False, [TILE]),
    "one_tile_plus_1": (4, 2, PS, "float32", False, [TILE + 1]),
    "window": (4, 2, PS, "float32", False, [W * TILE]),
    "window_plus_1": (4, 2, PS, "float32", False, [W * TILE + 1]),
    "tail_mid_page": (4, 2, PS, "float32", False, [(W + 3) * TILE + PS + 1]),
    "empty_between_live": (4, 2, PS, "float32", False, [TILE + 3, 0, 1, 0, 3 * TILE]),
    "odd_batch": (4, 2, PS, "float32", False, [5, W * TILE + 9, 1, 2 * TILE - 1, (W + 1) * TILE]),
    "gqa_8_to_1": (8, 1, PS, "float32", False, [TILE - 1, (W + 1) * TILE + 2]),
    "mha": (4, 4, PS, "float32", False, [PS + 1, (W + 1) * TILE + 2]),
    "bf16_word_split": (8, 2, PS, "bfloat16", False, [1, TILE + 1, (W + 1) * TILE + 5]),
    "bf16_four_kv_heads": (8, 4, PS, "bfloat16", False, [PS, (W + 2) * TILE - 1]),
    "bf16_odd_kv_heads": (6, 3, PS, "bfloat16", False, [TILE + 2, 3]),
    "page_is_tile": (4, 2, 128, "bfloat16", False, [1, 128, 129, (W128 + 2) * 128 + 7]),
    "int8": (8, 4, PS, "bfloat16", True, [1, TILE, (W + 1) * TILE + 6]),
    "int8_page_is_tile": (8, 4, 128, "bfloat16", True, [130, (W128 + 1) * 128 + 1]),
}


def _pools(rng, P, ps, hkv, dtype, int8, d=D, folded=False):
    shape = (P, ps, hkv * d) if folded else (P, ps, hkv, d)
    out = []
    for _ in range(2):
        x = jnp.asarray(rng.standard_normal((P, ps, hkv, d), dtype=np.float32), dtype)
        if int8:
            q, s = quantize_kv_rows(x.reshape(P * ps, hkv, d))
            x = QuantizedPages(q.reshape(shape), s.reshape(P, ps))
        out.append(x if int8 else x.reshape(shape))
    return out


B = 5  # odd: the last program's parity has no successor to prefetch for


def _batch(rng, ps, lengths, deep=False):
    """Disjoint page tables (page 0 is the padding every table ends in) and
    positions for B rows; a length of 0, and every row past ``lengths``, is a
    padded row, which the engine sends as position 0 over the trash page. One
    shape per page size (and one for a ``deep`` context of thousands of tokens), so
    cases of a geometry share a compiled kernel."""
    lengths = list(lengths) + [0] * (B - len(lengths))
    P, max_pages = (128, 48) if ps < 128 else (24, 8)
    if deep:  # lfm2-8b-a1b-d16's last rung: 320 pages, contexts to 4864 tokens
        P, max_pages = 384, 320
    order = 1 + rng.permutation(P - 1)
    tables = np.zeros((B, max_pages), np.int32)
    at = 0
    for b, n in enumerate(lengths):
        pages = -(-n // ps)
        tables[b, :pages] = order[at:at + pages]
        at += pages
    positions = jnp.asarray([max(n, 1) - 1 for n in lengths], jnp.int32)
    return P, jnp.asarray(tables), positions, np.asarray(lengths), set(order[:at].tolist())


def test_geometry_of_the_cases():
    # the lengths above are written against these: a tile of 128 tokens, and
    # one page per tile once a page holds that many
    assert (TP, TILE, W, W128) == (8, 128, 2, 4)
    assert decode_tile_pages(16, 2, 128, 2) == 8
    assert decode_tile_pages(128, 8, 128, 2) == decode_tile_pages(256, 8, 128, 2) == 1
    assert lookahead_window(16, 2, 128, 2) == 2
    # four tiles of 128 tokens would overrun the budget: the tile narrows
    assert decode_tile_pages(16, 32, 128, 4) == 2
    assert lookahead_window(16, 32, 128, 4) == 2


@pytest.mark.parametrize("name", CASES)
def test_tiled_decode_matches_reference(name):
    hq, hkv, ps, dtype, int8, lengths = CASES[name]
    rng = np.random.default_rng(sorted(CASES).index(name))
    P, tables, positions, lengths, _ = _batch(rng, ps, lengths)
    k, v = _pools(rng, P, ps, hkv, dtype, int8)
    q = jnp.asarray(rng.standard_normal((B, hq, D), dtype=np.float32), dtype)
    got = paged_decode_attention_pallas_lookahead(q, k, v, tables, positions, interpret=True)
    want = paged_decode_attention(q, k, v, tables, positions)
    live = lengths > 0
    atol = 2e-5 if dtype == "float32" else 2e-2  # one bf16 ulp of an output near 2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live], atol=atol
    )
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("folded", [False, True], ids=["heads", "folded"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_stale_values_in_unused_pages_do_not_reach_the_output(int8, folded):
    """Non-finite values in every page and scale row no sequence owns, and in
    the unused tail of each sequence's last page: the output is the one the
    clean pool gives. Over pools [P, ps, 4, 128] and folded [P, ps, 8 * 64]."""
    hq, hkv, d, ps = (16, 8, 64, PS) if folded else (8, 4, D, PS)
    kernel = (paged_decode_attention_pallas_folded if folded
              else paged_decode_attention_pallas_lookahead)
    rng = np.random.default_rng(11)
    P, tables, positions, lengths, owned = _batch(
        rng, ps, [1, TILE + 1, 0, (W + 1) * TILE + PS + 2, PS - 1])
    k, v = _pools(rng, P, ps, hkv, "bfloat16", int8, d, folded)
    q = jnp.asarray(rng.standard_normal((B, hq, d), dtype=np.float32), jnp.bfloat16)
    want = kernel(q, k, v, tables, positions, interpret=True)

    stale = np.ones((P, ps), bool)
    stale[0, 0] = False  # the padded row's one token, on the trash page
    for b, n in enumerate(lengths):
        for i in range(-(-n // ps)):
            stale[int(tables[b, i]), : min(ps, n - i * ps)] = False
    assert stale[sorted(set(range(1, P)) - owned)].all() and not stale.all()
    rows = stale.reshape(P, ps, *[1] * (k.ndim - 2))  # against a page's head and lane dims

    def plant(pool, bad):
        if int8:
            return QuantizedPages(jnp.where(rows, jnp.int8(127), pool.q),
                                  jnp.where(stale, bad, pool.s))
        return jnp.where(rows, jnp.asarray(bad, pool.dtype), pool)

    got = kernel(q, plant(k, jnp.nan), plant(v, jnp.inf), tables, positions, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert np.isfinite(np.asarray(got, np.float32)).all()


# ---------------------------------------------------------------- folded pools
# (head_dim under 128, or one kv head a tensor-parallel shard): the same walk
# with the folded row of Hkv * D lanes taken as one head, so the same tile of
# 128 tokens and window of two tiles at page 16, whatever the lanes

#: name -> (Hq, Hkv, D, pool dtype, int8, pool rank, lengths of the first rows)
FOLDED_CASES = {
    # F = 512: lfm2-8b-a1b's 8 kv heads of 64
    "f512_tile_edges": (16, 8, 64, "bfloat16", False, 3, [1, TILE - 1, TILE, TILE + 1, 2 * TILE - 1]),
    "f512_window_edges": (16, 8, 64, "bfloat16", False, 3, [W * TILE + 1, 0, W * TILE, 0, (W + 1) * TILE + PS + 1]),
    "f512_empty_between_live": (16, 8, 64, "bfloat16", False, 3, [TILE + 3, 0, 1, 0, 3 * TILE]),
    "f512_two_tile_edges": (16, 8, 64, "bfloat16", False, 3, [2 * TILE - 1, 2 * TILE + 1, 1, TILE - 1, 2 * TILE]),
    "f512_context_4096": (16, 8, 64, "bfloat16", False, 3, [4096, 1, TILE + 2]),
    # the longest context `lfm2-8b-a1b-d16.rag-over` decodes: 4096 + 768, on the last rung
    "f512_context_4864": (16, 8, 64, "bfloat16", False, 3, [TILE + 1, 0, 4864]),
    "f512_float32": (16, 8, 64, "float32", False, 3, [5, W * TILE + 9, 1, 2 * TILE - 1, (W + 2) * TILE]),
    "f512_int8": (16, 8, 64, "bfloat16", True, 3, [1, TILE, (W + 1) * TILE + 6]),
    "f512_int8_context_4096": (16, 8, 64, "bfloat16", True, 3, [4096, 0, TILE - 1]),
    # F = 256: TinyLlama's 4 kv heads of 64
    "f256_bf16": (32, 4, 64, "bfloat16", False, 3, [TILE + 1, 0, 1, 0, (W + 3) * TILE + 3]),
    "f256_float32": (8, 4, 64, "float32", False, 3, [TILE - 1, W * TILE + 1, 0, PS, (W + 1) * TILE]),
    "f256_int8": (8, 4, 64, "bfloat16", True, 3, [TILE + 1, (W + 1) * TILE + 2, 3]),
    # F = 128: one kv head of 128 a tensor-parallel shard
    "f128_one_kv_head": (7, 1, 128, "bfloat16", False, 3, [TILE - 1, (W + 1) * TILE + 1, 0, W * TILE, PS]),
    "f128_one_kv_head_float32": (7, 1, 128, "float32", False, 3, [1, W * TILE + 1, 2 * TILE]),
    "f128_one_kv_head_int8": (7, 1, 128, "bfloat16", True, 3, [TILE, 0, (W + 2) * TILE - 1]),
    # the direct call's convenience: a pool that is not folded yet
    "rank4_direct": (8, 4, 64, "bfloat16", False, 4, [TILE + 1, 0, (W + 1) * TILE + 5]),
    "rank4_direct_int8": (8, 4, 64, "bfloat16", True, 4, [1, W * TILE + 1, TILE]),
}


def test_geometry_of_the_folded_cases():
    # the folded row is one head to the walk: lfm2's 512 lanes, TinyLlama's 256
    # and a shard's 128 all get the tile of 8 pages and the window of two
    for lanes in (128, 256, 512):
        for itemsize in (1, 2, 4):
            assert decode_tile_pages(PS, 1, lanes, itemsize) == TP
            assert lookahead_window(PS, 1, lanes, itemsize) == W
    # a K+V tile of 512 lanes is 256 KiB: six of them are well inside the budget
    assert 6 * 2 * TP * PS * 512 * 2 == 1536 * 1024
    # where four tiles of 128 tokens overrun it the tile narrows, as for rank 4
    assert decode_tile_pages(PS, 1, 4096, 4) == 2
    # and a page that four of do not fit has no kernel: the dispatcher refuses it
    assert lookahead_window(128, 1, 4096, 2) == 0


@pytest.mark.parametrize("name", FOLDED_CASES)
def test_folded_tiled_decode_matches_reference(name):
    hq, hkv, d, dtype, int8, rank, lengths = FOLDED_CASES[name]
    rng = np.random.default_rng(100 + sorted(FOLDED_CASES).index(name))
    P, tables, positions, lengths, _ = _batch(rng, PS, lengths, deep=max(lengths) > 768)
    k, v = _pools(rng, P, PS, hkv, dtype, int8, d, folded=rank == 3)
    q = jnp.asarray(rng.standard_normal((B, hq, d), dtype=np.float32), dtype)
    got = paged_decode_attention_pallas_folded(q, k, v, tables, positions, interpret=True)
    want = paged_decode_attention(q, k, v, tables, positions)
    live = lengths > 0
    # bf16 pools: the probabilities go to the MXU in bf16 too, as they did a
    # page at a time; one bf16 ulp of an output near 2 still holds them
    atol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live], atol=atol
    )
    assert np.isfinite(np.asarray(got, np.float32)).all()


# ---------------------------------------------------------------- live rows
# (PR 46): the grid serves the rows `ops.live_rows` puts first; a row that is
# not live is a grid step and nothing else, and reads zero

#: name -> which of the B rows hold a sequence
LIVE_MASKS = {
    "all_live": [1, 1, 1, 1, 1],
    "first_dead": [0, 1, 1, 1, 1],
    "last_dead": [1, 1, 1, 1, 0],
    "alternating": [1, 0, 1, 0, 1],
    "alternating_first_dead": [0, 1, 0, 1, 0],
    "one_live": [0, 0, 0, 1, 0],
    "none_live": [0, 0, 0, 0, 0],
}
#: name -> (kernel, Hq, Hkv, D, pool dtype, int8, folded); every row holds a
#: context, so a dead row is one the mask alone takes out
LIVE_KERNELS = {
    "heads": (paged_decode_attention_pallas_lookahead, 4, 2, D, "float32", False, False),
    "heads_int8": (paged_decode_attention_pallas_lookahead, 8, 4, D, "bfloat16", True, False),
    "folded": (paged_decode_attention_pallas_folded, 16, 8, 64, "bfloat16", False, True),
    "folded_int8": (paged_decode_attention_pallas_folded, 16, 8, 64, "bfloat16", True, True),
}
LIVE_LENGTHS = [TILE + 3, (W + 1) * TILE + PS + 1, 1, W * TILE, 2 * TILE - 1]


def test_live_rows_come_first_and_in_order():
    from dynamo_tpu.ops.live_rows import every_row, live_rows

    for mask in LIVE_MASKS.values():
        live = live_rows(jnp.asarray(mask, bool))
        n = int(live.count[0])
        assert n == sum(mask) and live.count.dtype == live.order.dtype == jnp.int32
        assert np.asarray(live.order).tolist() == (
            [b for b in range(B) if mask[b]] + [b for b in range(B) if not mask[b]])
        np.testing.assert_array_equal(live.mask, np.asarray(mask, bool))
    whole = every_row(B)
    assert np.asarray(whole.order).tolist() == list(range(B)) and int(whole.count[0]) == B


@pytest.mark.parametrize("mask", LIVE_MASKS)
@pytest.mark.parametrize("kind", LIVE_KERNELS)
def test_the_grid_serves_the_live_rows_alone(kind, mask):
    """A live row's output is the all-live call's to the bit (and the
    reference's to the tolerance), a dead row's is zero; with no live row the
    call returns (no DMA is started, so no semaphore is waited on)."""
    from dynamo_tpu.ops.live_rows import every_row, live_rows

    kernel, hq, hkv, d, dtype, int8, folded = LIVE_KERNELS[kind]
    rng = np.random.default_rng(46)
    P, tables, positions, _, _ = _batch(rng, PS, LIVE_LENGTHS)
    k, v = _pools(rng, P, PS, hkv, dtype, int8, d, folded)
    q = jnp.asarray(rng.standard_normal((B, hq, d), dtype=np.float32), dtype)
    alive = np.asarray(LIVE_MASKS[mask], bool)
    got = np.asarray(
        kernel(q, k, v, tables, positions, live_rows(jnp.asarray(alive)), interpret=True),
        np.float32)
    whole = np.asarray(kernel(q, k, v, tables, positions, every_row(B), interpret=True), np.float32)
    want = np.asarray(paged_decode_attention(q, k, v, tables, positions), np.float32)
    np.testing.assert_array_equal(got[alive], whole[alive])
    np.testing.assert_allclose(got[alive], want[alive], atol=2e-5 if dtype == "float32" else 2e-2)
    np.testing.assert_array_equal(got[~alive], 0.0)


# ---------------------------------------------------------------- runs of a tile
# (PR 47): a tile whose table entries are first, first + 1, ... is one slab of
# the pool and moves as ONE copy a pool, whole, whatever the sequence has
# written of it; any other tile moves a page a copy. Same contexts, three
# layouts of their pages

RUN_WINDOW = 200  # a sliding window that starts inside a tile
#: name -> (kernel, keywords, Hq, Hkv, D, pool dtype, int8, folded)
RUN_KERNELS = {
    "heads": (paged_decode_attention_pallas_lookahead, {}, 4, 2, D, "bfloat16", False, False),
    "heads_float32": (paged_decode_attention_pallas_lookahead, {}, 8, 4, D, "float32", False, False),
    "sliding_window": (paged_decode_attention_pallas_lookahead, {"window": RUN_WINDOW},
                       4, 2, D, "bfloat16", False, False),
    "heads_int8": (paged_decode_attention_pallas_lookahead, {}, 8, 4, D, "bfloat16", True, False),
    "folded": (paged_decode_attention_pallas_folded, {}, 16, 8, 64, "bfloat16", False, True),
    "folded_int8": (paged_decode_attention_pallas_folded, {}, 16, 8, 64, "bfloat16", True, True),
}
RUN_LAYOUTS = ("every_tile_a_run", "no_tile_a_run", "mixed_in_a_sequence")
RUN_LENGTHS = [TILE + 3, (W + 1) * TILE + PS + 1, 1, W * TILE, 3 * TILE - 5]
RUN_P, RUN_MAX_PAGES = 30 * TP, 6 * TP


def _laid_out(layout, seed=47):
    """Page tables [B, RUN_MAX_PAGES] for RUN_LENGTHS. A run is an aligned slab
    of the pool and shows whole in the table, the pages past the sequence's
    own included (the allocator has reserved them: they hold NaN here). In
    the mixed layout a sequence's first tile is a shared-prefix boundary (a
    run's first half, then pages of its own from elsewhere), its last tile a
    run fetched whole over unwritten pages, the others by turns."""
    rng = np.random.default_rng(seed)
    slabs = iter((1 + rng.permutation(RUN_P // TP - 1)) * TP)
    tables = np.zeros((B, RUN_MAX_PAGES), np.int32)
    for b, n in enumerate(RUN_LENGTHS):
        pages = -(-n // PS)
        tiles = -(-pages // TP)
        for t in range(tiles):
            held = min(TP, pages - t * TP)
            first = int(next(slabs))
            run = first + np.arange(TP)
            scattered = first + rng.permutation(TP)
            while held > 1 and np.all(np.diff(scattered[:held]) == 1):
                scattered = first + rng.permutation(TP)
            if layout == "every_tile_a_run":
                kind = "run"
            elif layout == "no_tile_a_run":
                kind = "scattered"
            elif t == tiles - 1:
                kind = "run"
            elif t == 0:
                kind = "boundary"
            else:
                kind = "run" if t % 2 else "scattered"
            if kind == "run":
                tables[b, t * TP:(t + 1) * TP] = run
            elif kind == "scattered":
                tables[b, t * TP:t * TP + held] = scattered[:held]
            else:  # half of a writer's run, then own pages that do not continue it
                tables[b, t * TP:t * TP + TP // 2] = run[:TP // 2]
                tables[b, t * TP + TP // 2:(t + 1) * TP] = int(next(slabs)) + np.arange(TP // 2)
    return tables


def _pools_of(tables, hkv, d, dtype, int8, folded, seed=48):
    """(clean pools, planted pools) holding the SAME context rows wherever
    `tables` puts them: the planted ones have a non-finite value in every row
    no sequence has written (the reserved pages of a run among them)."""
    rng = np.random.default_rng(seed)
    max_tokens = RUN_MAX_PAGES * PS
    out = []
    for bad in (jnp.nan, jnp.inf):  # K, V
        rows = rng.standard_normal((B, max_tokens, hkv, d), dtype=np.float32)
        pool = np.zeros((RUN_P * PS, hkv, d), np.float32)
        written = np.zeros(RUN_P * PS, bool)
        for b, n in enumerate(RUN_LENGTHS):
            tok = np.arange(n)
            at = tables[b, tok // PS] * PS + tok % PS
            pool[at], written[at] = rows[b, :n], True
        x = jnp.asarray(pool, dtype)
        shape = (RUN_P, PS, hkv * d) if folded else (RUN_P, PS, hkv, d)
        mask = jnp.asarray(~written).reshape(RUN_P, PS)
        if int8:
            q, s = quantize_kv_rows(x)
            q, s = q.reshape(shape), s.reshape(RUN_P, PS)
            lanes = mask.reshape(RUN_P, PS, *[1] * (len(shape) - 2))
            out.append((QuantizedPages(q, s),
                        QuantizedPages(jnp.where(lanes, jnp.int8(127), q), jnp.where(mask, bad, s))))
        else:
            x = x.reshape(shape)
            lanes = mask.reshape(RUN_P, PS, *[1] * (len(shape) - 2))
            out.append((x, jnp.where(lanes, jnp.asarray(bad, x.dtype), x)))
    (k, k_bad), (v, v_bad) = out
    return (k, v), (k_bad, v_bad)


def _run_case(kind, layout):
    kernel, kw, hq, hkv, d, dtype, int8, folded = RUN_KERNELS[kind]
    tables = _laid_out(layout)
    clean, planted = _pools_of(tables, hkv, d, dtype, int8, folded)
    q = jnp.asarray(np.random.default_rng(49).standard_normal((B, hq, d), dtype=np.float32), dtype)
    positions = jnp.asarray([n - 1 for n in RUN_LENGTHS], jnp.int32)
    got = kernel(q, *planted, jnp.asarray(tables), positions, interpret=True, **kw)
    want = paged_decode_attention(q, *clean, jnp.asarray(tables), positions, kw.get("window", 0))
    return tables, np.asarray(got, np.float32), np.asarray(want, np.float32), dtype


def test_tile_runs_are_read_off_the_table():
    from dynamo_tpu.ops.pallas.paged_attention import tile_runs

    tiles = [-(-(-(-n // PS)) // TP) for n in RUN_LENGTHS]
    for layout in RUN_LAYOUTS:
        flags = np.asarray(tile_runs(jnp.asarray(_laid_out(layout)), TP)).reshape(B, -1)
        for b, n in enumerate(tiles):
            want = {"every_tile_a_run": [1] * n, "no_tile_a_run": [0] * n,
                    # the boundary, by turns, the last one whole (a context of one tile: that)
                    "mixed_in_a_sequence": ([0] + [t % 2 for t in range(1, n - 1)] + [1])[-n:]}[layout]
            assert flags[b].tolist() == want + [0] * (flags.shape[1] - n), (layout, b)
    # a layer's offset moves every entry alike; the null page never continues a run
    table = jnp.asarray(_laid_out("mixed_in_a_sequence"))
    np.testing.assert_array_equal(tile_runs(table + 7 * RUN_P, TP), tile_runs(table, TP))
    assert not np.asarray(tile_runs(jnp.zeros((2, 2 * TP), jnp.int32), TP)).any()
    assert not np.asarray(tile_runs(jnp.arange(8, 8 + 16)[None], 1)).any()  # a tile of one page
    with pytest.raises(ValueError, match="tile runs"):
        paged_decode_attention_pallas_lookahead(
            jnp.zeros((B, 4, D)), jnp.zeros((RUN_P, PS, 2, D)), jnp.zeros((RUN_P, PS, 2, D)),
            table, jnp.zeros((B,), jnp.int32), runs=jnp.zeros((B,), jnp.int32), interpret=True)


@pytest.mark.parametrize("layout", RUN_LAYOUTS)
@pytest.mark.parametrize("kind", RUN_KERNELS)
def test_runs_of_a_tile_match_the_reference(kind, layout):
    """Against the gather reference on clean pools, with NaN and inf in every
    row no sequence has written: a run's last tile is fetched whole, and what
    its reserved pages hold does not reach the output."""
    _, got, want, dtype = _run_case(kind, layout)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5 if dtype == "float32" else 2e-2)


@pytest.mark.parametrize("kind", RUN_KERNELS)
def test_a_row_reads_the_same_to_the_bit_however_its_pages_lie(kind):
    """One copy a tile or a copy a page put the same rows into the same
    scratch: the arithmetic of a live row does not move."""
    outs = [_run_case(kind, layout)[1] for layout in RUN_LAYOUTS]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
