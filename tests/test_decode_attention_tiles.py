"""The tiled lookahead decode kernel (interpret mode) against the gather
reference, at the boundaries its loop structure has: a tile of pages per
iteration, a cross-program window of W tiles, a double-buffered tail."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_decode_attention
from dynamo_tpu.ops.pallas.paged_attention import (
    decode_tile_pages,
    lookahead_window,
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


def _pools(rng, P, ps, hkv, dtype, int8):
    out = []
    for _ in range(2):
        x = jnp.asarray(rng.standard_normal((P, ps, hkv, D), dtype=np.float32), dtype)
        if int8:
            q, s = quantize_kv_rows(x.reshape(P * ps, hkv, D))
            x = QuantizedPages(q.reshape(P, ps, hkv, D), s.reshape(P, ps))
        out.append(x)
    return out


B = 5  # odd: the last program's parity has no successor to prefetch for


def _batch(rng, ps, lengths):
    """Disjoint page tables (page 0 is the padding every table ends in) and
    positions for B rows; a length of 0, and every row past ``lengths``, is a
    padded row, which the engine sends as position 0 over the trash page. One
    shape per page size, so cases of a geometry share a compiled kernel."""
    lengths = list(lengths) + [0] * (B - len(lengths))
    P, max_pages = (128, 48) if ps < 128 else (24, 8)
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


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_stale_values_in_unused_pages_do_not_reach_the_output(int8):
    """Non-finite values in every page and scale row no sequence owns, and in
    the unused tail of each sequence's last page: the output is the one the
    clean pool gives."""
    hq, hkv, ps = 8, 4, PS
    rng = np.random.default_rng(11)
    P, tables, positions, lengths, owned = _batch(
        rng, ps, [1, TILE + 1, 0, (W + 1) * TILE + PS + 2, PS - 1])
    k, v = _pools(rng, P, ps, hkv, "bfloat16", int8)
    q = jnp.asarray(rng.standard_normal((B, hq, D), dtype=np.float32), jnp.bfloat16)
    want = paged_decode_attention_pallas_lookahead(q, k, v, tables, positions, interpret=True)

    stale = np.ones((P, ps), bool)
    stale[0, 0] = False  # the padded row's one token, on the trash page
    for b, n in enumerate(lengths):
        for i in range(-(-n // ps)):
            stale[int(tables[b, i]), : min(ps, n - i * ps)] = False
    assert stale[sorted(set(range(1, P)) - owned)].all() and not stale.all()

    def plant(pool, bad):
        if int8:
            return QuantizedPages(
                jnp.where(stale[..., None, None], jnp.int8(127), pool.q),
                jnp.where(stale, bad, pool.s),
            )
        return jnp.where(stale[..., None, None], jnp.asarray(bad, pool.dtype), pool)

    got = paged_decode_attention_pallas_lookahead(
        q, plant(k, jnp.nan), plant(v, jnp.inf), tables, positions, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    assert np.isfinite(np.asarray(got, np.float32)).all()
