"""The head_dim-128 flash prefill kernels at the long context tile a wide page
table gets (`prefill_tile_pages`: 512 tokens under a table of more than 2048),
interpret mode, against the gather reference: the boundaries a tile of 32
pages moves (a chunk that starts inside a tile, a window's edge inside a tile,
a last tile that ends beyond the context, a context shorter than one tile), on
float32, bfloat16 and int8 pools. A tile that long runs the basic kernel
(`prefill_lookahead_window` gives it no cross-program window); the lookahead
kernel is held to the same boundaries at tiles of 256 tokens, a window of two."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import paged_prefill_attention
from dynamo_tpu.ops.pallas.prefill_attention import (
    paged_prefill_attention_pallas,
    prefill_lookahead_window,
    prefill_tile_pages,
)
from dynamo_tpu.quant.kv import QuantizedPages, quantize_kv_rows

PS, HQ, HKV, D, T = 16, 4, 2, 128, 64
POOL_PAGES = 100  # one pool shape for every case: a program compiles once a window

#: name -> (chunk start, window): where the chunk's rows stand against tiles
#: of 512 tokens
BOUNDARIES = {
    "shorter-than-a-tile": (0, 0),  # context 64: one tile, seven eighths beyond it
    "start-inside-a-tile": (700, 0),  # rows 700-763: tile 1 ends beyond the context
    "four-tiles": (1530, 0),  # the window tile, then the in-program double buffer
    "window-edge-inside-a-tile": (1530, 600),  # keys from 931: 419 tokens into tile 1
    "window-from-the-first-tile": (700, 600),  # keys from 101: the walk starts at tile 0
}

#: (table width, pool, tile in pages or None for the rule's): a program compiles
#: once for each of these and each window, ten seconds in interpret mode, so not
#: every product
KERNELS = [
    (256, "f32", None), (1024, "f32", None), (256, "bf16", None), (256, "int8", None),
    (256, "f32", 16), (256, "int8", 16),
]


def _pools(kind: str, pages: int, seed: int):
    rng = np.random.default_rng(seed)
    k = rng.standard_normal((pages, PS, HKV, D)).astype(np.float32)
    v = rng.standard_normal((pages, PS, HKV, D)).astype(np.float32)
    if kind == "int8":
        def quantized(x):
            q, s = quantize_kv_rows(jnp.asarray(x.reshape(pages * PS, HKV, D)))
            return QuantizedPages(q.reshape(pages, PS, HKV, D), s.reshape(pages, PS))
        return quantized(k), quantized(v)
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype)


@pytest.mark.parametrize("boundary", BOUNDARIES)
@pytest.mark.parametrize(
    "width,kind,tile_pages", KERNELS,
    ids=[f"{w}-{k}-{f'tile{t * PS}-lookahead' if t else 'rule'}" for w, k, t in KERNELS])
def test_long_tile_matches_reference(width, kind, tile_pages, boundary):
    start, window = BOUNDARIES[boundary]
    assert prefill_tile_pages(PS, width) * PS == 512
    rng = np.random.default_rng(start + window + width)
    live = -(-(start + T) // PS)  # the sequence holds this many pages
    k, v = _pools(kind, 1 + POOL_PAGES, seed=width + start)
    table = np.zeros(width, np.int32)  # beyond the sequence: the null page
    table[:live] = 1 + rng.permutation(live)
    q = jnp.asarray(rng.standard_normal((T, HQ, D)), jnp.float32)
    positions = jnp.arange(start, start + T, dtype=jnp.int32)
    if kind == "bf16":
        # bf16 inputs against the float32 reference of the same rounded values
        q = q.astype(jnp.bfloat16)
        want = paged_prefill_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
            jnp.asarray(table), positions, window)
        atol = 2e-2  # the output is rounded to bfloat16: 8 bits of mantissa
    else:
        want = paged_prefill_attention(q, k, v, jnp.asarray(table), positions, window)
        atol = 2e-4 if kind == "int8" else 2e-5
    if window:
        # the engine has given back the pages wholly behind the first row's window
        table[: max(0, start - window + 1) // PS] = 0
    got = paged_prefill_attention_pallas(
        q, k, v, jnp.asarray(table), positions,
        block_q=32, interpret=True, window=window, tile_pages=tile_pages,
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol, rtol=atol)


@pytest.mark.parametrize("page_size,width,tokens", [
    (16, 128, 128),  # every prompt of chat, chat-over and reason-over: unchanged
    (16, 256, 512), (16, 512, 512), (16, 1024, 512),  # the rest of the ladder
    (16, 40, 128), (4, 40, 128),  # the narrow tables of the older tests
    (64, 32, 128), (64, 64, 512),  # the rule counts tokens, not pages
    (128, 16, 128), (128, 128, 512), (256, 64, 512),  # never under one page
    (16, 0, 128),  # no width given: the folded kernel's tile
])
def test_tile_follows_the_tables_width(page_size, width, tokens):
    assert prefill_tile_pages(page_size, width) * page_size == max(tokens, page_size)


@pytest.mark.parametrize("page_size,tile_tokens,hkv,itemsize,tiles", [
    (16, 128, 2, 2, 4), (16, 128, 8, 2, 4), (128, 128, 8, 2, 4),  # as before PR 38
    (16, 256, 8, 2, 2), (64, 256, 8, 2, 2),
    # a long tile is the whole budget of pages: no window, the basic kernel runs
    (16, 512, 8, 2, 0), (16, 512, 2, 2, 0), (16, 512, 8, 1, 0), (128, 512, 8, 2, 0),
    (16, 1024, 8, 2, 0),
])
def test_lookahead_window_is_budgeted_in_pages(page_size, tile_tokens, hkv, itemsize, tiles):
    tile_pages = tile_tokens // page_size
    assert prefill_lookahead_window(page_size, tile_pages, hkv, D, itemsize) == tiles
    # `issue_pre` unrolls one DMA issue a page: never more than four short tiles' worth
    assert tiles * tile_pages <= 4 * prefill_tile_pages(page_size)
