"""Pallas TPU kernel: paged decode attention.

One query token per sequence attends over its paged KV context. The page
table rides in as scalar-prefetch (available before the kernel body, so page
DMAs can be issued from dynamic indices), K/V page pools stay in HBM, and
pages stream through a double-buffered VMEM scratch overlapping DMA with
compute (pallas_guide.md: PrefetchScalarGridSpec + double buffering): a tile
of pages at a time in the tiled kernel, which walks pools of either rank
(since PR 44), a page at a time in its fallback.

Contract matches the pure-JAX reference (dynamo_tpu/ops/attention.py
paged_decode_attention): q [B, Hq, D], pages [P, ps, Hkv, D] or FOLDED
[P, ps, Hkv*D] (head_dim under 128, or one kv head a tensor-parallel shard),
page_tables [B, max_pages], positions [B] (query position; context length =
position + 1). GQA folded as [Hkv, G, D] per-kv-head batched matmuls; over a
folded pool as one [Hq, Hkv*D] product on a zero-placed query.

Design record (PR 26; every time below is my chip run on a TPU v5e, 36
chained calls in one jit, best of 5, at the benchmark cells' shapes: B 64,
Hq 16, Hkv 2, D 128, page 16, pool 13312 pages, bf16; "45 rows" is 45 live
contexts of 200-900 tokens and 19 one-token slots, 1688 pages, as
qwen2.5-3b.chat-over has them; "15 rows" 15 live and 49 empty, 582 pages, as
qwen2.5-3b.chat; the HBM floor of the two is 33 and 11 us).

  us per call (ns per page)          45 rows        15 rows
  null, a page per iteration         373 (221)      150 (259)   DMA stream only
  perseq                             643 (381)      253 (435)
  lookahead, a page per iteration    630 (373)      243 (417)   the default until PR 26
  chunked (16 pages, no prefetch)    267 (158)      171 (293)
  null, a tile per iteration         123  (73)       74 (127)
  lookahead, a tile per iteration    203 (120)      120 (207)   the default now

  - A page at a time is bound by the DMA stream, not the arithmetic: the
    null kernel (same grid, same two 8 KiB DMAs a page, one page in flight
    behind the one in use, no math) takes 59% of the real kernel's time.
    With a tile's 2 x TP page DMAs started together and waited together the
    same stream takes a third of that, and from there the arithmetic counts.
  - TP: a tile is 128 context tokens (one full lane row of scores; 8 pages of
    16, one page of 128 and more). 64 and 256 timed 5-10% worse at 45 rows.
    decode_tile_pages halves it while four tiles overrun the VMEM budget.
  - W: program b starts program b+1's first W tiles (Mosaic runs the grid
    serially and scratch persists; the page table is scalar-prefetched).
    W = 1, 2, 4, 8, 16 timed 245-264 us, the small ones best, so W is 2
    (lookahead_window), less where the VMEM budget holds fewer, and the 4
    pages it was where a tile is one page. The page-at-a-time window barely
    paid at page 16 (630 against perseq's 643): it covered 64 tokens of a
    300-700 token context.
  - Operands stay f32. bf16 operands (q, K, V, probs to the MXU in bf16) ran
    17% SLOWER at [.., 2, 128] pages: the relayout [tokens, Hkv, D] ->
    [Hkv, tokens, D] is cheaper in 32-bit, and casting f32 rows back to bf16
    for the MXU doubled the kernel (428 against 212).
  - With two bf16 kv heads the relayout is not needed at all (_heads_major):
    203 against 248 us. With four or eight heads picking head pairs out of
    the words timed slower than the 32-bit transpose, which they keep.
  - Rows past the length in a sequence's last tile are stale VMEM. They are
    zeroed in place, on that tile only: as a select on every tile's V it
    cost 5%, and as a lax.cond around the select 20% more than that.
  - Not gained: a fast path for full tiles (one branch, one wait a tile) and
    q/out resident in VMEM for the whole grid gave 4% together and were left
    out; an empty slot cost 0.7 us until PR 46 (its own trash-page DMA, wait
    and tile; "Live rows" below), a quarter of the 15-row time.
  - Other geometries sharing this kernel, old -> new at 45 rows (final
    tree): Hkv 4 and 8 at page 16 623 -> 256 and 621 -> 259 us; int8 at page
    16 723 -> 396 and 711 -> 405; Hkv 8 at page 64 262 -> 239; at page 128,
    where a tile is a page and the kernel is the old one but for the word
    split, Hkv 4 and 8 195 -> 190 and 223 -> 223, int8 200 -> 206. (With the
    stale-row zeroing and a window of 2 there, Hkv 8 read 223 -> 239: both
    were taken out again for a tile of one page, which is fetched whole.)
  - The no-transpose dot_general variants (batch dim in K's middle position)
    are Mosaic-illegal outright (tpu.matmul requires leading batch dims).
  - perseq stays as the fallback for a geometry whose window does not fit.
  - chunked, and grouped (several sequences a grid program, a page at a
    time), were deleted in PR 31: bf16 only, slower than the tiled default at
    every shape measured, and reachable only through an environment variable.

Folded pools (PR 44, first built by PR 43; chip runs on a TPU v5e,
tools/profile_folded_attention.py: 24 chained calls, best of 5, at
lfm2-8b-a1b's shape, Hq 32, Hkv 8, D 64, page 16, bf16: a page's K and V are
one DMA of 16 KiB each; every sequence at the context named, HBM floor 82 /
246 / 656 us at a batch of 64). Until PR 44 a kernel of their own walked them
a page at a time (two DMAs in flight behind the page in use, two products of
16 context rows, a mask and a select a page):

  us per call (ns per page), batch 64     ctx 512      ctx 1536      ctx 4096
  folded, a page per iteration (PR 43)    829 (405)    2368 (385)    6198 (378)
  the tiled walk, folded merge (PR 44)    246 (120)     621 (101)    1566  (96)

  and 455 / 1207 / 3086 us at a batch of 128, 878 / 2384 / 6143 at 256
  (against 1631 / 4686 / 12330 and 3227 / 9346 / 24622): 3.4-4.0 times, and
  34-43% of the HBM roofline where a page at a time stood at 10-10.7%
  whatever the batch and the depth.

  - It is the SAME walk (_kernel_lookahead's tile / window / tail DMAs never
    look inside a page) with the folded row of Hkv * D lanes taken as one head
    (decode_tile_pages / lookahead_window at num_kv_heads 1, head_dim Hkv * D):
    a tile of 8 pages and a window of 2 at page 16 for 128, 256 and 512 lanes,
    bf16 or int8; six tiles of 256 KiB of scratch at 512 lanes. Only the merge
    follows the pool's rank. The programs lowered for rank-4 pools are what
    they were, instruction for instruction (the Mosaic module of the
    qwen2.5-3b, qwen2.5-7b int8, command-a-plus full and window, and mixtral
    page-128 / page-64 int8 decode kernels printed without debug locations,
    before and after: identical).
  - The folded merge keeps its operands as the pool holds them (bf16 K, V,
    zero-placed query and probabilities to the MXU, f32 accumulation; int8
    pages as f32): a tile [8, 16, F] is [128, F] with no relayout, bf16 pages
    being whole (16, 128) tiles, so there is no 32-bit transpose to pay and
    none of the f32-operand findings above apply to it.
  - Not tried: a tile of 256 tokens, a wider window (W 1-16 timed within 7%
    of each other for rank 4). An empty slot cost what it did there.
  - The file holds two kernel bodies: the tiled walk (lookahead, with a merge
    per pool rank) and perseq.

Live rows (PR 46; my chip runs on a TPU v5e, tools/profile_live_rows.py: 36
chained calls, best of 5; the rank-4 rows at the geometry of the design
record above with the live rows scattered over the 64 slots, the folded rows
at lfm2-8b-a1b's shape, 256 slots, every live context 1536 tokens). A decode
batch is the whole slot table, and until PR 46 the grid was too: a slot that
holds nobody has position 0, so its program found one token on the trash
page, started and waited a page DMA for K and V and ran a whole tile through
the merge. Now the grid is over the step's LIVE rows (ops/live_rows.py, made
once a decode step on the device): `order` rides as scalar prefetch beside the
page table and the lengths, program i serves row order[i] (q and out blocks
by the index maps), prefetches row order[i + 1]'s window, and the live count
is the grid's bound, read on the device (Pallas lowers a traced bound on this
backend; in interpret mode it is a while_loop). A dead row has no program: no
DMA, no block, no arithmetic; nothing writes its output row, which the
wrapper's mask reads as zero. With no live row (a warm-up shape) no program
runs, so no DMA starts and no semaphore is waited on.

  us per call (share of the HBM roofline)   until PR 46     the live rows only
  15 live + 49 empty (chat)                 114 (9.8%)      84 (13.3%)
  45 live + 19 empty (chat-over)            208 (15.4%)     195 (16.3%)
  64 of 64                                  270 (17.0%)     270 (17.0%)
  folded, 74 of 256 (rag-over)              820 (34.7%)     699 (40.8%)
  folded, 256 of 256                        2368 (41.6%)    2346 (42.0%)

  - An empty program cost 0.61-0.67 us at every one of the three partly empty
    shapes ((114 - 84) / 49, (208 - 195) / 19, (820 - 699) / 182): half of
    what PR 26's two-point fit gave it (1.19 us a program, live or not). The
    rest of that fit's constant belongs to the live rows: a call is about
    27 us and 3.8 us a live row of 36 pages (84, 195 and 270 us at 15, 45 and
    64 rows), so the 15-row call fell by 26% and not by half.
  - The first form tried kept the table for a grid ("clamped"): the body
    under a `pl.when`, and the q / out index maps of a step past the count
    repeating the last live step's block, so that the pipeline moves nothing:
    83.9 / 193.6 / 268.4 / 707 / 2350 in an earlier call, where that form
    with the count for its bound read 80.8 / 193.1 / 269.9 / 700 / 2349. A
    step that does nothing costs 0.06 us, then. The bound by count compiled
    on the chip, ran in interpret mode and cost a full table nothing, so it
    is the one kept, and the clamping went. (Why the kept form reads 84.0
    and not 80.8 at 15 rows is not known: 0.2 us a program.)
  - A live row's arithmetic is what it was, to the bit, whatever rows are
    live beside it (same tiles in the same order; only the scratch parity a
    row lands on differs, and stale scratch is masked as before).

Runs (PR 47; my chip runs on a TPU v5e, tools/profile_tile_runs.py: 36 chained
calls, best of 5, parent and change in one call; the rank-4 rows at the design
record's geometry, 64 / 45 / 15 live contexts of 200-900 tokens holding 2028 /
1601 / 554 pages in 280 / 215 / 77 tiles; the folded row at lfm2-8b-a1b's
shape, 256 contexts of 1536 tokens). What a call paid for was the NUMBER of
copies, not their bytes: 44 ns a page for its two 8 KiB DMAs, started and
waited by the scalar core in the merge's own instruction stream, where the
bytes take 20. The pool is one array indexed by physical page, so eight
consecutive physical pages are one contiguous slab, and the tile's scratch
slot already has that shape: a tile whose table entries are first, first + 1,
... moves as ONE copy a pool (`hbm.at[pl.ds(first, TP)]`), started once and
waited once. Which tiles are such RUNS is read off the input (`tile_runs`, made
once a decode step on the device, outside the layer scan: a layer's offset
moves every entry alike; 13-18 us a step), a fourth scalar-prefetch operand of
one word a tile; the engine's allocator gives a sequence its pages by aligned
runs of a tile (engine/page_table.py) and shows the rest of the newest run in
the table, so a run's last tile is fetched WHOLE: what its unwritten pages hold
is masked out of the scores and zeroed in V like any stale scratch.

  us per call (share of HBM roofline)   until PR 47   every tile a run   half    none
  64 of 64                              245.7 (16.5)  171.8 (23.7)       206.9   241.1
  45 live + 19 empty (chat-over)        192.4 (16.7)  135.3 (23.7)       162.3   190.9
  15 live + 49 empty (chat)              82.3 (13.5)   62.0 (17.9)        70.8    81.8
  no live row                            18.1          17.7
  folded, 256 of 256                    2341  (42.1)  1816  (54.3)       2021    2342
  null walk (no arithmetic), 64 of 64   145.0 *       107.0              107.4   133.4
  (* a wait a page, my first call; 133.4 is a page a copy with one wait a full tile)

  - A run saves 0.26 us a tile of the rank-4 walk (74 us over 280 tiles) and
    0.17 us a tile of the folded one (525 us over 3072), and half the tiles as
    runs save half of it: the cost is linear in the copies.
  - The null walk (same grid, window, tail and copies, no arithmetic) reads
    107 us with one copy a tile where the tile's 128 KiB take 160 ns (45 us
    for 280 tiles): the stream is still latency, not bandwidth. What is left
    of a real call: 18 us a call before its first row (launch, the page table
    into SMEM, q / out blocks), the merge's 0.23 us a tile (65 us: real less
    null), about 1 us a row.
  - A tile that is no run goes a page a copy as before, but its WAIT is one:
    the page copies of a FULL tile put a tile's bytes on the semaphore, which
    one wait takes off as it does a run's (16 waits less a tile). The first
    form branched twice a side (run / not run) and cost the scattered layout
    14 us of 244 (+5.7%, 12 ns a branch taken or not); one `pl.when` a side,
    with the page loop's trip count zeroed by a select, reads 241.1 against
    the parent's 244.4 with no tile a run.
  - A live row's arithmetic is what it was, to the bit, however its pages lie:
    the same rows land in the same scratch.

Int8 KV (quant/kv.py QuantizedPages): perseq and the tiled walk (both pool
ranks) accept int8 pools plus their per-row f32 scales, which arrive as
lane-aligned rows gathered by XLA in page-table order (gather_scale_rows —
Mosaic refuses to DMA-slice the raw [P, ps] plane when ps < 128), one row per
page in perseq, per tile of pages in the tiled walk. Scale rows ride their
own tiny DMAs beside the page DMAs (the HBM context stream halves — that is
the win) and dequantization is applied to the score/prob tiles in VMEM:
``scores *= k_s`` / ``probs *= v_s`` is the exact per-column algebra, and
both are lane-axis broadcasts (Mosaic-legal; no sub-128 minor-dim reshapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.live_rows import LiveRows, every_row, zero_dead_rows
from dynamo_tpu.quant.kv import QuantizedPages

_NEG_INF = -1e30


def gather_scale_rows(scales, tables, pages_per_row: int = 1):
    """Int8 scale plane [P, ps] -> lane-aligned rows [N, 1, W] in PAGE-TABLE
    order, gathered by XLA before the kernel runs.

    Mosaic refuses a DMA slice of the raw plane when ps < 128 ("slice shape
    must be aligned to tiling (128)"), so the kernels never index the plane
    by physical page. Instead the rows a call will need are gathered here
    (a few bytes per context token — noise next to the int8 page stream),
    ``pages_per_row`` consecutive logical pages are laid side by side on the
    lane axis (1 for the page-at-a-time decode kernel, the tile width for
    prefill and the tiled decode kernel), and the row is zero-padded to a
    multiple of 128 lanes. Row r of
    the result covers logical pages [r * pages_per_row, (r+1) * pages_per_row)
    of the flattened ``tables``; the kernel DMAs ``rows.at[r]`` -> [1, W] and
    reads its first pages_per_row * ps lanes."""
    ps = scales.shape[1]
    flat = tables.reshape(-1)
    rows = scales[flat].reshape(flat.shape[0] // pages_per_row, pages_per_row * ps)
    pad = -rows.shape[1] % 128
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, pad)))
    return rows[:, None, :]


def _decode_unpack_pools(k_pages, v_pages, page_tables, pages_per_row: int = 1):
    """(k, v, k_scale rows | None, v_scale rows | None, quantized): int8
    pools carry their scales as ``gather_scale_rows`` over the page tables
    (padded to whole rows), one [1, W] row per (sequence, ``pages_per_row``
    consecutive logical pages)."""
    if isinstance(k_pages, QuantizedPages):
        pad = -page_tables.shape[1] % pages_per_row
        tables = jnp.pad(page_tables, ((0, 0), (0, pad)))
        return (
            k_pages.q, v_pages.q,
            gather_scale_rows(k_pages.s, tables, pages_per_row),
            gather_scale_rows(v_pages.s, tables, pages_per_row),
            True,
        )
    return k_pages, v_pages, None, None, False


def _kernel(
    *refs,
    page_size: int,
    max_pages: int,
    quantized: bool = False,
):
    """perseq decode kernel (one sequence per grid program, in-program
    double buffer). refs: page_tables [B, max_pages] + lengths [B] (SMEM
    scalar prefetch) | q [1, Hq, D], k/v pools [P, ps, Hkv, D] HBM
    [, k/v scale rows [B*max_pages, 1, W], see gather_scale_rows] | out
    [1, Hq, D] | k/v scratch [2, ps, Hkv, D] [, scale scratch [2, 1, W]],
    sems [2, 2|4]."""
    if quantized:
        (page_tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         out_ref, k_scratch, v_scratch, ks_scratch, vs_scratch, sems) = refs
        pools = [(k_hbm, k_scratch), (v_hbm, v_scratch),
                 (ks_hbm, ks_scratch), (vs_hbm, vs_scratch)]
    else:
        (page_tables_ref, lengths_ref, q_ref, k_hbm, v_hbm,
         out_ref, k_scratch, v_scratch, sems) = refs
        pools = [(k_hbm, k_scratch), (v_hbm, v_scratch)]

    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = jnp.maximum(1, pl.cdiv(length, page_size))

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv

    q = q_ref[0].astype(jnp.float32).reshape(Hkv, G, D)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def dma(slot, i, c):
        hbm, scratch = pools[c]
        # pages by physical id; scale rows by (sequence, logical page)
        src = page_tables_ref[b, i] if c < 2 else b * max_pages + i
        return pltpu.make_async_copy(hbm.at[src], scratch.at[slot], sems.at[slot, c])

    # warm up buffer 0
    for c in range(len(pools)):
        dma(0, 0, c).start()

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        next_slot = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            for c in range(len(pools)):
                dma(next_slot, i + 1, c).start()

        for c in range(len(pools)):
            dma(slot, i, c).wait()

        k_page = k_scratch[slot].astype(jnp.float32)  # [ps, Hkv, D]
        v_page = v_scratch[slot].astype(jnp.float32)
        kt = jnp.transpose(k_page, (1, 0, 2))  # [Hkv, ps, D]
        vt = jnp.transpose(v_page, (1, 0, 2))

        # [Hkv, G, ps] = [Hkv, G, D] x [Hkv, ps, D]
        scores = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale
        if quantized:
            # per-row K scales multiply score COLUMNS: [1, ps] -> [1, 1, ps]
            scores = scores * ks_scratch[slot][:, :page_size][None]

        idx = i * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
        scores = jnp.where(idx < length, scores, _NEG_INF)

        chunk_max = jnp.max(scores, axis=-1)  # [Hkv, G]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[..., None])  # [Hkv, G, ps]
        new_l = l * corr + jnp.sum(probs, axis=-1)
        if quantized:
            # V scales fold into probs
            probs = probs * vs_scratch[slot][:, :page_size][None]
        # [Hkv, G, D] = [Hkv, G, ps] x [Hkv, ps, D]
        chunk_out = jax.lax.dot_general(
            probs, vt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        new_acc = acc * corr[..., None] + chunk_out
        return new_m, new_l, new_acc

    m0 = jnp.full((Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G), jnp.float32)
    acc0 = jnp.zeros((Hkv, G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[0] = out.reshape(Hq, D).astype(out_ref.dtype)


def _heads_major(tile_ref):
    """One context tile, ref ``[TP, ps, Hkv, D]`` -> value ``[Hkv, TP*ps, D]``
    f32, the batched-matmul operand layout.

    A bf16 pool of two kv heads never goes through a relayout: Mosaic keeps
    such a page as one 32-bit word per token and lane, head 0 in its low half
    and head 1 in its high half, and a bf16 is the top half of its f32, so a
    shift or a mask of the word IS the head's f32 row. Every other pool is
    cast and transposed in 32-bit, where the relayout is cheapest (with four
    or eight kv heads the words would have to be picked apart by head pair,
    which timed slower than the transpose: design record)."""
    TP, ps, Hkv, D = tile_ref.shape
    if tile_ref.dtype == jnp.bfloat16 and Hkv == 2:
        words = tile_ref.bitcast(jnp.uint32)[...].reshape(TP * ps, D)
        return jnp.stack([
            pltpu.bitcast(words << 16, jnp.float32),
            pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32),
        ])
    tile = tile_ref[...].astype(jnp.float32)
    return jnp.transpose(tile.reshape(TP * ps, Hkv, D), (1, 0, 2))


def _zero_tokens_from(tile_ref, first):
    """Zero tokens ``first`` and beyond of a tile ``[TP, ps, Hkv, D]`` in
    place, as whole 32-bit words where the heads fill them. A folded tile
    ``[TP, ps, Hkv*D]`` packs TOKENS into its words: it is zeroed as it is."""
    ps, Hkv = tile_ref.shape[1:3]
    per_word = 4 // tile_ref.dtype.itemsize
    words = len(tile_ref.shape) == 4 and Hkv % per_word == 0
    view = tile_ref.bitcast(jnp.uint32) if words else tile_ref
    token = (jax.lax.broadcasted_iota(jnp.int32, view.shape, 0) * ps
             + jax.lax.broadcasted_iota(jnp.int32, view.shape, 1))
    kept = jnp.where(token < first, view[...], jnp.zeros((), view.dtype))
    tile_ref[...] = kept if view is tile_ref else pltpu.bitcast(kept, tile_ref.dtype)


def _fold_query(q_ref, F: int, dtype):
    """(ownership mask [Hq, F] f32, zero-placed folded query [Hq, F] in
    ``dtype``) for a pool whose kv heads are folded into F lanes: head h owns
    the D lanes of its kv head, ``mask[h, f] = (f // D == h // G)``, and the
    query is Hkv copies of itself side by side with every lane a head does not
    own zeroed. Everything stays 2D: Mosaic rejects minor-dim reshapes."""
    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = F // D
    lane = jax.lax.broadcasted_iota(jnp.int32, (Hq, F), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (Hq, F), 0)
    own = (lane // D == head // (Hq // Hkv)).astype(jnp.float32)
    q = jnp.concatenate([q_ref[0].astype(jnp.float32)] * Hkv, axis=1)
    return own, (q * own).astype(dtype)


def _owned_lanes(acc, own, D: int):
    """Folded accumulator [Hq, F] -> [Hq, D]: zero the lanes a head does not
    own, then add the D-wide lane slices together (only the owned one is
    nonzero)."""
    acc = acc * own
    out = acc[:, 0:D]
    for j in range(1, acc.shape[1] // D):
        out = out + acc[:, j * D : (j + 1) * D]
    return out


def _kernel_lookahead(
    *refs,
    page_size: int,
    tile_pages: int,
    tiles_per_seq: int,
    lookahead: int,
    quantized: bool = False,
    window: int = 0,
):
    """Decode attention over TILES of pages, with CROSS-PROGRAM DMA
    pipelining.

    ``window`` > 0 (a sliding-window layer): the query sees the last
    ``window`` positions only, so the walk starts at the tile that holds
    position ``length - window`` (``first_tile``) and masks what lies before
    it in that tile; the page table's entries behind it are never read (the
    engine may have given those pages back). With no window every index below
    is what it was.

    A loop iteration handles one tile: ``tile_pages`` consecutive logical
    pages (128 context tokens at small page sizes). All of the tile's page
    DMAs are started together and waited together, and the online softmax
    takes one score product, one mask, one max / exp / sum over a full lane
    row and one ``probs x V`` product per tile. Only pages below the
    sequence's page count are fetched; what the rest of a tile's scratch
    holds (stale VMEM) is masked out of the scores and zeroed in V, so
    zero-weight garbage cannot reach the accumulator.

    The grid is over the batch's LIVE rows (program i serves row
    ``order_ref[i]``; the module docstring's "Live rows"). Grid programs
    execute serially on the core, and scratch PERSISTS across them; the page
    table is scalar-prefetched, so program i issues program i+1's first
    ``lookahead`` tiles into the opposite parity's window while it computes on
    its own (prefetched by i-1). Tiles >= lookahead (long contexts) stream
    through the in-program double buffer: tile t+1 in flight while tile t is
    merged.

    The walk never looks inside a page, so it serves pools of either rank;
    the merge follows the rank. Pools ``[P, ps, Hkv, D]`` (head_dim a multiple
    of 128): per-kv-head batched products on ``_heads_major`` f32 operands.
    FOLDED pools ``[P, ps, Hkv*D]`` (head_dim under 128, or one kv head a
    shard: Mosaic cannot DMA-slice a pool whose minor dim is under the
    128-lane tile, so the kv heads are folded into the lanes): the per-head
    math never unfolds. The query is placed into a zero-padded folded layout
    (``_fold_query``: each q head occupies its kv head's D lanes, zeros
    elsewhere), so one ``[Hq, F] x [S, F]`` product a tile yields exact
    per-head scores (the zero slices kill every cross-head term), and
    ``probs x V`` gives ``[Hq, F]`` with each head's true output in its kv
    head's lanes, picked out once after the walk (``_owned_lanes``). Folded
    operands go to the MXU as the pool holds them (bf16; int8 as f32, the
    per-row scale being head-independent), with f32 accumulation.

    refs: page_tables + lengths + order + runs [B * tiles_per_seq] (scalar
    prefetch; ``tile_runs``) | q, k/v pools [, k/v
    scale rows [B*tiles_per_seq, 1, Ws], one per tile, see
    gather_scale_rows] | out | k_pre, v_pre [2, W, TP, ps, Hkv, D] [, scale
    windows [2, W, 1, Ws]], k_tail, v_tail [2, TP, ps, Hkv, D] [, scale tails
    [2, 1, Ws]], sems_pre [2, W, 2|4], sems_tail [2, 2|4]; a folded pool's
    scratch is [.., TP, ps, Hkv*D]. The copies of one tile and pool share a
    semaphore: each wait takes one page's bytes off it, a run's one wait the
    tile's."""
    page_tables_ref, lengths_ref, order_ref, runs_ref, q_ref, *refs = refs
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm,
         out_ref, k_pre, v_pre, ks_pre, vs_pre, k_tail, v_tail, ks_tail,
         vs_tail, sems_pre, sems_tail) = refs
        pre_scales = [(ks_hbm, ks_pre), (vs_hbm, vs_pre)]
        tail_scales = [(ks_hbm, ks_tail), (vs_hbm, vs_tail)]
    else:
        (k_hbm, v_hbm,
         out_ref, k_pre, v_pre, k_tail, v_tail, sems_pre, sems_tail) = refs
        pre_scales = tail_scales = []
    pre_pools = [(k_hbm, k_pre), (v_hbm, v_pre)]
    tail_pools = [(k_hbm, k_tail), (v_hbm, v_tail)]

    i = pl.program_id(0)  # the grid is over the live rows: every program serves one
    nb = pl.num_programs(0)
    b = order_ref[i]  # the batch row this program serves
    par = jax.lax.rem(i, 2)
    W, TP = lookahead, tile_pages
    S = TP * page_size  # context tokens per tile
    length = lengths_ref[b]

    def pages_of(seq_idx):
        return jnp.maximum(1, pl.cdiv(lengths_ref[seq_idx], page_size))

    def first_tile(seq_idx):
        if not window:
            return 0
        return jnp.maximum(0, lengths_ref[seq_idx] - window) // S

    n_pages = pages_of(b)
    n_tiles = pl.cdiv(n_pages, TP)
    t0 = first_tile(b)

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    folded = len(k_hbm.shape) == 3
    if folded:  # no caller gives a folded pool a window
        # int8 pages go to the MXU as f32 (operand dtypes must match)
        operand = jnp.float32 if quantized else k_hbm.dtype
        own, q = _fold_query(q_ref, k_hbm.shape[2], operand)
        heads, width = (Hq,), k_hbm.shape[2]
        qk_dims, pv_dims = (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))

        def rows(tile_ref):  # [TP, ps, F] -> [S, F]: whole pages, lanes untouched
            return tile_ref[...].astype(operand).reshape(S, width)
    else:
        Hkv = k_hbm.shape[2]
        G = Hq // Hkv
        q = q_ref[0].astype(jnp.float32).reshape(Hkv, G, D)
        heads, width, rows = (Hkv, G), D, _heads_major
        qk_dims, pv_dims = (((2,), (2,)), ((0,), (0,))), (((2,), (1,)), ((0,), (0,)))
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    lead = (None,) * (len(heads) - 1)  # a scale row [1, S] against the scores

    def tile_dmas(op, seq_idx, t, npg, pools, scales, at, sems):
        """Start or wait (``op``) every copy of tile t of ``seq_idx``: for
        int8 pools the tile's scale rows, and its pages. A tile that is a RUN
        (``runs_ref``: its TP table entries are first, first + 1, ...) is one
        slab of the pool and moves as ONE copy a pool, whole, whatever the
        sequence has written of it; any other tile moves a page a copy, the
        pages below ``npg``. ``at(scratch)`` is the tile's slot in a scratch
        buffer."""

        def page(p, _):
            for c, (hbm, scratch) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    hbm.at[page_tables_ref[seq_idx, t * TP + p]],
                    at(scratch).at[p], sems.at[c],
                )
                getattr(copy, op)()
            return 0

        left = npg - t * TP  # the tile's pages that the sequence holds
        by_page = jnp.minimum(TP, left)
        if TP > 1:  # a tile of one page has no run to find
            whole = runs_ref[seq_idx * tiles_per_seq + t] != 0
            first = page_tables_ref[seq_idx, t * TP]
            if op == "wait":
                # a full tile's page copies have put a tile's bytes on the
                # semaphore too: one wait takes them off as it does a run's
                whole, first = whole | (left >= TP), 0

            @pl.when(whole)
            def _():
                for c, (hbm, scratch) in enumerate(pools):
                    copy = pltpu.make_async_copy(
                        hbm.at[pl.ds(first, TP)], at(scratch), sems.at[c]
                    )
                    getattr(copy, op)()

            by_page = jnp.where(whole, 0, by_page)
        jax.lax.fori_loop(0, by_page, page, 0)
        for c, (hbm, scratch) in enumerate(scales):
            copy = pltpu.make_async_copy(
                hbm.at[seq_idx * tiles_per_seq + t], at(scratch), sems.at[2 + c]
            )
            getattr(copy, op)()

    def pre_dmas(op, parity, j, seq_idx, npg, base):
        tile_dmas(op, seq_idx, base + j, npg, pre_pools, pre_scales,
                  lambda scratch: scratch.at[parity, j], sems_pre.at[parity, j])

    def tail_dmas(op, slot, t):
        tile_dmas(op, b, t, n_pages, tail_pools, tail_scales,
                  lambda scratch: scratch.at[slot], sems_tail.at[slot])

    def issue_pre(seq_idx, parity):
        npg = pages_of(seq_idx)
        base = first_tile(seq_idx)

        def issue(j, _):
            pre_dmas("start", parity, j, seq_idx, npg, base)
            return 0

        jax.lax.fori_loop(0, jnp.minimum(W, pl.cdiv(npg, TP) - base), issue, 0)

    # program 0 has no predecessor: prefetch its own window
    @pl.when(i == 0)
    def _():
        issue_pre(b, 0)

    # prefetch the NEXT live row's window while this one computes
    @pl.when(i + 1 < nb)
    def _():
        issue_pre(order_ref[i + 1], 1 - par)

    # long-context tail: warm the in-program double buffer for tile W
    @pl.when(t0 + W < n_tiles)
    def _():
        tail_dmas("start", jax.lax.rem(t0 + W, 2) if window else W % 2, t0 + W)

    col = jax.lax.broadcasted_iota(jnp.int32, (1,) * len(heads) + (S,), len(heads))

    def merge(carry, t, k_tile, v_tile, k_s, v_s):
        m, l, acc = carry

        if TP > 1:
            # a sequence's last tile holds pages that were never fetched:
            # stale VMEM. Their weights are zero, and zero times a stale NaN
            # would still poison acc. (A tile of one page is fetched whole.)
            @pl.when((t + 1) * S > length)
            def _():
                _zero_tokens_from(v_tile, length - t * S)

        kt = rows(k_tile)  # [Hkv, S, D] f32; folded [S, F]
        vt = rows(v_tile)
        # [Hkv, G, S] = [Hkv, G, D] x [Hkv, S, D]; folded [Hq, S] = [Hq, F] x [S, F]
        scores = jax.lax.dot_general(
            q, kt, qk_dims, preferred_element_type=jnp.float32
        ) * scale
        if quantized:
            scores = scores * k_s[:, :S][lead]  # [1, 1, S] per-row K scales
        valid = t * S + col < length
        if window:
            valid &= t * S + col >= length - window
        scores = jnp.where(valid, scores, _NEG_INF)
        chunk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[..., None])
        new_l = l * corr + jnp.sum(probs, axis=-1)
        if quantized:
            # V scales fold into probs (masked: a scale row's unused lanes
            # belong to whatever page the table's padding names)
            probs = jnp.where(valid, probs * v_s[:, :S][lead], 0.0)
        # [Hkv, G, D] = [Hkv, G, S] x [Hkv, S, D]; folded [Hq, F] = [Hq, S] x [S, F]
        chunk_out = jax.lax.dot_general(
            probs.astype(vt.dtype), vt, pv_dims, preferred_element_type=jnp.float32
        )
        return new_m, new_l, acc * corr[..., None] + chunk_out

    def pre_body(j, carry):
        pre_dmas("wait", par, j, b, n_pages, t0)
        return merge(
            carry, t0 + j, k_pre.at[par, j], v_pre.at[par, j],
            ks_pre[par, j] if quantized else None,
            vs_pre[par, j] if quantized else None,
        )

    def tail_body(t, carry):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            tail_dmas("start", 1 - slot, t + 1)

        tail_dmas("wait", slot, t)
        return merge(
            carry, t, k_tail.at[slot], v_tail.at[slot],
            ks_tail[slot] if quantized else None,
            vs_tail[slot] if quantized else None,
        )

    m0 = jnp.full(heads, _NEG_INF, jnp.float32)
    l0 = jnp.zeros(heads, jnp.float32)
    acc0 = jnp.zeros((*heads, width), jnp.float32)
    carry = jax.lax.fori_loop(0, jnp.minimum(W, n_tiles - t0), pre_body, (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(t0 + W, n_tiles, tail_body, carry)

    if folded:
        acc = _owned_lanes(acc, own, D)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[0] = out.reshape(Hq, D).astype(out_ref.dtype)


#: scratch budget for the lookahead kernel (VMEM is ~16 MB/core scoped)
_LOOKAHEAD_SCRATCH_BYTES = 6 * 1024 * 1024
#: context tokens per tile: one full 128-lane row of scores
_TILE_TOKENS = 128
#: tiles a program prefetches for its successor: (where a tile is several
#: pages, where it is one page). Of 1, 2, 4, 8 and 16 tiles of 8 pages the
#: small windows timed best, within 7%; a tile of one page keeps the 4 it had
#: as the page-at-a-time kernel, where 4 timed 2% better than 2 (design record)
_LOOKAHEAD_MAX_TILES = (2, 4)


def decode_tile_pages(page_size: int, num_kv_heads: int, head_dim: int,
                      itemsize: int = 2) -> int:
    """Pages per tile TP: ``_TILE_TOKENS`` of context (1 at page sizes of 128
    and more), halved while the four tiles the kernel cannot do without (one
    window tile per parity and the two tail slots) overrun the budget."""
    page_bytes = 2 * page_size * num_kv_heads * head_dim * itemsize  # k + v
    tp = max(1, _TILE_TOKENS // page_size)
    while tp > 1 and 4 * tp * page_bytes > _LOOKAHEAD_SCRATCH_BYTES:
        tp //= 2
    return tp


def lookahead_window(page_size: int, num_kv_heads: int, head_dim: int,
                     itemsize: int = 2) -> int:
    """Prefetch window W in TILES that fits the scratch budget (0 = kernel
    not applicable). Scratch = 2 parities x W tiles x (k+v) + the 2-slot
    tail; int8 scale rows are noise."""
    tp = decode_tile_pages(page_size, num_kv_heads, head_dim, itemsize)
    tile_bytes = 2 * tp * page_size * num_kv_heads * head_dim * itemsize
    budget = _LOOKAHEAD_SCRATCH_BYTES - 2 * tile_bytes  # tail buffers
    return max(0, min(_LOOKAHEAD_MAX_TILES[tp == 1], budget // (2 * tile_bytes)))


#: the window-layer calls' name on the device's operation line (a reader
#: tells them from the full layers' by it)
SLIDING_DECODE_NAME = "paged_decode_attention_sliding_window"


def tile_runs(page_tables: jnp.ndarray, tile_pages: int) -> jnp.ndarray:
    """``[B, max_pages]`` -> ``[B * tiles_per_seq]`` int32: 1 where the
    ``tile_pages`` table entries of a tile are first, first + 1, ... (one slab
    of the pool, which the tiled walk fetches as one copy), else 0. Adding a
    layer's offset to every entry changes nothing, so a model makes this once
    a decode step for all its layers (`ops.attention.decode_tile_runs`). The
    null page that pads a table never continues a run, and a tile of one page
    has none to find."""
    B, width = page_tables.shape
    T = pl.cdiv(width, tile_pages)
    if tile_pages == 1:
        return jnp.zeros((B * T,), jnp.int32)
    tables = jnp.pad(page_tables.astype(jnp.int32), ((0, 0), (0, T * tile_pages - width)))
    tiles = tables.reshape(B, T, tile_pages)
    run = jnp.all(tiles[..., 1:] - tiles[..., :-1] == 1, axis=-1)
    return run.reshape(B * T).astype(jnp.int32)


def _tiled_decode(q, k_pages, v_pages, page_tables, positions, live, runs, TP: int, W: int, *,
                  interpret: bool, window: int = 0, name=None):
    """``_kernel_lookahead`` over pools of either rank, at tiles of ``TP``
    pages and a window of ``W`` tiles. ``live`` (`ops.live_rows.LiveRows`, or
    None: every row is live) names the rows the grid serves: it has
    ``live.count`` programs (a grid bound read on the device), program i
    walks row ``live.order[i]``, and a row that is not live costs nothing and
    reads zero. ``runs`` (`tile_runs` of these tables at ``TP``, or None: made
    here) says which tiles are one slab of the pool."""
    B, Hq, D = q.shape
    if live is None:
        live = every_row(B)
    tiles_per_seq = pl.cdiv(page_tables.shape[1], TP)
    if runs is None:
        runs = tile_runs(page_tables, TP)
    if runs.shape != (B * tiles_per_seq,):
        raise ValueError(f"tile runs {runs.shape} are not of {B} rows of {tiles_per_seq} tiles of {TP}")
    kq, vq, ks, vs, quantized = _decode_unpack_pools(k_pages, v_pages, page_tables, TP)
    page = kq.shape[1:]  # [ps, Hkv, D], or folded [ps, Hkv*D]
    lengths = positions.astype(jnp.int32) + 1

    def tile_scratch(*lead):
        shapes = [pltpu.VMEM((*lead, TP, *page), kq.dtype),
                  pltpu.VMEM((*lead, TP, *page), vq.dtype)]
        if quantized:
            shapes += [pltpu.VMEM((*lead, 1, ks.shape[-1]), jnp.float32),
                       pltpu.VMEM((*lead, 1, vs.shape[-1]), jnp.float32)]
        return shapes

    def row_block(i, tables, lengths, order, runs):
        return order[i], 0, 0

    C = 4 if quantized else 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(live.count[0],),
        in_specs=[
            pl.BlockSpec((1, Hq, D), row_block),
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in range(C)],
        ],
        out_specs=pl.BlockSpec((1, Hq, D), row_block),
        scratch_shapes=[
            *tile_scratch(2, W),
            *tile_scratch(2),
            pltpu.SemaphoreType.DMA((2, W, C)),
            pltpu.SemaphoreType.DMA((2, C)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _kernel_lookahead, page_size=page[0], tile_pages=TP,
            tiles_per_seq=tiles_per_seq, lookahead=W,
            quantized=quantized, window=window,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        # cross-program scratch persistence (program i prefetches i+1's tiles
        # into the opposite parity's slots) requires the grid to run SERIALLY
        # — pin it rather than relying on the implicit default
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )
    args = (kq, vq, ks, vs) if quantized else (kq, vq)
    out = kernel(page_tables.astype(jnp.int32), lengths, live.order, runs, q, *args)
    # no program wrote a dead row's block: it is memory as it was found
    return zero_dead_rows(out, live)


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_decode_attention_pallas_lookahead(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages,  # [P, ps, Hkv, D] plain or QuantizedPages
    v_pages,
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    live: LiveRows | None = None,  # the rows to serve (None: every row)
    runs: jnp.ndarray | None = None,  # tile_runs of the tables (None: made here)
    interpret: bool = False,
    window: int = 0,  # sliding window in tokens (0: the whole context)
) -> jnp.ndarray:
    B, Hq, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    itemsize = k_pages.dtype.itemsize
    W = lookahead_window(ps, Hkv, D, itemsize)
    if W < 1:
        if window:
            raise ValueError("the page-at-a-time decode kernel takes no window")
        # the page-at-a-time kernel walks every row
        return zero_dead_rows(paged_decode_attention_pallas(
            q, k_pages, v_pages, page_tables, positions, interpret=interpret
        ), live)
    return _tiled_decode(
        q, k_pages, v_pages, page_tables, positions, live, runs,
        decode_tile_pages(ps, Hkv, D, itemsize), W, interpret=interpret,
        window=window, name=SLIDING_DECODE_NAME if window else None,
    )


#: the folded calls' name on the device's operation line (two readers of the
#: benchmark find the kernel by it)
FOLDED_DECODE_NAME = "paged_decode_attention_pallas_folded"


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas_folded(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages,  # [P, ps, Hkv*D] folded (plain or QuantizedPages), or [P, ps, Hkv, D]
    v_pages,
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    live: LiveRows | None = None,  # the rows to serve (None: every row)
    runs: jnp.ndarray | None = None,  # tile_runs of the tables (None: made here)
    interpret: bool = False,
) -> jnp.ndarray:
    """Decode attention for head_dim < 128 (TinyLlama, Qwen2-small, LFM2: 64)
    and for one kv head a tensor-parallel shard: the tiled walk of
    ``_kernel_lookahead`` over pools whose kv heads are folded into the lane
    dim, with its folded merge."""
    D = q.shape[-1]
    if k_pages.ndim == 4:
        # direct-call convenience (tests): fold here. Serving passes pools
        # ALREADY folded (LlamaConfig.kv_folded) — reshaping a donated,
        # scatter-updated pool at attention time copies the whole pool.
        P, ps, Hkv, _ = k_pages.shape
        if isinstance(k_pages, QuantizedPages):
            k_pages = QuantizedPages(k_pages.q.reshape(P, ps, Hkv * D), k_pages.s)
            v_pages = QuantizedPages(v_pages.q.reshape(P, ps, Hkv * D), v_pages.s)
        else:
            k_pages = k_pages.reshape(P, ps, Hkv * D)
            v_pages = v_pages.reshape(P, ps, Hkv * D)
    # the folded row of Hkv * D lanes is one head to the walk
    geometry = (k_pages.shape[1], 1, k_pages.shape[2], k_pages.dtype.itemsize)
    W = lookahead_window(*geometry)
    if W < 1:  # a page of 128 tokens by 4096 lanes: no kernel here walks such a pool
        raise ValueError(f"no tile of a folded pool {k_pages.shape} fits the decode kernel's VMEM")
    return _tiled_decode(q, k_pages, v_pages, page_tables, positions, live, runs,
                         decode_tile_pages(*geometry), W,
                         interpret=interpret, name=FOLDED_DECODE_NAME)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages,  # [P, ps, Hkv, D] plain or QuantizedPages
    v_pages,
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    kq, vq, ks, vs, quantized = _decode_unpack_pools(k_pages, v_pages, page_tables)
    P, ps, Hkv, _ = kq.shape
    max_pages = page_tables.shape[1]
    lengths = positions.astype(jnp.int32) + 1

    scratch_shapes = [
        pltpu.VMEM((2, ps, Hkv, D), kq.dtype),
        pltpu.VMEM((2, ps, Hkv, D), vq.dtype),
    ]
    if quantized:
        scratch_shapes += [
            pltpu.VMEM((2, 1, ks.shape[-1]), jnp.float32),
            pltpu.VMEM((2, 1, vs.shape[-1]), jnp.float32),
        ]
    C = 4 if quantized else 2
    scratch_shapes.append(pltpu.SemaphoreType.DMA((2, C)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            # k/v pages (and int8 scale planes) stay in HBM
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in range(C)],
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch_shapes,
    )
    kernel = pl.pallas_call(
        functools.partial(
            _kernel, page_size=ps, max_pages=max_pages, quantized=quantized
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    args = (kq, vq, ks, vs) if quantized else (kq, vq)
    return kernel(page_tables.astype(jnp.int32), lengths, q, *args)
