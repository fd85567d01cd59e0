"""Pallas TPU kernels: chunked-prefill flash attention over the paged KV pool.

A prefill chunk's queries attend causally over the sequence's paged context
(which already contains the chunk's own rows — the model scatters before
attending). The XLA reference path (ops/attention.py paged_prefill_attention)
materializes the whole gathered context ``[max_pages * ps, Hkv, D]`` plus a
``[Hq, T, S]`` score tensor per layer; these kernels stream context pages
HBM -> VMEM in multi-page tiles with double buffering and keep the online
softmax in VMEM, so HBM traffic is one pass over the needed pages and no
score/gather materialization at all. Causality additionally bounds work per
query block: block b only loops over tiles up to its last query position.

Two scheduling variants share the math:

  - ``_kernel`` (basic): per-program double buffer only. Every grid program
    (query block) pays the first-tile DMA latency at its boundary before any
    compute can start.
  - ``_kernel_lookahead``: the decode ``_kernel_lookahead`` insight ported to
    prefill. Grid programs run serially on the core and scratch PERSISTS
    across them; the page table and positions are scalar-prefetched, so query
    block b issues block b+1's first ``lookahead`` context-tile DMAs into the
    opposite parity's window while it runs its own online softmax. Tiles >=
    lookahead stream through the classic in-program double buffer.

``paged_prefill_attention_pallas`` chooses by shape, with no setting: the
context tile's length from the page table's width (``prefill_tile_pages``),
then the lookahead variant wherever a window of two tiles or more fits its
budget (``prefill_lookahead_window``) and the basic one elsewhere.

Design record (PR 38; every time below is my chip run on a TPU v5e,
tools/profile_prefill_attention.py: ONE chunk's call, 24 chained calls in one
jit, best of 5, bf16 pools of page 16 under a shuffled page table; the static
counts are operations a vreg in one iteration of the tile loop, from Mosaic's
own listing for a described v5e, by the recipe in that tool's docstring).

  command-a-plus-ep8: Hq 128, Hkv 8, T 512, block_q 32 (512 rows a kv head;
  a tile of 128 tokens is two products of 268 MFLOP, 1.36 us of the MXU)

  us a call                          window 4096,   full layer,     full layer,
                                     start 8192     context 16384   context 512
  null (the DMA stream alone), 128       434           1472              91
  tile 128, window 4 (until PR 38)      3182          11586             400
  tile 128, no window                   3104          11521             357
  tile 256, window 2                    2188           7582             354
  tile 256, no window                   2112           7515             306
  tile 512, window 1                    1890           6158             364
  tile 512, no window (the rule)        1809           6041             322
  tile 1024, no window                  1870           5648             502

  qwen2.5-3b: Hq 16, Hkv 2, T 512 (256 at context 256), block_q 128 (1024
  rows a kv head), full layer

  us a call, by context       256    512    1024   2048   4096   8192
  null, 128                  26.9   32.9   37.5   48.0   69.7    112
  tile 128, window 4         45.8   75.1    118    201    373    709
  tile 128, no window        43.1   63.6    107    192    361    701
  tile 256, window 2         48.3   73.8    113    186    333    629
  tile 512, window 1         58.7   79.8    114    181    315    577
  tile 512, no window        51.9   71.5    104    172    301    566
  tile 1024, no window       64.7   99.5   98.5    157    281    519

  - The cost is per TILE, and it is vector work, not the MXU and not the DMA
    stream: at 128 / 8 heads a tile of 128 tokens took 6.0 us (3182 us over
    528 tiles) where the MXU needs 1.36 and the null kernel 0.8. One
    iteration is 9263 operations for 16 ``tpu.matmul``: the scores are 512
    vregs, and the running max, sum and correction, kept one row a sublane,
    are 512 vregs too, so ``maximumf``, ``subf``, ``exp``, ``mulf``, ``addf``
    on them are 2560 operations for 4096 numbers, and each score vreg pays
    two cross-lane reductions (``all_reduce`` 1024). A longer tile's vregs
    are combined elementwise before the one cross-lane reduce (1024 an
    iteration at every length): 9263 / 14865 / 26069 operations at 128 / 256
    / 512 tokens are 9263 / 7433 / 6517 per 128 tokens (qwen2.5-3b's
    geometry 5827 / 4923 / 4470). The chip gains more than the count: 0.57 of
    the time on the window layer, 0.52 on the full one at 16k.
  - The rule (``prefill_tile_pages``): 512 tokens under a page table of more
    than 2048 tokens, 128 under a narrower one. The width is the sequence's
    depth bucket (EngineConfig.table_buckets; the engine allocates a
    prompt's pages at admission, so a long prompt's FIRST chunks carry the
    wide table too: 512 wins there as well, 322 against 400 us at context
    512). A table of 2048 tokens keeps 128: at 1024 rows a kv head and a
    context of 256 the long tile is 13% slower (28% with a window of one),
    and 128 pages is every prompt of three of the four benchmark cells, whose
    programs stay what they were. From a context of 1024 on the long
    tile wins on that geometry too (-12% at 1024, -20% at 8192).
  - The window re-budget (``prefill_lookahead_window``): the window is
    counted in pages, four tiles of 128 tokens, because ``issue_pre``
    unrolls a DMA issue a page and pool statically; that leaves a 512-token
    tile a window of one, and a window of one LOST to none at every shape
    (1890 against 1809, 364 against 322, 577 against 566 us): the lookahead
    kernel carries the loop body twice (window and tail), Mosaic compiled it
    in 15.4 s against 6.7 s for the basic kernel (9.1-11.6 s for the kernel
    of 128 with its window of four), and behind a tile of 512 tokens the first
    fetch a program waits for is small beside its work. So the long tile
    runs ``_kernel``: faster, and a shorter compile than before PR 38.
  - Not 1024: 7% faster on the full layer at 16k (5648 against 6041), 3%
    slower on the window layers, 56% slower at a context of 512, and 16 MiB
    each of scores and probabilities at 128 heads.
  - The window loses at the SHORT tile too (the rows "no window" at 128 and
    256: 2% at depth, 10-15% at contexts of 512-1024, and Mosaic compiles
    the basic kernel in 1.6-4.3 s against 4.4-11.6). PR 38 left the tile of
    128 its window of four so that the programs of a 2048-token table stay
    what they were; taking it away is a change to three benchmark cells'
    prefill programs and wants its own measurement end to end.
  - Operands stay f32, as in decode (paged_attention.py). That is not what
    the kernel pays for: jax 0.9.0's Pallas lowering hands Mosaic no
    precision for a product at default precision, so the MXU takes ONE bf16
    pass over the f32 vregs (PR 26 read rms 1.5e-4 against float64 off the
    chip). Operands cast to bf16 after the 32-bit relayout ADD some 4600
    pack / unpack / bitcast operations a tile (13724 against 9116 in ISSUE
    38's listing), which PR 26 measured on the chip for decode (288-305 us
    against 248).
  - No fast path for interior tiles (wholly inside the causal bound and the
    window, where the mask selects everything: 7 of 9 tiles of a window
    layer): as a ``lax.cond`` between a masked and an unmasked merge it ran
    1.6 times SLOWER (3050 against 1890 us on the window layer, 898 against
    374 at context 512, beside the window of one: the carry of 1536 vregs crosses the branch) and
    compiled in 30 s against 15: ISSUE 38 asked for 8% faster and under 20%
    more compile, so it is out.
  - A core has 2 KiB of DMA semaphores (512). Scratch takes one a page and
    pool (``_tile_scratch``); the scale rows of an int8 pool ride one more
    slot a channel, not two more channels (which the described chip refused
    at 32 pages a tile). A tile of 1024 tokens with a window does not fit.
  - Next, by the listing at 512: the K/V relayout [tokens, Hkv, D] -> [Hkv,
    tokens, D] is 1280 ``sublane_shuffle`` of the 6517 operations per 128
    tokens, repeated by every query block of 32 rows over the same context
    (more rows a program would amortize it, and ``prefill_block_q`` says what
    that costs to compile); the mask is 900.

Int8 KV (quant/kv.py QuantizedPages): the pools arrive as int8 plus a
per-row f32 scale plane. The scale rows a chunk needs are gathered by XLA
into one lane-aligned [1, S] row per context TILE before the kernel runs
(paged_attention.gather_scale_rows — Mosaic refuses to DMA-slice the raw
[P, ps] plane when ps < 128) and ride one tiny DMA per tile next to the page
DMAs (HBM reads stay int8 — that is the point: the context stream halves).
Dequantization happens on the score/prob TILES in VMEM: ``scores *=
k_scale_row`` and ``probs *= v_scale_row`` are exact per-column algebra (see
quant/kv.py) and touch only lane-axis broadcasts.

Contract: q [T, Hq, D] (bucket-padded chunk), k/v pages [P, ps, Hkv, D],
page_table [max_pages] (this sequence's logical pages, trash page 0 padding),
positions [T] absolute and **unit-stride** (positions[i] = positions[0] + i —
the mask derives row positions from positions[block_start] + row offset;
engine chunks always satisfy this; the XLA reference only needs monotone).
GQA folds as [Hkv, G*Bq, D] batched matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.paged_attention import gather_scale_rows
from dynamo_tpu.quant.kv import QuantizedPages

_NEG_INF = -1e30

#: scoped VMEM the prefill kernels ask for: half of the v5e core's 128 MiB.
#: Mosaic's default scoped limit is 16 MiB, and the compiler's own stack for
#: the f32 query/accumulator/score tiles at serving widths does not fit it.
#: Measured by compiling for a described v5e (tests/test_tpu_compile.py): the
#: stack grows with the q heads per device, about 0.7 MiB per head at
#: block_q 128 — 19 MiB at 16q/8kv and 28q/4kv, 33 MiB at 32q/8kv, 50 MiB at
#: 64q/8kv with the lookahead window, 17 MiB for MLA at 16 heads and a
#: 640-wide latent — and it also grows with the limit it is given, so the
#: window arithmetic below, which budgets only the kernels' own scratch,
#: cannot size it. A geometry past this limit fails to compile at engine
#: start, loudly; there is no fallback.
PREFILL_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


#: the window-layer calls' name on the device's operation line
SLIDING_PREFILL_NAME = "paged_prefill_attention_sliding_window"


def prefill_block_q(num_q_heads: int) -> int:
    """Query rows per grid program: 128 up to 32 query heads a device, halved
    while rows x heads pass 32 x 128. The kernel holds the whole [block_q, Hq,
    D] query block, its accumulator and a score tile in f32, and both the
    stack the compiler needs and the time Mosaic takes to compile the body
    grow faster than rows x heads: 64 heads at 128 rows took 50 of the 64 MiB
    of PREFILL_VMEM_LIMIT_BYTES, and at 128 heads a described v5e compiled 64
    rows in 32 s, 32 rows in 7 s, 16 rows in 3 s (PR 37; a server compiles
    the kernel in every prefill program it warms). 128 heads get 32 rows: 512
    rows a kv head for the MXU at 16 query heads a group. With the context
    tile of 512 tokens a wide page table gets (PR 38, on the chip) 32 rows
    compiled in 7.0 s as the basic kernel and 15.4 s with a cross-program
    window, against 9.2 s for the tile of 128 with its window; 16 query heads
    at 128 rows 3.3, 6.0 and 3.4 s."""
    block_q = 128
    while block_q > 8 and num_q_heads * block_q > 32 * 128:
        block_q //= 2
    return block_q


#: what the folded kernel's [R, F] float32 buffers may take of scoped VMEM
_FOLDED_WORKING_SET_BYTES = 12 * 1024 * 1024


def folded_prefill_block_q(num_q_heads: int, folded_lanes: int) -> int:
    """Query rows per grid program of the folded kernel: 64, halved while its
    working set does not fit. The kernel holds about five float32 buffers of
    [R, F] (R = rows x query heads, F = kv heads x head_dim folded lanes: the
    zero-placed queries, their mask, the accumulator and a tile's product).
    TinyLlama's 32 heads over 256 lanes keep 64 rows (10.5 MB); LFM2's 32 heads
    over 512 lanes (8 kv heads of 64) get 32. A shape that does not fit at 8
    rows is refused by the dispatcher (`folded_prefill_fits`)."""
    block_q = 64
    while block_q > 8 and not folded_prefill_fits(block_q, num_q_heads, folded_lanes):
        block_q //= 2
    return block_q


def folded_prefill_fits(block_q: int, num_q_heads: int, folded_lanes: int) -> bool:
    return block_q * num_q_heads * folded_lanes * 4 * 5 <= _FOLDED_WORKING_SET_BYTES


#: a table of more context tokens than this is walked in long tiles
_SHORT_TABLE_TOKENS = 2048
_LONG_TILE_TOKENS = 512


def prefill_tile_pages(page_size: int, max_pages: int = 0) -> int:
    """Pages per context tile of the head_dim-128 flash kernels, from what a
    call can see: 128 tokens (one page where a page holds more) under a page
    table of up to 2048 tokens, 512 tokens under a wider one (the design
    record in the module docstring has the times). The table's width is the
    depth bucket of the sequence (EngineConfig.table_buckets), so a deep
    context pays the per-tile vector work a quarter as often and a short one
    keeps the tile that wastes least beyond its causal bound. With no width
    given (the folded kernel): 128 tokens."""
    short = max(1, 128 // page_size)
    if max_pages * page_size > _SHORT_TABLE_TOKENS:
        return max(short, _LONG_TILE_TOKENS // page_size)
    return short


def _unpack_pools(k_pages, v_pages, page_table, tile_pages: int):
    """(k, v, k_scale tiles | None, v_scale tiles | None) from plain or
    QuantizedPages pools; a context tile is ``tile_pages`` pages. Scale tiles
    are ``gather_scale_rows`` over the page table (edge-padded to whole
    tiles: the kernels clamp their page indices the same way and mask what
    lies beyond the table): row t is context tile t's [1, S] scale row."""
    if not isinstance(k_pages, QuantizedPages):
        return k_pages, v_pages, None, None
    table = jnp.pad(page_table, (0, -page_table.shape[0] % tile_pages), mode="edge")
    return (
        k_pages.q, v_pages.q,
        gather_scale_rows(k_pages.s, table, tile_pages),
        gather_scale_rows(v_pages.s, table, tile_pages),
    )


def _tile_dma_helpers(page_table_ref, page_pairs, scale_pairs, sems,
                      tile_pages: int, max_pages: int):
    """Shared double-buffered context-tile DMA scaffolding for the prefill
    kernels: ``page_pairs`` is [(hbm_pool, scratch)] for k/v, each scratch
    indexed ``[buf, p]``; ``scale_pairs`` (int8 pools only) is [(scale tiles,
    scratch)], one [1, S] row per tile, scratch indexed ``[buf]``. ``sems``
    is ``[2, 2, TP (+ 1)]``: channel c (k, v) slot p for page p, slot TP for
    the channel's scale row. Returns (start, wait), each taking (buf,
    tile). The final tile clamps page indices to max_pages - 1 (aliased
    content is masked by the callers' ctx-bound check)."""

    def tile_dma(buf, tile):
        copies = []
        for p in range(tile_pages):
            idx = jnp.minimum(tile * tile_pages + p, max_pages - 1)
            for c, (hbm, scratch) in enumerate(page_pairs):
                copies.append(
                    pltpu.make_async_copy(
                        hbm.at[page_table_ref[idx]], scratch.at[buf, p],
                        sems.at[buf, c, p],
                    )
                )
        for c, (hbm, scratch) in enumerate(scale_pairs):
            copies.append(
                pltpu.make_async_copy(
                    hbm.at[tile], scratch.at[buf], sems.at[buf, c, tile_pages]
                )
            )
        return copies

    def start(buf, tile):
        for cp in tile_dma(buf, tile):
            cp.start()

    def wait(buf, tile):
        for cp in tile_dma(buf, tile):
            cp.wait()

    return start, wait


def _flash_merge(carry, q, kt, vt, scores_extra, mask, ks_row, vs_row):
    """One online-softmax merge step shared by every non-folded prefill
    kernel. kt/vt are [Hkv, S, D] f32 context tiles; ks_row/vs_row are
    [1, S] f32 scale rows (None on bf16 pools); mask [G*Bq, S]."""
    m, l, acc = carry
    scores = jax.lax.dot_general(
        q, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * scores_extra
    if ks_row is not None:
        scores = scores * ks_row[None]  # [1, 1, S] column scales (exact)
    scores = jnp.where(mask[None], scores, _NEG_INF)
    chunk_max = jnp.max(scores, axis=-1)
    new_m = jnp.maximum(m, chunk_max)
    corr = jnp.exp(m - new_m)
    probs = jnp.exp(scores - new_m[..., None])
    new_l = l * corr + jnp.sum(probs, axis=-1)
    if vs_row is not None:
        probs = probs * vs_row[None]  # scale probs, not V: stays one multiply
    chunk_out = jax.lax.dot_general(
        probs, vt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )
    return new_m, new_l, acc * corr[..., None] + chunk_out


def _kernel(
    *refs,
    page_size: int,
    max_pages: int,
    tile_pages: int,
    block_q: int,
    quantized: bool,
    window: int = 0,
):
    """Basic (in-program double buffer) flash prefill; see module docstring.
    ``window`` > 0: a row at position p sees keys in (p - window, p], and the
    walk starts at the tile that holds the first row's first key.

    refs layout: page_table, positions (scalar prefetch) | q, k_hbm, v_hbm
    [, ks_tiles, vs_tiles] | out | k_scratch, v_scratch [, ks_scratch,
    vs_scratch], sems."""
    if quantized:
        (page_table_ref, positions_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         out_ref, k_scratch, v_scratch, ks_scratch, vs_scratch, sems) = refs
        scale_pairs = [(ks_hbm, ks_scratch), (vs_hbm, vs_scratch)]
    else:
        (page_table_ref, positions_ref, q_ref, k_hbm, v_hbm,
         out_ref, k_scratch, v_scratch, sems) = refs
        scale_pairs = []
    pairs = [(k_hbm, k_scratch), (v_hbm, v_scratch)]

    qb = pl.program_id(0)
    Bq, Hq, D = q_ref.shape
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv
    TP = tile_pages
    S = TP * page_size  # context tile length

    # this block's query positions and causal context bound
    q_start = qb * block_q
    last_pos = positions_ref[q_start + Bq - 1]
    ctx_len = last_pos + 1
    n_tiles = jnp.minimum(
        pl.cdiv(ctx_len, S), pl.cdiv(jnp.int32(max_pages * page_size), S)
    )

    # [Hkv, G*Bq, D] query layout: head-major groups so each kv head's block
    # is one batched matmul operand
    q = (
        q_ref[...]
        .astype(jnp.float32)
        .reshape(Bq, Hkv, G, D)
        .transpose(1, 2, 0, 3)
        .reshape(Hkv, G * Bq, D)
    )
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    start, wait = _tile_dma_helpers(
        page_table_ref, pairs, scale_pairs, sems, TP, max_pages
    )
    # causal mask geometry, built directly in 2D [G*Bq, S] (Mosaic rejects 1D
    # vector reshapes): row i is block-row i % Bq; its query position is
    # positions[q_start] + (i % Bq)
    pos0 = positions_ref[q_start]
    base = jnp.maximum(0, pos0 - window + 1) // S if window else 0
    start(jax.lax.rem(base, 2) if window else 0, base)
    iota_row = jax.lax.broadcasted_iota(jnp.int32, (G * Bq, S), 0)
    iota_col = jax.lax.broadcasted_iota(jnp.int32, (G * Bq, S), 1)
    q_pos_2d = pos0 + jax.lax.rem(iota_row, Bq)  # [G*Bq, S]

    def body(t, carry):
        buf = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            start(jax.lax.rem(t + 1, 2), t + 1)

        wait(buf, t)

        kt = (
            k_scratch[buf]
            .astype(jnp.float32)
            .reshape(S, Hkv, D)
            .transpose(1, 0, 2)
        )  # [Hkv, S, D]
        vt = (
            v_scratch[buf]
            .astype(jnp.float32)
            .reshape(S, Hkv, D)
            .transpose(1, 0, 2)
        )
        ks_row = ks_scratch[buf][:, :S] if quantized else None
        vs_row = vs_scratch[buf][:, :S] if quantized else None

        ctx_idx = t * S + iota_col
        # causal, and never beyond the page table (the final tile clamps its
        # page indices to max_pages - 1, which would alias earlier content)
        mask = (ctx_idx <= q_pos_2d) & (ctx_idx < max_pages * page_size)
        if window:
            mask &= ctx_idx > q_pos_2d - window
        return _flash_merge(carry, q, kt, vt, scale, mask, ks_row, vs_row)

    m0 = jnp.full((Hkv, G * Bq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G * Bq), jnp.float32)
    acc0 = jnp.zeros((Hkv, G * Bq, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(base, n_tiles, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]  # [Hkv, G*Bq, D]
    out_ref[...] = (
        out.reshape(Hkv, G, Bq, D).transpose(2, 0, 1, 3).reshape(Bq, Hq, D)
    ).astype(out_ref.dtype)


def _kernel_lookahead(
    *refs,
    page_size: int,
    max_pages: int,
    tile_pages: int,
    block_q: int,
    lookahead: int,
    quantized: bool,
    window: int = 0,
):
    """Flash prefill with CROSS-PROGRAM context-tile prefetch (the decode
    lookahead kernel's scheduling applied to the query-block grid; see the
    module docstring for why the boundary exposure matters more here).
    ``window`` > 0: as in `_kernel`; the prefetch window then holds a query
    block's first tiles from ``block_base`` on.

    refs layout: page_table, positions | q, k_hbm, v_hbm [, ks_tiles,
    vs_tiles] | out | k_pre, v_pre [, ks_pre, vs_pre], k_tail, v_tail
    [, ks_tail, vs_tail], sems_pre, sems_tail. Semaphore channel c (k, v)
    slot p carries page p, slot TP the tile's scale row."""
    if quantized:
        (page_table_ref, positions_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         out_ref, k_pre, v_pre, ks_pre, vs_pre, k_tail, v_tail, ks_tail,
         vs_tail, sems_pre, sems_tail) = refs
        pre_scales = [(ks_hbm, ks_pre), (vs_hbm, vs_pre)]
        tail_scales = [(ks_hbm, ks_tail), (vs_hbm, vs_tail)]
    else:
        (page_table_ref, positions_ref, q_ref, k_hbm, v_hbm,
         out_ref, k_pre, v_pre, k_tail, v_tail, sems_pre, sems_tail) = refs
        pre_scales = tail_scales = []
    pre_pools = [(k_hbm, k_pre), (v_hbm, v_pre)]
    tail_pairs = [(k_hbm, k_tail), (v_hbm, v_tail)]

    qb = pl.program_id(0)
    nb = pl.num_programs(0)
    par = jax.lax.rem(qb, 2)
    W = lookahead
    Bq, Hq, D = q_ref.shape
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv
    TP = tile_pages
    S = TP * page_size
    ctx_cap = jnp.int32(max_pages * page_size)

    def block_tiles(block_idx):
        """Causal tile count for query block ``block_idx`` (its last row's
        position is scalar-prefetched, so any program can compute it)."""
        last_pos = positions_ref[block_idx * block_q + Bq - 1]
        return jnp.minimum(pl.cdiv(last_pos + 1, S), pl.cdiv(ctx_cap, S))

    def block_base(block_idx):
        """First tile that holds a key any row of the block may see."""
        if not window:
            return 0
        return jnp.maximum(0, positions_ref[block_idx * block_q] - window + 1) // S

    n_tiles = block_tiles(qb)
    base = block_base(qb)

    q = (
        q_ref[...]
        .astype(jnp.float32)
        .reshape(Bq, Hkv, G, D)
        .transpose(1, 2, 0, 3)
        .reshape(Hkv, G * Bq, D)
    )
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def pre_dmas(parity, j, first):
        """Every copy of window tile j (context tile ``first + j``): TP pages
        of k and v [+ scale rows]."""
        copies = []
        for p in range(TP):
            idx = jnp.minimum((first + j) * TP + p, max_pages - 1)
            for c, (hbm, scratch) in enumerate(pre_pools):
                copies.append(pltpu.make_async_copy(
                    hbm.at[page_table_ref[idx]],
                    scratch.at[parity, j, p],
                    sems_pre.at[parity, j, c, p],
                ))
        for c, (hbm, scratch) in enumerate(pre_scales):
            copies.append(pltpu.make_async_copy(
                hbm.at[first + j], scratch.at[parity, j], sems_pre.at[parity, j, c, TP]
            ))
        return copies

    def tail_dmas(slot, tile):
        """Every copy of in-program double-buffer tile ``tile``."""
        copies = []
        for p in range(TP):
            idx = jnp.minimum(tile * TP + p, max_pages - 1)
            for c, (hbm, scratch) in enumerate(tail_pairs):
                copies.append(pltpu.make_async_copy(
                    hbm.at[page_table_ref[idx]],
                    scratch.at[slot, p],
                    sems_tail.at[slot, c, p],
                ))
        for c, (hbm, scratch) in enumerate(tail_scales):
            copies.append(pltpu.make_async_copy(
                hbm.at[tile], scratch.at[slot], sems_tail.at[slot, c, TP]
            ))
        return copies

    def issue_pre(block_idx, parity):
        # context pages are shared by every query block of the chunk, so the
        # NEXT block's first W tiles are known from the page table alone;
        # only how many it needs (its causal bound) depends on the block
        first = block_base(block_idx)
        npg = block_tiles(block_idx) - first
        for j in range(W):  # static unroll: DMA issues only

            @pl.when(j < npg)
            def _(j=j):
                for cp in pre_dmas(parity, j, first):
                    cp.start()

    # program 0 has no predecessor: prefetch its own window
    @pl.when(qb == 0)
    def _():
        issue_pre(0, 0)

    # prefetch the NEXT query block's window while this one computes
    @pl.when(qb + 1 < nb)
    def _():
        issue_pre(qb + 1, 1 - par)

    # long-context tail: warm the in-program double buffer for tile W
    @pl.when(base + W < n_tiles)
    def _():
        for cp in tail_dmas(jax.lax.rem(base + W, 2) if window else W % 2, base + W):
            cp.start()

    pos0 = positions_ref[qb * block_q]
    iota_row = jax.lax.broadcasted_iota(jnp.int32, (G * Bq, S), 0)
    iota_col = jax.lax.broadcasted_iota(jnp.int32, (G * Bq, S), 1)
    q_pos_2d = pos0 + jax.lax.rem(iota_row, Bq)

    def merge_tile(carry, t, k_tile, v_tile, ks_tile, vs_tile):
        kt = k_tile.astype(jnp.float32).reshape(S, Hkv, D).transpose(1, 0, 2)
        vt = v_tile.astype(jnp.float32).reshape(S, Hkv, D).transpose(1, 0, 2)
        ks_row = ks_tile[:, :S] if quantized else None
        vs_row = vs_tile[:, :S] if quantized else None
        ctx_idx = t * S + iota_col
        mask = (ctx_idx <= q_pos_2d) & (ctx_idx < ctx_cap)
        if window:
            mask &= ctx_idx > q_pos_2d - window
        return _flash_merge(carry, q, kt, vt, scale, mask, ks_row, vs_row)

    def pre_body(j, carry):
        for cp in pre_dmas(par, j, base):
            cp.wait()
        return merge_tile(
            carry, base + j, k_pre[par, j], v_pre[par, j],
            ks_pre[par, j] if quantized else None,
            vs_pre[par, j] if quantized else None,
        )

    def tail_body(t, carry):
        slot = jax.lax.rem(t, 2)
        next_slot = jax.lax.rem(t + 1, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            for cp in tail_dmas(next_slot, t + 1):
                cp.start()

        for cp in tail_dmas(slot, t):
            cp.wait()
        return merge_tile(
            carry, t, k_tail[slot], v_tail[slot],
            ks_tail[slot] if quantized else None,
            vs_tail[slot] if quantized else None,
        )

    m0 = jnp.full((Hkv, G * Bq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G * Bq), jnp.float32)
    acc0 = jnp.zeros((Hkv, G * Bq, D), jnp.float32)
    carry = jax.lax.fori_loop(0, jnp.minimum(W, n_tiles - base), pre_body, (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(base + W, n_tiles, tail_body, carry)

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[...] = (
        out.reshape(Hkv, G, Bq, D).transpose(2, 0, 1, 3).reshape(Bq, Hq, D)
    ).astype(out_ref.dtype)


def _kernel_folded(
    *refs,
    page_size: int,
    max_pages: int,
    tile_pages: int,
    block_q: int,
    num_kv_heads: int,
    head_dim: int,
    quantized: bool,
):
    """Folded-lane flash prefill for head_dim < 128 (see the decode
    _kernel_lookahead in paged_attention.py for the trick): every (query row,
    head) pair becomes one row of a zero-placed folded Q [Bq*Hq, Hkv*D], so a
    single [R, F] x [S, F] matmul yields exact per-head scores — the zero
    slices kill cross-head terms and cost only Hkv x extra MACs on an op
    that is a rounding error of prefill FLOPs. All shape changes are
    leading-dim merges/splits (minor dim untouched: Mosaic-legal). Int8
    pools: the per-row scale is head-INDEPENDENT, so one [1, S] scale row
    applies to the folded scores/probs exactly like the unfolded case."""
    if quantized:
        (page_table_ref, positions_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         out_ref, k_scratch, v_scratch, ks_scratch, vs_scratch, sems) = refs
        scale_pairs = [(ks_hbm, ks_scratch), (vs_hbm, vs_scratch)]
    else:
        (page_table_ref, positions_ref, q_ref, k_hbm, v_hbm,
         out_ref, k_scratch, v_scratch, sems) = refs
        scale_pairs = []
    pairs = [(k_hbm, k_scratch), (v_hbm, v_scratch)]

    qb = pl.program_id(0)
    Bq, Hq, D = q_ref.shape
    Hkv, F = num_kv_heads, num_kv_heads * head_dim
    G = Hq // Hkv
    TP = tile_pages
    S = TP * page_size
    R = Bq * Hq

    q_start = qb * block_q
    last_pos = positions_ref[q_start + Bq - 1]
    ctx_len = last_pos + 1
    n_tiles = jnp.minimum(
        pl.cdiv(ctx_len, S), pl.cdiv(jnp.int32(max_pages * page_size), S)
    )

    # folded queries [R, F]: row r = (t, h) with t = r // Hq, h = r % Hq;
    # q[t, h] occupies kv(h) = (h // G)'s D-slice, zeros elsewhere
    q2 = q_ref[...].reshape(R, D)  # leading merge only
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, F), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, F), 0)
    own = (lane // D == jax.lax.rem(row, Hq) // G).astype(jnp.float32)
    qtile = jnp.concatenate([q2.astype(jnp.float32)] * Hkv, axis=1)  # [R, F]
    qf = (qtile * own).astype(q_ref.dtype)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    start, wait = _tile_dma_helpers(
        page_table_ref, pairs, scale_pairs, sems, TP, max_pages
    )
    start(0, 0)

    # causal geometry: row r's query position = positions[q_start] + r // Hq
    pos0 = positions_ref[q_start]
    iota_row = jax.lax.broadcasted_iota(jnp.int32, (R, S), 0)
    iota_col = jax.lax.broadcasted_iota(jnp.int32, (R, S), 1)
    q_pos_2d = pos0 + iota_row // Hq  # [R, S]

    def body(t, carry):
        m, l, acc = carry
        buf = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            start(jax.lax.rem(t + 1, 2), t + 1)

        wait(buf, t)

        kf = k_scratch[buf].reshape(S, F)  # leading merge
        vf = v_scratch[buf].reshape(S, F)

        # [R, S] exact per-(row, head) scores via the folded contraction
        # (int8 pages upcast to f32 for the dot — operand dtypes must match)
        scores = jax.lax.dot_general(
            qf.astype(jnp.float32) if quantized else qf,
            kf.astype(jnp.float32) if quantized else kf,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if quantized:
            scores = scores * ks_scratch[buf][:, :S]  # [1, S]
        ctx_idx = t * S + iota_col
        mask = (ctx_idx <= q_pos_2d) & (ctx_idx < max_pages * page_size)
        scores = jnp.where(mask, scores, _NEG_INF)

        chunk_max = jnp.max(scores, axis=-1)  # [R]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[:, None])
        new_l = l * corr + jnp.sum(probs, axis=-1)
        # [R, F] = [R, S] x [S, F]
        if quantized:
            probs = probs * vs_scratch[buf][:, :S]
            chunk_out = jax.lax.dot_general(
                probs, vf.astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            chunk_out = jax.lax.dot_general(
                probs.astype(kf.dtype), vf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        new_acc = acc * corr[:, None] + chunk_out
        return new_m, new_l, new_acc

    m0 = jnp.full((R,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((R,), jnp.float32)
    acc0 = jnp.zeros((R, F), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_tiles, body, (m0, l0, acc0))

    # keep each row's owned D-slice: zero the rest and fold the Hkv slices
    acc_m = acc * own
    out2 = acc_m[:, 0:D]
    for j in range(1, Hkv):
        out2 = out2 + acc_m[:, j * D : (j + 1) * D]
    out2 = out2 / jnp.maximum(l, 1e-20)[:, None]
    out_ref[...] = out2.reshape(Bq, Hq, D).astype(out_ref.dtype)  # leading split


def _tile_scratch(lead: tuple, tile_shape: tuple, kq, vq, ks, vs):
    """VMEM scratch + DMA semaphores for context tiles buffered ``lead``
    deep: k/v tiles ``[*lead, *tile_shape]`` [, int8 scale rows ``[*lead, 1,
    S]``], then sems ``[*lead, 2, TP (+ 1 for the scale row)]`` (the layout
    _tile_dma_helpers and _kernel_lookahead index; a core has 2 KiB of DMA
    semaphores, 512 of them, and a long tile's 32 pages a channel, window and
    tail, take 256)."""
    shapes = [pltpu.VMEM((*lead, *tile_shape), kq.dtype),
              pltpu.VMEM((*lead, *tile_shape), vq.dtype)]
    if ks is not None:
        shapes += [pltpu.VMEM((*lead, 1, ks.shape[-1]), jnp.float32),
                   pltpu.VMEM((*lead, 1, vs.shape[-1]), jnp.float32)]
    sems = pltpu.SemaphoreType.DMA((*lead, 2, tile_shape[0] + (ks is not None)))
    return shapes, sems


def _prefill_call(body, scratch_shapes, q, page_table, positions, pools,
                  block_q: int, interpret: bool, serial_grid: bool = False,
                  name: str | None = None):
    """One pallas_call over the query-block grid shared by every prefill
    kernel: page table + positions scalar-prefetched, q/out blocked by
    query block, pools (and int8 scale tiles) left in HBM for manual DMA."""
    T, Hq, D = q.shape
    assert T % block_q == 0, f"chunk {T} % block_q {block_q}"
    q_spec = pl.BlockSpec((block_q, Hq, D), lambda qb, *_: (qb, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // block_q,),
        in_specs=[q_spec, *[pl.BlockSpec(memory_space=pl.ANY) for _ in pools]],
        out_specs=q_spec,
        scratch_shapes=scratch_shapes,
    )
    kernel = pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((T, Hq, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            # cross-program scratch persistence (query block b prefetches
            # b+1's context tiles into the opposite parity) requires the
            # grid to run SERIALLY — pin it, as the decode lookahead kernel
            # does
            dimension_semantics=("arbitrary",) if serial_grid else None,
            vmem_limit_bytes=PREFILL_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=name,
    )
    return kernel(
        page_table.astype(jnp.int32), positions.astype(jnp.int32), q, *pools
    )


#: VMEM budget for the lookahead prefill window's own scratch (the compiler's
#: stack comes on top of it: see PREFILL_VMEM_LIMIT_BYTES)
_PREFILL_LOOKAHEAD_SCRATCH_BYTES = 8 * 1024 * 1024


def prefill_lookahead_window(page_size: int, tile_pages: int,
                             num_kv_heads: int, head_dim: int,
                             itemsize: int = 2) -> int:
    """Prefetch window W in context TILES that fits the scratch budget
    (0 = lookahead not applicable at this geometry). Scratch = 2 parities x
    W tiles x (k+v) + the 2-slot tail; int8 scale tiles are noise. The window
    is budgeted in PAGES, four tiles of 128 tokens (`issue_pre` unrolls one
    DMA issue a page and pool statically, twice a program), and a tile that
    takes the whole budget gets none: a window of one tile of 512 tokens
    timed 3-15% slower than the basic kernel and compiled twice as long
    (module docstring)."""
    tile_bytes = 2 * tile_pages * page_size * num_kv_heads * head_dim * itemsize
    budget = _PREFILL_LOOKAHEAD_SCRATCH_BYTES - 2 * tile_bytes  # tail buffers
    tiles = 4 * prefill_tile_pages(page_size) // tile_pages
    if tiles < 2:
        return 0
    return max(0, min(tiles, budget // (2 * tile_bytes)))


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def paged_prefill_attention_pallas_folded(
    q: jnp.ndarray,  # [T, Hq, D] bucket-padded chunk
    k_pages,  # [P, ps, Hkv*D] folded (plain or QuantizedPages), or [P, ps, Hkv, D]
    v_pages,
    page_table: jnp.ndarray,  # [max_pages] int32
    positions: jnp.ndarray,  # [T] int32 absolute positions (unit-stride)
    block_q: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    D = q.shape[-1]
    if k_pages.ndim == 4:  # direct-call convenience (tests)
        P, ps, Hkv, _ = k_pages.shape
        if isinstance(k_pages, QuantizedPages):
            k_pages = QuantizedPages(k_pages.q.reshape(P, ps, Hkv * D), k_pages.s)
            v_pages = QuantizedPages(v_pages.q.reshape(P, ps, Hkv * D), v_pages.s)
        else:
            k_pages = k_pages.reshape(P, ps, Hkv * D)
            v_pages = v_pages.reshape(P, ps, Hkv * D)
    tile_pages = prefill_tile_pages(k_pages.shape[1])
    kq, vq, ks, vs = _unpack_pools(k_pages, v_pages, page_table, tile_pages)
    _, ps, F = kq.shape
    shapes, sems = _tile_scratch((2,), (tile_pages, ps, F), kq, vq, ks, vs)
    body = functools.partial(
        _kernel_folded,
        page_size=ps,
        max_pages=page_table.shape[0],
        tile_pages=tile_pages,
        block_q=block_q,
        num_kv_heads=F // D,
        head_dim=D,
        quantized=ks is not None,
    )
    pools = (kq, vq) if ks is None else (kq, vq, ks, vs)
    return _prefill_call(
        body, [*shapes, sems], q, page_table, positions, pools, block_q, interpret
    )


@functools.partial(
    jax.jit,
    static_argnames=("interpret", "block_q", "lookahead", "window", "tile_pages"),
)
def paged_prefill_attention_pallas(
    q: jnp.ndarray,  # [T, Hq, D] bucket-padded chunk
    k_pages,  # [P, ps, Hkv, D] plain or QuantizedPages
    v_pages,
    page_table: jnp.ndarray,  # [max_pages] int32
    positions: jnp.ndarray,  # [T] int32 absolute positions (unit-stride)
    block_q: int = 128,
    interpret: bool = False,
    lookahead: bool = True,
    window: int = 0,  # sliding window in tokens (0: the whole context)
    tile_pages: int | None = None,  # None: prefill_tile_pages (tools time others)
) -> jnp.ndarray:
    """Flash prefill dispatcher: lookahead (cross-program tile prefetch)
    when the window fits its scratch budget — a trace-time choice by shape —
    else the basic in-program double buffer. The context tile's length
    follows the page table's width (``prefill_tile_pages``)."""
    if tile_pages is None:
        tile_pages = prefill_tile_pages(k_pages.shape[1], page_table.shape[0])
    kq, vq, ks, vs = _unpack_pools(k_pages, v_pages, page_table, tile_pages)
    _, ps, Hkv, D = kq.shape
    tile = (tile_pages, ps, Hkv, D)
    W = (
        prefill_lookahead_window(ps, tile_pages, Hkv, D, kq.dtype.itemsize)
        if lookahead
        else 0
    )
    common = dict(
        page_size=ps,
        max_pages=page_table.shape[0],
        tile_pages=tile_pages,
        block_q=block_q,
        quantized=ks is not None,
        window=window,
    )
    tail_shapes, tail_sems = _tile_scratch((2,), tile, kq, vq, ks, vs)
    if W >= 1:
        pre_shapes, pre_sems = _tile_scratch((2, W), tile, kq, vq, ks, vs)
        scratch = [*pre_shapes, *tail_shapes, pre_sems, tail_sems]
        body = functools.partial(_kernel_lookahead, lookahead=W, **common)
    else:
        scratch = [*tail_shapes, tail_sems]
        body = functools.partial(_kernel, **common)
    pools = (kq, vq) if ks is None else (kq, vq, ks, vs)
    return _prefill_call(
        body, scratch, q, page_table, positions, pools, block_q, interpret,
        serial_grid=W >= 1, name=SLIDING_PREFILL_NAME if window else None,
    )
