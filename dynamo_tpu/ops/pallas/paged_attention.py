"""Pallas TPU kernel: paged decode attention.

One query token per sequence attends over its paged KV context. The page
table rides in as scalar-prefetch (available before the kernel body, so page
DMAs can be issued from dynamic indices), K/V page pools stay in HBM, and
pages stream through a double-buffered VMEM scratch overlapping DMA with
compute (pallas_guide.md: PrefetchScalarGridSpec + double buffering).

Contract matches the pure-JAX reference (dynamo_tpu/ops/attention.py
paged_decode_attention): q [B, Hq, D], pages [P, ps, Hkv, D],
page_tables [B, max_pages], positions [B] (query position; context length =
position + 1). GQA folded as [Hkv, G, D] per-kv-head batched matmuls.

Design record. The variants below were A/B'd on an earlier v5e machine that
is gone; none of its numbers is quoted here, and nothing has been timed on
the current chip yet (PERF.md; ROADMAP S0/D3). What the record taught, and
what the kernels still encode:

  - perseq (one sequence per grid program, one-page-ahead double buffer) is
    the design point. The [ps, Hkv, D] leading-index page DMA it issues is
    the layout Mosaic moves fastest; fused-pool and row-flat prototypes that
    issued half the DMAs were several times slower and were deleted.
  - Mosaic pipelines ACROSS grid programs, so B one-sequence programs
    overlap each other's DMAs and compute for free; any within-program
    grouping (grouped, chunked, a concat-context prototype) trades that away
    for a serialized group body and lost every comparison.
  - The f32 casts are load-bearing: Mosaic relayouts
    ([ps,Hkv,D]->[Hkv,ps,D]) are far cheaper in 32-bit than bf16, and the
    no-transpose dot_general variants (batch dim in K's middle position) are
    Mosaic-illegal outright (tpu.matmul requires leading batch dims).
  - The gap between perseq and a null kernel (same grid, same DMA stream, no
    math) is the per-program DMA-latency exposure at every grid-program
    boundary, and the page table being scalar-prefetched means program b can
    issue program b+1's DMAs — see _kernel_lookahead below, the default for
    head_dim 128.

Int8 KV (quant/kv.py QuantizedPages): perseq, lookahead, and folded accept
int8 pools plus their per-row f32 scales, which arrive as lane-aligned rows
gathered by XLA in page-table order (gather_scale_rows — Mosaic refuses to
DMA-slice the raw [P, ps] plane when ps < 128). Scale rows ride their own
tiny DMAs beside the page DMAs (the HBM context stream halves — that is the
win) and dequantization is applied to the score/prob tiles in VMEM:
``scores *= k_s`` / ``probs *= v_s`` is the exact per-column algebra, and
both are lane-axis broadcasts (Mosaic-legal; no sub-128 minor-dim reshapes).
chunked/grouped stay bf16-only — the dispatcher never routes int8 to them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    lane axis (1 for decode's page-at-a-time loop, the tile width for
    prefill), and the row is zero-padded to a multiple of 128 lanes. Row r of
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


def _decode_unpack_pools(k_pages, v_pages, page_tables):
    """(k, v, k_scale rows | None, v_scale rows | None, quantized): int8
    pools carry their scales as ``gather_scale_rows`` over the page tables,
    one [1, W] row per (sequence, logical page)."""
    if isinstance(k_pages, QuantizedPages):
        return (
            k_pages.q, v_pages.q,
            gather_scale_rows(k_pages.s, page_tables),
            gather_scale_rows(v_pages.s, page_tables),
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


def _kernel_grouped(
    # scalar prefetch
    page_tables_ref,  # [B, max_pages] SMEM
    lengths_ref,  # [B] SMEM
    # inputs
    q_ref,  # [Gq, Hq, D] VMEM (this group's queries)
    k_hbm,  # [P, ps, Hkv, D] HBM
    v_hbm,  # [P, ps, Hkv, D] HBM
    # output
    out_ref,  # [Gq, Hq, D] VMEM
    # scratch
    k_scratch,  # [2, Gq, ps, Hkv, D] VMEM
    v_scratch,  # [2, Gq, ps, Hkv, D] VMEM
    sems,  # DMA sems [2, Gq, 2]
    *,
    page_size: int,
    group: int,
):
    """Gq sequences per grid program: page index walks the whole group at
    once (2*Gq outstanding DMAs per iteration) and the per-program fixed cost
    amortizes across the group — the winning regime once pages are large
    (few pages/seq, per-PROGRAM overhead dominates the per-seq kernel)."""
    g0 = pl.program_id(0) * group
    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv

    lengths = [lengths_ref[g0 + j] for j in range(group)]
    n_pages = [jnp.maximum(1, pl.cdiv(lengths[j], page_size)) for j in range(group)]
    max_n = n_pages[0]
    for j in range(1, group):
        max_n = jnp.maximum(max_n, n_pages[j])

    qs = [q_ref[j].reshape(Hkv, G, D) for j in range(group)]
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def dma(slot, j, i, which):
        hbm, scratch = (k_hbm, k_scratch) if which == 0 else (v_hbm, v_scratch)
        return pltpu.make_async_copy(
            hbm.at[page_tables_ref[g0 + j, i]],
            scratch.at[slot, j],
            sems.at[slot, j, which],
        )

    def start_all(slot, i):
        for j in range(group):  # static unroll
            @pl.when(i < n_pages[j])
            def _(j=j):
                dma(slot, j, i, 0).start()
                dma(slot, j, i, 1).start()

    def wait_all(slot, i):
        for j in range(group):
            @pl.when(i < n_pages[j])
            def _(j=j):
                dma(slot, j, i, 0).wait()
                dma(slot, j, i, 1).wait()

    start_all(0, 0)

    def body(i, carry):
        m, l, acc = carry  # [group, Hkv, G], [group, Hkv, G], [group, Hkv, G, D]
        slot = jax.lax.rem(i, 2)
        next_slot = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < max_n)
        def _():
            start_all(next_slot, i + 1)

        wait_all(slot, i)

        idx = i * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
        vidx = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size, 1), 1
        )
        ms, ls, accs = [], [], []
        for j in range(group):
            kt = jnp.transpose(k_scratch[slot, j], (1, 0, 2))  # [Hkv, ps, D] bf16
            vt = jnp.transpose(v_scratch[slot, j], (1, 0, 2))
            scores = jax.lax.dot_general(
                qs[j], kt, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale
            # beyond-length/stale rows: mask K scores outright and zero V so
            # 0-weight garbage (or uninitialized first-call VMEM) can't
            # poison acc via 0 * NaN
            scores = jnp.where(idx < lengths[j], scores, _NEG_INF)
            vt = jnp.where(vidx < lengths[j], vt, 0)

            chunk_max = jnp.max(scores, axis=-1)
            new_m = jnp.maximum(m[j], chunk_max)
            corr = jnp.exp(m[j] - new_m)
            probs = jnp.exp(scores - new_m[..., None])
            new_l = l[j] * corr + jnp.sum(probs, axis=-1)
            chunk_out = jax.lax.dot_general(
                probs.astype(kt.dtype), vt, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            ms.append(new_m)
            ls.append(new_l)
            accs.append(acc[j] * corr[..., None] + chunk_out)
        return jnp.stack(ms), jnp.stack(ls), jnp.stack(accs)

    m0 = jnp.full((group, Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((group, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((group, Hkv, G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, max_n, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[...] = out.reshape(group, Hq, D).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas_grouped(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,  # [P, ps, Hkv, D]
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    lengths = positions.astype(jnp.int32) + 1
    # largest group that divides B AND keeps the double-buffered K+V scratch
    # within a conservative VMEM budget (v5e scoped limit is ~16MB)
    bytes_per_seq = 2 * 2 * ps * Hkv * D * k_pages.dtype.itemsize  # 2 slots x k+v
    group = 1
    for cand in (8, 4, 2):
        if B % cand == 0 and cand * bytes_per_seq <= 8 * 1024 * 1024:
            group = cand
            break

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B // group,),
        in_specs=[
            pl.BlockSpec((group, Hq, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((group, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, group, ps, Hkv, D), k_pages.dtype),
            pltpu.VMEM((2, group, ps, Hkv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, group, 2)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_kernel_grouped, page_size=ps, group=group),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    return kernel(page_tables.astype(jnp.int32), lengths, q, k_pages, v_pages)


def _kernel_lookahead(
    *refs,
    page_size: int,
    max_pages: int,
    lookahead: int,
    quantized: bool = False,
):
    """perseq with CROSS-PROGRAM DMA pipelining.

    Grid programs execute serially on the core, and scratch PERSISTS across
    them; the page table is scalar-prefetched, so program b can issue program
    b+1's first ``lookahead`` page DMAs into the opposite parity's slot pair
    while it computes on its own pages (prefetched by b-1). The per-program
    DMA-latency exposure at every program boundary — the entire gap between
    perseq and the measured DMA floor — collapses to one program's worth for
    the whole grid. Pages >= lookahead (long contexts) stream through the
    classic in-program double buffer.

    refs: page_tables + lengths (scalar prefetch) | q, k/v pools [, k/v
    scale rows [B*max_pages, 1, Ws], see gather_scale_rows] | out | k_pre,
    v_pre [2, W, ps, Hkv, D] [, scale windows [2, W, 1, Ws]], k_tail, v_tail
    [2, ps, Hkv, D] [, scale tails [2, 1, Ws]], sems_pre [2, W, 2|4],
    sems_tail [2, 2|4]."""
    if quantized:
        (page_tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm,
         out_ref, k_pre, v_pre, ks_pre, vs_pre, k_tail, v_tail, ks_tail,
         vs_tail, sems_pre, sems_tail) = refs
        pre_pools = [(k_hbm, k_pre), (v_hbm, v_pre),
                     (ks_hbm, ks_pre), (vs_hbm, vs_pre)]
        tail_pools = [(k_hbm, k_tail), (v_hbm, v_tail),
                      (ks_hbm, ks_tail), (vs_hbm, vs_tail)]
    else:
        (page_tables_ref, lengths_ref, q_ref, k_hbm, v_hbm,
         out_ref, k_pre, v_pre, k_tail, v_tail, sems_pre, sems_tail) = refs
        pre_pools = [(k_hbm, k_pre), (v_hbm, v_pre)]
        tail_pools = [(k_hbm, k_tail), (v_hbm, v_tail)]

    b = pl.program_id(0)
    nb = pl.num_programs(0)
    par = jax.lax.rem(b, 2)
    W = lookahead
    length = lengths_ref[b]
    n_pages = jnp.maximum(1, pl.cdiv(length, page_size))

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv
    q = q_ref[0].astype(jnp.float32).reshape(Hkv, G, D)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def src(seq_idx, i, c):
        # pages by physical id; scale rows by (sequence, logical page)
        return page_tables_ref[seq_idx, i] if c < 2 else seq_idx * max_pages + i

    def pre_dma(parity, j, seq_idx, c):
        hbm, scratch = pre_pools[c]
        return pltpu.make_async_copy(
            hbm.at[src(seq_idx, j, c)],
            scratch.at[parity, j],
            sems_pre.at[parity, j, c],
        )

    def tail_dma(slot, i, c):
        hbm, scratch = tail_pools[c]
        return pltpu.make_async_copy(
            hbm.at[src(b, i, c)],
            scratch.at[slot],
            sems_tail.at[slot, c],
        )

    def issue_pre(seq_idx, parity):
        npg = jnp.maximum(1, pl.cdiv(lengths_ref[seq_idx], page_size))
        for j in range(W):  # static unroll: DMA issues only

            @pl.when(j < npg)
            def _(j=j):
                for c in range(len(pre_pools)):
                    pre_dma(parity, j, seq_idx, c).start()

    # program 0 has no predecessor: prefetch its own window
    @pl.when(b == 0)
    def _():
        issue_pre(0, 0)

    # prefetch the NEXT program's window while this one computes
    @pl.when(b + 1 < nb)
    def _():
        issue_pre(b + 1, 1 - par)

    # long-context tail: warm the in-program double buffer for page W
    @pl.when(W < n_pages)
    def _():
        for c in range(len(tail_pools)):
            tail_dma(W % 2, W, c).start()

    def merge(carry, k_page, v_page, j, k_s, v_s):
        m, l, acc = carry
        kt = jnp.transpose(k_page, (1, 0, 2))  # [Hkv, ps, D]
        vt = jnp.transpose(v_page, (1, 0, 2))
        scores = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale
        if quantized:
            scores = scores * k_s[:, :page_size][None]  # [1, 1, ps] per-row K scales
        idx = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
        scores = jnp.where(idx < length, scores, _NEG_INF)
        chunk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[..., None])
        new_l = l * corr + jnp.sum(probs, axis=-1)
        if quantized:
            probs = probs * v_s[:, :page_size][None]
        chunk_out = jax.lax.dot_general(
            probs, vt, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
        )
        return new_m, new_l, acc * corr[..., None] + chunk_out

    def pre_body(j, carry):
        for c in range(len(pre_pools)):
            pre_dma(par, j, b, c).wait()
        return merge(
            carry,
            k_pre[par, j].astype(jnp.float32),
            v_pre[par, j].astype(jnp.float32),
            j,
            ks_pre[par, j] if quantized else None,
            vs_pre[par, j] if quantized else None,
        )

    def tail_body(j, carry):
        slot = jax.lax.rem(j, 2)
        next_slot = jax.lax.rem(j + 1, 2)

        @pl.when(j + 1 < n_pages)
        def _():
            for c in range(len(tail_pools)):
                tail_dma(next_slot, j + 1, c).start()

        for c in range(len(tail_pools)):
            tail_dma(slot, j, c).wait()
        return merge(
            carry,
            k_tail[slot].astype(jnp.float32),
            v_tail[slot].astype(jnp.float32),
            j,
            ks_tail[slot] if quantized else None,
            vs_tail[slot] if quantized else None,
        )

    m0 = jnp.full((Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G), jnp.float32)
    acc0 = jnp.zeros((Hkv, G, D), jnp.float32)
    carry = jax.lax.fori_loop(0, jnp.minimum(W, n_pages), pre_body, (m0, l0, acc0))
    m, l, acc = jax.lax.fori_loop(W, n_pages, tail_body, carry)

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[0] = out.reshape(Hq, D).astype(out_ref.dtype)


#: scratch budget for the lookahead window (VMEM is ~16 MB/core scoped)
_LOOKAHEAD_SCRATCH_BYTES = 6 * 1024 * 1024


def lookahead_window(page_size: int, num_kv_heads: int, head_dim: int,
                     itemsize: int = 2) -> int:
    """Prefetch window W that fits the scratch budget (0 = kernel not
    applicable). Scratch = 2 parities x W pages x (k+v) + the 2-slot tail."""
    page_bytes = page_size * num_kv_heads * head_dim * itemsize
    budget = _LOOKAHEAD_SCRATCH_BYTES - 2 * 2 * page_bytes  # tail buffers
    return max(0, min(4, budget // (2 * 2 * page_bytes)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas_lookahead(
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
    W = lookahead_window(ps, Hkv, D, kq.dtype.itemsize)
    if W < 1:
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, page_tables, positions, interpret=interpret
        )

    scratch_shapes = [
        pltpu.VMEM((2, W, ps, Hkv, D), kq.dtype),
        pltpu.VMEM((2, W, ps, Hkv, D), vq.dtype),
    ]
    if quantized:
        scratch_shapes += [
            pltpu.VMEM((2, W, 1, ks.shape[-1]), jnp.float32),
            pltpu.VMEM((2, W, 1, vs.shape[-1]), jnp.float32),
        ]
    scratch_shapes += [
        pltpu.VMEM((2, ps, Hkv, D), kq.dtype),
        pltpu.VMEM((2, ps, Hkv, D), vq.dtype),
    ]
    if quantized:
        scratch_shapes += [
            pltpu.VMEM((2, 1, ks.shape[-1]), jnp.float32),
            pltpu.VMEM((2, 1, vs.shape[-1]), jnp.float32),
        ]
    C = 4 if quantized else 2
    scratch_shapes += [
        pltpu.SemaphoreType.DMA((2, W, C)),
        pltpu.SemaphoreType.DMA((2, C)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in range(C)],
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch_shapes,
    )
    kernel = pl.pallas_call(
        functools.partial(
            _kernel_lookahead, page_size=ps, max_pages=max_pages, lookahead=W,
            quantized=quantized,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        # cross-program scratch persistence (program b prefetches b+1's pages
        # into the opposite parity's slots) requires the grid to run SERIALLY
        # — pin it rather than relying on the implicit default
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
    args = (kq, vq, ks, vs) if quantized else (kq, vq)
    return kernel(page_tables.astype(jnp.int32), lengths, q, *args)


def _kernel_folded(
    *refs,
    page_size: int,
    max_pages: int,
    num_kv_heads: int,
    head_dim: int,
    quantized: bool = False,
):
    """Decode attention for head_dim < 128 (e.g. TinyLlama/Qwen2-small: 64).

    Mosaic can't DMA-slice an HBM pool whose minor dim is under the 128-lane
    tile, so the pools arrive with kv heads FOLDED into the lane dim
    ([ps, Hkv*D] rows, >= 128 lanes). The per-head math never unfolds in bf16:

      - scores: Q is placed into a zero-padded folded layout (each q head
        occupies its kv head's D-slice, zeros elsewhere), so one
        [Hq, Hkv*D] x [ps, Hkv*D] matmul yields exact per-head scores —
        the zero slices kill every cross-head term.
      - output: probs @ V_folded gives [Hq, Hkv*D]; each head's true output
        sits in its kv head's slice, selected with a one-hot contraction in
        f32 (32-bit ops may reshape the minor dim; bf16 may not).

    refs: page_tables + lengths (scalar prefetch) | q [1, Hq, D], k/v pools
    [P, ps, Hkv*D] [, k/v scale rows [B*max_pages, 1, W], see
    gather_scale_rows] | out | k/v scratch [2, ps, Hkv*D] [, scale scratch
    [2, 1, W]], sems [2, 2|4]. The per-row int8 scale is head-independent,
    so the folded scores/probs scale with the same [1, ps] rows as the
    unfolded kernels.
    """
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

    Hq, D = q_ref.shape[1], head_dim
    Hkv = num_kv_heads
    G = Hq // Hkv
    F = Hkv * D  # folded lane width

    q32 = q_ref[0].astype(jnp.float32)  # [Hq, D]
    # Everything stays 2D — Mosaic (this version) rejects minor-dim reshapes
    # outright. The folded-lane ownership mask [Hq, F]:
    #   mask[h, f] = (f // D == h // G)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Hq, F), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (Hq, F), 0)
    mask = (lane // D == head // G).astype(jnp.float32)
    # folded q via lane-tiling: concat Hkv copies of q along lanes, zero all
    # slices a head doesn't own
    qtile = jnp.concatenate([q32] * Hkv, axis=1)  # [Hq, F]
    qf = (qtile * mask).astype(q_ref.dtype)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def dma(slot, i, c):
        hbm, scratch = pools[c]
        # pages by physical id; scale rows by (sequence, logical page)
        src = page_tables_ref[b, i] if c < 2 else b * max_pages + i
        return pltpu.make_async_copy(hbm.at[src], scratch.at[slot], sems.at[slot, c])

    for c in range(len(pools)):
        dma(0, 0, c).start()

    def body(i, carry):
        m, l, acc = carry  # [Hq], [Hq], [Hq, F] f32
        slot = jax.lax.rem(i, 2)
        next_slot = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            for c in range(len(pools)):
                dma(next_slot, i + 1, c).start()

        for c in range(len(pools)):
            dma(slot, i, c).wait()

        k_page = k_scratch[slot]  # [ps, F] bf16 (or int8)
        v_page = v_scratch[slot]
        idx = i * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        vidx = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0
        )

        # [Hq, ps] exact per-head scores via the folded contraction
        # (int8 pages upcast to f32 for the dot — operand dtypes must match)
        scores = jax.lax.dot_general(
            qf.astype(jnp.float32) if quantized else qf,
            k_page.astype(jnp.float32) if quantized else k_page,
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if quantized:
            scores = scores * ks_scratch[slot][:, :page_size]  # [1, ps] per-row K scales
        scores = jnp.where(idx < length, scores, _NEG_INF)
        v_page = jnp.where(vidx < length, v_page, 0)

        chunk_max = jnp.max(scores, axis=-1)  # [Hq]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[:, None])  # [Hq, ps]
        new_l = l * corr + jnp.sum(probs, axis=-1)
        # [Hq, F] = [Hq, ps] x [ps, F]
        if quantized:
            probs = probs * vs_scratch[slot][:, :page_size]  # V scales fold into probs
            chunk_out = jax.lax.dot_general(
                probs, v_page.astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        else:
            chunk_out = jax.lax.dot_general(
                probs.astype(v_page.dtype), v_page,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        new_acc = acc * corr[:, None] + chunk_out
        return new_m, new_l, new_acc

    m0 = jnp.full((Hq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hq,), jnp.float32)
    acc0 = jnp.zeros((Hq, F), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))

    # select each head's slice: zero un-owned lanes, then fold the Hkv
    # D-wide lane slices together (only the owned one is nonzero)
    acc_m = acc * mask
    out = acc_m[:, 0:D]
    for j in range(1, Hkv):
        out = out + acc_m[:, j * D : (j + 1) * D]
    out = out / jnp.maximum(l, 1e-20)[:, None]
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas_folded(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages,  # [P, ps, Hkv*D] folded (plain or QuantizedPages), or [P, ps, Hkv, D]
    v_pages,
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    lengths = positions.astype(jnp.int32) + 1
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
    kf, vf, ks, vs, quantized = _decode_unpack_pools(k_pages, v_pages, page_tables)
    P, ps, F = kf.shape
    Hkv = F // D

    scratch_shapes = [
        pltpu.VMEM((2, ps, Hkv * D), kf.dtype),
        pltpu.VMEM((2, ps, Hkv * D), vf.dtype),
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
            *[pl.BlockSpec(memory_space=pl.ANY) for _ in range(C)],
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch_shapes,
    )
    kernel = pl.pallas_call(
        functools.partial(
            _kernel_folded, page_size=ps, max_pages=page_tables.shape[1],
            num_kv_heads=Hkv, head_dim=D, quantized=quantized,
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    args = (kf, vf, ks, vs) if quantized else (kf, vf)
    return kernel(page_tables.astype(jnp.int32), lengths, q, *args)


def _kernel_chunked(
    # scalar prefetch
    page_tables_ref,  # [B, max_pages] SMEM
    lengths_ref,  # [B] SMEM
    # inputs
    q_ref,  # [1, Hq, D] VMEM (this sequence's query)
    k_hbm,  # [P, ps, Hkv, D] HBM
    v_hbm,  # [P, ps, Hkv, D] HBM
    # output
    out_ref,  # [1, Hq, D] VMEM
    # scratch
    k_scratch,  # [2, C, ps, Hkv, D] VMEM
    v_scratch,  # [2, C, ps, Hkv, D] VMEM
    sems,  # DMA sems [2, C, 2]
    *,
    page_size: int,
    chunk: int,
):
    """Per-sequence grid, C pages per loop iteration: the C k/v DMAs of a
    chunk are all in flight together (hides HBM latency) and the softmax
    update contracts [Hkv, G, D] x [Hkv, C*ps, D] — C*ps context positions
    per MXU call instead of ps (the one-page version's 2x16 dots use a
    vanishing fraction of the 128x128 MXU tile and run overhead-bound)."""
    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = jnp.maximum(1, pl.cdiv(length, page_size))
    C = chunk
    n_chunks = pl.cdiv(n_pages, C)

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv
    q = q_ref[0].reshape(Hkv, G, D)  # native dtype: MXU takes bf16 directly
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def dma(slot, j, page_idx, which):
        hbm, scratch = (k_hbm, k_scratch) if which == 0 else (v_hbm, v_scratch)
        return pltpu.make_async_copy(
            hbm.at[page_tables_ref[b, page_idx]],
            scratch.at[slot, j],
            sems.at[slot, j, which],
        )

    def start_chunk(slot, c):
        for j in range(C):  # static unroll
            @pl.when(c * C + j < n_pages)
            def _(j=j):
                dma(slot, j, c * C + j, 0).start()
                dma(slot, j, c * C + j, 1).start()

    def wait_chunk(slot, c):
        for j in range(C):
            @pl.when(c * C + j < n_pages)
            def _(j=j):
                dma(slot, j, c * C + j, 0).wait()
                dma(slot, j, c * C + j, 1).wait()

    start_chunk(0, 0)

    def body(c, carry):
        m, l, acc = carry
        slot = jax.lax.rem(c, 2)
        next_slot = jax.lax.rem(c + 1, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            start_chunk(next_slot, c + 1)

        wait_chunk(slot, c)

        N = C * page_size
        # [C, ps, Hkv, D] -> [N, Hkv, D] (leading-dim merge: layout-preserving)
        # -> [Hkv, N, D] (one bf16 relayout per chunk)
        kt = jnp.transpose(k_scratch[slot].reshape(N, Hkv, D), (1, 0, 2))
        vt = jnp.transpose(v_scratch[slot].reshape(N, Hkv, D), (1, 0, 2))
        idx = c * N + jax.lax.broadcasted_iota(jnp.int32, (1, 1, N), 2)
        vidx = c * N + jax.lax.broadcasted_iota(jnp.int32, (1, N, 1), 1)

        # [Hkv, G, N] = [Hkv, G, D] x [Hkv, N, D]
        scores = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
        ) * scale
        # beyond-length/unfetched tail: mask K scores outright and zero V so
        # 0-weight garbage (or uninitialized first-call VMEM) can't poison
        # acc via 0 * NaN
        scores = jnp.where(idx < length, scores, _NEG_INF)
        vt = jnp.where(vidx < length, vt, 0)

        chunk_max = jnp.max(scores, axis=-1)  # [Hkv, G]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[..., None])  # [Hkv, G, N]
        new_l = l * corr + jnp.sum(probs, axis=-1)
        # [Hkv, G, D] = [Hkv, G, N] x [Hkv, N, D]; probs in the pages' dtype
        chunk_out = jax.lax.dot_general(
            probs.astype(kt.dtype), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        new_acc = acc * corr[..., None] + chunk_out
        return new_m, new_l, new_acc

    m0 = jnp.full((Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G), jnp.float32)
    acc0 = jnp.zeros((Hkv, G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_chunks, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[0] = out.reshape(Hq, D).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas_chunked(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,  # [P, ps, Hkv, D]
    v_pages: jnp.ndarray,  # [P, ps, Hkv, D]
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    interpret: bool = False,
) -> jnp.ndarray:
    B, Hq, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    max_pages = page_tables.shape[1]
    lengths = positions.astype(jnp.int32) + 1
    # ~256 context positions per chunk: MXU-worthy contraction length while
    # 2 x 2 x C pages of scratch stay tiny vs VMEM
    chunk = max(1, min(max_pages, -(-256 // ps)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, ps, Hkv, D), k_pages.dtype),
            pltpu.VMEM((2, chunk, ps, Hkv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, chunk, 2)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_kernel_chunked, page_size=ps, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    return kernel(page_tables.astype(jnp.int32), lengths, q, k_pages, v_pages)


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
