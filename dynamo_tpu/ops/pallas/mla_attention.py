"""Pallas TPU kernel: paged MLA (latent) decode attention.

The absorbed MLA formulation makes decode attention a pure latent-space
operation: with q_cat = [q_nope·W_kb ; q_rope] (computed outside, where the
MXU-shaped einsums belong) and each cached row = [norm(latent) ; rope(k_rope)],
the score is a single dot product over latent_dim = d_c + d_r, and the output
is the probability-weighted sum of the latent part only — the per-head v-up
projection also happens outside. So the kernel streams latent pages from HBM
(page-table scalar prefetch, double-buffered VMEM scratch) exactly like the
GQA kernel in paged_attention.py, but with one fused [H, latent] x [latent,
ps] matmul per page and an accumulator over rows' first d_c dims.

Contract (matches DeepseekModel._absorbed_attention's decode path):
  q_cat [B, H, d_c + d_r] — PRE-SCALED by 1/sqrt(d_n + d_r)
  pages [P, ps, d_c + d_r], page_tables [B, max_pages], positions [B]
  -> a_lat [B, H, d_c] (unprojected attention output in latent space)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.ops.pallas.prefill_attention import PREFILL_VMEM_LIMIT_BYTES

_NEG_INF = -1e30


def _kernel(
    # scalar prefetch
    page_tables_ref,  # [B, max_pages] SMEM
    lengths_ref,  # [B] SMEM
    # inputs
    q_ref,  # [1, H, latent] VMEM (this sequence's pre-scaled folded query)
    pages_hbm,  # [P, ps, latent] HBM
    # output
    out_ref,  # [1, H, d_c] VMEM
    # scratch
    scratch,  # [2, ps, latent] VMEM
    sems,  # DMA sems [2]
    *,
    page_size: int,
    d_c: int,
):
    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = jnp.maximum(1, pl.cdiv(length, page_size))

    q = q_ref[0].astype(jnp.float32)  # [H, latent]

    def dma(slot, i):
        return pltpu.make_async_copy(
            pages_hbm.at[page_tables_ref[b, i]], scratch.at[slot], sems.at[slot]
        )

    dma(0, 0).start()

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        next_slot = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            dma(next_slot, i + 1).start()

        dma(slot, i).wait()
        rows = scratch[slot].astype(jnp.float32)  # [ps, latent]

        # [H, ps] = [H, latent] x [latent, ps]
        scores = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        idx = i * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        scores = jnp.where(idx < length, scores, _NEG_INF)

        chunk_max = jnp.max(scores, axis=-1)  # [H]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[:, None])  # [H, ps]
        new_l = l * corr + jnp.sum(probs, axis=-1)
        # accumulate over the latent part of the rows: [H, d_c]
        chunk_out = jax.lax.dot_general(
            probs, rows[:, :d_c], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        new_acc = acc * corr[:, None] + chunk_out
        return new_m, new_l, new_acc

    H = q_ref.shape[1]
    m0 = jnp.full((H,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H,), jnp.float32)
    acc0 = jnp.zeros((H, d_c), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))

    out_ref[0] = (acc / jnp.maximum(l, 1e-20)[:, None]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("d_c", "interpret"))
def paged_mla_decode_attention_pallas(
    q_cat: jnp.ndarray,  # [B, H, latent] pre-scaled
    pages: jnp.ndarray,  # [P, ps, latent]
    page_tables: jnp.ndarray,  # [B, max_pages] int32
    positions: jnp.ndarray,  # [B] int32 query positions
    d_c: int,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, latent = q_cat.shape
    P, ps, _ = pages.shape
    lengths = positions.astype(jnp.int32) + 1
    # One latent page per loop iteration through the classic in-program
    # double buffer. It has not been measured on the current chip; the GQA
    # kernel's record (paged_attention.py) says a tile of pages per iteration
    # is what pays there, and ROADMAP M2 brings that with a cell to price it.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, latent), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # latent pages stay in HBM
        ],
        out_specs=pl.BlockSpec((1, H, d_c), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ps, latent), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, d_c=d_c),
        out_shape=jax.ShapeDtypeStruct((B, H, d_c), q_cat.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )
    return kernel(page_tables.astype(jnp.int32), lengths, q_cat, pages)


def _prefill_kernel(
    # scalar prefetch
    page_table_ref,  # [max_pages] SMEM
    positions_ref,  # [T] SMEM
    # inputs
    q_ref,  # [Bq, H, latent] VMEM (pre-scaled folded queries)
    pages_hbm,  # [P, ps, latent] HBM
    # output
    out_ref,  # [Bq, H, d_c] VMEM
    # scratch
    scratch,  # [2, TP, ps, latent] VMEM
    sems,  # DMA sems [2, TP]
    *,
    page_size: int,
    max_pages: int,
    tile_pages: int,
    block_q: int,
    d_c: int,
):
    qb = pl.program_id(0)
    Bq, H, latent = q_ref.shape
    TP = tile_pages
    S = TP * page_size

    q_start = qb * block_q
    ctx_len = positions_ref[q_start + Bq - 1] + 1
    n_tiles = jnp.minimum(
        pl.cdiv(ctx_len, S), pl.cdiv(jnp.int32(max_pages * page_size), S)
    )

    q = q_ref[...].astype(jnp.float32).transpose(1, 0, 2)  # [H, Bq, latent]

    def tile_dma(buf, tile):
        copies = []
        for p in range(TP):
            idx = jnp.minimum(tile * TP + p, max_pages - 1)  # clamp; masked below
            copies.append(
                pltpu.make_async_copy(
                    pages_hbm.at[page_table_ref[idx]], scratch.at[buf, p], sems.at[buf, p]
                )
            )
        return copies

    def start(buf, tile):
        for c_ in tile_dma(buf, tile):
            c_.start()

    def wait(buf, tile):
        for c_ in tile_dma(buf, tile):
            c_.wait()

    start(0, 0)

    pos0 = positions_ref[q_start]
    iota_row = jax.lax.broadcasted_iota(jnp.int32, (Bq, S), 0)
    iota_col = jax.lax.broadcasted_iota(jnp.int32, (Bq, S), 1)
    q_pos_2d = pos0 + iota_row  # unit-stride positions within the block

    def body(t, carry):
        m, l, acc = carry
        buf = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_tiles)
        def _():
            start(jax.lax.rem(t + 1, 2), t + 1)

        wait(buf, t)
        rows = scratch[buf].astype(jnp.float32).reshape(S, latent)

        # [H, Bq, S] = [H, Bq, latent] x [S, latent]
        scores = jax.lax.dot_general(
            q, rows, (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ctx_idx = t * S + iota_col
        mask = (ctx_idx <= q_pos_2d) & (ctx_idx < max_pages * page_size)
        scores = jnp.where(mask[None], scores, _NEG_INF)

        chunk_max = jnp.max(scores, axis=-1)  # [H, Bq]
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[..., None])  # [H, Bq, S]
        new_l = l * corr + jnp.sum(probs, axis=-1)
        # [H, Bq, d_c] accumulated over the latent part only
        chunk_out = jax.lax.dot_general(
            probs, rows[:, :d_c], (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        new_acc = acc * corr[..., None] + chunk_out
        return new_m, new_l, new_acc

    m0 = jnp.full((H, Bq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, Bq), jnp.float32)
    acc0 = jnp.zeros((H, Bq, d_c), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_tiles, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]  # [H, Bq, d_c]
    out_ref[...] = out.transpose(1, 0, 2).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("d_c", "block_q", "interpret"))
def paged_mla_prefill_attention_pallas(
    q_cat: jnp.ndarray,  # [T, H, latent] pre-scaled folded queries
    pages: jnp.ndarray,  # [P, ps, latent]
    page_table: jnp.ndarray,  # [max_pages] int32
    positions: jnp.ndarray,  # [T] int32, unit-stride within the chunk
    d_c: int,
    block_q: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Chunked-prefill MLA attention (the latent-space analogue of
    ops/pallas/prefill_attention.py): latent pages stream HBM -> VMEM in
    multi-page tiles, online softmax per query block, causal work bounded per
    block. Returns a_lat [T, H, d_c].

    block_q auto-sizes to the VMEM budget when None: MLA's wide rows (d_c up
    to 512) make the f32 query + accumulator the dominant VMEM tenants, so
    real-geometry models run 64- or 32-row blocks where GQA uses 128."""
    T, H, latent = q_cat.shape
    P, ps, _ = pages.shape
    max_pages = page_table.shape[0]
    if block_q is None:
        per_row = H * (latent + d_c) * 4  # f32 query + accumulator bytes/row
        block_q = 128
        while block_q > 32 and per_row * block_q > 6 * 1024 * 1024:
            block_q //= 2
    block_q = min(block_q, T)
    while T % block_q:
        block_q //= 2
    assert block_q >= 1
    tile_pages = max(1, 128 // ps)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, H, latent), lambda qb, *_: (qb, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_q, H, d_c), lambda qb, *_: (qb, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, tile_pages, ps, latent), pages.dtype),
            pltpu.SemaphoreType.DMA((2, tile_pages)),
        ],
    )
    kernel = pl.pallas_call(
        functools.partial(
            _prefill_kernel,
            page_size=ps,
            max_pages=max_pages,
            tile_pages=tile_pages,
            block_q=block_q,
            d_c=d_c,
        ),
        out_shape=jax.ShapeDtypeStruct((T, H, d_c), q_cat.dtype),
        grid_spec=grid_spec,
        # the f32 query + accumulator stack at 16 heads x 640 lanes overruns
        # Mosaic's 16 MiB default (17.3 MiB asked for a described v5e)
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=PREFILL_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )
    return kernel(page_table.astype(jnp.int32), positions.astype(jnp.int32), q_cat, pages)
