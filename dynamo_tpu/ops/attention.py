"""Paged attention — pure-JAX reference implementations.

The KV cache is paged: K and V each live in one **flat page pool**
``[num_layers * num_pages, page_size, num_kv_heads, head_dim]`` where layer
*l*'s physical page *p* sits at flat index ``l * num_pages + p``. A sequence's
logical block *i* maps to physical page ``page_table[i]``; because gathering
``pages[layer_offset + page_table]`` restores logical order, the flattened
context index *j* IS the token position, which keeps all masks trivially
computable under jit (static shapes, no data-dependent control flow).

Why flat (TPU note): the forward pass scans over layers with the K/V pools as
**loop carries**, so XLA performs every per-token scatter in place on the
donated buffers. Threading a per-layer ``[L, ...]`` cache through scan xs/ys
(the naive translation of a list-of-layer-tensors cache) forces XLA to
re-materialize the whole cache every step — measured 3x slower at decode on
v5e. With the flat pool nothing but the touched rows is ever written.

Page 0 of each layer (flat index ``l * num_pages``) is reserved as the
null/trash page by the allocator (dynamo_tpu/engine/page_table.py): padded
page-table entries and masked-out scatter rows all target it, so no valid data
is ever clobbered and no masked-select of old values is needed in the scatter.

Int8 KV cache (EngineConfig.kv_cache_dtype="int8"): the pools arrive as
``QuantizedPages`` (quant/kv.py) — an int8 pool plus a per-(page, token-row)
f32 scale plane. ``scatter_kv`` quantizes fresh rows on the way in (one
absmax per row; fully incremental, decode appends never requantize a page)
and ``gather_pages`` dequantizes the gathered context on the way out, so
every reference path below works unchanged. The Pallas kernels instead apply
the scales to score/prob tiles in VMEM after the int8 DMA — same algebra,
half the HBM context traffic.

The Pallas TPU kernel with the same contract lives in
dynamo_tpu/ops/pallas/paged_attention.py; this module is the semantic
reference and the CPU/test path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map

from dynamo_tpu.ops.live_rows import zero_dead_rows
from dynamo_tpu.quant.kv import QuantizedPages, quantize_kv_rows
from dynamo_tpu.utils.logging import get_logger

log = get_logger("ops.attention")

_NEG_INF = -1e30


@jax.named_scope("attn_kv")
def scatter_kv(
    k_pages,  # [LP, ps, Hkv, D] flat pool (plain or QuantizedPages)
    v_pages,  # [LP, ps, Hkv, D]
    k_new: jnp.ndarray,  # [T, Hkv, D]
    v_new: jnp.ndarray,  # [T, Hkv, D]
    phys_pages: jnp.ndarray,  # [T] int32 flat page per row (trash page for dropped rows)
    offsets: jnp.ndarray,  # [T] int32 offset within page
):
    """Scatter new K/V rows into their physical pages.

    Unconditional: the caller routes invalid rows to a trash page (see module
    docstring), so no old-value gather/select is needed — the scatter stays a
    pure in-place write on donated buffers. Int8 pools quantize each fresh
    row here (symmetric absmax over its head values) and scatter the int8
    row + its f32 scale together.
    """
    if k_pages.ndim == 3 and k_new.ndim == 3:
        # folded pool (see LlamaConfig.kv_folded): fold the NEW rows — tiny —
        # never the pool (reshaping a donated, scatter-updated pool copies it)
        k_new = k_new.reshape(k_new.shape[0], -1)
        v_new = v_new.reshape(v_new.shape[0], -1)
    if isinstance(k_pages, QuantizedPages):
        kq, ks = quantize_kv_rows(k_new)
        vq, vs = quantize_kv_rows(v_new)
        return (
            QuantizedPages(
                k_pages.q.at[phys_pages, offsets].set(kq),
                k_pages.s.at[phys_pages, offsets].set(ks),
            ),
            QuantizedPages(
                v_pages.q.at[phys_pages, offsets].set(vq),
                v_pages.s.at[phys_pages, offsets].set(vs),
            ),
        )
    k_pages = k_pages.at[phys_pages, offsets].set(k_new)
    v_pages = v_pages.at[phys_pages, offsets].set(v_new)
    return k_pages, v_pages


def write_kv_pages(
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [T] int32 absolute positions
    page_table: jnp.ndarray,  # [max_pages] int32 flat page ids (entry 0 = trash)
    valid: jnp.ndarray,  # [T] bool — False rows are routed to page_table[0]'s layer trash
    trash_page: jnp.ndarray | int = 0,  # flat index of this layer's trash page
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Position-addressed wrapper over scatter_kv for a single sequence."""
    page_size = k_pages.shape[1]
    phys = jnp.where(valid, page_table[positions // page_size], trash_page)
    offsets = jnp.where(valid, positions % page_size, 0)
    return scatter_kv(k_pages, v_pages, k_new, v_new, phys, offsets)


def gather_pages(pages, page_table: jnp.ndarray, head_dim: int | None = None) -> jnp.ndarray:
    """[P, ps, Hkv, D] gathered by [max_pages] -> [max_pages * ps, Hkv, D].

    Folded pools ([P, ps, Hkv*D], see LlamaConfig.kv_folded) unfold here —
    the GATHERED context is small, so the reshape is cheap, unlike reshaping
    the pool itself. Int8 pools dequantize the gathered context (tiny, like
    the unfold) with their per-row scales — the reference path's analogue of
    the kernels' in-VMEM dequant."""
    max_pages = page_table.shape[0]
    ps = pages.shape[1]
    if isinstance(pages, QuantizedPages):
        g = pages.q[page_table].astype(jnp.float32)  # [max_pages, ps, ...]
        s = pages.s[page_table]  # [max_pages, ps]
        g = g * s.reshape(s.shape + (1,) * (g.ndim - 2))
    else:
        g = pages[page_table]  # [max_pages, ps, ...]
    out = g.reshape(max_pages * ps, *g.shape[2:])
    if out.ndim == 2:  # folded: [S, Hkv*D] -> [S, Hkv, D]
        if head_dim is None:
            raise ValueError("folded pages need head_dim to unfold")
        return out.reshape(out.shape[0], -1, head_dim)
    return out


def _repeat_kv(x: jnp.ndarray, num_q_heads: int) -> jnp.ndarray:
    """GQA: [S, Hkv, D] -> [S, Hq, D] by repeating each kv head for its group."""
    num_kv = x.shape[1]
    if num_kv == num_q_heads:
        return x
    group = num_q_heads // num_kv
    return jnp.repeat(x, group, axis=1)


def attention_with_positions(
    q: jnp.ndarray,  # [T, Hq, D]
    k_ctx: jnp.ndarray,  # [S, Hkv, D] in logical order (index == position)
    v_ctx: jnp.ndarray,  # [S, Hkv, D]
    q_positions: jnp.ndarray,  # [T] int32
    window: int = 0,
) -> jnp.ndarray:
    """Causal attention where context index j attends iff j <= q_position[t]
    and, with a sliding ``window`` W > 0, j > q_position[t] - W (the query
    itself included, W keys in all).

    Softmax in float32; output cast back to q.dtype.
    """
    head_dim = q.shape[-1]
    k = _repeat_kv(k_ctx, q.shape[1])
    v = _repeat_kv(v_ctx, q.shape[1])
    scale = 1.0 / jnp.sqrt(jnp.float32(head_dim))
    scores = jnp.einsum("thd,shd->hts", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    ctx_idx = jnp.arange(k.shape[0], dtype=jnp.int32)
    mask = ctx_idx[None, :] <= q_positions[:, None]  # [T, S]
    if window:
        mask &= ctx_idx[None, :] > q_positions[:, None] - window
    scores = jnp.where(mask[None, :, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hts,shd->thd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_prefill_attention(
    q: jnp.ndarray,  # [T, Hq, D] (padded chunk)
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [max_pages]
    q_positions: jnp.ndarray,  # [T] absolute positions (pad rows: anything)
    window: int = 0,
) -> jnp.ndarray:
    """Chunk attention over all cached context + self (already written to pages)."""
    D = q.shape[-1]
    k_ctx = gather_pages(k_pages, page_table, head_dim=D)
    v_ctx = gather_pages(v_pages, page_table, head_dim=D)
    return attention_with_positions(q, k_ctx, v_ctx, q_positions, window)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, Hq, D]
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    page_tables: jnp.ndarray,  # [B, max_pages]
    positions: jnp.ndarray,  # [B] the query token's absolute position
    window: int = 0,
) -> jnp.ndarray:
    """Single-token-per-sequence attention for the decode batch."""
    D = q.shape[-1]

    def one(q_b, pt_b, pos_b):
        out = attention_with_positions(
            q_b[None, :, :],
            gather_pages(k_pages, pt_b, head_dim=D),
            gather_pages(v_pages, pt_b, head_dim=D),
            pos_b[None],
            window,
        )
        return out[0]

    return jax.vmap(one)(q, page_tables, positions)


def _on_tpu() -> bool:
    """True when the default backend is a TPU: the platform is "tpu" or it is
    not. Off the chip the reference path is the default and interpret mode is
    reachable only through an explicit DYNTPU_PALLAS=1."""
    return jax.default_backend() == "tpu"


_logged_paths: set = set()


def _log_path(op: str, path: str, why: str) -> None:
    """Log once per process, at trace time, which attention path a dispatch
    took and why. A gather-reference path on the chip is a performance cliff,
    so it warns; a kernel choice is informational (chip_smoke.py prints
    both)."""
    key = (op, path, why)
    if key in _logged_paths:
        return
    _logged_paths.add(key)
    level = log.warning if path == "reference" and _on_tpu() else log.info
    level("attention path: %s -> %s (%s)", op, path, why)


def pallas_flag():
    """DYNTPU_PALLAS override: True (forced on; interpret off-TPU), False
    (forced off), or None (kernel-specific default)."""
    import os

    flag = os.environ.get("DYNTPU_PALLAS")
    if flag == "0":
        return False
    if flag == "1":
        return True
    return None


def _pallas_enabled(shape_ok: bool) -> bool:
    """DYNTPU_PALLAS=1 forces the kernels on (interpret mode off the chip),
    =0 forces them off; unset, they run on a TPU backend for the shapes they
    support and nowhere else."""
    flag = pallas_flag()
    if flag is not None:
        return flag
    return _on_tpu() and shape_ok


def use_pallas_decode(head_dim: int, num_kv_heads: int) -> bool:
    """Trace-time choice of the Pallas decode kernel: on when either the
    head_dim is lane-aligned (128) or the folded-heads variant applies
    (head_dim < 128 with Hkv*D lane-aligned — TinyLlama/Qwen2-small shapes)."""
    return _pallas_enabled(
        head_dim % 128 == 0 or (num_kv_heads * head_dim) % 128 == 0
    )


def _tp_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map wrapper for pallas dispatchers (kernel outputs carry no vma
    info, so the replication check is disabled)."""
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def _head_shard_refusal(shape: str, Hq: int, Hkv: int, D: int, tp: int, folded: bool):
    """Why a kernel cannot run per head shard at this tp, or None."""
    if Hq % tp or Hkv % tp:
        return f"{shape}: tp={tp} does not divide the heads"
    if folded and (Hkv // tp) * D % 128:
        # the shard's folded lanes must stay 128-aligned or the shard kernel
        # would face the very sub-128 pool the folded layout exists to avoid
        return f"{shape}: tp={tp} leaves {(Hkv // tp) * D} folded lanes per shard (< 128-aligned)"
    return None


def _over_head_shards(fn, mesh, q, k_pages, v_pages, tables, positions, *rest):
    """Run an attention kernel per head shard: q and the pools split over
    "tp" (folded pools on their head-major lane dim), tables and positions
    replicated, as is what follows them (``rest``: a decode step's live
    rows). An int8 pool shards like the bf16 pool; its per-row scale
    plane is head-independent, so it replicates."""
    from jax.sharding import PartitionSpec as P

    pool_spec = P(None, None, "tp") if k_pages.ndim == 3 else P(None, None, "tp", None)
    if isinstance(k_pages, QuantizedPages):
        pool_spec = QuantizedPages(pool_spec, P(None, None))
    return _tp_shard_map(
        fn,
        mesh,
        in_specs=(
            P(None, "tp", None),
            pool_spec,
            pool_spec,
            P(*[None] * tables.ndim),
            P(None),
            *jax.tree.map(lambda x: P(*[None] * x.ndim), rest),
        ),
        out_specs=P(None, "tp", None),
    )(q, k_pages, v_pages, tables, positions, *rest)


def _decode_geometry(k_pages, head_dim: int, tp: int = 1) -> tuple:
    """``(page size, kv heads, head_dim, itemsize)`` of the tiled decode walk
    over such pools on one of ``tp`` head shards: what `decode_tile_pages` and
    `lookahead_window` derive its tile and window from. A pool the folded
    kernel serves (folded, or head_dim under a lane row) is one head of
    Hkv * D lanes to the walk."""
    folded = k_pages.ndim == 3
    heads = (k_pages.shape[2] // head_dim if folded else k_pages.shape[2]) // tp
    ps, itemsize = k_pages.shape[1], k_pages.dtype.itemsize
    if folded or head_dim % 128 != 0:
        return ps, 1, heads * head_dim, itemsize
    return ps, heads, head_dim, itemsize


def decode_tile_of(k_pages, head_dim: int, tp: int = 1) -> int:
    """Pages the tiled decode walk takes at a time over such pools (on one of
    ``tp`` head shards): the run the engine's allocator grows a sequence by."""
    from dynamo_tpu.ops.pallas.paged_attention import decode_tile_pages

    return decode_tile_pages(*_decode_geometry(k_pages, head_dim, tp))


@jax.named_scope("tile_runs")
def decode_tile_runs(page_tables, k_pages, head_dim: int, mesh=None):
    """Which tiles of a decode step's page tables are RUNS of the pool
    (`ops.pallas.paged_attention.tile_runs`, at the tile the dispatch below
    will walk these pools by), for `dispatch_paged_decode_attention`'s
    ``runs``: made once a decode step, outside the layer scan (a layer's
    offset moves every entry alike). None where no kernel will read it."""
    folded = k_pages.ndim == 3
    num_kv_heads = k_pages.shape[2] // head_dim if folded else k_pages.shape[2]
    tp = 1 if mesh is None else mesh.shape.get("tp", 1)
    if not use_pallas_decode(head_dim, num_kv_heads) or num_kv_heads % tp:
        return None
    from dynamo_tpu.ops.pallas.paged_attention import tile_runs

    return tile_runs(page_tables, decode_tile_of(k_pages, head_dim, tp))


@jax.named_scope("attn")
def dispatch_paged_decode_attention(q, k_pages, v_pages, page_tables, positions, mesh=None,
                                    window: int = 0, live=None, runs=None):
    """Pallas kernel on TPU, pure-JAX reference elsewhere (same contract).

    ``runs`` (`decode_tile_runs` of these tables, made once a decode step;
    None: the kernel makes it from the tables it is given) says which tiles
    are one slab of the pool, which the tiled walk fetches in one copy.

    ``live`` (`ops.live_rows.LiveRows`, made once a decode step; None: every
    row) names the batch rows that hold a sequence. The tiled kernel's grid
    is over those alone, and a row that is not live reads zero on every path.

    ``window`` W > 0 is a sliding-window layer: a query at position p sees the
    keys in (p - W, p]. The tiled kernel then walks only the tiles that hold
    such keys (a table entry behind them may be the null page: the engine has
    given the page back) and carries a name of its own on the device's
    operation line. A folded pool and the page-at-a-time kernel take no window.

    With a tensor-parallel mesh the kernel runs under shard_map: attention is
    head-parallel, so each device handles its Hq/Hkv shard with no
    communication (GSPMD cannot partition a pallas_call by itself)."""

    Hq, D = q.shape[1], q.shape[-1]
    folded = k_pages.ndim == 3
    num_kv_heads = k_pages.shape[2] // D if folded else k_pages.shape[2]
    shape = f"Hq={Hq} Hkv={num_kv_heads} D={D} ps={k_pages.shape[1]}"
    if window:
        shape += f" window={window}"

    def reference():
        return zero_dead_rows(
            paged_decode_attention(q, k_pages, v_pages, page_tables, positions, window), live)

    if not use_pallas_decode(D, num_kv_heads):
        _log_path("decode", "reference", f"{shape}: no Pallas kernel for this backend/shape")
        return reference()

    from dynamo_tpu.ops.pallas.paged_attention import (
        decode_tile_pages,
        lookahead_window,
        paged_decode_attention_pallas_folded,
        paged_decode_attention_pallas_lookahead,
    )

    # One walk for every shape class, its tile and window chosen from the
    # shapes alone: one sequence per grid program, a TILE of pages (128
    # context tokens) per loop iteration, cross-program prefetch of the next
    # sequence's first tiles (design record in ops/pallas/paged_attention.py).
    # lookahead: pools [P, ps, Hkv, D], tile and window from decode_tile_pages
    # / lookahead_window; it falls back to perseq (a page per iteration,
    # in-program double buffer only) internally when not even one window tile
    # fits the VMEM budget.
    # folded: the pool is folded or head_dim is under a lane row (Mosaic
    # can't DMA-slice sub-128-lane pools; heads live folded into the lane
    # dim). The same walk with the folded row of Hkv * D lanes taken as one
    # head, and the merge that never unfolds.
    tp = 1 if mesh is None else mesh.shape.get("tp", 1)
    use_folded = folded or D % 128 != 0
    kernel = (
        paged_decode_attention_pallas_folded
        if use_folded
        else paged_decode_attention_pallas_lookahead
    )
    interpret = not _on_tpu()
    path = f"pallas:{kernel.__name__}"
    if window:
        tiled = not use_folded and num_kv_heads % tp == 0 and lookahead_window(
            k_pages.shape[1], num_kv_heads // tp, D, k_pages.dtype.itemsize)
        if not tiled:
            _log_path("decode", "reference", f"{shape}: only the tiled kernel takes a window")
            return reference()
        kernel = functools.partial(kernel, window=window)
    if num_kv_heads % tp == 0:
        # the geometry one head shard's kernel derives, so a server log says
        # which tile width ran
        ps = k_pages.shape[1]
        geometry = _decode_geometry(k_pages, D, tp)
        ahead = lookahead_window(*geometry)
        if use_folded and not ahead:
            _log_path("decode", "reference", f"{shape}: no tile of the folded pool fits VMEM")
            return reference()
        path += (f" tile={decode_tile_pages(*geometry)}x{ps} window={ahead}"
                 if ahead else " window=0:perseq")
    path += " interpret" if interpret else ""
    if tp == 1:
        _log_path("decode", path, shape)
        return kernel(q, k_pages, v_pages, page_tables, positions, live, runs, interpret=interpret)

    why = _head_shard_refusal(shape, Hq, num_kv_heads, D, tp, folded)
    if why is not None:
        _log_path("decode", "reference", why)
        return reference()
    _log_path("decode", f"{path} shard_map tp={tp}", shape)
    return _over_head_shards(
        functools.partial(kernel, interpret=interpret),
        mesh, q, k_pages, v_pages, page_tables, positions, live, runs,
    )


#: context tokens a tile of the flash prefill kernel holds, by the width of the
#: page table it was traced for (no entry where the kernel never was): a label
#: for the scheduler's prefill dispatch span, the choice being static in the shape
prefill_tiles: dict[int, int] = {}


def use_pallas_prefill(head_dim: int, chunk_len: int, block_q: int = 128) -> bool:
    """Trace-time choice of the (unfolded) Pallas prefill kernel: lane-aligned
    head_dim and block-divisible chunks (buckets are multiples of 128 in
    practice)."""
    return chunk_len % block_q == 0 and _pallas_enabled(head_dim % 128 == 0)


@jax.named_scope("attn")
def dispatch_paged_prefill_attention(
    q, k_pages, v_pages, page_table, positions, mesh=None, window: int = 0
):
    """Chunked-prefill attention: Pallas flash kernel on TPU (context pages
    streamed HBM->VMEM in double-buffered tiles of 128 tokens, 512 under a
    page table of more than 2048 — with the next query block's short tiles
    prefetched ACROSS grid programs, see prefill_attention.py — online
    softmax, causal work bound per query block), gather-based pure-JAX
    reference elsewhere. Int8
    pools (QuantizedPages) ride the same kernels with scale rows DMA'd next
    to the pages. Under tensor parallelism the kernel runs per-head-shard
    via shard_map like the decode kernel. Folded pools (sub-128 head_dim)
    take the dedicated folded flash kernel.

    Kernel precondition (stricter than the reference): ``positions`` must be
    UNIT-STRIDE within the chunk (positions[i] = positions[0] + i), which is
    exactly what the engine's bucket-padded chunks provide. The reference
    path only needs monotone positions.

    ``window`` W > 0 is a sliding-window layer (see the decode dispatcher):
    the unfolded kernel masks by (p - W, p] per query row, skips the key tiles
    wholly behind every row's window and carries a name of its own."""
    from dynamo_tpu.ops.pallas.prefill_attention import (
        folded_prefill_block_q,
        folded_prefill_fits,
        prefill_block_q,
    )

    T, Hq, D = q.shape
    folded = k_pages.ndim == 3
    num_kv_heads = k_pages.shape[2] // D if folded else k_pages.shape[2]
    tp = 1 if mesh is None else mesh.shape.get("tp", 1)
    shape = f"T={T} Hq={Hq} Hkv={num_kv_heads} D={D} ps={k_pages.shape[1]}"
    if window:
        shape += f" window={window}"
    if folded:
        # the folded kernel's working set is several [R, F] f32 buffers per
        # head shard (R = block_q * Hq rows, F folded lanes); block_q follows
        # from the head count so that their sum stays inside scoped VMEM
        heads, F = Hq // tp, k_pages.shape[2] // tp
        block_q = folded_prefill_block_q(heads, F)
        fits = F % 128 == 0 and folded_prefill_fits(block_q, heads, F)
        enabled = _pallas_enabled(True)
    else:
        block_q = prefill_block_q(Hq // tp if Hq % tp == 0 else Hq)
        fits = True
        enabled = _pallas_enabled(D % 128 == 0)
    if not enabled:
        why = f"{shape}: no Pallas kernel for this backend/shape"
    elif window and folded:
        why = f"{shape}: the folded kernel takes no window"
    elif T % block_q:
        why = f"{shape}: chunk is not a multiple of block_q={block_q}"
    elif not fits:
        why = f"{shape}: folded working set does not fit VMEM"
    else:
        why = _head_shard_refusal(shape, Hq, num_kv_heads, D, tp, folded)
    if why is not None:
        _log_path("prefill", "reference", why)
        return paged_prefill_attention(q, k_pages, v_pages, page_table, positions, window)

    from dynamo_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas,
        paged_prefill_attention_pallas_folded,
        prefill_lookahead_window,
        prefill_tile_pages,
    )

    interpret = not _on_tpu()
    if folded:
        fn = functools.partial(
            paged_prefill_attention_pallas_folded, block_q=block_q, interpret=interpret
        )
        path = f"pallas:folded block_q={block_q}"
    else:
        fn = functools.partial(
            paged_prefill_attention_pallas, block_q=block_q, interpret=interpret, window=window
        )
        # the window one head shard's kernel derives: it takes the basic
        # in-program double buffer where not one lookahead tile fits
        ps = k_pages.shape[1]
        tile_pages = prefill_tile_pages(ps, page_table.shape[0])
        ahead = prefill_lookahead_window(
            ps, tile_pages, num_kv_heads // tp, D, k_pages.dtype.itemsize
        )
        path = "pallas:" + ("lookahead" if ahead else "basic")
        path += f" block_q={block_q} tile={tile_pages * ps}"
        prefill_tiles[page_table.shape[0]] = tile_pages * ps
    if interpret:
        path += " interpret"
    if tp == 1:
        _log_path("prefill", path, shape)
        return fn(q, k_pages, v_pages, page_table, positions)
    _log_path("prefill", f"{path} shard_map tp={tp}", shape)
    return _over_head_shards(fn, mesh, q, k_pages, v_pages, page_table, positions)
