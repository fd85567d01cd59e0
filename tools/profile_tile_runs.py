"""Microbenchmark on the chip: the tiled decode attention walk alone, by how
many of a context's tiles are RUNS of the pool (PR 47; PERF.md section 5).

A tile whose 8 table entries are first, first + 1, ... is one slab of the pool,
and since PR 47 the walk fetches it as one copy a pool where it paid 16. Same
contexts, three page layouts:

  run        every tile of every sequence is an aligned slab (what the
             allocator gives since PR 47), the last one whole
  scattered  pages drawn at random from the pool (the steady state until PR 47)
  half       each tile one or the other, by a coin

for `paged_decode_attention_pallas_lookahead` at `qwen2.5-3b`'s geometry (B 64,
Hq 16, Hkv 2, D 128, page 16, a pool of 13312 pages, bf16; contexts of 200-900
tokens; 64, 45 and 15 live rows and none: what a call costs before its first
row) and the folded kernel at `lfm2-8b-a1b`'s (B 256, Hq 32, Hkv 8, D 64, every
context 1536 tokens). Beside each, a NULL kernel: the same grid, window, tail
and copies and no arithmetic, so `null` is what the DMA stream costs alone at
that layout and `real - null` what the merge adds. And `tile_runs` alone: what a
step pays once for the flags.

In a checkout from before PR 47 the kernels take no `runs` and walk every
layout a page a copy: copy this file there (`.bench_check/parent/tools/`) for
the parent's side of the table; the null kernel is this file's own and reads
the same on both sides.

Timing as `profile_live_rows.py`: CALLS chained calls in one jitted `fori_loop`
(the query's heads roll every call, so nothing hoists), host clock around a run
that ends in `block_until_ready`, best of 5, divided by CALLS.

    chiprun -- python tools/profile_tile_runs.py   # chiprun_out/profile_tile_runs[.<side>].jsonl
    JAX_PLATFORMS=cpu python tools/profile_tile_runs.py --rehearse
"""

from __future__ import annotations

import functools
import inspect
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from dynamo_tpu.ops.live_rows import live_rows  # noqa: E402
from dynamo_tpu.ops.pallas import paged_attention  # noqa: E402
from dynamo_tpu.ops.pallas.paged_attention import (  # noqa: E402
    decode_tile_pages,
    lookahead_window,
    paged_decode_attention_pallas_folded,
    paged_decode_attention_pallas_lookahead,
)

REHEARSE = "--rehearse" in sys.argv
SIDE = sys.argv[sys.argv.index("--side") + 1] if "--side" in sys.argv else ""
OUT = ROOT / "chiprun_out" / f"profile_tile_runs{'.' + SIDE if SIDE else ''}.jsonl"
PS = 16
CALLS = 2 if REHEARSE else 36
HBM_BYTES_PER_S = 819e9  # benchmark/peaks.json, TPU v5 lite
#: the walk of this checkout fetches a run as one copy (PR 47 and later)
TAKES_RUNS = "runs" in inspect.signature(paged_decode_attention_pallas_lookahead).parameters
LAYOUTS = ("run", "scattered", "half")

#: (name, kernel, B, Hq, Hkv, D, folded, table pages, pool pages, context range, [live rows])
SHAPES = [
    ("qwen2.5-3b", paged_decode_attention_pallas_lookahead, 64, 16, 2, 128, False, 128, 13312,
     (200, 900), [64, 45, 15, 0]),
    ("lfm2-8b-a1b", paged_decode_attention_pallas_folded, 256, 32, 8, 64, True, 128, 13312 * 2,
     (1536, 1536), [256]),
]
if REHEARSE:
    SHAPES = [
        ("qwen2.5-3b", paged_decode_attention_pallas_lookahead, 4, 4, 2, 128, False, 32, 160,
         (20, 450), [4, 2, 0]),
        ("lfm2-8b-a1b", paged_decode_attention_pallas_folded, 3, 4, 2, 64, True, 16, 64,
         (150, 150), [3]),
    ]


def wall_us(run, *args) -> float:
    jax.block_until_ready(run(*args))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e6


def flags_of(tables: np.ndarray, TP: int) -> np.ndarray:
    """[B * tiles] int32: the tiles whose entries are consecutive (numpy twin
    of `paged_attention.tile_runs`, so a checkout without it can run the null
    kernel)."""
    B, width = tables.shape
    tiles = tables.reshape(B, width // TP, TP)
    return np.all(np.diff(tiles, axis=-1) == 1, axis=-1).reshape(-1).astype(np.int32)


def lay_out(rng, layout: str, lengths: np.ndarray, alive: np.ndarray, width: int,
            pool_pages: int, TP: int) -> tuple[np.ndarray, int]:
    """(page tables, pages the live contexts hold): a live row's tiles are
    aligned slabs of the pool (in random order: neighbours in a sequence are
    not neighbours in the pool) or pages drawn at random; a run's last tile is
    the sequence's whole, as the allocator reserves it."""
    tables = np.zeros((len(lengths), width), np.int32)
    slabs = 1 + rng.permutation(pool_pages // TP - 1)  # slab 0 holds the trash page
    n_slab = 0
    held = 0
    for b in np.flatnonzero(alive):
        pages = -(-int(lengths[b]) // PS)
        held += pages
        for t in range(-(-pages // TP)):
            first = int(slabs[n_slab % slabs.size]) * TP
            n_slab += 1
            as_run = layout == "run" or (layout == "half" and rng.random() < 0.5)
            if as_run:
                tables[b, t * TP:(t + 1) * TP] = first + np.arange(TP)
            else:  # the slab's pages in a random order that is no run, the sequence's alone
                n = min(TP, pages - t * TP)
                order = rng.permutation(TP)
                while n > 1 and np.all(np.diff(order[:n]) == 1):
                    order = rng.permutation(TP)
                tables[b, t * TP:t * TP + n] = first + order[:n]
    if layout == "scattered":  # not even the slab is shared: shuffle all held pages over the pool
        mask = tables > 0
        tables[mask] = 1 + rng.permutation(pool_pages - 1)[: int(mask.sum())]
    return tables, held


def _null_kernel(tables_ref, lengths_ref, order_ref, runs_ref, q_ref, k_hbm, v_hbm, out_ref,
                 k_pre, v_pre, k_tail, v_tail, sems_pre, sems_tail, *, TP, W, tiles_per_seq):
    """`_kernel_lookahead`'s grid, window, tail and copies with no arithmetic:
    a run moves as one copy a pool, any other tile a page a copy."""
    i = pl.program_id(0)
    nb = pl.num_programs(0)
    b = order_ref[i]
    par = jax.lax.rem(i, 2)

    def pages_of(row):
        return jnp.maximum(1, pl.cdiv(lengths_ref[row], PS))

    n_pages = pages_of(b)
    n_tiles = pl.cdiv(n_pages, TP)

    def tile_dmas(op, row, t, npg, pools, at, sems):
        left = npg - t * TP
        whole = runs_ref[row * tiles_per_seq + t] != 0
        first = tables_ref[row, t * TP]
        if op == "wait":  # one wait takes a full tile's bytes off, however they came
            whole, first = whole | (left >= TP), 0

        @pl.when(whole)
        def _():
            for c, (hbm, scratch) in enumerate(pools):
                getattr(pltpu.make_async_copy(hbm.at[pl.ds(first, TP)], at(scratch), sems.at[c]), op)()

        def page(p, _):
            for c, (hbm, scratch) in enumerate(pools):
                getattr(pltpu.make_async_copy(hbm.at[tables_ref[row, t * TP + p]],
                                              at(scratch).at[p], sems.at[c]), op)()
            return 0

        jax.lax.fori_loop(0, jnp.where(whole, 0, jnp.minimum(TP, left)), page, 0)

    pre = [(k_hbm, k_pre), (v_hbm, v_pre)]
    tail = [(k_hbm, k_tail), (v_hbm, v_tail)]

    def pre_dmas(op, parity, j, row, npg):
        tile_dmas(op, row, j, npg, pre, lambda s: s.at[parity, j], sems_pre.at[parity, j])

    def tail_dmas(op, slot, t):
        tile_dmas(op, b, t, n_pages, tail, lambda s: s.at[slot], sems_tail.at[slot])

    def issue_pre(row, parity):
        npg = pages_of(row)
        jax.lax.fori_loop(0, jnp.minimum(W, pl.cdiv(npg, TP)),
                          lambda j, _: (pre_dmas("start", parity, j, row, npg), 0)[1], 0)

    pl.when(i == 0)(lambda: issue_pre(b, 0))
    pl.when(i + 1 < nb)(lambda: issue_pre(order_ref[i + 1], 1 - par))
    pl.when(W < n_tiles)(lambda: tail_dmas("start", W % 2, W))
    jax.lax.fori_loop(0, jnp.minimum(W, n_tiles),
                      lambda j, _: (pre_dmas("wait", par, j, b, n_pages), 0)[1], 0)

    def tail_body(t, _):
        slot = jax.lax.rem(t, 2)
        pl.when(t + 1 < n_tiles)(lambda: tail_dmas("start", 1 - slot, t + 1))
        tail_dmas("wait", slot, t)
        return 0

    jax.lax.fori_loop(W, n_tiles, tail_body, 0)
    out_ref[...] = q_ref[...]


def null_walk(q, k, v, tables, positions, live, runs, *, interpret=False):
    B, Hq, D = q.shape
    page = k.shape[1:]
    lanes = (1, page[1]) if k.ndim == 3 else page[1:]
    TP = decode_tile_pages(PS, *lanes, k.dtype.itemsize)
    W = lookahead_window(PS, *lanes, k.dtype.itemsize)

    def tile_scratch(*lead):
        return [pltpu.VMEM((*lead, TP, *page), k.dtype), pltpu.VMEM((*lead, TP, *page), v.dtype)]

    def row_block(i, tables, lengths, order, runs):
        return order[i], 0, 0

    return pl.pallas_call(
        functools.partial(_null_kernel, TP=TP, W=W, tiles_per_seq=tables.shape[1] // TP),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(live.count[0],),
            in_specs=[pl.BlockSpec((1, Hq, D), row_block),
                      pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, Hq, D), row_block),
            scratch_shapes=[*tile_scratch(2, W), *tile_scratch(2),
                            pltpu.SemaphoreType.DMA((2, W, 2)), pltpu.SemaphoreType.DMA((2, 2))],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="null_tile_walk",
    )(tables, positions + 1, live.order, runs, q, k, v)


def chained(kernel):
    @jax.jit
    def run(q, k, v, tables, positions, live, runs):
        def body(_, carry):
            q, acc = carry
            kw = {"runs": runs} if kernel is null_walk or TAKES_RUNS else {}
            out = kernel(q, k, v, tables, positions, live, interpret=REHEARSE, **kw)
            return jnp.roll(q, 1, axis=1), acc + out[0, 0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, CALLS, body, (q, jnp.float32(0)))[1]

    return run


def main() -> int:
    if jax.default_backend() != "tpu" and not REHEARSE:
        print("profile_tile_runs.py measures a TPU; none found", file=sys.stderr)
        return 1
    device = jax.devices()[0].device_kind
    OUT.parent.mkdir(exist_ok=True)
    lines = []

    def report(**kw):
        kw.update(device=device, takes_runs=TAKES_RUNS, side=SIDE)
        lines.append(kw)
        print(json.dumps(kw), flush=True)
        if not REHEARSE:
            OUT.write_text("".join(json.dumps(x) + "\n" for x in lines))

    for name, kernel, B, hq, hkv, d, folded, width, pool_pages, (lo, hi), counts in SHAPES:
        rng = np.random.default_rng(47)
        page = (PS, hkv * d) if folded else (PS, hkv, d)
        TP = decode_tile_pages(PS, *((1, hkv * d) if folded else (hkv, d)), 2)
        kk, kv, kq = jax.random.split(jax.random.key(47), 3)
        k = jax.random.normal(kk, (pool_pages, *page), jnp.bfloat16)
        v = jax.random.normal(kv, (pool_pages, *page), jnp.bfloat16)
        q = jax.random.normal(kq, (B, hq, d), jnp.bfloat16)
        walks = {"real": chained(kernel), "null": chained(null_walk)}
        for n_live in counts:
            alive = np.zeros(B, bool)
            alive[rng.permutation(B)[:n_live]] = True
            lengths = np.where(alive, rng.integers(lo, hi + 1, B), 1)
            live = live_rows(jnp.asarray(alive))
            need = int(lengths[alive].sum()) * 2 * hkv * d * 2 + 2 * n_live * hq * d * 2
            for layout in LAYOUTS if n_live else ("scattered",):
                tables, held = lay_out(rng, layout, lengths, alive, width, pool_pages, TP)
                flags = flags_of(tables, TP)
                tiles = sum(-(-(-(-int(n) // PS)) // TP) for n in lengths[alive])
                for walk, run in walks.items():
                    try:
                        us = wall_us(run, q, k, v, jnp.asarray(tables),
                                     jnp.asarray(lengths - 1, jnp.int32), live, jnp.asarray(flags))
                    except Exception as e:  # one walk the compiler refuses does not end the table
                        report(kernel=kernel.__name__, shape=name, live=n_live, layout=layout,
                               walk=walk, error=f"{type(e).__name__}: {str(e)[:300]}")
                        continue
                    report(kernel=kernel.__name__, shape=name, slots=B, live=n_live, layout=layout,
                           walk=walk, pages=held, tiles=tiles, run_tiles=int(flags.sum()),
                           us=round(us, 1),
                           roofline=round(100 * need / HBM_BYTES_PER_S / (us * 1e-6), 2))
        del k, v

    if TAKES_RUNS:

        @functools.partial(jax.jit, static_argnames=("TP",))
        def flags(tables, TP):
            def body(_, carry):
                tables, acc = carry
                return jnp.roll(tables, 1, axis=0), acc + paged_attention.tile_runs(tables, TP)[0]

            return jax.lax.fori_loop(0, CALLS, body, (tables, jnp.int32(0)))[1]

        for B, width in ((4, 32),) if REHEARSE else ((64, 128), (128, 128), (256, 320)):
            tables = jnp.asarray(np.random.default_rng(1).integers(1, 13312, (B, width)), jnp.int32)
            report(kernel="tile_runs", slots=B, width=width,
                   us=round(wall_us(functools.partial(flags, TP=8), tables), 2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
