"""Microbenchmark on the chip: the FOLDED attention kernels alone, at the shape
`lfm2-8b-a1b-d16` serves (Hq 32, Hkv 8, D 64: pools of [pages, 16, 512] in
bf16, a shuffled page table of 320 pages; PERF.md section 5, PR 42).

  decode   `paged_decode_attention_pallas_folded`, since PR 44 the tiled walk
           (a tile of 8 pages = 128 context tokens an iteration, its 16 page
           DMAs started and waited together, the next sequence's first two
           tiles in flight; a page at a time until then): batches of 64, 128
           and 256 sequences, each at a context of 512, 1536 and 4096 tokens.
           `roofline` is the K and V of every context token and a query and
           an output row a sequence (`benchmark/costs.py`
           `decode_attention_bytes`) over 819 GB/s, as a share of the time
           measured; `us_per_page` divides the time by the pages walked (K and
           V of one page are one DMA of 16 KiB each; a tile is 8 of them, so
           a tile's time is 8 times the figure: it is kept by the page so
           that the tables before and after PR 44 read side by side).
  prefill  `paged_prefill_attention_pallas_folded` at the block of query rows
           the dispatcher gives this shape (`folded_prefill_block_q`: 32):
           chunks of 128, 256 and 512 rows that start at depths 0, 1024 and
           4096. `tflops` counts the useful products (Q K^T and P V over the
           causal pairs), not the folded kernel's Hkv-fold zero products.

Timing: CALLS chained calls in one jitted `fori_loop` (the page table rolls
every call and one output element is carried, so nothing hoists), host clock
around a run that ends in `block_until_ready`, best of 5, divided by CALLS.

    chiprun -- python tools/profile_folded_attention.py   # chiprun_out/profile_folded_attention.jsonl
    JAX_PLATFORMS=cpu python tools/profile_folded_attention.py --rehearse
"""

from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from dynamo_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas_folded  # noqa: E402
from dynamo_tpu.ops.pallas.prefill_attention import (  # noqa: E402
    folded_prefill_block_q,
    paged_prefill_attention_pallas_folded,
)

REHEARSE = "--rehearse" in sys.argv
OUT = Path(__file__).resolve().parents[1] / "chiprun_out" / "profile_folded_attention.jsonl"
PS = 16
HQ, HKV, D = (4, 2, 64) if REHEARSE else (32, 8, 64)
TABLE_PAGES = 8 if REHEARSE else 320  # --max-model-len 5120
CALLS = 2 if REHEARSE else 24
BATCHES = (2,) if REHEARSE else (64, 128, 256)
CONTEXTS = (48,) if REHEARSE else (512, 1536, 4096)
CHUNKS = (64,) if REHEARSE else (128, 256, 512)
DEPTHS = (0, 32) if REHEARSE else (0, 1024, 4096)
HBM_BYTES_PER_S = 819e9  # benchmark/peaks.json, TPU v5 lite


def chained(fn, roll_axis: int):
    """CALLS calls of fn(q, k, v, tables, positions) in one program."""

    @jax.jit
    def run(q, k, v, tables, positions):
        def body(_, carry):
            tables, acc = carry
            out = fn(q, k, v, tables, positions)
            return jnp.roll(tables, 1, axis=roll_axis), acc + out[0, 0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, CALLS, body, (tables, jnp.float32(0)))[1]

    return run


def wall_us(run, *args) -> float:
    jax.block_until_ready(run(*args))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e6


def main() -> int:
    if jax.default_backend() != "tpu" and not REHEARSE:
        print("profile_folded_attention.py measures a TPU; none found", file=sys.stderr)
        return 1
    device = jax.devices()[0].device_kind
    OUT.parent.mkdir(exist_ok=True)
    lines = []

    def report(**kw):
        kw["device"] = device
        lines.append(kw)
        print(json.dumps(kw), flush=True)
        if not REHEARSE:
            OUT.write_text("".join(json.dumps(x) + "\n" for x in lines))

    rng = np.random.default_rng(42)
    F = HKV * D
    shape = dict(Hq=HQ, Hkv=HKV, D=D, page_size=PS, table_pages=TABLE_PAGES)

    # decode: every sequence's pages are its own (a pool of B x table pages would
    # not fit at 256 x 320, so sequences share a pool of 64 tables' worth and
    # each table is a shuffle of its own: the walk fetches page by page all the same)
    pool_pages = TABLE_PAGES * min(64, max(BATCHES)) + 1
    kk, kv, kq = jax.random.split(jax.random.key(42), 3)
    k = jax.random.normal(kk, (pool_pages, PS, F), jnp.bfloat16)
    v = jax.random.normal(kv, (pool_pages, PS, F), jnp.bfloat16)
    decode = functools.partial(paged_decode_attention_pallas_folded, interpret=REHEARSE)
    run = chained(decode, roll_axis=0)
    for B in BATCHES:
        q = jax.random.normal(kq, (B, HQ, D), jnp.bfloat16)
        tables = jnp.asarray(np.stack([1 + rng.permutation(pool_pages - 1)[:TABLE_PAGES]
                                       for _ in range(B)]), jnp.int32)
        for i, ctx in enumerate(CONTEXTS):
            positions = jnp.full((B,), ctx - 1, jnp.int32)
            t0 = time.perf_counter()
            us = wall_us(run, q, k, v, tables, positions)
            took = time.perf_counter() - t0
            need = B * ctx * 2 * F * 2 + 2 * B * HQ * D * 2
            pages = B * -(-ctx // PS)
            extra = {"compile_and_6_runs_s": round(took, 1)} if i == 0 else {}
            report(kernel="paged_decode_attention_pallas_folded", **shape, batch=B, context=ctx,
                   us=round(us, 1), us_per_page=round(us / pages, 4),
                   roofline=round(100 * need / HBM_BYTES_PER_S / (us * 1e-6), 2), **extra)

    # prefill: one chunk of one sequence under the cell's table width
    block_q = folded_prefill_block_q(HQ, F)
    prefill = functools.partial(paged_prefill_attention_pallas_folded, block_q=block_q,
                                interpret=REHEARSE)
    run = chained(prefill, roll_axis=0)
    table = jnp.asarray(1 + rng.permutation(TABLE_PAGES), jnp.int32)
    for T in CHUNKS:
        q = jax.random.normal(kq, (T, HQ, D), jnp.bfloat16)
        for i, start in enumerate(DEPTHS):
            if start + T > TABLE_PAGES * PS:
                continue
            positions = jnp.arange(start, start + T, dtype=jnp.int32)
            t0 = time.perf_counter()
            us = wall_us(run, q, k, v, table, positions)
            took = time.perf_counter() - t0
            pairs = T * start + T * (T + 1) / 2
            extra = {"compile_and_6_runs_s": round(took, 1)} if i == 0 else {}
            report(kernel="paged_prefill_attention_pallas_folded", **shape, block_q=block_q,
                   rows=T, start=start, context=start + T, us=round(us, 1),
                   tflops=round(4 * HQ * D * pairs / (us * 1e-6) / 1e12, 2), **extra)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
