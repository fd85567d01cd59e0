"""Microbenchmark on the chip: the head_dim-128 flash prefill kernel alone, by
the length of its context tile (PERF.md section 5, PR 38).

One prefill chunk's attention call (`paged_prefill_attention_pallas`, bf16
pools of page 16, a shuffled page table) at the shapes of two benchmark
configurations:

  command-a-plus-ep8  Hq 128, Hkv 8, D 128, T 512, block_q 32 (512 rows a kv
                      head), a table of 1024 pages (the engine allocates a
                      prompt's pages at admission, so every chunk of an
                      opening carries it): window 4096 at chunk starts 0 to
                      12288, the full layer at contexts 512 to 16384
  qwen2.5-3b          Hq 16, Hkv 2, D 128, block_q 128 (1024 rows a kv head),
                      contexts 256 to 2048 under a table of 128 pages and 1k
                      to 8k under one of 512

and for each: `null` (the same grid, the same page DMAs a tile in the
in-program double buffer, no arithmetic), `kernel` at tiles of 128 (what every
call took until PR 38), 256, 512 and 1024 tokens with the cross-program window
`prefill_lookahead_window` gives that tile (4 / 2 / 0 / 0 tiles), and
`kernel_no_lookahead` where there is a window to take away. `rule_tile` on
every line is what `prefill_tile_pages` chooses from the table's width.

Timing: CALLS chained calls in one jitted `fori_loop` (the page table rolls
every call and one output element is carried, so nothing hoists), host clock
around a run that ends in `block_until_ready`, best of 5, divided by CALLS.

    chiprun -- python tools/profile_prefill_attention.py   # chiprun_out/profile_prefill_attention.jsonl
    JAX_PLATFORMS=cpu python tools/profile_prefill_attention.py --rehearse

The static operation count of one tile-loop iteration (PERF.md section 5) is
re-made here, with no chip, from Mosaic's own listing:

    LIBTPU_INIT_ARGS=--xla_mosaic_dump_to=/root/scratch/mosaic JAX_PLATFORMS=cpu python -c "import tools.tpu_compile as t; t.compile_case(t.window_cases()[2])"
    python tools/profile_prefill_attention.py --count /root/scratch/mosaic/*post-apply-vector-layout-simplify*
    # one operation a vreg; the largest `scf.for` body is the tail loop's iteration
"""

from __future__ import annotations

import collections
import functools
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from dynamo_tpu.ops.pallas.prefill_attention import (  # noqa: E402
    _prefill_call,
    _tile_dma_helpers,
    _tile_scratch,
    paged_prefill_attention_pallas,
    prefill_block_q,
    prefill_tile_pages,
)

PS, D = 16, 128
CALLS = 24
#: `--rehearse`: the same walk at toy sizes in interpret mode on the CPU, to
#: find a wrong argument before a chip call does; its times mean nothing
REHEARSE = "--rehearse" in sys.argv
OUT = Path("chiprun_out/profile_prefill_attention.jsonl")
TILES = (128, 256, 512, 1024)

#: (geometry, Hq, Hkv, T, window, table pages, chunk starts): one compile a
#: group, tile and variant; the starts are values, not shapes
GROUPS = [
    ("command-a-plus-ep8", 128, 8, 512, 4096, 1024, (0, 512, 1536, 4096, 8192, 12288)),
    ("command-a-plus-ep8", 128, 8, 512, 0, 1024,
     (0, 512, 1536, 2560, 3584, 5632, 7680, 11776, 15872)),
    ("qwen2.5-3b", 16, 2, 256, 0, 128, (0,)),
    ("qwen2.5-3b", 16, 2, 512, 0, 128, (0, 512, 1536)),
    ("qwen2.5-3b", 16, 2, 512, 0, 512, (512, 1536, 3584, 7680)),
]
if REHEARSE:
    CALLS, TILES = 2, (128, 256)
    GROUPS = [("command-a-plus-ep8", 16, 2, 64, 256, 40, (0, 320)),
              ("qwen2.5-3b", 4, 2, 128, 0, 40, (128,))]


def _null_kernel(page_table_ref, positions_ref, q_ref, k_hbm, v_hbm, out_ref,
                 k_scratch, v_scratch, sems, *, tile_pages, max_pages, block_q, window):
    """`_kernel`'s walk (first tile from the window, last from the causal
    bound, one tile in flight behind the one in use) with nothing computed."""
    S = tile_pages * PS
    q_start = pl.program_id(0) * block_q
    n_tiles = jnp.minimum(
        -(-(positions_ref[q_start + block_q - 1] + 1) // S), -(-(max_pages * PS) // S)
    )
    base = jnp.maximum(0, positions_ref[q_start] - window + 1) // S if window else 0
    start, wait = _tile_dma_helpers(
        page_table_ref, [(k_hbm, k_scratch), (v_hbm, v_scratch)], [], sems, tile_pages, max_pages
    )
    start(jax.lax.rem(base, 2) if window else 0, base)

    def body(t, carry):
        @pl.when(t + 1 < n_tiles)
        def _():
            start(jax.lax.rem(t + 1, 2), t + 1)

        wait(jax.lax.rem(t, 2), t)
        return carry

    jax.lax.fori_loop(base, n_tiles, body, 0)
    out_ref[...] = q_ref[...]


@functools.partial(jax.jit, static_argnames=("block_q", "window", "tile_pages"))
def null_stream(q, k_pages, v_pages, page_table, positions, *, block_q, window, tile_pages):
    tile = (tile_pages, *k_pages.shape[1:])
    shapes, sems = _tile_scratch((2,), tile, k_pages, v_pages, None, None)
    body = functools.partial(
        _null_kernel, tile_pages=tile_pages, max_pages=page_table.shape[0],
        block_q=block_q, window=window,
    )
    return _prefill_call(body, [*shapes, sems], q, page_table, positions,
                         (k_pages, v_pages), block_q, REHEARSE, name="prefill_null_stream")


def chained(fn):
    """CALLS calls of fn(q, k, v, table, positions) in one program."""

    @jax.jit
    def run(q, k, v, table, positions):
        def body(_, carry):
            table, acc = carry
            out = fn(q, k, v, table, positions)
            return jnp.roll(table, 1), acc + out[0, 0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, CALLS, body, (table, jnp.float32(0)))[1]

    return run


def wall_us(run, *args) -> float:
    jax.block_until_ready(run(*args))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e6


def tiles_walked(T, block_q, window, start, table_pages, tile) -> int:
    """Tile-loop iterations of one call: per query block, from the tile that
    holds the first row's first key to the one that holds the last row's."""
    n = 0
    for b in range(T // block_q):
        first, last = start + b * block_q, start + (b + 1) * block_q - 1
        base = max(0, first - window + 1) // tile if window else 0
        n += min(-(-(last + 1) // tile), -(-(table_pages * PS) // tile)) - base
    return n


def count_ops(path: str) -> int:
    """Operations by name in the largest `scf.for` body of a Mosaic listing
    (`*post-apply-vector-layout-simplify*`: one operation a vreg). A region
    ends at the `}` that stands at its `scf.for`'s indentation."""
    op_at = re.compile(r"^(\s*)(?:%[^=]*= )?\"?([a-z_]+\.[a-z_0-9.]+)")
    best, open_loops = collections.Counter(), []
    for line in Path(path).read_text().splitlines():
        indent = len(line) - len(line.lstrip())
        if open_loops and line.lstrip().startswith("}") and indent == open_loops[-1][0]:
            counter = open_loops.pop()[1]
            if sum(counter.values()) > sum(best.values()):
                best = counter
            continue
        m = op_at.match(line)
        if not m:
            continue
        for _, counter in open_loops:
            counter[m.group(2)] += 1
        if m.group(2) == "scf.for":
            open_loops.append((indent, collections.Counter()))
    for op, n in best.most_common(20):
        print(f"{n:7d}  {op}")
    print(f"{sum(best.values()):7d}  operations in the largest loop body of {path}")
    return 0


def main() -> int:
    if "--count" in sys.argv:
        return count_ops(sys.argv[sys.argv.index("--count") + 1])
    if jax.default_backend() != "tpu" and not REHEARSE:
        print("profile_prefill_attention.py measures a TPU; none found", file=sys.stderr)
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

    rng = np.random.default_rng(38)
    for name, Hq, Hkv, T, window, table_pages, starts in GROUPS:
        block_q = prefill_block_q(Hq) if not REHEARSE else 32
        kq, kk, kv = jax.random.split(jax.random.key(Hq + window), 3)
        q = jax.random.normal(kq, (T, Hq, D), jnp.bfloat16)
        k = jax.random.normal(kk, (table_pages + 1, PS, Hkv, D), jnp.bfloat16)
        v = jax.random.normal(kv, (table_pages + 1, PS, Hkv, D), jnp.bfloat16)
        table = jnp.asarray(1 + rng.permutation(table_pages), jnp.int32)
        rule = prefill_tile_pages(PS, table_pages) * PS
        group = dict(geometry=name, Hq=Hq, Hkv=Hkv, T=T, block_q=block_q, window=window,
                     table_pages=table_pages, rule_tile=rule)
        kernel = functools.partial(paged_prefill_attention_pallas, block_q=block_q,
                                   window=window, interpret=REHEARSE)
        variants = [(tile, "null", functools.partial(
            null_stream, block_q=block_q, window=window, tile_pages=tile // PS)) for tile in TILES]
        variants += [(tile, "kernel", functools.partial(kernel, tile_pages=tile // PS))
                     for tile in TILES]
        # what the cross-program window is worth where a tile gets one
        # (`prefill_lookahead_window`: four tiles of 128, two of 256, none beyond)
        variants += [(tile, "kernel_no_lookahead", functools.partial(
            kernel, tile_pages=tile // PS, lookahead=False)) for tile in TILES if tile < 512]
        for tile, variant, fn in variants:
            run = chained(fn)
            for i, start in enumerate(starts):
                positions = jnp.arange(start, start + T, dtype=jnp.int32)
                walked = tiles_walked(T, block_q, window, start, table_pages, tile)
                case = dict(group, start=start, context=start + T, tile=tile, variant=variant)
                try:
                    t0 = time.perf_counter()
                    us = wall_us(run, q, k, v, table, positions)
                    took = time.perf_counter() - t0
                except Exception as e:  # a tile the compiler refuses is a line, not the end
                    report(**case, error=" ".join(str(e).split())[:300])
                    break
                extra = {"compile_and_6_runs_s": round(took, 1)} if i == 0 else {}
                report(**case, us=round(us, 1), tiles=walked, us_per_tile=round(us / walked, 3),
                       us_per_128_tokens=round(us / walked / (tile // 128), 3), **extra)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
