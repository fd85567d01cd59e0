"""Microbenchmark on the chip: the device time of one `qwen2.5-3b` packed
prefill, by what the pack is made of (PERF.md section 5, PR 40).

One call of `LlamaModel.prefill_packed` at the benchmark's full configuration
(`benchmark/configs/qwen2.5-3b.json`: 36 layers, 6.17 GB of bfloat16 weights
drawn on the device, a page table of 128 pages of 16 tokens a lane, a pool
of just the pages 8 lanes need: its size moves no time) at

  blocks      N = 1..8 lanes of 128 rows: what the scheduler's block packer
              emits since PR 40 (`EngineConfig.prefill_block`, `pack_blocks`)
  rectangles  [1,512], [2,512], [4,256], [2,256], [1,256]: what the packer
              emitted before it for the same chunks (whole chunks a lane, all
              padded to the longest one's bucket, N a power of two)

Every lane is full and starts at position 0 (a first chunk): the rows
computed are N x T on every line, so two lines compare by their rows. Each
lane has pages of its own, so the scatter and the kernel's page DMAs are a
real pack's.

Timing: CALLS chained calls in one jitted `fori_loop` (the cache is carried
and the tokens roll every call, so nothing hoists), host clock around a run
that ends in `block_until_ready`, best of 5, divided by CALLS, as
tools/profile_prefill_attention.py does.

    chiprun -- python tools/profile_prefill_pack.py   # chiprun_out/profile_prefill_pack.jsonl
    JAX_PLATFORMS=cpu python tools/profile_prefill_pack.py --rehearse
"""

from __future__ import annotations

import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from dynamo_tpu.models.llama import LlamaConfig, LlamaModel  # noqa: E402

CALLS = 8
PS, NUM_PAGES, TABLE = 16, 1 + 8 * 128, 128
#: `--rehearse`: the same walk on the tiny model on the CPU, to find a wrong
#: argument before a chip call does; its times mean nothing
REHEARSE = "--rehearse" in sys.argv
OUT = Path("chiprun_out/profile_prefill_pack.jsonl")
CONFIG = Path(__file__).resolve().parents[1] / "benchmark/configs/qwen2.5-3b.json"

#: (variant, N, T)
SHAPES = [("blocks", n, 128) for n in range(1, 9)] \
    + [("rectangle", n, t) for n, t in ((1, 512), (2, 512), (4, 256), (2, 256), (1, 256))]
if REHEARSE:
    CALLS, NUM_PAGES, TABLE = 2, 64, 8
    SHAPES = [("blocks", 1, 16), ("blocks", 3, 16), ("rectangle", 1, 32)]


def chained(model, N: int, T: int):
    """CALLS packs of [N, T] in one program: (params, kv, tokens) -> (kv, scalar)."""
    positions = jnp.tile(jnp.arange(T, dtype=jnp.int32), (N, 1))
    valid = jnp.ones((N, T), bool)
    last = jnp.full((N,), T - 1, jnp.int32)
    # lane j's rows go to pages of its own: 1 + j * TABLE .. (page 0 is the trash page)
    tables = 1 + jnp.arange(N, dtype=jnp.int32)[:, None] * TABLE + jnp.arange(TABLE, dtype=jnp.int32)[None, :]

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(params, kv, tokens):
        def body(_, carry):
            kv, tokens, acc = carry
            logits, kv = model.prefill_packed(params, kv, tokens, positions, tables, valid, last)
            return kv, jnp.roll(tokens, 1, axis=1), acc + logits[0, 0]

        kv, _, acc = jax.lax.fori_loop(0, CALLS, body, (kv, tokens, jnp.float32(0)))
        return kv, acc

    return run


def wall_ms(run, params, kv, tokens) -> tuple:
    """(best of 5 runs in ms a call, the cache handed on: every run donates it)."""
    kv, acc = jax.block_until_ready(run(params, kv, tokens))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        kv, acc = jax.block_until_ready(run(params, kv, tokens))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e3, kv


def main() -> int:
    if jax.default_backend() != "tpu" and not REHEARSE:
        print("profile_prefill_pack.py measures a TPU; none found", file=sys.stderr)
        return 1
    device = jax.devices()[0].device_kind
    config = LlamaConfig.tiny() if REHEARSE else LlamaConfig.from_hf_config(json.loads(CONFIG.read_text()))
    model = LlamaModel(config)
    params = jax.jit(model.init_params)(jax.random.key(40))
    kv = model.init_kv_cache(NUM_PAGES, PS)
    OUT.parent.mkdir(exist_ok=True)
    lines = []
    for variant, N, T in SHAPES:
        tokens = jax.random.randint(jax.random.key(N * T), (N, T), 1, config.vocab_size, jnp.int32)
        t0 = time.perf_counter()
        ms, kv = wall_ms(chained(model, N, T), params, kv, tokens)
        line = dict(variant=variant, N=N, T=T, rows=N * T, ms=round(ms, 3), us_per_row=round(ms * 1e3 / (N * T), 2),
                    compile_and_6_runs_s=round(time.perf_counter() - t0, 1), calls=CALLS, device=device)
        lines.append(line)
        print(json.dumps(line), flush=True)
        if not REHEARSE:
            OUT.write_text("".join(json.dumps(x) + "\n" for x in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
