"""Microbenchmark on the chip: the expert layer's grouped matrix product, split
as PERF.md section 5 has it (PR 30).

At the shapes of `nemotron3-super-ep4` (bank [128, 1024, 2688] and
[128, 2688, 1024], bf16, 704.6 MB each: 0.86 ms at 819 GB/s), with group
sizes drawn as a served step draws them (each token chooses 22 of 512 experts,
ids 0-127 are held):

  decode      2816 static rows, 108 tokens: about 590 real rows, 4.6 an expert
  prefill     22528 static rows, 1024 tokens: about 5632 real rows, 44 an expert
  degenerate  2816 static rows, every token the same 22 experts: 6 experts live

and for each, (i) `jax.lax.ragged_dot`, (ii) a null kernel that only streams
every visited bank block through VMEM on the final kernel's grid, (iii) the
library's `megablox.gmm` at several tilings, (iv) `moe_grouped_matmul` at
several row tiles and column blocks. Also the pair w1 -> relu2 -> w2 as
`models/nemotron_h.py` runs it.

Timing: CALLS chained calls in one jitted `fori_loop` (the group sizes rotate
every call and one output element is carried, so nothing hoists), host clock
around a run that ends in `block_until_ready`, best of 5, divided by CALLS;
the lists a kernel is given are made inside the loop, as in a served step.

    chiprun -- python tools/profile_moe.py            # writes chiprun_out/profile_moe.jsonl
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

from dynamo_tpu.ops.moe import relu2  # noqa: E402
from dynamo_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas, walk  # noqa: E402

G, ROUTED, TOPK, Z, F = 128, 512, 22, 1024, 2688
CALLS = 40
#: `--rehearse`: the same walk at toy sizes in interpret mode on the CPU, to
#: find a wrong argument before a chip call does; its times mean nothing
REHEARSE = "--rehearse" in sys.argv
if REHEARSE:
    G, ROUTED, TOPK, Z, F, CALLS = 8, 32, 6, 128, 384, 2
OUT = Path("chiprun_out/profile_moe.jsonl")


def draw_sizes(rng, tokens: int, degenerate: bool = False) -> np.ndarray:
    """Rows each held expert receives from `tokens` tokens."""
    counts = np.zeros(ROUTED, np.int64)
    same = rng.choice(ROUTED // 4, min(6, G), replace=False)  # 6 of the 22 are held here
    for _ in range(tokens):
        chosen = same if degenerate else rng.choice(ROUTED, TOPK, replace=False)
        counts[chosen] += 1
    return counts[:G].astype(np.int32)


def _null_kernel(group_ref, tile_ref, offsets_ref, x_ref, w_ref, o_ref, *, tm):
    o_ref[0:8, 0:128] = (x_ref[0:8, 0:128] + w_ref[0:8, 0:128]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "block_n"))
def null_stream(rows, bank, group_sizes, *, tile_m=None, block_n=None):
    """`grouped_matmul_pallas`'s grid, block specs and lists; no product."""
    return walk(_null_kernel, "moe_null_stream", rows, bank, group_sizes, tile_m, block_n, REHEARSE)


def chained(fn):
    """CALLS calls of fn(rows, sizes) -> out in one program."""

    @jax.jit
    def run(rows, sizes, *banks):
        def body(_, carry):
            sizes, acc = carry
            out = fn(rows, sizes, *banks)
            return jnp.roll(sizes, 1), acc + out[0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, CALLS, body, (sizes, jnp.float32(0)))[1]

    return run


def wall_ms(run, *args) -> float:
    jax.block_until_ready(run(*args))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e3


def main() -> int:
    if jax.default_backend() != "tpu" and not REHEARSE:
        print("profile_moe.py measures a TPU; none found", file=sys.stderr)
        return 1
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    device = jax.devices()[0].device_kind
    OUT.parent.mkdir(exist_ok=True)
    rng = np.random.default_rng(30)
    key = jax.random.key(30)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    banks = {
        "w1": (jax.random.normal(k1, (G, Z, F), jnp.bfloat16) * 0.03).astype(jnp.bfloat16),
        "w2": (jax.random.normal(k2, (G, F, Z), jnp.bfloat16) * 0.02).astype(jnp.bfloat16),
    }
    shapes = {
        "decode": (2816, draw_sizes(rng, 108)),
        "prefill": (22528, draw_sizes(rng, 1024)),
        "degenerate": (2816, draw_sizes(rng, 128, degenerate=True)),
    }
    if REHEARSE:
        shapes = {"decode": (256, draw_sizes(rng, 10)), "degenerate": (256, draw_sizes(rng, 12, True))}
    lines = []

    def report(**kw):
        kw["device"] = device
        lines.append(kw)
        print(json.dumps(kw), flush=True)
        if not REHEARSE:
            OUT.write_text("".join(json.dumps(x) + "\n" for x in lines))

    def measure(case, bank_name, variant, fn, rows, sizes, *bank_args, check=None):
        try:
            ms = wall_ms(chained(fn), rows, sizes, *bank_args)
        except Exception as e:  # a tiling the compiler refuses is a line, not the end
            report(case=case, bank=bank_name, variant=variant, error=str(e)[:300])
            return
        extra = {}
        if check is not None:
            real = int(np.sum(np.asarray(sizes)))
            got = np.asarray(fn(rows, sizes, *bank_args)[:real], np.float32)
            extra["max_abs_diff_from_ragged_dot"] = float(np.max(np.abs(got - check[:real]))) if real else 0.0
        report(case=case, bank=bank_name, variant=variant, ms=round(ms, 4), **extra)

    for case, (M, sizes_np) in shapes.items():
        sizes = jnp.asarray(sizes_np)
        real = int(sizes_np.sum())
        report(case=case, static_rows=M, real_rows=real, live_experts=int((sizes_np > 0).sum()),
               floor_ms=round(int((sizes_np > 0).sum()) * Z * F * 2 / 819e9 * 1e3, 4))
        for bank_name, bank in banks.items():
            K, N = bank.shape[1:]
            rows = (jax.random.normal(k3, (M, K), jnp.float32)).astype(jnp.bfloat16)
            want = np.asarray(jax.lax.ragged_dot(rows, bank, sizes)[:real], np.float32)
            m = functools.partial(measure, case, bank_name)
            m("ragged_dot", lambda r, s, b: jax.lax.ragged_dot(r, b, s), rows, sizes, bank)
            tms = (128,) if case == "degenerate" else \
                (64, 128, 256, 512) if case == "prefill" else (16, 32, 64, 128, 256)
            col_blocks = (None, *(n for n in (128, 384, 896, 256, 512) if N % n == 0))
            for tm in tms:
                for tn in col_blocks if tm == 128 else (None,):
                    tag = f"tm{tm}_tn{tn or N}"
                    m(f"null_stream_{tag}",
                      lambda r, s, b, tm=tm, tn=tn: null_stream(r, b, s, tile_m=tm, block_n=tn),
                      rows, sizes, bank)
                    m(f"moe_grouped_matmul_{tag}",
                      lambda r, s, b, tm=tm, tn=tn: grouped_matmul_pallas(
                          r, b, s, tile_m=tm, block_n=tn, interpret=REHEARSE),
                      rows, sizes, bank, check=want)
            m("moe_grouped_matmul_default", lambda r, s, b: grouped_matmul_pallas(r, b, s, interpret=REHEARSE),
              rows, sizes, bank, check=want)
            tilings = [(128, K, 128), (128, K, 256), (128, 512, 896 if N % 896 == 0 else 512),
                       (32, K, 256), (512, K, 256)]
            for tiling in (tilings[1:2] if case == "degenerate" else tilings):
                m(f"megablox_gmm_{'x'.join(map(str, tiling))}",
                  lambda r, s, b, t=tiling: gmm(
                      r, b, s, preferred_element_type=jnp.bfloat16, tiling=t, interpret=REHEARSE),
                  rows, sizes, bank, check=want)
        # the pair as an expert block runs it
        rows = jax.random.normal(k4, (M, Z), jnp.float32).astype(jnp.bfloat16)
        pair = lambda mm: (lambda r, s, w1, w2: mm(relu2(mm(r, w1, s)), w2, s))  # noqa: E731
        measure(case, "w1+w2", "ragged_dot", pair(jax.lax.ragged_dot), rows, sizes, *banks.values())
        measure(case, "w1+w2", "moe_grouped_matmul_default",
                pair(functools.partial(grouped_matmul_pallas, interpret=REHEARSE)),
                rows, sizes, *banks.values())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
