"""Microbenchmark on the chip: the two decode kernels whose grid walks the slot
table, alone, by how much of the table is live (PR 46; PERF.md section 5).

  attention  `paged_decode_attention_pallas_lookahead` at `qwen2.5-3b`'s
             geometry (B 64, Hq 16, Hkv 2, D 128, page 16, a pool of 13312
             pages, bf16), PR 26's two batches: 15 live contexts of 200-900
             tokens + 49 empty slots (`chat`), 45 + 19 (`chat-over`), all 64
             live, and none (what a call costs before its first row); and the
             folded kernel at `lfm2-8b-a1b`'s (B 256, Hq 32, Hkv 8, D 64) with
             74 and 256 live at 1536 tokens. Live rows are scattered over the
             table as the scheduler leaves them.
  state      `ssm_state_update` at NemotronH's shape (128 slots of 128 heads
             x 64 x 128 float32) with 82 and 128 rows live, and Falcon-H1's
             (96 slots of 32 x 128 x 256) with 91 and 96.
  pair       `ops.live_rows.live_rows` alone (what a step pays once for the
             pair the kernels prefetch), at 64, 128 and 256 slots.

Each shape is timed twice: `walk = "every_row"` hands the kernel no live rows,
so a dead slot is what the engine sent until PR 46 (position 0 over the trash
page; the trash state row with decay 1 and dt x = 0), and `walk = "live"`
hands it the step's pair. In a checkout from before PR 46 the kernels take no
such argument and only the first is timed: copy this file there
(`.bench_check/parent/tools/`) for the parent's side of the table.

Timing as `profile_folded_attention.py`: CALLS chained calls in one jitted
`fori_loop` (the query's heads roll every call and the state is carried, so
nothing hoists), host clock around a run that ends in `block_until_ready`,
best of 5, divided by CALLS.

    chiprun -- python tools/profile_live_rows.py   # chiprun_out/profile_live_rows.jsonl
    JAX_PLATFORMS=cpu python tools/profile_live_rows.py --rehearse
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

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from dynamo_tpu.ops.pallas.paged_attention import (  # noqa: E402
    paged_decode_attention_pallas_folded,
    paged_decode_attention_pallas_lookahead,
)
from dynamo_tpu.ops.pallas.ssm_update import ssm_state_update_pallas  # noqa: E402

REHEARSE = "--rehearse" in sys.argv
OUT = ROOT / "chiprun_out" / "profile_live_rows.jsonl"
PS = 16
CALLS = 2 if REHEARSE else 36
HBM_BYTES_PER_S = 819e9  # benchmark/peaks.json, TPU v5 lite
#: the kernels of this checkout take the step's live rows (PR 46 and later)
TAKES_LIVE = "live" in inspect.signature(ssm_state_update_pallas).parameters
WALKS = ("every_row", "live") if TAKES_LIVE else ("every_row",)

#: (name, kernel, B, Hq, Hkv, D, folded, table pages, [(live rows, context range)])
ATTENTION = [
    ("qwen2.5-3b", paged_decode_attention_pallas_lookahead, 64, 16, 2, 128, False, 128,
     [(0, (200, 900)), (15, (200, 900)), (45, (200, 900)), (64, (200, 900))]),
    ("lfm2-8b-a1b", paged_decode_attention_pallas_folded, 256, 32, 8, 64, True, 128,
     [(74, (1536, 1536)), (256, (1536, 1536))]),
]
#: (name, slots, H, P, G, N, [live rows])
STATE = [
    ("nemotron-h", 128, 128, 64, 8, 128, [82, 128]),
    ("falcon-h1", 96, 32, 128, 2, 256, [91, 96]),
]
PAIR_SLOTS = (64, 128, 256)
if REHEARSE:
    ATTENTION = [
        ("qwen2.5-3b", paged_decode_attention_pallas_lookahead, 4, 4, 2, 128, False, 16,
         [(0, (20, 200)), (1, (20, 200)), (4, (20, 200))]),
        ("lfm2-8b-a1b", paged_decode_attention_pallas_folded, 4, 4, 2, 64, True, 16, [(3, (150, 150))]),
    ]
    STATE = [("nemotron-h", 4, 8, 8, 2, 16, [3, 4]), ("falcon-h1", 3, 4, 16, 2, 16, [2])]
    PAIR_SLOTS = (4,)


def wall_us(run, *args) -> float:
    jax.block_until_ready(run(*args))
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e6


def live_pair(alive: np.ndarray):
    from dynamo_tpu.ops.live_rows import live_rows

    return live_rows(jnp.asarray(alive))


def chained_attention(kernel):
    @jax.jit
    def run(q, k, v, tables, positions, live):
        def body(_, carry):
            q, acc = carry
            kw = {} if live is None else {"live": live}
            out = kernel(q, k, v, tables, positions, interpret=REHEARSE, **kw)
            return jnp.roll(q, 1, axis=1), acc + out[0, 0, 0].astype(jnp.float32)

        return jax.lax.fori_loop(0, CALLS, body, (q, jnp.float32(0)))[1]

    return run


def chained_state():
    @functools.partial(jax.jit, donate_argnums=0)
    def run(state, decay, dtx, b, c, rows, live):
        def body(_, carry):
            state, acc = carry
            kw = {} if live is None else {"live": live}
            y, state = ssm_state_update_pallas(state, decay, dtx, b, c, rows,
                                               interpret=REHEARSE, **kw)
            return state, acc + y[0, 0, 0]

        return jax.lax.fori_loop(0, CALLS, body, (state, jnp.float32(0)))

    return run


def main() -> int:
    if jax.default_backend() != "tpu" and not REHEARSE:
        print("profile_live_rows.py measures a TPU; none found", file=sys.stderr)
        return 1
    device = jax.devices()[0].device_kind
    OUT.parent.mkdir(exist_ok=True)
    lines = []

    def report(**kw):
        kw.update(device=device, takes_live=TAKES_LIVE)
        lines.append(kw)
        print(json.dumps(kw), flush=True)
        if not REHEARSE:
            OUT.write_text("".join(json.dumps(x) + "\n" for x in lines))

    rng = np.random.default_rng(46)
    for name, kernel, B, hq, hkv, d, folded, width, batches in ATTENTION:
        pool_pages = 13312 if not REHEARSE else B * width + 1
        page = (PS, hkv * d) if folded else (PS, hkv, d)
        kk, kv, kq = jax.random.split(jax.random.key(46), 3)
        k = jax.random.normal(kk, (pool_pages, *page), jnp.bfloat16)
        v = jax.random.normal(kv, (pool_pages, *page), jnp.bfloat16)
        q = jax.random.normal(kq, (B, hq, d), jnp.bfloat16)
        run = chained_attention(kernel)
        for n_live, (lo, hi) in batches:
            alive = np.zeros(B, bool)
            alive[rng.permutation(B)[:n_live]] = True
            # a slot that holds nobody: position 0 over the trash page
            lengths = np.where(alive, rng.integers(lo, hi + 1, B), 1)
            tables = np.zeros((B, width), np.int32)
            free = 1 + rng.permutation(pool_pages - 1)
            at = 0
            for b in np.flatnonzero(alive):
                pages = -(-int(lengths[b]) // PS)
                tables[b, :pages] = free[np.arange(at, at + pages) % free.size]
                at += pages
            need = int(lengths[alive].sum()) * 2 * hkv * d * 2 + 2 * n_live * hq * d * 2
            for walk in WALKS:
                live = live_pair(alive) if walk == "live" else None
                us = wall_us(run, q, k, v, jnp.asarray(tables),
                             jnp.asarray(lengths - 1, jnp.int32), live)
                report(kernel=kernel.__name__, shape=name, slots=B, live=n_live, walk=walk,
                       pages=at, us=round(us, 1),
                       roofline=round(100 * need / HBM_BYTES_PER_S / (us * 1e-6), 2))
        del k, v

    run = chained_state()
    for name, slots, H, P, G, N, counts in STATE:
        ks = jax.random.split(jax.random.key(7), 5)
        decay = jnp.exp(-jnp.abs(jax.random.normal(ks[0], (slots, H), jnp.float32)))
        dtx = jax.random.normal(ks[1], (slots, H, P), jnp.float32)
        b = jax.random.normal(ks[2], (slots, G, N), jnp.float32)
        c = jax.random.normal(ks[3], (slots, G, N), jnp.float32)
        for n_live in counts:
            alive = np.zeros(slots, bool)
            alive[rng.permutation(slots)[:n_live]] = True
            mask = jnp.asarray(alive)
            for walk in WALKS:
                state = jax.random.normal(ks[4], (slots + 1, H, P, N), jnp.float32).at[slots].set(0.0)
                if walk == "live":
                    args = (decay, dtx, b, c, jnp.arange(slots, dtype=jnp.int32), live_pair(alive))
                else:  # until PR 46: a dead row is the trash row's identity update
                    args = (jnp.where(mask[:, None], decay, 1.0),
                            jnp.where(mask[:, None, None], dtx, 0.0), b, c,
                            jnp.where(mask, jnp.arange(slots), slots).astype(jnp.int32), None)
                # the state is donated: every run takes the last one's
                state, _ = jax.block_until_ready(run(state, *args))
                best = 1e9
                for _ in range(5):
                    t0 = time.perf_counter()
                    state, _ = jax.block_until_ready(run(state, *args))
                    best = min(best, time.perf_counter() - t0)
                us = best / CALLS * 1e6
                need = 2 * n_live * H * P * N * 4
                report(kernel="ssm_state_update", shape=name, slots=slots, live=n_live, walk=walk,
                       us=round(us, 1), roofline=round(100 * need / HBM_BYTES_PER_S / (us * 1e-6), 2))
                del state

    if TAKES_LIVE:
        from dynamo_tpu.ops.live_rows import live_rows

        @jax.jit
        def pairs(active):
            def body(_, carry):
                active, acc = carry
                live = live_rows(active)
                return jnp.roll(active, 1), acc + live.order[0] + live.count[0]

            return jax.lax.fori_loop(0, CALLS, body, (active, jnp.int32(0)))[1]

        for slots in PAIR_SLOTS:
            active = jnp.asarray(rng.random(slots) < 0.5)
            report(kernel="live_rows", slots=slots, us=round(wall_us(pairs, active), 2))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
