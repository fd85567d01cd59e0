"""Decompose packed-prefill dispatch cost on the chip.

The bench docstring carried a standing claim — "~10 ms fixed cost per packed
prefill call, roughly flat from 128 to 512 rows" — inferred from section
walls, never measured directly. This tool states and falsifies it with three
independent measurements:

  1. Two widths through the PRODUCTION path: steady-state per-call walls of
     runner.prefill_chunk_batch (a burst of calls, one sync at the end, over
     the count) at the 128- and 512-row buckets fit cost(rows) = fixed +
     slope*rows, so ``fixed_ms`` is the rows->0 extrapolation and
     ``per_row_us`` the marginal row cost.
  2. Direct stage timings of the SAME call split the fixed cost:
     pack_prefill_lanes (host prep, pure numpy), jnp.asarray staging (H2D),
     and the dispatch-return wall (async return, no sync); the remainder vs
     the steady-state per-call cost is device execution residue.
  3. Null-kernel A/B (methodology ported from tools/profile_attn.py): chain
     paged_prefill_attention_pallas vs paged_prefill_dmaonly inside one
     jitted lax.scan and divide the wall by its length. The dmaonly
     arm keeps the exact grid + double-buffered page-DMA stream but does no
     math, so its time is the irreducible DMA floor and the difference is
     pure attention compute.

On non-TPU platforms the kernel A/B runs in interpret mode at toy geometry
(smoke only — the printed platform tag says so); the runner-path numbers are
real wall time on whatever platform is active.

Usage: python tools/profile_prefill.py [batch] [page_size] [model_id]
"""

from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")
import bench  # noqa: E402  (repo-root bench config = single source of truth)

M_CALLS = 8  # runner-path calls per timed burst
ROWS_A, ROWS_B = 128, 512  # prefill buckets measured (both in bench_config)


def best_wall(fn, reps=3):
    fn()  # compile / warm caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.models.registry import load_model

    B = int(sys.argv[1]) if len(sys.argv) > 1 else bench.HEADLINE[0]
    PS = int(sys.argv[2]) if len(sys.argv) > 2 else bench.HEADLINE[1]
    model_id = sys.argv[3] if len(sys.argv) > 3 else None
    cfg = bench.bench_config(B, PS, model_id=model_id)
    model, params = load_model(cfg.model_id)
    runner = ModelRunner(cfg, model, params)
    platform = jax.devices()[0].platform

    rng = np.random.default_rng(0)
    V = model.config.vocab_size
    pages_b = -(-ROWS_B // cfg.page_size)
    if 1 + pages_b > cfg.num_pages:
        raise SystemExit(f"pool too small: need {1 + pages_b} pages")
    # same table length for both widths so the table bucket (and thus the
    # packed-int geometry other than the row bucket) is identical — the
    # difference isolates the rows term
    page_table = 1 + np.arange(pages_b, dtype=np.int32)
    greedy = SamplingParams()  # temperature 0

    def lane(rows):
        tokens = rng.integers(1, V, size=rows, dtype=np.int32)
        # final chunk of a rows-long prompt: samples a token (device output
        # materially depends on the full forward) and writes the slot-0
        # feedback entry
        return (tokens, 0, page_table, 0, greedy, (), True)

    lanes = {rows: [lane(rows)] for rows in (ROWS_A, ROWS_B)}

    # ---- 1. two widths through the production path ----
    def run_calls(m, rows):
        toks = None
        for _ in range(m):
            toks = runner.prefill_chunk_batch(lanes[rows], N=1)
        return int(np.asarray(toks)[0])  # sync once, after the burst

    per_call = {
        rows: best_wall(lambda r=rows: run_calls(M_CALLS, r)) / M_CALLS
        for rows in (ROWS_A, ROWS_B)
    }

    slope = (per_call[ROWS_B] - per_call[ROWS_A]) / (ROWS_B - ROWS_A)
    fixed_s = per_call[ROWS_A] - slope * ROWS_A

    # ---- 2. direct stage split at the wide bucket ----
    host_prep_s = best_wall(lambda: runner.pack_prefill_lanes(lanes[ROWS_B], 1))
    ints, flts, _, _ = runner.pack_prefill_lanes(lanes[ROWS_B], 1)
    h2d_s = best_wall(
        lambda: jax.block_until_ready((jnp.asarray(ints), jnp.asarray(flts)))
    )
    # async-return wall: host prep + H2D + trace/dispatch, NO device wait
    return_s = best_wall(lambda: runner.prefill_chunk_batch(lanes[ROWS_B], N=1))
    dispatch_s = max(0.0, return_s - host_prep_s - h2d_s)
    device_residue_s = max(0.0, per_call[ROWS_B] - return_s)

    # ---- 3. null-kernel A/B: real attention vs DMA-only ----
    from dynamo_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas,
        paged_prefill_dmaonly,
    )

    mc = model.config
    if platform == "tpu":
        T, CTX, ps = 512, 3072, PS
        Hq, Hkv, D = mc.num_heads, getattr(mc, "num_kv_heads", mc.num_heads), mc.head_dim
        block_q, interp = 128, False
        n_calls = 24
    else:
        # interpret-mode smoke: proves the harness runs, not the chip
        T, CTX, ps = 16, 32, 8
        Hq, Hkv, D = 4, 2, 8
        block_q, interp = 8, True
        n_calls = 5
    n_pages = -(-CTX // ps)
    kq = jnp.asarray(rng.standard_normal((T, Hq, D)) * 0.1, jnp.bfloat16)
    k_pages = jnp.asarray(rng.standard_normal((n_pages + 2, ps, Hkv, D)) * 0.1, jnp.bfloat16)
    v_pages = jnp.asarray(rng.standard_normal((n_pages + 2, ps, Hkv, D)) * 0.1, jnp.bfloat16)
    pt = jnp.asarray(1 + np.arange(n_pages, dtype=np.int32) % (n_pages + 1))
    # the LAST chunk of a CTX-long prefill: deepest causal context per row
    pos = jnp.asarray(CTX - T + np.arange(T, dtype=np.int32))

    def make_loop(kern, n):
        @jax.jit
        def loop(q0, kp, vp, ptab, p):
            def body(qc, _):
                o = kern(qc, kp, vp, ptab, p)
                return o.astype(q0.dtype), ()
            qf, _ = jax.lax.scan(body, q0, None, length=n)
            return qf
        return loop

    def timed(kern):
        # dmaonly mirrors the basic (non-lookahead) dispatcher branch, so
        # the main arm pins lookahead=False for a like-for-like grid
        def call(q, kp, vp, ptab, p, kern=kern):
            if kern is paged_prefill_attention_pallas:
                return kern(q, kp, vp, ptab, p, block_q=block_q,
                            interpret=interp, lookahead=False)
            return kern(q, kp, vp, ptab, p, block_q=block_q, interpret=interp)

        loop = make_loop(call, n_calls)
        return best_wall(
            lambda: jax.block_until_ready(loop(kq, k_pages, v_pages, pt, pos))
        ) / n_calls

    attn_s = timed(paged_prefill_attention_pallas)
    dma_s = timed(paged_prefill_dmaonly)

    # ---- roofline: the SHARED estimator (utils/step_anatomy.py), the same
    # arithmetic dynamo_engine_prefill_roofline_fraction prices live ----
    from dynamo_tpu.utils.step_anatomy import roofline_for_runner

    roof = roofline_for_runner(runner, cfg)
    floor_s = roof.prefill_floor_seconds(ROWS_B) if roof is not None else None

    L = getattr(mc, "num_layers", 1)
    out = {
        "platform": platform,
        "B": B, "page_size": PS, "model": cfg.model_id.split(":")[0],
        "per_call_ms": {r: round(per_call[r] * 1e3, 3) for r in per_call},
        "fixed_ms": round(fixed_s * 1e3, 3),  # rows->0 extrapolation
        "per_row_us": round(slope * 1e6, 3),
        "fixed_split_ms": {
            "host_prep": round(host_prep_s * 1e3, 3),
            "h2d_staging": round(h2d_s * 1e3, 3),
            "dispatch": round(dispatch_s * 1e3, 3),
            "device_residue": round(device_residue_s * 1e3, 3),
        },
        "attn_kernel_ab": {
            "geometry": f"T={T} ctx={CTX} Hq={Hq} Hkv={Hkv} D={D} ps={ps}"
                        + (" INTERPRET-SMOKE" if interp else ""),
            "attn_us_per_layer": round(attn_s * 1e6, 1),
            "dma_floor_us_per_layer": round(dma_s * 1e6, 1),
            "attn_minus_dma_us": round((attn_s - dma_s) * 1e6, 1),
            "per_chunk_ms_x_layers": round(attn_s * L * 1e3, 3),
        },
    }
    if floor_s is not None:
        out["roofline"] = {
            "floor_ms_512rows": round(floor_s * 1e3, 3),
            "pct_of_roofline": round(100 * floor_s / per_call[ROWS_B], 1),
            "param_count": roof.param_count,
            "mxu_flops_s": roof.mxu_flops,
        }
    print(out)


if __name__ == "__main__":
    main()
