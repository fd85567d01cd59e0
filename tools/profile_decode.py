"""Decompose decode-window time on the chip.

Methodology: per-step cost = t(one 64-step window) / 64, the host clock
around a dispatch whose token output is fetched to the host (which waits for
the device). A profiler trace gives device time directly and will replace
this (ROADMAP S0).

Reports, per decode step at the bench config (1.3B llama-shaped):
  window   — full dispatch_decode_window (model + sampling + feedback)
  model    — scan of model.decode alone (argmax feedback, donated kv)
  attention (separate: tools/profile_attn.py)

Usage: python tools/profile_decode.py [batch] [page_size]
"""

from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")
import bench  # noqa: E402  (repo-root bench config = single source of truth)


def main():
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.engine.model_runner import ModelRunner
    from dynamo_tpu.models.registry import load_model

    B = int(sys.argv[1]) if len(sys.argv) > 1 else bench.HEADLINE[0]
    PS = int(sys.argv[2]) if len(sys.argv) > 2 else bench.HEADLINE[1]
    cfg = bench.bench_config(B, PS)
    model, params = load_model(cfg.model_id)
    runner = ModelRunner(cfg, model, params)
    ctx = bench.PROMPT_LEN + bench.DECODE_TOKENS // 2

    pages_per_seq = -(-ctx // cfg.page_size)
    pt = np.zeros((B, cfg.max_pages_per_seq), np.int32)
    npp = pages_per_seq + 1  # room for the 64-step window's growth
    if 1 + B * npp > cfg.num_pages:
        raise SystemExit(f"pool too small: need {1 + B * npp} pages, have {cfg.num_pages}")
    for i in range(B):
        pt[i, :npp] = 1 + i * npp + np.arange(npp)
    positions = np.full(B, ctx, np.int32)
    active = np.ones(B, bool)
    limits = np.full(B, npp * PS - 2, np.int32)
    temps = np.zeros(B, np.float32)
    top_ks = np.zeros(B, np.int32)
    top_ps = np.ones(B, np.float32)

    def best_wall(fn, reps=4):
        fn()  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    # ---- full window through the runner (greedy, like the bench) ----
    def window(num_steps):
        toks = runner.dispatch_decode_window(
            positions, pt, active, limits, temps, top_ks, top_ps, num_steps
        )
        return np.asarray(jax.device_get(toks))

    per_window = best_wall(lambda: window(64)) / 64

    # ---- model.decode alone, argmax feedback, donated kv/state ----
    pt_j = jnp.asarray(pt)
    act = jnp.asarray(active)

    def model_only_impl(params, kv, toks0, pos0, *, num_steps):
        def body(carry, _):
            kv_, toks, pos = carry
            logits, kv_ = model.decode(params, kv_, toks, pos, pt_j, act)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
            return (kv_, toks, pos + 1), toks

        (kv, _, _), ys = jax.lax.scan(body, (kv, toks0, pos0), None, length=num_steps)
        return ys, kv

    model_only_jit = jax.jit(
        lambda p, kv, t, q: model_only_impl(p, kv, t, q, num_steps=64),
        donate_argnums=(1,),
    )

    def model_only():
        ys, runner.kv_cache = model_only_jit(
            runner.params, runner.kv_cache, jnp.zeros(B, jnp.int32),
            jnp.asarray(positions),
        )
        return np.asarray(jax.device_get(ys))

    per_model = best_wall(model_only) / 64

    # bytes-moved floor from the SHARED estimator (utils/step_anatomy.py) —
    # the same arithmetic the live dynamo_engine_roofline_fraction gauge and
    # the bench step_anatomy section use, so this one-off tool and the
    # standing plane can never disagree on what "the roofline" means
    from dynamo_tpu.utils.step_anatomy import roofline_for_runner

    roof = roofline_for_runner(runner, cfg)
    if roof is None:
        raise SystemExit("runner/model cannot price the roofline")
    live_pages = B * pages_per_seq
    floor = roof.step_floor_seconds(live_pages)
    if floor is None:
        raise SystemExit(f"no published peaks for {roof.device_kind!r}: no roofline")
    out = {
        "B": B, "page_size": PS, "ctx": ctx,
        "per_step_ms": {
            "window": round(per_window * 1e3, 3),
            "model_only": round(per_model * 1e3, 3),
            "sampling_and_feedback": round((per_window - per_model) * 1e3, 3),
        },
        "window_tok_s": round(B / per_window, 1),
        "hbm_floor_ms": round(floor * 1e3, 3),
        "pct_of_roofline": round(100 * floor / per_window, 1),
        "param_bytes": roof.param_bytes,
        "kv_bytes_per_step": live_pages * roof.page_bytes,
        "hbm_bw_bytes_s": roof.hbm_bw,
    }
    print(out)


if __name__ == "__main__":
    main()
