"""Microbenchmark: paged decode attention kernel variants on the chip.

Headline bench geometry (bench.py): B=64, Hq=16, Hkv=8, D=128, ps=128,
24-layer flat pool (224 pages/layer), context ~256 tokens (2 pages/seq).

Timing method: chain N kernel calls inside one jitted lax.scan (output q
feeds the next call), take the host clock around a run that ends in
block_until_ready, and divide by N. A profiler trace gives the kernel's
device time directly and will replace this (ROADMAP S0).

Usage: python tools/profile_attn.py [B] [ps] [ctx]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

B = int(sys.argv[1]) if len(sys.argv) > 1 else 64
PS = int(sys.argv[2]) if len(sys.argv) > 2 else 128
CTX = int(sys.argv[3]) if len(sys.argv) > 3 else 256
Hq, Hkv, D = 16, 8, 128
L = 24
PAGES_PER_LAYER = 224
MAX_PAGES = 8  # max_model_len 1024 / ps 128
N_CALLS = 144


def _wall(fn, *args):
    jax.block_until_ready(fn(*args))  # compile
    best = 1e9
    for _ in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def timed(make_loop, *args):
    """Per-call time: best wall of one N_CALLS-long chained scan over N."""
    return _wall(make_loop(N_CALLS), *args) / N_CALLS


def _null_kernel(
    page_tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, out_ref,
    k_scratch, v_scratch, sems, *, page_size: int,
):
    """Null hypothesis: perseq's exact grid + 2-page double-buffered DMA
    stream with NO attention math — isolates the irreducible DMA cost."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = jnp.maximum(1, pl.cdiv(length, page_size))

    def k_dma(slot, i):
        return pltpu.make_async_copy(
            k_hbm.at[page_tables_ref[b, i]], k_scratch.at[slot], sems.at[slot, 0]
        )

    def v_dma(slot, i):
        return pltpu.make_async_copy(
            v_hbm.at[page_tables_ref[b, i]], v_scratch.at[slot], sems.at[slot, 1]
        )

    k_dma(0, 0).start()
    v_dma(0, 0).start()

    def body(i, acc):
        slot = jax.lax.rem(i, 2)
        next_slot = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            k_dma(next_slot, i + 1).start()
            v_dma(next_slot, i + 1).start()

        k_dma(slot, i).wait()
        v_dma(slot, i).wait()
        # consume one lane per page so the waits can't be elided; no matmuls,
        # no softmax, no casts
        return acc + k_scratch[slot, 0].astype(jnp.float32) + v_scratch[slot, 0].astype(jnp.float32)

    Hkv, D = k_hbm.shape[2], k_hbm.shape[3]
    acc = jax.lax.fori_loop(0, n_pages, body, jnp.zeros((Hkv, D), jnp.float32))
    out_ref[0] = jnp.broadcast_to(
        acc[:1] * 1e-6, out_ref.shape[1:]
    ).astype(out_ref.dtype)


def paged_decode_dmaonly(q, k_pages, v_pages, page_tables, positions):
    import functools as ft

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hq, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    lengths = positions.astype(jnp.int32) + 1
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ps, Hkv, D), k_pages.dtype),
            pltpu.VMEM((2, ps, Hkv, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = pl.pallas_call(
        ft.partial(_null_kernel, page_size=ps),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
    )
    return kernel(page_tables.astype(jnp.int32), lengths, q, k_pages, v_pages)


def _perseq_variant_kernel(
    page_tables_ref, lengths_ref, q_ref, k_hbm, v_hbm, out_ref,
    k_scratch, v_scratch, sems, *, page_size: int, cast_f32: bool):
    """perseq with the two per-page VPU costs toggled: the f32 casts of the
    whole K/V page and the [ps,Hkv,D]->[Hkv,ps,D] relayout."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _NEG_INF = -1e30
    b = pl.program_id(0)
    length = lengths_ref[b]
    n_pages = jnp.maximum(1, pl.cdiv(length, page_size))

    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = k_hbm.shape[2]
    G = Hq // Hkv

    q = q_ref[0].reshape(Hkv, G, D)
    if cast_f32:
        q = q.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    def k_dma(slot, i):
        return pltpu.make_async_copy(
            k_hbm.at[page_tables_ref[b, i]], k_scratch.at[slot], sems.at[slot, 0]
        )

    def v_dma(slot, i):
        return pltpu.make_async_copy(
            v_hbm.at[page_tables_ref[b, i]], v_scratch.at[slot], sems.at[slot, 1]
        )

    k_dma(0, 0).start()
    v_dma(0, 0).start()

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        next_slot = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            k_dma(next_slot, i + 1).start()
            v_dma(next_slot, i + 1).start()

        k_dma(slot, i).wait()
        v_dma(slot, i).wait()

        k_page = k_scratch[slot]  # [ps, Hkv, D]
        v_page = v_scratch[slot]
        if cast_f32:
            k_page = k_page.astype(jnp.float32)
            v_page = v_page.astype(jnp.float32)
        kt = jnp.transpose(k_page, (1, 0, 2))  # [Hkv, ps, D]
        vt = jnp.transpose(v_page, (1, 0, 2))
        scores = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale

        idx = i * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
        scores = jnp.where(idx < length, scores, _NEG_INF)

        chunk_max = jnp.max(scores, axis=-1)
        new_m = jnp.maximum(m, chunk_max)
        corr = jnp.exp(m - new_m)
        probs = jnp.exp(scores - new_m[..., None])  # [Hkv, G, ps] f32
        new_l = l * corr + jnp.sum(probs, axis=-1)
        chunk_out = jax.lax.dot_general(
            probs if cast_f32 else probs.astype(vt.dtype), vt,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        new_acc = acc * corr[..., None] + chunk_out
        return new_m, new_l, new_acc

    m0 = jnp.full((Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, G), jnp.float32)
    acc0 = jnp.zeros((Hkv, G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, acc0))

    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out_ref[0] = out.reshape(Hq, D).astype(out_ref.dtype)


def make_perseq_variant(cast_f32: bool):
    import functools as ft

    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def run(q, k_pages, v_pages, page_tables, positions):
        B, Hq, D = q.shape
        P, ps, Hkv, _ = k_pages.shape
        lengths = positions.astype(jnp.int32) + 1
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, Hq, D), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ps, Hkv, D), k_pages.dtype),
                pltpu.VMEM((2, ps, Hkv, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        kernel = pl.pallas_call(
            ft.partial(_perseq_variant_kernel, page_size=ps, cast_f32=cast_f32),
            out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
            grid_spec=grid_spec,
        )
        return kernel(page_tables.astype(jnp.int32), lengths, q, k_pages, v_pages)

    return run


def main():
    rng = np.random.default_rng(0)
    LP = L * PAGES_PER_LAYER
    k_pages = jnp.asarray(rng.standard_normal((LP, PS, Hkv, D)) * 0.1, jnp.bfloat16)
    v_pages = jnp.asarray(rng.standard_normal((LP, PS, Hkv, D)) * 0.1, jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, Hq, D)) * 0.1, jnp.bfloat16)
    n_pages_per_seq = -(-CTX // PS)
    # sequential allocation, like the page allocator's steady state
    pt = np.zeros((B, MAX_PAGES), np.int32)
    nxt = 1
    for b in range(B):
        for i in range(n_pages_per_seq):
            pt[b, i] = nxt
            nxt += 1
    page_tables = jnp.asarray(pt)
    positions = jnp.full(B, CTX - 1, jnp.int32)

    from dynamo_tpu.ops.pallas import paged_attention as pa

    # _nt (no-transpose via dot_general batch dims ((0,),(1,))) variants were
    # Mosaic-ILLEGAL (tpu.matmul requires leading batch dims) — deleted; the
    # transpose stays.
    variants = {
        "perseq": pa.paged_decode_attention_pallas,
        "dmaonly": paged_decode_dmaonly,
        "perseq_bf16": make_perseq_variant(cast_f32=False),
        "chunked": pa.paged_decode_attention_pallas_chunked,
        "grouped": pa.paged_decode_attention_pallas_grouped,
    }
    # production cross-program-prefetch kernel (r5 default for GQA decode)
    variants["lookahead"] = pa.paged_decode_attention_pallas_lookahead
    if hasattr(pa, "paged_decode_attention_pallas_fused"):
        variants["fused"] = pa.paged_decode_attention_pallas_fused

    # numerics gate: every variant must agree with perseq before its timing
    # is taken seriously (dmaonly is exempt — it computes garbage by design)
    ref = np.asarray(
        variants["perseq"](q, k_pages, v_pages, page_tables, positions),
        np.float32,
    )
    bad = set()
    for name, kern in variants.items():
        if name in ("perseq", "dmaonly"):
            continue
        try:
            out = np.asarray(kern(q, k_pages, v_pages, page_tables, positions), np.float32)
            err = float(np.max(np.abs(out - ref)))
            print(f"{name:14s}: max|diff vs perseq| = {err:.4f}", flush=True)
            if err > 0.05:
                bad.add(name)
        except Exception as e:
            print(f"{name:14s}: NUMERICS FAILED {type(e).__name__}: {str(e)[:160]}", flush=True)
            bad.add(name)

    results = {}
    for name, kern in variants.items():
        if name in bad:
            print(f"{name:10s}: SKIPPED (failed numerics gate)", flush=True)
            continue
        def make_loop(n, kern=kern):
            @jax.jit
            def loop(q0, kp, vp, ptab, pos):
                def body(qc, _):
                    o = kern(qc, kp, vp, ptab, pos)
                    return o, ()
                qf, _ = jax.lax.scan(body, q0, None, length=n)
                return qf
            return loop

        try:
            t = timed(make_loop, q, k_pages, v_pages, page_tables, positions)
            results[name] = t
            # per decode STEP (x L layers) attention cost
            print(f"{name:10s}: {t*1e6:8.1f} us/call -> {t*L*1e3:6.2f} ms/step (x{L} layers)", flush=True)
        except Exception as e:
            print(f"{name:10s}: FAILED {type(e).__name__}: {str(e)[:200]}", flush=True)

    # roofline context: KV bytes actually needed per call
    kv_bytes = B * n_pages_per_seq * PS * Hkv * D * 2 * 2
    from dynamo_tpu.utils.step_anatomy import device_peaks

    hbm_bw, _ = device_peaks()  # None for a device with no published peaks
    floor = f"{kv_bytes / hbm_bw * 1e6:.1f} us at {hbm_bw / 1e9:.0f} GB/s" if hbm_bw else "no peaks for this device"
    print(f"\nKV traffic/call: {kv_bytes/1e6:.1f} MB -> {floor}")
    print(f"DMA issues/call (perseq): {B * n_pages_per_seq * 2}")

    # matmul reference: one [B,2048]x[2048,5632] (the MLP gate shape) per call
    w = jnp.asarray(rng.standard_normal((2048, 5632)) * 0.02, jnp.bfloat16)
    h = jnp.asarray(rng.standard_normal((B, 2048)) * 0.1, jnp.bfloat16)

    def make_mm_loop(n):
        @jax.jit
        def mm_loop(h0, w0):
            def body(hc, _):
                o = hc @ w0
                return (o @ w0.T * 1e-3).astype(jnp.bfloat16), ()
            hf, _ = jax.lax.scan(body, h0, None, length=n)
            return hf
        return mm_loop

    t = timed(make_mm_loop, h, w)
    mm_bytes = 2048 * 5632 * 2 * 2
    print(f"matmul pair [B,2048]x[2048,5632]x2: {t*1e6:.1f} us/iter "
          f"(weight bytes {mm_bytes/1e6:.0f} MB"
          + (f" -> floor {mm_bytes / hbm_bw * 1e6:.1f} us)" if hbm_bw else ")"))


if __name__ == "__main__":
    main()
