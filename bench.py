"""Benchmark: serving throughput on the TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Measures end-to-end engine decode throughput (output tokens/sec/chip) through
the full serving stack — async engine, continuous batching scheduler, paged KV
cache, fused sampling — on a 1.3B-parameter Llama-shaped model (bf16) that
fits a single v5e chip alongside its KV cache.

vs_baseline: the reference publishes no absolute numbers (BASELINE.json
published = {}), so the ratio is against PARITY_TARGET_TOK_S, a
roofline-derived parity bar for this config on v5e: weights ~2.5 GiB bf16,
v5e HBM BW 819 GB/s -> ~330 weight-bound steps/s ceiling; at batch 8 a
well-tuned serving stack should clear ~1000 out tok/s/chip.

It measures a TPU and fails where JAX finds none, unless a CPU smoke is asked
for by name (``python bench.py --cpu-smoke``: tiny shapes, counts and parity
only, every number labeled with the platform). A section that fails makes the
run exit non-zero; the sections that finished are still printed. Nothing here
has run on the current chip yet: PERF.md says what has been measured.

Sections prefer DETERMINISTIC signals (recompute token counts, restored-block
counts) priced at in-section measured rates over raw wall medians wherever a
ratio is the deliverable. tools/profile_prefill.py splits the per-call cost of
a packed prefill into a rows->0 fixed intercept plus a per-row slope, stage
timings (host-prep / H2D staging / dispatch / device residue) and a
null-kernel A/B (paged_prefill_dmaonly) that separates attention compute from
its DMA floor; lanes pack to a 1024-row budget, and prefill_pipeline_depth
(default 2) dispatch-aheads packed calls so the fixed cost overlaps device
time (bench section prefill_anatomy proves parity + fewer forced stalls). The
headline config batches 64 sequences so weight reads amortize; bs=8 is kept as
a secondary round-over-round continuity metric.
"""

from __future__ import annotations

import asyncio
import json
import math
import time

import numpy as np

PARITY_TARGET_TOK_S = 1000.0

PROMPT_LEN = 128
DECODE_TOKENS = 128

# (batch, page_size): headline serving config + round-1-comparable config
HEADLINE = (64, 128)
CONTINUITY = (8, 16)
# round-1 measured continuity value (bs8): the fixed round-over-round anchor
R01_VALUE_BS8 = 1341.84


def bench_config(batch: int = 64, page_size: int = 64, model_id: str | None = None):
    from dynamo_tpu.engine.config import EngineConfig

    return EngineConfig(
        model_id=model_id or json_model_id(),
        page_size=page_size,
        num_pages=max(1024 * 16 // page_size, batch * 28 * 16 // page_size),
        max_seqs=batch,
        max_model_len=1024,
        prefill_buckets=(128, 256, 512),
        tp=1,
        # swept on v5e: decode_steps x pipeline_depth over {16,32,64} x {2,3,4}
        # all within ~3% - dispatch latency is hidden; 32x3 best (re-confirmed
        # r5 at lookahead-kernel speeds: 32x3 7527 > 16x4 7512 > 64x3 7437)
        decode_steps=32,
        pipeline_depth=3,
    )


def json_model_id() -> str:
    # ~1.3B params: llama-shaped (GQA 4:1), bf16
    cfg = {
        "vocab_size": 32000,
        "hidden_size": 2048,
        "intermediate_size": 5632,
        "num_layers": 24,
        "num_heads": 16,
        "num_kv_heads": 8,
        "head_dim": 128,
        "dtype": "bf16",
    }
    return "tiny:" + json.dumps(cfg)


def quant_model_id() -> str:
    """The headline llama-1.3b geometry served weight-only int8: identical
    shapes/seed to json_model_id(), so the two engines hold the SAME random
    weights before quantization and the int8-vs-bf16 comparison isolates the
    quantization itself."""
    cfg = json.loads(json_model_id().split(":", 1)[1])
    cfg["quantize"] = "int8_wo"
    return "tiny:" + json.dumps(cfg)


def mla_model_id() -> str:
    """DeepSeek-MLA geometry at ~1.3B (bf16, single v5e): real MLA head
    shapes (kv_lora_rank 512, rope 64, nope/v 128 — DeepSeek-V2 values,
    reference: the vLLM patch's deepseek_v2.py), MLP kept dense
    (first_k_dense_replace = num_layers) so the section isolates the MLA
    decode kernel; MoE is priced by moe_decode below."""
    cfg = {
        "vocab_size": 32000, "hidden_size": 2048, "intermediate_size": 5632,
        "num_layers": 24, "num_heads": 16, "q_lora_rank": None,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "first_k_dense_replace": 24,
        "n_routed_experts": 4, "num_experts_per_tok": 2, "n_shared_experts": 1,
        "moe_intermediate_size": 32, "dtype": "bf16",
    }
    return "tiny-mla:" + json.dumps(cfg)


def moe_model_id() -> str:
    """Mixtral geometry scaled to ~2.3B total / top-2-of-8 routing (bf16):
    per-step active weights ~ attention + 2/8 of expert banks, but at serving
    batch sizes nearly every expert is hit, so the decode roofline reads the
    full expert banks each step."""
    cfg = {
        "vocab_size": 32000, "hidden_size": 1024, "intermediate_size": 3584,
        "num_layers": 12, "num_heads": 8, "num_kv_heads": 4, "head_dim": 128,
        "num_experts": 8, "num_experts_per_tok": 2,
        "dtype": "bf16",
    }
    return "tiny-moe:" + json.dumps(cfg)


async def run_config(
    batch: int,
    page_size: int,
    rounds: int = 3,
    prompt_len: int = PROMPT_LEN,
    decode_tokens: int = DECODE_TOKENS,
    max_model_len: int = 1024,
    model_id: str | None = None,
    vocab: int = 31000,
) -> dict:
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    cfg = bench_config(batch, page_size, model_id=model_id)
    if max_model_len != cfg.max_model_len:
        import dataclasses

        need_pages = batch * (-(-(prompt_len + decode_tokens) // page_size) + 4)
        cfg = dataclasses.replace(
            cfg,
            max_model_len=max_model_len,
            num_pages=max(cfg.num_pages, need_pages),
            # 1024 cap: long prompts run as chunked prefill
            prefill_buckets=(128, 256, 512, 1024),
        )
    engine = AsyncJaxEngine(cfg)
    await engine.start()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, vocab, prompt_len).tolist() for _ in range(batch)]
    best = None
    round_tok_s = []

    async def one(i: int, warmup: bool, rnd: int = 0):
        req = EngineRequest(
            request_id=f"{'w' if warmup else 'b'}{rnd}-{i}",
            token_ids=prompts[i] if not warmup else rng.integers(1, vocab, prompt_len).tolist(),
            sampling=SamplingParams(
                temperature=0.0,
                max_tokens=8 if warmup else decode_tokens,
                ignore_eos=True,
            ),
        )
        n = 0
        ttft = None
        t0 = time.monotonic()
        async for out in engine.generate(req):
            if out.token is not None:
                if ttft is None:
                    ttft = time.monotonic() - t0
                n += 1
        return n, ttft

    try:
        # warmup: compile prefill buckets + decode, then one full-length pass
        # so the page allocator reaches its steady-state churn pattern (the
        # first measured round otherwise under-reports while the pool
        # fills/evicts)
        await asyncio.gather(*[one(i, warmup=True) for i in range(batch)])
        for i in range(batch):
            prompts[i] = rng.integers(1, vocab, prompt_len).tolist()
        await asyncio.gather(*[one(i, warmup=False, rnd=99) for i in range(batch)])

        # best of N measured rounds (fresh prompts each round so the prefix
        # cache never helps)
        for rnd in range(rounds):
            for i in range(batch):
                prompts[i] = rng.integers(1, vocab, prompt_len).tolist()
            t0 = time.monotonic()
            results = await asyncio.gather(*[one(i, warmup=False, rnd=rnd) for i in range(batch)])
            elapsed = time.monotonic() - t0
            total_tokens = sum(n for n, _ in results)
            ttfts = [t for _, t in results if t is not None]
            round_tok_s.append(round(total_tokens / elapsed, 2))
            if best is None or total_tokens / elapsed > best[0]:
                best = (total_tokens / elapsed, total_tokens, elapsed, ttfts)
        # per-stage latency attribution (engine StageStats, cumulative over
        # warmup + all rounds): lets a round's artifact answer whether TTFT
        # sits in queue wait, prefill dispatch, or device sync without a
        # re-run under DYNTPU_TRACE
        stage = engine.stage_snapshot()
    finally:
        # a cancelled/timed-out section must still release the engine (HBM,
        # device buffers) before the next section starts its own
        await engine.shutdown()
    tok_s, total_tokens, elapsed, ttfts = best
    return {
        "tok_s": round(tok_s, 2),
        "total_output_tokens": total_tokens,
        "elapsed_s": round(elapsed, 3),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
        "batch": batch,
        "page_size": page_size,
        "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
        "rounds": round_tok_s,
        "stage_breakdown": stage,
    }


async def _request(eng, rid, prompt, max_tokens=8, holder="", holder_blocks=0):
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    req = EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True),
        kv_holder_addr=holder, kv_holder_blocks=holder_blocks,
    )
    t0 = time.monotonic()
    ttft, toks, cached = None, [], 0
    async for out in eng.generate(req):
        if out.token is not None and ttft is None:
            ttft = time.monotonic() - t0
        if out.token is not None:
            toks.append(out.token)
        cached = max(cached, out.cached_tokens)
    if ttft is None:
        raise RuntimeError(f"bench request {rid} yielded no tokens")
    return toks, ttft, cached


def _parity_config(**over):
    from dynamo_tpu.engine.config import EngineConfig

    d = dict(
        model_id=json_model_id(), page_size=64, num_pages=384, max_seqs=4,
        max_model_len=4096, prefill_buckets=(512, 1024, 2048),
        decode_steps=8, pipeline_depth=2,
    )
    d.update(over)
    return EngineConfig(**d)


async def run_routing_parity(n_workers=2, sessions=4, turns=3, plen=3072) -> dict:
    """BASELINE.md parity checkpoint: KV-aware routing vs random on
    prefix-heavy multi-turn traffic across two colocated engines.

    Reports wall TTFT and recomputed prefill tokens — the actual TTFT driver
    the reference's 3x claim comes from."""
    import gc
    import random

    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer, RouterEvent

    async def workload(kv_aware: bool):
        indexer = KvIndexer(kv_block_size=64)
        engines = []
        try:
            for i in range(n_workers):
                sink = (lambda wid: (
                    lambda ev: indexer.apply_event(RouterEvent(worker_id=wid, event=ev))
                ))(i)
                eng = AsyncJaxEngine(_parity_config(), kv_event_sink=sink)
                await eng.start()
                engines.append(eng)
            rng = random.Random(7)
            rr = np.random.default_rng(3)
            # prompts long enough that a full recompute (~plen tokens of
            # prefill chip time) dominates a request's fixed cost
            hist = {s: rr.integers(1, 31000, plen).tolist() for s in range(sessions)}
            seed_ttfts = []
            for s in range(sessions):
                _, st, _ = await _request(engines[s % n_workers], f"seed{kv_aware}-{s}", hist[s])
                seed_ttfts.append(st)
            seed_ttft = float(np.median(seed_ttfts))
            ttfts, recompute = [], 0
            for t in range(turns):
                for s in range(sessions):
                    prompt = hist[s]
                    if kv_aware:
                        scores = indexer.find_matches_for_request(prompt).scores
                        wid = max(scores, key=scores.get) if scores else rng.randrange(n_workers)
                    else:
                        wid = rng.randrange(n_workers)
                    toks, ttft, cached = await _request(engines[wid], f"{kv_aware}r{t}-{s}", prompt)
                    ttfts.append(ttft)
                    recompute += len(prompt) - cached
                    hist[s] = (prompt + toks + [11 + t])[:3600]
        finally:
            for e in engines:
                try:
                    await e.shutdown()
                except Exception:
                    import traceback

                    traceback.print_exc()
            engines.clear()
            gc.collect()
        return float(np.median(ttfts)), recompute, seed_ttft

    t_kv, rc_kv, seed_kv = await workload(True)
    t_rand, rc_rand, seed_rand = await workload(False)
    # Two views of the same claim:
    #   measured — the ratio of the wall TTFT medians.
    #   derived — the deterministic recomputed-token counts priced at the
    #     per-token prefill rate measured in-section from the seeding
    #     requests (fresh full prefills). Recompute counts are exact and
    #     repeatable.
    n_req = sessions * turns
    rate = min(seed_kv, seed_rand) / plen  # s per prefill token
    der_kv = rc_kv / n_req * rate
    der_rand = rc_rand / n_req * rate
    return {
        "ttft_kv_aware_ms": round(t_kv * 1e3, 1),
        "ttft_random_ms": round(t_rand * 1e3, 1),
        "ttft_ratio": round(t_rand / t_kv, 2),
        "recomputed_prefill_tokens_kv_aware": rc_kv,
        "recomputed_prefill_tokens_random": rc_rand,
        "recompute_ratio": round(rc_rand / max(1, rc_kv), 1),
        "prefill_rate_us_per_token": round(rate * 1e6, 1),
        "ttft_derived_kv_aware_ms": round(der_kv * 1e3, 1),
        "ttft_derived_random_ms": round(der_rand * 1e3, 1),
        # denominator floored at one KV block's prefill so a perfect cache
        # (rc_kv ~ 0) can't divide by ~0
        "ttft_ratio_derived": round(der_rand / max(der_kv, rate * 64), 2),
        "target": "ttft_ratio_derived >= 3 (BASELINE.md: reference claims 3x TTFT)",
        "note": (
            "derived = deterministic recompute counts x in-section measured "
            "prefill rate; ttft_ratio = wall TTFT medians"
        ),
    }


def _measure_restore(eng) -> dict:
    """Time the two restore-path components on the device the engine runs on:

      host (measured): a batch of block bytes resident in host memory ->
        jitted scatter into the donated pool, i.e. the host->HBM transfer
        plus the scatter — what a host-tier restore pays.
      scatter (measured): the same batch already device-resident — the
        on-chip half alone.

    Per-block costs are one 16-block call over 16, per-call overhead
    included (a real restore pays it too)."""
    import time as _time

    import jax
    import jax.numpy as jnp

    one = eng.runner.extract_pages(np.asarray([1], np.int32))
    axis = getattr(eng.runner.model, "wire_n_axis", 2)
    nbytes_block = one.nbytes
    n = 16
    ids = np.arange(1, n + 1, dtype=np.int32)
    data = np.concatenate([one] * n, axis=axis)

    def timed(data, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = _time.monotonic()
            eng.runner.inject_pages(ids, data)
            jax.block_until_ready(eng.runner.kv_cache)
            best = min(best, _time.monotonic() - t0)
        return best

    host_s = timed(data)
    staged = jax.block_until_ready(jnp.asarray(data))  # staging paid outside the timing
    scatter_s = timed(staged)
    return {
        "block_wire_bytes": int(nbytes_block),
        "host_restore_bw_GBps_measured": round(n * nbytes_block / host_s / 1e9, 3),
        "scatter_bw_GBps_measured_device_staged": round(n * nbytes_block / scatter_s / 1e9, 2),
        "host_restore_s_per_block_measured": host_s / n,
        "scatter_s_per_block_measured": scatter_s / n,
    }


async def run_offload_parity(sessions=3, plen=512) -> dict:
    """BASELINE.md parity checkpoint: host-DRAM KV offload on multi-turn
    revisit traffic, device pool sized so revisits need the host tier.

    Wall TTFT is reported beside the deterministic signal:
    restored-vs-recomputed prefix tokens."""
    import dataclasses
    import gc

    from dynamo_tpu.engine.engine import AsyncJaxEngine

    base_cfg = _parity_config(
        num_pages=20, max_seqs=2, max_model_len=1024, prefill_buckets=(64, 512)
    )

    async def workload(host_blocks: int):
        eng = AsyncJaxEngine(
            dataclasses.replace(base_cfg, host_cache_blocks=host_blocks)
        )
        await eng.start()
        try:
            rr = np.random.default_rng(5)
            prompts = {s: rr.integers(1, 31000, plen).tolist() for s in range(sessions)}
            for s in range(sessions):
                await _request(eng, f"h{host_blocks}-v1-{s}", prompts[s])
            # measured recompute cost of one plen-token prefill: M concurrent
            # FRESH prompts serialize on the chip, so wall/M is one prompt's
            # share. The revisit TTFT medians below can't give this number —
            # the device pool retains the most recent sessions' blocks, so
            # the median revisit is often a cache hit, not a recompute.
            Mf = 4
            fresh = [rr.integers(1, 31000, plen).tolist() for _ in range(Mf)]
            t0 = time.monotonic()
            await asyncio.gather(*[
                _request(eng, f"h{host_blocks}-fresh-{j}", fresh[j], max_tokens=1)
                for j in range(Mf)
            ])
            recompute_s = (time.monotonic() - t0) / Mf
            ttfts, cacheds = [], []
            for s in range(sessions):
                _, ttft, cached = await _request(eng, f"h{host_blocks}-v2-{s}", prompts[s])
                ttfts.append(ttft)
                cacheds.append(cached)
            loads = eng.offload.loads if eng.offload else 0
            restore = _measure_restore(eng) if host_blocks else None
        finally:
            await eng.shutdown()
            del eng
            gc.collect()
        return (float(np.median(ttfts)), int(np.sum(cacheds)), loads,
                recompute_s, restore)

    t_on, cached_on, loads, _, restore = await workload(256)
    t_off, cached_off, _, recompute_s, _ = await workload(0)
    eps = 2e-3
    # Restore cost of one revisit, composed from the in-section measurement:
    # block loads x the measured per-block host -> HBM restore (transfer +
    # scatter), against the measured recompute prefill time.
    mcfg = json.loads(base_cfg.model_id.split(":", 1)[1])
    block_bytes = (
        base_cfg.page_size * mcfg["num_kv_heads"] * mcfg["head_dim"] * 2 * 2
        * mcfg["num_layers"]
    )
    loads_per_revisit = loads / max(1, sessions)
    restore_s_projected = loads_per_revisit * (
        restore["host_restore_s_per_block_measured"] if restore else 0.0
    )
    projected_ratio = recompute_s / max(restore_s_projected, eps)
    return {
        "ttft_offload_ms": round(t_on * 1e3, 1),
        "ttft_no_offload_ms": round(t_off * 1e3, 1),
        "revisit_tokens_restored_with_offload": cached_on,
        "revisit_tokens_restored_without": cached_off,
        "host_block_loads": loads,
        "restore_path_measured": restore,
        "projection": {
            "block_bytes": block_bytes,
            "loads_per_revisit": round(loads_per_revisit, 1),
            "restore_ms_projected": round(restore_s_projected * 1e3, 2),
            "recompute_ms_measured": round(recompute_s * 1e3, 1),
            "ttft_ratio_projected": round(projected_ratio, 2),
            "restore_bw_source": "host-resident inject measured in-section (host->HBM transfer + scatter)",
        },
        "target": "ttft_ratio_projected >= 1.4 (BASELINE.md: reference claims 1.4x TTFT)",
        "note": (
            "the projection prices a revisit's block loads at the per-block "
            "host->HBM restore cost measured in restore_path_measured, "
            "against the measured recompute prefill time"
        ),
    }


async def run_kv_tiers(sessions=3, plen=512, fillers=6) -> dict:
    """Third KV tier (engine/kv_store.py): disk-backed cold-session resume.

    Multi-turn sessions generate, then PARK while filler traffic churns the
    HBM pool and a deliberately small host tier — demoting the parked
    sessions' blocks host -> disk. The resume turn revisits the parked
    prompts: the tiered arm restores from disk through the FETCHING_KV
    deferred-admission path, the control arm (no off-device tiers)
    recomputes the prefill. Headline is the resume-TTFT ratio
    (tiered/recompute, lower is better), exact greedy parity between the
    arms, and the disk byte cap held under churn.

    Both arms run an int8 KV cache so the disk tier's int8 wire format is a
    bit-exact roundtrip — parity is exact, not approximate. The platform
    tag rides the artifact; both arms pay the same fixed per-request cost."""
    import dataclasses
    import gc

    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.kv_store import DiskKvStore, _block_disk_nbytes, disk_block_bytes

    base_cfg = _parity_config(
        num_pages=20, max_seqs=2, max_model_len=1024, prefill_buckets=(64, 512),
        kv_cache_dtype="int8",
    )
    mcfg = json.loads(base_cfg.model_id.split(":", 1)[1])
    blk = disk_block_bytes(
        base_cfg.page_size, mcfg["num_kv_heads"], mcfg["head_dim"],
        mcfg["num_layers"],
    )
    # generous budget for the resume arms (parked sessions + filler churn
    # both fit: the cap-under-churn proof runs store-level below where the
    # eviction victim choice can't race the resume measurement)
    disk_budget = blk * (sessions + fillers + 2) * (plen // base_cfg.page_size + 1)

    async def workload(tiered: bool):
        cfg = dataclasses.replace(
            base_cfg,
            host_cache_blocks=8 if tiered else 0,
            disk_cache_bytes=disk_budget if tiered else 0,
        )
        eng = AsyncJaxEngine(cfg)
        await eng.start()
        try:
            rr = np.random.default_rng(11)
            prompts = {s: rr.integers(1, 31000, plen).tolist() for s in range(sessions)}
            turn1 = {}
            for s in range(sessions):
                toks, _, _ = await _request(eng, f"kt{int(tiered)}-v1-{s}", prompts[s])
                turn1[s] = toks
            # park: filler churn evicts the parked sessions from HBM and
            # (tiered arm) pushes their host copies down to disk
            for j in range(fillers):
                filler = rr.integers(1, 31000, plen).tolist()
                await _request(eng, f"kt{int(tiered)}-fill-{j}", filler, max_tokens=1)
            # resume: the same conversations come back cold
            ttfts, cacheds, turn2 = [], [], {}
            for s in range(sessions):
                toks, ttft, cached = await _request(
                    eng, f"kt{int(tiered)}-v2-{s}", prompts[s]
                )
                ttfts.append(ttft)
                cacheds.append(cached)
                turn2[s] = toks
            snap = eng.resource_snapshot()
        finally:
            await eng.shutdown()
            del eng
            gc.collect()
        return (float(np.median(ttfts)), int(np.sum(cacheds)), turn1, turn2, snap)

    t_tier, cached_tier, t1_tier, t2_tier, snap = await workload(True)
    t_rec, cached_rec, _, t2_rec, _ = await workload(False)
    if not snap.get("disk_restore_hits"):
        raise RuntimeError(
            f"tiered arm never took the disk restore path (snapshot: "
            f"spills={snap.get('disk_spills')} restores={snap.get('disk_restores')} "
            f"fallbacks={snap.get('disk_restore_fallbacks')})"
        )
    if snap.get("disk_bytes_resident", 0) > snap.get("disk_budget_bytes", 0):
        raise RuntimeError("disk tier over budget after churn")
    # exact greedy parity: the resumed continuation must match both the
    # recompute arm AND the never-parked turn-1 output (same prompt, greedy)
    parity = sum(
        1 for s in t2_tier
        if t2_tier[s] == t2_rec.get(s) and t2_tier[s] == t1_tier.get(s)
    ) / max(1, len(t2_tier))
    # cap-under-churn proof at the store level: a 4-block budget churned
    # with 16 distinct blocks must hold the cap and actually evict
    rr = np.random.default_rng(23)
    shape = (4, 2, 2, base_cfg.page_size, 16)
    probe = rr.standard_normal(shape).astype(np.float32)
    probe_bytes = _block_disk_nbytes(probe)
    store = DiskKvStore(budget_bytes=4 * probe_bytes, page_axis=2,
                        block_bytes=probe_bytes)
    max_resident = 0
    try:
        for h in range(16):
            store.spill(h + 1, rr.standard_normal(shape).astype(np.float32))
            max_resident = max(max_resident, store.bytes_resident)
        churn_drops = store.drops
        store.flush()
    finally:
        store.close()
    if max_resident > 4 * probe_bytes:
        raise RuntimeError("store-level churn exceeded the disk byte cap")
    if churn_drops < 12:
        raise RuntimeError(f"store-level churn under-evicted ({churn_drops} drops)")
    return {
        "resume_ttft_tiered_ms": round(t_tier * 1e3, 1),
        "resume_ttft_recompute_ms": round(t_rec * 1e3, 1),
        "resume_ttft_ratio": round(t_tier / max(t_rec, 1e-9), 3),
        "resume_tokens_restored_tiered": cached_tier,
        "resume_tokens_restored_recompute": cached_rec,
        "restore_parity": parity,
        "disk": {
            "spills": snap.get("disk_spills"),
            "restores": snap.get("disk_restores"),
            "restore_hits": snap.get("disk_restore_hits"),
            "restore_fallbacks": snap.get("disk_restore_fallbacks"),
            "restore_tokens": snap.get("disk_restore_tokens"),
            "io_errors": snap.get("disk_io_errors"),
            "blocks_resident": snap.get("disk_blocks_resident"),
            "bytes_resident": snap.get("disk_bytes_resident"),
            "budget_bytes": snap.get("disk_budget_bytes"),
        },
        "cap_under_churn": {
            "budget_bytes": 4 * probe_bytes,
            "max_resident_bytes": max_resident,
            "drops": churn_drops,
        },
        "target": "resume_ttft_ratio < 1.0 (disk restore beats recompute)",
        "note": (
            "tiered arm: 8-block host tier + disk; sessions park while "
            "filler traffic demotes their blocks host -> disk, then resume "
            "through the FETCHING_KV restore path. int8 KV cache in both "
            "arms -> the disk wire format roundtrips bit-exact and parity "
            "is exact"
        ),
    }


async def run_disagg_parity(
    clients: int = 18, n_requests: int = 24, plen: int = 3072, osl: int = 150,
    batch: int = 12, page_size: int = 128,
) -> dict:
    """BASELINE.md parity checkpoint #1: disaggregated prefill/decode vs
    aggregated throughput per chip, reference workload shape (3K ISL/150 OSL;
    reference claim: +30 percent per GPU single-node, docs/architecture.md:57-61).

    Three measurements, all on the one real chip:
      measured_aggregated   — one engine, continuous closed-loop traffic
                              (prefill/decode interference included)
      measured_disagg_1chip — REAL two-worker disagg (prefill worker + decode
                              worker + broker, ICI in-process KV handoff) on
                              the same chip. Both workers share the chip, so
                              this proves the path and prices the KV-transfer
                              overhead — it cannot show the specialization
                              win (that needs >= 2 chips).
      projected_disagg      — the specialization arithmetic with every term
                              measured: per-request prefill chip-time Wp
                              (prefill-only), per-request decode chip-time cd
                              (decode-only), so a disagg pool split costs
                              Wp + cd chip-seconds per request with no
                              interference. ratio_projected = that throughput
                              vs measured_aggregated — the falsifiable analogue
                              of the reference's >= 1.3x single-host claim.
    """
    import gc
    import time as _time

    from dynamo_tpu.cplane.broker import Broker
    from dynamo_tpu.disagg.decode_worker import DisaggDecodeEngine
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.llm.disagg_router import DisaggregatedRouter, DisaggRouterConf
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    pages_per_seq = -(-(plen + osl) // page_size) + 2
    # HBM budget (r5 post-mortem: the r4-sized section OOM'd at batch=16 —
    # decode pool 6.2 GB + prefill pool 2.3 GB + 2x 2.5 GB weights left no
    # slack, and a mid-section RESOURCE_EXHAUSTED poisons the process's
    # allocator so every LATER section dies at init; batch=12 keeps the
    # two-worker phase near 11 GB of the 16 GB chip)
    decode_cfg = _parity_config(
        page_size=page_size, max_seqs=batch, max_model_len=4096,
        num_pages=(batch + 2) * pages_per_seq + 8,
        prefill_buckets=(512, 1024), decode_steps=32, pipeline_depth=3,
    )
    rng = np.random.default_rng(11)
    M = 6  # prefill-cost sample size
    prompts = [
        rng.integers(1, 31000, plen).tolist()
        for _ in range(n_requests + M + batch + 1)
    ]
    wp_prompts = prompts[n_requests : n_requests + M]
    cd_prompts = prompts[n_requests + M : n_requests + M + batch]
    warm_prompt = prompts[-1]

    async def continuous(eng, tag: str) -> dict:
        """Closed-loop with `clients` in flight until n_requests finish."""
        done = []
        ttfts = []
        next_i = 0
        t0 = _time.monotonic()

        async def client():
            nonlocal next_i
            while next_i < n_requests:
                i = next_i
                next_i += 1
                toks, ttft, _ = await _request(
                    eng, f"{tag}-{i}", prompts[i], max_tokens=osl
                )
                done.append(len(toks))
                ttfts.append(ttft)

        await asyncio.gather(*[client() for _ in range(clients)])
        elapsed = _time.monotonic() - t0
        return {
            "tok_s": round(sum(done) / elapsed, 2),
            "requests": len(done),
            "elapsed_s": round(elapsed, 2),
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
        }

    # ---- aggregated: one engine, continuous traffic ----
    agg = AsyncJaxEngine(decode_cfg)
    await agg.start()
    try:
        # warmup: compile prefill buckets + window variants
        await _request(agg, "warm-agg", warm_prompt, max_tokens=4)
        agg_res = await continuous(agg, "agg")

        # ---- component costs on the same engine/executables ----
        # Wp: M concurrent fresh 1-token requests; the chip serializes their
        # prefill chunks, so wall/M ~ per-request prefill chip-time
        t0 = _time.monotonic()
        await asyncio.gather(*[
            _request(agg, f"wp-{j}", wp_prompts[j], max_tokens=1)
            for j in range(M)
        ])
        wp = (_time.monotonic() - t0) / M
        # cd: decode chip-time per request. Round 1 on fresh prompts warms the
        # prefix cache; later rounds re-send the SAME prompts, so their
        # prefill is a cache hit (last token only) and each round is pure
        # batched decode. Best of 2 measured rounds.
        await asyncio.gather(*[
            _request(agg, f"cdw-{j}", cd_prompts[j], max_tokens=osl)
            for j in range(batch)
        ])
        cd = float("inf")
        cache_hits = 0
        for rnd in range(2):
            t0 = _time.monotonic()
            res2 = await asyncio.gather(*[
                _request(agg, f"cd{rnd}-{j}", cd_prompts[j], max_tokens=osl)
                for j in range(batch)
            ])
            cd = min(cd, (_time.monotonic() - t0) / batch)
            cache_hits = max(cache_hits, sum(c for _, _, c in res2))
    finally:
        await agg.shutdown()
        del agg
        gc.collect()

    # ---- real two-worker disagg on the one chip ----
    # teardown stack: anything successfully started gets torn down even when
    # a later setup step or the measurement itself dies
    cleanups = []
    try:
        broker = Broker()
        port = await broker.start()
        cleanups.append(broker.stop)
        addr = f"127.0.0.1:{port}"
        decode_rt = DistributedRuntime(cplane_address=addr)
        await decode_rt.connect()
        cleanups.append(decode_rt._shutdown_hook)
        prefill_rt = DistributedRuntime(cplane_address=addr)
        await prefill_rt.connect()
        cleanups.append(prefill_rt._shutdown_hook)
        decode_inner = AsyncJaxEngine(decode_cfg)
        await decode_inner.start()
        cleanups.append(decode_inner.shutdown)
        prefill_engine = AsyncJaxEngine(_parity_config(
            page_size=page_size, max_seqs=4, max_model_len=4096,
            num_pages=6 * pages_per_seq + 8,
            prefill_buckets=(512, 1024), decode_steps=8, pipeline_depth=2,
        ))
        await prefill_engine.start()
        cleanups.append(prefill_engine.shutdown)
        router = DisaggregatedRouter(
            "bench", conf=DisaggRouterConf(max_local_prefill_length=256)
        )
        decode = DisaggDecodeEngine(
            decode_inner, decode_rt, "bench", "decoder", "bench", disagg_router=router
        )
        await decode.start()
        cleanups.append(decode.shutdown)
        pw = PrefillWorker(prefill_engine, prefill_rt, "bench", "bench")
        await pw.start()
        cleanups.append(pw.stop)

        await _request(decode, "warm-dis", warm_prompt, max_tokens=4)
        dis_res = await continuous(decode, "dis")
        remote = decode.remote_prefills
    finally:
        for stop in reversed(cleanups):
            try:
                await stop()
            except Exception:
                # keep tearing the rest down, but leave a trace: a silently
                # leaked engine/broker corrupts every later section
                import traceback

                traceback.print_exc()
        # belt: a cancelled request can race its ICI-transfer cleanup; a
        # parked device array is ~hundreds of MB of HBM the next sections need
        from dynamo_tpu.disagg import ici as _ici

        dropped = _ici.drain_all()
        if dropped:
            import sys as _sys

            print(f"[bench] disagg teardown dropped {dropped} parked ICI transfers",
                  file=_sys.stderr, flush=True)
    gc.collect()

    projected = osl / (wp + cd)
    # marginal prefill cost actually observed in the aggregated mix: the agg
    # round's wall minus what its tokens would take at the pure-decode rate.
    # Where prefill chunks slot into the decode pipeline's dispatch gaps,
    # the isolated wp above is an UPPER bound on prefill cost and
    # ratio_projected a lower bound on the pool-split ratio.
    decode_only_s = agg_res["requests"] * cd
    marginal_prefill = max(0.0, agg_res["elapsed_s"] - decode_only_s) / max(1, agg_res["requests"])
    return {
        "workload": {"isl": plen, "osl": osl, "clients": clients, "requests": n_requests},
        "measured_aggregated": agg_res,
        "measured_disagg_1chip": {**dis_res, "remote_prefills": remote},
        "ratio_measured_1chip": round(dis_res["tok_s"] / agg_res["tok_s"], 3),
        "components": {
            "prefill_chip_s_per_req_isolated": round(wp, 3),
            "prefill_s_per_req_marginal_in_mix": round(marginal_prefill, 3),
            "decode_chip_s_per_req": round(cd, 3),
            "cd_round_cache_hit_tokens": cache_hits,
        },
        "projected_disagg_tok_s_per_chip": round(projected, 1),
        "ratio_projected": round(projected / agg_res["tok_s"], 3),
        "target": ">= 1.3 single host (reference docs/architecture.md:57-61)",
        "note": (
            "one chip hosts both workers, so measured_disagg_1chip proves the "
            "path + prices KV handoff but cannot show the specialization win; "
            "ratio_projected uses measured per-stage chip-times for an "
            "interference-free pool split. r5 conclusion: the aggregated "
            "engine overlaps prefill into decode so well that the MARGINAL "
            "prefill cost in the mix is below the isolated cost "
            "(prefill_s_per_req_marginal_in_mix < _isolated), which puts the "
            "pool-split projection BELOW 1 — for this single-model 3K/150 "
            "workload on this engine, disaggregation has no interference "
            "left to remove, and the reference's +30% (whose engines pay "
            "real prefill/decode interference) does not transfer. The "
            "disagg machinery's value here is structural (pool pressure, "
            "heterogeneous pools, cross-host scaling), and the MECHANISM is "
            "demonstrated in CI "
            "(tests/test_disagg.py::test_disagg_pool_specialization_counters): "
            "with a prefill worker joined, the decode engine's local prefill "
            "rows collapse to ~0 (remote_prefills == all long prompts) with "
            "token-exact outputs and no added page-pressure events"
        ),
    }


async def run_disagg_stream(
    n_requests: int = 5, plen: int = 2600, osl: int = 24, page_size: int = 128,
) -> dict:
    """Streamed (chunk-pipelined, multi-lane) vs monolithic KV transfer on the
    cross-process socket path, long multi-chunk prompts.

    ici.is_local is forced off so the bulk KV really rides the TCP data plane
    (same-process workers would otherwise take the device handoff). Both arms
    run the identical two-worker fleet; only the prefill engine's kv_stream
    flag differs. Reports per-arm TTFT, exact token parity between arms, and
    the measured compute/transfer overlap fraction from the prefill worker's
    counters — the pipelining win the v2 wire protocol exists for (on this
    single-host loopback the transfer leg is cheap, so the TTFT delta is a
    lower bound on what a real DCN hop would recover)."""
    import gc
    import time as _time  # noqa: F401 — parity with sibling sections

    from dynamo_tpu.cplane.broker import Broker
    from dynamo_tpu.disagg import ici as _ici
    from dynamo_tpu.disagg.decode_worker import DisaggDecodeEngine
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.llm.disagg_router import DisaggregatedRouter, DisaggRouterConf
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, 31000, plen).tolist() for _ in range(n_requests)]
    warm_prompt = rng.integers(1, 31000, plen).tolist()
    pages_per_seq = -(-(plen + osl) // page_size) + 2
    orig_is_local = _ici.is_local
    _ici.is_local = lambda worker_id: False  # force the socket data plane
    arms: dict[str, dict] = {}
    try:
        for arm, stream in (("monolithic", False), ("streamed", True)):
            cleanups = []
            try:
                broker = Broker()
                port = await broker.start()
                cleanups.append(broker.stop)
                addr = f"127.0.0.1:{port}"
                decode_rt = DistributedRuntime(cplane_address=addr)
                await decode_rt.connect()
                cleanups.append(decode_rt._shutdown_hook)
                prefill_rt = DistributedRuntime(cplane_address=addr)
                await prefill_rt.connect()
                cleanups.append(prefill_rt._shutdown_hook)
                decode_inner = AsyncJaxEngine(_parity_config(
                    page_size=page_size, max_seqs=4, max_model_len=4096,
                    num_pages=6 * pages_per_seq + 8,
                    prefill_buckets=(512, 1024), decode_steps=16,
                    pipeline_depth=2,
                ))
                await decode_inner.start()
                cleanups.append(decode_inner.shutdown)
                prefill_engine = AsyncJaxEngine(_parity_config(
                    page_size=page_size, max_seqs=4, max_model_len=4096,
                    num_pages=6 * pages_per_seq + 8,
                    prefill_buckets=(512, 1024), decode_steps=8,
                    pipeline_depth=2, kv_stream=stream, kv_stream_lanes=2,
                ))
                await prefill_engine.start()
                cleanups.append(prefill_engine.shutdown)
                router = DisaggregatedRouter(
                    "bench", conf=DisaggRouterConf(max_local_prefill_length=256)
                )
                decode = DisaggDecodeEngine(
                    decode_inner, decode_rt, "bstream", "decoder", "bench",
                    disagg_router=router,
                )
                await decode.start()
                cleanups.append(decode.shutdown)
                pw = PrefillWorker(prefill_engine, prefill_rt, "bstream", "bench")
                await pw.start()
                cleanups.append(pw.stop)

                await _request(decode, f"warm-{arm}", warm_prompt, max_tokens=2)
                ttfts, tokens = [], []
                # sequential requests: the TTFT signal must not mix queueing
                for i, p in enumerate(prompts):
                    toks, ttft, _ = await _request(
                        decode, f"{arm}-{i}", p, max_tokens=osl
                    )
                    ttfts.append(ttft)
                    tokens.append(toks)
                arms[arm] = {
                    "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
                    "ttft_mean_ms": round(float(np.mean(ttfts)) * 1e3, 1),
                    "remote_prefills": decode.remote_prefills,
                    "parts_scattered": decode.parts_scattered,
                    "stream_parts": pw.stream_parts,
                    "stream_bytes": pw.stream_bytes,
                    "stream_send_s": round(pw.stream_send_s, 4),
                    "stream_overlap_s": round(pw.stream_overlap_s, 4),
                    "_tokens": tokens,
                }
            finally:
                for stop in reversed(cleanups):
                    try:
                        await stop()
                    except Exception:
                        import traceback

                        traceback.print_exc()
                dropped = _ici.drain_all()
                if dropped:
                    import sys as _sys

                    print(f"[bench] disagg_stream teardown dropped {dropped} "
                          "parked ICI transfers", file=_sys.stderr, flush=True)
            gc.collect()
    finally:
        _ici.is_local = orig_is_local

    parity = arms["streamed"].pop("_tokens") == arms["monolithic"].pop("_tokens")
    send_s = arms["streamed"]["stream_send_s"]
    overlap_fraction = (
        round(arms["streamed"]["stream_overlap_s"] / send_s, 3) if send_s else 0.0
    )
    return {
        "workload": {
            "isl": plen, "osl": osl, "requests": n_requests,
            "chunks_per_prompt": -(-plen // 1024), "lanes": 2,
        },
        "monolithic": arms["monolithic"],
        "streamed": arms["streamed"],
        "token_parity": parity,
        "overlap_fraction": overlap_fraction,
        "ttft_ratio_streamed_over_monolithic": round(
            arms["streamed"]["ttft_p50_ms"]
            / max(arms["monolithic"]["ttft_p50_ms"], 1e-9), 3,
        ),
        "target": (
            "token_parity exact; overlap_fraction > 0; streamed TTFT <= "
            "monolithic on multi-chunk prompts (ratio <= 1.0)"
        ),
    }


async def run_fleet_prefix(sessions: int = 3, osl: int = 8) -> dict:
    """Fleet-wide prefix cache: cross-worker KV pull vs full recompute on a
    shared-system-prompt workload (the millions-of-users chat shape: many
    sessions share a long system prompt, the router can't always land them
    on the worker that already holds it).

    Three engines per KV dtype: a HOLDER seeded with every session's shared
    prefix (and serving a KvPullServer), a HIT engine whose requests carry
    the holder as kv_holder (admission pulls the prefix over the wire —
    FETCHING_KV), and a COLD engine running the identical requests with no
    holder (full prefix recompute). Reports the cross-worker-hit vs
    recompute TTFT ratio (< 1.0 is the win), the fleet recompute-token
    ratio, pulled bytes at the ACTUAL wire KV dtype (int8 payloads are half
    the bf16 bytes), and exact token parity between the arms.

    On CPU (no TPU in the build container) the section scales the geometry
    down; parity and the recompute-ratio are exact either way, the driver's
    TPU run prices the TTFT ratio at serving geometry."""
    import gc

    import jax

    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        geom = {
            "vocab_size": 512, "hidden_size": 512, "intermediate_size": 1024,
            "num_layers": 4, "num_heads": 4, "num_kv_heads": 2,
            "head_dim": 128, "dtype": "f32",
        }
        base_id = "tiny:" + json.dumps(geom)
        page_size, shared_len, tail_len, vocab = 16, 448, 31, 500
        prefill_buckets = (64, 128, 256, 512)
        max_model_len = 1024
    else:
        base_id = json_model_id()
        page_size, shared_len, tail_len, vocab = 64, 1536, 127, 31000
        prefill_buckets = (512, 1024, 2048)
        max_model_len = 4096

    ps = page_size
    prefix_blocks = shared_len // ps
    plen = shared_len + tail_len
    pages_per_seq = -(-(plen + osl) // ps) + 2
    num_pages = (sessions + 4) * pages_per_seq + 8

    rng = np.random.default_rng(41)
    # one shared system prompt per session (warm session included), so every
    # measured request is a genuine first-placement miss that must pull
    all_prompts = [
        rng.integers(1, vocab, shared_len).tolist()
        + rng.integers(1, vocab, tail_len).tolist()
        for _ in range(sessions + 1)
    ]
    warm_prompt, prompts = all_prompts[0], all_prompts[1:]

    results: dict[str, dict] = {}
    for dtype in (None, "int8"):
        label = dtype or "bf16"

        def cfg():
            return EngineConfig(
                model_id=base_id, page_size=ps, num_pages=num_pages,
                max_seqs=4, max_model_len=max_model_len,
                prefill_buckets=prefill_buckets, decode_steps=4,
                pipeline_depth=2, kv_cache_dtype=dtype,
                prefix_fetch_timeout_s=60.0,
            )

        cleanups = []
        try:
            holder = AsyncJaxEngine(cfg())
            await holder.start()
            cleanups.append(holder.shutdown)
            hit_eng = AsyncJaxEngine(cfg())
            await hit_eng.start()
            cleanups.append(hit_eng.shutdown)
            cold_eng = AsyncJaxEngine(cfg())
            await cold_eng.start()
            cleanups.append(cold_eng.shutdown)
            srv = await KvPullServer(holder, host="127.0.0.1").start()
            cleanups.append(srv.stop)
            fetcher = PrefixFetchClient(asyncio.get_running_loop(), timeout_s=60.0)
            hit_eng.attach_prefix_fetch(fetcher)

            # fleet state: the holder computed (and cached) every session's
            # shared prefix
            for i, p in enumerate(all_prompts):
                await _request(holder, f"seed-{label}-{i}", p, max_tokens=2)
            # warm both serving arms on the warm session: compiles prefill
            # buckets, decode windows, and the fetch-scatter executables out
            # of the measurement (the warm hit request exercises a real pull)
            await _request(hit_eng, f"warm-hit-{label}", warm_prompt,
                           max_tokens=2, holder=srv.address,
                           holder_blocks=prefix_blocks)
            await _request(cold_eng, f"warm-cold-{label}", warm_prompt, max_tokens=2)

            hit_ttfts, hit_tokens, hit_recompute = [], [], 0
            for i, p in enumerate(prompts):
                toks, ttft, cached = await _request(
                    hit_eng, f"hit-{label}-{i}", p, max_tokens=osl,
                    holder=srv.address, holder_blocks=prefix_blocks,
                )
                hit_ttfts.append(ttft)
                hit_tokens.append(toks)
                hit_recompute += plen - cached
            cold_ttfts, cold_tokens, cold_recompute = [], [], 0
            for i, p in enumerate(prompts):
                toks, ttft, cached = await _request(
                    cold_eng, f"cold-{label}-{i}", p, max_tokens=osl,
                )
                cold_ttfts.append(ttft)
                cold_tokens.append(toks)
                cold_recompute += plen - cached

            sched = hit_eng.scheduler
            results[label] = {
                "ttft_hit_p50_ms": round(float(np.percentile(hit_ttfts, 50)) * 1e3, 1),
                "ttft_recompute_p50_ms": round(
                    float(np.percentile(cold_ttfts, 50)) * 1e3, 1
                ),
                "ttft_ratio_hit_over_recompute": round(
                    float(np.percentile(hit_ttfts, 50))
                    / max(float(np.percentile(cold_ttfts, 50)), 1e-9), 3
                ),
                "token_parity": hit_tokens == cold_tokens,
                "prefix_fetch_hits": sched.prefix_fetch_hits,
                "prefix_fetch_fallbacks": sched.prefix_fetch_fallbacks,
                "pulled_blocks": sched.prefix_fetch_blocks,
                # at the ACTUAL wire KV dtype: int8 payloads are half the
                # bf16 bytes (scale planes ride part headers, uncounted)
                "pulled_bytes": sched.prefix_fetch_bytes,
                "recompute_tokens_hit_arm": hit_recompute,
                "recompute_tokens_cold_arm": cold_recompute,
                "recompute_ratio": round(
                    hit_recompute / max(1, cold_recompute), 4
                ),
                "served_blocks": dict(srv.served_blocks),
            }
        finally:
            for stop in reversed(cleanups):
                try:
                    await stop()
                except Exception:
                    import traceback

                    traceback.print_exc()
            gc.collect()

    assert results["bf16"]["token_parity"], "cross-worker pull broke token parity"
    assert results["int8"]["token_parity"], "int8 cross-worker pull broke parity"
    return {
        "cpu_smoke": on_cpu,
        "workload": {
            "sessions": sessions, "shared_prefix_len": shared_len,
            "prompt_len": plen, "osl": osl, "page_size": ps,
            "prefix_blocks": prefix_blocks,
        },
        "bf16": results["bf16"],
        "int8": results["int8"],
        "wire_bytes_ratio_int8_over_bf16": round(
            results["int8"]["pulled_bytes"]
            / max(1, results["bf16"]["pulled_bytes"]), 3
        ),
        "target": (
            "token parity exact both dtypes; hit-arm TTFT ratio < 1.0; "
            "recompute_ratio ~= tail/plen (the fleet stops recomputing "
            "shared prefixes); int8 wire bytes = itemsize ratio (0.5x vs "
            "bf16 on TPU, 0.25x vs the f32 CPU-smoke geometry)"
        ),
    }


async def run_migration(sessions: int = 3, osl: int = 24) -> dict:
    """Live sequence migration vs kill+resume (the round-14 tentpole):
    migrated-vs-killed request outcome on identical mid-decode interrupts.

    Three engines: a BASELINE serving each prompt uninterrupted (the parity
    reference and the no-interrupt gap distribution), a SOURCE + DEST pair
    for the migrated arm (requests start on SOURCE, migrate mid-decode over
    the seq_handoff pull dataplane, finish on DEST with the stream relayed),
    and a kill+resume arm on SOURCE (cancel at the same point + preempt-
    style resume — today's alternative). Reports exact token parity for the
    migrated arm, the client-visible pause p99 (freeze -> first continuation
    token), tokens salvaged by the KV pull, and the goodput delta between
    the arms under a shared per-token ITL budget.

    On CPU (no TPU in the build container) the section scales the geometry
    down; parity and the salvage counters are exact either way, the
    driver's TPU run prices pause/goodput at serving geometry."""
    import gc

    import jax

    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest
    from dynamo_tpu.utils.goodput import RequestOutcome, outcome_meets

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        geom = {
            "vocab_size": 512, "hidden_size": 256, "intermediate_size": 512,
            "num_layers": 2, "num_heads": 4, "num_kv_heads": 2,
            "head_dim": 64, "dtype": "f32",
        }
        base_id = "tiny:" + json.dumps(geom)
        page_size, plen, vocab = 16, 96, 500
        prefill_buckets = (32, 64, 128)
        max_model_len = 256
    else:
        base_id = json_model_id()
        page_size, plen, vocab = 64, 1536, 31000
        prefill_buckets = (512, 1024, 2048)
        max_model_len = 4096

    half = osl // 2
    pages_per_seq = -(-(plen + osl) // page_size) + 2
    num_pages = (sessions + 2) * pages_per_seq + 8

    def cfg():
        return EngineConfig(
            model_id=base_id, page_size=page_size, num_pages=num_pages,
            max_seqs=4, max_model_len=max_model_len,
            prefill_buckets=prefill_buckets, decode_steps=2,
            pipeline_depth=2, migration_timeout_s=60.0,
            # pre-compile every prefill-bucket/window variant: a cold XLA
            # compile landing inside one measured handoff would otherwise
            # dominate the pause percentiles (the warm migration below still
            # covers the handoff-only executables like the part scatter)
            warmup=True,
        )

    rng = np.random.default_rng(47)
    mig_prompts = [rng.integers(1, vocab, plen).tolist() for _ in range(sessions)]
    kill_prompts = [rng.integers(1, vocab, plen).tolist() for _ in range(sessions)]

    def req_for(rid, prompt, max_tokens=osl):
        return EngineRequest(
            request_id=rid, token_ids=list(prompt),
            sampling=SamplingParams(
                temperature=0.0, max_tokens=max_tokens, ignore_eos=True
            ),
        )

    async def collect(eng, req, stop_after=None):
        """(tokens, arrival walls). stop_after=n breaks the stream after n
        tokens (the kill arm's client walking through a worker death)."""
        toks, walls = [], []
        async for out in eng.generate(req):
            if out.token is not None:
                toks.append(out.token)
                walls.append(time.monotonic())
            if stop_after is not None and len(toks) >= stop_after:
                break
            if out.finished:
                break
        return toks, walls

    async def wait_generated(eng, rid, n, timeout=120.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            seq = next(
                (s for s in eng.scheduler.slots
                 if s is not None and s.req.request_id == rid), None,
            )
            if seq is not None and len(seq.generated) >= n:
                return True
            await asyncio.sleep(0.005)
        return False

    cleanups = []
    try:
        baseline = AsyncJaxEngine(cfg())
        await baseline.start()
        cleanups.append(baseline.shutdown)
        source = AsyncJaxEngine(cfg())
        await source.start()
        cleanups.append(source.shutdown)
        dest = AsyncJaxEngine(cfg())
        await dest.start()
        cleanups.append(dest.shutdown)
        srv = await KvPullServer(source, host="127.0.0.1").start()
        cleanups.append(srv.stop)
        source.kv_pull_server = srv
        dest.attach_prefix_fetch(
            PrefixFetchClient(asyncio.get_running_loop(), timeout_s=60.0)
        )

        # baseline arm: uninterrupted runs = the parity reference + the
        # undisturbed per-token gap distribution (warm run compiles first)
        await collect(baseline, req_for("warm-base", mig_prompts[0], 4))
        expected, base_gaps = [], []
        for i, p in enumerate(mig_prompts):
            toks, walls = await collect(baseline, req_for(f"base-{i}", p))
            expected.append(toks)
            base_gaps.extend(np.diff(walls).tolist())

        # migrated arm: start on SOURCE, freeze+handoff at `half` tokens,
        # finish on DEST with the stream relayed through the source. Warm
        # the WHOLE handoff path first (manifest, seq_handoff pull, scatter,
        # adoption prefill executables) with a throwaway migration so the
        # measured pauses price the handoff, not cold XLA compiles.
        warm_prompt = rng.integers(1, vocab, plen).tolist()
        wt = asyncio.ensure_future(collect(source, req_for("warm-mig", warm_prompt)))
        if await wait_generated(source, "warm-mig", half):
            await source.migrate_out("warm-mig", dest.adopt_migrated)
        await wt
        mig_tokens, mig_pauses, mig_gap_series = [], [], []
        for i, p in enumerate(mig_prompts):
            rid = f"mig-{i}"
            task = asyncio.ensure_future(collect(source, req_for(rid, p)))
            assert await wait_generated(source, rid, half), "migration arm stalled"
            res = await source.migrate_out(rid, dest.adopt_migrated)
            assert res["status"] == "ok", f"handoff failed: {res}"
            toks, walls = await task
            mig_tokens.append(toks)
            mig_pauses.append(res["pause_s"])
            mig_gap_series.append(np.diff(walls).tolist())

        # kill arm: the worker DIES at the same point — the client's retry
        # lands on the peer with the history as its prompt and NO KV to
        # pull (the dead worker's pages are gone), so the whole history
        # re-prefills cold. This is the outcome migration must beat; a
        # same-worker resume would instead model preemption (its local
        # prefix cache recovers the blocks, which a dead worker cannot).
        kill_gap_series, kill_pauses = [], []
        for i, p in enumerate(kill_prompts):
            rid = f"kill-{i}"
            got, walls = await collect(source, req_for(rid, p), stop_after=half)
            rest, walls2 = await collect(
                dest, req_for(f"{rid}-retry", list(p) + got, osl - len(got))
            )
            kill_pauses.append(walls2[0] - walls[-1] if walls2 else 0.0)
            kill_gap_series.append(
                np.diff(walls).tolist()
                + ([walls2[0] - walls[-1]] if walls2 else [])
                + np.diff(walls2).tolist()
            )

        # shared per-token ITL budget: generous over the undisturbed gap
        # distribution, so only the interrupt stall can miss it
        itl_budget = max(
            float(np.percentile(base_gaps, 95)) * 3.0 if base_gaps else 0.05,
            0.05,
        )

        def arm_goodput(series):
            met = 0
            for gaps in series:
                out = RequestOutcome(
                    "x", itl_s=tuple(gaps), output_tokens=len(gaps) + 1,
                )
                met += 1 if outcome_meets(out, None, itl_budget) else 0
            return met / max(1, len(series))

        gp_mig = arm_goodput(mig_gap_series)
        gp_kill = arm_goodput(kill_gap_series)
        parity = sum(
            1 for got, want in zip(mig_tokens, expected) if got == want
        ) / max(1, sessions)
        dsched = dest.scheduler
        assert parity == 1.0, (
            f"migration broke token parity: {mig_tokens} != {expected}"
        )
        assert dsched.migration_in_pulled >= 1, "no handoff pull landed"
        return {
            "cpu_smoke": on_cpu,
            "workload": {"sessions": sessions, "prompt_len": plen,
                         "osl": osl, "migrate_at": half,
                         "page_size": page_size},
            "parity": parity,
            "pause_ms_p50": round(float(np.percentile(mig_pauses, 50)) * 1e3, 1),
            "pause_ms_p99": round(float(np.percentile(mig_pauses, 99)) * 1e3, 1),
            "kill_pause_ms_p99": round(
                float(np.percentile(kill_pauses, 99)) * 1e3, 1
            ),
            "tokens_salvaged": dsched.migration_tokens_salvaged,
            "migrations_pulled": dsched.migration_in_pulled,
            "migrations_recomputed": dsched.migration_in_recomputed,
            "itl_budget_ms": round(itl_budget * 1e3, 1),
            "goodput_migrated": round(gp_mig, 4),
            "goodput_killed": round(gp_kill, 4),
            "goodput_delta": round(gp_mig - gp_kill, 4),
            "target": (
                "parity exact; pause p99 under the kill+resume stall; "
                "goodput_delta >= 0 (migrating a sequence must beat killing "
                "it); salvaged tokens ~= sessions * committed history"
            ),
        }
    finally:
        for stop in reversed(cleanups):
            try:
                await stop()
            except Exception:
                import traceback

                traceback.print_exc()
        gc.collect()


async def run_qos() -> dict:
    """Multi-tenant QoS isolation experiment (utils/qos.py): tenant A bursts
    batch-class traffic with long outputs through ONE engine while tenant B
    runs a steady critical-class stream — with QoS on vs off on the same
    trace.

    QoS on: B rides the critical lane (admission order, victim ordering
    prefers batch lanes, a waiting critical request evicts a batch lane) and
    A's burst is charged against a per-tenant token budget (the frontend
    bucket semantics, replayed at the trace's own timestamps — shed requests
    never reach the engine, exactly like the 429 path). QoS off: classes are
    ignored (FIFO admission, recency-only victims) and nothing sheds — A's
    page-pressure churn preempts B mid-stream.

    Headline: tenant B's per-request ITL-p99 stays within budget with QoS on
    while the off arm violates it; shed_fraction says how much of A's burst
    the budget refused; critical_goodput (B under burst, QoS on) must hold
    the no-burst baseline. The engine asserts B was NEVER a preemption
    victim in the on arm."""
    import gc

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.loadgen import compile_trace, load_scenario
    from dynamo_tpu.loadgen.replay import replay_engine
    from dynamo_tpu.utils.goodput import percentile
    from dynamo_tpu.utils.qos import AdmissionController, QosPolicy

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        model_id = "tiny"
        n_a, n_b, speed = 12, 6, 2.0
        # budgets sized to separate window-scale gaps (~2 ms measured) from
        # preempt+requeue stalls (~0.5 s+) on the CPU tiny engine
        ttft_budget_ms, itl_budget_ms = 30000.0, 250.0
        # pages sized so three LONG tenant-A lanes cannot coexist: A's decode
        # growth (osl 96 on a 32-token prompt) forces preemption churn — the
        # noisy-neighbor pathology the off arm must exhibit against B
        eng_kw = dict(
            page_size=4, num_pages=64, max_seqs=3, max_model_len=256,
            prefill_buckets=(16, 32, 64), decode_steps=2, pipeline_depth=1,
            prefill_batches_per_step=1, qos_preempt_wait_ms=50.0,
        )
        a_scale = dict(isl_mean=32, isl_max=64, osl_dist="fixed", osl_mean=96,
                       osl_max=96, vocab=256, rate_rps=24.0, burst_factor=6.0,
                       num_requests=n_a, slo_ttft_ms=ttft_budget_ms,
                       slo_itl_ms=itl_budget_ms)
        # B outputs long enough that a mid-stream preemption (the off arm's
        # failure mode) lands INSIDE the ITL series, spaced so at most two B
        # lanes overlap (three critical lanes alone would exhaust the pool
        # and force critical-on-critical preemption even with QoS on)
        b_scale = dict(isl_mean=12, isl_max=24, osl_dist="fixed", osl_mean=48,
                       osl_max=48, vocab=256, rate_rps=0.8, num_requests=n_b,
                       slo_ttft_ms=ttft_budget_ms, slo_itl_ms=itl_budget_ms)
        # A's budget: ~2 requests' worth of burst, then ~1 per 6 s — most of
        # the burst must shed so the bucket actually bites
        budget_spec = "tenant-a=20:300"
    else:
        model_id = json_model_id()
        n_a, n_b, speed = 32, 16, 1.0
        ttft_budget_ms, itl_budget_ms = 2000.0, 200.0
        eng_kw = dict(
            page_size=16, num_pages=2048, max_seqs=8, max_model_len=2048,
            prefill_buckets=(128, 256, 512), decode_steps=8, pipeline_depth=2,
            prefill_batches_per_step=2, qos_preempt_wait_ms=100.0,
        )
        a_scale = dict(isl_mean=256, isl_max=1024, osl_dist="fixed",
                       osl_mean=256, osl_max=256, vocab=31000, rate_rps=32.0,
                       burst_factor=6.0, num_requests=n_a,
                       slo_ttft_ms=ttft_budget_ms, slo_itl_ms=itl_budget_ms)
        b_scale = dict(isl_mean=64, isl_max=256, osl_dist="fixed", osl_mean=48,
                       osl_max=48, vocab=31000, rate_rps=4.0, num_requests=n_b,
                       slo_ttft_ms=ttft_budget_ms, slo_itl_ms=itl_budget_ms)
        budget_spec = "tenant-a=2000:8192"

    spec_a = load_scenario("bursty_chat", seed=5).replace(
        name="qos_burst_a", tenants=("tenant-a",), **a_scale)
    spec_b = load_scenario("bursty_chat", seed=6).replace(
        name="qos_steady_b", arrival="poisson", tenants=("tenant-b",),
        **b_scale)
    trace_a, trace_b = compile_trace(spec_a), compile_trace(spec_b)
    merged = sorted(trace_a + trace_b, key=lambda tr: tr.at_s)

    def stamp_priority(req, tr):
        req.priority = "critical" if tr.tenant == "tenant-b" else "batch"

    # frontend-bucket admission replayed at the trace's own timestamps (a
    # virtual clock makes the shed set deterministic): shed requests never
    # reach the engine — on the wire they'd be structured retriable 429s
    clock = {"t": 0.0}
    ctl = AdmissionController(
        QosPolicy.from_specs(budget_spec, "tenant-a=batch,tenant-b=critical"),
        clock=lambda: clock["t"],
    )
    admitted_trace, shed = [], 0
    for tr in merged:
        clock["t"] = tr.at_s
        if tr.tenant == "tenant-a":
            d = ctl.admit(tr.tenant, "batch", len(tr.token_ids) + tr.max_tokens)
            if not d.admitted:
                shed += 1
                continue
        else:
            ctl.admit(tr.tenant, "critical", len(tr.token_ids) + tr.max_tokens)
        admitted_trace.append(tr)
    shed_fraction = shed / max(1, len(trace_a))

    def tenant_stats(report, tenant):
        outs = [o for o in report["outcomes"] if o.get("tenant") == tenant]
        itl_p99s = [o["itl_p99_ms"] for o in outs if o.get("itl_p99_ms") is not None]
        met = sum(
            1 for o in outs
            if not o.get("error")
            and (o.get("ttft_ms") is not None and o["ttft_ms"] <= ttft_budget_ms)
            and (o.get("itl_p99_ms") is None or o["itl_p99_ms"] <= itl_budget_ms)
        )
        return {
            "requests": len(outs),
            "errors": sum(1 for o in outs if o.get("error")),
            "itl_p99_ms": percentile(itl_p99s, 99),
            "ttft_p99_ms": percentile(
                [o["ttft_ms"] for o in outs if o.get("ttft_ms") is not None], 99
            ),
            "goodput": round(met / len(outs), 4) if outs else None,
        }

    async def arm(qos_on: bool, trace, hook):
        eng = AsyncJaxEngine(EngineConfig(model_id=model_id, qos=qos_on, **eng_kw))
        try:
            await eng.start()
            # warm BOTH tenants' shapes (prefill buckets/lane counts) so a
            # cold XLA compile can't masquerade as an ITL stall mid-arm
            for wspec in (spec_a.replace(seed=98, num_requests=3),
                          spec_b.replace(seed=99, num_requests=3)):
                await replay_engine(
                    eng, compile_trace(wspec), spec=wspec, speed=100.0,
                )
            # warm traffic ran at class "standard": its preemptions must not
            # pollute the measured arm's enforcement audit
            sched = eng.scheduler
            sched.qos_preempted.clear()
            sched.qos_sheds = sched.qos_shed_migrations = 0
            sched.preempt_count = 0
            report = await replay_engine(
                eng, trace, spec=spec_b, speed=speed, request_hook=hook,
            )
            sched = eng.scheduler
            report["engine_qos"] = {
                "preempted": dict(sched.qos_preempted),
                "sheds": sched.qos_sheds,
                "preempt_count": sched.preempt_count,
            }
            return report
        finally:
            await eng.shutdown()
            gc.collect()

    rep_on = await arm(True, admitted_trace, stamp_priority)
    rep_off = await arm(False, merged, None)
    # no-burst baseline: tenant B alone on a QoS engine — the bar
    # critical-class goodput under burst must hold
    rep_base = await arm(True, trace_b, stamp_priority)

    b_on = tenant_stats(rep_on, "tenant-b")
    b_off = tenant_stats(rep_off, "tenant-b")
    b_base = tenant_stats(rep_base, "tenant-b")
    for rep in (rep_on, rep_off, rep_base):
        rep.pop("outcomes", None)

    # enforcement audit: with QoS on, tenant B (critical) was NEVER a
    # preemption victim — batch lanes paid for all of A's page pressure
    assert rep_on["engine_qos"]["preempted"].get("critical", 0) == 0, (
        rep_on["engine_qos"],
    )
    assert shed_fraction > 0.0, "A's burst never hit the token budget"
    assert b_on["errors"] == 0 and b_base["errors"] == 0
    # the isolation headline: B within its ITL budget with QoS on, and the
    # SAME trace without QoS blowing it (the off arm's preempt churn hits B)
    assert b_on["itl_p99_ms"] is not None and \
        b_on["itl_p99_ms"] <= itl_budget_ms, (b_on, itl_budget_ms)
    assert b_off["itl_p99_ms"] is not None and \
        b_off["itl_p99_ms"] > itl_budget_ms, (b_off, itl_budget_ms)

    return {
        "cpu_smoke": on_cpu,
        "platform": jax.devices()[0].platform,
        "ttft_budget_ms": ttft_budget_ms,
        "itl_budget_ms": itl_budget_ms,
        "tenant_b_on": b_on,
        "tenant_b_off": b_off,
        "tenant_b_baseline": b_base,
        "tenant_b_itl_ratio": (
            round(b_on["itl_p99_ms"] / b_off["itl_p99_ms"], 4)
            if b_on["itl_p99_ms"] and b_off["itl_p99_ms"] else None
        ),
        "b_within_budget_on": bool(
            b_on["itl_p99_ms"] is not None
            and b_on["itl_p99_ms"] <= itl_budget_ms
        ),
        "b_violates_off": bool(
            b_off["itl_p99_ms"] is not None
            and b_off["itl_p99_ms"] > itl_budget_ms
        ),
        "shed_fraction": round(shed_fraction, 4),
        "sheds": shed,
        "critical_goodput": b_on["goodput"],
        "baseline_goodput": b_base["goodput"],
        "admission": ctl.snapshot(),
        "engine_qos_on": rep_on["engine_qos"],
        "engine_qos_off": rep_off["engine_qos"],
    }


async def run_long_context(osl: int = 32) -> dict:
    """Long-context serving (round-8 tentpole): 16K/64K-token prompts
    end-to-end through the page-table width ladder + depth-aware chunked
    prefill, reporting TTFT, decode tok/s, and the KV page high-watermark
    (the PR 5 ``kv_pages_peak`` gauge) per depth — plus EXACT token parity
    between the ladder and the dense-table path on the deepest prompt, and
    a short-prompt ladder-vs-dense TTFT ratio (the no-regression guard).

    On CPU (no TPU in the build container) the geometry scales down 16x,
    exactly like fleet_prefix: "16k"/"64k" become 1K/4K-token prompts on
    the tiny-json model and prefill_flat_depth scales with them so the
    depth-aware chunk shrinking genuinely engages; parity and the gauge
    plumbing are exact either way, and the driver's TPU run prices the
    real depths."""
    import gc

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        geom = {
            "vocab_size": 512, "hidden_size": 512, "intermediate_size": 1024,
            "num_layers": 4, "num_heads": 4, "num_kv_heads": 2,
            "head_dim": 128, "dtype": "f32",
        }
        base_id = "tiny:" + json.dumps(geom)
        page_size, vocab = 16, 500
        depths = {"16k": 1024, "64k": 4096}  # 16x scale-down
        short_len, max_model_len = 256, 8192
        prefill_buckets = (128, 256, 512)
        flat_depth = 1024  # scaled with the depths: shrinking engages at "64k"
    else:
        base_id = json_model_id()
        page_size, vocab = 64, 31000
        depths = {"16k": 16384, "64k": 65536}
        short_len, max_model_len = 2048, 131072
        prefill_buckets = (512, 1024, 2048)
        flat_depth = 8192
    mp = max_model_len // page_size  # dense table width
    num_pages = (
        depths["64k"] // page_size + 4 * (short_len // page_size) + 64
    )

    def cfg(**over):
        return EngineConfig(
            model_id=base_id, page_size=page_size, num_pages=num_pages,
            max_seqs=2, max_model_len=max_model_len,
            prefill_buckets=prefill_buckets, prefill_flat_depth=flat_depth,
            decode_steps=4, pipeline_depth=2, **over,
        )

    rng = np.random.default_rng(17)
    prompts = {
        label: rng.integers(1, vocab, depth).tolist()
        for label, depth in depths.items()
    }
    short_prompt = rng.integers(1, vocab, short_len).tolist()

    async def timed(eng, rid, prompt):
        t0 = time.monotonic()
        toks, ttft, _ = await _request(eng, rid, prompt, max_tokens=osl)
        total = time.monotonic() - t0
        decode_s = max(total - ttft, 1e-9)
        return toks, ttft, (len(toks) - 1) / decode_s

    out: dict = {"cpu_smoke": on_cpu, "scale": {
        "depths_tokens": dict(depths), "short_len": short_len,
        "page_size": page_size, "dense_table_width": mp,
    }}
    cleanups = []
    try:
        ladder = AsyncJaxEngine(cfg())
        await ladder.start()
        cleanups.append(ladder.shutdown)
        dense = AsyncJaxEngine(cfg(page_table_buckets=(mp,)))
        await dense.start()
        cleanups.append(dense.shutdown)
        out["table_buckets"] = list(ladder.config.table_buckets)

        # warm both arms: the short-prompt buckets + decode window, and ONE
        # deep prompt each so the wide-table/deep-chunk executables compile
        # out of the measured TTFT (fresh random prompts — no prefix reuse
        # between warm and measured requests)
        warm_deep = rng.integers(1, vocab, depths["64k"]).tolist()
        await _request(ladder, "warm-l", short_prompt, max_tokens=2)
        await _request(dense, "warm-d", short_prompt, max_tokens=2)
        await _request(ladder, "warm-l-deep", warm_deep, max_tokens=2)
        await _request(dense, "warm-d-deep", warm_deep, max_tokens=2)

        deep_tokens: dict[str, list] = {}
        for label in depths:
            toks, ttft, tok_s = await timed(ladder, f"lc-{label}", prompts[label])
            deep_tokens[label] = toks
            snap = ladder.resource_snapshot()
            out[label] = {
                "ttft_ms": round(ttft * 1e3, 1),
                "decode_tok_s": round(tok_s, 1),
                "kv_pages_peak": snap["kv_pages_peak"],
                "kv_pages_total": snap["kv_pages_total"],
                "table_dispatches": dict(snap["context_table_dispatches"]),
                "chunk_dispatches": dict(snap["context_chunk_dispatches"]),
            }

        # dense arm serves the DEEPEST prompt for the acceptance parity:
        # the ladder must be byte-identical to the dense-table path
        toks_dense, ttft_dense, _ = await timed(dense, "lc-64k-dense", prompts["64k"])
        out["64k"]["ttft_dense_ms"] = round(ttft_dense * 1e3, 1)
        out["parity_64k_ladder_vs_dense"] = deep_tokens["64k"] == toks_dense

        # short-prompt no-regression: the ladder's narrow tables must not be
        # slower than the dense path on <= 2K-scale traffic (both engines
        # warm; p50 of a few repeats to damp scheduling noise)
        lt, dt = [], []
        for i in range(5):
            _, t, _ = await _request(ladder, f"short-l{i}", short_prompt, max_tokens=8)
            lt.append(t)
            _, t, _ = await _request(dense, f"short-d{i}", short_prompt, max_tokens=8)
            dt.append(t)
        out["short_ttft_ladder_ms"] = round(float(np.percentile(lt, 50)) * 1e3, 1)
        out["short_ttft_dense_ms"] = round(float(np.percentile(dt, 50)) * 1e3, 1)
        out["short_ttft_ratio_ladder_over_dense"] = round(
            float(np.percentile(lt, 50)) / max(float(np.percentile(dt, 50)), 1e-9), 3
        )
    finally:
        for stop in reversed(cleanups):
            try:
                await stop()
            except Exception:
                import traceback

                traceback.print_exc()
        gc.collect()

    assert out["parity_64k_ladder_vs_dense"], \
        "page-table ladder broke token parity on the 64K prompt"
    out["target"] = (
        "64k serves end-to-end with EXACT ladder-vs-dense parity; deep TTFT "
        "scales sub-linearly vs dense (narrow tables + flat chunks); "
        "short-prompt ratio ~<= 1.0 (no regression); kv_pages_peak tracks "
        "the deep prompt's working set"
    )
    return out


async def run_quant_int8_parity(decode_tokens: int = 72) -> dict:
    """Weight-only int8 vs bf16 on the headline llama-1.3b config: decode
    throughput (the weight-bound roofline argument — int8 weights halve the
    HBM stream every decode step reads) plus numeric parity on greedy
    decoding.

    Throughput legs run the full run_config harness back-to-back in the same
    process. Parity runs model-level on the SAME
    random weights (same tiny seed — quantization is the only delta):

      teacher-forced agreement — the bf16 model free-runs a greedy chain,
        then the int8 model replays the SAME fed tokens and we compare each
        step's argmax. This is the well-defined per-step metric: this
        config's weights are random, so logit top-2 gaps are near-degenerate
        and a single flip in a free-running chain compounds into total
        divergence. CPU calibration at this geometry: raw per-step agreement
        ~0.82, every flip on a bf16 top-2 margin well under the logit std —
        so the asserted pair is raw agreement >= 0.7 AND "agree or near-tie"
        >= 0.95 (a step counts as near-tie when bf16's own margin between
        its choice and int8's choice is < 0.5, i.e. quantization only flips
        decisions bf16 held by under half a logit-std; real checkpoints'
        confident distributions agree far more often).
      max_abs_logit_delta — prefill last-token logits, bf16 vs int8, plus
        the delta normalized by the bf16 logit std (CPU-calibrated at ~0.22
        for this geometry/seed)."""
    import gc

    # ---- throughput: bf16 leg then int8 leg, same harness/shapes ----
    bf16 = await run_config(*HEADLINE, rounds=2)
    int8 = await run_config(*HEADLINE, rounds=2, model_id=quant_model_id())
    speedup = int8["tok_s"] / bf16["tok_s"] if bf16["tok_s"] else None

    # ---- model-level parity on identical pre-quantization weights ----
    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models.registry import load_model

    rng = np.random.default_rng(23)
    probe = rng.integers(1, 31000, PROMPT_LEN)
    positions = np.arange(PROMPT_LEN, dtype=np.int32)
    # pages from 1 (page 0 is the allocator's trash-page convention); enough
    # pages to cover prompt + decode_tokens
    n_pages = -(-(PROMPT_LEN + decode_tokens) // 64) + 1
    page_table = np.arange(1, n_pages + 1, dtype=np.int32)

    def greedy_chain(model_id: str, forced: list | None = None):
        """Free-running greedy argmax chain (forced=None), or the per-step
        argmax while replaying ``forced`` as the fed tokens (teacher-forced).
        Returns (argmaxes [decode_tokens], per-step logits [decode_tokens, V]
        — step 0 is the prefill's last-token logits)."""
        model, params = load_model(model_id)
        kv = model.init_kv_cache(n_pages + 2, 64)
        pts = np.zeros((1, n_pages + 2), np.int32)
        pts[0, : len(page_table)] = page_table
        logits, kv = jax.jit(model.prefill)(
            params, kv, jnp.asarray(probe, jnp.int32), jnp.asarray(positions),
            jnp.asarray(page_table), jnp.ones(PROMPT_LEN, bool),
            jnp.asarray(PROMPT_LEN - 1),
        )
        all_logits = [np.asarray(jax.device_get(logits), np.float32)]
        decode = jax.jit(model.decode)
        out = [int(all_logits[0].argmax())]
        feed = out[0] if forced is None else forced[0]
        for i in range(decode_tokens - 1):
            logits, kv = decode(
                params, kv, jnp.asarray([feed], jnp.int32),
                jnp.asarray([PROMPT_LEN + i], jnp.int32), jnp.asarray(pts),
                jnp.asarray([True]),
            )
            row = np.asarray(jax.device_get(logits), np.float32)[0]
            all_logits.append(row)
            tok = int(row.argmax())
            out.append(tok)
            feed = tok if forced is None else forced[i + 1]
        return out, np.stack(all_logits)

    ref_chain, l_bf16 = greedy_chain(json_model_id())
    tf_chain, l_int8 = greedy_chain(quant_model_id(), forced=ref_chain)
    # teacher forcing => both models saw IDENTICAL context each step, so the
    # per-step bf16 margin between its own choice and int8's choice measures
    # how strongly held every flipped decision was
    agree = [int(a == b) for a, b in zip(ref_chain, tf_chain)]
    flip_margins = [
        float(l_bf16[i, ref_chain[i]] - l_bf16[i, tf_chain[i]])
        for i in range(decode_tokens)
        if ref_chain[i] != tf_chain[i]
    ]
    NEAR_TIE = 0.5  # bf16 margins under this count as quantization-noise ties
    agree_or_tie = [
        int(a == b or float(l_bf16[i, a] - l_bf16[i, b]) < NEAR_TIE)
        for i, (a, b) in enumerate(zip(ref_chain, tf_chain))
    ]
    n_eval = min(64, decode_tokens)
    agree_64 = sum(agree[:n_eval]) / n_eval
    agree_or_tie_64 = sum(agree_or_tie[:n_eval]) / n_eval
    first_div = next((i for i, ok in enumerate(agree) if not ok), decode_tokens)
    max_delta = float(np.max(np.abs(l_bf16[0] - l_int8[0])))
    logit_std = float(np.std(l_bf16[0]))
    gc.collect()

    return {
        "tok_s_bf16": bf16["tok_s"],
        "tok_s_int8": int8["tok_s"],
        "speedup_int8_over_bf16": round(speedup, 3) if speedup else None,
        "rounds": {"bf16": bf16["rounds"], "int8": int8["rounds"]},
        "ttft_p50_ms": {"bf16": bf16["ttft_p50_ms"], "int8": int8["ttft_p50_ms"]},
        "greedy_decode_tokens": decode_tokens,
        "teacher_forced_agreement_64": round(agree_64, 4),
        "teacher_forced_agree_or_near_tie_64": round(agree_or_tie_64, 4),
        "flip_bf16_margins": [round(m, 4) for m in flip_margins],
        "free_run_first_divergence": first_div,
        "max_abs_logit_delta": round(max_delta, 4),
        "logit_std_bf16": round(logit_std, 4),
        "max_abs_logit_delta_over_std": round(max_delta / max(logit_std, 1e-9), 4),
        "weights_note": (
            "per-output-channel symmetric int8 on wq/wk/wv/wo/gate/up/down; "
            "embed/lm_head/norms stay bf16 — quantized weight bytes ~0.5x of "
            "the layer-stack stream the decode roofline reads; random weights "
            "=> near-degenerate logit top-2 gaps (CPU-calibrated raw "
            "agreement ~0.82), so the asserted pair is raw agreement plus "
            "agree-or-near-tie (flips only on bf16 margins < 0.5)"
        ),
        "target": (
            "speedup >= 1.25; over 64 teacher-forced steps: raw agreement "
            ">= 0.7 AND agree-or-near-tie(0.5) >= 0.95; "
            "max_abs_logit_delta_over_std <= 0.35"
        ),
        "pass": {
            "speedup": bool(speedup and speedup >= 1.25),
            "greedy_agreement": bool(agree_64 >= 0.7 and agree_or_tie_64 >= 0.95),
            "logit_delta": bool(max_delta / max(logit_std, 1e-9) <= 0.35),
        },
    }


def kv_int8_model_id(base: str | None = None) -> str:
    """A tiny:{...} model id with the int8 KV cache turned on — identical
    shapes/seed to its base, so the int8-vs-bf16 KV comparison isolates the
    cache quantization itself (the weight-int8 section's trick, applied to
    the cache)."""
    base = base or json_model_id()
    fam, js = base.split(":", 1)
    cfg = json.loads(js)
    cfg["kv_cache_dtype"] = "int8"
    return fam + ":" + json.dumps(cfg)


async def run_prefill_kv_int8(decode_tokens: int = 64) -> dict:
    """Int8 KV cache vs bf16 KV on the prefill-bound reference workload
    shape (3K ISL / 150 OSL — the config that has been flat for three judge
    rounds): TTFT p50 + tok/s with the cache as the only delta, the
    page-capacity ratio at an equal HBM budget (the ~2x claim, computed from
    the real per-page byte cost including scale planes), and teacher-forced
    greedy agreement over 64 steps (the acceptance bar: >= 0.9 — KV
    quantization error is per-row absmax/127, far gentler than weight
    quantization, so flips only happen on near-degenerate margins).

    On CPU (no TPU in the build container) the section scales the geometry
    down and forces DYNTPU_PALLAS=1 so the int8 decode + lookahead-prefill
    kernels execute in interpret mode — the smoke proves the whole
    config -> engine -> kernel path, the driver's TPU run prices it."""
    import gc
    import os

    import jax

    from dynamo_tpu.quant.kv import pages_for_hbm_budget

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        # interpret-mode kernels at a CPU-tractable geometry; D=128 keeps
        # the non-folded flash kernels (incl. the lookahead prefill) engaged
        geom = {
            "vocab_size": 512, "hidden_size": 256, "intermediate_size": 512,
            "num_layers": 2, "num_heads": 2, "num_kv_heads": 2,
            "head_dim": 128, "dtype": "f32",
        }
        base_id = "tiny:" + json.dumps(geom)
        run_kw = dict(
            rounds=1, prompt_len=192, decode_tokens=8, max_model_len=512,
            vocab=500,
        )
        batch, page_size = 2, 16
        tf_steps = min(decode_tokens, 16)  # interpret decode is slow
        tf_prompt = 64
        prev_pallas = os.environ.get("DYNTPU_PALLAS")
        os.environ["DYNTPU_PALLAS"] = "1"
    else:
        geom = json.loads(json_model_id().split(":", 1)[1])
        base_id = json_model_id()
        run_kw = dict(
            rounds=2, prompt_len=3072, decode_tokens=150, max_model_len=4096,
        )
        batch, page_size = 16, 128
        tf_steps = decode_tokens
        tf_prompt = PROMPT_LEN
        prev_pallas = None
    int8_id = kv_int8_model_id(base_id)

    try:
        # ---- throughput/TTFT: bf16-KV leg then int8-KV leg, same harness
        # shapes back-to-back in one process ----
        bf16 = await run_config(batch, page_size, model_id=base_id, **run_kw)
        int8 = await run_config(batch, page_size, model_id=int8_id, **run_kw)
        speedup = int8["tok_s"] / bf16["tok_s"] if bf16["tok_s"] else None
        ttft_ratio = (
            int8["ttft_p50_ms"] / bf16["ttft_p50_ms"]
            if bf16["ttft_p50_ms"]
            else None
        )

        # ---- page capacity at an equal HBM budget (deterministic
        # arithmetic from the real per-page cost incl. int8 scale planes;
        # page 0 is the allocator's reserved trash page either way) ----
        budget = 1 << 30  # 1 GiB nominal; the RATIO is budget-independent
        cap_args = (
            page_size, geom["num_kv_heads"], geom["head_dim"],
            geom["num_layers"],
        )
        pages_bf16 = pages_for_hbm_budget(budget, *cap_args, None)
        pages_int8 = pages_for_hbm_budget(budget, *cap_args, "int8")
        capacity_ratio = pages_int8 / max(1, pages_bf16)

        # ---- greedy-agreement parity: teacher-forced per-step argmax with
        # the int8 cache replaying the bf16 chain's fed tokens ----
        import jax.numpy as jnp

        from dynamo_tpu.models.registry import load_model

        rng = np.random.default_rng(23)
        probe = rng.integers(1, run_kw["vocab"] if "vocab" in run_kw else 31000, tf_prompt)
        positions = np.arange(tf_prompt, dtype=np.int32)
        tf_ps = 64 if not on_cpu else 16
        n_pages = -(-(tf_prompt + tf_steps) // tf_ps) + 1
        page_table = np.arange(1, n_pages + 1, dtype=np.int32)

        def greedy_chain(model_id: str, forced=None):
            model, params = load_model(model_id)
            kv = model.init_kv_cache(n_pages + 2, tf_ps)
            pts = np.zeros((1, n_pages + 2), np.int32)
            pts[0, : len(page_table)] = page_table
            logits, kv = jax.jit(model.prefill)(
                params, kv, jnp.asarray(probe, jnp.int32), jnp.asarray(positions),
                jnp.asarray(page_table), jnp.ones(tf_prompt, bool),
                jnp.asarray(tf_prompt - 1),
            )
            all_logits = [np.asarray(jax.device_get(logits), np.float32)]
            decode = jax.jit(model.decode)
            out = [int(all_logits[0].argmax())]
            feed = out[0] if forced is None else forced[0]
            for i in range(tf_steps - 1):
                logits, kv = decode(
                    params, kv, jnp.asarray([feed], jnp.int32),
                    jnp.asarray([tf_prompt + i], jnp.int32), jnp.asarray(pts),
                    jnp.asarray([True]),
                )
                row = np.asarray(jax.device_get(logits), np.float32)[0]
                all_logits.append(row)
                tok = int(row.argmax())
                out.append(tok)
                feed = tok if forced is None else forced[i + 1]
            return out, np.stack(all_logits)

        ref_chain, l_bf16 = greedy_chain(base_id)
        tf_chain, l_int8 = greedy_chain(int8_id, forced=ref_chain)
        agree = sum(int(a == b) for a, b in zip(ref_chain, tf_chain)) / len(ref_chain)
        max_delta = float(np.max(np.abs(l_bf16[0] - l_int8[0])))
        logit_std = float(np.std(l_bf16[0]))
    finally:
        if prev_pallas is None:
            os.environ.pop("DYNTPU_PALLAS", None)
        else:
            os.environ["DYNTPU_PALLAS"] = prev_pallas
        gc.collect()

    return {
        "kv_cache_dtype": "int8",
        "cpu_smoke": on_cpu,
        "workload": {
            "batch": batch, "page_size": page_size,
            "prompt_len": run_kw["prompt_len"],
            "decode_tokens": run_kw["decode_tokens"],
        },
        "tok_s_bf16_kv": bf16["tok_s"],
        "tok_s_int8_kv": int8["tok_s"],
        "speedup_int8_over_bf16_kv": round(speedup, 3) if speedup else None,
        "ttft_p50_ms": {"bf16": bf16["ttft_p50_ms"], "int8": int8["ttft_p50_ms"]},
        "ttft_ratio_int8_over_bf16": round(ttft_ratio, 3) if ttft_ratio else None,
        "stage_breakdown": {"bf16": bf16.get("stage_breakdown"),
                            "int8": int8.get("stage_breakdown")},
        "page_capacity_equal_hbm": {
            "budget_bytes": budget,
            "pages_bf16": pages_bf16,
            "pages_int8": pages_int8,
            "ratio": round(capacity_ratio, 3),
        },
        "teacher_forced_steps": tf_steps,
        "teacher_forced_agreement": round(agree, 4),
        "max_abs_logit_delta": round(max_delta, 4),
        "logit_std_bf16_kv": round(logit_std, 4),
        "target": (
            "greedy agreement >= 0.9 over the teacher-forced steps; "
            "capacity ratio ~2x (1.94 at ps=128 after scale planes); on TPU "
            "the prefill-bound TTFT should finally move (halved context "
            "stream + lookahead-prefetch flash prefill)"
        ),
        "pass": {
            "greedy_agreement": bool(agree >= 0.9),
            "page_capacity_2x": bool(capacity_ratio >= 1.8),
        },
    }


async def run_spec_ngram(
    batch: int = 8, page_size: int = 64, prompt_len: int = 192,
    decode_tokens: int = 128, model_id: str | None = None,
) -> dict:
    """Speculative decoding (prompt-lookup ngram:4 + batched multi-token
    verification, dynamo_tpu/spec/) vs the classic fused-window decode path
    on a repetition-heavy workload.

    Workload: each prompt tiles a short random pattern, so the n-gram
    proposer finds its suffixes immediately and greedy decoding on this
    model's random weights settles into short loops — the regime speculative
    decoding exists for (code, quoting, multi-turn chat). Both legs run the
    SAME prompts greedy on the SAME tiny seed, so the parity check is exact
    token equality per request; the speedup is decode throughput spec/base.
    Acceptance counters come from the engine's StageStats (the same numbers
    /metrics exports as dynamo_spec_proposed_total / _accepted_total)."""
    import dataclasses

    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    cfg = bench_config(batch, page_size, model_id=model_id)
    need_pages = batch * (-(-(prompt_len + decode_tokens) // page_size) + 4)
    cfg = dataclasses.replace(cfg, num_pages=max(cfg.num_pages, need_pages))
    rng = np.random.default_rng(7)
    prompts = []
    for _ in range(batch):
        pattern = rng.integers(1, 31000, 24)
        prompts.append(np.tile(pattern, -(-prompt_len // 24))[:prompt_len].tolist())

    async def leg(speculative: str | None):
        eng = AsyncJaxEngine(dataclasses.replace(cfg, speculative=speculative))
        await eng.start()

        async def one(i: int, rnd: int):
            req = EngineRequest(
                request_id=f"s{speculative or 'base'}-{rnd}-{i}",
                token_ids=list(prompts[i]),
                sampling=SamplingParams(
                    temperature=0.0, max_tokens=decode_tokens, ignore_eos=True
                ),
            )
            toks = []
            async for out in eng.generate(req):
                if out.token is not None:
                    toks.append(out.token)
            return toks

        try:
            await asyncio.gather(*[one(i, 0) for i in range(batch)])  # warmup
            best = None
            streams = None
            for rnd in (1, 2):
                t0 = time.monotonic()
                results = await asyncio.gather(*[one(i, rnd) for i in range(batch)])
                elapsed = time.monotonic() - t0
                total = sum(len(t) for t in results)
                if best is None or total / elapsed > best:
                    best = total / elapsed
                    streams = results
            stage = eng.stage_snapshot()
        finally:
            await eng.shutdown()
        return round(best, 2), streams, stage

    # k=8 on the bench: verify rounds are synchronous, so tokens-per-round is
    # what amortizes both the weight stream and the per-round dispatch+sync;
    # at this workload's ~0.95+ acceptance a round advances ~8 tokens/slot
    base_tok_s, base_streams, _ = await leg(None)
    spec_tok_s, spec_streams, stage = await leg("ngram:8")
    parity = sum(
        int(a == b) for a, b in zip(base_streams, spec_streams)
    ) / max(1, batch)
    speedup = spec_tok_s / base_tok_s if base_tok_s else None
    proposed = stage.get("spec_proposed", 0)
    accepted = stage.get("spec_accepted", 0)
    return {
        "tok_s_spec": spec_tok_s,
        "tok_s_base": base_tok_s,
        "speedup_spec_over_base": round(speedup, 3) if speedup else None,
        "greedy_parity": round(parity, 4),
        "spec_proposed": proposed,
        "spec_accepted": accepted,
        "acceptance_rate": round(accepted / max(1, proposed), 4),
        "spec_rounds": stage.get("spec_rounds", 0),
        "spec_emitted": stage.get("spec_emitted", 0),
        "speculative": "ngram:8",
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
        "workload_note": (
            "tiled 24-token patterns (prompt-lookup's native regime); both "
            "legs greedy on identical prompts/weights so parity is exact "
            "token equality per request"
        ),
        "target": "speedup >= 1.3 on this workload; greedy_parity == 1.0",
        "pass": {
            "speedup": bool(speedup and speedup >= 1.3),
            "greedy_parity": parity == 1.0,
        },
    }


async def run_spec_draft(osl: int | None = None) -> dict:
    """Draft-model speculation vs n-gram vs the classic decode path on a
    NON-repetitive workload — the regime n-gram acceptance collapses in and
    the draft-model proposer exists for (Leviathan/Chen: a small draft
    recovers multi-token rounds on arbitrary text).

    Prompts are pure random token streams (no tiling), so prompt-lookup
    finds no suffix match while the draft model keeps proposing. The draft
    IS the target model here (the only honestly-available draft in a
    synthetic-weights bench), which makes two things exact: greedy token
    parity vs the classic engine (asserted per request) and ~full
    acceptance of every proposed token. It also means the draft leg runs
    the target twice per round — on equal-size models wall-clock CANNOT
    beat classic by construction, so the gates are parity + acceptance +
    draft-pages-visible; the TPU run with a 5-10x smaller draft is where
    the tok/s win appears, and the three tok/s legs reported here price
    the dispatch overhead that win must clear.

    On CPU (no TPU in the build container) the section scales the geometry
    down like fleet_prefix does."""
    import dataclasses

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        geom = {
            "vocab_size": 512, "hidden_size": 512, "intermediate_size": 1024,
            "num_layers": 4, "num_heads": 4, "num_kv_heads": 2,
            "head_dim": 128, "dtype": "f32",
        }
        base_id = "tiny:" + json.dumps(geom)
        batch, page_size, prompt_len, vocab = 6, 16, 128, 500
        decode_tokens = osl or 64
        prefill_buckets = (64, 128, 256)
    else:
        base_id = json_model_id()
        batch, page_size, prompt_len, vocab = 8, 64, 192, 31000
        decode_tokens = osl or 128
        prefill_buckets = (128, 256, 512)
    K = 4
    pages_per_seq = -(-(prompt_len + decode_tokens + K + 1) // page_size) + 2
    num_pages = batch * pages_per_seq + 8

    rng = np.random.default_rng(17)
    # pure random streams: no token pair repeats by construction of the draw
    # (vocab >> prompt_len), so n-gram's longest-suffix match comes up empty
    prompts = [rng.integers(1, vocab, prompt_len).tolist() for _ in range(batch)]

    def cfg(speculative):
        return EngineConfig(
            model_id=base_id, page_size=page_size, num_pages=num_pages,
            max_seqs=batch, max_model_len=prompt_len + decode_tokens + 2 * K,
            prefill_buckets=prefill_buckets, decode_steps=8, pipeline_depth=2,
            speculative=speculative,
        )

    async def leg(speculative: str | None):
        eng = AsyncJaxEngine(cfg(speculative))
        await eng.start()

        async def one(i: int, rnd: int):
            req = EngineRequest(
                request_id=f"d{(speculative or 'base').split(':')[0]}-{rnd}-{i}",
                token_ids=list(prompts[i]),
                sampling=SamplingParams(
                    temperature=0.0, max_tokens=decode_tokens, ignore_eos=True
                ),
            )
            toks = []
            async for out in eng.generate(req):
                if out.token is not None:
                    toks.append(out.token)
            return toks

        try:
            await asyncio.gather(*[one(i, 0) for i in range(batch)])  # warmup
            best, streams = None, None
            for rnd in (1, 2):
                t0 = time.monotonic()
                results = await asyncio.gather(*[one(i, rnd) for i in range(batch)])
                elapsed = time.monotonic() - t0
                total = sum(len(t) for t in results)
                if best is None or total / elapsed > best:
                    best = total / elapsed
                    streams = results
            stage = eng.stage_snapshot()
            snap = eng.resource_snapshot()
        finally:
            await eng.shutdown()
        return round(best, 2), streams, stage, snap

    base_tok_s, base_streams, _, _ = await leg(None)
    ngram_tok_s, ngram_streams, ngram_stage, _ = await leg(f"ngram:{K}")
    draft_spec = f"draft:{base_id}:{K}"
    draft_tok_s, draft_streams, draft_stage, draft_snap = await leg(draft_spec)

    parity = sum(
        int(a == b) for a, b in zip(base_streams, draft_streams)
    ) / max(1, batch)
    ngram_parity = sum(
        int(a == b) for a, b in zip(base_streams, ngram_streams)
    ) / max(1, batch)

    def rate(stage):
        return stage.get("spec_accepted", 0) / max(1, stage.get("spec_proposed", 0))

    draft_rate, ngram_rate = rate(draft_stage), rate(ngram_stage)
    assert parity == 1.0, "draft==target greedy must be token-identical"
    assert draft_rate > ngram_rate, (
        f"draft acceptance {draft_rate} must beat n-gram's {ngram_rate} on "
        "non-repetitive text"
    )
    assert draft_snap.get("spec_draft_pages_total", 0) > 0, (
        "draft KV pages must be visible in resource_snapshot()"
    )
    return {
        "tok_s_draft": draft_tok_s,
        "tok_s_ngram": ngram_tok_s,
        "tok_s_classic": base_tok_s,
        "speedup_draft_over_classic": round(draft_tok_s / base_tok_s, 3),
        "speedup_ngram_over_classic": round(ngram_tok_s / base_tok_s, 3),
        "acceptance_rate_draft": round(draft_rate, 4),
        "acceptance_rate_ngram": round(ngram_rate, 4),
        "greedy_parity_draft": round(parity, 4),
        "greedy_parity_ngram": round(ngram_parity, 4),
        "spec_proposed_draft": draft_stage.get("spec_proposed", 0),
        "spec_accepted_draft": draft_stage.get("spec_accepted", 0),
        "spec_proposed_ngram": ngram_stage.get("spec_proposed", 0),
        "spec_draft_calls": draft_stage.get("spec_draft_calls", 0),
        "spec_draft_dispatch_s": draft_stage.get("spec_draft_s", 0.0),
        "spec_draft_prefills": draft_stage.get("spec_draft_prefills", 0),
        "draft_pages_total": draft_snap.get("spec_draft_pages_total", 0),
        "draft_model": "== target (exact-parity smoke; TPU uses a smaller draft)",
        "k": K,
        "batch": batch,
        "prompt_len": prompt_len,
        "decode_tokens": decode_tokens,
        "workload_note": (
            "pure random token streams — prompt-lookup finds no match "
            "(acceptance ~0) while the draft model proposes every round"
        ),
        "target": (
            "greedy_parity_draft == 1.0; acceptance_rate_draft > "
            "acceptance_rate_ngram; draft pages visible. tok/s legs price "
            "dispatch overhead: a same-size draft can't beat classic on "
            "wall clock (runs the target twice) — the TPU win needs a "
            "5-10x smaller draft"
        ),
        "pass": {
            "greedy_parity": parity == 1.0,
            "draft_acceptance_above_ngram": bool(draft_rate > ngram_rate),
            "draft_pages_visible": bool(
                draft_snap.get("spec_draft_pages_total", 0) > 0
            ),
        },
    }


async def run_multi_lora(M: int = 4, osl: int = 32) -> dict:
    """Multi-LoRA multiplexing: M fine-tunes of one base model served from
    ONE engine via gathered adapter kernels (Punica/S-LoRA BGMV shape).

    Three arms:
      - base engine, no adapters: the throughput reference at the same
        batch shape
      - lora engine, mixed batch: the B concurrent requests round-robin
        across M adapters — each decode window is ONE gathered dispatch
        (per-slot adapter ids gathered on device), not M per-adapter calls
      - parity: every request re-served ALONE on a fresh identical engine
        must be token-identical to its mixed-batch output (greedy)

    Plus an eviction arm: M adapters through M//2 device slots, proving the
    LRU hot-swap path churns without breaking determinism. Acceptance:
    mixed_tok_s_ratio >= 0.85 of base on the same shape (recorded, gated on
    TPU where the ratio is meaningful; CPU smoke records the measured value).

    On CPU (no TPU in the build container) the section scales the geometry
    down; parity/evictions are exact either way."""
    import gc

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        geom = {
            "vocab_size": 512, "hidden_size": 256, "intermediate_size": 512,
            "num_layers": 4, "num_heads": 4, "num_kv_heads": 2,
            "head_dim": 64, "dtype": "f32",
        }
        base_id = "tiny:" + json.dumps(geom)
        page_size, plen, vocab, rank = 16, 96, 500, 8
        prefill_buckets = (64, 128)
    else:
        base_id = json_model_id()
        page_size, plen, vocab, rank = 64, 512, 31000, 16
        prefill_buckets = (128, 256, 512)

    B = 8
    adapters = tuple(f"a{i}=random:{100 + i}" for i in range(M))
    num_pages = (B + 2) * (-(-(plen + osl) // page_size) + 2) + 8

    def cfg(**over):
        d = dict(
            model_id=base_id, page_size=page_size, num_pages=num_pages,
            max_seqs=B, max_model_len=2048, prefill_buckets=prefill_buckets,
            decode_steps=8, pipeline_depth=2,
        )
        d.update(over)
        return EngineConfig(**d)

    rng = np.random.default_rng(43)
    prompts = [rng.integers(1, vocab, plen).tolist() for _ in range(B)]
    lane_lora = [f"a{i % M}" for i in range(B)]

    async def one(eng, rid, prompt, lora):
        from dynamo_tpu.engine.sampling import SamplingParams
        from dynamo_tpu.engine.scheduler import EngineRequest

        req = EngineRequest(
            request_id=rid, token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=osl, ignore_eos=True),
            lora_name=lora,
        )
        toks = []
        async for out in eng.generate(req):
            if out.token is not None:
                toks.append(out.token)
        return toks

    async def throughput(eng, tag, loras):
        # warmup round (compiles + allocator steady state), then 2 measured
        await asyncio.gather(*[
            one(eng, f"w-{tag}-{i}", rng.integers(1, vocab, plen).tolist(), loras[i])
            for i in range(B)
        ])
        best, toks_last = 0.0, None
        for rnd in range(2):
            fresh = [rng.integers(1, vocab, plen).tolist() for _ in range(B)]
            use = prompts if rnd == 1 else fresh  # final round = parity prompts
            t0 = time.monotonic()
            results = await asyncio.gather(*[
                one(eng, f"{tag}-{rnd}-{i}", use[i], loras[i]) for i in range(B)
            ])
            dt = time.monotonic() - t0
            best = max(best, sum(len(t) for t in results) / dt)
            toks_last = results
        return best, toks_last

    cleanups = []
    try:
        base_eng = AsyncJaxEngine(cfg())
        await base_eng.start()
        cleanups.append(base_eng.shutdown)
        tok_s_base, _ = await throughput(base_eng, "base", [""] * B)

        lora_eng = AsyncJaxEngine(cfg(
            lora_adapters=adapters, max_loras=M, lora_rank=rank
        ))
        await lora_eng.start()
        cleanups.append(lora_eng.shutdown)
        tok_s_mixed, mixed_toks = await throughput(lora_eng, "mixed", lane_lora)
        lora_snap = lora_eng.resource_snapshot()

        # parity: each request alone on a FRESH identical engine (no shared
        # prefix cache / device state with the mixed run)
        alone_eng = AsyncJaxEngine(cfg(
            lora_adapters=adapters, max_loras=M, lora_rank=rank
        ))
        await alone_eng.start()
        cleanups.append(alone_eng.shutdown)
        parity = True
        for i in range(B):
            alone = await one(alone_eng, f"alone-{i}", prompts[i], lane_lora[i])
            parity = parity and alone == mixed_toks[i]

        # eviction/hot-swap arm: M adapters through M//2 slots, two passes —
        # the second pass's reloads must reproduce the first pass exactly
        evict_eng = AsyncJaxEngine(cfg(
            lora_adapters=adapters, max_loras=max(1, M // 2), lora_rank=rank
        ))
        await evict_eng.start()
        cleanups.append(evict_eng.shutdown)
        churn_prompt = prompts[0]
        first_pass = {}
        for name in [f"a{i}" for i in range(M)]:
            first_pass[name] = await one(evict_eng, f"e1-{name}", churn_prompt, name)
        swap_coherent = True
        for name in [f"a{i}" for i in range(M)]:
            again = await one(evict_eng, f"e2-{name}", churn_prompt, name)
            swap_coherent = swap_coherent and again == first_pass[name]
        evictions = evict_eng.runner.lora_store.evictions
    finally:
        for stop in reversed(cleanups):
            try:
                await stop()
            except Exception:
                import traceback

                traceback.print_exc()
        gc.collect()

    assert parity, "mixed-adapter batch diverged from single-adapter serving"
    assert swap_coherent, "LRU hot-swap changed a reloaded adapter's output"
    assert evictions > 0, "eviction arm never churned a slot"
    ratio = round(tok_s_mixed / max(tok_s_base, 1e-9), 3)
    if not on_cpu:
        assert ratio >= 0.85, f"mixed-adapter throughput ratio {ratio} < 0.85"
    return {
        "cpu_smoke": on_cpu,
        "workload": {
            "adapters": M, "batch": B, "prompt_len": plen, "osl": osl,
            "lora_rank": rank, "page_size": page_size,
        },
        "tok_s_base": round(tok_s_base, 2),
        "tok_s_mixed": round(tok_s_mixed, 2),
        "mixed_tok_s_ratio": ratio,
        "parity_mixed_vs_alone": parity,
        "hot_swap_coherent": swap_coherent,
        "resident_evictions": evictions,
        "lora_loads": lora_snap.get("lora_loads"),
        "lora_resident": lora_snap.get("lora_resident"),
        "target": (
            "parity exact; hot-swap coherent; evictions > 0; mixed 4-adapter "
            "decode >= 0.85x base throughput at the same batch shape (ONE "
            "gathered dispatch per window — gated on TPU, recorded on the "
            "CPU smoke)"
        ),
    }


async def run_http_serving(batch: int = 32, page_size: int = 64) -> dict:
    """HTTP-level serving numbers through /v1/chat/completions — the
    reference's published numbers are serving-stack numbers, not engine-loop
    numbers (reference: docs/architecture.md:57-87).

    Serves a full HF-FORMAT checkpoint (TinyLlama-1.1B geometry: config.json
    + safetensors + a genuine trained BPE tokenizer with chat template; the
    weight VALUES are synthetic — no real weights are reachable zero-egress,
    and throughput is independent of them)."""
    import gc
    import os
    import sys
    import time as _time

    import aiohttp

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from tools.make_hf_checkpoint import make_checkpoint

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.frontends.pipeline import build_pipeline
    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.llm.model_card import ModelDeploymentCard

    ckpt = "/tmp/dyntpu_ckpt_tinyllama_1b"
    if not os.path.exists(os.path.join(ckpt, "model.safetensors")):
        make_checkpoint(ckpt)

    card = ModelDeploymentCard.from_local_path(ckpt, name="tinyllama-1.1b-synth")
    engine = AsyncJaxEngine(EngineConfig.for_model(
        ckpt, page_size=page_size, num_pages=max(320, batch * 20 * 16 // page_size),
        max_seqs=batch, max_model_len=1024, prefill_buckets=(128, 256, 512),
        decode_steps=32, pipeline_depth=3,
        # pre-compile every decode-window + (packed-)prefill trace variant:
        # a cold XLA compile mid-HTTP-traffic stalls past client timeouts
        warmup=True,
    ))
    await engine.start()

    rng = np.random.default_rng(17)

    # engine-loop leg runner: the SAME engine and workload shape with the
    # HTTP/preprocessor/detokenizer/SSE stack removed — the serving-overhead
    # denominator: only a same-process ratio is meaningful.
    # 304 tokens = the measured tokenized length of this section's chat
    # prompts, so both legs hit the same prefill bucket/packing shape.
    async def engine_round(rnd: int):
        fresh = [rng.integers(1, 30000, 304).tolist() for _ in range(batch)]
        t0 = _time.monotonic()
        res = await asyncio.gather(*[
            _request(engine, f"eng-{rnd}-{i}", fresh[i], max_tokens=DECODE_TOKENS)
            for i in range(batch)
        ])
        tok_s = batch * DECODE_TOKENS / (_time.monotonic() - t0)
        return tok_s, [t for _, t, _ in res]

    # symmetric warmup (r4 post-mortem: the engine leg measured BELOW the
    # HTTP leg — ratio 1.105 > 1 — because it ran first, straight out of
    # 8-token warmups, paying the allocator's fill/evict transient that
    # run_config's full-length warmup pass exists to absorb):
    #   1. both legs get an 8-token compile warmup
    #   2. both legs get one full-length warmup round (allocator steady state)
    #   3. measured rounds ALTERNATE engine/HTTP so drift between legs
    #      cancels instead of biasing whichever leg ran last
    await asyncio.gather(*[
        _request(engine, f"eng-w-{i}", rng.integers(1, 30000, 304).tolist(), max_tokens=8)
        for i in range(batch)
    ])
    await engine_round(99)  # engine full-length warmup

    svc = HttpService(host="127.0.0.1", port=0)
    svc.manager.add(build_pipeline(engine, card))
    port = await svc.start()
    base = f"http://127.0.0.1:{port}/v1"

    words = ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot", "golf", "hotel"]

    async def one(session, i, rnd, max_tokens=DECODE_TOKENS):
        body = {
            "model": "tinyllama-1.1b-synth",
            "messages": [{
                "role": "user",
                "content": " ".join(words[(i + j + rnd) % len(words)] for j in range(96)) + f" q{rnd}-{i}",
            }],
            "max_tokens": max_tokens,
            "temperature": 0.0,
            "stream": True,
            "ext": {"ignore_eos": True},
        }
        t0 = _time.monotonic()
        ttft = None
        async with session.post(f"{base}/chat/completions", json=body) as r:
            r.raise_for_status()
            async for line in r.content:
                # first delta chunk of ANY kind: the service now emits the
                # role chunk at first-token time, so this is true first-token
                # TTFT — comparable to the engine leg's (first CONTENT can
                # lag several tokens while byte fragments stabilize)
                if line.startswith(b"data:") and b'"delta"' in line:
                    if ttft is None:
                        ttft = _time.monotonic() - t0
        if ttft is None:
            ttft = _time.monotonic() - t0  # stream completed with no delta
        # ignore_eos + max_tokens => the engine generated exactly max_tokens
        # (SSE delta count undercounts: multi-token BPE merges coalesce)
        return max_tokens, ttft

    async def http_round(session, rnd):
        t0 = _time.monotonic()
        results = await asyncio.gather(*[one(session, i, rnd) for i in range(batch)])
        elapsed = _time.monotonic() - t0
        toks = sum(n for n, _ in results)
        return toks / elapsed, elapsed, [t for _, t in results if t is not None]

    eng_rounds, http_rounds = [], []
    try:
        # no total timeout (aiohttp default 300 s aborted r3's whole bench):
        # per-request pacing is the sock_read gap between stream chunks, sized
        # far above worst-case engine stalls; the section-level timeout in
        # run() is the real backstop
        client_timeout = aiohttp.ClientTimeout(
            total=None, sock_connect=60, sock_read=600
        )
        async with aiohttp.ClientSession(timeout=client_timeout) as session:
            # HTTP leg warmups: compile (8 tok) + one full-length round, so
            # both legs enter their measured rounds in the same engine state
            await asyncio.gather(*[one(session, i, 0, max_tokens=8) for i in range(batch)])
            await http_round(session, 98)
            # measured rounds alternate legs (drift cancels)
            for rnd in (1, 2):
                eng_rounds.append(await engine_round(rnd))
                http_rounds.append(await http_round(session, rnd))
    finally:
        # a failed round must not leak the engine's HBM into the parity
        # sections that start their own engines next
        await svc.stop()
        await engine.shutdown()
        gc.collect()
    eng_best, eng_ttfts = max(eng_rounds, key=lambda r: r[0])
    tok_s, elapsed, ttfts = max(http_rounds, key=lambda r: r[0])
    return {
        "model": "TinyLlama-1.1B geometry (synthetic HF checkpoint)",
        "endpoint": "/v1/chat/completions (stream)",
        "tok_s": round(tok_s, 2),
        "engine_loop_tok_s": round(eng_best, 2),
        "http_over_engine_ratio": round(tok_s / eng_best, 3) if eng_best else None,
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 1),
        "engine_ttft_p50_ms": round(float(np.percentile(eng_ttfts, 50)) * 1e3, 1),
        "rounds": {
            "engine_tok_s": [round(r[0], 1) for r in eng_rounds],
            "http_tok_s": [round(r[0], 1) for r in http_rounds],
        },
        "batch": batch,
        "decode_tokens": DECODE_TOKENS,
        "elapsed_s": round(elapsed, 3),
        "target": "http_over_engine_ratio in (0.8, 1.0] (same process, same "
                  "shapes, symmetric warmup, alternating measured rounds)",
    }


async def run_replay() -> dict:
    """Trace-replay bench spine (dynamo_tpu/loadgen): seeded scenario traces
    replayed against in-process engines, producing per-scenario
    goodput/TTFT-p99/ITL-p99/tok_s — one arm per post-r05 subsystem:

      bursty_chat            base engine (the chat shape)
      int8_kv                bursty chat on an int8 KV cache
      long_context_sessions  shared-prefix sessions (table ladder / prefix cache)
      lora_churn             zipf hot/cold adapters over multiple tenants
      spec_draft             bursty chat under draft-model speculation
      fleet_prefix           session prefixes pulled from a peer holder
      mm_vl                  Qwen2-VL image requests (first perf numbers)

    On CPU (no TPU in the build container) geometry and budgets scale down —
    numbers are labeled cpu_smoke; the driver's TPU run prices the same
    scenarios at serving geometry. Every arm records the replay report's
    goodput verdict against the scenario's (platform-scaled) SLO budgets."""
    import gc

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.loadgen import compile_trace, load_scenario
    from dynamo_tpu.loadgen.replay import ReplayMetrics, replay_engine
    from dynamo_tpu.utils.goodput import GoodputTracker

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        base_id = "tiny"  # registry tiny (64-hidden f32): CPU-fast
        n, speed = 12, 2.0
        # CPU smoke budgets: generous enough that the verdict measures the
        # serving stack, not the absence of a TPU
        budgets = {"slo_ttft_ms": 30000.0, "slo_itl_ms": 5000.0}
        eng_kw = dict(
            page_size=4, num_pages=1024, max_seqs=4, max_model_len=640,
            prefill_buckets=(16, 32, 64, 128, 256), decode_steps=4,
            pipeline_depth=2,
        )
        scale = dict(
            isl_mean=24, isl_max=96, osl_dist="fixed", osl_mean=8, osl_max=8,
            rate_rps=8.0, vocab=256, **budgets,
        )
        lctx_scale = dict(
            shared_prefix_len=128, isl_mean=32, isl_max=96, osl_dist="fixed",
            osl_mean=8, osl_max=8, vocab=256, **budgets,
        )
    else:
        base_id = json_model_id()
        n, speed = 48, 1.0
        budgets = {"slo_ttft_ms": 2000.0, "slo_itl_ms": 100.0}
        eng_kw = dict(
            page_size=16, num_pages=8192, max_seqs=16, max_model_len=2048,
            prefill_buckets=(128, 256, 512), decode_steps=16,
            pipeline_depth=3,
        )
        scale = dict(isl_mean=128, isl_max=512, osl_mean=48, osl_max=128,
                     rate_rps=16.0, vocab=31000, **budgets)
        lctx_scale = dict(shared_prefix_len=512, isl_mean=128, isl_max=512,
                          osl_mean=32, osl_max=64, vocab=31000, **budgets)

    lora_names = ("a1", "a2", "a3", "a4", "a5", "a6")
    arms = [
        # (scenario key, spec, engine-config overrides, model id)
        ("bursty_chat",
         load_scenario("bursty_chat", num_requests=n).replace(**scale),
         {}, base_id),
        ("int8_kv",
         load_scenario("bursty_chat", num_requests=n, seed=1).replace(
             name="int8_kv", **scale),
         {"kv_cache_dtype": "int8"}, base_id),
        ("long_context_sessions",
         load_scenario("long_context_sessions", num_requests=max(8, n // 2))
         .replace(**lctx_scale),
         {}, base_id),
        ("lora_churn",
         load_scenario("lora_churn", num_requests=n).replace(
             adapters=lora_names, **scale),
         {"lora_adapters": lora_names, "max_loras": 4, "lora_rank": 4},
         base_id),
        ("spec_draft",
         load_scenario("bursty_chat", num_requests=max(8, n // 2), seed=2)
         .replace(name="spec_draft", **scale),
         {"speculative": f"draft:{base_id}:2"}, base_id),
        ("mm_vl",
         load_scenario("mm_vl", num_requests=max(6, n // 4)).replace(
             vocab=250, image_hw=(16, 16), **budgets),
         {"max_model_len": 640}, "tiny-vl"),
    ]

    out: dict = {
        "cpu_smoke": on_cpu,
        "platform": jax.devices()[0].platform,
        "speed": speed,
        "budgets": budgets,
        "scenarios": {},
    }
    goodput = GoodputTracker()
    for key, spec, over, model_id in arms:
        eng = AsyncJaxEngine(EngineConfig(model_id=model_id, **{**eng_kw, **over}))
        try:
            await eng.start()
            # warm the executables out of the measurement (a cold XLA compile
            # inside the replay would blow every budget on its own)
            warm = compile_trace(spec.replace(seed=spec.seed + 97,
                                              num_requests=2, images=spec.images))
            await replay_engine(eng, warm, spec=spec, speed=100.0)
            report = await replay_engine(
                eng, compile_trace(spec), spec=spec, speed=speed,
                goodput=goodput, metrics=ReplayMetrics(),
            )
            report.pop("outcomes", None)
            report["engine_stage"] = eng.stage_snapshot()
            out["scenarios"][key] = report
        finally:
            await eng.shutdown()
            gc.collect()

    # fleet_prefix arm: a holder engine computes (and serves) every session's
    # shared prefix; the replay engine's requests carry the holder hint, so
    # admission PULLS the prefix over the dataplane instead of recomputing
    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient

    spec = load_scenario(
        "long_context_sessions", num_requests=max(8, n // 2), seed=3,
    ).replace(name="fleet_prefix", **lctx_scale)
    trace = compile_trace(spec)
    ps = eng_kw["page_size"]
    prefix_blocks = spec.shared_prefix_len // ps
    cfg = dict(eng_kw, prefix_fetch_timeout_s=60.0)
    cleanups = []
    try:
        holder = AsyncJaxEngine(EngineConfig(model_id=base_id, **cfg))
        await holder.start()
        cleanups.append(holder.shutdown)
        puller = AsyncJaxEngine(EngineConfig(model_id=base_id, **cfg))
        await puller.start()
        cleanups.append(puller.shutdown)
        srv = await KvPullServer(holder, host="127.0.0.1").start()
        cleanups.append(srv.stop)
        fetcher = PrefixFetchClient(asyncio.get_running_loop(), timeout_s=60.0)
        puller.attach_prefix_fetch(fetcher)
        # seed the holder's cache with each session's shared prefix
        seen = set()
        for tr in trace:
            if tr.session not in seen:
                seen.add(tr.session)
                await _request(holder, f"seed-{tr.session}",
                               tr.token_ids[: spec.shared_prefix_len],
                               max_tokens=2)

        def attach_holder(req, tr):
            req.kv_holder_addr = srv.address
            req.kv_holder_blocks = prefix_blocks

        warm = compile_trace(spec.replace(seed=spec.seed + 97, num_requests=2))
        await replay_engine(puller, warm, spec=spec, speed=100.0,
                            request_hook=attach_holder)
        report = await replay_engine(
            puller, trace, spec=spec, speed=speed, goodput=goodput,
            metrics=ReplayMetrics(), request_hook=attach_holder,
        )
        report.pop("outcomes", None)
        sched = puller.scheduler
        report["prefix_fetch"] = {
            "hits": sched.prefix_fetch_hits,
            "fallbacks": sched.prefix_fetch_fallbacks,
            "pulled_blocks": sched.prefix_fetch_blocks,
            "pulled_bytes": sched.prefix_fetch_bytes,
        }
        out["scenarios"]["fleet_prefix"] = report
        assert sched.prefix_fetch_hits > 0, "fleet_prefix replay never pulled"
    finally:
        for stop in reversed(cleanups):
            try:
                await stop()
            except Exception:
                import traceback

                traceback.print_exc()
        gc.collect()

    out["overall_goodput"] = goodput.snapshot()["goodput"]
    # every scenario must have produced the acceptance keys
    for key, rep in out["scenarios"].items():
        for field in ("goodput", "ttft_p99_ms", "tok_s"):
            assert rep.get(field) is not None, f"replay.{key}.{field} missing"
    return out


async def run_step_anatomy() -> dict:
    """Step-anatomy plane (utils/step_anatomy.py): price the host-overhead
    fraction and the live roofline fraction across three serving arms —
    plain decode, draft-model speculation, and multi-LoRA — from the
    per-dispatch phase attribution the scheduler now records on every step.

    The r5 decomposition ("decode at 69.8% of the 5.05 ms floor, ~30% of
    every step host overhead") was a one-off tools/profile_decode.py run;
    this section re-derives the same two numbers from the standing plane so
    every future round (and the item-3 fused-decode work) has a before/after
    in the artifact. Consistency gate: the anatomy's device_wait seconds
    must equal the scheduler's reconcile_wait_s counter (same measurement
    site), so host_frac = 1 - reconcile_wait/total is checkable from
    StageStats alone."""
    import gc

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        base_id = "tiny"
        n, plen, osl = 8, 48, 32
        eng_kw = dict(
            page_size=4, num_pages=1024, max_seqs=8, max_model_len=256,
            prefill_buckets=(16, 32, 64), decode_steps=4, pipeline_depth=2,
        )
        vocab = 256
    else:
        base_id = json_model_id()
        n, plen, osl = 16, PROMPT_LEN, DECODE_TOKENS
        eng_kw = dict(
            page_size=64, num_pages=4096, max_seqs=16, max_model_len=1024,
            prefill_buckets=(128, 256), decode_steps=32, pipeline_depth=3,
        )
        vocab = 31000

    lora_names = ("a1", "a2", "a3")
    arms = [
        ("decode", {}, ()),
        ("spec_draft", {"speculative": f"draft:{base_id}:2"}, ()),
        ("multi_lora",
         {"lora_adapters": lora_names, "max_loras": 2, "lora_rank": 4},
         lora_names),
    ]

    async def one(eng, rid, prompt, lora_name=""):
        req = EngineRequest(
            request_id=rid, token_ids=list(prompt),
            sampling=SamplingParams(
                temperature=0.0, max_tokens=osl, ignore_eos=True
            ),
            lora_name=lora_name,
        )
        async for _ in eng.generate(req):
            pass

    out: dict = {"cpu_smoke": on_cpu, "platform": jax.devices()[0].platform}
    rng = np.random.default_rng(7)
    for key, over, adapters in arms:
        eng = AsyncJaxEngine(EngineConfig(model_id=base_id, **{**eng_kw, **over}))
        try:
            await eng.start()
            # warm the executables (and the LoRA host loads) out of the
            # measured anatomy, then reset the counters so the recorded
            # phases cover steady-state serving only
            await asyncio.gather(*[
                one(eng, f"w-{i}", rng.integers(1, vocab, plen).tolist(),
                    lora_name=adapters[i % len(adapters)] if adapters else "")
                for i in range(min(4, n))
            ])
            from dynamo_tpu.utils.step_anatomy import StepAnatomy

            sched = eng.scheduler
            sched.anatomy = StepAnatomy(roofline=sched.anatomy.roofline)
            store = getattr(eng.runner, "lora_store", None)
            if store is not None:
                store.anatomy = sched.anatomy
            base_wait = sched.stage.reconcile_wait_s
            t0 = time.monotonic()
            await asyncio.gather(*[
                one(eng, f"m-{i}", rng.integers(1, vocab, plen).tolist(),
                    lora_name=adapters[i % len(adapters)] if adapters else "")
                for i in range(n)
            ])
            wall = time.monotonic() - t0
            snap = sched.anatomy.snapshot()
            wait_s = sum(
                v for k, v in snap["phase_seconds"].items()
                if k.startswith("device_wait.")
            )
            total_s = sum(snap["phase_seconds"].values())
            stage_wait = sched.stage.reconcile_wait_s - base_wait
            arm = {
                "host_frac": snap["host_frac"],
                "decode_host_frac": snap["decode_host_frac"],
                "roofline_frac": snap["roofline_frac"],
                "dispatch_gap_ms_p50": snap["dispatch_gap_ms_p50"],
                "dispatches": snap["dispatches"],
                "phase_seconds": snap["phase_seconds"],
                "attributed_s": round(total_s, 4),
                "wall_s": round(wall, 4),
                "device_wait_s": round(wait_s, 4),
                "stage_reconcile_wait_s": round(stage_wait, 4),
                "output_tokens": n * osl,
            }
            # acceptance: the anatomy's device_wait and StageStats'
            # reconcile_wait_s are the SAME measurement (one site feeds
            # both), so host_frac is auditable from the stage counters
            spec_wait = sum(
                v for k, v in snap["phase_seconds"].items()
                if k in ("device_wait.spec_draft", "device_wait.spec_verify")
            )
            assert abs((wait_s - spec_wait) - stage_wait) <= max(
                0.05, 0.05 * max(wait_s, stage_wait)
            ), f"{key}: anatomy device_wait {wait_s} (spec {spec_wait}) " \
               f"disagrees with reconcile_wait_s {stage_wait}"
            assert arm["host_frac"] is not None
            if key == "decode":
                assert arm["roofline_frac"] is not None
                assert snap["dispatches"].get("decode_window", 0) >= 2
            if key == "spec_draft":
                assert snap["dispatches"].get("spec_verify", 0) >= 1
                assert snap["dispatches"].get("spec_draft", 0) >= 1
            if key == "multi_lora":
                assert snap["dispatches"].get("lora_slot_load", 0) >= 1
            out[key] = arm
        finally:
            await eng.shutdown()
            gc.collect()
    return out


async def run_prefill_anatomy() -> dict:
    """Prefill anatomy (the dispatch-cost attack): the same ref-shaped burst
    through two engines that differ ONLY in ``prefill_pipeline_depth`` —
    1 = strict reconcile-per-packed-call (the old mixed-regime behavior),
    2 = dispatch-ahead. Acceptance, asserted here: exact greedy token parity
    between the arms (the knob must not touch numerics), and strictly fewer
    forced blocking reconciles (``stage.prefill_stalls``) in the pipelined
    arm. The artifact also records the standing plane's measured per-call
    fixed cost (``prefill_fixed_ms``, the rows-amortized host_prep+dispatch
    seconds) and roofline fraction, so the tools/profile_prefill.py
    decomposition has a live counterpart every round."""
    import gc

    import jax

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    on_cpu = jax.devices()[0].platform == "cpu"
    if on_cpu:
        base_id = "tiny"
        # 12 x 48-token prompts against 64-row buckets at 2 lanes: each
        # burst is ~6 packed calls back-to-back, so the depth-1 arm pays a
        # forced stall per call while depth-2 overlaps them
        n, plen, osl = 12, 48, 16
        eng_kw = dict(
            page_size=4, num_pages=1024, max_seqs=16, max_model_len=256,
            prefill_buckets=(16, 32, 64), prefill_lanes=2,
            decode_steps=4, pipeline_depth=2,
        )
        vocab = 256
    else:
        # the reference-shaped workload (ISL 3072 / OSL 150): each prompt
        # is 6 chunked 512-row calls, the regime the ~10 ms per-call fixed
        # cost dominates
        base_id = json_model_id()
        n, plen, osl = 8, 3072, 150
        eng_kw = dict(
            page_size=64, num_pages=1024, max_seqs=8, max_model_len=4096,
            prefill_buckets=(128, 256, 512), prefill_lanes=4,
            decode_steps=32, pipeline_depth=3,
        )
        vocab = 31000

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, vocab, plen).tolist() for _ in range(n)]

    async def one(eng, rid, prompt, toks_out, ttfts):
        req = EngineRequest(
            request_id=rid, token_ids=list(prompt),
            sampling=SamplingParams(
                temperature=0.0, max_tokens=osl, ignore_eos=True
            ),
        )
        t0 = time.monotonic()
        first = None
        toks_out[rid] = []
        async for out in eng.generate(req):
            if out.token is not None:
                if first is None:
                    first = time.monotonic() - t0
                toks_out[rid].append(out.token)
        if first is not None:
            ttfts.append(first)

    out: dict = {"cpu_smoke": on_cpu, "platform": jax.devices()[0].platform}
    arm_tokens: dict[int, dict] = {}
    for depth in (1, 2):
        eng = AsyncJaxEngine(EngineConfig(
            model_id=base_id, prefill_pipeline_depth=depth, **eng_kw
        ))
        try:
            await eng.start()
            toks: dict = {}
            ttfts: list = []
            # warm the executables out of the measured counters
            await asyncio.gather(*[
                one(eng, f"w-{i}", prompts[i], toks, ttfts)
                for i in range(min(4, n))
            ])
            sched = eng.scheduler
            from dynamo_tpu.utils.step_anatomy import StepAnatomy

            sched.anatomy = StepAnatomy(roofline=sched.anatomy.roofline)
            base_stalls = sched.stage.prefill_stalls
            base_calls = sched.stage.prefill_calls
            base_waits = sched.stage.reconcile_waits
            toks, ttfts = {}, []
            t0 = time.monotonic()
            await asyncio.gather(*[
                one(eng, i, prompts[i], toks, ttfts) for i in range(n)
            ])
            wall = time.monotonic() - t0
            snap = sched.anatomy.snapshot()
            arm_tokens[depth] = toks
            out[f"depth{depth}"] = {
                "prefill_stalls": sched.stage.prefill_stalls - base_stalls,
                "prefill_calls": sched.stage.prefill_calls - base_calls,
                "reconcile_waits": sched.stage.reconcile_waits - base_waits,
                "prefill_fixed_ms": snap["prefill_fixed_ms"],
                "prefill_host_frac": snap["prefill_host_frac"],
                "prefill_roofline_frac": snap["prefill_roofline_frac"],
                "ttft_p50_ms": round(float(np.median(ttfts)) * 1e3, 1),
                "wall_s": round(wall, 4),
                "output_tokens": sum(len(v) for v in toks.values()),
            }
        finally:
            await eng.shutdown()
            gc.collect()

    d1, d2 = out["depth1"], out["depth2"]
    # acceptance 1: the knob is a scheduling change only — greedy tokens
    # must match token-for-token across the arms
    assert set(arm_tokens[1]) == set(arm_tokens[2])
    mismatch = [r for r in arm_tokens[1] if arm_tokens[1][r] != arm_tokens[2][r]]
    assert not mismatch, f"greedy parity broke for requests {mismatch}"
    out["greedy_parity"] = "exact"
    # acceptance 2: dispatch-ahead must strictly cut the forced blocking
    # reconciles the depth-1 contract pays per packed call
    assert d1["prefill_stalls"] > 0, "depth-1 arm recorded no prefill stalls"
    assert d2["prefill_stalls"] < d1["prefill_stalls"], (
        f"pipelined arm did not reduce stalls: "
        f"{d2['prefill_stalls']} vs {d1['prefill_stalls']}"
    )
    # both arms price the standing prefill plane
    assert d2["prefill_fixed_ms"] is not None
    assert d2["prefill_roofline_frac"] is not None
    out["stall_delta"] = d1["prefill_stalls"] - d2["prefill_stalls"]
    return out


async def run_events() -> dict:
    """Flight-recorder overhead (observability tentpole): the journal must be
    effectively free on the hot path, so price one emit() against the MEASURED
    decode step wall on this platform and assert the fraction stays under 1%.
    Also price the forensic read side — timeline() reconstruction against a
    full 4096-event ring with a loaded capture set — since /debug/requests
    runs on the serving event loop."""
    import jax

    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest
    from dynamo_tpu.utils.events import CAPACITY, EventJournal

    from tests.test_engine import tiny_engine_config  # CPU-smoke config

    on_cpu = jax.devices()[0].platform == "cpu"
    osl = 32
    if on_cpu:
        eng = AsyncJaxEngine(tiny_engine_config(decode_steps=4, pipeline_depth=2))
        prompt = list(range(1, 33))
    else:
        eng = AsyncJaxEngine(bench_config(8, 64))
        prompt = np.random.default_rng(7).integers(1, 31000, 256).tolist()

    # ---- decode step wall: bs=1 so tokens map 1:1 to model steps; measure
    # first-token..last-token (decode only, prefill excluded). Also count the
    # journal events the run ACTUALLY emitted for the measured request — the
    # journal's hot-path contract is a handful of emits per request, not per
    # token, so the per-step overhead is (emits/request) amortized over the
    # request's decode steps.
    from dynamo_tpu.utils import events as events_mod

    async def one(rid):
        req = EngineRequest(
            request_id=rid, token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=osl,
                                    ignore_eos=True),
        )
        stamps = []
        async for out in eng.generate(req):
            if out.token is not None:
                stamps.append(time.perf_counter())
        return stamps

    try:
        await eng.start()
        await one("warm")  # executables out of the measurement
        stamps = await one("measured")
    finally:
        await eng.shutdown()
    assert len(stamps) == osl
    step_wall_s = (stamps[-1] - stamps[0]) / (osl - 1)
    emits_per_request = len(events_mod.JOURNAL.events_for("measured"))
    assert emits_per_request >= 3  # enqueued/admitted/first_token/finished

    # ---- emit cost: a dedicated journal (same code path as the global one),
    # realistic payload, mean over enough rounds to dominate timer noise
    j = EventJournal()
    n_emit = 20000
    t0 = time.perf_counter()
    for i in range(n_emit):
        j.emit("sched.admitted", request_id="bench-r%d" % (i % 64),
               tenant="bench", priority="standard", slot=i % 8, tokens=256)
    emit_s = (time.perf_counter() - t0) / n_emit

    # ---- forensic reconstruction: full ring + loaded capture set, read the
    # way /debug/requests/{id} does (pinned chain wins over ring scan)
    full = EventJournal()
    n_req = 256
    for i in range(CAPACITY):
        full.emit("request.first_token", request_id="r%d" % (i % n_req))
    for i in range(32):
        full.pin("r%d" % i, "ttft_over_budget")
    reads = 200
    t0 = time.perf_counter()
    for i in range(reads):
        tl = full.timeline("r%d" % (i % n_req))
        assert tl["found"]
    reconstruct_ms = (time.perf_counter() - t0) / reads * 1e3

    # the request's whole journal cost amortized over its decode steps, as a
    # fraction of one measured step: the honest per-step price at the REAL
    # emit rate (the planes emit on lifecycle decisions, not per token)
    overhead_frac = (emit_s * emits_per_request / osl) / step_wall_s
    out = {
        "cpu_smoke": on_cpu,
        "decode_step_wall_ms": round(step_wall_s * 1e3, 4),
        "emit_us": round(emit_s * 1e6, 3),
        "emits_per_request": emits_per_request,
        "emit_overhead_frac": round(overhead_frac, 6),
        "journal_events": CAPACITY,
        "reconstruct_ms": round(reconstruct_ms, 4),
    }
    # acceptance: the journal costs <1% of decode step wall at the measured
    # emit rate — even against the CPU-smoke toy model's sub-ms steps
    assert overhead_frac < 0.01, out
    # the forensic read must be interactive-debugging cheap (it runs on the
    # serving loop); 50 ms is generous even for CPU-smoke machines
    assert reconstruct_ms < 50.0, out
    return out


async def run_metering() -> dict:
    """Cost-attribution plane (observability tentpole): drive a real engine
    with two tagged tenants and check BOTH conservation identities on the
    live ledger — attributed device-seconds vs the step-anatomy wall totals,
    and per-tier summed KV byte-seconds vs the occupancy integrals. Then
    price the hot-path writes (one on_phase split, one KV edge pair) against
    the MEASURED decode step wall and assert the metering plane costs <1%
    of a step, same contract as the flight recorder."""
    import jax

    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest
    from dynamo_tpu.utils.metering import MeterLedger
    from dynamo_tpu.utils.step_anatomy import StepRecord

    from tests.test_engine import tiny_engine_config  # CPU-smoke config

    on_cpu = jax.devices()[0].platform == "cpu"
    osl = 32
    if on_cpu:
        eng = AsyncJaxEngine(tiny_engine_config(decode_steps=4, pipeline_depth=2))
        prompt = list(range(1, 33))
    else:
        eng = AsyncJaxEngine(bench_config(8, 64))
        prompt = np.random.default_rng(11).integers(1, 31000, 256).tolist()

    async def one(rid, tenant):
        req = EngineRequest(
            request_id=rid, token_ids=list(prompt), tenant=tenant,
            sampling=SamplingParams(temperature=0.0, max_tokens=osl,
                                    ignore_eos=True),
        )
        stamps = []
        async for out in eng.generate(req):
            if out.token is not None:
                stamps.append(time.perf_counter())
        return stamps

    try:
        await eng.start()
        await one("warm", "bench-a")  # executables out of the measurement
        stamps = await one("measured", "bench-a")
        # a concurrent two-tenant pair so the split path (multi-row bills,
        # shared decode windows) is what conservation is checked against
        await asyncio.gather(one("m2", "bench-a"), one("m3", "bench-b"))
        cons = eng.meter.conservation(anatomy=eng.scheduler.anatomy)
        snap = eng.meter.snapshot()
        anat = eng.scheduler.anatomy
        with anat._lock:
            d_steps = anat.steps_total.get("decode_window", 0)
            d_calls = anat.dispatch_counts.get("decode_window", 0)
        steps_per_dispatch = max(1.0, d_steps / max(1, d_calls))
    finally:
        await eng.shutdown()
    assert len(stamps) == osl
    step_wall_s = (stamps[-1] - stamps[0]) / (osl - 1)

    # ---- hot-path price: a dedicated ledger (same code path), a billed
    # two-row record, mean over enough rounds to dominate timer noise
    led = MeterLedger()
    rec = StepRecord(seq=1, ts=0.0, kind="decode_window", bill=[
        ("bench-r1", "bench-a", "", "standard", 3.0),
        ("bench-r2", "bench-b", "", "standard", 1.0),
    ])
    n = 20000
    # best-of-3 with a warmup pass: the first repeat absorbs dict sizing
    # and bytecode-cache first-touch; min strips scheduler noise so the
    # price reflects the steady state the contract is about
    on_phase_s = math.inf
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            led.on_phase(rec, "device_wait", 1e-4)
        on_phase_s = min(on_phase_s, (time.perf_counter() - t0) / n)
    kv_acq_s = math.inf
    kv_rel_s = math.inf
    for r in range(3):
        t0 = time.perf_counter()
        for i in range(n):
            led.kv_acquire("hbm", (r, i), 4096, ("bench-a", "bench-r1"))
        kv_acq_s = min(kv_acq_s, (time.perf_counter() - t0) / n)
        t0 = time.perf_counter()
        for i in range(n):
            led.kv_release("hbm", (r, i))
        kv_rel_s = min(kv_rel_s, (time.perf_counter() - t0) / n)

    # per MODEL STEP: 4 phase splits per decode dispatch amortized over the
    # dispatch's steps, plus ~1/page_size acquire edges per sequence-step (a
    # fresh page every page_size generated tokens). The matching releases
    # land in the end-of-life free batch, not inside a decode step, so the
    # steady-state step pays only the acquire half (release price reported)
    page_size = eng.config.page_size
    per_step_s = (4.0 * on_phase_s) / steps_per_dispatch + kv_acq_s / page_size
    overhead_frac = per_step_s / step_wall_s
    out = {
        "cpu_smoke": on_cpu,
        "decode_step_wall_ms": round(step_wall_s * 1e3, 4),
        "on_phase_us": round(on_phase_s * 1e6, 3),
        "kv_acquire_us": round(kv_acq_s * 1e6, 3),
        "kv_release_us": round(kv_rel_s * 1e6, 3),
        "overhead_frac": round(overhead_frac, 6),
        "device_rel_err": cons["device"]["rel_err"],
        "kv_rel_err": {t: cons["kv"][t]["rel_err"] for t in cons["kv"]},
        "device_s_total": snap["device_s_total"],
        "tenants_metered": sorted(t for t in snap["tenants"] if t),
    }
    # acceptance: both identities hold on the LIVE ledger (by-construction
    # exact; tolerance covers float summation order), and the metering
    # plane prices under 1% of a measured decode step
    assert cons["device"]["rel_err"] < 1e-6, out
    for tier, side in cons["kv"].items():
        assert side["rel_err"] < 1e-6, (tier, out)
    assert {"bench-a", "bench-b"} <= set(snap["tenants"]), out
    assert overhead_frac < 0.01, out
    return out


async def run_router_scale() -> dict:
    """Router radix index under internet-scale distinct-prefix churn: the
    bounded/sharded index (PR 17) vs the unbounded baseline.

    Pure-CPU, pure-index — no engine. Both arms store a HOT working set
    (depth-4 prefix chains) and then churn distinct single-block prefixes
    through the index, re-touching the hot set as they go; the bounded arm
    churns >1M distinct prefixes against a 75k-node cap, the unbounded arm a
    smaller volume (an unbounded 1M-node Python tree is ~0.5 GB — the
    monotonic-growth checkpoints prove the leak without paying for it).
    Acceptance, asserted here: resident nodes hold the cap under churn while
    the unbounded baseline only grows; the hot-set hit ratio stays within 5%
    of unbounded; hot-lookup p99 stays flat (the per-shard dict walk does
    not price the resident count)."""
    import random

    from dynamo_tpu.llm.kv_events import KvCacheEvent, StoredBlock
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer, RouterEvent

    CAP = 75_000
    SHARDS = 4
    HOT = 2_000          # hot prefix lines, each a depth-4 chain
    HOT_DEPTH = 4
    BOUNDED_CHURN = 1_050_000
    UNBOUNDED_CHURN = 200_000
    PROBES = 10_000
    rng = random.Random(20817)

    def hot_seq(j: int) -> list:
        return [(1 << 40) + j * HOT_DEPTH + d for d in range(HOT_DEPTH)]

    async def arm(churn: int, **kw) -> dict:
        idx = KvIndexer(kv_block_size=16, use_native=False, **kw)
        for j in range(HOT):
            seq = hot_seq(j)
            idx.apply_event(RouterEvent(1, KvCacheEvent.stored(
                None, [StoredBlock((1 << 50) + h, h) for h in seq])))
        checkpoints = []
        for i in range(churn):
            idx.apply_event(RouterEvent(1, KvCacheEvent.stored(
                None, [StoredBlock((1 << 51) + i, i)])))
            if i % 8 == 0:
                # keep the hot working set recently-hit, the way real
                # traffic does — LRU only protects what gets walked
                idx.find_matches(hot_seq((i // 8) % HOT))
            if i % 50_000 == 0:
                checkpoints.append(idx.radix_stats()["nodes"])
                await asyncio.sleep(0)  # keep the section cancellable
        # hot-set hit ratio: matched blocks over expected across every line
        matched = sum(
            idx.find_matches(hot_seq(j)).scores.get(1, 0) for j in range(HOT)
        )
        hot_ratio = matched / float(HOT * HOT_DEPTH)
        # lookup latency over a hit/miss mix (misses = absent prefixes)
        times_ns = []
        for k in range(PROBES):
            seq = hot_seq(rng.randrange(HOT)) if k % 2 == 0 else [(1 << 45) + k]
            t0 = time.perf_counter_ns()
            idx.find_matches(seq)
            times_ns.append(time.perf_counter_ns() - t0)
        times_ns.sort()
        s = idx.radix_stats()
        return {
            "churn": churn,
            "resident_nodes": s["nodes"],
            "resident_bytes": s["bytes"],
            "cap_nodes": s["max_nodes"],
            "shards": s["shards"],
            "evictions": s["evictions_total"],
            "hot_hit_ratio": round(hot_ratio, 4),
            "lookup_p50_ms": round(times_ns[len(times_ns) // 2] / 1e6, 5),
            "lookup_p99_ms": round(times_ns[(len(times_ns) * 99) // 100] / 1e6, 5),
            "node_checkpoints": checkpoints,
        }

    unbounded = await arm(UNBOUNDED_CHURN)
    bounded = await arm(BOUNDED_CHURN, max_nodes=CAP, num_shards=SHARDS)
    # the unbounded baseline only ever grows (the pre-PR-17 behavior this
    # section exists to price): every churn checkpoint is >= the last
    cps = unbounded["node_checkpoints"]
    assert all(b >= a for a, b in zip(cps, cps[1:])), cps
    # the bounded index holds its cap under >1M distinct-prefix churn
    assert bounded["resident_nodes"] <= CAP, bounded
    assert bounded["evictions"] > 0, bounded
    # hot-working-set hit ratio within 5% of unbounded (LRU keeps what the
    # traffic actually walks)
    assert bounded["hot_hit_ratio"] >= unbounded["hot_hit_ratio"] - 0.05, (
        bounded, unbounded)
    # lookup p99 must not price the resident count (generous bound: shared
    # CPU-smoke timers are noisy at single-digit microseconds)
    assert bounded["lookup_p99_ms"] <= unbounded["lookup_p99_ms"] * 3.0 + 0.2, (
        bounded, unbounded)
    return {
        "bounded": bounded,
        "unbounded": unbounded,
        # the gated headline keys (bench_compare router_scale.*)
        "resident_nodes": bounded["resident_nodes"],
        "hot_hit_ratio": bounded["hot_hit_ratio"],
        "lookup_p50_ms": bounded["lookup_p50_ms"],
        "lookup_p99_ms": bounded["lookup_p99_ms"],
    }


#: filled section-by-section so a crash in section N never erases sections
#: 1..N-1 — __main__ prints whatever landed here even on a fatal error
DETAIL: dict = {}
ERRORS: dict = {}


async def _section(name: str, thunk, timeout_s: float) -> None:
    """Run one bench section with its own timeout and error isolation.

    A section that times out is cancelled; every section's engines shut down
    in finally blocks, so the next section starts clean. The failure lands in
    ERRORS[name] and the bench carries on — a crash in one section must never
    zero the whole artifact (r3 post-mortem: one aiohttp timeout discarded 10
    minutes of measured results)."""
    import gc
    import os
    import sys
    import traceback

    wanted = {
        s.strip()
        for s in os.environ.get("DYNTPU_BENCH_SECTIONS", "").split(",")
        if s.strip()
    }
    if wanted and name not in wanted:
        print(f"[bench] section {name} skipped (DYNTPU_BENCH_SECTIONS)",
              file=sys.stderr, flush=True)
        return

    t0 = time.monotonic()
    try:
        DETAIL[name] = await asyncio.wait_for(thunk(), timeout_s)
        print(f"[bench] section {name} ok in {time.monotonic()-t0:.0f}s",
              file=sys.stderr, flush=True)
    except Exception as e:
        tb = traceback.format_exc(limit=8)
        ERRORS[name] = {
            "error": f"{type(e).__name__}: {e}",
            "elapsed_s": round(time.monotonic() - t0, 1),
            "traceback_tail": tb[-1500:],
        }
        print(f"[bench] section {name} FAILED after {time.monotonic()-t0:.0f}s: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
    finally:
        gc.collect()


class NoTpuError(RuntimeError):
    """The bench measures a TPU and JAX found none."""


async def run(cpu_smoke: bool = False) -> dict:
    import os

    import jax

    # the artifact must say what it measured on. A kernel the dispatch chose
    # either compiles or the section fails: nothing here switches the
    # kernels off and carries on under their names.
    dev = jax.devices()[0]
    DETAIL["platform"] = dev.platform
    DETAIL["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    if dev.platform != "tpu" and not cpu_smoke:
        raise NoTpuError(
            f"bench.py measures a TPU and JAX found {dev.platform!r} "
            f"({dev.device_kind}); pass --cpu-smoke for a CPU smoke run "
            "(tiny shapes, counts and parity only)"
        )
    await _section("headline_bs%d_ps%d" % HEADLINE,
                   lambda: run_config(*HEADLINE), 1500)
    await _section("continuity_bs%d_ps%d" % CONTINUITY,
                   lambda: run_config(*CONTINUITY), 900)
    DETAIL.update({
        "prompt_len": PROMPT_LEN,
        "decode_tokens": DECODE_TOKENS,
        "devices": 1,
        "r01_value_bs8": R01_VALUE_BS8,
    })
    if os.environ.get("DYNTPU_BENCH_PARITY", "1") != "0":
        # the reference's tracked workload shape (BASELINE.md: 3K ISL /
        # 150 OSL serving configs)
        await _section("ref_workload_isl3k_osl150", lambda: run_config(
            16, 128, rounds=2, prompt_len=3072, decode_tokens=150,
            max_model_len=4096,
        ), 1500)
        await _section("http_serving", run_http_serving, 2400)
        # on-chip decode numbers for the non-Llama families (the vLLM patch
        # exists substantially for DeepSeek MLA — SURVEY.md §2.4)

        async def mla():
            return {
                **await run_config(32, 128, rounds=3, model_id=mla_model_id()),
                "roofline_note": (
                    "~1.3B dense-MLP MLA geometry (kv_lora 512/rope 64): "
                    "weights ~2.6 GB bf16 -> ~315 weight-bound steps/s "
                    "(3.15 ms/step floor); latent cache is 1.25 KB/token vs "
                    "4 KB for the GQA headline (the MLA win); the section "
                    "wall adds prefill amortization on top"
                ),
            }

        async def moe():
            return {
                **await run_config(32, 128, rounds=3, model_id=moe_model_id()),
                "roofline_note": (
                    "~2.3B Mixtral-geometry top-2/8: at bs32 nearly every "
                    "expert is active each step -> full ~2.3 GB read -> ~355 "
                    "steps/s weight-bound ceiling"
                ),
            }

        await _section("mla_decode", mla, 1500)
        await _section("moe_decode", moe, 1500)
        # speculative decoding vs classic decode on a repetition-heavy
        # workload: speedup + exact greedy parity + acceptance counters
        await _section("spec_ngram", run_spec_ngram, 1800)
        # draft-model speculation vs n-gram vs classic on a NON-repetitive
        # workload (exact greedy parity draft==target; acceptance must beat
        # n-gram's where prompt-lookup collapses)
        await _section("spec_draft", run_spec_draft, 1800)
        # multi-LoRA multiplexing: M fine-tunes in one mixed batch through
        # the gathered adapter kernels vs the base engine at the same shape,
        # with exact mixed-vs-alone parity and the LRU eviction arm (the
        # round-10 tentpole)
        await _section("multi_lora", run_multi_lora, 1800)
        # weight-only int8 vs bf16 on the headline config: throughput ratio +
        # greedy/logit parity (the round-6 tentpole)
        await _section("parity_quant_int8", run_quant_int8_parity, 2400)
        # int8 KV cache vs bf16 KV on the prefill-bound ref-workload shape:
        # TTFT/tok_s, ~2x page capacity at equal HBM, greedy parity (the
        # round-7 tentpole; composes with the int8 weights above)
        await _section("prefill_kv_int8", run_prefill_kv_int8, 2400)
        await _section("parity_disagg", run_disagg_parity, 2400)
        # streamed vs monolithic KV transfer on the socket path: TTFT on
        # multi-chunk prompts, token parity, compute/transfer overlap
        await _section("disagg_stream", run_disagg_stream, 1800)
        await _section("parity_kv_routing", run_routing_parity, 1500)
        # fleet-wide prefix cache: cross-worker KV pull vs recompute on a
        # shared-system-prompt workload (exact parity + TTFT ratio)
        await _section("fleet_prefix", run_fleet_prefix, 1800)
        # live migration: migrated-vs-killed mid-decode interrupts (exact
        # parity, client-visible pause p99, tokens salvaged, goodput delta)
        await _section("migration", run_migration, 1800)
        # multi-tenant QoS: tenant-A burst vs tenant-B steady through one
        # engine, QoS on/off — B's ITL-p99 must hold its budget under the
        # burst (priority scheduling + token-budget shed), off arm violates
        await _section("qos", run_qos, 1800)
        # long-context serving: 16K/64K TTFT + tok/s + KV high-watermark
        # through the page-table ladder, exact parity vs the dense path,
        # short-prompt no-regression ratio (CPU smoke scales down 16x)
        await _section("long_context", run_long_context, 2400)
        await _section("parity_host_offload", run_offload_parity, 1200)
        # third KV tier: disk-backed cold-session resume — parked sessions
        # demote host -> disk, resume restores through FETCHING_KV; TTFT
        # vs the recompute arm + exact greedy parity + byte cap under churn
        await _section("kv_tiers", run_kv_tiers, 1800)
    # trace-replay spine (ROADMAP item 2): seeded scenarios re-price the
    # post-r05 subsystems in goodput/TTFT-p99/ITL-p99 terms per scenario
    await _section("replay", run_replay, 2400)
    # step-anatomy plane (r7 tentpole): host-overhead + roofline fractions
    # from the standing per-dispatch attribution, across decode/spec/LoRA
    await _section("step_anatomy", run_step_anatomy, 1500)
    # prefill anatomy (r19 tentpole): depth-1 vs dispatch-ahead packed
    # prefill on the ref-shaped burst — exact greedy parity + strictly
    # fewer forced stalls asserted; fixed-cost + roofline from the plane
    await _section("prefill_anatomy", run_prefill_anatomy, 1500)
    # flight recorder: emit cost vs the measured decode step wall (<1%
    # asserted) + forensic timeline-reconstruction latency
    await _section("events", run_events, 900)
    # cost attribution: both conservation identities on a live two-tenant
    # engine ledger + the metering hot-path priced against the measured
    # decode step wall (<1% asserted inside)
    await _section("metering", run_metering, 900)
    # router index under >1M distinct-prefix churn: bounded/sharded vs
    # unbounded (pure CPU; resident cap + hot-hit ratio asserted inside)
    await _section("router_scale", run_router_scale, 900)
    return _result()


#: summary-line aliases for the replay scenarios (tail-budget compression);
#: bench_detail.json keeps the full names
_REPLAY_ALIASES = {
    "bursty_chat": "bursty",
    "int8_kv": "int8",
    "long_context_sessions": "lctx",
    "lora_churn": "lora",
    "spec_draft": "spec",
    "fleet_prefix": "fleet",
    "mm_vl": "mm",
}


def _get(d: dict | None, *path, default=None):
    cur = d
    for p in path:
        if not isinstance(cur, dict) or p not in cur:
            return default
        cur = cur[p]
    return cur


def _summary(errors: dict) -> dict:
    """The compact (<1.5 KB) per-section key numbers for the round artifact.

    The driver records only the TAIL of stdout, so the LAST printed line must
    be self-contained: headline, continuity, ref workload, http ratio, mla/moe,
    and all three parity ratios — measured AND derived, labeled — plus a
    compact errors map (r4 post-mortem: the full-detail line was truncated and
    the artifact lost its own headline)."""
    head = DETAIL.get("headline_bs%d_ps%d" % HEADLINE)
    cont = DETAIL.get("continuity_bs%d_ps%d" % CONTINUITY)
    refw = DETAIL.get("ref_workload_isl3k_osl150")
    http = DETAIL.get("http_serving")
    mla = DETAIL.get("mla_decode")
    moe = DETAIL.get("moe_decode")
    dis = DETAIL.get("parity_disagg")
    dstream = DETAIL.get("disagg_stream")
    rout = DETAIL.get("parity_kv_routing")
    fleet = DETAIL.get("fleet_prefix")
    mig = DETAIL.get("migration")
    qos = DETAIL.get("qos")
    lctx = DETAIL.get("long_context")
    off = DETAIL.get("parity_host_offload")
    ktier = DETAIL.get("kv_tiers")
    quant = DETAIL.get("parity_quant_int8")
    kvq = DETAIL.get("prefill_kv_int8")
    spec = DETAIL.get("spec_ngram")
    sdraft = DETAIL.get("spec_draft")
    mlora = DETAIL.get("multi_lora")
    replay = DETAIL.get("replay")
    sanat = DETAIL.get("step_anatomy")
    panat = DETAIL.get("prefill_anatomy")
    evts = DETAIL.get("events")
    mtr = DETAIL.get("metering")
    rscale = DETAIL.get("router_scale")
    # per-scenario acceptance keys (replay.{scenario}.{goodput,ttft_p99_ms,
    # itl_p99_ms,tok_s}); wall/lag/stage detail rides bench_detail.json
    replay_summary = None
    if replay:
        # compact aliased-array form against the driver's hard 2000-char
        # stdout-tail cap (BENCH_r02..r05 all recorded exactly 2000):
        # replay_cols names the columns, _REPLAY_ALIASES maps the keys; the
        # full named-key reports (replay.{scenario}.{goodput,ttft_p99_ms,
        # itl_p99_ms,tok_s} + wall/lag/stage breakdowns) ride
        # bench_detail.json under their full scenario names
        def ims(v):  # integer ms: sub-ms precision is noise at p99
            return round(v) if isinstance(v, float) else v

        replay_summary = {
            _REPLAY_ALIASES.get(sc, sc): [
                _get(rep, "goodput"),
                ims(_get(rep, "ttft_p99_ms")),
                ims(_get(rep, "itl_p99_ms")),
                ims(_get(rep, "tok_s")),
            ]
            for sc, rep in sorted(replay.get("scenarios", {}).items())
        }
    return {
        "platform": DETAIL.get("platform"),
        "headline_tok_s": _get(head, "tok_s"),
        # r01_value_bs8 (the fixed continuity anchor) moved to
        # bench_detail.json — it is a code constant, not a measurement, and
        # the summary line's truncation budget needs the bytes
        "continuity_bs8_tok_s": _get(cont, "tok_s"),
        "ref_workload_isl3k_osl150": {
            "tok_s": _get(refw, "tok_s"), "ttft_p50_ms": _get(refw, "ttft_p50_ms"),
            # stages (the per-stage engine seconds kept here to chase the
            # flat-TTFT attribution) moved to bench_detail.json: r19's
            # prefill_anatomy keys below ARE that attribution now (the fixed
            # cost was per-dispatch, and the pipelined arm's TTFT is gated),
            # and the summary-line truncation budget needed the bytes
        },
        "http_serving": {
            # ttft_p50_ms and tok_s moved to bench_detail.json (summary-line
            # truncation budget — tok_s went with the kv_tiers keys; the
            # gated ratio carries the signal)
            "http_over_engine_ratio": _get(http, "http_over_engine_ratio"),
        },
        "mla_decode_tok_s": _get(mla, "tok_s"),
        "moe_decode_tok_s": _get(moe, "tok_s"),
        "parity_quant_int8": {
            # tok_s_int8/tok_s_bf16, teacher_forced_agreement_64,
            # max_abs_logit_delta + agree_or_near_tie_64 all moved to
            # bench_detail.json (summary-line truncation budget; the section
            # asserts agreement itself and the gated speedup carries the
            # signal)
            "speedup": _get(quant, "speedup_int8_over_bf16"),
        },
        "prefill_kv_int8": {
            # kv_cache_dtype + both raw tok/s legs ride bench_detail.json
            # (summary-line truncation budget; the ratios + agreement gate
            # carry the signal)
            # teacher_forced_agreement also rides bench_detail.json
            # (truncation budget; the section asserts it itself)
            "ttft_ratio": _get(kvq, "ttft_ratio_int8_over_bf16"),
            "page_capacity_ratio": _get(kvq, "page_capacity_equal_hbm", "ratio"),
        },
        "spec_ngram": {
            # tok_s_spec/tok_s_base live in bench_detail.json (the speedup
            # ratio carries them; summary-line truncation budget)
            "speedup": _get(spec, "speedup_spec_over_base"),
            "acceptance_rate": _get(spec, "acceptance_rate"),
            # raw proposed/accepted counters + greedy_parity live in
            # bench_detail.json (summary-line truncation budget; the section
            # asserts parity itself and the rate carries the signal)
        },
        # draft-model speculation on NON-repetitive text: acceptance is the
        # headline signal (the draft proposes where n-gram can't; a
        # same-size CPU-smoke draft can't win wall clock by construction).
        # tok_s legs, speedups, raw counters, and the draft-pool gauges all
        # ride bench_detail.json under spec_draft.
        "spec_draft": {
            "accept_draft": _get(sdraft, "acceptance_rate_draft"),
            # accept_ngram (the control arm) and greedy_parity moved to
            # bench_detail.json (truncation budget; the section asserts
            # parity itself and the draft acceptance is the gated signal)
        },
        # M=4 adapters mixed-batch vs base at the same shape: the throughput
        # ratio + exact mixed-vs-alone parity + LRU churn proof (raw tok/s
        # legs and load/residency gauges ride bench_detail.json)
        "multi_lora": {
            "mixed_tok_s_ratio": _get(mlora, "mixed_tok_s_ratio"),
            # parity_mixed_vs_alone + resident_evictions moved to
            # bench_detail.json (truncation budget; both are asserted
            # inside the section and the gated ratio carries the signal)
        },
        "parity_disagg": {
            "ratio_measured_1chip": _get(dis, "ratio_measured_1chip"),
            "ratio_projected": _get(dis, "ratio_projected"),
        },
        "disagg_stream": {
            # streamed/monolithic raw TTFTs + token_parity live in
            # bench_detail.json (the section asserts parity itself — a break
            # fails the section; the ratio + overlap carry the signal)
            "ttft_ratio": _get(dstream, "ttft_ratio_streamed_over_monolithic"),
            "overlap_fraction": _get(dstream, "overlap_fraction"),
        },
        "parity_kv_routing": {
            # ratio_derived lives in bench_detail.json (truncation budget)
            "ratio_measured": _get(rout, "ttft_ratio"),
        },
        "fleet_prefix": {
            "ttft_ratio_bf16": _get(fleet, "bf16", "ttft_ratio_hit_over_recompute"),
            # ttft_ratio_int8 + wire_bytes_ratio_int8 moved to
            # bench_detail.json (summary-line truncation budget needed the
            # bytes for the migration keys; the bf16 ratio is the gated one)
        },
        # live migration: exact-parity flag, client-visible pause p99, and
        # the migrated-minus-killed goodput delta (salvage counters, kill
        # pause, and the budget ride bench_detail.json)
        "migration": {
            "parity": _get(mig, "parity"),
            "pause_ms_p99": _get(mig, "pause_ms_p99"),
            "goodput_delta": _get(mig, "goodput_delta"),
        },
        # multi-tenant QoS isolation: B's ITL-p99 on/off ratio under the A
        # burst, the fraction of A's burst the token budget shed, and
        # critical-class goodput under burst (per-tenant breakdowns, budget
        # values, and the engine enforcement audit ride bench_detail.json)
        "qos": {
            "tenant_b_itl_ratio": _get(qos, "tenant_b_itl_ratio"),
            "shed_fraction": _get(qos, "shed_fraction"),
            "critical_goodput": _get(qos, "critical_goodput"),
        },
        # 16K/64K TTFT + KV high-watermark (acceptance keys; tok/s and the
        # dispatch histograms ride bench_detail.json)
        "long_context": {
            "ttft_ms_64k": _get(lctx, "64k", "ttft_ms"),
            # ttft_ms_16k, kv_peak_64k, tok_s_64k and parity_64k moved to
            # bench_detail.json (truncation budget; the section asserts
            # parity itself and the gated 64k TTFT carries the signal)
            "short_ratio": _get(lctx, "short_ttft_ratio_ladder_over_dense"),
        },
        # restore_bw_source moved to bench_detail.json (truncation budget)
        "parity_host_offload": {
            "ratio_projected": _get(off, "projection", "ttft_ratio_projected"),
        },
        # third KV tier, cold-session resume: disk-restore TTFT over the
        # recompute arm (lower is better), exact greedy parity, and the
        # disk-resident footprint after churn (raw TTFT legs, restore
        # counters, and the cap-under-churn proof ride bench_detail.json)
        "kv_tiers": {
            "resume_ttft_ratio": _get(ktier, "resume_ttft_ratio"),
            "restore_parity": _get(ktier, "restore_parity"),
            "disk_resident_bytes": _get(ktier, "disk", "bytes_resident"),
        },
        # step anatomy (decode arm): host-overhead fraction of engine time,
        # HBM-floor fraction of measured decode seconds, and the decode
        # window dispatch cadence — the item-3 fused-decode before/after
        # numbers (per-arm spec/LoRA breakdowns ride bench_detail.json)
        # dispatch_gap_ms_p50 moved to bench_detail.json (truncation
        # budget; the gated host_frac/roofline_frac carry the signal)
        "step_anatomy": {
            "host_frac": _get(sanat, "decode", "host_frac"),
            "roofline_frac": _get(sanat, "decode", "roofline_frac"),
        },
        # prefill anatomy (pipelined arm): measured per-call fixed cost from
        # the standing plane, dispatch count, and TTFT p50 — the r19
        # dispatch-cost before/after keys. Parity + stall deltas are
        # asserted inside the section; per-arm detail rides bench_detail.json
        "prefill_anatomy": {
            "fixed_ms": _get(panat, "depth2", "prefill_fixed_ms"),
            "dispatches": _get(panat, "depth2", "prefill_calls"),
            "ttft_p50_ms": _get(panat, "depth2", "ttft_p50_ms"),
        },
        # flight recorder: the journal's per-step cost fraction at the
        # measured emit rate (the section asserts <1% itself) and the
        # forensic timeline-reconstruction latency against a full ring.
        # Short keys for the truncation budget — the full-named report
        # (emit_us, decode_step_wall_ms, emits_per_request,
        # emit_overhead_frac, reconstruct_ms) rides bench_detail.json
        "events": {
            "emit_frac": _get(evts, "emit_overhead_frac"),
            "rec_ms": _get(evts, "reconstruct_ms"),
        },
        # cost attribution: the WORST conservation residual across both
        # planes (device vs anatomy, per-tier byte-seconds — each asserted
        # <1e-6 inside the section) + the metering hot-path's per-step
        # price fraction (asserted <1% inside). Short keys for the
        # truncation budget — per-plane residuals, on_phase/kv-edge
        # prices, and the per-tenant rollup ride bench_detail.json
        "metering": {
            "err": max(
                (v for v in [
                    _get(mtr, "device_rel_err"),
                    *(_get(mtr, "kv_rel_err") or {}).values(),
                ] if v is not None),
                default=None,
            ),
            "frac": _get(mtr, "overhead_frac"),
        },
        # router index under >1M distinct-prefix churn (bounded arm): the
        # gated resident-cap / hot-hit / lookup-latency keys (per-arm
        # detail incl. the unbounded baseline rides bench_detail.json)
        "router_scale": {
            "lookup_p99_ms": _get(rscale, "lookup_p99_ms"),
            "resident_nodes": _get(rscale, "resident_nodes"),
            "hot_hit_ratio": _get(rscale, "hot_hit_ratio"),
        },
        # the trace-replay spine: goodput under per-scenario SLO budgets,
        # columns per replay_cols (budgets + cpu_smoke flag + full named
        # reports in bench_detail.json)
        "replay_cols": "goodput,ttft_p99_ms,itl_p99_ms,tok_s"
        if replay_summary else None,
        "replay": replay_summary,
        # 120-char cap per error: a raw XLA error repr is routinely thousands
        # of chars and would re-trigger the very tail truncation this summary
        # exists to survive (full text lands in bench_detail.json)
        "errors": {k: v.get("error", "?")[:120] for k, v in errors.items()} or None,
    }


def _result(extra_errors: dict | None = None) -> dict:
    """Assemble the compact one-line artifact from whatever sections landed.

    Full per-section detail goes to bench_detail.json next to this script;
    stdout carries only `value` + the compact summary so the driver's tail
    truncation can never eat the round's own numbers."""
    import os

    head = DETAIL.get("headline_bs%d_ps%d" % HEADLINE)
    value = head["tok_s"] if head else 0.0
    errors = {**ERRORS, **(extra_errors or {})}
    detail_path = os.environ.get("DYNTPU_BENCH_DETAIL") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_detail.json")
    try:
        # temp + rename: a mid-write failure must not leave a truncated file
        # where post-mortem tooling expects the previous run's detail
        tmp = detail_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"detail": DETAIL, "errors": errors}, f, indent=1, default=str)
        os.replace(tmp, detail_path)
    except (OSError, TypeError, ValueError):
        # a non-serializable value in DETAIL must not destroy the artifact
        # line itself — the summary carries plain floats and serializes fine
        detail_path = None
    out = {
        "metric": "engine_decode_throughput_llama1.3b_bf16",
        "value": value,
        "unit": "out_tok/s/chip",
        "vs_baseline": round(value / PARITY_TARGET_TOK_S, 3),
        "summary": _summary(errors),
        "detail_file": detail_path,
    }
    return out


def main(argv: list | None = None) -> int:
    """Run the bench; 0 only when every section that ran succeeded. Whatever
    finished is printed either way — a failure must not erase it, and must
    not pass for a result."""
    import sys

    argv = sys.argv[1:] if argv is None else argv
    unknown = [a for a in argv if a != "--cpu-smoke"]
    if unknown:
        print(f"usage: bench.py [--cpu-smoke] (unknown: {unknown})", file=sys.stderr)
        return 2

    # the bench starts 10+ engine instances with identical geometries: with
    # the persistent cache, instance N>1 deserializes from disk
    from dynamo_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()

    try:
        result = asyncio.run(run(cpu_smoke="--cpu-smoke" in argv))
    except NoTpuError as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 1
    except BaseException as e:  # even a fatal crash must emit the sections that finished
        if isinstance(e, (KeyboardInterrupt, SystemExit)):
            label = "interrupted"
        else:
            label = f"{type(e).__name__}: {e}"
        result = _result(extra_errors={"__run__": {"error": label}})
    # compact separators: the default ", " formatting alone costs ~200 chars
    # on a full summary line
    print(json.dumps(result, separators=(",", ":")))
    return 1 if result["summary"]["errors"] else 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
