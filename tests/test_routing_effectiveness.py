"""KV-aware routing effectiveness: on prefix-heavy traffic across two real
engines, routing by radix-tree overlap must recover ~all prefix tokens from
cache while random routing forfeits roughly half — the mechanism behind the
reference's 3x TTFT / 2x latency claim for KV-aware routing (reference:
docs/architecture.md:76-87, the "KV-aware routing" rows of BASELINE.md).
"""

import asyncio
import random

import pytest

from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import EngineRequest
from dynamo_tpu.llm.kv_router.indexer import KvIndexer, RouterEvent

from tests.test_engine import _collect, tiny_engine_config

pytestmark = pytest.mark.slow

BS = 4  # kv block size == page size


def _mk_engines(n):
    engines = []
    indexer = KvIndexer(kv_block_size=BS)

    async def boot():
        for i in range(n):
            sink = (lambda wid: (
                lambda ev: indexer.apply_event(RouterEvent(worker_id=wid, event=ev))
            ))(i)
            eng = AsyncJaxEngine(
                tiny_engine_config(page_size=BS, num_pages=128, max_seqs=4),
                kv_event_sink=sink,
            )
            await eng.start()
            engines.append(eng)

    asyncio.run(boot())
    return engines, indexer


def _run_workload(engines, indexer, kv_aware: bool, sessions=4, turns=8) -> int:
    """Prefix-heavy multi-turn replay; returns total RECOMPUTED prefill tokens
    (the TTFT driver: tokens the chosen worker had to prefill because its
    cache lacked them)."""
    rng = random.Random(42)
    total_recompute = 0
    histories = {
        s: [100 + 31 * s + j for j in range(12)]  # distinct 3-block roots
        for s in range(sessions)
    }

    async def one(eng, rid, prompt):
        req = EngineRequest(
            request_id=rid,
            token_ids=list(prompt),
            sampling=SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True),
        )
        toks, _, cached = await _collect(eng, req)
        return toks, cached

    r = 0
    for turn in range(turns):
        for s in range(sessions):
            prompt = histories[s]
            if kv_aware:
                scores = indexer.find_matches_for_request(prompt).scores
                wid = max(scores, key=scores.get) if scores else rng.randrange(len(engines))
            else:
                wid = rng.randrange(len(engines))
            toks, cached = asyncio.run(one(engines[wid], f"{kv_aware}-{s}-{turn}", prompt))
            total_recompute += len(prompt) - cached
            # multi-turn growth: the answer + a new user turn extend the history
            histories[s] = prompt + toks + [7 + r % 90]
            r += 1
    return total_recompute


def test_kv_routing_beats_random_on_prefix_heavy_traffic():
    engines, indexer = _mk_engines(4)
    try:
        recompute_kv = _run_workload(engines, indexer, kv_aware=True)
    finally:
        for e in engines:
            asyncio.run(e.shutdown())

    engines2, indexer2 = _mk_engines(4)
    try:
        recompute_random = _run_workload(engines2, indexer2, kv_aware=False)
    finally:
        for e in engines2:
            asyncio.run(e.shutdown())

    # KV-aware pins every session to the worker holding its prefix, so only
    # genuinely-new tokens are prefilled; random routing lands each turn on a
    # worker whose cache is stale-or-empty for that session most of the time
    assert recompute_kv > 0
    assert recompute_random >= 2 * recompute_kv, (
        f"kv-aware recomputed {recompute_kv} prefill tokens, "
        f"random recomputed {recompute_random}"
    )
