"""Prefill dispatch-ahead (EngineConfig.prefill_pipeline_depth): config
validation, backlog-aware chunk-bucket promotion, the prefill roofline floor
arithmetic, the StepAnatomy prefill plane, and token-identical parity of the
pipelined scheduler vs the strict reconcile-per-call baseline (greedy,
seeded, and int8-KV arms) plus cancel-mid-pipeline safety."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.utils.step_anatomy import (
    DEVICE_PEAKS,
    RooflineModel,
    StepAnatomy,
)


# ---------------- config ----------------


def test_pipeline_depth_validation():
    assert EngineConfig(model_id="tiny").prefill_pipeline_depth == 2
    assert EngineConfig(model_id="tiny", prefill_pipeline_depth=1) is not None
    with pytest.raises(ValueError):
        EngineConfig(model_id="tiny", prefill_pipeline_depth=0)


def test_chunk_len_for_backlog_promotion():
    cfg = EngineConfig(
        model_id="tiny", page_size=4, num_pages=256, max_model_len=1024,
        prefill_buckets=(16, 32, 64), prefill_flat_depth=128,
    )
    # flat-depth budget = 64*128 = 8192: at context depth 256 only the
    # 16-row bucket fits (16*272 <= 8192 < 32*288)
    assert cfg.chunk_len_for(256) == 16
    # a deep backlog (>= 2*top rows pending) doubles the budget: 32*288
    # now fits, 64*320 still doesn't — fewer, larger dispatches
    assert cfg.chunk_len_for(256, backlog_rows=128) == 32
    assert cfg.chunk_len_for(256, backlog_rows=127) == 16
    # no promotion past what the doubled budget allows
    assert cfg.chunk_len_for(256, backlog_rows=10_000) == 32


# ---------------- prefill floor arithmetic ----------------


V5E = "TPU v5 lite"
DEFAULT_MXU_TFLOPS = DEVICE_PEAKS[V5E]["mxu_tflops"]


def test_prefill_floor_hand_computed(monkeypatch):
    monkeypatch.delenv("DYNTPU_MXU_TFLOPS", raising=False)
    roof = RooflineModel(
        param_bytes=1_000_000, page_bytes=2048, page_size=16,
        hbm_bw=1e9, param_count=500_000, device_kind=V5E,
    )
    # bytes bound: params + ceil(48/16)=3 pages; FLOP bound: 2*N*rows/MXU
    rows = 48
    bytes_floor = (1_000_000 + 3 * 2048) / 1e9
    flop_floor = 2.0 * 500_000 * rows / (DEFAULT_MXU_TFLOPS * 1e12)
    assert roof.prefill_floor_bytes(rows) == 1_000_000 + 3 * 2048
    assert roof.prefill_floor_seconds(rows) == pytest.approx(
        max(bytes_floor, flop_floor)
    )
    # a big enough model goes FLOP-bound; the env knob moves the bound
    big = RooflineModel(
        param_bytes=10, page_bytes=1, page_size=16,
        hbm_bw=1e15, param_count=10**12, device_kind=V5E,
    )
    assert big.prefill_floor_seconds(512) == pytest.approx(
        2.0 * 10**12 * 512 / (DEFAULT_MXU_TFLOPS * 1e12)
    )
    monkeypatch.setenv("DYNTPU_MXU_TFLOPS", "100")
    big2 = RooflineModel(
        param_bytes=10, page_bytes=1, page_size=16,
        hbm_bw=1e15, param_count=10**12,
    )
    assert big2.prefill_floor_seconds(512) == pytest.approx(
        2.0 * 10**12 * 512 / 100e12
    )


def test_prefill_plane_accumulation_and_gauge():
    roof = RooflineModel(param_bytes=1000, page_bytes=10, page_size=4,
                         hbm_bw=1000.0, param_count=100, device_kind=V5E)
    a = StepAnatomy(roofline=roof)
    assert a.prefill_roofline_fraction() is None  # no priced prefill yet
    assert a.prefill_fixed_ms() is None
    assert "dynamo_engine_prefill_roofline_fraction" not in a.render_metrics()
    rec = a.begin("prefill_packed")
    a.add_phase(rec, "host_prep", 0.001)
    a.add_phase(rec, "dispatch", 0.009)
    a.note_steps(rec, tokens=8, participants=2)
    a.note_prefill_floor(rec, 8)
    # floor = (1000 + 2*10) / 1000 B/s = 1.02 s over 0.010 s measured
    assert rec.floor_s == pytest.approx(1.02)
    assert a.prefill_roofline_fraction() == pytest.approx(1.02 / 0.010)
    assert a.prefill_fixed_ms() == pytest.approx(10.0)
    snap = a.snapshot()
    assert snap["prefill_roofline_frac"] == pytest.approx(102.0)
    assert snap["prefill_fixed_ms"] == pytest.approx(10.0)
    assert snap["prefill_host_frac"] == 1.0
    # the prefill floor must NOT pollute the decode roofline fraction
    assert a.roofline_fraction() is None
    text = a.render_metrics()
    assert "dynamo_engine_prefill_roofline_fraction" in text
    # /debug/steps record carries the per-dispatch floor
    assert rec.to_dict()["floor_ms"] == pytest.approx(1020.0)


# ---------------- scheduler parity: pipelined vs reconcile-per-call ----------


def _cfg(depth, **over):
    base = dict(
        model_id="tiny", page_size=4, num_pages=256, max_seqs=8,
        max_model_len=96, prefill_buckets=(8, 16, 32), prefill_lanes=2,
        decode_steps=4, pipeline_depth=2, prefill_pipeline_depth=depth,
    )
    base.update(over)
    return EngineConfig(**base)


async def _serve_tokens(cfg, prompts, sampling_kw):
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    eng = AsyncJaxEngine(cfg)
    await eng.start()
    try:
        toks = {i: [] for i in range(len(prompts))}

        async def one(i):
            req = EngineRequest(
                request_id=f"p-{i}", token_ids=list(prompts[i]),
                sampling=SamplingParams(max_tokens=8, ignore_eos=True,
                                        **sampling_kw),
            )
            async for out in eng.generate(req):
                if out.token is not None:
                    toks[i].append(out.token)

        await asyncio.gather(*[one(i) for i in range(len(prompts))])
        stalls = eng.scheduler.stage.prefill_stalls
        calls = eng.scheduler.stage.prefill_calls
        return toks, stalls, calls
    finally:
        await eng.shutdown()


@pytest.mark.parametrize(
    "sampling_kw,over",
    [
        ({"temperature": 0.0}, {}),  # greedy
        ({"temperature": 0.8, "seed": 7}, {}),  # seeded stochastic
        ({"temperature": 0.0}, {"kv_cache_dtype": "int8"}),  # int8 KV
    ],
    ids=["greedy", "seeded", "int8_kv"],
)
def test_pipelined_token_parity(sampling_kw, over):
    """Dispatch-ahead is a scheduling change only: depth=2 must produce the
    exact token streams of the strict depth=1 baseline — greedy, seeded
    (per-request deterministic stream), and quantized-KV arms alike."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 200, 24).tolist() for _ in range(6)]

    async def both():
        t1, s1, c1 = await _serve_tokens(_cfg(1, **over), prompts, sampling_kw)
        t2, s2, c2 = await _serve_tokens(_cfg(2, **over), prompts, sampling_kw)
        return t1, s1, c1, t2, s2, c2

    t1, s1, c1, t2, s2, c2 = asyncio.run(both())
    for i in range(len(prompts)):
        assert t1[i], f"request {i} produced no tokens"
        assert t1[i] == t2[i], f"request {i}: {t1[i]} != {t2[i]}"
    # the burst packs multiple calls (2 lanes over 6 prompts), so the strict
    # arm must have paid forced stalls the pipelined arm avoids
    assert c1 >= 2 and c2 >= 2
    assert s1 > s2, f"depth=1 stalls {s1} not above depth=2 stalls {s2}"


def test_cancel_mid_pipeline():
    """Cancelling requests while packed prefills ride unreconciled must not
    wedge the gate or corrupt survivors: stale in-flight entries skip
    finished sequences, remaining requests complete, and the engine serves
    fresh traffic afterwards."""
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, 24).tolist() for _ in range(6)]

    async def run():
        eng = AsyncJaxEngine(_cfg(2))
        await eng.start()
        try:
            done = {}

            async def one(i):
                req = EngineRequest(
                    request_id=f"c-{i}", token_ids=list(prompts[i % len(prompts)]),
                    sampling=SamplingParams(temperature=0.0, max_tokens=8,
                                            ignore_eos=True),
                )
                toks = []
                async for out in eng.generate(req):
                    if out.token is not None:
                        toks.append(out.token)
                done[i] = toks

            tasks = [asyncio.create_task(one(i)) for i in range(6)]
            # let the burst enter the scheduler, then kill half the clients
            # while their prefills are (or were just) in flight
            await asyncio.sleep(0)
            for t in tasks[::2]:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            # survivors completed with output
            for i in (1, 3, 5):
                assert done.get(i), f"survivor {i} produced no tokens"
            # the engine still serves fresh traffic (slots/pages released)
            await one(99)
            assert done[99]
        finally:
            await eng.shutdown()

    asyncio.run(run())
