"""Step-anatomy profiler (utils/step_anatomy.py): ring bounds, phase
attribution, roofline arithmetic vs hand-computed bytes (bf16 + int8 KV),
the /debug/steps payload, dynotop STEP/ROOF columns, exposition conformance
of the dynamo_step_* families, and the live scheduler integration (anatomy
device-wait agreeing with StageStats.reconcile_wait_s on the same run)."""

import asyncio
from types import SimpleNamespace

import numpy as np
import pytest

from dynamo_tpu.quant.kv import kv_page_bytes
from dynamo_tpu.utils.prometheus import _sample_surfaces, check_exposition
from dynamo_tpu.utils.step_anatomy import (
    KINDS,
    RooflineModel,
    StepAnatomy,
    roofline_for_runner,
)


# ---------------- ring + phase attribution ----------------


def test_ring_bounds_and_eviction():
    a = StepAnatomy(ring_size=8)
    for _ in range(20):
        a.begin("decode_window")
    assert len(a.ring) == 8  # bounded: eviction, not growth
    # cumulative counters survive eviction
    assert a.dispatch_counts["decode_window"] == 20
    recs = a.records(limit=4)
    assert [r["seq"] for r in recs] == [17, 18, 19, 20]  # newest last
    assert len(a.records(limit=100)) == 8
    # kind filter
    a.record("offload_drain", dispatch_s=0.001)
    assert [r["kind"] for r in a.records(kind="offload_drain")] == ["offload_drain"]


def test_phase_attribution_and_host_fraction():
    a = StepAnatomy()
    assert a.host_fraction() is None  # no data: no fake 1.0/0.0
    rec = a.begin("decode_window")
    a.add_phase(rec, "host_prep", 0.001)
    a.add_phase(rec, "dispatch", 0.002)
    a.add_phase(rec, "device_wait", 0.007)
    assert rec.total_s == pytest.approx(0.010)
    assert rec.host_s == pytest.approx(0.003)
    assert a.host_fraction() == pytest.approx(0.3)
    # a later reconcile mutates the SAME record (pipelined attribution)
    a.add_phase(rec, "reconcile", 0.002)
    assert a.host_fraction() == pytest.approx(5.0 / 12.0)
    d = rec.to_dict()
    assert d["dispatch_ms"] == pytest.approx(2.0)
    assert d["device_wait_ms"] == pytest.approx(7.0)
    # None-safe: an untracked entry still lands in the totals
    a.add_phase(None, "device_wait", 0.01)
    assert a.phase_seconds[("device_wait", "decode_window")] == pytest.approx(0.017)


def test_every_issue_kind_is_in_vocabulary():
    for kind in ("decode_window", "prefill_packed", "prefill_chunk",
                 "spec_draft", "spec_verify", "lora_slot_load",
                 "prefix_fetch_scatter", "offload_drain"):
        assert kind in KINDS


# ---------------- roofline arithmetic ----------------

_GEO = dict(page_size=16, num_kv_heads=8, head_dim=128, num_layers=24)


def test_roofline_bytes_bf16_hand_computed():
    # one page: K and V, all layers, page_size rows of Hkv*D bf16 values
    page = kv_page_bytes(_GEO["page_size"], _GEO["num_kv_heads"],
                         _GEO["head_dim"], _GEO["num_layers"], None, itemsize=2)
    assert page == 2 * 24 * 16 * (8 * 128 * 2)
    roof = RooflineModel(param_bytes=2_600_000_000, page_bytes=page,
                         page_size=16, hbm_bw=819e9)
    live = 64 * 28
    assert roof.step_floor_bytes(live) == 2_600_000_000 + live * page
    assert roof.step_floor_seconds(live) == pytest.approx(
        (2_600_000_000 + live * page) / 819e9
    )


def test_roofline_bytes_int8_hand_computed():
    page8 = kv_page_bytes(_GEO["page_size"], _GEO["num_kv_heads"],
                          _GEO["head_dim"], _GEO["num_layers"], "int8")
    # int8 rows: Hkv*D one-byte values + one f32 scale per row
    assert page8 == 2 * 24 * 16 * (8 * 128 * 1 + 4)
    page16 = kv_page_bytes(_GEO["page_size"], _GEO["num_kv_heads"],
                           _GEO["head_dim"], _GEO["num_layers"], None, itemsize=2)
    # the int8 floor is genuinely lower at the same occupancy (the estimator
    # must track the cache dtype, not assume bf16)
    live = 512
    f8 = RooflineModel(1_000, page8, 16, hbm_bw=1e9).step_floor_bytes(live)
    f16 = RooflineModel(1_000, page16, 16, hbm_bw=1e9).step_floor_bytes(live)
    assert f8 < f16
    assert f16 - f8 == live * (page16 - page8)


def test_roofline_for_runner_reads_actual_leaves():
    model = SimpleNamespace(
        config=None, kv_page_bytes=lambda ps: 4096 if ps == 4 else 0
    )
    runner = SimpleNamespace(
        model=model,
        params={"w": np.zeros((8, 4), np.float32), "b": np.zeros(4, np.int8)},
    )
    roof = roofline_for_runner(runner, SimpleNamespace(page_size=4))
    assert roof is not None
    assert roof.param_bytes == 8 * 4 * 4 + 4  # f32 + int8 leaves, as stored
    assert roof.page_bytes == 4096
    # runners that can't price pages degrade to None, never raise
    assert roofline_for_runner(SimpleNamespace(model=None, params=None),
                               SimpleNamespace(page_size=4)) is None


def test_roofline_fraction_and_dispatch_gap():
    roof = RooflineModel(param_bytes=1000, page_bytes=10, page_size=4,
                         hbm_bw=1000.0)
    a = StepAnatomy(roofline=roof)
    assert a.roofline_fraction() is None  # no priced dispatch yet
    rec = a.begin("decode_window", ts=1.0)
    a.add_phase(rec, "dispatch", 1.0)
    a.note_steps(rec, steps=2, floor_bytes=a.decode_floor_bytes(5, 2))
    # floor = (1000 + 5*10) * 2 steps = 2100 bytes / 1000 B/s = 2.1 s over
    # 1.0 s measured
    assert a.roofline_fraction() == pytest.approx(2.1)
    rec2 = a.begin("decode_window", ts=1.5)
    a.add_phase(rec2, "dispatch", 0.5)
    assert a.dispatch_gap_ms("decode_window") == pytest.approx(500.0)
    # other kinds don't pollute the decode cadence
    a.record("prefill_packed", dispatch_s=0.1, ts=1.25)
    assert a.dispatch_gap_ms("decode_window") == pytest.approx(500.0)
    assert a.dispatch_gap_ms("offload_drain") is None


@pytest.mark.parametrize("kind", ["cpu", "TPU v99 imaginary"])
def test_unknown_device_kind_yields_no_roofline_fraction(kind, monkeypatch):
    """Peaks come from one table keyed by device_kind; a device that is not
    in it gets no fraction (it used to get the v5e's), while the bytes stay
    exact. The env overrides still supply a value for any device."""
    from dynamo_tpu.utils.step_anatomy import DEVICE_PEAKS, device_peaks

    monkeypatch.delenv("DYNTPU_HBM_GBPS", raising=False)
    monkeypatch.delenv("DYNTPU_MXU_TFLOPS", raising=False)
    assert kind not in DEVICE_PEAKS
    assert device_peaks(kind) == (None, None)
    assert device_peaks("TPU v5 lite") == (819e9, 197e12)
    assert "Google Cloud" in DEVICE_PEAKS["TPU v5 lite"]["source"]

    roof = RooflineModel(param_bytes=1000, page_bytes=10, page_size=4,
                         param_count=100, device_kind=kind)
    assert roof.step_floor_bytes(5) == 1050
    assert roof.step_floor_seconds(5) is None
    assert roof.prefill_floor_seconds(64) is None
    a = StepAnatomy(roofline=roof)
    rec = a.begin("decode_window", ts=1.0)
    a.add_phase(rec, "dispatch", 1.0)
    a.note_steps(rec, steps=2, floor_bytes=a.decode_floor_bytes(5, 2))
    prec = a.begin("prefill_packed", ts=2.0)
    a.add_phase(prec, "dispatch", 1.0)
    a.note_prefill_floor(prec, 64)
    snap = a.snapshot()
    assert snap["floor_bytes_total"] == 2100
    assert snap["roofline_frac"] is None and snap["prefill_roofline_frac"] is None
    assert snap["roofline"]["device_kind"] == kind
    assert "roofline_fraction" not in a.render_metrics()

    monkeypatch.setenv("DYNTPU_HBM_GBPS", "1")
    assert device_peaks(kind) == (1e9, None)
    roof2 = RooflineModel(param_bytes=1000, page_bytes=10, page_size=4, device_kind=kind)
    assert roof2.step_floor_seconds(5) == pytest.approx(1050 / 1e9)


def test_decode_floor_without_roofline_is_zero():
    a = StepAnatomy()
    assert a.decode_floor_bytes(100, 4) == 0
    assert a.roofline_fraction() is None


# ---------------- exposition conformance ----------------


def test_render_metrics_conformant_and_families_present():
    a = StepAnatomy(roofline=RooflineModel(1000, 10, 4, hbm_bw=1e9))
    rec = a.begin("decode_window")
    a.add_phase(rec, "dispatch", 0.002)
    a.add_phase(rec, "device_wait", 0.005)
    a.note_steps(rec, steps=4, floor_bytes=a.decode_floor_bytes(8, 4))
    a.record("lora_slot_load", dispatch_s=0.003)
    text = a.render_metrics()
    assert check_exposition(text) == []
    assert 'dynamo_step_seconds_total{kind="decode_window",phase="dispatch"}' in text
    assert 'dynamo_step_seconds_total{kind="decode_window",phase="device_wait"}' in text
    assert 'dynamo_step_dispatch_total{kind="lora_slot_load"} 1' in text
    assert "# TYPE dynamo_engine_roofline_fraction gauge" in text
    assert "# TYPE dynamo_step_host_fraction gauge" in text
    # empty tracker still renders conformant zero-sample families
    empty = StepAnatomy().render_metrics()
    assert check_exposition(empty) == []
    assert "dynamo_step_seconds_total" in empty
    # ...but never a fake roofline gauge
    assert "dynamo_engine_roofline_fraction" not in empty


def test_step_families_on_sample_surface():
    """The lint-gate surface list must carry the new families (acceptance:
    dynamo_step_* + dynamo_engine_roofline_fraction pass conformance via
    python -m dynamo_tpu.utils.prometheus --check)."""
    text = dict(_sample_surfaces())["engine.render_stage_metrics"]
    assert check_exposition(text) == []
    assert "# TYPE dynamo_step_seconds_total counter" in text
    assert "# TYPE dynamo_step_dispatch_total counter" in text
    assert "# TYPE dynamo_engine_roofline_fraction gauge" in text
    assert 'dynamo_step_dispatch_total{kind="lora_slot_load"}' in text


# ---------------- /debug/steps payload ----------------


def _bare_engine():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.page_table import PageAllocator
    from dynamo_tpu.engine.scheduler import Scheduler

    cfg = EngineConfig(model_id="tiny", page_size=4, num_pages=8, max_seqs=2,
                       prefill_buckets=(16,))
    eng = AsyncJaxEngine(cfg)
    eng.allocator = PageAllocator(cfg.num_pages, cfg.page_size)
    eng.scheduler = Scheduler(cfg, None, eng.allocator)
    return eng


def test_debug_steps_payload_shape():
    eng = _bare_engine()
    # pre-data: well-formed and empty
    empty = eng.debug_steps()
    assert empty["records"] == [] and "summary" in empty
    a = eng.scheduler.anatomy
    for i in range(5):
        rec = a.begin("decode_window")
        a.add_phase(rec, "dispatch", 0.001 * (i + 1))
        a.note_steps(rec, steps=4, tokens=8, participants=2)
    a.record("prefill_packed", dispatch_s=0.004)
    doc = eng.debug_steps(limit=3)
    assert len(doc["records"]) == 3
    for r in doc["records"]:
        assert set(r) == {
            "seq", "ts", "kind", "host_prep_ms", "dispatch_ms",
            "device_wait_ms", "reconcile_ms", "steps", "tokens",
            "participants", "floor_bytes", "floor_ms",
        }
    # kind filter reaches through
    only = eng.debug_steps(kind="prefill_packed")
    assert {r["kind"] for r in only["records"]} == {"prefill_packed"}
    summary = doc["summary"]
    assert summary["dispatches"]["decode_window"] == 5
    assert summary["host_frac"] == 1.0  # no device_wait recorded
    # JSON-serializable end to end (the endpoint json_response contract)
    import json

    json.dumps(doc)


def test_debug_steps_http_endpoint():
    """The /debug/steps route serves the engine payload (and an empty shell
    when no engine is attached)."""
    from aiohttp.test_utils import TestClient, TestServer

    from dynamo_tpu.llm.http.service import HttpService

    eng = _bare_engine()
    a = eng.scheduler.anatomy
    rec = a.begin("decode_window")
    a.add_phase(rec, "dispatch", 0.002)

    async def run():
        svc = HttpService(step_source=eng.debug_steps)
        client = TestClient(TestServer(svc.app))
        await client.start_server()
        try:
            r = await client.get("/debug/steps?limit=10")
            assert r.status == 200
            doc = await r.json()
            assert doc["records"][-1]["kind"] == "decode_window"
            assert doc["summary"]["dispatches"]["decode_window"] == 1
            r2 = await client.get("/debug/steps?kind=prefill_packed")
            assert (await r2.json())["records"] == []
        finally:
            await client.close()

        bare = HttpService()
        client = TestClient(TestServer(bare.app))
        await client.start_server()
        try:
            r = await client.get("/debug/steps")
            assert await r.json() == {"records": [], "summary": {}}
        finally:
            await client.close()

    asyncio.run(run())


def test_resource_snapshot_carries_step_anatomy():
    eng = _bare_engine()
    a = eng.scheduler.anatomy
    rec = a.begin("decode_window")
    a.add_phase(rec, "dispatch", 0.002)
    snap = eng.resource_snapshot()
    assert "step_anatomy" in snap
    assert snap["step_anatomy"]["dispatches"]["decode_window"] == 1
    assert "host_frac" in snap["step_anatomy"]


# ---------------- dynotop columns ----------------


def _load_dynotop():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "dynotop_sa", Path(__file__).resolve().parent.parent / "tools" / "dynotop.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dynotop_step_roof_columns():
    dynotop = _load_dynotop()
    doc = {
        "summary": {"workers": 1, "servable": 1, "stale": 0, "unservable": 0},
        "workers": [{
            "worker_id": "ab", "health": {"state": "ready", "heartbeat_age_s": 0.1},
            "kv_metrics": {"request_active_slots": 1, "request_total_slots": 8,
                           "kv_active_blocks": 2, "kv_total_blocks": 10,
                           "num_requests_waiting": 0},
            "resources": {"step_anatomy": {
                "host_frac": 0.312, "roofline_frac": 0.698,
                "dispatch_gap_ms_p50": 2.484,
                "prefill_host_frac": 0.974, "prefill_fixed_ms": 10.23,
                "prefill_roofline_frac": 0.63,
            }},
            "last_seen_s": 0.2, "missed_scrapes": 0,
        }],
    }
    text = dynotop.render_status(doc)
    assert "STEP" in text and "ROOF" in text and "PREFILL" in text
    assert "h31% 2.5ms" in text
    assert "70%" in text
    assert "h97% 10.2ms 63%" in text
    # workers predating the plane render "-" without crashing
    doc["workers"][0]["resources"] = {}
    text = dynotop.render_status(doc)
    assert "h31%" not in text and "70%" not in text
    assert "h97%" not in text


# ---------------- scheduler integration (tiny engine e2e) ----------------


@pytest.fixture(scope="module")
def served_engine():
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    cfg = EngineConfig(
        model_id="tiny", page_size=4, num_pages=256, max_seqs=4,
        max_model_len=160, prefill_buckets=(16, 32, 64), decode_steps=4,
        pipeline_depth=2,
    )
    eng = AsyncJaxEngine(cfg)
    loop = asyncio.new_event_loop()
    loop.run_until_complete(eng.start())
    yield eng, loop
    loop.run_until_complete(eng.shutdown())
    loop.close()


def test_live_engine_records_step_anatomy(served_engine):
    """Serving traffic populates the ring: decode windows + prefill kinds,
    priced floors, and device_wait agreeing with StageStats'
    reconcile_wait_s (the acceptance criterion's consistency check — both
    numbers come from the same measurement site)."""
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    eng, loop = served_engine
    rng = np.random.default_rng(0)

    async def one(i):
        req = EngineRequest(
            request_id=f"sa-{i}", token_ids=rng.integers(1, 200, 24).tolist(),
            sampling=SamplingParams(temperature=0.0, max_tokens=12,
                                    ignore_eos=True),
        )
        async for _ in eng.generate(req):
            pass

    async def run_all():
        await asyncio.gather(*[one(i) for i in range(4)])

    loop.run_until_complete(run_all())
    anatomy = eng.scheduler.anatomy
    snap = anatomy.snapshot()
    assert snap["dispatches"].get("decode_window", 0) >= 2
    assert snap["dispatches"].get("prefill_packed", 0) \
        + snap["dispatches"].get("prefill_chunk", 0) >= 1
    assert snap["steps"]["decode_window"] >= 4 * 12 // eng.config.decode_steps
    # the roofline estimator priced real floors off the tiny model's actual
    # geometry (param bytes > 0, page bytes from model.kv_page_bytes)
    assert anatomy.roofline is not None and anatomy.roofline.param_bytes > 0
    assert snap["floor_bytes_total"] > 0
    assert snap["host_frac"] is not None
    # consistency: anatomy's non-spec device_wait IS reconcile_wait_s (same
    # dt feeds both counters)
    wait = sum(v for k, v in snap["phase_seconds"].items()
               if k.startswith("device_wait."))
    assert wait == pytest.approx(eng.scheduler.stage.reconcile_wait_s, abs=1e-6)
    # /debug/steps sees the same traffic
    doc = eng.debug_steps(limit=256)
    assert any(r["kind"] == "decode_window" and r["tokens"] > 0
               for r in doc["records"])
    # and the engine exposition carries the families conformantly
    text = eng.render_stage_metrics()
    assert check_exposition(text) == []
    assert "dynamo_step_seconds_total" in text


# ---------------- one timed block per boundary; the first token's chain ----------------


def _serve(eng, loop, requests):
    """Run EngineRequests to their end, all at once."""
    async def one(req):
        async for _ in eng.generate(req):
            pass

    async def run_all():
        await asyncio.gather(*[one(r) for r in requests])

    loop.run_until_complete(run_all())


def _request(rid, n_prompt, max_tokens, seed=0):
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    rng = np.random.default_rng(seed)
    return EngineRequest(
        request_id=rid, token_ids=rng.integers(1, 200, n_prompt).tolist(),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True),
    )


def _chain_closes(sched, spans_by_request):
    """queue_wait + prefill_hold + first_token_wait == ttft: over the
    histograms' sums, and request by request (admission by admission) on the
    recorder's spans, whose durations are whole microseconds."""
    h = sched.stage_hist
    n = h["ttft"].count
    assert n and h["queue_wait"].count == h["prefill_hold"].count == h["first_token_wait"].count == n
    parts = h["queue_wait"].sum + h["prefill_hold"].sum + h["first_token_wait"].sum
    assert parts == pytest.approx(h["ttft"].sum, abs=1e-6 * n)
    for rid, spans in spans_by_request.items():
        chain = {k: [e["dur"] for e in spans if e["name"] == f"engine.{k}"]
                 for k in ("queue_wait", "prefill_hold", "first_token_wait", "ttft")}
        assert len({len(v) for v in chain.values()}) == 1 and chain["ttft"], (rid, chain)
        for qw, hold, wait, ttft in zip(*chain.values()):
            assert abs(qw + hold + wait - ttft) <= 3, (rid, chain)
            assert hold >= 0 and wait > 0


@pytest.mark.parametrize("case", ["chunked_and_packed", "preempted"])
def test_first_token_chain_adds_up_to_ttft(case, served_engine):
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.utils import tracing

    from tests.test_engine import tiny_engine_config

    tracing.clear()
    tracing.enable()
    try:
        if case == "chunked_and_packed":
            eng, loop = served_engine
            before = eng.scheduler.stage_hist["ttft"].count
            # 150 tokens take three chunks of the widest bucket (64); the
            # three short prompts arrive together and share packed calls
            reqs = [_request("chain-long", 150, 6, seed=1)] + [
                _request(f"chain-{i}", 20 + i, 6, seed=2 + i) for i in range(3)]
            _serve(eng, loop, reqs)
            assert eng.scheduler.stage_hist["ttft"].count == before + 4
            assert eng.scheduler.anatomy.dispatch_counts.get("prefill_packed", 0) \
                + eng.scheduler.anatomy.dispatch_counts.get("prefill_chunk", 0) >= 3
        else:
            # 8 usable pages, two sequences that grow to 6 pages each: the
            # younger is preempted and admitted a second time
            loop = asyncio.new_event_loop()
            eng = AsyncJaxEngine(tiny_engine_config(
                num_pages=9, max_seqs=2, max_model_len=32, watermark=0.0))
            loop.run_until_complete(eng.start())
            try:
                reqs = [_request(f"chain-p{i}", 8, 16, seed=10 + i) for i in range(2)]
                for r in reqs:
                    r.sampling.ignore_eos = False
                _serve(eng, loop, reqs)
                assert eng.scheduler.preempt_count >= 1
                # the preempted request observed a first token per admission
                assert eng.scheduler.stage_hist["ttft"].count == 2 + eng.scheduler.preempt_count
            finally:
                loop.run_until_complete(eng.shutdown())
                loop.close()
        spans = {r.request_id: tracing.events(request_id=r.request_id) for r in reqs}
        _chain_closes(eng.scheduler, spans)
    finally:
        tracing.disable()
        tracing.clear()


def test_recorder_events_of_one_request_name_their_parent(served_engine):
    """The Chrome events a request had before this plane's phases were folded
    into one block are still there under their names, and each now says which
    span it was recorded in."""
    from dynamo_tpu.utils import tracing

    eng, loop = served_engine
    tracing.clear()
    tracing.enable()
    try:
        _serve(eng, loop, [_request("parent-1", 24, 10, seed=7)])
        evs = tracing.events(request_id="parent-1")
    finally:
        tracing.disable()
        tracing.clear()
    by_name = {e["name"]: e for e in evs}
    assert {"engine.queue_wait", "engine.prefill", "engine.decode.window", "engine.ttft",
            "engine.prefill_hold", "engine.first_token_wait"} <= set(by_name)
    assert all("parent" in e["args"] for e in evs)
    for name in ("engine.queue_wait", "engine.prefill", "engine.decode.window"):
        assert by_name[name]["args"]["parent"] == "engine.step", by_name[name]
    # the first token is materialized in the reconcile phase of its prefill
    assert by_name["engine.ttft"]["args"]["parent"].endswith(".reconcile")
    assert by_name["engine.decode.window"]["args"]["k"] == eng.config.decode_steps
    assert by_name["engine.prefill"]["args"]["rows"] == 24


def test_phases_stand_on_the_profilers_host_plane(served_engine, tmp_path):
    """Under a real `jax.profiler` session (the options the benchmark's entry
    uses) the engine thread's line of the host plane holds the dispatch and
    device_wait phases with the `seq` of their `/debug/steps` records, inside
    `engine.step` spans; `benchmark/trace_steps.load` is what reads them."""
    import importlib.util
    from pathlib import Path

    import jax

    spec = importlib.util.spec_from_file_location(
        "trace_steps", Path(__file__).resolve().parents[1] / "benchmark" / "trace_steps.py")
    trace_steps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_steps)

    eng, loop = served_engine
    stage = eng.scheduler.stage
    windows0, waits0 = stage.decode_windows, stage.reconcile_waits
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(eng, loop, [_request(f"prof-{i}", 24, 12, seed=20 + i) for i in range(2)])
    finally:
        jax.profiler.stop_trace()
    (trace,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = trace_steps.load(str(trace))["host"]
    steps = [(s, s + d) for name, s, d, _ in host if name == "engine.step"]
    dispatch = [(s, s + d, st) for name, s, d, st in host if name == "engine.decode_window.dispatch"]
    waits = [(s, s + d, st) for name, s, d, st in host if name.endswith(".device_wait")]
    assert len(dispatch) == stage.decode_windows - windows0 > 0
    assert len(waits) == stage.reconcile_waits - waits0
    recorded = {r["seq"] for r in eng.debug_steps(limit=512)["records"] if r["kind"] == "decode_window"}
    assert {st["seq"] for _, _, st in dispatch} <= recorded
    assert all(st["k"] == eng.config.decode_steps for _, _, st in dispatch)
    for s, e, st in dispatch + waits:
        assert st["seq"] > 0 and any(a <= s and e <= b for a, b in steps), (s, e, st)
    names = {name for name, *_ in host}
    assert {"engine.post", "engine.decode_window.reconcile"} <= names
    # the reduction's view of the engine thread: the phases lie inside the steps
    thread = trace_steps.reduce({"modules": {}, "ops": {}, "host": host})["thread"]
    assert 0 < thread["phases_s"] <= thread["step_s"] <= thread["seconds"]
