"""End-to-end request tracing + per-stage latency attribution.

Covers the observability plane: the span recorder (utils/tracing.py), trace-id
propagation across runtime hops via the RequestContext metadata bag, the
serving-stack Prometheus histograms (TTFT / inter-token latency / queue wait),
promtool-style exposition conformance of every /metrics producer, the /trace
debug endpoint, request-id stamping in log records, and the stitched two-hop
disagg trace (decode worker + prefill worker sharing one trace id).
"""

import asyncio
import json
import logging

import pytest

from dynamo_tpu.runtime.context import RequestContext, new_context, use_context
from dynamo_tpu.utils import tracing
from dynamo_tpu.utils.prometheus import (
    Histogram,
    check_exposition,
    fmt_value,
    render_family,
)


@pytest.fixture(autouse=True)
def clean_tracer():
    """Each test sees an empty ring and leaves the recorder disabled."""
    tracing.clear()
    yield
    tracing.disable()
    tracing.clear()


# ---------------- span recorder ----------------


def test_recorder_disabled_is_noop():
    assert not tracing.enabled()
    with tracing.span("x"):
        pass
    tracing.record_span("y", 0.0, duration=1.0)
    assert tracing.events() == []


def test_span_records_chrome_events(tmp_path):
    path = tmp_path / "trace.jsonl"
    tracing.enable(str(path))
    with tracing.span("stage.a", foo=7):
        pass
    tracing.record_span("stage.b", 1.0, duration=0.5, request_id="r1", trace_id="t1")
    evs = tracing.events()
    assert [e["name"] for e in evs] == ["stage.a", "stage.b"]
    a, b = evs
    assert a["ph"] == "X" and a["cat"] == "dyntpu"
    assert a["args"]["foo"] == 7
    assert isinstance(a["ts"], int) and isinstance(a["dur"], int)
    assert b["dur"] == 500_000  # µs
    assert b["args"]["trace_id"] == "t1" and b["args"]["request_id"] == "r1"
    # filtering
    assert [e["name"] for e in tracing.events(trace_id="t1")] == ["stage.b"]
    assert tracing.events(request_id="nope") == []
    # the JSONL file carries the same events, one parseable object per line
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [e["name"] for e in lines] == ["stage.a", "stage.b"]
    # the export document is Perfetto-shaped
    doc = tracing.export(trace_id="t1")
    assert [e["name"] for e in doc["traceEvents"]] == ["stage.b"]


def test_span_names_its_parent_and_records_under_its_alias():
    tracing.enable()
    with tracing.span("outer", a=1):
        with tracing.span("engine.decode_window.dispatch", alias="engine.decode.window", k=8) as inner:
            tracing.record_span("point", inner.t0, duration=0.0)
    names = [(e["name"], e["args"]["parent"]) for e in tracing.events()]
    # the alias is the recorder's name, for the event and for its children
    assert names == [("point", "engine.decode.window"), ("engine.decode.window", "outer"),
                     ("outer", None)]
    assert inner.dt == inner.t1 - inner.t0 >= 0
    # a span on another thread has no parent here
    import threading

    def other():
        with tracing.span("elsewhere"):
            pass

    with tracing.span("outer2"):
        t = threading.Thread(target=other)
        t.start()
        t.join(5)
    assert {e["name"]: e["args"]["parent"] for e in tracing.events()}["elsewhere"] is None


def test_phase_block_is_one_clock_pair_and_silent_when_off():
    """No profiler session, DYNTPU_TRACE unset: a `phase()` block appends
    nothing to the ring, and what it adds to the record and hands to the
    stage sink is the one interval that `add_phase` would have been given."""
    import time

    from dynamo_tpu.utils.step_anatomy import StepAnatomy

    assert not tracing.enabled()
    fed = []
    a = StepAnatomy()
    a.stage_sink = lambda kind, phase, dt: fed.append((kind, phase, dt))
    rec = a.begin("decode_window")
    with a.phase(rec, "dispatch", k=4, requests=["not", "a", "scalar"]) as ph:
        time.sleep(0.002)
    with a.phase(None, "device_wait"):  # an untracked entry: charged as add_phase charges it
        pass
    assert tracing.events() == []
    assert ph.dt >= 0.002 and rec.dispatch_s == ph.dt
    assert fed[0] == ("decode_window", "dispatch", ph.dt) and fed[1][:2] == ("decode_window", "device_wait")
    b = StepAnatomy()
    rec_b = b.begin("decode_window")
    b.add_phase(rec_b, "dispatch", ph.dt)
    b.add_phase(None, "device_wait", fed[1][2])
    assert a.phase_seconds == b.phase_seconds and rec_b.dispatch_s == rec.dispatch_s
    # on: the recorder gets the phase under the name it always had
    tracing.enable()
    with a.phase(rec, "dispatch", request_id="r1", k=4):
        pass
    (ev,) = tracing.events()
    assert ev["name"] == "engine.decode.window" and ev["args"]["seq"] == rec.seq
    assert ev["args"]["request_id"] == "r1" and ev["args"]["k"] == 4


def test_span_ids_default_to_ambient_context():
    tracing.enable()
    ctx = new_context(request_id="req-9", metadata={"trace_id": "trace-9"})
    with use_context(ctx):
        with tracing.span("inside"):
            pass
    with tracing.span("outside"):
        pass
    inside, outside = tracing.events()
    assert inside["args"]["request_id"] == "req-9"
    assert inside["args"]["trace_id"] == "trace-9"
    assert outside["args"]["request_id"] is None


def test_context_trace_id_helpers():
    ctx = new_context(request_id="rid")
    assert ctx.trace_id == "rid"  # falls back to the request id
    assert ctx.ensure_trace_id() == "rid"
    assert ctx.metadata["trace_id"] == "rid"
    ctx2 = RequestContext.from_wire(ctx.to_wire())
    assert ctx2.trace_id == "rid"  # survives the wire round trip
    ctx3 = new_context(metadata={"trace_id": "edge"})
    assert ctx3.trace_id == "edge"
    ctx3.ensure_trace_id()
    assert ctx3.metadata["trace_id"] == "edge"  # idempotent, edge stamp wins


# ---------------- prometheus helpers ----------------


def test_fmt_value_canonical():
    assert fmt_value(0.005) == "0.005"
    assert fmt_value(1.0) == "1"
    assert fmt_value(60) == "60"
    assert fmt_value(float("inf")) == "+Inf"
    # a computed bucket bound must not render as repr() noise
    assert fmt_value(0.1 + 0.2) == "0.3"


def test_histogram_render_conformant():
    h = Histogram("t_seconds", "a test histogram", (0.1, 1.0), ("model",))
    h.observe(0.05, ("m1",))
    h.observe(0.5, ("m1",))
    h.observe(5.0, ("m2",))
    text = h.render()
    assert check_exposition(text) == []
    assert 't_seconds_bucket{le="0.1",model="m1"} 1' in text
    assert 't_seconds_bucket{le="+Inf",model="m1"} 2' in text
    assert 't_seconds_count{model="m2"} 1' in text
    assert h.count == 3


def test_check_exposition_catches_violations():
    # sample with no HELP/TYPE
    assert check_exposition("foo 1\n")
    # duplicate TYPE
    bad = "# HELP f h\n# TYPE f gauge\n# TYPE f gauge\nf 1\n"
    assert any("duplicate TYPE" in p for p in check_exposition(bad))
    # unparseable le
    bad = (
        "# HELP h x\n# TYPE h histogram\n"
        'h_bucket{le="abc"} 1\nh_sum 1\nh_count 1\n'
    )
    assert any("le" in p for p in check_exposition(bad))
    # conformant family passes
    good = render_family("g_total", "counter", "help", [({"a": "b"}, 2)])
    assert check_exposition(good) == []


def test_http_metrics_render_conformant():
    from dynamo_tpu.llm.http.metrics import Metrics

    m = Metrics()
    m.inc_request("m", "chat_completions", "stream", "200")
    m.inflight("m", 1)
    m.observe_duration("m", "chat_completions", 0.25)
    m.observe_ttft("m", 0.03)
    m.observe_itl("m", 0.004)
    text = m.render()
    assert check_exposition(text) == []
    assert "llm_http_service_time_to_first_token_seconds_bucket" in text
    assert "llm_http_service_inter_token_latency_seconds_count" in text
    # le labels are canonical floats, not repr() output
    assert 'le="0.005"' in text


def test_metrics_component_render_conformant():
    """Satellite: components/metrics.py must emit one HELP/TYPE pair per
    family (the old render had a single free-text comment for everything)."""
    import time

    from dynamo_tpu.components.metrics import MetricsService
    from dynamo_tpu.llm.kv_router.metrics_aggregator import WorkerView
    from dynamo_tpu.llm.kv_router.scheduler import WorkerLoad

    class _Drt:
        cplane = None

    svc = MetricsService(_Drt(), "ns", "backend")
    kv = {
        "request_active_slots": 1, "request_total_slots": 8,
        "kv_active_blocks": 5, "kv_total_blocks": 100,
        "num_requests_waiting": 0, "gpu_cache_usage_perc": 0.05,
        "gpu_prefix_cache_hit_rate": 0.5,
    }
    stage = {
        "queue_wait_s": 0.5, "prefill_s": 1.25, "decode_dispatch_s": 3.0,
        "reconcile_wait_s": 0.1, "queue_wait_n": 4,
    }
    svc.aggregator._workers[0xAB] = WorkerView(
        0xAB,
        data={"kv_metrics": kv, "stage_seconds": stage},
        load=WorkerLoad.from_wire(0xAB, kv),
        last_seen=time.monotonic(),
    )
    svc._isl_blocks, svc._overlap_blocks = 10, 4
    text = svc.render()
    assert check_exposition(text) == [], check_exposition(text)
    # every family got its own HELP/TYPE
    assert text.count("# TYPE llm_kv_kv_active_blocks ") == 1
    assert "# TYPE llm_kv_kv_active_blocks_avg gauge" in text
    assert "llm_kv_hit_rate_percent" in text and "40.0" in text
    # per-stage engine seconds aggregated from worker stats
    assert 'llm_engine_stage_seconds_total{' in text
    assert 'stage="prefill"' in text and 'worker_id="ab"' in text
    # counts (_n fields) don't leak into the seconds family
    assert 'stage="queue_wait_n"' not in text


# ---------------- logging ----------------


def test_log_records_stamp_request_id():
    from dynamo_tpu.utils.logging import JsonlFormatter, PlainFormatter

    rec = logging.LogRecord("dynamo_tpu.t", logging.INFO, __file__, 1, "hello", (), None)
    ctx = new_context(request_id="log-rid", metadata={"trace_id": "log-tid"})
    with use_context(ctx):
        entry = json.loads(JsonlFormatter().format(rec))
        plain = PlainFormatter("%(message)s").format(rec)
    assert entry["request_id"] == "log-rid"
    assert entry["trace_id"] == "log-tid"
    assert "[rid=log-rid]" in plain
    # outside a request: no stamping
    entry = json.loads(JsonlFormatter().format(rec))
    assert "request_id" not in entry
    assert PlainFormatter("%(message)s").format(rec) == "hello"


# ---------------- cross-hop propagation (runtime, no JAX) ----------------


def test_trace_id_propagates_across_runtime_hop():
    """The edge-stamped trace id rides the RPC envelope: the server-side
    handler's spans (recorded inside the replayed context) land on the same
    trace as the caller's."""
    from dynamo_tpu.cplane.broker import Broker
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    tracing.enable()

    async def body():
        broker = Broker()
        port = await broker.start()
        addr = f"127.0.0.1:{port}"
        server_rt = DistributedRuntime(cplane_address=addr)
        await server_rt.connect()
        client_rt = DistributedRuntime(cplane_address=addr)
        await client_rt.connect()

        async def handler(req):
            with tracing.span("server.work"):
                yield {"ok": True}

        ep = server_rt.namespace("tr").component("c").endpoint("e")
        served = await ep.serve_endpoint(handler)
        client = await client_rt.client("tr", "c", "e")
        await client.wait_for_instances(timeout=10)
        try:
            ctx = new_context(request_id="hop-1", metadata={"trace_id": "trace-hop"})
            with use_context(ctx):
                stream = await client.random({"x": 1})
                items = [item async for item in stream]
            assert items == [{"ok": True}]
        finally:
            await served.stop()
            await client.stop()
            await client_rt._shutdown_hook()
            await server_rt._shutdown_hook()
            await broker.stop()

    asyncio.run(body())
    evs = tracing.events(trace_id="trace-hop")
    names = {e["name"] for e in evs}
    # caller-side hop span + server-side handler spans, one trace id
    assert "rpc.push.c.e" in names
    assert "rpc.handle.e" in names
    assert "server.work" in names
    assert all(e["args"]["request_id"] == "hop-1" for e in evs)


# ---------------- HTTP service (echo backend, no JAX) ----------------


def test_http_service_ttft_metrics_and_trace_endpoint():
    import aiohttp

    from dynamo_tpu.frontends.pipeline import build_pipeline, card_for_model
    from dynamo_tpu.llm.echo import EchoEngine
    from dynamo_tpu.llm.http.service import HttpService

    tracing.enable()

    async def body():
        service = HttpService(host="127.0.0.1", port=0)
        card = card_for_model("tiny")
        card.display_name = "echo"
        service.manager.add(build_pipeline(EchoEngine(), card))
        port = await service.start()
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                body = {
                    "model": "echo",
                    "messages": [{"role": "user", "content": "hello tracing"}],
                    "max_tokens": 8, "temperature": 0.0,
                    "ext": {"ignore_eos": True},
                }
                async with s.post(f"{base}/v1/chat/completions", json=body) as resp:
                    assert resp.status == 200
                    await resp.json()
                async with s.get(f"{base}/metrics") as resp:
                    metrics_text = await resp.text()
                async with s.get(f"{base}/trace") as resp:
                    trace_doc = await resp.json()
        finally:
            await service.stop()
        return metrics_text, trace_doc

    metrics_text, trace_doc = asyncio.run(body())
    assert check_exposition(metrics_text) == [], check_exposition(metrics_text)
    # TTFT histogram is non-empty after one served request
    assert 'llm_http_service_time_to_first_token_seconds_count{model="echo"} 1' in metrics_text
    # ... and runs from the request's arrival, as its help says: it holds the
    # preprocessing that ended before the stream began
    pre = next(e for e in trace_doc["traceEvents"] if e["name"] == "http.preprocess")
    ttft_sum = float(next(
        ln.split()[-1] for ln in metrics_text.splitlines()
        if ln.startswith('llm_http_service_time_to_first_token_seconds_sum{model="echo"}')))
    assert ttft_sum >= pre["dur"] / 1e6
    # /trace serves a Perfetto-loadable document with the request's spans
    names = {e["name"] for e in trace_doc["traceEvents"]}
    assert "http.request" in names and "http.preprocess" in names
    tids = {e["args"]["trace_id"] for e in trace_doc["traceEvents"]}
    assert len(tids) == 1  # one request, one stitched trace


# ---------------- two-hop disagg trace (JAX, full matrix tier) ----------------


@pytest.mark.slow
def test_disagg_two_hop_trace_and_stage_histograms():
    """Satellite: a single request through the disaggregated prefill->decode
    path yields spans from BOTH workers under one trace id, and the decode
    engine's TTFT/queue-wait histograms are non-empty afterwards."""
    from dynamo_tpu.cplane.broker import Broker
    from dynamo_tpu.disagg.decode_worker import DisaggDecodeEngine
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.llm.disagg_router import DisaggregatedRouter, DisaggRouterConf
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    from tests.test_disagg import LONG_PROMPT, collect, req_for
    from tests.test_engine import tiny_engine_config

    tracing.enable()

    async def body():
        broker = Broker()
        port = await broker.start()
        addr = f"127.0.0.1:{port}"
        decode_rt = DistributedRuntime(cplane_address=addr)
        await decode_rt.connect()
        prefill_rt = DistributedRuntime(cplane_address=addr)
        await prefill_rt.connect()
        decode_inner = AsyncJaxEngine(tiny_engine_config())
        await decode_inner.start()
        prefill_engine = AsyncJaxEngine(tiny_engine_config())
        await prefill_engine.start()
        router = DisaggregatedRouter(
            "tiny", conf=DisaggRouterConf(max_local_prefill_length=6)
        )
        decode = DisaggDecodeEngine(
            decode_inner, decode_rt, "nst", "decoder", "tiny", disagg_router=router
        )
        await decode.start()
        prefill_worker = PrefillWorker(prefill_engine, prefill_rt, "nst", "tiny")
        await prefill_worker.start()
        try:
            # the edge stamp: what an HTTP frontend would put on the context
            ctx = new_context(request_id="d1", metadata={"trace_id": "trace-2hop"})
            with use_context(ctx):
                toks, _ = await collect(decode, req_for("d1", LONG_PROMPT))
            assert len(toks) == 6
            assert decode.remote_prefills == 1
            return decode_inner, prefill_engine
        finally:
            await prefill_worker.stop()
            await decode.shutdown()
            await prefill_engine.shutdown()
            await decode_rt._shutdown_hook()
            await prefill_rt._shutdown_hook()
            await broker.stop()

    decode_inner, prefill_engine = asyncio.run(body())

    evs = tracing.events(trace_id="trace-2hop")
    names = {e["name"] for e in evs}
    # decode-worker side of the hop
    assert "disagg.remote_prefill" in names
    # prefill-worker side: the queue message carried the trace id across
    assert "disagg.prefill" in names
    assert "disagg.kv_extract" in names
    # engine spans from the prefill worker's engine thread stitched too
    assert "engine.prefill" in names
    # both hops agree on the stitching keys
    by_name = {e["name"]: e["args"] for e in evs}
    assert by_name["disagg.prefill"]["request_id"] == "d1"
    assert by_name["disagg.remote_prefill"]["request_id"] == "d1"

    # stage histograms on the decode engine are non-empty after the request
    sched = decode_inner.scheduler
    assert sched.stage_hist["ttft"].count >= 1
    assert sched.stage_hist["queue_wait"].count >= 1
    assert sched.stage.ttft_n >= 1
    text = decode_inner.render_stage_metrics()
    assert check_exposition(text) == [], check_exposition(text)
    assert "dynamo_engine_ttft_seconds_bucket" in text
    snap = decode_inner.stage_snapshot()
    assert snap["queue_wait_n"] >= 1 and snap["decode_windows"] >= 1


# ---------------- post-PR-1 subsystem spans (tracing gap fix) ----------------
# Subsystems added after the tracing PR emitted no spans: draft-model
# speculation, LoRA slot loads, and the pressure-driven offload drain. These
# tests pin their spans so a future subsystem can't silently regress the
# per-request timeline again.


def test_lora_slot_load_span_and_anatomy():
    """A cold adapter's device-slot scatter emits lora.slot_load and records
    a lora_slot_load step-anatomy dispatch."""
    from types import SimpleNamespace

    from dynamo_tpu.lora.store import LoraStore
    from dynamo_tpu.utils.step_anatomy import StepAnatomy

    tracing.enable()
    cfg = SimpleNamespace(max_loras=2, lora_rank=2, lora_adapters=("a1",))
    store = LoraStore(cfg, SimpleNamespace(config=None),
                      scatter_fn=lambda slot, tree, scale: None)
    store.anatomy = StepAnatomy()
    store._host["a1"] = ({}, 1.0)  # host weights already cached
    slot = store.acquire("a1")
    assert slot is not None
    evs = [e for e in tracing.events() if e["name"] == "lora.slot_load"]
    assert len(evs) == 1
    assert evs[0]["args"]["adapter"] == "a1"
    assert evs[0]["args"]["slot"] == slot
    assert store.anatomy.dispatch_counts.get("lora_slot_load") == 1
    # a warm re-acquire pins the resident slot: no second scatter span
    store.release("a1")
    assert store.acquire("a1") == slot
    assert len([e for e in tracing.events() if e["name"] == "lora.slot_load"]) == 1


def test_offload_drain_span_and_anatomy():
    """The watermark-driven cold-block drain emits engine.offload.drain with
    the drained block count and records an offload_drain dispatch."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.scheduler import Scheduler

    tracing.enable()

    class _Alloc:  # page pool past the watermark with drainable cold blocks
        offload = object()

        def __init__(self):
            self.used_pages = 14
            self._reusable = [1, 2, 3]

        def drain_to_host(self, batch):
            self.used_pages -= 8
            return 3

    cfg = EngineConfig(model_id="tiny", page_size=4, num_pages=16, max_seqs=2,
                       prefill_buckets=(16,), offload_watermark=0.5)
    sched = Scheduler(cfg, None, _Alloc())
    sched._drain_cold_to_host()
    evs = [e for e in tracing.events() if e["name"] == "engine.offload.drain"]
    assert len(evs) == 1
    assert evs[0]["args"]["blocks"] == 3
    assert sched.offload_pressure_blocks == 3
    assert sched.anatomy.dispatch_counts.get("offload_drain") == 1
    # below the watermark: no span, no record
    tracing.clear()
    sched._drain_cold_to_host()
    assert tracing.events() == []


def test_spec_draft_span_emitted():
    """A draft-model engine's drafting dispatch emits engine.spec.draft
    (alongside the verify pass's engine.spec.verify) — the draft phase was
    invisible in traces before this."""
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    tracing.enable()

    async def body():
        eng = AsyncJaxEngine(EngineConfig(
            model_id="tiny", page_size=4, num_pages=128, max_seqs=2,
            max_model_len=96, prefill_buckets=(16, 32),
            speculative="draft:tiny:1",
        ))
        await eng.start()
        try:
            rng = np.random.default_rng(0)
            req = EngineRequest(
                request_id="sd-1", token_ids=rng.integers(1, 200, 12).tolist(),
                sampling=SamplingParams(temperature=0.0, max_tokens=6,
                                        ignore_eos=True),
            )
            async for _ in eng.generate(req):
                pass
            return eng.scheduler.anatomy.snapshot()
        finally:
            await eng.shutdown()

    snap = asyncio.run(body())
    names = {e["name"] for e in tracing.events()}
    assert "engine.spec.draft" in names
    assert "engine.spec.verify" in names
    # the step-anatomy plane saw the same dispatches
    assert snap["dispatches"].get("spec_draft", 0) >= 1
    assert snap["dispatches"].get("spec_verify", 0) >= 1
