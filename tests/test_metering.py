"""Cost-attribution plane (utils/metering.py): both conservation identities
(attributed device-seconds == step-anatomy wall totals; per-tier summed KV
byte-seconds == occupancy integrals) under weighted bills and tier churn,
the owner handoff down the HBM -> host -> disk ladder, the zero-cost path
with metering off, per-request footers, exposition conformance of the five
dynamo_cost_* families, the goodput (tenant|adapter) join, the planner's
per-tenant burn signal, the metrics component's fleet merge, the replay
report's per-tenant rollup, and the dynotop COST column. The slow leg runs
a two-tenant replay against a real engine and checks the heavy tenant's
measured device-time share tracks its token share end to end."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.utils.metering import MeterLedger, TIERS
from dynamo_tpu.utils.step_anatomy import StepAnatomy, StepRecord


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def bill_row(rid, tenant, adapter="", priority="", weight=1.0):
    return (rid, tenant, adapter, priority, weight)


# ---------------- device-time plane ----------------


def test_device_conservation_vs_anatomy_totals():
    """Every clamped phase delta the anatomy adds is forwarded to the meter
    with the record's bill, so attributed device-seconds sum to the anatomy
    wall totals exactly — across billed, system, and one-shot records."""
    meter = MeterLedger(clock=FakeClock())
    anat = StepAnatomy()
    anat.meter = meter

    rec = anat.begin("decode_window", bill=[
        bill_row("r1", "acme", "a1", "critical", 3.0),
        bill_row("r2", "umbrella", "", "standard", 1.0),
    ])
    anat.add_phase(rec, "host_prep", 0.001)
    anat.add_phase(rec, "dispatch", 0.002)
    anat.add_phase(rec, "device_wait", 0.008)
    anat.add_phase(rec, "reconcile", 0.001)
    # system work: no bill -> the ("","","") key, still conserved
    anat.record("offload_drain", dispatch_s=0.004)
    # negative clamps to zero on BOTH sides of the identity
    anat.add_phase(rec, "reconcile", -0.5)
    prec = anat.begin("prefill_packed", bill=[bill_row("r1", "acme", "a1", "critical", 16)])
    anat.add_phase(prec, "dispatch", 0.01)

    cons = meter.conservation(anatomy=anat)
    assert cons["device"]["anatomy_s"] == pytest.approx(0.026)
    assert cons["device"]["rel_err"] < 1e-9
    # proportional split: acme gets 3/4 of the decode window, umbrella 1/4
    snap = meter.snapshot()
    assert snap["tenants"]["acme"]["by_kind"]["decode_window"] == pytest.approx(
        0.012 * 0.75
    )
    assert snap["tenants"]["umbrella"]["device_s"] == pytest.approx(0.012 * 0.25)
    assert snap["tenants"][""]["by_kind"]["offload_drain"] == pytest.approx(0.004)
    # the (tenant|adapter) join key the goodput plane shares
    assert snap["adapters"]["acme|a1"] == pytest.approx(0.012 * 0.75 + 0.01)
    assert snap["top_tenant"] == "acme"


def test_device_zero_weight_bills_fall_back_to_even_split():
    meter = MeterLedger(clock=FakeClock())
    rec = StepRecord(seq=1, ts=0.0, kind="decode_window", bill=[
        bill_row("r1", "a", weight=0.0), bill_row("r2", "b", weight=0.0),
    ])
    meter.on_phase(rec, "device_wait", 0.01)
    snap = meter.snapshot()
    assert snap["tenants"]["a"]["device_s"] == pytest.approx(0.005)
    assert snap["tenants"]["b"]["device_s"] == pytest.approx(0.005)
    assert meter.device_seconds_total() == pytest.approx(0.01)


# ---------------- KV-residency plane ----------------


def test_kv_conservation_under_tier_churn():
    """Byte-seconds integrate on allocate/free/demote/restore edges with one
    clock read per edge, so per-tenant sums equal the occupancy integral per
    tier exactly — including the demotion ladder carrying owners down."""
    clock = FakeClock()
    meter = MeterLedger(clock=clock)
    meter.kv_acquire("hbm", "p1", 1000, ("acme", "r1"))
    meter.kv_acquire("hbm", "p2", 500, ("umbrella", "r2"))
    clock.advance(2.0)
    # idempotent: a cache hit never re-owns or double-counts
    meter.kv_acquire("hbm", "p1", 1000, ("umbrella", "r9"))
    assert meter.kv_resident_bytes("hbm") == 1500
    # demote p1: HBM release returns the ORIGINAL owner, host acquires it
    owner = meter.kv_release("hbm", "p1")
    assert owner == ("acme", "r1")
    meter.kv_acquire("host", "h1", 1000, owner)
    clock.advance(3.0)
    # demote further to disk at compressed size, then release everywhere
    owner = meter.kv_release("host", "h1")
    meter.kv_acquire("disk", "d1", 250, owner)
    clock.advance(5.0)
    meter.kv_release("disk", "d1")
    meter.kv_release("hbm", "p2")
    # unknown key (metering attached mid-flight): no-op, returns None
    assert meter.kv_release("hbm", "never-seen") is None
    clock.advance(1.0)

    hbm = meter.kv_byte_seconds("hbm")
    assert hbm["tenants"]["acme"] == pytest.approx(1000 * 2.0)  # resident 2s
    assert hbm["tenants"]["umbrella"] == pytest.approx(500 * 10.0)
    assert hbm["resident_bytes"] == 0
    assert meter.kv_byte_seconds("host")["tenants"]["acme"] == pytest.approx(3000.0)
    assert meter.kv_byte_seconds("disk")["tenants"]["acme"] == pytest.approx(1250.0)
    cons = meter.conservation(now=clock())
    for tier in TIERS:
        assert cons["kv"][tier]["rel_err"] < 1e-9, (tier, cons)


def test_page_allocator_meters_hbm_residency():
    """PageAllocator edges: allocation acquires under the owner, freeing
    uncached pages releases, reusable-pool parking keeps charging the owner
    until reclaim demotes (with the owner riding into the host pool)."""
    from dynamo_tpu.engine.page_table import PageAllocator

    clock = FakeClock()
    meter = MeterLedger(clock=clock)
    alloc = PageAllocator(16, 4)
    alloc.meter = meter
    alloc.meter_page_bytes = 4096

    alloc.allocate_sequence("s1", list(range(10)), owner=("acme", "r1"))
    pages = alloc._seqs["s1"].num_pages
    assert meter.kv_resident_bytes("hbm") == pages * 4096
    snap = meter.snapshot()
    assert snap["tenants"]["acme"]["kv_resident_bytes"]["hbm"] == pages * 4096
    clock.advance(1.0)
    # committed prefill registers the full blocks: freeing parks them in the
    # reusable pool — bytes stay resident and keep charging acme (residency
    # is the benefit the cache sells)
    alloc.commit_prefilled("s1", 10)
    alloc.free_sequence("s1")
    parked = meter.kv_resident_bytes("hbm")
    assert parked > 0 and parked == alloc.used_pages * 4096
    # a second tenant's allocation: fresh pages acquire under umbrella; the
    # meter tracks the pool's own occupancy truth throughout
    alloc.allocate_sequence("s2", list(range(100, 130)), owner=("umbrella", "r2"))
    assert meter.kv_resident_bytes("hbm") == alloc.used_pages * 4096
    alloc.free_sequence("s2")
    clock.advance(1.0)
    cons = meter.conservation(now=clock())
    assert cons["kv"]["hbm"]["rel_err"] < 1e-9
    assert meter.kv_resident_bytes("hbm") == alloc.used_pages * 4096
    # acme still owns the parked bytes (no re-own on parking)
    assert meter.snapshot()["tenants"]["acme"]["kv_resident_bytes"]["hbm"] == parked


def test_host_pool_eviction_carries_owner_to_disk():
    """HostKvPool LRU victims release the host tier under their ORIGINAL
    owner and the owner rides into DiskKvStore.spill, which charges the
    int8-compressed bytes under the same tenant."""
    from dynamo_tpu.engine.kv_store import DiskKvStore
    from dynamo_tpu.engine.offload import HostKvPool
    from dynamo_tpu.models.paged import PagedModel

    class _Runner:
        model = PagedModel(None)  # the contract's defaults: `wire_n_axis`

        def extract_pages(self, ids):
            return np.zeros((2, 2, len(ids), 4, 2, 2), np.float32)

    clock = FakeClock()
    meter = MeterLedger(clock=clock)
    pool = HostKvPool(_Runner(), capacity_blocks=2, block_bytes=256)
    pool.meter = meter
    store = DiskKvStore(budget_bytes=1 << 20)
    store.meter = meter
    pool.disk = store
    try:
        pool.save(901, 1, owner=("acme", "r1"))
        pool.save(902, 2, owner=("umbrella", "r2"))
        assert meter.kv_resident_bytes("host") == 512
        # third save evicts the LRU victim (901, acme) down to disk
        pool.save(903, 3, owner=("umbrella", "r2"))
        assert meter.kv_resident_bytes("host") == 512
        disk = meter.kv_byte_seconds("disk")
        assert meter.kv_resident_bytes("disk") > 0
        assert set(disk["tenants"]) == {"acme"}  # the original owner pays
        # discard releases the host entry
        pool.discard(902)
        assert meter.kv_resident_bytes("host") == 256
        clock.advance(1.0)
        cons = meter.conservation(now=clock())
        for tier in ("host", "disk"):
            assert cons["kv"][tier]["rel_err"] < 1e-9
    finally:
        store.close()


# ---------------- queue/token plane + footers ----------------


def test_tokens_queued_and_request_footer():
    meter = MeterLedger(clock=FakeClock())
    rec = StepRecord(seq=1, ts=0.0, kind="decode_window", bill=[
        bill_row("r1", "acme", "a1", "critical", 2.0),
    ])
    meter.on_phase(rec, "device_wait", 0.006)
    meter.kv_acquire("hbm", "p1", 4096, ("acme", "r1"))
    meter.queued("acme", 0.25)
    meter.charge_tokens("acme", "admitted", 40)
    meter.charge_tokens("acme", "prompt", 16)
    meter.charge_tokens("acme", "output", 8)
    meter.charge_tokens("acme", "output", 0)  # no-op

    snap = meter.snapshot()
    assert snap["tenants"]["acme"]["queued_s"] == pytest.approx(0.25)
    assert snap["tenants"]["acme"]["tokens"] == {
        "admitted": 40, "prompt": 16, "output": 8,
    }
    cost = meter.request_cost("r1")
    assert cost["tenant"] == "acme" and cost["priority"] == "critical"
    assert cost["device_ms"]["decode_window"] == pytest.approx(6.0)
    assert cost["device_ms_total"] == pytest.approx(6.0)
    assert cost["kv_peak_bytes"]["hbm"] == 4096
    assert meter.request_cost("nope") is None


def test_footer_lru_bounded():
    meter = MeterLedger(clock=FakeClock(), footer_capacity=4)
    for i in range(10):
        rec = StepRecord(seq=i, ts=0.0, kind="decode_window",
                         bill=[bill_row(f"r{i}", "t")])
        meter.on_phase(rec, "dispatch", 0.001)
    assert meter.request_cost("r0") is None  # evicted
    assert meter.request_cost("r9") is not None
    assert meter.snapshot()["footers"] == 4
    # conservation is unaffected by footer eviction
    assert meter.device_seconds_total() == pytest.approx(0.01)


# ---------------- exposition ----------------


def test_render_metrics_conformant_and_declared():
    import re

    from dynamo_tpu.utils.prometheus import (
        DECLARED_METRIC_FAMILIES, check_exposition,
    )

    def families(text):
        return set(re.findall(r"^# TYPE (\S+)", text, re.M))

    declared = {n for n in DECLARED_METRIC_FAMILIES if n.startswith("dynamo_cost_")}
    assert len(declared) == 5
    # zero state: all five families render their zero-sample fallbacks
    empty = MeterLedger(clock=FakeClock())
    assert families(empty.render_metrics()) == declared
    # populated state conforms
    meter = MeterLedger(clock=FakeClock())
    rec = StepRecord(seq=1, ts=0.0, kind="decode_window",
                     bill=[bill_row("r1", "acme", "a1", "critical", 1.0)])
    meter.on_phase(rec, "device_wait", 0.004)
    meter.kv_acquire("hbm", "p", 4096, ("acme", "r1"))
    meter.queued("acme", 0.1)
    meter.charge_tokens("acme", "admitted", 12)
    text = meter.render_metrics()
    assert check_exposition(text) == []
    assert families(text) == declared
    assert 'tenant="acme"' in text and 'kind="decode_window"' in text


def test_zero_cost_path_when_metering_off():
    """metering=False: no ledger anywhere — the engine carries meter=None,
    cost surfaces return empty, and no dynamo_cost_* family is emitted."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.page_table import PageAllocator
    from dynamo_tpu.engine.scheduler import Scheduler

    cfg = EngineConfig(model_id="tiny", page_size=4, num_pages=8, max_seqs=2,
                       prefill_buckets=(16,), metering=False)
    eng = AsyncJaxEngine(cfg)
    assert eng.meter is None
    eng.allocator = PageAllocator(cfg.num_pages, cfg.page_size)
    eng.scheduler = Scheduler(cfg, None, eng.allocator)
    assert eng.cost_snapshot() == {}
    assert eng.request_cost("any") is None
    assert "dynamo_cost_" not in eng.render_stage_metrics()
    assert "costs" not in eng.resource_snapshot() or not eng.resource_snapshot()["costs"]
    # the on path: a default-config engine has the ledger + surfaces
    eng2 = AsyncJaxEngine(EngineConfig(model_id="tiny", page_size=4,
                                       num_pages=8, max_seqs=2,
                                       prefill_buckets=(16,)))
    assert eng2.meter is not None
    assert eng2.cost_snapshot()["device_s_total"] == 0.0


# ---------------- joins + fleet surfaces ----------------


def test_goodput_adapter_join_key():
    from dynamo_tpu.utils.goodput import GoodputTracker, RequestOutcome

    gp = GoodputTracker(ttft_budget_s=1.0, itl_budget_s=1.0)
    gp.observe(RequestOutcome("r1", tenant="acme", adapter="a1",
                              ttft_s=0.1, itl_s=(0.01,), output_tokens=4))
    gp.observe(RequestOutcome("r2", tenant="acme", adapter="a2",
                              ttft_s=0.2, output_tokens=2))
    gp.observe(RequestOutcome("r3", tenant="", adapter="", ttft_s=0.1))
    snap = gp.snapshot()
    assert set(snap["adapters"]) == {"acme|a1", "acme|a2"}
    assert snap["adapters"]["acme|a1"]["requests"] == 1
    # the same join key format the meter publishes
    meter = MeterLedger(clock=FakeClock())
    rec = StepRecord(seq=1, ts=0.0, kind="decode_window",
                     bill=[bill_row("r1", "acme", "a1")])
    meter.on_phase(rec, "dispatch", 0.002)
    assert set(meter.snapshot()["adapters"]) == {"acme|a1"}


def test_planner_tenant_burn_differencing():
    from dynamo_tpu.components.planner import PlannerService, demand_key
    from dynamo_tpu.llm.kv_router.metrics_aggregator import WorkerView

    assert demand_key("ns", "worker") == "planner/ns/demand/worker"

    class _Drt:
        cplane = None

    svc = PlannerService(_Drt(), "ns")

    def views(dev_a, dev_b=None):
        data = {"costs": {"tenants": {
            "acme": {"device_s": dev_a}, "": {"device_s": 99.0},
        }}}
        out = [WorkerView(1, data=data)]
        if dev_b is not None:
            out.append(WorkerView(2, data={"costs": {"tenants": {
                "umbrella": {"device_s": dev_b},
            }}}))
        return out

    class _Agg:
        def __init__(self):
            self._v = []

        def worker_views(self):
            return self._v

    svc.aggregator = _Agg()
    svc.aggregator._v = views(2.0, 1.0)
    assert svc.observe_tenant_burn() == {"acme": 2.0, "umbrella": 1.0}
    # second scrape: only the delta is demand; flat tenants drop out
    svc.aggregator._v = views(3.5, 1.0)
    assert svc.observe_tenant_burn() == {"acme": 1.5}
    assert svc.tenant_demand == {"acme": 1.5}
    # worker restart (cumulative shrink): baseline resets, no negative burn
    svc.aggregator._v = views(0.5)
    assert svc.observe_tenant_burn() == {}
    svc.aggregator._v = views(0.9)
    assert svc.observe_tenant_burn() == {"acme": pytest.approx(0.4)}
    # the untagged system row never becomes demand
    assert "" not in svc._last_burn or True
    assert all(t for t in svc.tenant_demand)


def test_metrics_component_cluster_costs_merge():
    import time as _time

    from dynamo_tpu.components.metrics import MetricsService
    from dynamo_tpu.llm.kv_router.metrics_aggregator import WorkerView

    class _Drt:
        cplane = None

    svc = MetricsService(_Drt(), "ns", "backend")
    mk = lambda t, dev, kvb: {
        "tenants": {t: {
            "device_s": dev, "by_kind": {"decode_window": dev},
            "kv_byte_s": {"hbm": kvb}, "kv_resident_bytes": {"hbm": 4096},
            "queued_s": 0.1, "tokens": {"admitted": 10, "output": 4},
        }},
        "adapters": {f"{t}|a1": dev},
        "tiers": {"hbm": {"resident_bytes": 4096, "byte_s": kvb}},
        "device_s_total": dev, "top_tenant": t,
    }
    svc.aggregator._workers[1] = WorkerView(
        1, data={"costs": mk("acme", 2.0, 100.0)}, last_seen=_time.monotonic())
    svc.aggregator._workers[2] = WorkerView(
        2, data={"costs": mk("acme", 1.0, 50.0)}, last_seen=_time.monotonic())
    svc.aggregator._workers[3] = WorkerView(
        3, data={}, last_seen=_time.monotonic())  # pre-plane worker: skipped

    doc = svc.cluster_costs()
    assert doc["tenants"]["acme"]["device_s"] == pytest.approx(3.0)
    assert doc["tenants"]["acme"]["kv_byte_s"]["hbm"] == pytest.approx(150.0)
    assert doc["tenants"]["acme"]["kv_resident_bytes"]["hbm"] == 8192
    assert doc["tenants"]["acme"]["tokens"] == {"admitted": 20, "output": 8}
    assert doc["adapters"]["acme|a1"] == pytest.approx(3.0)
    assert doc["tiers"]["hbm"]["resident_bytes"] == 8192
    assert doc["device_s_total"] == pytest.approx(3.0)
    assert doc["device_share"]["acme"] == pytest.approx(1.0)
    assert len(doc["workers"]) == 2
    # the per-worker cluster_status entries carry the costs blob for dynotop
    status = svc.cluster_status()
    by_id = {w["worker_id"]: w for w in status["workers"]}
    assert by_id["1"]["costs"]["top_tenant"] == "acme"


def test_replay_tenant_rollup_and_report_rows():
    from dynamo_tpu.loadgen.replay import _tenant_rollup
    from dynamo_tpu.loadgen.report import render_report
    from dynamo_tpu.utils.goodput import RequestOutcome

    outcomes = [
        RequestOutcome("r1", tenant="acme", prompt_tokens=30, output_tokens=30),
        RequestOutcome("r2", tenant="acme", prompt_tokens=20, output_tokens=20),
        RequestOutcome("r3", tenant="umbrella", prompt_tokens=10,
                       output_tokens=10, error=True),
    ]
    costs = {"acme": {"device_s": 0.09, "kv_byte_s": 900.0},
             "umbrella": {"device_s": 0.01, "kv_byte_s": 100.0}}
    rows = _tenant_rollup(outcomes, costs)
    assert rows["acme"]["requests"] == 2 and rows["acme"]["errors"] == 0
    assert rows["acme"]["token_share"] == pytest.approx(100 / 120, abs=1e-4)
    assert rows["acme"]["device_ms"] == pytest.approx(90.0)
    assert rows["acme"]["device_share"] == pytest.approx(0.9)
    assert rows["umbrella"]["kv_share"] == pytest.approx(0.1)
    # no meter reachable: token rows only
    bare = _tenant_rollup(outcomes, None)
    assert "device_ms" not in bare["acme"]
    # renderer shows the tenant sub-rows for multi-tenant/metered reports
    rep = {"scenario": "bursty_chat", "requests": 3, "errors": 1,
           "goodput": 0.5, "schedule_lag_max_s": 0.001, "tenants": rows}
    text = render_report([rep])
    assert "tenant acme" in text and "dev_ms=90.0 (90.0%)" in text
    # single-tenant unmetered report keeps the old compact shape
    rep2 = dict(rep, tenants=_tenant_rollup(outcomes[:2], None))
    assert "tenant acme" not in render_report([rep2])


def test_dynotop_cost_column():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "dynotop_cost",
        Path(__file__).resolve().parent.parent / "tools" / "dynotop.py",
    )
    dynotop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dynotop)
    doc = {
        "summary": {"workers": 1, "servable": 1, "stale": 0, "unservable": 0},
        "workers": [{
            "worker_id": "ab", "health": {"state": "ready", "heartbeat_age_s": 0.1},
            "kv_metrics": {"request_active_slots": 1, "request_total_slots": 8,
                           "kv_active_blocks": 2, "kv_total_blocks": 10,
                           "num_requests_waiting": 0},
            "resources": {}, "last_seen_s": 0.2, "missed_scrapes": 0,
            "costs": {"device_s_total": 12.34, "top_tenant": "acme-corp"},
        }],
    }
    text = dynotop.render_status(doc)
    assert "COST" in text
    assert "12.3s acme-c" in text
    # pre-plane worker shows "-"
    del doc["workers"][0]["costs"]
    assert "12.3s" not in dynotop.render_status(doc)


def test_http_debug_request_cost_footer():
    """/debug/requests/{id} merges the engine's cost footer into the
    journal timeline when a cost_source is wired."""
    import aiohttp

    from dynamo_tpu.llm.http.service import HttpService
    from dynamo_tpu.utils import events

    async def body():
        footer = {"request_id": "r-cost", "tenant": "acme",
                  "device_ms_total": 6.5}
        svc = HttpService(
            port=0, cost_source=lambda rid: footer if rid == "r-cost" else None,
        )
        events.JOURNAL.emit("request.enqueued", request_id="r-cost")
        port = await svc.start()
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get(
                    f"http://127.0.0.1:{port}/debug/requests/r-cost"
                ) as r:
                    doc = await r.json()
                    assert doc["cost"]["device_ms_total"] == 6.5
                async with s.get(
                    f"http://127.0.0.1:{port}/debug/requests/r-none"
                ) as r:
                    assert "cost" not in await r.json()
        finally:
            await svc.stop()

    asyncio.run(body())


# ---------------- slow e2e: two-tenant replay conservation ----------------


@pytest.mark.slow
def test_two_tenant_replay_share_tracks_tokens():
    """End-to-end acceptance: a bursty two-tenant replay against a real
    engine — the token-heavy tenant's measured device-time share tracks its
    token share, BOTH conservation identities hold on the live ledger, and
    the replay report's rollup carries the measured shares."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.loadgen.replay import replay_engine
    from dynamo_tpu.loadgen.trace import TraceRequest

    cfg = EngineConfig(
        model_id="tiny", page_size=4, num_pages=256, max_seqs=4,
        max_model_len=128, prefill_buckets=(16, 32), decode_steps=4,
        pipeline_depth=2,
    )
    eng = AsyncJaxEngine(cfg)
    # zipf-heavy mix: acme sends 6 requests at 3x the output length of
    # umbrella's 2 — its token share should be ~0.9
    trace, rid = [], 0
    for i in range(6):
        trace.append(TraceRequest(
            at_s=i * 0.01, request_id=f"a{rid}", scenario="bursty_chat",
            token_ids=list(range(1, 17)), max_tokens=24, tenant="acme",
        ))
        rid += 1
    for i in range(2):
        trace.append(TraceRequest(
            at_s=i * 0.02, request_id=f"u{rid}", scenario="bursty_chat",
            token_ids=list(range(1, 9)), max_tokens=8, tenant="umbrella",
        ))
        rid += 1

    async def body():
        await eng.start()
        try:
            return await replay_engine(eng, trace, speed=100.0)
        finally:
            cons = eng.meter.conservation(anatomy=eng.scheduler.anatomy)
            snap = eng.meter.snapshot()
            await eng.shutdown()
            body.cons, body.snap = cons, snap

    report = asyncio.run(body())
    cons, snap = body.cons, body.snap
    assert report["errors"] == 0
    # both identities on the live ledger
    assert cons["device"]["rel_err"] < 1e-6, cons
    for tier in TIERS:
        assert cons["kv"][tier]["rel_err"] < 1e-6, (tier, cons)
    # token vs measured device-time share for the heavy tenant
    tok = {t: r["prompt_tokens"] + r["output_tokens"]
           for t, r in report["tenants"].items() if t}
    tok_share = tok["acme"] / sum(tok.values())
    dev = {t: r["device_s"] for t, r in snap["tenants"].items() if t}
    dev_share = dev["acme"] / sum(dev.values())
    assert tok_share > 0.8
    # generous tolerance: prefill packing and window co-residency blur the
    # split, but the heavy tenant must clearly dominate and track tokens
    assert dev_share == pytest.approx(tok_share, abs=0.2)
    assert dev_share > 0.6
    # the report rollup carries the measured shares (engine meter reachable)
    assert report["tenants"]["acme"]["device_share"] == pytest.approx(
        dev_share, abs=0.05
    )
    # admitted-vs-consumed: admitted = prompt + max_tokens per request, and
    # ignore_eos is off so output <= admitted budget
    tokens = snap["tenants"]["acme"]["tokens"]
    assert tokens["admitted"] == 6 * (16 + 24)
    assert tokens["prompt"] == 6 * 16
    assert 0 < tokens["output"] <= 6 * 24
    # per-request footer reachable through the engine surface the debug
    # endpoint uses
    cost = body.snap and eng.meter.request_cost("a0")
    assert cost is not None and cost["tenant"] == "acme"
    assert cost["device_ms_total"] > 0
