"""Fleet health plane units: HealthMonitor lifecycle + watchdog, SloTracker
percentiles/error budget, monitored-jit compile counting, aggregator aging,
and the dynotop renderer."""

import asyncio

import pytest

from dynamo_tpu.utils.compile_monitor import CompileMonitor, monitored_jit
from dynamo_tpu.utils.health import HealthMonitor, is_snapshot_servable
from dynamo_tpu.utils.prometheus import check_exposition
from dynamo_tpu.utils.slo import SloTracker


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------- HealthMonitor ----------------


def test_health_lifecycle_and_heartbeat():
    clock = FakeClock()
    hm = HealthMonitor("engine", clock=clock)
    assert hm.state == "starting"
    hm.set_state("ready", "init done")
    assert hm.state == "ready" and hm.is_servable()

    hm.beat()
    clock.advance(2.5)
    assert hm.heartbeat_age() == pytest.approx(2.5)
    snap = hm.snapshot()
    assert snap["state"] == "ready"
    assert snap["heartbeat_age_s"] == pytest.approx(2.5)
    assert snap["transitions"][-1]["to"] == "ready"

    hm.set_state("draining", "scale down")
    assert not hm.is_servable()
    hm.set_state("dead", "gone")
    # dead is terminal: later transitions are ignored
    hm.set_state("ready", "zombie")
    assert hm.state == "dead"


def test_health_watchdog_stuck_queue_and_recovery():
    clock = FakeClock()
    hm = HealthMonitor("engine", stuck_queue_s=10.0, no_progress_s=5.0, clock=clock)
    hm.set_state("ready", "")
    assert hm.check(oldest_waiting_age=3.0) is None
    assert hm.state == "ready"
    assert hm.check(oldest_waiting_age=11.0) == "stuck-queue"
    assert hm.state == "degraded"
    # alarm clears -> auto-recover to ready
    assert hm.check(oldest_waiting_age=0.0) is None
    assert hm.state == "ready"


def test_health_watchdog_no_progress():
    clock = FakeClock()
    hm = HealthMonitor("engine", stuck_queue_s=100.0, no_progress_s=5.0, clock=clock)
    hm.set_state("ready", "")
    hm.check(has_work=True, progress_marker=7)
    clock.advance(6.0)
    # marker frozen past the threshold while work exists -> degraded
    assert hm.check(has_work=True, progress_marker=7) == "no-progress"
    assert hm.state == "degraded"
    # progress resumes -> recovered
    assert hm.check(has_work=True, progress_marker=8) is None
    assert hm.state == "ready"
    # idle engines never alarm no matter how long the marker freezes
    clock.advance(100.0)
    assert hm.check(has_work=False, progress_marker=8) is None


def test_health_watchdog_never_overrides_draining():
    clock = FakeClock()
    hm = HealthMonitor("engine", stuck_queue_s=1.0, clock=clock)
    hm.set_state("draining", "scale down")
    hm.check(oldest_waiting_age=999.0)
    assert hm.state == "draining"


def test_health_exposition_conformant():
    hm = HealthMonitor("engine")
    hm.set_state("ready", "")
    text = hm.render_metrics()
    assert check_exposition(text) == []
    assert 'dynamo_health_state{component="engine",state="ready"} 1' in text
    assert 'state="dead"} 0' in text


def test_snapshot_servable_predicate():
    assert is_snapshot_servable(None)  # no health plane = servable
    assert is_snapshot_servable({"state": "ready"})
    assert is_snapshot_servable({"state": "degraded"})
    assert not is_snapshot_servable({"state": "draining"})
    assert not is_snapshot_servable({"state": "dead"})


# ---------------- SloTracker ----------------


def test_slo_percentiles_and_budget():
    clock = FakeClock()
    slo = SloTracker({"ttft": 0.5}, window_s=60.0, objective=0.9, clock=clock)
    # 8 good, 2 bad out of 10: violations == allowed (10%) -> budget 0.0
    for v in [0.1] * 8 + [0.9] * 2:
        slo.observe("ttft", v)
    s = slo.metric_state("ttft")
    assert s["count"] == 10 and s["violations"] == 2
    assert s["compliance"] == pytest.approx(0.8)
    assert s["error_budget"] == pytest.approx(-1.0)  # 2 violations, 1 allowed
    assert not s["ok"]
    assert s["p50_ms"] == pytest.approx(100.0)
    assert s["p99_ms"] == pytest.approx(900.0)

    # old samples fall out of the window
    clock.advance(120.0)
    slo.observe("ttft", 0.1)
    s = slo.metric_state("ttft")
    assert s["count"] == 1 and s["violations"] == 0 and s["ok"]
    # lifetime counters survive the pruning
    assert s["observed_total"] == 11 and s["violations_total"] == 2


def test_slo_untargeted_metric_never_violates():
    slo = SloTracker({})
    slo.observe("itl", 5.0)
    s = slo.metric_state("itl")
    assert s["ok"] and s["target_ms"] is None and s["error_budget"] == 1.0
    assert slo.snapshot()["ok"]


def test_slo_exposition_conformant():
    slo = SloTracker({"ttft": 0.2})
    for v in (0.05, 0.1, 0.4):
        slo.observe("ttft", v)
    text = slo.render_metrics()
    assert check_exposition(text) == []
    assert 'dynamo_slo_latency_seconds{metric="ttft",quantile="0.99"}' in text
    assert "dynamo_slo_error_budget_remaining" in text


def test_slo_env_targets(monkeypatch):
    from dynamo_tpu.utils.slo import targets_from_env

    monkeypatch.setenv("DYNTPU_SLO_TTFT_MS", "500")
    monkeypatch.setenv("DYNTPU_SLO_ITL_MS", "junk")  # ignored, not a crash
    t = targets_from_env({"itl": 25})
    assert t["ttft"] == pytest.approx(0.5)
    assert t["itl"] == pytest.approx(0.025)  # explicit override wins


# ---------------- monitored jit ----------------


def test_monitored_jit_counts_compiles():
    jax = pytest.importorskip("jax")
    import numpy as np

    mon = CompileMonitor()
    f = monitored_jit(jax.jit(lambda x: x + 1), "add", mon)
    f(np.zeros(3, np.float32))
    assert mon.compiles == 1 and mon.compile_s > 0
    f(np.zeros(3, np.float32))  # cache hit: no new compile
    assert mon.compiles == 1
    f(np.zeros(5, np.float32))  # new shape: recompile
    assert mon.compiles == 2
    snap = mon.snapshot()
    assert snap["per_label"] == {"add": 2}
    assert snap["last_label"] == "add"


def test_a_compile_log_line_says_which_program(monkeypatch):
    """A label has many executables (one per lanes x rows x table width): the
    line of a slow compile carries the shapes of the call's integer arrays and
    its static keywords that are set."""
    import numpy as np

    from dynamo_tpu.utils import compile_monitor
    from dynamo_tpu.utils.compile_monitor import _describe

    ints, flts = np.zeros((4, 215), np.int32), np.zeros((6, 4), np.float32)
    assert _describe((None, ints, flts), {"mp": 128, "want_lp": True, "want_pen": False}) == "4x215 mp=128 want_lp"
    assert _describe((), {}) == ""
    lines = []
    monkeypatch.setattr(compile_monitor.log, "info", lambda fmt, *a: lines.append(fmt % a))
    CompileMonitor().record("prefill_packed", 2.5, what="4x215 mp=128")
    CompileMonitor().record("prefill_packed", 0.5, what="4x215 mp=128")  # fast: no line
    assert lines == ["xla compile: prefill_packed took 2.50s (1 total) [4x215 mp=128]"]


def test_monitored_jit_passthrough_without_monitor():
    def fn(x):
        return x

    assert monitored_jit(fn, "x", None) is fn


# ---------------- aggregator aging ----------------


def _mk_aggregator(max_missed=2):
    from dynamo_tpu.llm.kv_router.metrics_aggregator import KvMetricsAggregator

    return KvMetricsAggregator(None, "ns", "backend", max_missed_scrapes=max_missed)


def _fake_scrape(agg, endpoints):
    """Drive one scrape round against injected endpoint stats (no cplane)."""
    import dynamo_tpu.llm.kv_router.metrics_aggregator as mod
    from dynamo_tpu.runtime.service import EndpointStats, ServiceSet

    async def fake_collect(cplane, ns, comp, timeout=0.0):
        return ServiceSet(endpoints=[
            EndpointStats(instance_id=i, endpoint="generate", subject="s", data=d)
            for i, d in endpoints
        ])

    orig = mod.collect_service_stats
    mod.collect_service_stats = fake_collect
    try:
        return asyncio.run(agg.scrape_once())
    finally:
        mod.collect_service_stats = orig


KV = {
    "request_active_slots": 1, "request_total_slots": 8,
    "kv_active_blocks": 5, "kv_total_blocks": 100,
}


def test_aggregator_ages_out_silent_workers():
    agg = _mk_aggregator(max_missed=2)
    loads = _fake_scrape(agg, [(1, {"kv_metrics": KV}), (2, {"kv_metrics": KV})])
    assert {w.worker_id for w in loads} == {1, 2}

    # worker 2 goes silent: stale immediately, aged out after max_missed
    _fake_scrape(agg, [(1, {"kv_metrics": KV})])
    views = {v.instance_id: v for v in agg.worker_views()}
    assert views[2].stale and views[2].missed_scrapes == 1
    assert {w.worker_id for w in agg.get_metrics()} == {1, 2}  # not aged yet
    _fake_scrape(agg, [(1, {"kv_metrics": KV})])
    _fake_scrape(agg, [(1, {"kv_metrics": KV})])
    assert {w.worker_id for w in agg.get_metrics()} == {1}
    assert [v.instance_id for v in agg.worker_views()] == [1]

    # a returning worker is fresh again
    _fake_scrape(agg, [(1, {"kv_metrics": KV}), (2, {"kv_metrics": KV})])
    assert {w.worker_id for w in agg.get_metrics()} == {1, 2}


def test_aggregator_excludes_draining_and_dead_immediately():
    agg = _mk_aggregator()
    _fake_scrape(agg, [
        (1, {"kv_metrics": KV, "health": {"state": "ready"}}),
        (2, {"kv_metrics": KV, "health": {"state": "draining"}}),
        (3, {"kv_metrics": KV, "health": {"state": "dead"}}),
    ])
    assert {w.worker_id for w in agg.get_metrics()} == {1}
    assert {i for i, _ in agg.get_raw()} == {1}
    # the status surface still SHOWS them
    assert [v.instance_id for v in agg.worker_views()] == [1, 2, 3]


def test_aggregator_last_seen_tracks_freshness():
    agg = _mk_aggregator()
    _fake_scrape(agg, [(7, {"kv_metrics": KV})])
    view = agg.worker_views()[0]
    assert view.age_s() < 1.0
    assert view.last_seen_wall > 0


# ---------------- dynotop renderer ----------------


def test_dynotop_render_status_pure():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "dynotop", Path(__file__).resolve().parent.parent / "tools" / "dynotop.py"
    )
    dynotop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dynotop)

    doc = {
        "namespace": "ns", "component": "backend",
        "summary": {"workers": 2, "servable": 1, "stale": 1, "unservable": 1},
        "scrape_interval_s": 1.0,
        "kv_hit_rate": {"isl_blocks": 10, "overlap_blocks": 4},
        "workers": [
            {
                "worker_id": "ab", "last_seen_s": 0.2, "missed_scrapes": 0,
                "stale": False, "servable": True,
                "health": {"state": "ready", "heartbeat_age_s": 0.05},
                "kv_metrics": {"request_active_slots": 2, "request_total_slots": 8,
                               "kv_active_blocks": 50, "kv_total_blocks": 100,
                               "num_requests_waiting": 1},
                "resources": {"hbm_bytes_in_use": 2 * 1024**3, "xla_compiles": 12},
                "slo": {"metrics": {"ttft": {"target_ms": 500.0, "error_budget": 0.75}}},
            },
            {
                "worker_id": "cd", "last_seen_s": 9.5, "missed_scrapes": 3,
                "stale": True, "servable": False,
                "health": {"state": "dead"}, "kv_metrics": {}, "resources": {},
            },
        ],
    }
    text = dynotop.render_status(doc)
    assert "ab" in text and "cd" in text
    assert "ready" in text and "dead" in text
    assert "STALE" in text
    assert "50.0%" in text  # kv occupancy
    assert "2.0GB" in text
    assert "budget +0.75 OK" in text
    assert "hit rate: 40.0%" in text

    # empty fleet renders, not crashes
    empty = dynotop.render_status({"summary": {}, "workers": []})
    assert "no workers" in empty
