"""The second reduction's arithmetic (device time by step, the pairing of a
prefill's dispatch span with its run on the device, gaps named by the engine
thread's span, idle time the host caused): on intervals small enough to check
by hand, and on a recorded slice of a chip trace kept beside this file."""

import json
from pathlib import Path

import trace_steps

HERE = Path(__file__).resolve().parent
DEV = "/device:TPU:0"


def _host(*spans):
    return sorted(((n, s, d, st) for n, s, d, st in spans), key=lambda e: (e[1], -e[2]))


def _prefill(seq, dispatch, reconcile_at):
    s, e = dispatch
    return [("engine.prefill_packed.dispatch", s, e - s, {"seq": seq, "rows": 256}),
            ("engine.prefill_packed.reconcile", reconcile_at, 5, {"seq": seq})]


def test_modules_steps_and_shares_by_hand():
    ev = {
        "modules": {DEV: [("decode_window", 0, 800), ("prefill_packed", 800, 100), ("decode_window", 900, 800),
                          ("set_lora", 1700, 10)]},
        "ops": {DEV: [(0, 800), (800, 100), (900, 800), (1760, 40)]},
        "host": _host(("engine.step", 0, 1000, {}),
                      ("engine.decode_window.dispatch", 10, 20, {"seq": 1, "k": 8}),
                      ("engine.decode_window.dispatch", 910, 20, {"seq": 3, "k": 8}),
                      ("engine.post", 1000, 100, {})),
    }
    r = trace_steps.reduce(ev)
    m = r["modules"]["decode_window"]
    assert m["calls"] == 2 and abs(m["seconds"] - 1600e-9) < 1e-15 and abs(m["mean_ms"] - 800e-6) < 1e-12
    assert r["decode"]["steps"] == 16 and abs(r["decode"]["step_ms"] - 100e-6) < 1e-12
    assert r["prefill"]["calls"] == 1 and abs(r["prefill"]["mean_ms"] - 100e-6) < 1e-12
    # busy [0,1700] + [1760,1800] of a window of 1800
    assert abs(r["busy_s"] - 1740e-9) < 1e-15 and abs(r["window_s"] - 1800e-9) < 1e-15
    # the engine thread: 1100 ns from the first span to the last one's end,
    # 40 ns of phases inside the step, nothing uncovered
    t = r["thread"]
    assert abs(t["seconds"] - 1100e-9) < 1e-15 and abs(t["step_s"] - 1000e-9) < 1e-15
    assert abs(t["post_s"] - 100e-9) < 1e-15 and abs(t["phases_s"] - 40e-9) < 1e-15
    assert abs(t["uncovered_s"]) < 1e-15


def test_pairing_skips_runs_dispatched_before_the_trace_and_spans_run_after_it():
    # run A was dispatched before the trace began and starts AFTER the first
    # span does: "the first run at or after the span's start" would pair it
    # with span 11, one dispatch early all the way down
    runs = [("prefill_packed", 150, 50),    # A
            ("prefill_packed", 400, 50),    # of seq 11
            ("decode_window", 450, 100),
            ("prefill_packed", 700, 60)]    # of seq 12
    host = _host(("engine.step", 0, 2000, {}),
                 *_prefill(11, (100, 120), 460), *_prefill(12, (380, 400), 770),
                 ("engine.prefill_packed.dispatch", 900, 20, {"seq": 13, "rows": 64}))  # its run: after the trace
    pairs, how = trace_steps.pair(host, runs)
    assert [(p["seq"], round(p["backlog_ms"] * 1e6), round(p["device_ms"] * 1e6)) for p in pairs] == [
        (11, 280, 50), (12, 300, 60)], how
    assert "1 runs before the first span" in how and "1 spans after the last run" in how


def test_a_run_that_starts_before_its_dispatch_is_refused():
    # one span, one run, and the run is over before the span begins (two
    # clocks that do not agree): no pair, and the reason
    runs = [("prefill_packed", 10, 50)]
    host = _host(("engine.step", 0, 500, {}), *_prefill(5, (100, 120), 300))
    pairs, how = trace_steps.pair(host, runs)
    assert pairs == [] and "no alignment" in how
    # and a backlog is never below zero: a run that starts inside its span
    pairs, _ = trace_steps.pair(_host(("engine.step", 0, 500, {}), *_prefill(5, (100, 120), 300)),
                                [("prefill_packed", 110, 50)])
    assert [p["backlog_ms"] for p in pairs] == [0.0]


def test_gaps_are_named_by_the_innermost_span_and_idle_time_is_split():
    ev = {
        "modules": {DEV: [("decode_window", 0, 100_000)]},
        "ops": {DEV: [(0, 100_000), (200_000, 100_000), (400_000, 100_000), (500_020, 1000), (700_000, 1000)]},
        "host": _host(("engine.step", 0, 350_000, {}),
                      ("engine.decode_window.device_wait", 90_000, 120_000, {"seq": 1}),
                      ("engine.decode_window.reconcile", 290_000, 50_000, {"seq": 1}),
                      ("engine.wait_for_work", 600_000, 50_000, {})),
    }
    r = trace_steps.reduce(ev)
    # gaps over 50 us: [100k,200k] under device_wait, [300k,400k] under
    # reconcile, [501_020,700k] under nothing; the 20 ns one is no gap
    assert [(g["span"], round(g["ms"] * 1e6)) for g in sorted(r["gaps"], key=lambda g: g["start_ns"])] == [
        ("engine.decode_window.device_wait", 100_000), ("engine.decode_window.reconcile", 100_000),
        ("no span", 198_980)]
    assert abs(r["gap_seconds_by_span"]["engine.decode_window.reconcile"] - 100e-6) < 1e-12
    # idle 100k + 100k + 20 + 198_980 ns; the host waited (device_wait,
    # wait_for_work) through 100k + 50k of it
    assert abs(r["idle_host_s"] - (399_000 - 150_000) * 1e-9) < 1e-15


def test_a_program_without_spans_or_step_names_reports_nothing():
    ev = {"modules": {DEV: [("jit__decode_window_impl(123)", 0, 100)]}, "ops": {DEV: [(0, 100), (300, 100)]},
          "host": []}
    r = trace_steps.reduce(ev)
    assert r["decode"] is None and r["prefill"] is None and r["pairs"] == []
    assert r["idle_host_s"] is None and r["thread"] is None
    assert trace_steps.reduce({"modules": {}, "ops": {}, "host": []})["busy_s"] == 0.0


def test_recorded_chip_slice():
    fixture = HERE / "trace_steps_slice.json"
    doc = json.loads(fixture.read_text())
    ev = {"modules": {p: [tuple(e) for e in v] for p, v in doc["events"]["modules"].items()},
          "ops": {p: [tuple(e) for e in v] for p, v in doc["events"]["ops"].items()},
          "host": [tuple(e) for e in doc["events"]["host"]]}
    r = trace_steps.reduce(ev)
    for key, want in doc["expect"].items():
        got = r
        for part in key.split("."):
            got = got[part]
        assert abs(got - want) <= 1e-9 * max(1.0, abs(want)), key
    # 8 packed prefills, each run 125-318 ms behind its dispatch: two runs at
    # the slice's start belong to dispatches made before it
    assert len(r["pairs"]) == doc["pairs"] and "2 runs before the first span" in r["pairing"]
    assert [round(p["backlog_ms"], 6) for p in r["pairs"]] == [round(b, 6) for b in doc["backlog_ms"]]
    assert all(100 < p["backlog_ms"] < 330 and 10 < p["device_ms"] < 45 for p in r["pairs"])
    assert 0 < r["busy_s"] <= r["window_s"]
