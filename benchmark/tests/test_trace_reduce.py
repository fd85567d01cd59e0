"""The reduction from device operations to busy time, idle share, time by
operation and gaps: on intervals small enough to check by hand, and on a
recorded slice of a chip trace kept beside this file."""

import json
from pathlib import Path

import trace_reduce

HERE = Path(__file__).resolve().parent


def test_union_and_gaps_by_hand():
    ev = {"/device:TPU:0": [
        ("fusion.1", 0, 100), ("while.2", 200, 400), ("fusion.3", 250, 100),
        ("kernel_a", 400, 150), ("fusion.9", 900, 100)]}
    r = trace_reduce.reduce_events(ev)
    # busy: [0,100] + [200,600] + [900,1000] = 600 ns of a 1000 ns window
    assert abs(r["busy_s"] - 600e-9) < 1e-15 and abs(r["window_s"] - 1000e-9) < 1e-15
    # while.2 contains fusion.3 and kernel_a: only leaves are counted by name,
    # and a gap is named by the operation that ended last before it
    assert "while.2" not in r["ops_by_name"]
    assert abs(r["ops_by_name"]["kernel_a"] - 150e-9) < 1e-15
    assert dict(map(tuple, r["device_ops"]))["fusion"] == 300e-9
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert abs(gaps["fusion->while"] - 100e-9) < 1e-15 and abs(gaps["while->fusion"] - 300e-9) < 1e-15


def test_two_chips_are_averaged():
    ev = {"/device:TPU:0": [("a", 0, 100)], "/device:TPU:1": [("a", 0, 300)]}
    r = trace_reduce.reduce_events(ev)
    assert abs(r["busy_s"] - 200e-9) < 1e-15 and r["planes"] == 2
    assert trace_reduce.reduce_events({})["busy_s"] == 0.0


def test_recorded_chip_slice():
    fixture = HERE / "trace_slice.json"
    doc = json.loads(fixture.read_text())
    r = trace_reduce.reduce_events({k: [tuple(e) for e in v] for k, v in doc["events"].items()})
    for key, want in doc["expect"].items():
        assert abs(r[key] - want) <= 1e-9 * max(1.0, abs(want)), key
    assert 0 < r["busy_s"] <= r["window_s"]
