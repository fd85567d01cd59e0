"""Every name in BENCHMARK.json resolves to a file, and the file keeps the
contract's limits that can be checked without a run."""

import json
import re
from pathlib import Path

import run

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_resolves():
    files = run.Files(ROOT)
    spec = files.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    configs = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        conf = files.config_file(c["name"])
        assert (ROOT / c["file"]).is_relative_to(ROOT / spec["paths"][0])
        assert conf["reduced"] == c["reduced"]
        b = conf["benchmark"]
        assert files.find("launchers", f"{b['launcher']}.py")
        assert files.find("reference", f"{b['reference']}.py")
        assert hasattr(files.module("checkpoints", b.get("checkpoint", "dense")), "tensor_plan")
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        mix = files.data("traffic", w["traffic"])
        assert files.find("generators", f"{mix['generator']}.py")
        files.data("cells", w["name"])
    cells = {w["name"] for w in spec["workloads"]}
    by_name = {m["name"]: m for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        # what a metric moves is reported in every cell where the metric is
        assert set(m.get("workloads", cells)) <= set(by_name[m["moves"]].get("workloads", cells))
        assert hasattr(files.module("layer_metrics", run.reader_of(m["name"])), "read")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert {c["name"] for c in spec["configs"]} == {w["config"] for w in spec["workloads"]}
    assert len(json.dumps(spec)) < 64 * 1024
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_rate_is_four_fifths_of_the_knee():
    files = run.Files(ROOT)
    for w in files.spec["workloads"]:
        cell = files.data("cells", w["name"])
        if "knee_rps" in cell:  # a cell above the knee says so in `from` and names none
            assert abs(cell["rate_rps"] - 0.8 * cell["knee_rps"]) < 0.01 * cell["knee_rps"]
