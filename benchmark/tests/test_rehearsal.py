"""The CPU rehearsal of one cell end to end at tiny size, as a throwaway
configuration, mix and cell in a temporary directory: a later PR adds files
and entries and edits no file that is there. Takes a few minutes."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "name": "tiny", "source": "none: a rehearsal", "architectures": ["Qwen2ForCausalLM"],
    "model_type": "qwen2", "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0, "tie_word_embeddings": True, "max_position_embeddings": 2048,
    "reduced": [], "assumed": [], "deployment": "a rehearsal on the CPU",
    "benchmark": {"launcher": "single", "reference": "qwen2", "platform": "cpu",
                  "server_args": ["--max-seqs", 8, "--num-pages", 512, "--max-model-len", 2048],
                  "logprob_atol": 0.25, "env": {"JAX_PLATFORMS": "cpu"}},
}
MIX = {"generator": "open_loop", "arrival": "poisson",
       "prompt": {"median": 40, "sigma": 0.6, "min": 8, "max": 200},
       "output": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
       "lead_in_s": 2.0, "drain_s": 20.0,
       "warm": {"depths": [0], "tails": [20, 100, 200], "bursts": [1, 2, 4], "repeats": 3, "tokens": 9}}


def throwaway_spec(tmp: Path, config: str, traffic: str) -> None:
    """`BENCHMARK.json` of a temporary root: the repo's metrics, one
    configuration and one cell of it, everything else under `extra/`."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": config, "source": "none", "file": f"extra/configs/{config}.json",
                        "reduced": [], "why": "rehearsal"}]
    spec["workloads"] = [{"name": f"{config}.{traffic}", "config": config, "traffic": traffic,
                          "chips": 1, "why": "rehearsal"}]
    spec["paths"] = ["extra"]
    # every metric in the one throwaway cell, each quantity once
    spec["per_layer"] = [m for m in spec["per_layer"] if "." not in m["name"]]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))


def _tree(tmp: Path) -> None:
    throwaway_spec(tmp, "tiny", "chat-tiny")
    for sub in ("configs", "traffic", "cells"):
        (tmp / "extra" / sub).mkdir(parents=True)
    (tmp / "extra/configs/tiny.json").write_text(json.dumps(TINY))
    (tmp / "extra/traffic/chat-tiny.json").write_text(json.dumps(MIX))
    (tmp / "extra/cells/tiny.chat-tiny.json").write_text(json.dumps({"rate_rps": 4.0}))


def _run(tmp: Path, trace: int, workload: str = "tiny.chat-tiny") -> tuple:
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--root", str(tmp),
         "--workload", workload, "--seed", str(2**31 + 11), "--seconds", "6",
         "--trace", str(trace)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def benchmark_files() -> dict:
    return {p: p.stat().st_mtime_ns for p in (ROOT / "benchmark").rglob("*")
            if p.is_file() and ".cache" not in p.parts and "__pycache__" not in p.parts}


def test_throwaway_cell_runs_end_to_end(tmp_path):
    _tree(tmp_path)
    before = benchmark_files()
    res, log = _run(tmp_path, 0)
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 15, log[-3000:]
    assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p95_ms", "output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"

    traced, log = _run(tmp_path, 1)
    assert traced["correct"] is True and "breakdown" in traced, log[-3000:]
    assert traced["metrics"]["compiles_in_window"]["value"] == 0
    assert {"client_lag_p95_ms", "ttft_p95_ms", "queue_wait_mean_ms", "decode_batch_mean", "decode_step_ms",
            "host_share", "kv_pages_active_share"} <= set(traced["metrics"])
    # the profiler's side thread ran in the server and the reduction read its file
    assert {"busy_s", "window_s"} <= set(traced["device"])
    # the second run found the first's reference result and checkpoint
    assert '"reference_kept": true' in log and '"made": false' in log
    assert benchmark_files() == before, "a run edited a file of the benchmark"


def test_no_result_without_the_platform_the_configuration_asks_for(tmp_path):
    """A configuration that asks for a TPU, on a machine with none: non-zero
    exit and no result line."""
    _tree(tmp_path)
    conf = dict(TINY, benchmark=dict(TINY["benchmark"], platform="tpu"))
    (tmp_path / "extra/configs/tiny.json").write_text(json.dumps(conf))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--root", str(tmp_path),
         "--workload", "tiny.chat-tiny", "--seed", "3", "--seconds", "2", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=900)
    assert proc.returncode != 0
    assert not any(ln.startswith('{"correct"') for ln in proc.stdout.splitlines())
    assert "not 'tpu'" in proc.stderr
