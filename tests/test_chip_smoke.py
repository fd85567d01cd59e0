"""CPU rehearsal of chip_smoke.py at the tiny geometry (on-chip-measurement
guide section 2, rehearsal 1): every phase runs through the same code the
chip run uses — the generated HF checkpoint, the `dynamo_tpu.launch.run`
child, the plain HTTP client, the kernel-parity child — with the CPU and
Pallas interpret mode asked for HERE, through the environment the children
inherit, not through an option of the program. And: a failed phase exits
non-zero with `"ok": false`; no accelerator exits non-zero with no result."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from tools.make_hf_checkpoint import QWEN25_7B_GEOMETRY, TINY_GEOMETRY  # noqa: E402


def tiny_sizes(**overrides) -> chip_smoke.Sizes:
    # TinyLlama's head_dim (64: folded pools) at toy width, so the served
    # path takes the same folded kernels the chip run takes
    serve = dict(TINY_GEOMETRY, hidden_size=128, num_attention_heads=2, num_key_value_heads=2)
    fields = dict(
        name="tiny",
        platform="cpu",
        serve_geometry=serve,
        serve_args=["--num-pages", "192", "--max-seqs", "4", "--max-model-len", "1024"],
        long_prompt_tokens=560,
        expect_pallas=False,  # interpret mode is not a compiled kernel
        tp_geometry=dict(
            QWEN25_7B_GEOMETRY, hidden_size=512, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=512,
            max_position_embeddings=1024,
        ),
        replica_geometry=serve,
        ready_timeout_s=600.0,
    )
    fields.update(overrides)
    return chip_smoke.Sizes(**fields)


@pytest.fixture()
def smoke(tmp_path, monkeypatch, capfd):
    """chip_smoke with its scratch directory under tmp_path and children that
    run the Pallas kernels in interpret mode on the CPU."""
    monkeypatch.setattr(chip_smoke, "WORK", tmp_path / "work")
    monkeypatch.setattr(chip_smoke, "RESULTS", [])
    monkeypatch.setattr(chip_smoke, "_children", [])
    monkeypatch.setenv("DYNTPU_PALLAS", "1")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla_cache"))

    def run(sizes, chips=1):
        rc = chip_smoke.run(sizes, chips, seed=0)
        out = capfd.readouterr().out
        rows = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
        return rc, rows, out

    return run


def test_one_chip_rehearsal_runs_every_phase(smoke):
    rc, rows, out = smoke(tiny_sizes())
    phases = {r["phase"]: r for r in rows if "phase" in r}
    assert rc == 0, out
    assert [p for p in phases if not phases[p]["ok"]] == [], out
    assert set(phases) >= {"environment", "generate:tiny", "serve", "parity"}

    serve = phases["serve"]
    assert serve["device"]["platform"] == "cpu" and serve["server_exit"] is not None
    assert serve["unary"]["prefix_cache_hit_blocks"] > 0
    assert serve["long_prompt"]["prompt_tokens"] > 512
    assert serve["max_decode_batch"] > 1 and serve["dispatches"]["prefill_packed"] > 0
    assert serve["compiles_before_traffic"]["compiles"] > 0
    assert serve["xla_cache_at_ready"]["dir"].endswith("xla_cache")
    # interpret mode was asked for by this test and the log says so
    assert any("pallas:" in p and "interpret" in p for p in serve["attention_paths"]), serve
    assert "native" in phases["environment"]["radix_index"] or "python" in phases["environment"]["radix_index"]

    cases = [r for r in rows if "case" in r]
    assert len(cases) >= 19 and all(c["ok"] for c in cases), cases
    assert max(c["max_abs_err"] for c in cases) <= chip_smoke.PARITY_ATOL

    # the contract's last line: what the serving child reported it ran on
    assert rows[-1]["ok"] is True
    assert rows[-1]["device"] == {"platform": "cpu", "kind": serve["device"]["kind"], "count": serve["device"]["count"]}
    assert out.strip().splitlines()[-1].startswith('{"ok": true, "device": {"platform":')


@pytest.mark.slow
def test_four_chip_rehearsal_on_virtual_devices(smoke, monkeypatch):
    """`--chips 4` on four virtual CPU devices (guide section 2, rehearsal
    2): tp=4 against tp=1 in one child, then four workers behind the KV
    router under the SDK supervisor. No one-chip phase runs."""
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    monkeypatch.setenv("DYNTPU_TPU_CHIPS", "4")  # what the allocator would detect
    rc, rows, out = smoke(tiny_sizes(), chips=4)
    phases = {r["phase"]: r for r in rows if "phase" in r}
    assert rc == 0, out
    assert set(phases) == {"environment", "generate:tiny-qwen", "tp", "generate:tiny", "replicas"}
    tp = next(r for r in rows if r.get("case", "").startswith("tp=4 logprobs"))
    assert tp["ok"] and tp["max_abs_logprob_diff"] <= chip_smoke.TP_LOGPROB_ATOL
    rep = phases["replicas"]
    assert len({d["visible"] for d in rep["worker_devices"].values()}) == 4
    assert rep["supervisor_off_the_chip"] and len(rep["workers_that_served"]) > 1
    assert rep["requests_routed_to_cached_prefix"] > 0
    assert rows[-1]["ok"] is True and rows[-1]["device"]["count"] == 4


def test_a_failed_phase_exits_nonzero(smoke, monkeypatch):
    """Break the serve phase (a checkpoint that is not there): the script
    still runs the later phase, prints "ok": false last and exits 1. (The
    parity child is stubbed: the rehearsal above runs the real one.)"""
    monkeypatch.setattr(chip_smoke, "phase_generate", lambda *a, **k: Path("/nonexistent-ckpt"))
    later = []
    monkeypatch.setattr(
        chip_smoke, "phase_child_rows",
        lambda phase, *a, **k: (later.append(phase), chip_smoke.report(phase, True), (True, []))[-1],
    )
    rc, rows, out = smoke(tiny_sizes(ready_timeout_s=60.0))
    assert rc == 1, out
    phases = {r["phase"]: r for r in rows if "phase" in r}
    assert phases["serve"]["ok"] is False  # the server could not load a checkpoint
    assert "server exited" in phases["serve"]["error"] and phases["serve"]["server_log_tail"]
    assert later == ["parity"] and phases["parity"]["ok"] is True  # later phases still ran
    assert rows[-1]["ok"] is False and rows[-1]["device"]["platform"] == "cpu"


def test_no_accelerator_exits_nonzero_with_no_result(smoke):
    """As the driver runs it in a sandbox: FULL sizes want a TPU, JAX finds
    the CPU, nothing expensive starts and no result line is printed."""
    rc, rows, out = smoke(chip_smoke.full_sizes())
    assert rc == 2
    assert rows == [] and '"ok"' not in out
