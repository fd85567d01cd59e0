"""The round artifact must be self-contained: the driver keeps only the tail
of bench stdout (~2000 chars), so the LAST line has to carry every section's
key number by itself (r4 post-mortem: the full-detail line was truncated and
BENCH_r04.json lost its own headline)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture()
def bench_mod():
    import bench

    saved_detail, saved_errors = dict(bench.DETAIL), dict(bench.ERRORS)
    bench.DETAIL.clear()
    bench.ERRORS.clear()
    yield bench
    bench.DETAIL.clear()
    bench.DETAIL.update(saved_detail)
    bench.ERRORS.clear()
    bench.ERRORS.update(saved_errors)


def _fill_representative(bench):
    """Populate DETAIL with r4-scale values (worst-case field widths)."""
    bench.DETAIL["headline_bs%d_ps%d" % bench.HEADLINE] = {
        "tok_s": 6354.12, "total_output_tokens": 8192, "elapsed_s": 1.289,
        "ttft_p50_ms": 171.4, "rounds": [6102.44, 6354.12, 6233.91],
    }
    bench.DETAIL["continuity_bs%d_ps%d" % bench.CONTINUITY] = {"tok_s": 1402.77}
    bench.DETAIL["ref_workload_isl3k_osl150"] = {
        "tok_s": 731.55, "ttft_p50_ms": 1893.2,
        "stage_breakdown": {
            "queue_wait_s": 12.3456, "queue_wait_n": 48, "prefill_s": 31.9071,
            "prefill_calls": 96, "prefill_rows": 147456,
            "decode_dispatch_s": 55.1203, "decode_windows": 240,
            "decode_steps": 7680, "reconcile_wait_s": 8.0042,
            "reconcile_waits": 120, "ttft_s": 90.8, "ttft_n": 48,
        },
    }
    bench.DETAIL["http_serving"] = {
        "tok_s": 3264.18, "engine_loop_tok_s": 3401.02,
        "http_over_engine_ratio": 0.96, "ttft_p50_ms": 287.3,
    }
    bench.DETAIL["mla_decode"] = {"tok_s": 4658.33}
    bench.DETAIL["moe_decode"] = {"tok_s": 5425.87}
    bench.DETAIL["parity_disagg"] = {
        "ratio_measured_1chip": 0.941, "ratio_projected": 1.387,
    }
    bench.DETAIL["parity_kv_routing"] = {
        "ttft_ratio": 2.79, "ttft_ratio_derived": 16.14,
    }
    bench.DETAIL["parity_host_offload"] = {
        "projection": {"ttft_ratio_projected": 8.82, "restore_bw_source": "measured"},
    }
    bench.DETAIL["kv_tiers"] = {
        "resume_ttft_tiered_ms": 123.4, "resume_ttft_recompute_ms": 534.2,
        "resume_ttft_ratio": 0.231, "restore_parity": 1.0,
        "resume_tokens_restored_tiered": 1344,
        "disk": {"spills": 72, "restores": 21, "restore_hits": 3,
                 "restore_fallbacks": 0, "restore_tokens": 1344,
                 "io_errors": 0, "blocks_resident": 72,
                 "bytes_resident": 452984832, "budget_bytes": 905969664},
        "cap_under_churn": {"budget_bytes": 1048576,
                            "max_resident_bytes": 1048400, "drops": 12},
    }
    bench.DETAIL["long_context"] = {
        "16k": {"ttft_ms": 13956.5, "decode_tok_s": 123.4, "kv_pages_peak": 1088},
        "64k": {"ttft_ms": 57321.8, "decode_tok_s": 98.7, "kv_pages_peak": 4160},
        "parity_64k_ladder_vs_dense": True,
        "short_ttft_ratio_ladder_over_dense": 0.169,
    }
    bench.DETAIL["spec_draft"] = {
        "tok_s_draft": 4123.45, "tok_s_ngram": 3356.71, "tok_s_classic": 3310.02,
        "speedup_draft_over_classic": 1.246, "acceptance_rate_draft": 0.9873,
        "acceptance_rate_ngram": 0.0512, "greedy_parity_draft": 1.0,
    }
    bench.DETAIL["migration"] = {
        "parity": 1.0, "pause_ms_p99": 1234.5, "kill_pause_ms_p99": 4567.8,
        "goodput_delta": 0.0417, "tokens_salvaged": 4096,
    }
    bench.DETAIL["qos"] = {
        "tenant_b_itl_ratio": 0.0052, "shed_fraction": 0.8333,
        "critical_goodput": 0.9873, "baseline_goodput": 1.0,
        "tenant_b_on": {"itl_p99_ms": 3.432}, "tenant_b_off": {"itl_p99_ms": 654.4},
    }
    bench.DETAIL["platform"] = "tpu"
    bench.DETAIL["events"] = {
        "cpu_smoke": False, "decode_step_wall_ms": 5.0521, "emit_us": 8.271,
        "emits_per_request": 7, "emit_overhead_frac": 0.002803,
        "journal_events": 4096, "reconstruct_ms": 0.2905,
    }
    bench.DETAIL["step_anatomy"] = {
        "cpu_smoke": False,
        "decode": {"host_frac": 0.3124, "roofline_frac": 0.6981,
                   "dispatch_gap_ms_p50": 231.456,
                   "dispatches": {"decode_window": 240}},
        "spec_draft": {"host_frac": 0.4123},
        "multi_lora": {"host_frac": 0.3852},
    }
    bench.DETAIL["metering"] = {
        "cpu_smoke": False, "decode_step_wall_ms": 8.456,
        "on_phase_us": 1.395, "kv_acquire_us": 2.084,
        "kv_release_us": 1.586, "overhead_frac": 0.000423,
        "device_rel_err": 1.3e-09,
        "kv_rel_err": {"hbm": 2.7e-09, "host": 0.0, "disk": 0.0},
        "device_s_total": 123.456,
        "tenants_metered": ["acme", "umbrella"],
    }
    bench.DETAIL["prefill_anatomy"] = {
        "greedy_parity": "exact", "stall_delta": 7,
        "depth1": {"prefill_stalls": 7, "prefill_calls": 8,
                   "reconcile_waits": 248, "prefill_fixed_ms": 10.234,
                   "prefill_host_frac": 0.9741, "prefill_roofline_frac": 0.6312,
                   "ttft_p50_ms": 1509.7, "wall_s": 41.5214,
                   "output_tokens": 1200},
        "depth2": {"prefill_stalls": 0, "prefill_calls": 8,
                   "reconcile_waits": 241, "prefill_fixed_ms": 9.871,
                   "prefill_host_frac": 0.9702, "prefill_roofline_frac": 0.6518,
                   "ttft_p50_ms": 1287.3, "wall_s": 38.1042,
                   "output_tokens": 1200},
    }
    bench.DETAIL["replay"] = {
        "cpu_smoke": False,
        "scenarios": {
            sc: {"goodput": 0.9873, "ttft_p99_ms": 3965.343,
                 "itl_p99_ms": 552.341, "tok_s": 4123.45, "wall_s": 12.3}
            for sc in ("bursty_chat", "int8_kv", "long_context_sessions",
                       "lora_churn", "spec_draft", "fleet_prefix", "mm_vl")
        },
    }


def test_summary_line_fits_truncation_budget(bench_mod, tmp_path, monkeypatch):
    monkeypatch.setenv("DYNTPU_BENCH_DETAIL", str(tmp_path / "detail.json"))
    _fill_representative(bench_mod)
    bench_mod.ERRORS["parity_disagg"] = {
        "error": "TimeoutError: section exceeded its 2400s budget on the chip",
        "elapsed_s": 2400.1, "traceback_tail": "x" * 1500,
    }
    result = bench_mod._result()
    # what __main__ actually prints: compact separators (the driver keeps
    # only the last 2000 chars of stdout — measured at exactly 2000 in every
    # BENCH_r02..r05 capture — and ", " formatting alone costs ~200 chars)
    line = json.dumps(result, separators=(",", ":"))
    assert len(line) < 1950, f"artifact line too long: {len(line)}"
    s = result["summary"]
    assert s["headline_tok_s"] == 6354.12
    assert s["platform"] == "tpu"
    # replay spine: one aliased array per scenario, columns per replay_cols
    assert s["replay_cols"] == "goodput,ttft_p99_ms,itl_p99_ms,tok_s"
    assert s["replay"]["bursty"] == [0.9873, 3965, 552, 4123]
    assert set(s["replay"]) == {
        "bursty", "int8", "lctx", "lora", "spec", "fleet", "mm",
    }
    assert result["value"] == 6354.12
    assert s["ref_workload_isl3k_osl150"]["tok_s"] == 731.55
    # the per-stage seconds moved to bench_detail.json in r19: the flat-TTFT
    # attribution now rides the gated prefill_anatomy keys instead
    assert "stages" not in s["ref_workload_isl3k_osl150"]
    # prefill anatomy acceptance keys (pipelined arm only; the depth-1
    # baseline arm and stall deltas stay in bench_detail.json — parity and
    # strictly-fewer-stalls are asserted inside the section itself)
    assert s["prefill_anatomy"] == {
        "fixed_ms": 9.871, "dispatches": 8, "ttft_p50_ms": 1287.3,
    }
    assert s["http_serving"]["http_over_engine_ratio"] == 0.96
    # step-anatomy acceptance keys ride the compact line (decode arm only;
    # the dispatch cadence and spec/LoRA arm breakdowns stay in
    # bench_detail.json)
    assert s["step_anatomy"] == {
        "host_frac": 0.3124, "roofline_frac": 0.6981,
    }
    # cost attribution: worst residual across both planes + hot-path price
    assert s["metering"] == {"err": 2.7e-09, "frac": 0.000423}
    assert s["mla_decode_tok_s"] == 4658.33
    assert s["moe_decode_tok_s"] == 5425.87
    # live-migration acceptance keys ride the compact line (salvage counters
    # and the kill-arm pause stay in bench_detail.json)
    assert s["migration"] == {
        "parity": 1.0, "pause_ms_p99": 1234.5, "goodput_delta": 0.0417,
    }
    # multi-tenant QoS acceptance keys ride the compact line (per-tenant
    # breakdowns and budget values stay in bench_detail.json)
    assert s["qos"] == {
        "tenant_b_itl_ratio": 0.0052, "shed_fraction": 0.8333,
        "critical_goodput": 0.9873,
    }
    # flight recorder: short keys on the line (full-named report in
    # bench_detail.json)
    assert s["events"] == {"emit_frac": 0.002803, "rec_ms": 0.2905}
    # ratio_derived moved to bench_detail.json (truncation budget)
    assert s["parity_kv_routing"] == {"ratio_measured": 2.79}
    assert s["parity_host_offload"]["ratio_projected"] == 8.82
    # third KV tier acceptance keys ride the compact line (restore counters
    # and the cap-under-churn proof stay in bench_detail.json)
    assert s["kv_tiers"] == {
        "resume_ttft_ratio": 0.231, "restore_parity": 1.0,
        "disk_resident_bytes": 452984832,
    }
    # errors land compactly (no tracebacks) in the summary itself
    assert "TimeoutError" in s["errors"]["parity_disagg"]
    assert "traceback" not in json.dumps(s)


def test_detail_lands_in_file_not_stdout(bench_mod, tmp_path, monkeypatch):
    monkeypatch.setenv("DYNTPU_BENCH_DETAIL", str(tmp_path / "detail.json"))
    _fill_representative(bench_mod)
    result = bench_mod._result()
    line = json.dumps(result)
    # full detail must NOT ride stdout (it is what got truncated in r4)
    assert "total_output_tokens" not in line
    path = result["detail_file"]
    assert path and os.path.exists(path)
    with open(path) as f:
        detail = json.load(f)
    assert detail["detail"]["headline_bs%d_ps%d" % bench_mod.HEADLINE][
        "total_output_tokens"] == 8192


def test_empty_sections_still_produce_parseable_line(bench_mod, tmp_path, monkeypatch):
    """A fatal crash before any section lands must still emit valid compact
    JSON with an errors map (the driver's `parsed` must never be null)."""
    monkeypatch.setenv("DYNTPU_BENCH_DETAIL", str(tmp_path / "detail.json"))
    result = bench_mod._result(extra_errors={"__run__": {"error": "boom"}})
    line = json.dumps(result)
    parsed = json.loads(line)
    assert parsed["value"] == 0.0
    assert parsed["summary"]["errors"]["__run__"] == "boom"
    assert len(line) < 1800


# ---------------- exit codes: no fallback that hides a failure ----------------


def _run_main(bench_mod, monkeypatch, tmp_path, argv, run=None):
    """bench.main with the cache helper stubbed (it would re-point this test
    process's JAX cache) and, optionally, a stand-in for run()."""
    import dynamo_tpu.utils.xla_cache as xla_cache

    monkeypatch.setenv("DYNTPU_BENCH_DETAIL", str(tmp_path / "detail.json"))
    monkeypatch.setattr(xla_cache, "enable_compilation_cache", lambda: None)
    if run is not None:
        monkeypatch.setattr(bench_mod, "run", run)
    return bench_mod.main(argv)


def test_failed_section_gives_nonzero_exit(bench_mod, monkeypatch, tmp_path, capsys):
    """A section that raises used to leave exit code 0 whenever a headline
    value existed. Now the finished sections are still printed and the run
    exits non-zero."""

    async def run(cpu_smoke=False):
        async def good():
            return {"tok_s": 123.0}

        async def boom():
            raise RuntimeError("kernel refused by the compiler")

        await bench_mod._section("headline_bs%d_ps%d" % bench_mod.HEADLINE, good, 5)
        await bench_mod._section("moe_decode", boom, 5)
        return bench_mod._result()

    assert _run_main(bench_mod, monkeypatch, tmp_path, ["--cpu-smoke"], run) == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] == 123.0  # the section that finished is still reported
    assert "kernel refused" in line["summary"]["errors"]["moe_decode"]

    # and with every section passing the same run exits 0
    bench_mod.DETAIL.clear()
    bench_mod.ERRORS.clear()

    async def run_ok(cpu_smoke=False):
        async def good():
            return {"tok_s": 123.0}

        await bench_mod._section("headline_bs%d_ps%d" % bench_mod.HEADLINE, good, 5)
        return bench_mod._result()

    assert _run_main(bench_mod, monkeypatch, tmp_path, ["--cpu-smoke"], run_ok) == 0


def test_bench_fails_without_a_tpu_unless_cpu_smoke_is_named(bench_mod, monkeypatch, tmp_path, capsys):
    """No TPU: the bench fails instead of measuring the CPU under device-metric
    names. (`--cpu-smoke` is the way in by name; the probe that switched the
    kernels off for the whole run is gone.)"""
    assert not hasattr(bench_mod, "_probe_pallas")
    assert _run_main(bench_mod, monkeypatch, tmp_path, []) == 1
    captured = capsys.readouterr()
    assert captured.out.strip() == ""  # no result line at all
    assert "measures a TPU" in captured.err and "--cpu-smoke" in captured.err
    assert bench_mod.DETAIL["device"]["platform"] == "cpu"
    assert _run_main(bench_mod, monkeypatch, tmp_path, ["--bogus"]) == 2
