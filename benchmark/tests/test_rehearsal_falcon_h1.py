"""The Falcon-H1 configuration's own files (`checkpoints/falcon_h1.py`,
`reference/falcon_h1.py`, the two readers PR 45 added) rehearsed on the CPU at
small size: a throwaway configuration, mix and cell laid into a temporary
`--root`, served through `launch.run` (a Mamba-2 mixer and an attention mixer
in every block, a state row and pages for the same layer, five query heads a
key head, the published multipliers), measured, traced and compared with the
plain reference. Takes some minutes. The plan's digest is pinned: names,
shapes, kinds and order ARE the weights of every checkpoint it wrote."""

import json

import pytest

import run
import test_rehearsal
from checkpoints import falcon_h1
from test_checkpoint import _plan_digest
from test_rehearsal import ROOT, _run, benchmark_files, throwaway_spec

CELL = "tiny-falcon.assist-tiny"

FULL = {k: v for k, v in json.loads(
    (ROOT / "benchmark" / "configs" / "falcon-h1-34b-d6.json").read_text()).items()
    if k not in run.OWN_KEYS}

#: the published keys at small widths, the multipliers as published
TINY = {
    **FULL,
    "name": "tiny-falcon", "source": "none: a rehearsal of benchmark/configs/falcon-h1-34b-d6.json's keys",
    "hidden_size": 256, "vocab_size": 512, "num_hidden_layers": 3, "intermediate_size": 512,
    "num_attention_heads": 10, "num_key_value_heads": 2, "head_dim": 32,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_ssm": 128, "mamba_d_state": 32,
    "mamba_n_groups": 2, "mamba_chunk_size": 16,
    "reduced": [], "assumed": [], "deployment": "a rehearsal on the CPU",
    "benchmark": {"launcher": "single", "checkpoint": "falcon_h1", "reference": "falcon_h1",
                  "platform": "cpu",
                  "server_args": ["--max-seqs", 8, "--num-pages", 512, "--max-model-len", 2048],
                  "env": {"JAX_PLATFORMS": "cpu"},
                  "logprob_atol": 0.0001,
                  "logprob_atol_why": "CPU, bfloat16 server against the float32 reference at width 256 "
                                      "under the published multipliers: the logits are 0.02 x sqrt(256) / "
                                      "128 = 0.0025 wide, a logprob moves in that scale; measured "
                                      "0.000027 over 4 probes x 8 tokens (a CPU run, PR 45)"},
}
del TINY["published"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("falcon_h1")
    throwaway_spec(tmp, "tiny-falcon", "assist-tiny")
    for sub in ("configs", "traffic", "cells"):
        (tmp / "extra" / sub).mkdir(parents=True)
    (tmp / "extra/configs/tiny-falcon.json").write_text(json.dumps(TINY))
    (tmp / "extra/traffic/assist-tiny.json").write_text(json.dumps(test_rehearsal.MIX))
    (tmp / f"extra/cells/{CELL}.json").write_text(json.dumps({"rate_rps": 2.0}))
    before = benchmark_files()
    untraced, log0 = _run(tmp, 0, CELL)
    traced, log1 = _run(tmp, 1, CELL)
    return dict(untraced=untraced, traced=traced, log=log0 + log1, edited=benchmark_files() != before)


def test_the_cell_runs_end_to_end_and_agrees_with_the_reference(served):
    res, traced, log = served["untraced"], served["traced"], served["log"]
    assert res["correct"] is True and traced["correct"] is True, log[-3000:]
    assert res["failed"] == 0 and res["attempted"] >= 8, log[-3000:]
    assert "ckpt-tiny-falcon-seed" in log
    assert '"logprobs_compared": 32' in log and '"compiles_in_window": 0.0' in log
    assert not served["edited"], "a run edited a file of the benchmark"


def test_the_new_readers_find_their_counters(served):
    got = served["traced"]["metrics"]
    assert 0.0 < got["state_slots_active_share"]["value"] <= 100.0
    # no kernel and no device line on the CPU: the device-trace readers find
    # nothing and are left out, and do not raise
    assert "ssm_update_roofline_h1" not in got and "mixers_share_of_busy" not in got
    assert "ssm_update_roofline" not in got


def test_the_state_updates_roofline_reads_the_sequences_that_decode():
    """Two decode windows of 4 steps, 40 and 80 sequences decoding: a mean
    step has 60. The kernel ran 48 calls (6 layers x 8 steps) of 1 ms each on
    a device of 1 TB/s: a call has to move 60 x (2 x 32 x 128 x 256 x 4 of
    state + (32 + 2 x 4096 + 2 x 512) x 4 of vectors) = 505.5 MB (a made-up
    device: the arithmetic is what is held, not the share)."""
    from layer_metrics import ssm_update_roofline, ssm_update_roofline_h1 as reader

    windows = [{"kind": "decode_window", "steps": 4, "tokens": 4 * n} for n in (40, 80)]
    ctx = {"config": FULL, "peaks": {"hbm_bytes_per_s": 1e12},
           "records": windows + [{"kind": "prefill_packed", "steps": 0, "tokens": 900}],
           "trace": {"ops_by_name": {"ssm_state_update": 48e-3, "fusion.1": 1.0},
                     "calls_by_name": {"ssm_state_update": 48, "fusion.1": 7}}}
    need = 60 * (2 * 32 * 128 * 256 * 4 + (32 + 2 * 32 * 128 + 2 * 2 * 256) * 4)
    assert reader.read(ctx) == pytest.approx(100.0 * need / 1e12 / 1e-3)
    # NemotronH's reader gives nothing for this configuration, this one
    # nothing for NemotronH's, for a trace without the kernel, or for none
    assert ssm_update_roofline.read(ctx) is None
    nemotron = {"mamba_num_heads": 128, "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8}
    assert reader.read(dict(ctx, config=nemotron)) is None
    assert reader.read(dict(ctx, trace={"ops_by_name": {"fusion.1": 1.0}, "calls_by_name": {"fusion.1": 7}})) is None
    assert reader.read(dict(ctx, trace=None)) is None


@pytest.mark.parametrize("cfg, pinned", [
    ({k: v for k, v in TINY.items() if k not in run.OWN_KEYS}, (54, 'a19c612178e5ebc2d3a304f58810d9c4b91606fbfce00dd677bcf9faad91b9c6')),
    (FULL, (105, '515987c4040183df39fb2861e9533d779dcd810e7eb04b313e03af484775e9ed')),
], ids=["tiny", "falcon-h1-34b-d6"])
def test_the_plan_is_pinned(cfg, pinned):
    assert _plan_digest(falcon_h1.tensor_plan(cfg)) == pinned


def test_the_full_plan_is_the_stage_the_configuration_states():
    """10.51 GB of bfloat16: 6 layers of 430.1M parameters, the whole
    vocabulary twice (embedding and an untied head)."""
    import numpy as np

    plan = falcon_h1.tensor_plan(FULL)
    count = {n: int(np.prod(s)) for n, s, _ in plan}
    assert round(sum(count.values()) / 1e6, 1) == 5254.6
    layer0 = sum(v for n, v in count.items() if n.startswith("model.layers.0."))
    assert round(layer0 / 1e6, 1) == 430.1
    assert not any(n.startswith("model.layers.6.") for n in count)
    shapes = dict((n, s) for n, s, _ in plan)
    assert shapes["model.layers.0.mamba.in_proj.weight"] == (9248, 5120)
    assert shapes["model.layers.5.mamba.conv1d.weight"] == (5120, 1, 4)
    assert shapes["model.layers.0.self_attn.k_proj.weight"] == (512, 5120)
    assert shapes["lm_head.weight"] == shapes["model.embed_tokens.weight"] == (261120, 5120)
