"""The NemotronH configuration's own files (`checkpoints/nemotron_h.py`,
`reference/nemotron_h.py`, the four readers PR 29 added) rehearsed on the CPU
at small size: a throwaway configuration, mix and cell laid into a temporary
`--root`, served through `launch.run` (Mamba blocks with the per-slot state
cache, half of 8 experts held, a NoPE attention block), measured, traced and
compared with the plain reference. Takes some minutes. The plan's digest is
pinned: names, shapes, kinds and order ARE the weights of every checkpoint it
wrote."""

import json

import pytest

import run
import test_rehearsal
from checkpoints import nemotron_h
from test_checkpoint import _plan_digest
from test_rehearsal import ROOT, _run, benchmark_files, throwaway_spec

CELL = "tiny-nemotron.reason-tiny"

TINY = {
    "name": "tiny-nemotron", "source": "none: a rehearsal of benchmark/configs/nemotron3-super-ep4.json's keys",
    "architectures": ["NemotronHForCausalLM"], "model_type": "nemotron_h", "torch_dtype": "bfloat16",
    "hidden_size": 256, "vocab_size": 512, "num_hidden_layers": 5, "hybrid_override_pattern": "ME*EM",
    "layer_norm_epsilon": 1e-5,
    "mamba_num_heads": 8, "mamba_head_dim": 32, "ssm_state_size": 32, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 32, "use_conv_bias": True,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "n_routed_experts": 4, "moe_routed_over": 8, "moe_expert_offset": 0,
    "num_experts_per_tok": 3, "moe_latent_size": 64, "moe_intermediate_size": 128,
    "moe_shared_expert_intermediate_size": 256, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
    "mlp_hidden_act": "relu2",
    "reduced": [], "assumed": [], "deployment": "a rehearsal on the CPU",
    "benchmark": {"launcher": "single", "checkpoint": "nemotron_h", "reference": "nemotron_h",
                  "platform": "cpu",
                  "server_args": ["--max-seqs", 8, "--num-pages", 512, "--max-model-len", 2048],
                  "env": {"JAX_PLATFORMS": "cpu"},
                  "logprob_atol": 0.05,
                  "logprob_atol_why": "CPU, bfloat16 server against the float32 reference at width 256: "
                                      "measured 0.006 at most over 4 probes x 8 tokens (a CPU run, PR 29)"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("nemotron_h")
    throwaway_spec(tmp, "tiny-nemotron", "reason-tiny")
    for sub in ("configs", "traffic", "cells"):
        (tmp / "extra" / sub).mkdir(parents=True)
    (tmp / "extra/configs/tiny-nemotron.json").write_text(json.dumps(TINY))
    (tmp / "extra/traffic/reason-tiny.json").write_text(json.dumps(test_rehearsal.MIX))
    (tmp / f"extra/cells/{CELL}.json").write_text(json.dumps({"rate_rps": 2.0}))
    before = benchmark_files()
    untraced, log0 = _run(tmp, 0, CELL)
    traced, log1 = _run(tmp, 1, CELL)
    return dict(untraced=untraced, traced=traced, log=log0 + log1, edited=benchmark_files() != before)


def test_the_cell_runs_end_to_end_and_agrees_with_the_reference(served):
    res, traced, log = served["untraced"], served["traced"], served["log"]
    assert res["correct"] is True and traced["correct"] is True, log[-3000:]
    assert res["failed"] == 0 and res["attempted"] >= 8, log[-3000:]
    assert "ckpt-tiny-nemotron-seed" in log
    assert '"logprobs_compared": 32' in log and '"compiles_in_window": 0.0' in log
    assert not served["edited"], "a run edited a file of the benchmark"


def test_the_new_readers_find_their_counters(served):
    got = served["traced"]["metrics"]
    # half the experts are held and 3 of 8 chosen: 1.5 assignments a token, over 4 held
    assert 0.0 < got["moe_tokens_per_expert"]["value"]
    assert 0.0 < got["state_slots_active_share"]["value"] <= 100.0
    # no kernel runs on the CPU: the device-trace readers find nothing and are left out
    assert "ssm_update_roofline" not in got


FULL = {k: v for k, v in json.loads(
    (ROOT / "benchmark" / "configs" / "nemotron3-super-ep4.json").read_text()).items()
    if k not in run.OWN_KEYS}


@pytest.mark.parametrize("cfg, pinned", [
    ({k: v for k, v in TINY.items() if k not in run.OWN_KEYS},
     (56, "3168fef36b5adeac96a1adffb002a6792f38d2940ad9f51eefbe2b07b09060c4")),
    (FULL, (1368, "7668ff4b6a79bdc3cbe0e11e81be18e819320facde03a11b458982277509af41")),
], ids=["tiny", "nemotron3-super-ep4"])
def test_the_plan_is_pinned(cfg, pinned):
    assert _plan_digest(nemotron_h.tensor_plan(cfg)) == pinned


def test_the_full_plan_is_the_share_the_configuration_states():
    """9.30 GB of bfloat16: 128 of 512 experts a block, 32768 of 131072 ids."""
    plan = nemotron_h.tensor_plan(FULL)
    size = {n: 2 * int(__import__("numpy").prod(s)) for n, s, _ in plan}
    assert 9.25e9 < sum(size.values()) < 9.35e9
    assert sum(1 for n in size if ".experts.127.up_proj" in n) == 5
    assert not any(".experts.128." in n for n in size)
    assert dict((n, s) for n, s, _ in plan)["backbone.layers.1.mixer.gate.weight"] == (512, 4096)
