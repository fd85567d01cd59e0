"""The Cohere2-MoE configuration's own files (`checkpoints/cohere2_moe.py`,
`reference/cohere2_moe.py`, the readers PR 37 added) rehearsed on the CPU at
small size: a throwaway configuration, a `sessions` mix and a cell laid into a
temporary `--root`, served through `launch.run` (window layers beside a full
one under a page table each, half of 8 experts held), measured, traced and
compared with the plain reference. Contexts pass the window of 32 many times
over, sessions extend prompts the server has seen, and the pages behind the
window go back while they run. Takes some minutes. The plan's digest is
pinned: names, shapes, kinds and order ARE the weights of every checkpoint it
wrote."""

import json

import numpy as np
import pytest

import run
from checkpoints import cohere2_moe
from test_checkpoint import _plan_digest
from test_rehearsal import ROOT, _run, benchmark_files, throwaway_spec

CELL = "tiny-command-a.doc-tiny"

TINY = {
    "name": "tiny-command-a", "source": "none: a rehearsal of benchmark/configs/command-a-plus-ep8.json's keys",
    "architectures": ["Cohere2MoeForCausalLM"], "model_type": "cohere2_moe", "torch_dtype": "bfloat16",
    "hidden_size": 256, "vocab_size": 512, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"],
    "sliding_window": 32, "rope_theta": 50000, "position_embedding_type": "rope_gptj",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_experts": 4, "moe_routed_over": 8, "moe_expert_offset": 0, "num_experts_per_tok": 3,
    "num_shared_experts": 2, "intermediate_size": 128, "layer_norm_eps": 1e-5, "rms_norm_eps": None,
    "logit_scale": 1, "tie_word_embeddings": True, "use_parallel_block": True,
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "shared_expert_combination_strategy": "average", "first_k_dense_replace": 0,
    "reduced": [], "assumed": [], "deployment": "a rehearsal on the CPU",
    "benchmark": {"launcher": "single", "checkpoint": "cohere2_moe", "reference": "cohere2_moe",
                  "platform": "cpu",
                  "server_args": ["--max-seqs", 8, "--num-pages", 2048, "--max-model-len", 2048],
                  "env": {"JAX_PLATFORMS": "cpu"},
                  "logprob_atol": 0.05,
                  "logprob_atol_why": "CPU, bfloat16 server against the float32 reference at width 256: "
                                      "measured 0.01 at most over 4 probes x 8 tokens (a CPU run, PR 37)"},
}
MIX = {"generator": "sessions", "sessions": 4, "system_prompts": 65536, "system_len": 96, "turns": 4,
       "tail": {"min": 20, "max": 60}, "output": {"min": 8, "max": 24}, "think_s": 0.1,
       "lead_in_s": 2.0, "drain_s": 20.0, "order_seed": 37,
       "warm": {"depths": [0, 96], "tails": [20, 100, 200], "bursts": [1, 2, 4], "repeats": 2, "tokens": 9}}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cohere2_moe")
    throwaway_spec(tmp, "tiny-command-a", "doc-tiny")
    for sub in ("configs", "traffic", "cells"):
        (tmp / "extra" / sub).mkdir(parents=True)
    (tmp / "extra/configs/tiny-command-a.json").write_text(json.dumps(TINY))
    (tmp / "extra/traffic/doc-tiny.json").write_text(json.dumps(MIX))
    (tmp / f"extra/cells/{CELL}.json").write_text(json.dumps({"sessions": 4}))
    before = benchmark_files()
    untraced, log0 = _run(tmp, 0, CELL)
    traced, log1 = _run(tmp, 1, CELL)
    return dict(untraced=untraced, traced=traced, log=log0 + log1, edited=benchmark_files() != before)


def test_the_cell_runs_end_to_end_and_agrees_with_the_reference(served):
    res, traced, log = served["untraced"], served["traced"], served["log"]
    assert res["correct"] is True and traced["correct"] is True, log[-3000:]
    assert res["failed"] == 0 and res["attempted"] >= 8, log[-3000:]
    assert "ckpt-tiny-command-a-seed" in log
    assert '"logprobs_compared": 32' in log and '"compiles_in_window": 0.0' in log
    assert not served["edited"], "a run edited a file of the benchmark"


def test_the_new_readers_find_their_counters(served):
    got = served["traced"]["metrics"]
    assert got["kv_window_pages_released"]["value"] > 0
    assert 0.0 < got["kv_window_pages_active_share"]["value"] < 100.0
    # half the experts are held and 3 of 8 chosen: 1.5 assignments a token, over 4 held
    assert 0.0 < got["moe_rows_per_expert"]["value"]
    assert got["prefix_hit_share"]["value"] > 30.0
    assert got["preemptions"]["value"] == 0
    # no kernel runs on the CPU: the device-trace readers find nothing and are left out
    assert "attn_window_roofline" not in got and "attn_full_roofline" not in got
    # a NemotronH reader finds nothing in this configuration
    assert "moe_tokens_per_expert" not in got


FULL = {k: v for k, v in json.loads(
    (ROOT / "benchmark" / "configs" / "command-a-plus-ep8.json").read_text()).items()
    if k not in run.OWN_KEYS}


@pytest.mark.parametrize("cfg, pinned", [
    ({k: v for k, v in TINY.items() if k not in run.OWN_KEYS},
     (98, "4e6a3eea0311c718a54ee01198df371bbe6ea91bce569e0ab8ce1d693b386dcc")),
    (FULL, (266, "84d03b7348bab1579a5e8841d4d5f4a96b980080fe849adc6fdb4608ab9eaeed")),
], ids=["tiny", "command-a-plus-ep8"])
def test_the_plan_is_pinned(cfg, pinned):
    assert _plan_digest(cohere2_moe.tensor_plan(cfg)) == pinned


def test_the_full_plan_is_the_share_the_configuration_states():
    """9.47 GB of bfloat16: 16 of 128 experts a layer, 32768 of 262144 ids, a
    tied embedding written once."""
    plan = cohere2_moe.tensor_plan(FULL)
    size = {n: 2 * int(np.prod(s)) for n, s, _ in plan}
    assert 9.44e9 < sum(size.values()) < 9.50e9
    assert sum(1 for n in size if ".mlp.experts.15.up_proj" in n) == 4
    assert not any(".mlp.experts.16." in n for n in size) and not any("lm_head" in n for n in size)
    assert sum(1 for n in size if ".mlp.shared_experts.3.down_proj" in n) == 4
    shapes = dict((n, s) for n, s, _ in plan)
    assert shapes["model.layers.1.mlp.gate.weight"] == (128, 4096)
    assert shapes["model.layers.3.self_attn.q_proj.weight"] == (16384, 4096)
