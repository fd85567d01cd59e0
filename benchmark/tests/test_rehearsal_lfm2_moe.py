"""The LFM2-MoE configuration's own files (`checkpoints/lfm2_moe.py`,
`reference/lfm2_moe.py`, the three readers PR 42 added) rehearsed on the CPU
at small size: a throwaway configuration, mix and cell laid into a temporary
`--root`, served through `launch.run` (conv blocks with the per-slot window,
attention on folded pools with a norm per head, a dense FFN and expert layers
with every expert held), measured, traced and compared with the plain
reference. Takes some minutes. The plan's digest is pinned: names, shapes,
kinds and order ARE the weights of every checkpoint it wrote."""

import json

import pytest

import run
import test_rehearsal
from checkpoints import lfm2_moe
from test_checkpoint import _plan_digest
from test_rehearsal import ROOT, _run, benchmark_files, throwaway_spec

CELL = "tiny-lfm2.rag-tiny"

TINY = {
    "name": "tiny-lfm2", "source": "none: a rehearsal of benchmark/configs/lfm2-8b-a1b-d16.json's keys",
    "architectures": ["Lfm2MoeForCausalLM"], "model_type": "lfm2_moe", "torch_dtype": "bfloat16",
    "hidden_size": 256, "vocab_size": 512, "num_hidden_layers": 5,
    "layer_types": ["conv", "conv", "full_attention", "conv", "full_attention"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "rope_theta": 1000000,
    "num_dense_layers": 2, "intermediate_size": 512,
    "num_experts": 8, "num_experts_per_tok": 3, "moe_intermediate_size": 128,
    "norm_topk_prob": True, "use_expert_bias": True, "routed_scaling_factor": 1,
    "tie_word_embeddings": True,
    "reduced": [], "assumed": [], "deployment": "a rehearsal on the CPU",
    "benchmark": {"launcher": "single", "checkpoint": "lfm2_moe", "reference": "lfm2_moe",
                  "platform": "cpu",
                  "server_args": ["--max-seqs", 8, "--num-pages", 512, "--max-model-len", 2048],
                  "env": {"JAX_PLATFORMS": "cpu"},
                  "logprob_atol": 0.2,
                  "logprob_atol_why": "CPU, bfloat16 server against the float32 reference at width 256: "
                                      "measured 0.056 over 4 probes x 8 tokens (a CPU run, PR 42). The "
                                      "model in bfloat16 against itself in float32 on 128 positions reads "
                                      "up to 0.036 on the rows whose experts agree and 0.127 on the 6 rows "
                                      "where a near-tie of the top-3 of 8 flips (a third of the routed sum)"},
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lfm2_moe")
    throwaway_spec(tmp, "tiny-lfm2", "rag-tiny")
    for sub in ("configs", "traffic", "cells"):
        (tmp / "extra" / sub).mkdir(parents=True)
    (tmp / "extra/configs/tiny-lfm2.json").write_text(json.dumps(TINY))
    (tmp / "extra/traffic/rag-tiny.json").write_text(json.dumps(test_rehearsal.MIX))
    (tmp / f"extra/cells/{CELL}.json").write_text(json.dumps({"rate_rps": 2.0}))
    before = benchmark_files()
    untraced, log0 = _run(tmp, 0, CELL)
    traced, log1 = _run(tmp, 1, CELL)
    return dict(untraced=untraced, traced=traced, log=log0 + log1, edited=benchmark_files() != before)


def test_the_cell_runs_end_to_end_and_agrees_with_the_reference(served):
    res, traced, log = served["untraced"], served["traced"], served["log"]
    assert res["correct"] is True and traced["correct"] is True, log[-3000:]
    assert res["failed"] == 0 and res["attempted"] >= 8, log[-3000:]
    assert "ckpt-tiny-lfm2-seed" in log
    assert '"logprobs_compared": 32' in log and '"compiles_in_window": 0.0' in log
    assert not served["edited"], "a run edited a file of the benchmark"


def test_the_new_readers_find_their_counters(served):
    got = served["traced"]["metrics"]
    # every expert held, 3 of 8 chosen: batch x 3 / 8 rows an expert, step and ROUTING layer
    assert 0.0 < got["moe_rows_per_sparse_expert"]["value"] <= 8 * 3 / 8
    assert 0.0 < got["state_slots_active_share"]["value"] <= 100.0
    # no kernel and no device line on the CPU: the device-trace readers find
    # nothing and are left out, and do not raise
    assert "moe_grouped_roofline" not in got and "conv_share_of_busy" not in got
    assert "attn_decode_folded_roofline" not in got


def test_the_folded_decode_roofline_reads_the_context_of_the_sequences_that_decode():
    """Two decode windows of 4 steps: 10 sequences on 1000 pages and 30 on
    3000, beside sequences in prefill whose pages the pool counts as active
    and no record does. The kernel ran 8 calls of 1 ms on a device of 1 GB/s:
    a call has to move the mean window's 2000 pages x 16 tokens x 2048 B and
    20 query and 20 output rows of 4096 B, 65.7 MB, 65.7 ms at that peak (a
    made-up device: the arithmetic is what is held, not the share)."""
    from layer_metrics import attn_decode_folded_roofline as reader

    roof = {"param_bytes": 5000, "page_bytes": 131072, "page_size": 16}
    windows = [{"kind": "decode_window", "steps": 4, "participants": n,
                "floor_bytes": 4 * (roof["param_bytes"] + pages * roof["page_bytes"])}
               for n, pages in ((10, 1000), (30, 3000))]
    ctx = {"config": FULL, "peaks": {"hbm_bytes_per_s": 1e9}, "steps1": {"roofline": roof},
           "records": windows + [{"kind": "prefill_packed", "steps": 0, "participants": 8, "floor_bytes": 0}],
           "trace": {"ops_by_name": {"paged_decode_attention_pallas_folded": 8e-3, "moe_grouped_matmul": 1.0},
                     "calls_by_name": {"paged_decode_attention_pallas_folded": 8, "moe_grouped_matmul": 42}}}
    assert reader.read(ctx) == pytest.approx(100.0 * (2000 * 16 * 2048 + 2 * 20 * 32 * 64 * 2) / 1e9 / 1e-3)
    # a program without the summary's constants, or a trace whose decode
    # kernel is not the folded one (every other cell), gives nothing
    assert reader.read(dict(ctx, steps1={})) is None
    other = {"ops_by_name": {"paged_decode_attention_pallas_tiled": 8e-3}, "calls_by_name": {"paged_decode_attention_pallas_tiled": 8}}
    assert reader.read(dict(ctx, trace=other)) is None
    assert reader.read(dict(ctx, trace=None)) is None


FULL = {k: v for k, v in json.loads(
    (ROOT / "benchmark" / "configs" / "lfm2-8b-a1b-d16.json").read_text()).items()
    if k not in run.OWN_KEYS}


@pytest.mark.parametrize("cfg, pinned", [
    ({k: v for k, v in TINY.items() if k not in run.OWN_KEYS},
     (117, 'bdb062a023d61bf5edb78097ad59170ff2ce50d0f7d5a8d5433014f075f4795f')),
    (FULL, (1472, '55efd2cb2c2dd6e85809577f30308ed719641216f7598d40324baba3798570cf')),
], ids=["tiny", "lfm2-8b-a1b-d16"])
def test_the_plan_is_pinned(cfg, pinned):
    assert _plan_digest(lfm2_moe.tensor_plan(cfg)) == pinned


def test_the_full_plan_is_the_stage_the_configuration_states():
    """10.80 GB of bfloat16: 16 layers, all 32 experts in 14 of them, the
    whole vocabulary once (the head is the embedding)."""
    import numpy as np

    plan = lfm2_moe.tensor_plan(FULL)
    size = {n: 2 * int(np.prod(s)) for n, s, _ in plan}
    assert 10.75e9 < sum(size.values()) < 10.85e9
    assert sum(1 for n in size if ".experts.31.w1" in n) == 14
    assert not any(".experts.32." in n or "lm_head" in n for n in size)
    assert not any("layers.0.feed_forward.gate" in n or "layers.1.feed_forward.experts" in n for n in size)
    shapes = dict((n, s) for n, s, _ in plan)
    assert shapes["model.layers.2.self_attn.q_layernorm.weight"] == (64,)
    assert shapes["model.layers.0.conv.in_proj.weight"] == (6144, 2048)
    assert shapes["model.layers.2.feed_forward.expert_bias"] == (32,)
