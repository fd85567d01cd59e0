"""A configuration of ANOTHER architecture needs files only: a small
DeepSeek-V2-style model (latent attention with a low-rank q path, one dense
layer, then four routed experts top-2 beside a shared one) whose tensor plan,
plain reference, configuration, mix and cell are the files under
`fixtures/mla_moe/`, laid into a temporary `--root` as a later PR would lay
them into a directory of its own. `run.py` writes the checkpoint from the plan
it finds by name, serves it through `launch.run` on the CPU, and compares
logprobs with the reference. Takes some minutes."""

import json
import shutil
from pathlib import Path

import pytest

import test_rehearsal
from test_checkpoint import _tensors
from test_rehearsal import ROOT, _run, benchmark_files, throwaway_spec

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "mla_moe"
CELL = "tiny-mla-moe.chat-tiny-mla"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mla_moe")
    throwaway_spec(tmp, "tiny-mla-moe", "chat-tiny-mla")
    shutil.copytree(FIXTURE, tmp / "extra")
    before = benchmark_files()
    untraced, log0 = _run(tmp, 0, CELL)
    traced, log1 = _run(tmp, 1, CELL)
    return dict(untraced=untraced, traced=traced, log=log0 + log1, edited=benchmark_files() != before)


def test_second_architecture_comes_as_files(served):
    """Plan, reference, configuration, mix and cell under `--root`, no edit
    to `benchmark/`: served, measured, traced, compared, a result printed."""
    res, traced, log = served["untraced"], served["traced"], served["log"]
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device"}
    assert res["failed"] == 0 and res["attempted"] >= 8, log[-3000:]
    assert set(res["metrics"]) == {"ttft_p50_ms", "tpot_p95_ms", "output_tokens_per_s", "setup_s"}
    assert "ckpt-tiny-mla-moe-seed" in log  # the plan under --root wrote the checkpoint
    assert '"logprobs_compared": 32' in log and '"compiles_in_window": 0.0' in log
    assert "breakdown" in traced and {"busy_s", "window_s"} <= set(traced["device"])
    assert not served["edited"], "a run edited a file of the benchmark"


@pytest.mark.xfail(strict=True, reason="the program rotates the rope part of q and k by HALVES "
                   "(`ops/rotary.apply_rope` in `models/deepseek.py`), the published model by interleaved pairs: "
                   "0.21-0.25 from the reference at a tolerance of 0.05. PERF.md section 7, first item; the "
                   "`model_config` PR that repairs the program takes this mark away")
def test_logprobs_agree_with_the_published_model(served):
    assert served["untraced"]["correct"] is True and served["traced"]["correct"] is True


LAYERS_FIRST = '''"""A second dense plan: the layers' tensors before the embedding and the
final norm. Same names, another order, so other draws: another checkpoint."""
from checkpoints import dense


def tensor_plan(cfg):
    plan = dense.tensor_plan(cfg)
    layers = [t for t in plan if t[0].startswith("model.layers.")]
    return layers + [t for t in plan if t not in layers]
'''


def test_a_second_dense_plan_is_found_by_name(tmp_path):
    """The lookup by name end to end with `correct` true: a Llama-style
    configuration whose plan file exists only under `--root`."""
    throwaway_spec(tmp_path, "tiny-llama", "chat-tiny")
    for sub in ("configs", "traffic", "cells", "checkpoints"):
        (tmp_path / "extra" / sub).mkdir(parents=True)
    conf = dict(test_rehearsal.TINY, name="tiny-llama", architectures=["LlamaForCausalLM"], model_type="llama",
                tie_word_embeddings=False,
                benchmark=dict(test_rehearsal.TINY["benchmark"], checkpoint="layers_first"))
    (tmp_path / "extra/configs/tiny-llama.json").write_text(json.dumps(conf))
    (tmp_path / "extra/traffic/chat-tiny.json").write_text(json.dumps(test_rehearsal.MIX))
    (tmp_path / "extra/cells/tiny-llama.chat-tiny.json").write_text(json.dumps({"rate_rps": 4.0}))
    (tmp_path / "extra/checkpoints/layers_first.py").write_text(LAYERS_FIRST)
    res, log = _run(tmp_path, 0, "tiny-llama.chat-tiny")
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 15, log[-3000:]
    made = next((ROOT / "benchmark" / ".cache").glob("ckpt-tiny-llama-seed*")) / "model.safetensors"
    names = list(_tensors(made)[0])
    assert names[0] == "model.layers.0.input_layernorm.weight" and names[-1] == "lm_head.weight"
    assert not any("bias" in n for n in names)
