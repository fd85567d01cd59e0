"""The checkpoint is a pure function of (config, seed); tied embeddings are
written once; the tokenizer covers the vocabulary."""

import hashlib
import json
import types
from pathlib import Path

import numpy as np
import pytest

import checkpoint
import run
from checkpoints import dense
from test_rehearsal import throwaway_spec

ROOT = Path(__file__).resolve().parents[2]

CFG = {"architectures": ["Qwen2ForCausalLM"], "model_type": "qwen2", "hidden_size": 64,
       "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "vocab_size": 512, "tie_word_embeddings": True}


def _tensors(path):
    raw = path.read_bytes()
    n = int.from_bytes(raw[:8], "little")
    head = json.loads(raw[8:8 + n])
    return head, raw[8 + n:]


def test_seeded_tied_and_normal(tmp_path):
    a, made, _, _ = checkpoint.ensure_checkpoint(tmp_path / "a", "t", CFG, 2**31 + 3, dense)
    b, _, _, _ = checkpoint.ensure_checkpoint(tmp_path / "b", "t", CFG, 2**31 + 3, dense)
    c, _, _, _ = checkpoint.ensure_checkpoint(tmp_path / "c", "t", CFG, 4, dense)
    assert made
    ha, da = _tensors(a / "model.safetensors")
    assert da == _tensors(b / "model.safetensors")[1] != _tensors(c / "model.safetensors")[1]
    assert "lm_head.weight" not in ha and "model.layers.1.self_attn.q_proj.bias" in ha
    lo, hi = ha["model.embed_tokens.weight"]["data_offsets"]
    bits = np.frombuffer(da[lo:hi], np.uint16).astype(np.uint32) << 16
    vals = bits.view(np.float32)
    assert abs(float(vals.std()) - 0.02) < 0.001 and abs(float(vals.mean())) < 0.001
    untied = dense.tensor_plan(dict(CFG, tie_word_embeddings=False))
    assert "lm_head.weight" in [n for n, _, _ in untied]
    # a second call with the same seed reuses; another seed replaces (one is kept)
    assert checkpoint.ensure_checkpoint(tmp_path / "a", "t", CFG, 2**31 + 3, dense)[1] is False
    checkpoint.ensure_checkpoint(tmp_path / "a", "t", CFG, 5, dense)
    assert [p.name for p in (tmp_path / "a").glob("ckpt-*")] == ["ckpt-t-seed5"]


def test_tokenizer_covers_the_vocabulary(tmp_path):
    from transformers import AutoTokenizer

    out, *_ = checkpoint.ensure_checkpoint(tmp_path, "t", CFG, 1, dense)
    tok = AutoTokenizer.from_pretrained(str(out))
    assert len(tok) == 512
    assert all(tok.decode([i], skip_special_tokens=False) for i in (0, 3, 100, 511))
    assert checkpoint.token_id_of(tok.decode([511])) == 511
    assert checkpoint.token_id_of("</s>") == 1


# ---- the plan by name (PR 27). The pins were computed on the parent commit
# 57ac644 with its `checkpoint.tensor_plan` / `ensure_checkpoint`: the draw of
# tensor i is keyed by (seed, i), so names, shapes and order ARE the weights.


def _plan_digest(plan) -> tuple:
    return len(plan), hashlib.sha256(json.dumps([[n, list(s), k] for n, s, k in plan]).encode()).hexdigest()


QWEN_3B = {k: v for k, v in json.loads((ROOT / "benchmark" / "configs" / "qwen2.5-3b.json").read_text()).items()
           if k not in run.OWN_KEYS}


@pytest.mark.parametrize("cfg, pinned", [
    (CFG, (26, "3a36124e3cc3b24afb2fd0aea2ea38eabef81ffed58bc70ac1f225c10660e65c")),
    (dict(CFG, model_type="llama", tie_word_embeddings=False),
     (21, "e95a01155d07bc7d27cf1e46754ce7754a30160b8f976f9e4db008d4b3092b37")),
    (QWEN_3B, (434, "3ce2868796a4fe0d8b8124935e00d86453c931ad785f4455983f04749dcd724d")),
], ids=["tiny-qwen2", "tiny-llama-untied", "qwen2.5-3b"])
def test_dense_plan_is_the_parents(cfg, pinned):
    assert _plan_digest(dense.tensor_plan(cfg)) == pinned


@pytest.mark.parametrize("seed, sha256", [
    (2**31 + 3, "28b8507ba3d6a272cbd7683981eff96c61ce9853ecd101980563ecf3584e42fb"),
    (4, "7c9531452cbb0d47cf8000dff040cd31d4b4bc6bd43dae3d1edb8eace4441d49"),
])
def test_checkpoint_bytes_are_the_parents(tmp_path, seed, sha256):
    out, *_ = checkpoint.ensure_checkpoint(tmp_path, "t", CFG, seed, dense)
    assert hashlib.sha256((out / "model.safetensors").read_bytes()).hexdigest() == sha256
    assert hashlib.sha256((out / "config.json").read_bytes()).hexdigest() == \
        "a7768c8a2d3ca44d29392b389517ebc0d5e290b768e66b56290b1480471079dd"
    assert hashlib.sha256((out / "tokenizer.json").read_bytes()).hexdigest() == \
        "f2ea8fb7e0f45bb6ec0fb5a621a4d065b95a6f3e42b3037a99ef6198f1401463"


def test_checkpoint_py_names_no_tensor():
    text = (ROOT / "benchmark" / "checkpoint.py").read_text()
    assert not any(word in text for word in ("_proj", "layernorm", "embed_tokens", "lm_head", "model.layers"))


def _root_with(tmp: Path, conf: dict) -> run.Files:
    throwaway_spec(tmp, "t", "chat")
    (tmp / "extra" / "configs").mkdir(parents=True)
    (tmp / "extra" / "configs" / "t.json").write_text(json.dumps(conf))
    return run.Files(tmp)


def test_a_plan_under_root_comes_before_the_benchmarks_own(tmp_path):
    files = _root_with(tmp_path, {})
    assert files.module("checkpoints", "dense").__file__ == str(ROOT / "benchmark" / "checkpoints" / "dense.py")
    (tmp_path / "extra" / "checkpoints").mkdir()
    (tmp_path / "extra" / "checkpoints" / "dense.py").write_text(
        "def tensor_plan(cfg):\n    return [('only.weight', (2, 2), 'normal')]\n")
    mine = files.module("checkpoints", "dense")
    assert mine.__file__ == str(tmp_path / "extra" / "checkpoints" / "dense.py")
    out, *_ = checkpoint.ensure_checkpoint(tmp_path / "c", "t", CFG, 1, mine)
    assert list(_tensors(out / "model.safetensors")[0]) == ["only.weight"]


def test_an_unknown_plan_is_an_error_before_any_server_starts(tmp_path, monkeypatch):
    conf = dict(CFG, benchmark={"launcher": "single", "checkpoint": "no_such_plan", "reference": "qwen2",
                                "platform": "cpu", "server_args": []})
    files = _root_with(tmp_path, conf)
    started = []
    monkeypatch.setattr(run, "Server", lambda *a, **k: started.append(a) or (_ for _ in ()).throw(AssertionError))
    args = types.SimpleNamespace(seed=1, seconds=1.0)
    with pytest.raises(run.BenchError, match="checkpoints/no_such_plan.py"):
        run.serve_and_measure(files, args, files.cell("t.chat"), False)
    assert not started
