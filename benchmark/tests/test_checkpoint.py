"""The checkpoint is a pure function of (config, seed); tied embeddings are
written once; the tokenizer covers the vocabulary."""

import json

import numpy as np

import checkpoint

CFG = {"architectures": ["Qwen2ForCausalLM"], "model_type": "qwen2", "hidden_size": 64,
       "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "vocab_size": 512, "tie_word_embeddings": True}


def _tensors(path):
    raw = path.read_bytes()
    n = int.from_bytes(raw[:8], "little")
    head = json.loads(raw[8:8 + n])
    return head, raw[8 + n:]


def test_seeded_tied_and_normal(tmp_path):
    a, made, _, _ = checkpoint.ensure_checkpoint(tmp_path / "a", "t", CFG, 2**31 + 3)
    b, _, _, _ = checkpoint.ensure_checkpoint(tmp_path / "b", "t", CFG, 2**31 + 3)
    c, _, _, _ = checkpoint.ensure_checkpoint(tmp_path / "c", "t", CFG, 4)
    assert made
    ha, da = _tensors(a / "model.safetensors")
    assert da == _tensors(b / "model.safetensors")[1] != _tensors(c / "model.safetensors")[1]
    assert "lm_head.weight" not in ha and "model.layers.1.self_attn.q_proj.bias" in ha
    lo, hi = ha["model.embed_tokens.weight"]["data_offsets"]
    bits = np.frombuffer(da[lo:hi], np.uint16).astype(np.uint32) << 16
    vals = bits.view(np.float32)
    assert abs(float(vals.std()) - 0.02) < 0.001 and abs(float(vals.mean())) < 0.001
    untied = checkpoint.tensor_plan(dict(CFG, tie_word_embeddings=False))
    assert "lm_head.weight" in [n for n, _, _ in untied]
    # a second call with the same seed reuses; another seed replaces (one is kept)
    assert checkpoint.ensure_checkpoint(tmp_path / "a", "t", CFG, 2**31 + 3)[1] is False
    checkpoint.ensure_checkpoint(tmp_path / "a", "t", CFG, 5)
    assert [p.name for p in (tmp_path / "a").glob("ckpt-*")] == ["ckpt-t-seed5"]


def test_tokenizer_covers_the_vocabulary(tmp_path):
    from transformers import AutoTokenizer

    out, *_ = checkpoint.ensure_checkpoint(tmp_path, "t", CFG, 1)
    tok = AutoTokenizer.from_pretrained(str(out))
    assert len(tok) == 512
    assert all(tok.decode([i], skip_special_tokens=False) for i in (0, 3, 100, 511))
    assert checkpoint.token_id_of(tok.decode([511])) == 511
    assert checkpoint.token_id_of("</s>") == 1
