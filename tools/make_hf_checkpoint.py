"""Synthesize a fully HF-format Llama checkpoint directory.

Zero-egress environments have no real weights to download, but the SERVING
stack doesn't care about weight values — loading, tokenization, chat
templating, sharding, and throughput behave identically for a random
checkpoint of the same geometry. This builds one end to end:

  config.json           — LlamaForCausalLM at the requested geometry
  model.safetensors     — random-normal weights in HF tensor names/layouts
  tokenizer.json        — a REAL byte-level BPE tokenizer trained in-process
  tokenizer_config.json — chat template + special tokens

Default geometry matches TinyLlama-1.1B (2048 hidden, 22 layers, 32 q / 4 kv
heads, 32000 vocab) so on-chip numbers are comparable to published 1.1B-class
serving results.

Usage: python tools/make_hf_checkpoint.py OUTDIR [--tiny] [--seed N]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

TINYLLAMA_GEOMETRY = dict(
    hidden_size=2048,
    intermediate_size=5632,
    num_hidden_layers=22,
    num_attention_heads=32,
    num_key_value_heads=4,
    vocab_size=32000,
)

#: Qwen2.5-7B's published widths (config.json of Qwen/Qwen2.5-7B-Instruct),
#: qkv bias included; depth is the caller's to cut (28 published)
QWEN25_7B_GEOMETRY = dict(
    architectures=["Qwen2ForCausalLM"],
    model_type="qwen2",
    hidden_size=3584,
    intermediate_size=18944,
    num_hidden_layers=28,
    num_attention_heads=28,
    num_key_value_heads=4,
    vocab_size=152064,
    rope_theta=1000000.0,
    rms_norm_eps=1e-6,
    max_position_embeddings=32768,
)

TINY_GEOMETRY = dict(
    hidden_size=64,
    intermediate_size=128,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    vocab_size=512,
)

CHAT_TEMPLATE = (
    "{% for message in messages %}"
    "{% if message['role'] == 'system' %}<|system|>\n{{ message['content'] }}</s>\n"
    "{% elif message['role'] == 'user' %}<|user|>\n{{ message['content'] }}</s>\n"
    "{% elif message['role'] == 'assistant' %}<|assistant|>\n{{ message['content'] }}</s>\n"
    "{% endif %}{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>\n{% endif %}"
)


def _train_tokenizer(out: Path, vocab_size: int) -> None:
    """A genuine byte-level BPE tokenizer trained on synthetic text — real
    enough that AutoTokenizer loads it and merges/offsets all behave."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    rng = np.random.default_rng(0)
    words = ["".join(rng.choice(list("abcdefghijklmnopqrstuvwxyz"), size=rng.integers(2, 9)))
             for _ in range(4000)]

    def corpus():
        for _ in range(2000):
            yield " ".join(rng.choice(words, size=rng.integers(4, 30)))

    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size,
        special_tokens=["<s>", "</s>", "<unk>", "<|system|>", "<|user|>", "<|assistant|>"],
        show_progress=False,
    )
    tok.train_from_iterator(corpus(), trainer)
    tok.save(str(out / "tokenizer.json"))
    (out / "tokenizer_config.json").write_text(json.dumps({
        "tokenizer_class": "PreTrainedTokenizerFast",
        "bos_token": "<s>",
        "eos_token": "</s>",
        "unk_token": "<unk>",
        "chat_template": CHAT_TEMPLATE,
        "model_max_length": 2048,
    }, indent=1))
    (out / "special_tokens_map.json").write_text(json.dumps({
        "bos_token": "<s>", "eos_token": "</s>", "unk_token": "<unk>",
    }))


def make_checkpoint(out_dir: str, geometry: dict | None = None, seed: int = 0) -> Path:
    from safetensors.numpy import save_file

    g = dict(TINYLLAMA_GEOMETRY)
    g.update(geometry or {})
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)

    head_dim = g["hidden_size"] // g["num_attention_heads"]
    config = {
        "architectures": ["LlamaForCausalLM"],
        "model_type": "llama",
        "hidden_act": "silu",
        "bos_token_id": 1,
        "eos_token_id": 2,
        "max_position_embeddings": 2048,
        "rms_norm_eps": 1e-5,
        "rope_theta": 10000.0,
        "tie_word_embeddings": False,
        "torch_dtype": "bfloat16",
        "head_dim": head_dim,
        **g,
    }
    (out / "config.json").write_text(json.dumps(config, indent=1))

    rng = np.random.default_rng(seed)
    D, I, V = g["hidden_size"], g["intermediate_size"], g["vocab_size"]
    Hq, Hkv = g["num_attention_heads"], g["num_key_value_heads"]

    def w(*shape, scale=0.02):
        return (rng.standard_normal(shape, dtype=np.float32) * scale).astype(np.float16)

    tensors = {
        "model.embed_tokens.weight": w(V, D),
        "model.norm.weight": np.ones(D, np.float16),
        "lm_head.weight": w(V, D),
    }
    for l in range(g["num_hidden_layers"]):
        pre = f"model.layers.{l}."
        tensors[pre + "input_layernorm.weight"] = np.ones(D, np.float16)
        tensors[pre + "post_attention_layernorm.weight"] = np.ones(D, np.float16)
        tensors[pre + "self_attn.q_proj.weight"] = w(Hq * head_dim, D)
        tensors[pre + "self_attn.k_proj.weight"] = w(Hkv * head_dim, D)
        tensors[pre + "self_attn.v_proj.weight"] = w(Hkv * head_dim, D)
        tensors[pre + "self_attn.o_proj.weight"] = w(D, Hq * head_dim)
        if "qwen" in config["model_type"]:  # Qwen2-family qkv biases
            tensors[pre + "self_attn.q_proj.bias"] = w(Hq * head_dim)
            tensors[pre + "self_attn.k_proj.bias"] = w(Hkv * head_dim)
            tensors[pre + "self_attn.v_proj.bias"] = w(Hkv * head_dim)
        tensors[pre + "mlp.gate_proj.weight"] = w(I, D)
        tensors[pre + "mlp.up_proj.weight"] = w(I, D)
        tensors[pre + "mlp.down_proj.weight"] = w(D, I)
    save_file(tensors, str(out / "model.safetensors"))

    _train_tokenizer(out, g["vocab_size"])
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("out_dir")
    ap.add_argument("--tiny", action="store_true", help="tiny geometry for tests")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out = make_checkpoint(args.out_dir, TINY_GEOMETRY if args.tiny else None, seed=args.seed)
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
