"""The plain reference for the Qwen2 family: one forward pass in plain
`jax.numpy`, float32, `default_matmul_precision("highest")`, no cache, no
kernel, no batching tricks, nothing imported from `dynamo_tpu`.

It follows the published model (`transformers` `Qwen2ForCausalLM`): RMSNorm
(eps from the config) -> q/k/v projections WITH bias -> rotary embedding in
the rotate-half form over the whole head (theta from the config) -> causal
grouped-query attention, softmax in float32, scale 1/sqrt(head_dim) -> output
projection without bias -> residual -> RMSNorm -> SwiGLU (down(silu(gate) *
up)) -> residual; final RMSNorm; logits through the tied embedding where
`tie_word_embeddings`, else through `lm_head`. The Llama family is the same
without the biases (`attention_bias` false), which this file also runs.

The checkpoint is read one layer at a time and cast to float32, so the host
never holds the model in float32. Run as a script by `benchmark/run.py`, in a
process of its own held to the CPU:

    JAX_PLATFORMS=cpu python benchmark/reference/qwen2.py CKPT PROBES.json OUT.json

PROBES.json: [{"tokens": [prompt ids ..., chosen ids ...], "prompt_len": n}].
OUT.json: [[log p(tokens[i] | tokens[:i]) for i in prompt_len..len-1], ...]:
teacher-forced on the tokens the server chose, one pass per probe covers what
the server did as a prefill and then as decode steps through its cache.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope(x, positions, theta):
    """x [B, L, H, hd]; rotate-half form, angles position / theta^(2i/hd)."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, :, None].astype(jnp.float32) * inv[None, None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[:, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def layer_forward(h, w, cfg):
    """One decoder layer over h [B, L, D] (float32); w: this layer's tensors
    by their HF suffix, float32, in HF's [out, in] layout."""
    import jax
    import jax.numpy as jnp

    B, L, D = h.shape
    Hq, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or D // Hq
    x = _rms_norm(h, w["input_layernorm.weight"], cfg["rms_norm_eps"])

    def proj(name):
        y = x @ w[f"self_attn.{name}_proj.weight"].T
        b = w.get(f"self_attn.{name}_proj.bias")
        return y if b is None else y + b

    pos = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
    q = _rope(proj("q").reshape(B, L, Hq, hd), pos, cfg["rope_theta"])
    k = _rope(proj("k").reshape(B, L, Hkv, hd), pos, cfg["rope_theta"])
    v = proj("v").reshape(B, L, Hkv, hd)
    k = jnp.repeat(k, Hq // Hkv, axis=2)
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    h = h + attn.reshape(B, L, Hq * hd) @ w["self_attn.o_proj.weight"].T
    x = _rms_norm(h, w["post_attention_layernorm.weight"], cfg["rms_norm_eps"])
    gate = x @ w["mlp.gate_proj.weight"].T
    up = x @ w["mlp.up_proj.weight"].T
    return h + (jax.nn.silu(gate) * up) @ w["mlp.down_proj.weight"].T


def teacher_forced_logprobs(ckpt: Path, probes: list) -> list:
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy for safetensors)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    cfg = json.loads((ckpt / "config.json").read_text())
    cfg.setdefault("rope_theta", 10000.0)
    cfg.setdefault("rms_norm_eps", 1e-5)
    L = max(len(p["tokens"]) for p in probes)
    tokens = np.zeros((len(probes), L), np.int32)  # right-padded: causal, so harmless
    for i, p in enumerate(probes):
        tokens[i, : len(p["tokens"])] = p["tokens"]

    with jax.default_matmul_precision("highest"), \
            safe_open(str(ckpt / "model.safetensors"), framework="np") as sf:
        names = set(sf.keys())

        def get(name):
            return jnp.asarray(sf.get_tensor(name).astype(np.float32))

        embed = get("model.embed_tokens.weight")
        h = embed[tokens]
        step = jax.jit(lambda h, w: layer_forward(h, w, cfg))
        for l in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{l}."
            w = {n[len(pre):]: get(n) for n in sorted(names) if n.startswith(pre)}
            h = step(h, w)
        h = _rms_norm(h, get("model.norm.weight"), cfg["rms_norm_eps"])
        head = embed if cfg.get("tie_word_embeddings", False) or "lm_head.weight" not in names \
            else get("lm_head.weight")
        out = []
        for i, p in enumerate(probes):
            n0, n1 = p["prompt_len"], len(p["tokens"])
            # position j predicts token j+1
            logits = h[i, n0 - 1: n1 - 1] @ head.T
            logp = jax.nn.log_softmax(logits, axis=-1)
            chosen = jnp.asarray(p["tokens"][n0:n1])
            out.append([float(x) for x in logp[jnp.arange(n1 - n0), chosen]])
    return out


def main(argv: list) -> int:
    ckpt, probes_path, out_path = Path(argv[0]), Path(argv[1]), Path(argv[2])
    probes = json.loads(probes_path.read_text())
    out_path.write_text(json.dumps(teacher_forced_logprobs(ckpt, probes)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
