"""The plain reference for the DeepSeek-V2 family: one forward pass in plain
`jax.numpy`, float32, `default_matmul_precision("highest")`, no cache, no
kernel, no absorbed projections, nothing imported from `dynamo_tpu`. A test
fixture beside its tensor plan (`../checkpoints/mla_moe.py`).

It follows the published model (`transformers` `DeepseekV2ForCausalLM`):
RMSNorm -> multi-head latent attention -> residual -> RMSNorm -> dense SwiGLU
(the first `first_k_dense_replace` layers) or shared + routed experts ->
residual; final RMSNorm; logits through `lm_head` (the embedding where tied).

Attention, with K and V materialised per head: q = q_b(RMSNorm(q_a(x))) where
`q_lora_rank` is set, else q_proj(x), split per head into a `qk_nope_head_dim`
part and a `qk_rope_head_dim` part; kv_a_proj_with_mqa(x) gives the latent
(`kv_lora_rank`) and ONE rope key shared by all heads; kv_b_proj(RMSNorm(latent))
gives each head its k_nope and v. The rope parts are rotated in the
INTERLEAVED form, pairs (2i, 2i+1) at angle position / theta^(2i/d_rope): the
published code de-interleaves q and k and then rotates halves, which is the
same scores. Scale 1/sqrt(nope + rope), causal softmax in float32, o_proj.

Experts: s = softmax(x W_r^T) over all experts in float32, the top
`num_experts_per_tok` of s (`topk_method` greedy, no groups); their weights are
s_k / (sum s_k + 1e-20) where `norm_topk_prob`, else s_k *
`routed_scaling_factor`; every token reaches every expert it chose (nothing is
dropped); the shared experts (one SwiGLU of width `n_shared_experts` x
`moe_intermediate_size`) are added unscaled. Other `scoring_func`,
`topk_method` or `rope_scaling` values are refused, not guessed.

    JAX_PLATFORMS=cpu python deepseek_v2.py CKPT PROBES.json OUT.json

Same command line and files as `benchmark/reference/qwen2.py`.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _rope_interleaved(x, theta):
    """x [B, L, H, d]: pairs (2i, 2i+1) rotated by position / theta^(2i/d).
    Returned de-interleaved (evens, then odds), for q and k alike."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin], axis=-1)


def _swiglu(x, w, prefix):
    import jax

    gate = x @ w[prefix + "gate_proj.weight"].T
    return (jax.nn.silu(gate) * (x @ w[prefix + "up_proj.weight"].T)) @ w[prefix + "down_proj.weight"].T


def _experts(x, w, cfg):
    """x [B, L, D] -> routed experts' weighted sum + the shared experts."""
    import jax
    import jax.numpy as jnp

    E, K = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.softmax(x @ w["mlp.gate.weight"].T, axis=-1)  # [B, L, E]
    top, idx = jax.lax.top_k(s, K)
    if cfg.get("norm_topk_prob", False) and K > 1:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    else:
        top = top * cfg.get("routed_scaling_factor", 1.0)
    # weight of expert e for each token: 0 where it was not chosen
    per_expert = jnp.sum(jax.nn.one_hot(idx, E, dtype=x.dtype) * top[..., None], axis=-2)
    out = _swiglu(x, w, "mlp.shared_experts.")
    for e in range(E):
        out = out + per_expert[..., e:e + 1] * _swiglu(x, w, f"mlp.experts.{e}.")
    return out


def layer_forward(h, w, cfg, dense: bool):
    """One decoder layer over h [B, L, D] (float32); w: this layer's tensors
    by their HF suffix, float32, in HF's [out, in] layout."""
    import jax
    import jax.numpy as jnp

    B, L, _ = h.shape
    H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    dn, dr, dv, dc = (cfg[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank"))
    x = _rms_norm(h, w["input_layernorm.weight"], eps)
    if cfg.get("q_lora_rank"):
        q = _rms_norm(x @ w["self_attn.q_a_proj.weight"].T, w["self_attn.q_a_layernorm.weight"], eps)
        q = q @ w["self_attn.q_b_proj.weight"].T
    else:
        q = x @ w["self_attn.q_proj.weight"].T
    q = q.reshape(B, L, H, dn + dr)
    ckv = x @ w["self_attn.kv_a_proj_with_mqa.weight"].T  # [B, L, dc + dr]
    kv = _rms_norm(ckv[..., :dc], w["self_attn.kv_a_layernorm.weight"], eps) @ w["self_attn.kv_b_proj.weight"].T
    kv = kv.reshape(B, L, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = _rope_interleaved(q[..., dn:], cfg["rope_theta"])
    k_pe = _rope_interleaved(ckv[:, :, None, dc:], cfg["rope_theta"])  # one key for all heads
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :dn], k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, k_pe[:, :, 0])) / jnp.sqrt(jnp.float32(dn + dr))
    causal = jnp.tril(jnp.ones((L, L), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    h = h + attn.reshape(B, L, H * dv) @ w["self_attn.o_proj.weight"].T
    x = _rms_norm(h, w["post_attention_layernorm.weight"], eps)
    return h + (_swiglu(x, w, "mlp.") if dense else _experts(x, w, cfg))


def teacher_forced_logprobs(ckpt: Path, probes: list) -> list:
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy for safetensors)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from safetensors import safe_open

    cfg = json.loads((ckpt / "config.json").read_text())
    cfg.setdefault("rope_theta", 10000.0)
    cfg.setdefault("rms_norm_eps", 1e-6)
    for key, plain in (("scoring_func", "softmax"), ("topk_method", "greedy"), ("rope_scaling", None)):
        if cfg.get(key, plain) != plain:
            raise SystemExit(f"this reference does not follow {key}={cfg[key]!r}")
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
        steps = {dense: jax.jit(lambda h, w, dense=dense: layer_forward(h, w, cfg, dense))
                 for dense in (True, False)}
        for l in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{l}."
            w = {n[len(pre):]: get(n) for n in sorted(names) if n.startswith(pre)}
            h = steps[l < cfg.get("first_k_dense_replace", 0)](h, w)
        h = _rms_norm(h, get("model.norm.weight"), cfg["rms_norm_eps"])
        head = embed if cfg.get("tie_word_embeddings", False) or "lm_head.weight" not in names \
            else get("lm_head.weight")
        out = []
        for i, p in enumerate(probes):
            n0, n1 = p["prompt_len"], len(p["tokens"])
            # position j predicts token j+1
            logp = jax.nn.log_softmax(h[i, n0 - 1: n1 - 1] @ head.T, axis=-1)
            chosen = jnp.asarray(p["tokens"][n0:n1])
            out.append([float(x) for x in logp[jnp.arange(n1 - n0), chosen]])
    return out


def main(argv: list) -> int:
    ckpt, probes_path, out_path = Path(argv[0]), Path(argv[1]), Path(argv[2])
    out_path.write_text(json.dumps(teacher_forced_logprobs(ckpt, json.loads(probes_path.read_text()))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
