"""HF checkpoint loading round-trips: synthesize an HF-style safetensors
checkpoint from randomly-initialized params via the inverse name/layout
mapping, load it through the registry, and require identical prefill logits.

This validates the name mapping, transposes, expert stacking, and the
kv_b_proj k-up/v-up split without needing real checkpoints (zero-egress env);
reference: launch/dynamo-run/src/hub.rs resolves HF repos, here local dirs.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from safetensors.numpy import save_file

from dynamo_tpu.models.registry import load_model


# compile-heavy JAX e2e: runs in the full matrix, not the <2-min default tier
pytestmark = pytest.mark.slow

PROMPT = np.array([5, 9, 2, 77, 31, 8], dtype=np.int32)


def _prefill_logits(model, params, num_pages=16, page_size=4):
    kv = model.init_kv_cache(num_pages, page_size)
    T = len(PROMPT)
    pt = np.array([3, 5, 7, 0, 0, 0, 0, 0], np.int32)
    positions = np.arange(8, dtype=np.int32)
    tokens = np.zeros(8, np.int32)
    tokens[:T] = PROMPT
    logits, _ = model.prefill(
        params, kv, jnp.array(tokens), jnp.array(positions),
        jnp.array(pt), jnp.array(positions < T), jnp.array(T - 1),
    )
    return np.asarray(logits)


def _np(x):
    return np.asarray(x, np.float32)


def _T(x):
    # safetensors writes the raw buffer of non-contiguous views (silently
    # wrong for transposes) — always materialize the transpose
    return np.ascontiguousarray(_np(x).T)


def test_llama_checkpoint_roundtrip(tmp_path):
    hf_cfg = {
        "architectures": ["LlamaForCausalLM"],
        "vocab_size": 128,
        "hidden_size": 32,
        "intermediate_size": 64,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 8,
        "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5,
    }
    (tmp_path / "config.json").write_text(json.dumps(hf_cfg))

    from dynamo_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.from_hf_config(hf_cfg)
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.key(7))

    tensors = {
        "model.embed_tokens.weight": _np(params["embed"]),
        "model.norm.weight": _np(params["final_norm"]),
        "lm_head.weight": _np(params["lm_head"]),
    }
    lw = params["layers"]
    for l in range(cfg.num_layers):
        pre = f"model.layers.{l}."
        tensors[pre + "input_layernorm.weight"] = _np(lw["input_norm"][l])
        tensors[pre + "self_attn.q_proj.weight"] = _T(lw["wq"][l])
        tensors[pre + "self_attn.k_proj.weight"] = _T(lw["wk"][l])
        tensors[pre + "self_attn.v_proj.weight"] = _T(lw["wv"][l])
        tensors[pre + "self_attn.o_proj.weight"] = _T(lw["wo"][l])
        tensors[pre + "post_attention_layernorm.weight"] = _np(lw["post_norm"][l])
        tensors[pre + "mlp.gate_proj.weight"] = _T(lw["gate"][l])
        tensors[pre + "mlp.up_proj.weight"] = _T(lw["up"][l])
        tensors[pre + "mlp.down_proj.weight"] = _T(lw["down"][l])
    save_file(tensors, str(tmp_path / "model.safetensors"))

    loaded_model, loaded_params = load_model(str(tmp_path))
    np.testing.assert_allclose(
        _prefill_logits(loaded_model, loaded_params),
        _prefill_logits(model, params),
        atol=1e-3,
    )


def test_mixtral_checkpoint_roundtrip(tmp_path):
    hf_cfg = {
        "architectures": ["MixtralForCausalLM"],
        "model_type": "mixtral",
        "vocab_size": 128,
        "hidden_size": 32,
        "intermediate_size": 48,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 8,
        "num_local_experts": 4,
        "num_experts_per_tok": 2,
    }
    (tmp_path / "config.json").write_text(json.dumps(hf_cfg))

    from dynamo_tpu.models.mixtral import MixtralConfig, MixtralModel

    cfg = MixtralConfig.from_hf_config(hf_cfg)
    model = MixtralModel(cfg)
    params = model.init_params(jax.random.key(8))

    tensors = {
        "model.embed_tokens.weight": _np(params["embed"]),
        "model.norm.weight": _np(params["final_norm"]),
        "lm_head.weight": _np(params["lm_head"]),
    }
    lw = params["layers"]
    for l in range(cfg.num_layers):
        pre = f"model.layers.{l}."
        tensors[pre + "input_layernorm.weight"] = _np(lw["input_norm"][l])
        tensors[pre + "self_attn.q_proj.weight"] = _T(lw["wq"][l])
        tensors[pre + "self_attn.k_proj.weight"] = _T(lw["wk"][l])
        tensors[pre + "self_attn.v_proj.weight"] = _T(lw["wv"][l])
        tensors[pre + "self_attn.o_proj.weight"] = _T(lw["wo"][l])
        tensors[pre + "post_attention_layernorm.weight"] = _np(lw["post_norm"][l])
        tensors[pre + "block_sparse_moe.gate.weight"] = _T(lw["router"][l])
        for e in range(cfg.num_experts):
            epre = pre + f"block_sparse_moe.experts.{e}."
            tensors[epre + "w1.weight"] = _T(lw["w_gate"][l, e])
            tensors[epre + "w3.weight"] = _T(lw["w_up"][l, e])
            tensors[epre + "w2.weight"] = _T(lw["w_down"][l, e])
    save_file(tensors, str(tmp_path / "model.safetensors"))

    loaded_model, loaded_params = load_model(str(tmp_path))
    np.testing.assert_allclose(
        _prefill_logits(loaded_model, loaded_params),
        _prefill_logits(model, params),
        atol=1e-3,
    )


def test_deepseek_checkpoint_roundtrip(tmp_path):
    hf_cfg = {
        "architectures": ["DeepseekV2ForCausalLM"],
        "model_type": "deepseek_v2",
        "vocab_size": 128,
        "hidden_size": 32,
        "intermediate_size": 48,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "q_lora_rank": 24,
        "kv_lora_rank": 16,
        "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4,
        "v_head_dim": 8,
        "n_routed_experts": 4,
        "num_experts_per_tok": 2,
        "n_shared_experts": 1,
        "moe_intermediate_size": 16,
        "first_k_dense_replace": 1,
    }
    (tmp_path / "config.json").write_text(json.dumps(hf_cfg))

    from dynamo_tpu.models.deepseek import DeepseekConfig, DeepseekModel

    cfg = DeepseekConfig.from_hf_config(hf_cfg)
    model = DeepseekModel(cfg)
    params = model.init_params(jax.random.key(9))

    tensors = {
        "model.embed_tokens.weight": _np(params["embed"]),
        "model.norm.weight": _np(params["final_norm"]),
        "lm_head.weight": _np(params["lm_head"]),
    }
    dn, dv, dc = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    H = cfg.num_heads
    Ld = cfg.first_k_dense_replace
    for l in range(cfg.num_layers):
        dense = l < Ld
        lw = params["dense_layers"] if dense else params["moe_layers"]
        gl = l if dense else l - Ld
        pre = f"model.layers.{l}."
        tensors[pre + "input_layernorm.weight"] = _np(lw["input_norm"][gl])
        tensors[pre + "self_attn.q_a_proj.weight"] = _T(lw["w_dq"][gl])
        tensors[pre + "self_attn.q_a_layernorm.weight"] = _np(lw["q_norm"][gl])
        tensors[pre + "self_attn.q_b_proj.weight"] = _T(lw["w_uq"][gl])
        tensors[pre + "self_attn.kv_a_proj_with_mqa.weight"] = _T(lw["w_dkv"][gl])
        tensors[pre + "self_attn.kv_a_layernorm.weight"] = _np(lw["kv_norm"][gl])
        # [dc, H, dn] + [dc, H, dv] -> HF kv_b_proj [H*(dn+dv), dc]
        kvb = np.concatenate([_np(lw["w_kb"][gl]), _np(lw["w_vb"][gl])], axis=-1)
        tensors[pre + "self_attn.kv_b_proj.weight"] = np.ascontiguousarray(kvb.reshape(dc, H * (dn + dv)).T)
        tensors[pre + "self_attn.o_proj.weight"] = _T(lw["wo"][gl])
        tensors[pre + "post_attention_layernorm.weight"] = _np(lw["post_norm"][gl])
        if dense:
            tensors[pre + "mlp.gate_proj.weight"] = _T(lw["gate"][gl])
            tensors[pre + "mlp.up_proj.weight"] = _T(lw["up"][gl])
            tensors[pre + "mlp.down_proj.weight"] = _T(lw["down"][gl])
        else:
            tensors[pre + "mlp.gate.weight"] = _T(lw["router"][gl])
            tensors[pre + "mlp.shared_experts.gate_proj.weight"] = _T(lw["shared_gate"][gl])
            tensors[pre + "mlp.shared_experts.up_proj.weight"] = _T(lw["shared_up"][gl])
            tensors[pre + "mlp.shared_experts.down_proj.weight"] = _T(lw["shared_down"][gl])
            for e in range(cfg.n_routed_experts):
                epre = pre + f"mlp.experts.{e}."
                tensors[epre + "gate_proj.weight"] = _T(lw["w_gate"][gl, e])
                tensors[epre + "up_proj.weight"] = _T(lw["w_up"][gl, e])
                tensors[epre + "down_proj.weight"] = _T(lw["w_down"][gl, e])
    save_file(tensors, str(tmp_path / "model.safetensors"))

    loaded_model, loaded_params = load_model(str(tmp_path))
    np.testing.assert_allclose(
        _prefill_logits(loaded_model, loaded_params),
        _prefill_logits(model, params),
        atol=1e-3,
    )


def test_qwen2_vl_checkpoint_roundtrip(tmp_path):
    """Text + vision towers: synthesize HF qwen2_vl names (conv3d patch embed,
    fused qkv, LayerNorm biases, merger MLP) and require identical mm logits."""
    hf_cfg = {
        "architectures": ["Qwen2VLForConditionalGeneration"],
        "model_type": "qwen2_vl",
        "vocab_size": 128,
        "hidden_size": 32,
        "intermediate_size": 64,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 8,
        "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5,
        "attention_bias": True,
        "vision_config": {
            "patch_size": 4,
            "in_channels": 3,
            "spatial_merge_size": 2,
            "embed_dim": 16,
            "intermediate_size": 32,
            "depth": 2,
            "num_heads": 2,
        },
    }
    (tmp_path / "config.json").write_text(json.dumps(hf_cfg))

    from dynamo_tpu.models.qwen2_vl import Qwen2VLConfig, Qwen2VLModel

    cfg = Qwen2VLConfig.from_hf_config(hf_cfg)
    model = Qwen2VLModel(cfg)
    params = model.init_params(jax.random.key(11))
    # exercise nonzero biases/norm offsets (init is zeros/ones)
    params = jax.tree.map(
        lambda x: x + 0.01 * jax.random.normal(jax.random.key(1), x.shape, jnp.float32).astype(x.dtype)
        if x.ndim <= 2 else x,
        params,
    )

    vc = cfg.vision
    tensors = {
        "model.embed_tokens.weight": _np(params["embed"]),
        "model.norm.weight": _np(params["final_norm"]),
        "lm_head.weight": _np(params["lm_head"]),
    }
    lw = params["layers"]
    for l in range(cfg.num_layers):
        pre = f"model.layers.{l}."
        tensors[pre + "input_layernorm.weight"] = _np(lw["input_norm"][l])
        tensors[pre + "self_attn.q_proj.weight"] = _T(lw["wq"][l])
        tensors[pre + "self_attn.k_proj.weight"] = _T(lw["wk"][l])
        tensors[pre + "self_attn.v_proj.weight"] = _T(lw["wv"][l])
        tensors[pre + "self_attn.o_proj.weight"] = _T(lw["wo"][l])
        tensors[pre + "self_attn.q_proj.bias"] = _np(lw["bq"][l])
        tensors[pre + "self_attn.k_proj.bias"] = _np(lw["bk"][l])
        tensors[pre + "self_attn.v_proj.bias"] = _np(lw["bv"][l])
        tensors[pre + "post_attention_layernorm.weight"] = _np(lw["post_norm"][l])
        tensors[pre + "mlp.gate_proj.weight"] = _T(lw["gate"][l])
        tensors[pre + "mlp.up_proj.weight"] = _T(lw["up"][l])
        tensors[pre + "mlp.down_proj.weight"] = _T(lw["down"][l])

    vis = params["vision"]
    # our linear [C*ps*ps, D] -> HF conv3d [D, C, T=2, ps, ps]; the loader sums
    # the temporal taps so split the weight across two taps to prove that path
    pe = _np(vis["patch_embed"]).reshape(vc.patch_size, vc.patch_size, vc.in_channels, vc.hidden_size)
    conv = pe.transpose(3, 2, 0, 1)  # [D, C, ps, ps]
    tap = conv / 2.0
    tensors["visual.patch_embed.proj.weight"] = np.ascontiguousarray(
        np.stack([tap, tap], axis=2)
    )
    vl = vis["layers"]
    for l in range(vc.num_layers):
        pre = f"visual.blocks.{l}."
        tensors[pre + "norm1.weight"] = _np(vl["norm1"][l])
        tensors[pre + "norm1.bias"] = _np(vl["norm1_b"][l])
        tensors[pre + "attn.qkv.weight"] = _T(vl["wqkv"][l])
        tensors[pre + "attn.qkv.bias"] = _np(vl["bqkv"][l])
        tensors[pre + "attn.proj.weight"] = _T(vl["wo"][l])
        tensors[pre + "attn.proj.bias"] = _np(vl["bo"][l])
        tensors[pre + "norm2.weight"] = _np(vl["norm2"][l])
        tensors[pre + "norm2.bias"] = _np(vl["norm2_b"][l])
        tensors[pre + "mlp.fc1.weight"] = _T(vl["fc1"][l])
        tensors[pre + "mlp.fc1.bias"] = _np(vl["bfc1"][l])
        tensors[pre + "mlp.fc2.weight"] = _T(vl["fc2"][l])
        tensors[pre + "mlp.fc2.bias"] = _np(vl["bfc2"][l])
    tensors["visual.merger.ln_q.weight"] = _np(vis["merger_norm"])
    tensors["visual.merger.ln_q.bias"] = _np(vis["merger_norm_b"])
    tensors["visual.merger.mlp.0.weight"] = _T(vis["merger_fc1"])
    tensors["visual.merger.mlp.0.bias"] = _np(vis["merger_bfc1"])
    tensors["visual.merger.mlp.2.weight"] = _T(vis["merger_fc2"])
    tensors["visual.merger.mlp.2.bias"] = _np(vis["merger_bfc2"])

    save_file(tensors, str(tmp_path / "model.safetensors"))
    loaded_model, loaded_params = load_model(str(tmp_path))
    assert type(loaded_model).__name__ == "Qwen2VLModel"

    from dynamo_tpu.llm.multimodal import image_content_hash, patchify, virtual_token_ids

    img = np.random.default_rng(4).random((16, 16, 3)).astype(np.float32)
    patches, rows, cols, _ = patchify(img, vc.patch_size, vc.spatial_merge_size)
    n_img = patches.shape[0] // vc.spatial_merge_size**2

    def mm_logits(m, p):
        emb = m.encode_images(
            p, jnp.asarray(patches), jnp.asarray(rows), jnp.asarray(cols),
            jnp.ones(len(rows), bool),
        )
        toks = [5, 9] + virtual_token_ids(image_content_hash(img), n_img, cfg.vocab_size) + [2]
        T = len(toks)
        Tp = 64
        tokens = np.zeros(Tp, np.int32)
        tokens[:T] = toks
        embeds = np.zeros((Tp, cfg.hidden_size), np.float32)
        embeds[2 : 2 + n_img] = np.asarray(emb, np.float32)
        mask = np.zeros(Tp, bool)
        mask[2 : 2 + n_img] = True
        positions = np.arange(Tp, dtype=np.int32)
        kv = m.init_kv_cache(32, 4)
        logits, _ = m.prefill(
            p, kv, jnp.asarray(tokens), jnp.asarray(positions),
            jnp.asarray(np.arange(1, 17, dtype=np.int32)),
            jnp.asarray(positions < T), jnp.asarray(T - 1),
            input_embeds=jnp.asarray(embeds), embeds_mask=jnp.asarray(mask),
        )
        return np.asarray(logits)

    ref = mm_logits(model, params)
    got = mm_logits(loaded_model, loaded_params)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
