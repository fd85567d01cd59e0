"""Model registry: model_id -> (model, params).

Supported ids:
  - ``tiny`` / ``tiny:<json-overrides>``: random-weight test model (and
    ``tiny-moe``, ``tiny-mla``, ``tiny-vl``, ``tiny-hybrid``, ``tiny-window``,
    ``tiny-conv``, ``tiny-parallel`` likewise)
  - a local HuggingFace checkpoint directory (config.json [+ safetensors])

The reference resolves models from HF repos via its model-deployment-card
machinery (reference: lib/llm/src/model_card/create.rs, launch/dynamo-run/src/hub.rs);
here local directories fill that role (zero-egress environment).
"""

from __future__ import annotations

import json
from pathlib import Path

import jax

from dynamo_tpu.models.llama import LlamaConfig, LlamaModel
from dynamo_tpu.utils import get_logger

log = get_logger("models.registry")

# single-entry params cache for the synthetic "tiny*" families: colocated
# engines serving the SAME model (disagg prefill+decode pairs, router
# replicas, a test's engine fleets) share one set of immutable weight
# buffers instead of materializing a copy each — params are never donated
# (only kv/slot_state are), and ModelRunner's device_put is a no-op when the
# sharding already matches, so sharing is safe. One entry only (loading a
# different model evicts the previous), and checkpoint DIRECTORIES are never
# cached: their content can change under the same path, and pinning a real
# model's host tree for process lifetime is not worth it. Written as one
# atomic (key, value) tuple: load_model runs on executor threads.
_cache: tuple | None = None  # ((model_id, seed), (model_cls, config, params))


#: checkpoint architectures (config.json `architectures[0]`, by exact name) ->
#: (module, config class, model class, loader in models/loader.py). A
#: checkpoint that names none is read as a Llama.
ARCHITECTURES = {
    "LlamaForCausalLM": ("llama", "LlamaConfig", "LlamaModel", "load_llama_weights"),
    "MistralForCausalLM": ("llama", "LlamaConfig", "LlamaModel", "load_llama_weights"),
    "Qwen2ForCausalLM": ("llama", "LlamaConfig", "LlamaModel", "load_llama_weights"),
    "MixtralForCausalLM": ("mixtral", "MixtralConfig", "MixtralModel", "load_mixtral_weights"),
    "DeepseekV2ForCausalLM": ("deepseek", "DeepseekConfig", "DeepseekModel", "load_deepseek_weights"),
    "DeepseekV3ForCausalLM": ("deepseek", "DeepseekConfig", "DeepseekModel", "load_deepseek_weights"),
    "Qwen2VLForConditionalGeneration": (
        "qwen2_vl", "Qwen2VLConfig", "Qwen2VLModel", "load_qwen2_vl_weights"),
    "NemotronHForCausalLM": (
        "nemotron_h", "NemotronHConfig", "NemotronHModel", "load_nemotron_h_weights"),
    "Cohere2MoeForCausalLM": (
        "cohere2_moe", "Cohere2MoeConfig", "Cohere2MoeModel", "load_cohere2_moe_weights"),
    "Lfm2MoeForCausalLM": (
        "lfm2_moe", "Lfm2MoeConfig", "Lfm2MoeModel", "load_lfm2_moe_weights"),
    "FalconH1ForCausalLM": (
        "falcon_h1", "FalconH1Config", "FalconH1Model", "load_falcon_h1_weights"),
}


#: the tiny families whose config class has a `tiny(**overrides)`
_TINY_FAMILIES = {
    "tiny-hybrid": ("nemotron_h", "NemotronHConfig", "NemotronHModel"),
    "tiny-window": ("cohere2_moe", "Cohere2MoeConfig", "Cohere2MoeModel"),
    "tiny-conv": ("lfm2_moe", "Lfm2MoeConfig", "Lfm2MoeModel"),
    "tiny-parallel": ("falcon_h1", "FalconH1Config", "FalconH1Model"),
}


def _resolve(entry: tuple):
    """(config class, model class, loader) of an ARCHITECTURES entry; the
    model's module is imported only when a checkpoint asks for it."""
    import importlib

    module, config_cls, model_cls, loader = entry
    mod = importlib.import_module(f"dynamo_tpu.models.{module}")
    loaders = importlib.import_module("dynamo_tpu.models.loader")
    return getattr(mod, config_cls), getattr(mod, model_cls), getattr(loaders, loader)



def is_tiny_family(model_id) -> bool:
    """Exactly the synthetic tiny-family forms this registry special-cases —
    NOT any path that merely starts with "tiny": a checkpoint directory named
    tinyllama-1.1b/ is a real model and must be treated as one (not cached
    here, not given the byte-tokenizer tiny card by callers)."""
    if model_id is None:
        return True
    s = str(model_id)
    for fam in ("tiny", "tiny-moe", "tiny-mla", "tiny-vl", *_TINY_FAMILIES):
        if s == fam or s.startswith(fam + ":"):
            return True
    return False


_cacheable = is_tiny_family


def load_model(model_id: str, seed: int = 0, quantize: str | None = None,
               kv_cache_dtype: str | None = None):
    """Returns (model, params); for tiny-family models params may be shared
    with other engines in this process — treat as immutable.

    ``quantize`` ("int8_wo") applies weight-only quantization at load time —
    tiny families quantize their random init, checkpoint models quantize in
    the loader's _finish step. ``kv_cache_dtype`` ("int8") sets the KV cache
    storage dtype on the model config (pages are int8 + per-row scales,
    quant/kv.py) — llama-family pools only; the MLA latent cache raises. A
    mode embedded in a tiny:{...} override JSON works too; the explicit
    argument wins when both are set."""
    global _cache
    if kv_cache_dtype == "bf16":
        kv_cache_dtype = None  # the default storage dtype, spelled out
    key = (model_id, seed, quantize, kv_cache_dtype)
    entry = _cache
    if entry is not None and entry[0] == key:
        model_cls, cfg, params = entry[1]
        return model_cls(cfg), params  # fresh model object: attn_mesh is per-engine
    model, params = _load_model_uncached(model_id, seed, quantize, kv_cache_dtype)
    if kv_cache_dtype and not model.SUPPORTS_KV_INT8:
        raise ValueError(
            f"kv_cache_dtype={kv_cache_dtype!r} is not supported by "
            f"{type(model).__name__} (the MLA latent cache is its own "
            "compression; int8 KV covers the k/v page-pool families)"
        )
    if _cacheable(model_id):
        _cache = (key, (type(model), model.config, params))
    return model, params


def _load_model_uncached(model_id: str, seed: int = 0, quantize: str | None = None,
                         kv_cache_dtype: str | None = None):
    """Returns (model, params) on host (unsharded); caller places onto mesh."""
    import dataclasses

    def with_quant(cfg):
        replace = {}
        fields = getattr(cfg, "__dataclass_fields__", {})
        if quantize and "quantize" not in fields:
            raise ValueError(
                f"quantize={quantize!r} is not supported by {type(cfg).__name__}"
            )
        if quantize:
            replace["quantize"] = quantize
        if kv_cache_dtype and "kv_cache_dtype" in getattr(
            cfg, "__dataclass_fields__", {}
        ):
            replace["kv_cache_dtype"] = kv_cache_dtype
        elif kv_cache_dtype:
            raise ValueError(
                f"kv_cache_dtype={kv_cache_dtype!r} is not supported by "
                f"{type(cfg).__name__}"
            )
        return dataclasses.replace(cfg, **replace) if replace else cfg

    if model_id is not None and (model_id == "tiny-moe" or model_id.startswith("tiny-moe:")):
        from dynamo_tpu.models.mixtral import MixtralConfig, MixtralModel

        overrides = json.loads(model_id.split(":", 1)[1]) if ":" in model_id else {}
        cfg = with_quant(MixtralConfig.tiny_moe(**overrides))
        model = MixtralModel(cfg)
        params = jax.jit(lambda key: model.init_params(key))(jax.random.key(seed))
        jax.block_until_ready(params)
        return model, params

    if model_id is not None and (model_id == "tiny-mla" or model_id.startswith("tiny-mla:")):
        from dynamo_tpu.models.deepseek import DeepseekConfig, DeepseekModel

        overrides = json.loads(model_id.split(":", 1)[1]) if ":" in model_id else {}
        cfg = with_quant(DeepseekConfig.tiny_mla(**overrides))
        model = DeepseekModel(cfg)
        params = jax.jit(lambda key: model.init_params(key))(jax.random.key(seed))
        jax.block_until_ready(params)
        return model, params

    for family, (module, config_cls, model_cls) in _TINY_FAMILIES.items():
        if model_id is not None and (model_id == family or model_id.startswith(family + ":")):
            import importlib

            mod = importlib.import_module(f"dynamo_tpu.models.{module}")
            overrides = json.loads(model_id.split(":", 1)[1]) if ":" in model_id else {}
            model = getattr(mod, model_cls)(with_quant(getattr(mod, config_cls).tiny(**overrides)))
            params = jax.jit(model.init_params)(jax.random.key(seed))
            jax.block_until_ready(params)
            return model, params

    if model_id is not None and (model_id == "tiny-vl" or model_id.startswith("tiny-vl:")):
        from dynamo_tpu.models.qwen2_vl import Qwen2VLConfig, Qwen2VLModel

        overrides = json.loads(model_id.split(":", 1)[1]) if ":" in model_id else {}
        cfg = with_quant(Qwen2VLConfig.tiny_vl(**overrides))
        model = Qwen2VLModel(cfg)
        params = jax.jit(lambda key: model.init_params(key))(jax.random.key(seed))
        jax.block_until_ready(params)
        return model, params

    if model_id is None or model_id == "tiny" or model_id.startswith("tiny:"):
        overrides = {}
        if model_id and ":" in model_id:
            overrides = json.loads(model_id.split(":", 1)[1])
        cfg = with_quant(LlamaConfig.tiny(**overrides))
        model = LlamaModel(cfg)
        # single jitted init: one compile for the whole tree
        params = jax.jit(lambda key: model.init_params(key))(jax.random.key(seed))
        jax.block_until_ready(params)
        return model, params

    path = Path(model_id)
    if path.is_dir() and (path / "config.json").exists():
        hf_cfg = json.loads((path / "config.json").read_text())
        arch = (hf_cfg.get("architectures") or ["LlamaForCausalLM"])[0]
        entry = ARCHITECTURES.get(arch)
        if entry is None:
            raise ValueError(
                f"unsupported architecture {arch!r}; supported: {sorted(ARCHITECTURES)}"
            )
        config_cls, model_cls, loader = _resolve(entry)
        cfg = with_quant(config_cls.from_hf_config(hf_cfg))
        model = model_cls(cfg)
        return model, loader(model, path)

    raise ValueError(f"unknown model id {model_id!r} (not 'tiny' and not a local checkpoint dir)")
