"""HF checkpoint -> scan-stacked JAX param loading (safetensors / torch .bin).

Weight name mapping follows the HF conventions per family; our layout is
[in, out] (HF nn.Linear stores [out, in]) with all layers stacked on a leading
axis. Allocation comes from ``jax.eval_shape(model.init_params, ...)`` so the
loader can never drift from the model's param tree: shapes, dtypes, and
presence of optional leaves (biases, tied lm_head) are all derived from the
single source of truth.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.utils import get_logger

log = get_logger("models.loader")


def _iter_checkpoint_tensors(path: Path):
    """Yield (name, np.ndarray) from safetensors shards or torch .bin files."""
    st_files = sorted(path.glob("*.safetensors"))
    if st_files:
        from safetensors import safe_open

        for f in st_files:
            with safe_open(str(f), framework="np") as sf:
                for name in sf.keys():
                    yield name, sf.get_tensor(name)
        return
    bin_files = sorted(path.glob("pytorch_model*.bin"))
    if bin_files:
        import torch

        for f in bin_files:
            state = torch.load(str(f), map_location="cpu", weights_only=True)
            for name, t in state.items():
                yield name, t.float().numpy()
        return
    raise FileNotFoundError(f"no safetensors or pytorch_model*.bin under {path}")


def _alloc_like(model):
    """(numpy f32 arrays, ShapeDtypeStruct tree) matching the model's RAW
    (pre-quantization) param tree — checkpoint tensors fill full-precision
    buffers; _finish applies the config's quantize mode once at the end."""
    shapes = jax.eval_shape(
        lambda key: model.init_params(key, quantize=False), jax.random.key(0)
    )
    arrays = jax.tree.map(lambda s: np.zeros(s.shape, np.float32), shapes)
    return arrays, shapes


def _finish(arrays, shapes, model=None):
    """Cast the filled numpy arrays to the model's exact leaf dtypes, then
    quantize (quantize="int8_wo" checkpoints: weight-only int8 conversion
    happens HERE, at load time — the serving stack never sees bf16 copies of
    the quantized weights)."""
    if model is not None and getattr(model.config, "quantize", None):
        params = jax.tree.map(lambda a, s: jnp.asarray(a, s.dtype), arrays, shapes)
        return model.quantize_params(params)
    # stay on the HOST: ModelRunner's device_put then moves each leaf straight
    # to its shards. jnp.asarray here would stage the whole tree on the
    # default device first — a 7B model at tp=4 does not fit one chip
    return jax.tree.map(lambda a, s: a.astype(s.dtype), arrays, shapes)


def _set_layer(group: dict, key: str, layer: int, tensor: np.ndarray, transpose: bool):
    t = tensor.T if transpose else tensor
    group[key][layer] = t.astype(np.float32)


def load_llama_weights(model: LlamaModel, path: Path) -> dict:
    c = model.config
    arrays, shapes = _alloc_like(model)
    layers = arrays["layers"]

    per_layer = {
        "input_layernorm.weight": ("input_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.bias": ("bv", False),
        "post_attention_layernorm.weight": ("post_norm", False),
        "mlp.gate_proj.weight": ("gate", True),
        "mlp.up_proj.weight": ("up", True),
        "mlp.down_proj.weight": ("down", True),
    }

    seen_embed = seen_head = False
    for name, tensor in _iter_checkpoint_tensors(path):
        if name == "model.embed_tokens.weight":
            arrays["embed"][:] = tensor.astype(np.float32)
            seen_embed = True
        elif name == "model.norm.weight":
            arrays["final_norm"][:] = tensor.astype(np.float32)
        elif name == "lm_head.weight" and "lm_head" in arrays:
            arrays["lm_head"][:] = tensor.astype(np.float32)
            seen_head = True
        elif name.startswith("model.layers."):
            rest = name[len("model.layers.") :]
            layer_str, sub = rest.split(".", 1)
            l = int(layer_str)
            mapping = per_layer.get(sub)
            if mapping is None or mapping[0] not in layers or l >= c.num_layers:
                log.debug("skipping unmapped weight %s", name)
                continue
            _set_layer(layers, mapping[0], l, tensor, mapping[1])
        else:
            log.debug("skipping unmapped weight %s", name)

    if not seen_embed:
        raise ValueError("checkpoint missing model.embed_tokens.weight")
    if "lm_head" in arrays and not seen_head:
        arrays["lm_head"][:] = arrays["embed"]
    return _finish(arrays, shapes, model)


def load_mixtral_weights(model, path: Path) -> dict:
    """HF Mixtral convention: attention matches Llama; the sparse MLP stores
    block_sparse_moe.gate (router) + per-expert w1 (gate), w2 (down), w3 (up)."""
    c = model.config
    arrays, shapes = _alloc_like(model)
    layers = arrays["layers"]

    per_layer = {
        "input_layernorm.weight": ("input_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("post_norm", False),
        "block_sparse_moe.gate.weight": ("router", True),
    }
    expert_map = {"w1": "w_gate", "w3": "w_up", "w2": "w_down"}

    seen_embed = seen_head = False
    for name, tensor in _iter_checkpoint_tensors(path):
        if name == "model.embed_tokens.weight":
            arrays["embed"][:] = tensor.astype(np.float32)
            seen_embed = True
        elif name == "model.norm.weight":
            arrays["final_norm"][:] = tensor.astype(np.float32)
        elif name == "lm_head.weight" and "lm_head" in arrays:
            arrays["lm_head"][:] = tensor.astype(np.float32)
            seen_head = True
        elif name.startswith("model.layers."):
            rest = name[len("model.layers.") :]
            layer_str, sub = rest.split(".", 1)
            l = int(layer_str)
            if l >= c.num_layers:
                log.debug("skipping out-of-range layer weight %s", name)
                continue
            if sub.startswith("block_sparse_moe.experts."):
                _, _, e_str, w_name, _ = sub.split(".")
                layers[expert_map[w_name]][l, int(e_str)] = tensor.T.astype(np.float32)
                continue
            mapping = per_layer.get(sub)
            if mapping is None:
                log.debug("skipping unmapped weight %s", name)
                continue
            _set_layer(layers, mapping[0], l, tensor, mapping[1])
        else:
            log.debug("skipping unmapped weight %s", name)

    if not seen_embed:
        raise ValueError("checkpoint missing model.embed_tokens.weight")
    if "lm_head" in arrays and not seen_head:
        arrays["lm_head"][:] = arrays["embed"]
    return _finish(arrays, shapes, model)


def load_deepseek_weights(model, path: Path) -> dict:
    """HF deepseek_v2/v3 convention -> the MLA param layout of
    dynamo_tpu/models/deepseek.py. kv_b_proj [H*(dn+dv), dc] splits into the
    k-up (w_kb) and v-up (w_vb) banks; layers partition into the leading dense
    group and the MoE group (first_k_dense_replace boundary). Names with a
    layer index >= num_layers (e.g. DeepSeek-V3's multi-token-prediction
    layer) are skipped, as are auxiliary tensors this serving stack doesn't
    model."""
    c = model.config
    arrays, shapes = _alloc_like(model)
    dn, dv, dc = c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank
    H = c.num_heads
    Ld = c.first_k_dense_replace

    attn_map = {
        "input_layernorm.weight": ("input_norm", False),
        "self_attn.q_proj.weight": ("w_q", True),
        "self_attn.q_a_proj.weight": ("w_dq", True),
        "self_attn.q_a_layernorm.weight": ("q_norm", False),
        "self_attn.q_b_proj.weight": ("w_uq", True),
        "self_attn.kv_a_proj_with_mqa.weight": ("w_dkv", True),
        "self_attn.kv_a_layernorm.weight": ("kv_norm", False),
        "self_attn.o_proj.weight": ("wo", True),
        "post_attention_layernorm.weight": ("post_norm", False),
        "mlp.gate_proj.weight": ("gate", True),
        "mlp.up_proj.weight": ("up", True),
        "mlp.down_proj.weight": ("down", True),
        "mlp.gate.weight": ("router", True),
        "mlp.shared_experts.gate_proj.weight": ("shared_gate", True),
        "mlp.shared_experts.up_proj.weight": ("shared_up", True),
        "mlp.shared_experts.down_proj.weight": ("shared_down", True),
    }
    expert_map = {"gate_proj": "w_gate", "up_proj": "w_up", "down_proj": "w_down"}

    seen_embed = seen_head = False
    for name, tensor in _iter_checkpoint_tensors(path):
        if name == "model.embed_tokens.weight":
            arrays["embed"][:] = tensor.astype(np.float32)
            seen_embed = True
        elif name == "model.norm.weight":
            arrays["final_norm"][:] = tensor.astype(np.float32)
        elif name == "lm_head.weight":
            arrays["lm_head"][:] = tensor.astype(np.float32)
            seen_head = True
        elif name.startswith("model.layers."):
            rest = name[len("model.layers.") :]
            layer_str, sub = rest.split(".", 1)
            l = int(layer_str)
            if l >= c.num_layers:
                log.debug("skipping out-of-range layer weight %s", name)
                continue
            group, gl = (
                (arrays["dense_layers"], l) if l < Ld else (arrays["moe_layers"], l - Ld)
            )
            if sub == "self_attn.kv_b_proj.weight":
                # [H*(dn+dv), dc] -> [dc, H, dn+dv] -> split k-up / v-up
                t = tensor.T.reshape(dc, H, dn + dv).astype(np.float32)
                group["w_kb"][gl] = t[..., :dn]
                group["w_vb"][gl] = t[..., dn:]
                continue
            if sub.startswith("mlp.experts."):
                _, _, e_str, w_name, _ = sub.split(".")
                group[expert_map[w_name]][gl, int(e_str)] = tensor.T.astype(np.float32)
                continue
            mapping = attn_map.get(sub)
            if mapping is None or mapping[0] not in group:
                log.debug("skipping unmapped weight %s", name)
                continue
            _set_layer(group, mapping[0], gl, tensor, mapping[1])
        else:
            log.debug("skipping unmapped weight %s", name)

    if not seen_embed:
        raise ValueError("checkpoint missing model.embed_tokens.weight")
    if not seen_head:
        arrays["lm_head"][:] = arrays["embed"]
    return _finish(arrays, shapes, model)


def load_qwen2_vl_weights(model, path: Path) -> dict:
    """HF qwen2_vl convention: text half matches Qwen2 (llama layout + qkv
    biases under ``model.``); the vision tower lives under ``visual.``:
    conv patch embed (conv3d over 2 duplicated temporal frames — folded into a
    single linear by summing the temporal taps, exact for static images),
    fused ``attn.qkv``, LayerNorm ``norm1``/``norm2``, ``mlp.fc1/fc2``, and
    the ``merger`` (ln_q + 2-layer MLP into the LLM hidden size)."""
    c = model.config
    arrays, shapes = _alloc_like(model)
    vis = arrays["vision"]
    vlayers = vis["layers"]
    vc = c.vision

    text_arrays = {k: v for k, v in arrays.items() if k != "vision"}

    per_layer = {
        "input_layernorm.weight": ("input_norm", False),
        "self_attn.q_proj.weight": ("wq", True),
        "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True),
        "self_attn.o_proj.weight": ("wo", True),
        "self_attn.q_proj.bias": ("bq", False),
        "self_attn.k_proj.bias": ("bk", False),
        "self_attn.v_proj.bias": ("bv", False),
        "post_attention_layernorm.weight": ("post_norm", False),
        "mlp.gate_proj.weight": ("gate", True),
        "mlp.up_proj.weight": ("up", True),
        "mlp.down_proj.weight": ("down", True),
    }
    vis_per_layer = {
        "norm1.weight": ("norm1", False),
        "norm1.bias": ("norm1_b", False),
        "attn.qkv.weight": ("wqkv", True),
        "attn.qkv.bias": ("bqkv", False),
        "attn.proj.weight": ("wo", True),
        "attn.proj.bias": ("bo", False),
        "norm2.weight": ("norm2", False),
        "norm2.bias": ("norm2_b", False),
        "mlp.fc1.weight": ("fc1", True),
        "mlp.fc1.bias": ("bfc1", False),
        "mlp.fc2.weight": ("fc2", True),
        "mlp.fc2.bias": ("bfc2", False),
    }
    merger_map = {
        "merger.ln_q.weight": "merger_norm",
        "merger.ln_q.bias": "merger_norm_b",
        "merger.mlp.0.bias": "merger_bfc1",
        "merger.mlp.2.bias": "merger_bfc2",
    }

    seen_embed = seen_head = False
    for name, tensor in _iter_checkpoint_tensors(path):
        if name == "model.embed_tokens.weight":
            text_arrays["embed"][:] = tensor.astype(np.float32)
            seen_embed = True
        elif name == "model.norm.weight":
            text_arrays["final_norm"][:] = tensor.astype(np.float32)
        elif name == "lm_head.weight" and "lm_head" in text_arrays:
            text_arrays["lm_head"][:] = tensor.astype(np.float32)
            seen_head = True
        elif name.startswith("model.layers."):
            rest = name[len("model.layers.") :]
            layer_str, sub = rest.split(".", 1)
            l = int(layer_str)
            mapping = per_layer.get(sub)
            if mapping is None or mapping[0] not in text_arrays["layers"] or l >= c.num_layers:
                log.debug("skipping unmapped weight %s", name)
                continue
            _set_layer(text_arrays["layers"], mapping[0], l, tensor, mapping[1])
        elif name == "visual.patch_embed.proj.weight":
            t = tensor.astype(np.float32)
            if t.ndim == 5:  # conv3d [D, C, T, ps, ps]: sum temporal taps
                t = t.sum(axis=2)
            # conv2d [D, C, ps, ps] -> linear [C*ps*ps, D] matching patchify's
            # pixel order (ps, ps, C) per patch
            t = t.transpose(2, 3, 1, 0).reshape(-1, t.shape[0])
            if t.shape != vis["patch_embed"].shape:
                raise ValueError(
                    f"patch_embed shape {t.shape} != {vis['patch_embed'].shape}"
                )
            vis["patch_embed"][:] = t
        elif name.startswith("visual.blocks."):
            rest = name[len("visual.blocks.") :]
            layer_str, sub = rest.split(".", 1)
            l = int(layer_str)
            mapping = vis_per_layer.get(sub)
            if mapping is None or l >= vc.num_layers:
                log.debug("skipping unmapped weight %s", name)
                continue
            _set_layer(vlayers, mapping[0], l, tensor, mapping[1])
        elif name == "visual.merger.mlp.0.weight":
            vis["merger_fc1"][:] = tensor.T.astype(np.float32)
        elif name == "visual.merger.mlp.2.weight":
            vis["merger_fc2"][:] = tensor.T.astype(np.float32)
        elif name[len("visual.") :] in merger_map and name.startswith("visual."):
            vis[merger_map[name[len("visual.") :]]][:] = tensor.astype(np.float32)
        else:
            log.debug("skipping unmapped weight %s", name)

    if not seen_embed:
        raise ValueError("checkpoint missing model.embed_tokens.weight")
    if "lm_head" in text_arrays and not seen_head:
        text_arrays["lm_head"][:] = text_arrays["embed"]
    return _finish(arrays, shapes, model)


def load_nemotron_h_weights(model, path: Path) -> dict:
    """NemotronH (`backbone.layers.N.mixer.*`): blocks are a list, not a
    stack, and the leaves are filled in their own dtype (the expert banks of
    one chip's share are gigabytes; a float32 staging copy would double them).
    Experts are read from `experts.0 ..` up to the count the config holds.
    The copies run on a pool of threads: HF's [out, in] layout is transposed
    into place, which one core does at 0.5 GB/s (20 s for the 9.3 GB share)."""
    import os
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    arrays = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    per_block = {
        "in_proj.weight": ("in_proj", True), "conv1d.bias": ("conv_b", False),
        "dt_bias": ("dt_bias", False), "A_log": ("A_log", False), "D": ("D", False),
        "norm.weight": ("mixer_norm", False), "out_proj.weight": ("out_proj", True),
        "q_proj.weight": ("wq", True), "k_proj.weight": ("wk", True),
        "v_proj.weight": ("wv", True), "o_proj.weight": ("wo", True),
        "gate.weight": ("router", True),
        "gate.e_score_correction_bias": ("router_bias", False),
        "fc1_latent_proj.weight": ("lat_down", True),
        "fc2_latent_proj.weight": ("lat_up", True),
        "shared_experts.up_proj.weight": ("shared_up", True),
        "shared_experts.down_proj.weight": ("shared_down", True),
    }
    top = {"backbone.embeddings.weight": "embed", "backbone.norm_f.weight": "final_norm",
           "lm_head.weight": "lm_head"}
    blocks = arrays["blocks"]
    filled = set()
    pending: deque = deque()
    with ThreadPoolExecutor(min(16, os.cpu_count() or 4)) as pool:

        def put(dest: np.ndarray, src: np.ndarray) -> None:
            pending.append(pool.submit(dest.__setitem__, ..., src))
            if len(pending) > 256:  # bounds the tensors read and not yet copied
                pending.popleft().result()

        for name, tensor in _iter_checkpoint_tensors(path):
            if name in top:
                put(arrays[top[name]], tensor)
                filled.add(top[name])
                continue
            if not name.startswith("backbone.layers."):
                log.debug("skipping unmapped weight %s", name)
                continue
            layer_str, sub = name[len("backbone.layers."):].split(".", 1)
            l = int(layer_str)
            if l >= len(blocks):
                continue
            bp = blocks[l]
            if sub == "norm.weight":
                put(bp["norm"], tensor)
            elif sub == "mixer.conv1d.weight":  # [C, 1, K] -> taps first
                put(bp["conv_w"], tensor[:, 0, :].T)
            elif sub.startswith("mixer.experts."):
                e_str, which = sub[len("mixer.experts."):].split(".", 1)
                e = int(e_str)
                key = {"up_proj.weight": "w1", "down_proj.weight": "w2"}.get(which)
                if key is not None and e < bp[key].shape[0]:
                    put(bp[key][e], tensor.T)
            else:
                key, transpose = per_block.get(sub[len("mixer."):], (None, False))
                if key is None or key not in bp:
                    log.debug("skipping unmapped weight %s", name)
                    continue
                put(bp[key], tensor.T if transpose else tensor)
        for done in pending:
            done.result()
    missing = {"embed", "final_norm", "lm_head"} - filled
    if missing:
        raise ValueError(f"checkpoint {path} lacks {sorted(missing)}")
    return arrays


def load_cohere2_moe_weights(model, path: Path) -> dict:
    """Cohere2-MoE (`model.layers.N.{input_layernorm, self_attn.*, mlp.*}`):
    layers are a list, the leaves are filled in their own dtype on a pool of
    threads (as `load_nemotron_h_weights`: the expert banks of one chip's share
    are gigabytes). Routed experts are read from `mlp.experts.0 ..` up to the
    count the config holds; the shared experts `mlp.shared_experts.0 ..` go
    side by side into one matrix each (their outputs are averaged, so the
    concatenated product divided by their number is the same sum)."""
    import os
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    arrays = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    F = model.config.intermediate_size
    per_layer = {
        "input_layernorm.weight": ("norm", False),
        "self_attn.q_proj.weight": ("wq", True), "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True), "self_attn.o_proj.weight": ("wo", True),
        "mlp.gate.weight": ("router", True),
    }
    routed = {"gate_proj.weight": "w_gate", "up_proj.weight": "w_up", "down_proj.weight": "w_down"}
    top = {"model.embed_tokens.weight": "embed", "model.norm.weight": "final_norm"}
    layers = arrays["layers"]
    filled = set()
    pending: deque = deque()
    with ThreadPoolExecutor(min(16, os.cpu_count() or 4)) as pool:

        def put(dest: np.ndarray, src: np.ndarray) -> None:
            pending.append(pool.submit(dest.__setitem__, ..., src))
            if len(pending) > 256:  # bounds the tensors read and not yet copied
                pending.popleft().result()

        for name, tensor in _iter_checkpoint_tensors(path):
            if name in top:
                put(arrays[top[name]], tensor)
                filled.add(top[name])
                continue
            if not name.startswith("model.layers."):
                log.debug("skipping unmapped weight %s", name)
                continue
            layer_str, sub = name[len("model.layers."):].split(".", 1)
            if int(layer_str) >= len(layers):
                continue
            lp = layers[int(layer_str)]
            if sub.startswith("mlp.experts."):
                e_str, which = sub[len("mlp.experts."):].split(".", 1)
                if which in routed and int(e_str) < lp["w_gate"].shape[0]:
                    put(lp[routed[which]][int(e_str)], tensor.T)
            elif sub.startswith("mlp.shared_experts."):
                j_str, which = sub[len("mlp.shared_experts."):].split(".", 1)
                at = slice(int(j_str) * F, (int(j_str) + 1) * F)
                if which == "down_proj.weight":
                    put(lp["shared_down"][at], tensor.T)
                elif which in routed:
                    put(lp["shared_" + which.split("_")[0]][:, at], tensor.T)
            elif sub in per_layer:
                key, transpose = per_layer[sub]
                put(lp[key], tensor.T if transpose else tensor)
            else:
                log.debug("skipping unmapped weight %s", name)
        for done in pending:
            done.result()
    missing = {"embed", "final_norm"} - filled
    if missing:
        raise ValueError(f"checkpoint {path} lacks {sorted(missing)}")
    return arrays


def load_lfm2_moe_weights(model, path: Path) -> dict:
    """LFM2-MoE (`model.layers.N.{operator_norm, ffn_norm, conv.*, self_attn.*,
    feed_forward.*}`, `model.embedding_norm`; the head is the embedding):
    blocks are a list, the leaves are filled in their own dtype on a pool of
    threads (as `load_nemotron_h_weights`: the expert banks are gigabytes).
    `conv.in_proj`'s output thirds are B, C, x in that order; `conv.conv.weight`
    [C, 1, K] goes taps first. Experts are read from `feed_forward.experts.0 ..`
    up to the count the config holds."""
    import os
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    arrays = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    per_block = {
        "operator_norm.weight": ("op_norm", False), "ffn_norm.weight": ("ffn_norm", False),
        "conv.in_proj.weight": ("in_proj", True), "conv.out_proj.weight": ("out_proj", True),
        "self_attn.q_proj.weight": ("wq", True), "self_attn.k_proj.weight": ("wk", True),
        "self_attn.v_proj.weight": ("wv", True), "self_attn.out_proj.weight": ("wo", True),
        "self_attn.q_layernorm.weight": ("q_norm", False),
        "self_attn.k_layernorm.weight": ("k_norm", False),
        "feed_forward.w1.weight": ("w1", True), "feed_forward.w2.weight": ("w2", True),
        "feed_forward.w3.weight": ("w3", True),
        "feed_forward.gate.weight": ("router", True),
        "feed_forward.expert_bias": ("router_bias", False),
    }
    top = {"model.embed_tokens.weight": "embed", "model.embedding_norm.weight": "final_norm"}
    blocks = arrays["blocks"]
    filled = set()
    pending: deque = deque()
    with ThreadPoolExecutor(min(16, os.cpu_count() or 4)) as pool:

        def put(dest: np.ndarray, src: np.ndarray) -> None:
            pending.append(pool.submit(dest.__setitem__, ..., src))
            if len(pending) > 256:  # bounds the tensors read and not yet copied
                pending.popleft().result()

        for name, tensor in _iter_checkpoint_tensors(path):
            if name in top:
                put(arrays[top[name]], tensor)
                filled.add(top[name])
                continue
            if not name.startswith("model.layers."):
                log.debug("skipping unmapped weight %s", name)
                continue
            layer_str, sub = name[len("model.layers."):].split(".", 1)
            if int(layer_str) >= len(blocks):
                continue
            bp = blocks[int(layer_str)]
            if sub == "conv.conv.weight":  # [C, 1, K] -> taps first
                put(bp["conv_w"], tensor[:, 0, :].T)
            elif sub.startswith("feed_forward.experts."):
                e_str, which = sub[len("feed_forward.experts."):].split(".", 1)
                key = which[: -len(".weight")]
                if key in ("w1", "w2", "w3") and int(e_str) < bp[key].shape[0]:
                    put(bp[key][int(e_str)], tensor.T)
            else:
                key, transpose = per_block.get(sub, (None, False))
                if key is None or key not in bp:
                    log.debug("skipping unmapped weight %s", name)
                    continue
                put(bp[key], tensor.T if transpose else tensor)
        for done in pending:
            done.result()
    missing = {"embed", "final_norm"} - filled
    if missing:
        raise ValueError(f"checkpoint {path} lacks {sorted(missing)}")
    return arrays


def load_falcon_h1_weights(model, path: Path) -> dict:
    """Falcon-H1 (`model.layers.N.{input_layernorm, pre_ff_layernorm, mamba.*,
    self_attn.*, feed_forward.*}`, `model.final_layernorm`, an untied
    `lm_head`): the layers are one stack, and the leaves are filled in their
    own dtype on a pool of threads (as `load_nemotron_h_weights`: a float32
    staging copy of 10.5 GB would double it). `mamba.in_proj`'s output rows are
    z | x B C | dt in that order and become three matrices;
    `mamba.conv1d.weight` [C, 1, K] goes taps first. No multiplier is folded
    into a matrix."""
    import os
    from collections import deque
    from concurrent.futures import ThreadPoolExecutor

    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    arrays = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), shapes)
    per_layer = {
        "input_layernorm.weight": ("input_norm", False),
        "pre_ff_layernorm.weight": ("pre_ff_norm", False),
        "mamba.conv1d.bias": ("conv_b", False),
        "mamba.dt_bias": ("dt_bias", False), "mamba.A_log": ("A_log", False),
        "mamba.D": ("D", False), "mamba.norm.weight": ("mixer_norm", False),
        "mamba.out_proj.weight": ("out_proj", True),
        # q, k and v stay [out, in] as the checkpoint has them (models/falcon_h1.py says why)
        "self_attn.q_proj.weight": ("wq", False), "self_attn.k_proj.weight": ("wk", False),
        "self_attn.v_proj.weight": ("wv", False), "self_attn.o_proj.weight": ("wo", True),
        "feed_forward.gate_proj.weight": ("gate", True),
        "feed_forward.up_proj.weight": ("up", True),
        "feed_forward.down_proj.weight": ("down", True),
    }
    top = {"model.embed_tokens.weight": "embed", "model.final_layernorm.weight": "final_norm",
           "lm_head.weight": "lm_head"}
    layers = arrays["layers"]
    filled = set()
    pending: deque = deque()
    with ThreadPoolExecutor(min(16, os.cpu_count() or 4)) as pool:

        def put(dest: np.ndarray, src: np.ndarray) -> None:
            pending.append(pool.submit(dest.__setitem__, ..., src))
            if len(pending) > 64:  # bounds the tensors read and not yet copied
                pending.popleft().result()

        for name, tensor in _iter_checkpoint_tensors(path):
            if name in top:
                put(arrays[top[name]], tensor)
                filled.add(top[name])
                continue
            if not name.startswith("model.layers."):
                log.debug("skipping unmapped weight %s", name)
                continue
            layer_str, sub = name[len("model.layers."):].split(".", 1)
            l = int(layer_str)
            if l >= model.config.num_layers:
                continue
            if sub == "mamba.conv1d.weight":  # [C, 1, K] -> taps first
                put(layers["conv_w"][l], tensor[:, 0, :].T)
                filled.add((l, "conv_w"))
                continue
            if sub == "mamba.in_proj.weight":  # rows z | x B C | dt, a matrix each
                at = 0
                for key in ("in_z", "in_xbc", "in_dt"):
                    width = layers[key].shape[-1]
                    put(layers[key][l], tensor[at:at + width].T)
                    filled.add((l, key))
                    at += width
                continue
            key, transpose = per_layer.get(sub, (None, False))
            if key is None:
                log.debug("skipping unmapped weight %s", name)
                continue
            put(layers[key][l], tensor.T if transpose else tensor)
            filled.add((l, key))
        for done in pending:
            done.result()
    want = set(top.values()) | {(l, k) for l in range(model.config.num_layers) for k in layers}
    if want - filled:
        raise ValueError(f"checkpoint {path} lacks {sorted(map(str, want - filled))[:8]}")
    return arrays
