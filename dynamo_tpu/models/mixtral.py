"""Mixtral-family MoE model: Llama attention + sparse top-k expert MLP.

Reuses the paged-attention layer machinery from LlamaModel; replaces the dense
MLP with the dropless MoE dispatch (dynamo_tpu/ops/moe.py). Expert weights
carry a leading [E] axis sharded over the mesh's "ep" axis; everything else
follows the Llama TP rules. Covers the reference's DeepSeek-V3/Mixtral MoE
target (BASELINE.md config 4; the reference itself delegates MoE to engines,
SURVEY.md §2.8).
"""

from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.llama import LlamaConfig, LlamaModel
from dynamo_tpu.ops.moe import moe_block
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.quant import qlinear, quantize_shardings_int8


@dataclass(frozen=True)
class MixtralConfig(LlamaConfig):
    num_experts: int = 8
    num_experts_per_tok: int = 2

    @classmethod
    def from_hf_config(cls, d: dict) -> "MixtralConfig":
        base = LlamaConfig.from_hf_config(d)
        return cls(
            **{f: getattr(base, f) for f in base.__dataclass_fields__},
            num_experts=d.get("num_local_experts", 8),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
        )

    @classmethod
    def tiny_moe(cls, **overrides) -> "MixtralConfig":
        from dynamo_tpu.models.llama import parse_dtype

        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        tiny = LlamaConfig.tiny()
        base = cls(
            **{f: getattr(tiny, f) for f in tiny.__dataclass_fields__},
            num_experts=4,
            num_experts_per_tok=2,
        )
        return replace(base, **overrides)


class MixtralModel(LlamaModel):
    #: the MoE _layer override predates the gathered LoRA pass; expert-bank
    #: adapter deltas need their own routing-aware treatment
    SUPPORTS_LORA = False

    #: attention matmuls + the per-expert FFN banks quantize; the router
    #: stays f32 (routing decisions are precision-sensitive and tiny)
    QUANT_WEIGHT_NAMES = frozenset(
        {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"}
    )

    # `expert_mesh`: set by ModelRunner where the engine's mesh has several
    # devices; the grouped product is then XLA's (ops/moe.grouped_matmul)

    def _init_raw_params(self, rng: jax.Array) -> dict:
        c = self.config
        params = super()._init_raw_params(rng)
        keys = iter(jax.random.split(jax.random.fold_in(rng, 1), 8))

        def dense(key, shape, scale_axis):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(c.dtype)

        L, D, F, E = c.num_layers, c.hidden_size, c.intermediate_size, c.num_experts
        layers = params["layers"]
        # replace the dense MLP with router + expert banks
        for k in ("gate", "up", "down"):
            del layers[k]
        layers["router"] = dense(next(keys), (L, D, E), 0).astype(jnp.float32)
        layers["w_gate"] = dense(next(keys), (L, E, D, F), 2)
        layers["w_up"] = dense(next(keys), (L, E, D, F), 2)
        layers["w_down"] = dense(next(keys), (L, E, F, D), 2)
        return params

    def param_shardings(self, mesh: Mesh, tp_axis: str = "tp", ep_axis: str = "ep") -> dict:
        shardings = super().param_shardings(mesh, tp_axis)
        layers = shardings["layers"]
        for k in ("gate", "up", "down"):
            del layers[k]
        ep = ep_axis if ep_axis in mesh.axis_names else None

        def ns(*spec):
            return NamedSharding(mesh, P(*spec))

        layers["router"] = ns(None, None, None)
        layers["w_gate"] = ns(None, ep, None, None)
        layers["w_up"] = ns(None, ep, None, None)
        layers["w_down"] = ns(None, ep, None, None)
        # second pass for the expert banks super() hadn't seen yet
        # (idempotent: the already-wrapped attention leaves skip)
        if self.config.quantize:
            shardings["layers"] = quantize_shardings_int8(
                shardings["layers"], self.QUANT_WEIGHT_NAMES
            )
        return shardings

    def _layer(self, lp, hidden, k_pool, v_pool, positions, flat_phys, offsets, attn_fn,
               rope_positions=None):
        # rope_positions (M-RoPE) accepted for base-class contract parity;
        # Mixtral is text-only so plain 1D RoPE always applies
        c = self.config
        T = hidden.shape[0]
        # attention sublayer identical to Llama
        from dynamo_tpu.ops.rotary import apply_rope
        from dynamo_tpu.ops.attention import scatter_kv

        h = rms_norm(hidden, lp["input_norm"], c.rms_norm_eps)
        q = apply_rope(qlinear(h, lp["wq"]).reshape(T, c.num_heads, c.head_dim), positions, c.rope_theta)
        k = apply_rope(qlinear(h, lp["wk"]).reshape(T, c.num_kv_heads, c.head_dim), positions, c.rope_theta)
        v = qlinear(h, lp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)
        k_pool, v_pool = scatter_kv(k_pool, v_pool, k, v, flat_phys, offsets)
        attn = attn_fn(q, k, v, k_pool, v_pool)
        hidden = hidden + qlinear(attn.reshape(T, -1), lp["wo"])

        # sparse MoE sublayer
        h = rms_norm(hidden, lp["post_norm"], c.rms_norm_eps)
        moe_out = moe_block(
            h,
            lp["router"],
            lp["w_gate"],
            lp["w_up"],
            lp["w_down"],
            num_experts_per_tok=c.num_experts_per_tok,
            mesh=self.expert_mesh,
        )
        hidden = hidden + moe_out
        return hidden, k_pool, v_pool
