"""LFM2-MoE (`Lfm2MoeForCausalLM`, LiquidAI LFM2-8B-A1B): a hybrid decoder whose
blocks are NOT one scanned stack. Every block is

    h = h + op(RMSNorm_operator(h));  h = h + ffn(RMSNorm_ffn(h))

on a residual in the model's dtype, with `op` by `layer_types`:

  `conv`            gated short convolution: `B, C, x = split3(W_in u)`;
                    `z_t = sum_k w_k (B*x)_{t-K+1+k}` per channel (depthwise,
                    causal, `conv_L_cache` taps, no bias, no activation);
                    `y = W_out (C * z)` (ops/ssm.causal_conv)
  `full_attention`  grouped-query, causal, RMSNorm per head on q and k (one
                    weight of head_dim, shared by the heads) BEFORE rope by
                    halves, on the paged KV pool (ops/attention.py)

and `ffn` by depth: the first `num_dense_layers` are a dense SwiGLU, the rest a
sigmoid router with a selection bias over `num_experts` SwiGLU experts, top-k
weights normalised over the chosen (ops/moe.py). Then `embedding_norm` and a
head tied to the embedding.

Two caches. The paged KV pool holds the attention blocks only, flat over them,
FOLDED (`[layers * pages, page_size, Hkv * head_dim]`: head_dim 64 is under a
lane row, see models/llama.py `kv_folded`). Beside it every conv block keeps,
per DECODE SLOT and not per page, the last `conv_L_cache - 1` values of `B*x`:
rows of one flat array laid out as models/nemotron_h.py lays its state (`slot`
of conv block m at row `m * (max_seqs + 1) + slot`, the last row of each block
a trash row for padding lanes; a chunk that starts at position 0 starts from
zeros, so a slot needs no clearing between sequences). There is no other state.

An expert layer may hold a share of the experts (`num_experts` held, from
`moe_expert_offset`, of `moe_routed_over`): see ops/moe.py. The published model
holds them all.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import parse_dtype
from dynamo_tpu.models.paged import (
    DecodeStep,
    ExpertCounts,
    Pack,
    PackedPrefillModel,
    route,
    state_rows,
)
from dynamo_tpu.ops.attention import scatter_kv
from dynamo_tpu.ops.moe import grouped_matmul, moe_dispatch, sigmoid_topk_routing
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.rotary import apply_rope
from dynamo_tpu.ops.ssm import causal_conv

CONV, ATTENTION = "conv", "full_attention"
#: the published `modeling_lfm2_moe` adds this to the sum of the chosen scores
ROUTING_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple = (CONV, CONV, ATTENTION, CONV)
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    conv_kernel: int = 3  # conv_L_cache
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    # experts HELD here, of how many routed over, from which id
    num_experts: int = 32
    moe_routed_over: int = 32
    moe_expert_offset: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def num_expert_layers(self) -> int:
        return max(0, self.num_layers - self.num_dense_layers)

    @property
    def routed_per_token(self) -> int:
        """Expert assignments one token makes through the whole model."""
        return self.num_experts_per_tok * self.num_expert_layers

    @classmethod
    def from_hf_config(cls, d: dict) -> "Lfm2MoeConfig":
        layer_types = tuple(d["layer_types"])
        if len(layer_types) != d["num_hidden_layers"] or set(layer_types) - {CONV, ATTENTION}:
            raise ValueError(
                f"layer_types must name num_hidden_layers={d['num_hidden_layers']} layers, "
                f"each {CONV} or {ATTENTION}; got {layer_types}"
            )
        only = {
            "conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True,
            "tie_word_embeddings": True,
        }
        for key, want in only.items():
            if d.get(key, want) != want:
                raise ValueError(f"lfm2_moe: {key}={d[key]!r} is not supported (only {want!r})")
        rope = d.get("rope_parameters") or {}
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            layer_types=layer_types,
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"],
            rope_theta=float(d.get("rope_theta") or rope.get("rope_theta") or 1e6),
            conv_kernel=int(d["conv_L_cache"]),
            num_dense_layers=int(d["num_dense_layers"]),
            intermediate_size=d["intermediate_size"],
            num_experts=d["num_experts"],
            moe_routed_over=d.get("moe_routed_over", d["num_experts"]),
            moe_expert_offset=d.get("moe_expert_offset", 0),
            num_experts_per_tok=d["num_experts_per_tok"],
            moe_intermediate_size=d["moe_intermediate_size"],
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            norm_eps=d.get("norm_eps") or 1e-5,
            dtype=parse_dtype(d.get("torch_dtype") or "bfloat16"),
        )

    @classmethod
    def tiny(cls, **overrides) -> "Lfm2MoeConfig":
        """Small config for tests: both operators and both kinds of FFN."""
        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        if "layer_types" in overrides:
            overrides["layer_types"] = tuple(overrides["layer_types"])
        base = cls(
            vocab_size=256, hidden_size=64, layer_types=(CONV, ATTENTION, CONV, CONV),
            num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=1e6, conv_kernel=3,
            num_dense_layers=1, intermediate_size=96,
            num_experts=8, moe_routed_over=8, moe_expert_offset=0, num_experts_per_tok=3,
            moe_intermediate_size=48, dtype=jnp.float32,
        )
        return replace(base, **overrides)


class Lfm2MoeModel(PackedPrefillModel):
    """Stateless forward functions over a params pytree (models/paged.py's
    contract; `prefill` and `prefill_packed` are `PackedPrefillModel`'s over
    `_packed_forward`, with the per-slot state: `state_slot(s)`). One chip
    (model_runner.recurrent_refusal), so `attn_mesh` stays None."""

    recurrent = True

    # ---------------- params ----------------

    def init_params(self, rng: jax.Array) -> dict:
        c = self.config
        keys = iter(jax.random.split(rng, 12 * c.num_layers + 2))

        def dense(shape, scale_axis=0, dtype=None):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            w = jax.random.normal(next(keys), shape, jnp.float32) * scale
            return w.astype(dtype or c.dtype)

        def small(shape):
            return jax.random.normal(next(keys), shape, jnp.float32) * 0.5

        D, F, Fm, E = c.hidden_size, c.intermediate_size, c.moe_intermediate_size, c.num_experts
        blocks = []
        for l, kind in enumerate(c.layer_types):
            bp = {"op_norm": jnp.ones((D,), c.dtype), "ffn_norm": jnp.ones((D,), c.dtype)}
            if kind == CONV:
                bp.update(
                    in_proj=dense((D, 3 * D)),
                    conv_w=small((c.conv_kernel, D)) + 0.5,
                    out_proj=dense((D, D)),
                )
            else:
                bp.update(
                    wq=dense((D, c.num_heads * c.head_dim)),
                    wk=dense((D, c.num_kv_heads * c.head_dim)),
                    wv=dense((D, c.num_kv_heads * c.head_dim)),
                    wo=dense((c.num_heads * c.head_dim, D)),
                    q_norm=(small((c.head_dim,)) * 0.2 + 1.0).astype(c.dtype),
                    k_norm=(small((c.head_dim,)) * 0.2 + 1.0).astype(c.dtype),
                )
            if l < c.num_dense_layers:
                bp.update(w1=dense((D, F)), w3=dense((D, F)), w2=dense((F, D)))
            else:
                bp.update(
                    router=dense((D, c.moe_routed_over), dtype=jnp.float32),
                    router_bias=small((c.moe_routed_over,)) * 0.1,
                    w1=dense((E, D, Fm), 1),
                    w3=dense((E, D, Fm), 1),
                    w2=dense((E, Fm, D), 1),
                )
            blocks.append(bp)
        return {
            "embed": dense((c.vocab_size, D), 1),
            "blocks": blocks,
            "final_norm": jnp.ones((D,), c.dtype),
        }

    # ---------------- the paged KV pool (attention blocks only, folded) ----------------

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        c = self.config
        return (c.count(ATTENTION) * num_pages, page_size, c.num_kv_heads * c.head_dim)

    # ---------------- the per-slot state cache (conv blocks) ----------------

    window_counters = ExpertCounts.NAMES

    def init_state_cache(self, max_seqs: int) -> dict:
        """`conv`, a row per (conv block, slot) plus each block's trash row,
        and the expert counters."""
        c = self.config
        rows = c.count(CONV) * (max_seqs + 1)
        return {
            "conv": jnp.zeros((rows, c.conv_kernel - 1, c.hidden_size), c.dtype),
            **ExpertCounts.leaves(c.num_experts),
        }

    # ---------------- blocks ----------------

    #: the residual add after an operator counts with the projection that feeds it
    RESIDUAL_PART = {CONV: "ssm_proj", ATTENTION: "attn_proj"}

    def _conv(self, bp, h, window, n_valid):
        """h [L, T, D]; window [L, K-1, D]: the `B*x` before the lane's rows;
        n_valid [L]. Returns (out [L, T, D], the window after each lane's last
        real row)."""
        c = self.config
        # parts by scope (benchmark/trace_parts.py PARTS): the projections are
        # `ssm_proj`; the gates, the taps and the state rows are `ssm`
        with jax.named_scope("ssm_proj"):
            B, C, x = jnp.split(h @ bp["in_proj"], 3, axis=-1)
        with jax.named_scope("ssm"):
            # the product in the model's dtype: the window keeps what the taps saw
            z, window = causal_conv(B * x, window, bp["conv_w"], None, n_valid)
            y = (C.astype(jnp.float32) * z).astype(c.dtype)
        with jax.named_scope("ssm_proj"):
            return y @ bp["out_proj"], window

    def _conv_prefill(self, bp, h, cache, rows, fresh, valid):
        """h [L, T, D]; rows [L] this block's state row per lane; fresh [L]:
        the lane starts its sequence; valid [L, T]."""
        with jax.named_scope("ssm"):
            window = jnp.where(fresh[:, None, None], 0, cache["conv"][rows])
            n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
        out, window = self._conv(bp, h, window, n_valid)
        with jax.named_scope("ssm"):
            return out, dict(cache, conv=cache["conv"].at[rows].set(window))

    def _conv_decode(self, bp, h, cache, base, active):
        """h [B, D]; batch row b's window is row base + b; rows that are not
        active leave it as it was."""
        mine = base + jnp.arange(h.shape[0])
        with jax.named_scope("ssm"):
            window = cache["conv"][mine]
        out, window = self._conv(bp, h[:, None, :], window, active.astype(jnp.int32))
        with jax.named_scope("ssm"):
            return out[:, 0], dict(cache, conv=cache["conv"].at[mine].set(window))

    def _attention(self, bp, h, kv, positions, flat_phys, offsets, attn_fn):
        """h [T, D], positions [T]. Per-head RMSNorm on q and k, then rope."""
        c = self.config
        T = h.shape[0]
        with jax.named_scope("attn_proj"):
            q = (h @ bp["wq"]).reshape(T, c.num_heads, c.head_dim)
            k = (h @ bp["wk"]).reshape(T, c.num_kv_heads, c.head_dim)
            v = (h @ bp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)
        with jax.named_scope("attn_kv"):
            q = rms_norm(q, bp["q_norm"], c.norm_eps)
            k = rms_norm(k, bp["k_norm"], c.norm_eps)
        q = apply_rope(q, positions, c.rope_theta)  # `attn_kv`, as the cache write
        k = apply_rope(k, positions, c.rope_theta)
        k_pool, v_pool = scatter_kv(kv["k"], kv["v"], k, v, flat_phys, offsets)
        with jax.named_scope("attn"):
            attn = attn_fn(q, k_pool, v_pool)
        with jax.named_scope("attn_proj"):
            return attn.reshape(T, -1) @ bp["wo"], dict(kv, k=k_pool, v=v_pool)

    def _dense_ffn(self, bp, h):
        with jax.named_scope("mlp"):
            return (jax.nn.silu(h @ bp["w1"]) * (h @ bp["w3"])) @ bp["w2"]

    def _experts(self, bp, h, count_rows=None):
        """h [T, D] -> (out [T, D], the held experts' assignment counts over
        the rows of `count_rows` (all rows when None))."""
        c = self.config
        weights, idx = route(
            h, bp["router"],
            lambda logits: sigmoid_topk_routing(
                logits, bp["router_bias"], c.num_experts_per_tok, c.routed_scaling_factor,
                eps=ROUTING_EPS),
            count_rows,
        )

        def ffn(rows, group_sizes):  # `moe_dispatch` calls it under `moe_experts`
            gated = jax.nn.silu(grouped_matmul(rows, bp["w1"], group_sizes))
            up = grouped_matmul(rows, bp["w3"], group_sizes)
            return grouped_matmul(gated * up, bp["w2"], group_sizes)

        routed, counts = moe_dispatch(
            h, weights, idx, ffn, num_held=c.num_experts, offset=c.moe_expert_offset
        )
        with jax.named_scope("moe_dispatch"):
            return routed.astype(c.dtype), counts

    def _unembed(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope("lm_head"):
            h = rms_norm(hidden, params["final_norm"], self.config.norm_eps)
            return jax.lax.dot_general(
                h, params["embed"], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    def _ffn_part(self, l: int) -> str:
        return "mlp" if l < self.config.num_dense_layers else "moe_dispatch"

    # ---------------- forward ----------------

    def _packed_forward(self, params, cache, tokens, positions, page_tables, valid, state_slots):
        """N lanes (chunks of N different sequences) through every block.
        Returns (hidden [N*T, D], cache)."""
        c = self.config
        N, T = tokens.shape
        num_pages = cache["k"].shape[0] // max(1, c.count(ATTENTION))
        slot_rows = cache["conv"].shape[0] // max(1, c.count(CONV))
        pack = Pack(page_tables, positions, valid, cache["k"].shape[1], self.attn_mesh)
        fresh, slots = state_rows(state_slots, slot_rows, positions)
        flat_pos = pack.flat_positions

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens.reshape(N * T)].astype(c.dtype)
        m = a = 0
        for l, (kind, bp) in enumerate(zip(c.layer_types, params["blocks"])):
            h = rms_norm(hidden, bp["op_norm"], c.norm_eps)
            if kind == CONV:
                out, cache = self._conv_prefill(
                    bp, h.reshape(N, T, -1), cache, m * slot_rows + slots, fresh, valid
                )
                out = out.reshape(N * T, -1)
                m += 1
            else:
                off = a * num_pages
                out, cache = self._attention(
                    bp, h, cache, flat_pos, off + pack.phys.reshape(N * T), pack.offsets,
                    pack.attend(off),
                )
                a += 1
            with jax.named_scope(self.RESIDUAL_PART[kind]):
                hidden = hidden + out
            h = rms_norm(hidden, bp["ffn_norm"], c.norm_eps)
            out = self._dense_ffn(bp, h) if l < c.num_dense_layers else self._experts(bp, h)[0]
            with jax.named_scope(self._ffn_part(l)):
                hidden = hidden + out
        return hidden, cache

    def decode(self, params, kv_cache, tokens, positions, page_tables, active,
               rope_deltas=None):
        """One decode step for the whole batch; batch row b is decode slot b.
        Returns (logits [B, V], cache)."""
        c = self.config
        cache = kv_cache
        num_pages = cache["k"].shape[0] // max(1, c.count(ATTENTION))
        slot_rows = cache["conv"].shape[0] // max(1, c.count(CONV))
        step = DecodeStep(page_tables, positions, active, cache["k"], c.head_dim, self.attn_mesh)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(c.dtype)
        routed = ExpertCounts(cache)
        m = a = 0
        for l, (kind, bp) in enumerate(zip(c.layer_types, params["blocks"])):
            h = rms_norm(hidden, bp["op_norm"], c.norm_eps)
            if kind == CONV:
                out, cache = self._conv_decode(bp, h, cache, m * slot_rows, active)
                m += 1
            else:
                off = a * num_pages
                out, cache = self._attention(
                    bp, h, cache, positions, off + step.phys, step.offsets, step.attend(off)
                )
                a += 1
            with jax.named_scope(self.RESIDUAL_PART[kind]):
                hidden = hidden + out
            h = rms_norm(hidden, bp["ffn_norm"], c.norm_eps)
            if l < c.num_dense_layers:
                out = self._dense_ffn(bp, h)
            else:
                out, n = self._experts(bp, h, count_rows=active)
                with jax.named_scope("moe_dispatch"):
                    routed.add(n)
            with jax.named_scope(self._ffn_part(l)):
                hidden = hidden + out
        return self._unembed(params, hidden), routed.into(cache)
