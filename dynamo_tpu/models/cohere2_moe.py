"""Cohere2-MoE (`Cohere2MoeForCausalLM`, Command A+): a decoder whose layers
are PARALLEL blocks, `x = x + Attn(n) + FFN(n)` with ONE `n = LayerNorm(x)` a
layer, and whose attention layers come in two kinds (`layer_types`):

  `sliding_attention`  rope on q and k by INTERLEAVED PAIRS (`rope_gptj`,
                       ops/rotary.apply_rope_pairs); a query at position p sees
                       the keys in (p - `sliding_window`, p]
  `full_attention`     causal over the whole context, NO positional embedding

FFN of every layer: a sigmoid router over ALL experts routed over (float32,
no selection bias), top-k, weights normalised over the chosen
(`norm_topk_prob`), SwiGLU experts of `intermediate_size`, plus the AVERAGE of
`num_shared_experts` shared SwiGLU experts of the same width (stored
concatenated: one product a matrix). Tied embedding, a final LayerNorm.

Layer groups. The KV pool is ONE array of single-layer pages
`[num_pages, page_size, Hkv, D]`, and every attention layer has a page table
of its own over it: `page_tables` arrives as `[rows, kv_tables * width]`,
table-major. Layers of one kind form a group (`layer_groups`): the engine
allocates and gives back a group's pages together (engine/page_table.py
`GroupedPageAllocator`), so a sliding-window group holds its last
`sliding_window` tokens and no more, whatever the context. An entry behind the
window may be the null page: the kernels never read it.

An expert layer may hold a share of the experts (`num_experts` held, from
`moe_expert_offset`, of `moe_routed_over`): see ops/moe.py.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from dynamo_tpu.models.llama import parse_dtype
from dynamo_tpu.models.paged import DecodeStep, ExpertCounts, Pack, PackedPrefillModel, route
from dynamo_tpu.ops.attention import scatter_kv
from dynamo_tpu.ops.moe import grouped_matmul, moe_dispatch, sigmoid_topk_routing
from dynamo_tpu.ops.norms import layer_norm
from dynamo_tpu.ops.rotary import apply_rope_pairs

SLIDING, FULL = "sliding_attention", "full_attention"


class LayerGroup(NamedTuple):
    """Attention layers that keep the same tokens: `tables` are their indices
    among the model's page tables, `window` the tokens a layer of the group can
    still see behind its newest position (0: all of them)."""

    name: str
    tables: tuple
    window: int


@dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL)
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    rope_theta: float = 50000.0
    # experts HELD here, of how many routed over, from which id
    num_experts: int = 128
    moe_routed_over: int = 128
    moe_expert_offset: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    intermediate_size: int = 4096
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def routed_per_token(self) -> int:
        """Expert assignments one token makes through the whole model."""
        return self.num_experts_per_tok * self.num_layers

    @classmethod
    def from_hf_config(cls, d: dict) -> "Cohere2MoeConfig":
        layer_types = tuple(d["layer_types"])
        if len(layer_types) != d["num_hidden_layers"] or set(layer_types) - {SLIDING, FULL}:
            raise ValueError(
                f"layer_types must name num_hidden_layers={d['num_hidden_layers']} layers, "
                f"each {SLIDING} or {FULL}; got {layer_types}"
            )
        only = {
            "use_parallel_block": True, "use_qk_norm": False, "attention_bias": False,
            "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
            "shared_expert_combination_strategy": "average", "hidden_act": "silu",
            "use_gated_activation": True, "position_embedding_type": "rope_gptj",
            "rotary_pct": 1, "first_k_dense_replace": 0, "tie_word_embeddings": True,
        }
        for key, want in only.items():
            if d.get(key, want) != want:
                raise ValueError(f"cohere2_moe: {key}={d[key]!r} is not supported (only {want!r})")
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            layer_types=layer_types,
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"],
            sliding_window=int(d["sliding_window"]),
            rope_theta=float(d.get("rope_theta", 50000.0)),
            num_experts=d["num_experts"],
            moe_routed_over=d.get("moe_routed_over", d["num_experts"]),
            moe_expert_offset=d.get("moe_expert_offset", 0),
            num_experts_per_tok=d["num_experts_per_tok"],
            num_shared_experts=d["num_shared_experts"],
            intermediate_size=d["intermediate_size"],
            layer_norm_eps=d.get("layer_norm_eps") or 1e-5,
            logit_scale=float(d.get("logit_scale", 1.0)),
            dtype=parse_dtype(d.get("torch_dtype") or "bfloat16"),
        )

    @classmethod
    def tiny(cls, **overrides) -> "Cohere2MoeConfig":
        """Small config for tests: both kinds of layer, a window contexts pass
        several times over, a share of the experts."""
        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        if "layer_types" in overrides:
            overrides["layer_types"] = tuple(overrides["layer_types"])
        base = cls(
            vocab_size=256, hidden_size=64, layer_types=(SLIDING, SLIDING, FULL, SLIDING),
            num_heads=4, num_kv_heads=2, head_dim=16, sliding_window=32, rope_theta=50000.0,
            num_experts=4, moe_routed_over=8, moe_expert_offset=0, num_experts_per_tok=3,
            num_shared_experts=2, intermediate_size=48, dtype=jnp.float32,
        )
        return replace(base, **overrides)


class Cohere2MoeModel(PackedPrefillModel):
    """Stateless forward functions over a params pytree (models/paged.py's
    contract; `prefill` and `prefill_packed` are `PackedPrefillModel`'s over
    `_packed_forward`; the page tables come one per attention layer, see the
    module docstring). One chip (model_runner.layer_group_refusal), so
    `attn_mesh` stays None."""

    # ---------------- layer groups ----------------

    @property
    def kv_tables(self) -> int:
        """Page tables a sequence has: one per attention layer."""
        return self.config.num_layers

    @property
    def layer_groups(self) -> tuple:
        c = self.config
        window = tuple(i for i, k in enumerate(c.layer_types) if k == SLIDING)
        full = tuple(i for i, k in enumerate(c.layer_types) if k == FULL)
        groups = []
        if window:
            groups.append(LayerGroup("window", window, c.sliding_window))
        if full:
            groups.append(LayerGroup("full", full, 0))
        return tuple(groups)

    # ---------------- params ----------------

    def init_params(self, rng: jax.Array) -> dict:
        c = self.config
        keys = iter(jax.random.split(rng, 12 * c.num_layers + 2))

        def dense(shape, scale_axis=0, dtype=None):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            w = jax.random.normal(next(keys), shape, jnp.float32) * scale
            return w.astype(dtype or c.dtype)

        D, F, E = c.hidden_size, c.intermediate_size, c.num_experts
        Fs = c.num_shared_experts * F
        layers = []
        for _ in c.layer_types:
            layers.append(dict(
                norm=jnp.ones((D,), c.dtype),
                wq=dense((D, c.num_heads * c.head_dim)),
                wk=dense((D, c.num_kv_heads * c.head_dim)),
                wv=dense((D, c.num_kv_heads * c.head_dim)),
                wo=dense((c.num_heads * c.head_dim, D)),
                router=dense((D, c.moe_routed_over), dtype=jnp.float32),
                w_gate=dense((E, D, F), 1),
                w_up=dense((E, D, F), 1),
                w_down=dense((E, F, D), 1),
                shared_gate=dense((D, Fs)),
                shared_up=dense((D, Fs)),
                shared_down=dense((Fs, D)),
            ))
        return {
            "embed": dense((c.vocab_size, D), 1),
            "layers": layers,
            "final_norm": jnp.ones((D,), c.dtype),
        }

    # ---------------- the paged KV pool: single-layer pages ----------------

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        """A page holds K or V of `page_size` tokens in ONE layer."""
        c = self.config
        return (num_pages, page_size, c.num_kv_heads, c.head_dim)

    # ---------------- what rides beside the pool ----------------

    window_counters = ExpertCounts.NAMES

    def init_state_cache(self, max_seqs: int) -> dict:
        return ExpertCounts.leaves(self.config.num_experts)

    # ---------------- blocks ----------------

    def _attention(self, lp, kind, n, kv, positions, phys, offsets, attn_fn):
        """n [T, D] (normed), positions [T]. Returns (out [T, D], kv)."""
        c = self.config
        T = n.shape[0]
        # the outer scope says which kind of layer; inside it the innermost name
        # of the closed vocabulary is the part (benchmark/trace_parts.py PARTS):
        # rope and the cache write name themselves `attn_kv`
        with jax.named_scope("attn_window" if kind == SLIDING else "attn_full"):
            with jax.named_scope("attn_proj"):
                q = (n @ lp["wq"]).reshape(T, c.num_heads, c.head_dim)
                k = (n @ lp["wk"]).reshape(T, c.num_kv_heads, c.head_dim)
                v = (n @ lp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)
            if kind == SLIDING:
                q = apply_rope_pairs(q, positions, c.rope_theta)
                k = apply_rope_pairs(k, positions, c.rope_theta)
            k_pool, v_pool = scatter_kv(kv["k"], kv["v"], k, v, phys, offsets)
            with jax.named_scope("attn"):
                attn = attn_fn(q, k_pool, v_pool)
            with jax.named_scope("attn_proj"):
                return attn.reshape(T, -1) @ lp["wo"], dict(kv, k=k_pool, v=v_pool)

    def _experts(self, lp, n, count_rows=None):
        """n [T, D] -> (routed + shared [T, D], the held experts' assignment
        counts over the rows of `count_rows` (all rows when None))."""
        c = self.config
        weights, idx = route(
            n, lp["router"],
            lambda logits: sigmoid_topk_routing(
                logits, jnp.zeros((c.moe_routed_over,), jnp.float32), c.num_experts_per_tok),
            count_rows,
        )

        def ffn(rows, group_sizes):  # `moe_dispatch` calls it under `moe_experts`
            gated = jax.nn.silu(grouped_matmul(rows, lp["w_gate"], group_sizes))
            up = grouped_matmul(rows, lp["w_up"], group_sizes)
            return grouped_matmul(gated * up, lp["w_down"], group_sizes)

        routed, counts = moe_dispatch(
            n, weights, idx, ffn, num_held=c.num_experts, offset=c.moe_expert_offset
        )
        with jax.named_scope("shared_experts"):
            mid = jax.nn.silu(n @ lp["shared_gate"]) * (n @ lp["shared_up"])
            shared = (mid @ lp["shared_down"]).astype(jnp.float32) / c.num_shared_experts
            return (routed + shared).astype(c.dtype), counts

    def _unembed(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        c = self.config
        with jax.named_scope("lm_head"):
            h = layer_norm(hidden, params["final_norm"], c.layer_norm_eps)
            logits = jax.lax.dot_general(
                h, params["embed"], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return logits * c.logit_scale if c.logit_scale != 1.0 else logits

    def _window(self, kind: str) -> int:
        return self.config.sliding_window if kind == SLIDING else 0

    # ---------------- forward ----------------

    def _packed_forward(self, params, kv_cache, tokens, positions, page_tables, valid):
        """N lanes through every layer: each T consecutive rows of one
        sequence under that sequence's page tables, several of them one
        sequence's where its chunk rides as blocks (every layer scatters all
        lanes' rows before any lane's attention reads the pages).
        Returns (hidden [N*T, D], cache)."""
        c = self.config
        N, T = tokens.shape
        pack = Pack(page_tables, positions, valid, kv_cache["k"].shape[1], self.attn_mesh,
                    kv_tables=self.kv_tables)
        flat_pos = pack.flat_positions

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens.reshape(N * T)].astype(c.dtype)
        cache = kv_cache
        for l, (kind, lp) in enumerate(zip(c.layer_types, params["layers"])):
            at = pack.layer(l)
            n = layer_norm(hidden, lp["norm"], c.layer_norm_eps)
            # one lane at a time through ONE instance of the kernel (a loop,
            # not N copies: at 128 query heads Mosaic takes seconds to compile
            # each copy, in every packed program a server warms)
            attn, cache = self._attention(
                lp, kind, n, cache, flat_pos, at.phys.reshape(N * T), at.offsets,
                at.attend_mapped(self._window(kind)),
            )
            ffn, _ = self._experts(lp, n)
            with jax.named_scope("attn_proj"):  # the residual add of a parallel block
                hidden = hidden + attn + ffn
        return hidden, cache

    def decode(self, params, kv_cache, tokens, positions, page_tables, active,
               rope_deltas=None):
        """One decode step for the whole batch. Returns (logits [B, V], cache)."""
        c = self.config
        cache = kv_cache
        step = DecodeStep(page_tables, positions, active, cache["k"], c.head_dim, self.attn_mesh,
                          kv_tables=self.kv_tables)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(c.dtype)
        routed = ExpertCounts(cache)
        for l, (kind, lp) in enumerate(zip(c.layer_types, params["layers"])):
            at = step.layer(l)
            n = layer_norm(hidden, lp["norm"], c.layer_norm_eps)
            attn, cache = self._attention(
                lp, kind, n, cache, positions, at.phys, at.offsets,
                at.attend(window=self._window(kind)),
            )
            ffn, got = self._experts(lp, n, count_rows=active)
            routed.add(got)
            with jax.named_scope("attn_proj"):  # the residual add of a parallel block
                hidden = hidden + attn + ffn
        return self._unembed(params, hidden), routed.into(cache)
