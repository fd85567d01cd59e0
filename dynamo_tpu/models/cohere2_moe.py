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
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.llama import parse_dtype
from dynamo_tpu.ops.attention import (
    decode_tile_runs,
    dispatch_paged_decode_attention,
    dispatch_paged_prefill_attention,
    scatter_kv,
)
from dynamo_tpu.ops.live_rows import live_rows
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


class Cohere2MoeModel:
    """Stateless forward functions over a params pytree (models/llama.py's
    contract; the page tables come one per attention layer, see the module
    docstring)."""

    SUPPORTS_LORA = False
    SUPPORTS_KV_INT8 = False

    def __init__(self, config: Cohere2MoeConfig):
        self.config = config
        self.attn_mesh = None  # one chip: see model_runner.layer_group_refusal

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

    def param_shardings(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        shapes = jax.eval_shape(self.init_params, jax.random.key(0))
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), shapes)

    # ---------------- the paged KV pool: single-layer pages ----------------

    kv_folded = False

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        c = self.config
        return (num_pages, page_size, c.num_kv_heads, c.head_dim)

    def init_kv_cache(self, num_pages: int, page_size: int) -> dict:
        shape = self.kv_cache_shape(num_pages, page_size)
        return {"k": jnp.zeros(shape, self.config.dtype), "v": jnp.zeros(shape, self.config.dtype)}

    def kv_page_bytes(self, page_size: int) -> int:
        """One page of the pool: K and V of `page_size` tokens in ONE layer."""
        c = self.config
        return 2 * page_size * c.num_kv_heads * c.head_dim * jnp.dtype(c.dtype).itemsize

    def kv_cache_sharding(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        ns = NamedSharding(mesh, P())
        return {"k": ns, "v": ns}

    # ---------------- what rides beside the pool ----------------

    #: the state-cache leaves a decode window zeroes, adds to and hands back
    window_counters = ("moe_counts", "moe_touched")

    def init_state_cache(self, max_seqs: int) -> dict:
        """The `window_counters`: `moe_counts`, where decode steps add the
        assignments each held expert received, and `moe_touched`, where they
        add the number of (layer, held expert) pairs that received a row (the
        engine zeroes both at the start of a decode window and reads them at
        the end)."""
        return {"moe_counts": jnp.zeros((self.config.num_experts,), jnp.int32),
                "moe_touched": jnp.zeros((1,), jnp.int32)}

    def state_cache_sharding(self, mesh: Mesh) -> dict:
        ns = NamedSharding(mesh, P())
        return {"moe_counts": ns, "moe_touched": ns}

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
                attn = attn_fn(q, k_pool, v_pool, c.sliding_window if kind == SLIDING else 0)
            with jax.named_scope("attn_proj"):
                return attn.reshape(T, -1) @ lp["wo"], dict(kv, k=k_pool, v=v_pool)

    def _experts(self, lp, n, count_rows=None):
        """n [T, D] -> (routed + shared [T, D], the held experts' assignment
        counts over the rows of `count_rows` (all rows when None))."""
        c = self.config
        with jax.named_scope("moe_router"):
            # the router: float32 on the normed hidden state, at full precision
            # (a bf16 pass would move the choice of expert, not just a weight)
            logits = jnp.dot(
                n.astype(jnp.float32), lp["router"], precision=jax.lax.Precision.HIGHEST
            )
            weights, idx = sigmoid_topk_routing(
                logits, jnp.zeros((c.moe_routed_over,), jnp.float32), c.num_experts_per_tok
            )
            if count_rows is not None:
                idx = jnp.where(count_rows[:, None], idx, -1)  # held nowhere

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

    def _tables(self, page_tables: jnp.ndarray) -> jnp.ndarray:
        """[rows, kv_tables * width] table-major -> [kv_tables, rows, width]."""
        rows = page_tables.shape[0]
        return page_tables.reshape(rows, self.kv_tables, -1).transpose(1, 0, 2)

    # ---------------- forward ----------------

    def prefill_packed(self, params, kv_cache, tokens, positions, page_tables, valid, last_idx):
        """N lanes through every layer: each T consecutive rows of one
        sequence under that sequence's page tables, several of them one
        sequence's where its chunk rides as blocks (every layer scatters all
        lanes' rows before any lane's attention reads the pages).
        Returns (logits [N, V], cache)."""
        c = self.config
        N, T = tokens.shape
        page_size = kv_cache["k"].shape[1]
        tables = self._tables(page_tables)  # [L, N, W]
        lane = jnp.arange(N)
        with jax.named_scope("attn_kv"):  # where each row's K and V go
            offsets = jnp.where(valid, positions % page_size, 0).reshape(N * T)
        flat_pos = positions.reshape(N * T)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens.reshape(N * T)].astype(c.dtype)
        cache = kv_cache
        for l, (kind, lp) in enumerate(zip(c.layer_types, params["layers"])):
            table = tables[l]
            with jax.named_scope("attn_kv"):
                phys = jnp.where(valid, table[lane[:, None], positions // page_size], 0)

            def attn_fn(q, k_pool, v_pool, window, table=table):
                # one lane at a time through ONE instance of the kernel (a
                # loop, not N copies: at 128 query heads Mosaic takes seconds
                # to compile each copy, in every packed program a server warms)
                def lane(args):
                    q_j, table_j, positions_j = args
                    return dispatch_paged_prefill_attention(
                        q_j, k_pool, v_pool, table_j, positions_j,
                        mesh=self.attn_mesh, window=window,
                    )

                out = jax.lax.map(lane, (q.reshape(N, T, *q.shape[1:]), table, positions))
                return out.reshape(N * T, *q.shape[1:])

            n = layer_norm(hidden, lp["norm"], c.layer_norm_eps)
            attn, cache = self._attention(
                lp, kind, n, cache, flat_pos, phys.reshape(N * T), offsets, attn_fn
            )
            ffn, _ = self._experts(lp, n)
            with jax.named_scope("attn_proj"):  # the residual add of a parallel block
                hidden = hidden + attn + ffn
        rows = hidden[jnp.arange(N) * T + last_idx]
        return self._unembed(params, rows), cache

    def prefill(self, params, kv_cache, tokens, positions, page_table, valid, last_idx,
                input_embeds=None, embeds_mask=None, rope_positions=None):
        """One chunk of one sequence: a pack of one lane."""
        if input_embeds is not None or rope_positions is not None:
            raise ValueError("cohere2_moe is served text-only")
        logits, kv_cache = self.prefill_packed(
            params, kv_cache, tokens[None], positions[None], page_table[None],
            valid[None], jnp.reshape(last_idx, (1,)),
        )
        return logits[0], kv_cache

    def decode(self, params, kv_cache, tokens, positions, page_tables, active,
               rope_deltas=None):
        """One decode step for the whole batch. Returns (logits [B, V], cache)."""
        c = self.config
        cache = kv_cache
        page_size = cache["k"].shape[1]
        B = tokens.shape[0]
        tables = self._tables(page_tables)  # [L, B, W]
        with jax.named_scope("attn_kv"):
            offsets = jnp.where(active, positions % page_size, 0)
        live = live_rows(active)  # once a step, for every layer's kernel
        # likewise, a row per layer's table (the grouped allocator gives no runs: all zero)
        runs = decode_tile_runs(tables.reshape(-1, tables.shape[-1]), cache["k"], c.head_dim,
                                self.attn_mesh)
        runs = None if runs is None else runs.reshape(tables.shape[0], -1)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(c.dtype)
        # absent where no engine keeps them
        counts, touched = cache.get("moe_counts"), cache.get("moe_touched")
        for l, (kind, lp) in enumerate(zip(c.layer_types, params["layers"])):
            table = tables[l]
            with jax.named_scope("attn_kv"):
                phys = jnp.where(active, table[jnp.arange(B), positions // page_size], 0)

            def attn_fn(q, k_pool, v_pool, window, table=table, l=l):
                return dispatch_paged_decode_attention(
                    q, k_pool, v_pool, table, positions, mesh=self.attn_mesh, window=window,
                    live=live, runs=None if runs is None else runs[l],
                )

            n = layer_norm(hidden, lp["norm"], c.layer_norm_eps)
            attn, cache = self._attention(lp, kind, n, cache, positions, phys, offsets, attn_fn)
            ffn, got = self._experts(lp, n, count_rows=active)
            if counts is not None:
                counts = counts + got
                touched = touched + jnp.sum(got > 0, dtype=jnp.int32)
            with jax.named_scope("attn_proj"):  # the residual add of a parallel block
                hidden = hidden + attn + ffn
        if counts is not None:
            cache = dict(cache, moe_counts=counts, moe_touched=touched)
        return self._unembed(params, hidden), cache
