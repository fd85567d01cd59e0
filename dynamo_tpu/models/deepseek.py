"""DeepSeek-V2/V3-family model: Multi-head Latent Attention (MLA) + MoE with
shared experts, in pure JAX with a paged *latent* KV cache.

Why this family matters here: the reference's disaggregation patch explicitly
extends vLLM's deepseek_v2 model for MLA + disagg (reference: patch
`+++ b/vllm/model_executor/models/deepseek_v2.py`, SURVEY.md §2.4/§2.8), and
MLA is the strongest long-context lever available: the cache stores one
``kv_lora_rank + qk_rope_head_dim`` latent vector per token instead of
``2 * Hkv * head_dim`` — ~10-25x less HBM per token, which multiplies the
usable context length / batch on a TPU chip.

TPU-first design:
  - **Absorbed (weight-folded) attention everywhere**: scores are computed
    directly against the cached latents (q folded through the k-up projection,
    outputs folded through the v-up projection), so decode is two dense
    einsums over ``[S, d_c + d_r]`` — MXU-shaped, no per-head KV expansion and
    no gather of materialized K/V.
  - The latent cache is a flat page pool ``{"ckv": [L*P, ps, d_c + d_r]}``
    carried through the layer scans and donated (same in-place scatter
    property as the Llama pool; see dynamo_tpu/ops/attention.py).
  - Layers are scan-stacked in two homogeneous groups (DeepSeek interleaves
    dense and MoE layers: the first ``first_k_dense_replace`` are dense MLP,
    the rest are shared-expert + routed-expert MoE), one compiled body each.
  - Tensor parallelism: per-head projections (q up, k-up, v-up, o) shard on
    the ``tp`` axis; the latent path (down-projections, cache) is replicated —
    it is head-independent by construction. Routed experts shard on ``ep``.

Cache-content convention: the pool row for a token stores
``[rms_norm(c_latent), rope(k_rope)]`` — the normalized latent and the
position-rotated shared rope key, i.e. exactly what the absorbed score needs.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.paged import PagedModel
from dynamo_tpu.ops.moe import moe_block
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.rotary import apply_rope
from dynamo_tpu.quant import (
    QUANT_MODES,
    qlinear,
    quantize_shardings_int8,
    quantize_tree_int8,
)

_NEG_INF = -1e30


def _use_pallas_mla() -> bool:
    """Trace-time choice of the Pallas latent-page decode kernel: same
    DYNTPU_PALLAS override semantics as the GQA kernel (shared pallas_flag);
    default on for real TPU backends."""
    from dynamo_tpu.ops.attention import _pallas_enabled

    return _pallas_enabled(True)


@dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288  # dense layers' MLP width
    num_layers: int = 60
    num_heads: int = 128
    # MLA geometry
    q_lora_rank: Optional[int] = 1536  # None => plain q projection
    kv_lora_rank: int = 512  # d_c
    qk_nope_head_dim: int = 128  # d_n
    qk_rope_head_dim: int = 64  # d_r
    v_head_dim: int = 128  # d_v
    # MoE geometry
    n_routed_experts: int = 160
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    moe_intermediate_size: int = 1536
    first_k_dense_replace: int = 1
    # routed-expert output scale (DeepSeek-V2 uses 16.0; V2-Lite 1.0)
    routed_scaling_factor: float = 1.0
    # False (DeepSeek default): top-k probs taken from the full softmax,
    # not renormalized over the selected k
    norm_topk_prob: bool = False
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    # weight-only quantization mode (None or "int8_wo"); see LlamaConfig
    quantize: Any = None
    dtype: Any = jnp.bfloat16

    @property
    def latent_dim(self) -> int:
        """Logical cache row width: latent + shared rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_dim_padded(self) -> int:
        """Physical row width, padded to the TPU lane tiling (128): Mosaic
        requires 128-aligned minor dims, and DeepSeek's 512+64=576 is not.
        ~11%% extra on a cache that is already ~20x smaller than full KV."""
        return -(-self.latent_dim // 128) * 128

    @classmethod
    def from_hf_config(cls, d: dict) -> "DeepseekConfig":
        """Build from a HuggingFace deepseek_v2/v3 config.json dict.

        Raises for checkpoint features this implementation does not model yet
        (wrong numerics would otherwise be silent): sigmoid routing with
        correction bias (V3), group-limited top-k, and yarn rope scaling."""
        unsupported = []
        if d.get("scoring_func", "softmax") != "softmax":
            unsupported.append(f"scoring_func={d['scoring_func']!r} (V3 sigmoid routing)")
        if d.get("topk_method", "greedy") not in ("greedy", None):
            unsupported.append(f"topk_method={d['topk_method']!r} (group-limited top-k)")
        if d.get("rope_scaling"):
            unsupported.append("rope_scaling (yarn + mscale)")
        if unsupported:
            raise ValueError(
                "deepseek checkpoint needs unsupported features: "
                + ", ".join(unsupported)
            )
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            q_lora_rank=d.get("q_lora_rank"),
            kv_lora_rank=d.get("kv_lora_rank", 512),
            qk_nope_head_dim=d.get("qk_nope_head_dim", 128),
            qk_rope_head_dim=d.get("qk_rope_head_dim", 64),
            v_head_dim=d.get("v_head_dim", 128),
            n_routed_experts=d.get("n_routed_experts", 64),
            num_experts_per_tok=d.get("num_experts_per_tok", 6),
            n_shared_experts=d.get("n_shared_experts", 2),
            moe_intermediate_size=d.get("moe_intermediate_size", 1408),
            first_k_dense_replace=d.get("first_k_dense_replace", 1),
            routed_scaling_factor=d.get("routed_scaling_factor", 1.0),
            norm_topk_prob=d.get("norm_topk_prob", False),
            rope_theta=d.get("rope_theta", 10000.0),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
        )

    @classmethod
    def tiny_mla(cls, **overrides) -> "DeepseekConfig":
        """Small config for tests (1 dense + 1 MoE layer)."""
        from dynamo_tpu.models.llama import parse_dtype

        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        base = cls(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            q_lora_rank=None,
            kv_lora_rank=32,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
            n_routed_experts=4,
            num_experts_per_tok=2,
            n_shared_experts=1,
            moe_intermediate_size=32,
            first_k_dense_replace=1,
            dtype=jnp.float32,
        )
        return replace(base, **overrides)


class DeepseekModel(PagedModel):
    """Stateless forward functions over a params pytree (MLA + MoE);
    models/paged.py's contract. ModelRunner sets `attn_mesh` for tp > 1 (the
    Pallas MLA kernel runs under shard_map on it: heads sharded, latent pool
    replicated) and `expert_mesh` where its mesh has several devices (the
    grouped product is then XLA's, ops/moe.grouped_matmul)."""

    #: quantizable per-layer weights in both layer groups (applied
    #: by-presence). Deliberately excluded: the k-up/v-up banks w_kb/w_vb
    #: (3-D per-head einsum operands, ~1% of bytes), norms, and the f32
    #: router.
    QUANT_WEIGHT_NAMES = frozenset({
        "w_q", "w_dq", "w_uq", "w_dkv", "wo",
        "gate", "up", "down",
        "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down",
    })

    # ---------------- params ----------------

    def _attn_params(self, keys, L: int) -> dict:
        c = self.config

        def dense(key, shape, scale_axis):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(c.dtype)

        D, H = c.hidden_size, c.num_heads
        dn, dr, dv, dc = (
            c.qk_nope_head_dim,
            c.qk_rope_head_dim,
            c.v_head_dim,
            c.kv_lora_rank,
        )
        p = {
            "input_norm": jnp.ones((L, D), c.dtype),
            "w_dkv": dense(next(keys), (L, D, dc + dr), 1),
            "kv_norm": jnp.ones((L, dc), c.dtype),
            # k-up and v-up projections from the latent, per head
            "w_kb": dense(next(keys), (L, dc, H, dn), 1),
            "w_vb": dense(next(keys), (L, dc, H, dv), 1),
            "wo": dense(next(keys), (L, H * dv, D), 1),
            "post_norm": jnp.ones((L, D), c.dtype),
        }
        if c.q_lora_rank:
            p["w_dq"] = dense(next(keys), (L, D, c.q_lora_rank), 1)
            p["q_norm"] = jnp.ones((L, c.q_lora_rank), c.dtype)
            p["w_uq"] = dense(next(keys), (L, c.q_lora_rank, H * (dn + dr)), 1)
        else:
            p["w_q"] = dense(next(keys), (L, D, H * (dn + dr)), 1)
        return p

    def quantize_params(self, params: dict) -> dict:
        """Apply config.quantize to both layer groups (no-op when unset)."""
        mode = self.config.quantize
        if not mode:
            return params
        if mode not in QUANT_MODES:
            raise ValueError(f"unknown quantize mode {mode!r} (supported: {QUANT_MODES})")
        params = dict(params)
        for group in ("dense_layers", "moe_layers"):
            params[group] = quantize_tree_int8(params[group], self.QUANT_WEIGHT_NAMES)
        return params

    def init_params(self, rng: jax.Array, quantize: bool = True) -> dict:
        params = self._init_raw_params(rng)
        return self.quantize_params(params) if quantize else params

    def _init_raw_params(self, rng: jax.Array) -> dict:
        c = self.config
        keys = iter(jax.random.split(rng, 48))

        def dense(key, shape, scale_axis):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(c.dtype)

        D, F, V, E = (
            c.hidden_size,
            c.intermediate_size,
            c.vocab_size,
            c.n_routed_experts,
        )
        Fm, Fs = c.moe_intermediate_size, c.n_shared_experts * c.moe_intermediate_size
        Ld, Lm = c.first_k_dense_replace, c.num_layers - c.first_k_dense_replace

        dense_layers = self._attn_params(keys, Ld)
        dense_layers.update(
            {
                "gate": dense(next(keys), (Ld, D, F), 1),
                "up": dense(next(keys), (Ld, D, F), 1),
                "down": dense(next(keys), (Ld, F, D), 1),
            }
        )
        moe_layers = self._attn_params(keys, Lm)
        moe_layers.update(
            {
                "router": dense(next(keys), (Lm, D, E), 1).astype(jnp.float32),
                "w_gate": dense(next(keys), (Lm, E, D, Fm), 2),
                "w_up": dense(next(keys), (Lm, E, D, Fm), 2),
                "w_down": dense(next(keys), (Lm, E, Fm, D), 2),
                "shared_gate": dense(next(keys), (Lm, D, Fs), 1),
                "shared_up": dense(next(keys), (Lm, D, Fs), 1),
                "shared_down": dense(next(keys), (Lm, Fs, D), 1),
            }
        )
        return {
            "embed": dense(next(keys), (V, D), 1),
            "dense_layers": dense_layers,
            "moe_layers": moe_layers,
            "final_norm": jnp.ones((D,), c.dtype),
            "lm_head": dense(next(keys), (V, D), 1),
        }

    def param_shardings(self, mesh: Mesh, tp_axis: str = "tp", ep_axis: str = "ep") -> dict:
        c = self.config
        tp = tp_axis if tp_axis in mesh.axis_names else None
        ep = ep_axis if ep_axis in mesh.axis_names else None

        def ns(*spec):
            return NamedSharding(mesh, P(*spec))

        def attn():
            p = {
                "input_norm": ns(None, None),
                "w_dkv": ns(None, None, None),
                "kv_norm": ns(None, None),
                "w_kb": ns(None, None, tp, None),
                "w_vb": ns(None, None, tp, None),
                "wo": ns(None, tp, None),
                "post_norm": ns(None, None),
            }
            if c.q_lora_rank:
                p["w_dq"] = ns(None, None, None)
                p["q_norm"] = ns(None, None)
                p["w_uq"] = ns(None, None, tp)
            else:
                p["w_q"] = ns(None, None, tp)
            return p

        dense_layers = attn()
        dense_layers.update(
            {"gate": ns(None, None, tp), "up": ns(None, None, tp), "down": ns(None, tp, None)}
        )
        moe_layers = attn()
        moe_layers.update(
            {
                "router": ns(None, None, None),
                "w_gate": ns(None, ep, None, None),
                "w_up": ns(None, ep, None, None),
                "w_down": ns(None, ep, None, None),
                "shared_gate": ns(None, None, tp),
                "shared_up": ns(None, None, tp),
                "shared_down": ns(None, tp, None),
            }
        )
        if c.quantize:
            dense_layers = quantize_shardings_int8(dense_layers, self.QUANT_WEIGHT_NAMES)
            moe_layers = quantize_shardings_int8(moe_layers, self.QUANT_WEIGHT_NAMES)
        return {
            "embed": ns(None, None),
            "dense_layers": dense_layers,
            "moe_layers": moe_layers,
            "final_norm": ns(None),
            "lm_head": ns(tp, None),
        }

    # ---------------- KV cache (paged latents) ----------------

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        c = self.config
        return (c.num_layers * num_pages, page_size, c.latent_dim_padded)

    def init_kv_cache(self, num_pages: int, page_size: int) -> dict:
        return {"ckv": jnp.zeros(self.kv_cache_shape(num_pages, page_size), self.config.dtype)}

    def kv_page_bytes(self, page_size: int) -> int:
        """0: the latent cache's page was never priced (this class had no such
        method and the engine took the absence for 0), so the host tier honours
        no byte budget for it, the meter charges its pages nothing and the
        roofline gauge is off. Kept as it was; ROADMAP M2 names it."""
        return 0

    def kv_cache_sharding(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        # the latent cache is head-independent: replicated across tp
        return {"ckv": NamedSharding(mesh, P(None, None, None))}

    def _layer_offsets(self, num_pages: int, start_layer: int, n_layers: int) -> jnp.ndarray:
        return (start_layer + jnp.arange(n_layers, dtype=jnp.int32)) * num_pages

    # ---------------- disagg / offload wire format ----------------

    wire_n_axis = 1  # [L, n, ...]: no K/V axis before the pages

    def gather_pages_wire(self, kv: dict, flat_ids: jnp.ndarray) -> jnp.ndarray:
        """[L, n] flat page ids -> wire array [L, n, ps, latent_dim_padded]
        (the physical 128-aligned row width; receivers must size buffers from
        kv_cache_shape, not latent_dim)."""
        return kv["ckv"][flat_ids]

    def scatter_pages_wire(self, kv: dict, flat_ids: jnp.ndarray, data: jnp.ndarray) -> dict:
        return {"ckv": kv["ckv"].at[flat_ids].set(data.astype(kv["ckv"].dtype))}

    def wire_sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, P(None, None, None, None))

    # ---------------- attention core ----------------

    def _queries(self, lp: dict, h: jnp.ndarray, positions: jnp.ndarray):
        """h [T, D] -> (q_nope [T, H, dn], q_rope [T, H, dr] roped)."""
        c = self.config
        T = h.shape[0]
        H, dn, dr = c.num_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
        if c.q_lora_rank:
            ql = rms_norm(qlinear(h, lp["w_dq"]), lp["q_norm"], c.rms_norm_eps)
            q = qlinear(ql, lp["w_uq"]).reshape(T, H, dn + dr)
        else:
            q = qlinear(h, lp["w_q"]).reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = apply_rope(q_rope, positions, c.rope_theta)
        return q_nope, q_rope

    def _cache_rows(self, lp: dict, h: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
        """h [T, D] -> cache rows [T, latent_dim] = [norm(latent), roped k_rope]."""
        c = self.config
        dc = c.kv_lora_rank
        ckv = qlinear(h, lp["w_dkv"])  # [T, dc + dr]
        latent = rms_norm(ckv[:, :dc], lp["kv_norm"], c.rms_norm_eps)
        k_rope = apply_rope(ckv[:, None, dc:], positions, c.rope_theta)[:, 0]
        row = jnp.concatenate([latent, k_rope], axis=-1).astype(c.dtype)
        pad = c.latent_dim_padded - c.latent_dim
        if pad:
            row = jnp.pad(row, ((0, 0), (0, pad)))
        return row

    def _absorbed_attention(
        self,
        lp: dict,
        q_nope: jnp.ndarray,  # [T, H, dn]
        q_rope: jnp.ndarray,  # [T, H, dr] (roped)
        ctx: jnp.ndarray,  # [S, latent_dim] gathered cache rows (logical order)
        q_positions: jnp.ndarray,  # [T]
    ) -> jnp.ndarray:
        """Causal attention against cached latents; returns [T, H*dv]."""
        c = self.config
        dc = c.kv_lora_rank
        scale = 1.0 / jnp.sqrt(jnp.float32(c.qk_nope_head_dim + c.qk_rope_head_dim))
        latents = ctx[:, :dc].astype(jnp.float32)  # [S, dc]
        k_rope = ctx[:, dc : dc + c.qk_rope_head_dim].astype(jnp.float32)  # [S, dr]

        # fold q through the k-up projection: [T, H, dc]
        q_eff = jnp.einsum(
            "thn,chn->thc", q_nope.astype(jnp.float32), lp["w_kb"].astype(jnp.float32)
        )
        scores = (
            jnp.einsum("thc,sc->hts", q_eff, latents)
            + jnp.einsum("thr,sr->hts", q_rope.astype(jnp.float32), k_rope)
        ) * scale
        ctx_idx = jnp.arange(ctx.shape[0], dtype=jnp.int32)
        mask = ctx_idx[None, :] <= q_positions[:, None]  # [T, S]
        scores = jnp.where(mask[None, :, :], scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)  # [H, T, S]
        # attend in latent space, then fold through the v-up projection
        a_lat = jnp.einsum("hts,sc->thc", probs, latents)  # [T, H, dc]
        out = jnp.einsum(
            "thc,chv->thv", a_lat, lp["w_vb"].astype(jnp.float32)
        )  # [T, H, dv]
        return out.astype(self.config.dtype).reshape(out.shape[0], -1)

    def _latent_kernel(self, op, variant, kernel, q_cat, pool, tables, positions):
        """Run a latent-space Pallas kernel, per head shard under tensor
        parallelism (GSPMD cannot partition a pallas_call; attention is
        head-parallel, the latent pool and page tables are replicated), and
        log once which path that was."""
        from dynamo_tpu.ops.attention import _log_path, _on_tpu, _tp_shard_map

        mesh = self.attn_mesh
        tp = 1 if mesh is None else mesh.shape.get("tp", 1)
        sharded = tp > 1 and q_cat.shape[1] % tp == 0
        _log_path(
            op,
            f"pallas:{variant}" + ("" if _on_tpu() else " interpret")
            + (f" shard_map tp={tp}" if sharded else ""),
            (f"T={q_cat.shape[0]} " if tables.ndim == 1 else "")
            + f"H={q_cat.shape[1]} latent={q_cat.shape[2]} ps={pool.shape[1]}"
            + (f": tp={tp} does not divide the heads, kernel runs unsharded"
               if tp > 1 and not sharded else ""),
        )
        if not sharded:
            return kernel(q_cat, pool, tables, positions)
        return _tp_shard_map(
            kernel,
            mesh,
            in_specs=(P(None, "tp", None), P(None, None, None), P(*[None] * tables.ndim), P(None)),
            out_specs=P(None, "tp", None),
        )(q_cat, pool, tables, positions)

    def _mla_decode_pallas(
        self, lp, q_nope, q_rope, pool, page_tables, positions
    ) -> jnp.ndarray:
        """Decode-batch attention via the Pallas latent-page kernel: the q
        fold (MXU matmul) and the v-up projection stay outside; the kernel
        streams latent pages and returns the latent-space attention output."""
        from dynamo_tpu.ops.attention import _on_tpu
        from dynamo_tpu.ops.pallas.mla_attention import paged_mla_decode_attention_pallas

        c = self.config
        dc = c.kv_lora_rank
        q_cat = self._fold_q(lp, q_nope, q_rope)
        import functools

        kernel = functools.partial(
            paged_mla_decode_attention_pallas, d_c=dc, interpret=not _on_tpu()
        )
        a_lat = self._latent_kernel(
            "mla decode", "classic", kernel, q_cat, pool, page_tables, positions
        )
        out = jnp.einsum(
            "bhc,chv->bhv", a_lat.astype(jnp.float32), lp["w_vb"].astype(jnp.float32)
        )
        return out.astype(c.dtype).reshape(out.shape[0], -1)

    def _fold_q(self, lp, q_nope, q_rope):
        """(q_nope, q_rope) -> pre-scaled q_cat [.., H, latent_padded] for the
        latent-space kernels (the MXU-shaped fold through w_kb stays outside
        the pallas_call)."""
        c = self.config
        scale = 1.0 / jnp.sqrt(jnp.float32(c.qk_nope_head_dim + c.qk_rope_head_dim))
        q_eff = jnp.einsum(
            "...hn,chn->...hc", q_nope.astype(jnp.float32), lp["w_kb"].astype(jnp.float32)
        )
        q_cat = jnp.concatenate([q_eff, q_rope.astype(jnp.float32)], axis=-1) * scale
        pad = c.latent_dim_padded - c.latent_dim
        if pad:
            widths = [(0, 0)] * (q_cat.ndim - 1) + [(0, pad)]
            q_cat = jnp.pad(q_cat, widths)
        return q_cat

    def _mla_prefill_pallas(
        self, lp, q_nope, q_rope, pool, page_table, positions
    ) -> jnp.ndarray:
        """Chunked-prefill attention via the latent flash kernel; the v-up
        fold happens outside. Returns [T, H*dv]."""
        from dynamo_tpu.ops.attention import _on_tpu
        from dynamo_tpu.ops.pallas.mla_attention import (
            paged_mla_prefill_attention_pallas,
        )

        c = self.config
        q_cat = self._fold_q(lp, q_nope, q_rope)
        import functools

        interpret = not _on_tpu()
        kernel = functools.partial(
            paged_mla_prefill_attention_pallas,
            d_c=c.kv_lora_rank,
            interpret=interpret,
        )
        a_lat = self._latent_kernel(
            "mla prefill", "flash", kernel, q_cat, pool, page_table, positions
        )
        out = jnp.einsum(
            "thc,chv->thv", a_lat.astype(jnp.float32), lp["w_vb"].astype(jnp.float32)
        )
        return out.astype(c.dtype).reshape(out.shape[0], -1)

    def _layer(
        self,
        lp: dict,
        hidden: jnp.ndarray,  # [T, D]
        pool: jnp.ndarray,  # [LP, ps, latent_dim] (carried)
        positions: jnp.ndarray,
        flat_phys: jnp.ndarray,
        offsets: jnp.ndarray,
        gather_tables: jnp.ndarray,  # [max_pages] or [B, max_pages] flat ids
        moe: bool,
        verify_T: int = 0,  # >0: B-lane speculative verify, T queries per lane
    ):
        c = self.config
        T = hidden.shape[0]
        h = rms_norm(hidden, lp["input_norm"], c.rms_norm_eps)
        q_nope, q_rope = self._queries(lp, h, positions)
        rows = self._cache_rows(lp, h, positions)
        pool = pool.at[flat_phys, offsets].set(rows)

        if verify_T:
            # speculative verify: each lane attends its own paged context with
            # verify_T query positions (absorbed-attention reference path; the
            # chunk is a handful of rows, so the per-lane gather is cheap)
            Bv = gather_tables.shape[0]
            ps = pool.shape[1]
            qn = q_nope.reshape(Bv, verify_T, *q_nope.shape[1:])
            qr = q_rope.reshape(Bv, verify_T, *q_rope.shape[1:])
            pos2 = positions.reshape(Bv, verify_T)
            outs = [
                self._absorbed_attention(
                    lp, qn[j], qr[j],
                    pool[gather_tables[j]].reshape(
                        gather_tables.shape[1] * ps, c.latent_dim_padded
                    ),
                    pos2[j],
                )
                for j in range(Bv)
            ]
            attn = jnp.concatenate(outs, axis=0)
        elif gather_tables.ndim == 1:
            if _use_pallas_mla() and T % 128 == 0:
                attn = self._mla_prefill_pallas(
                    lp, q_nope, q_rope, pool, gather_tables, positions
                )
            else:
                from dynamo_tpu.ops.attention import _log_path

                _log_path(
                    "mla prefill", "reference",
                    f"T={T}: no Pallas kernel for this backend, or chunk is "
                    "not a multiple of 128",
                )
                ps = pool.shape[1]
                ctx = pool[gather_tables].reshape(
                    gather_tables.shape[0] * ps, c.latent_dim_padded
                )
                attn = self._absorbed_attention(lp, q_nope, q_rope, ctx, positions)
        elif _use_pallas_mla():
            attn = self._mla_decode_pallas(lp, q_nope, q_rope, pool, gather_tables, positions)
        else:
            ps = pool.shape[1]

            def one(qn_b, qr_b, pt_b, pos_b):
                ctx = pool[pt_b].reshape(pt_b.shape[0] * ps, c.latent_dim_padded)
                return self._absorbed_attention(
                    lp, qn_b[None], qr_b[None], ctx, pos_b[None]
                )[0]

            attn = jax.vmap(one)(q_nope, q_rope, gather_tables, positions)

        hidden = hidden + qlinear(attn, lp["wo"])
        h = rms_norm(hidden, lp["post_norm"], c.rms_norm_eps)
        if moe:
            shared = qlinear(
                jax.nn.silu(qlinear(h, lp["shared_gate"])) * qlinear(h, lp["shared_up"]),
                lp["shared_down"],
            )
            routed = moe_block(
                h,
                lp["router"],
                lp["w_gate"],
                lp["w_up"],
                lp["w_down"],
                num_experts_per_tok=c.num_experts_per_tok,
                renormalize=c.norm_topk_prob,
                mesh=self.expert_mesh,
            )
            hidden = hidden + shared + c.routed_scaling_factor * routed
        else:
            mlp = qlinear(jax.nn.silu(qlinear(h, lp["gate"])) * qlinear(h, lp["up"]), lp["down"])
            hidden = hidden + mlp
        return hidden, pool

    def _unembed(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        h = rms_norm(hidden, params["final_norm"], self.config.rms_norm_eps)
        return jax.lax.dot_general(
            h, params["lm_head"], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    def _forward(
        self,
        params: dict,
        pool: jnp.ndarray,
        hidden: jnp.ndarray,
        positions: jnp.ndarray,
        phys: jnp.ndarray,  # logical phys page per token (trash=0)
        offsets: jnp.ndarray,
        tables: jnp.ndarray,  # [max_pages] or [B, max_pages] logical ids
        num_pages: int,
        verify_T: int = 0,
    ):
        c = self.config
        Ld = c.first_k_dense_replace

        def group(hidden, pool, lp_group, start, n, moe):
            offs = self._layer_offsets(num_pages, start, n)

            def body(carry, xs):
                h, pl = carry
                lp, off = xs
                h, pl = self._layer(
                    lp, h, pl, positions, off + phys, offsets, off + tables, moe,
                    verify_T=verify_T,
                )
                return (h, pl), None

            (hidden, pool), _ = jax.lax.scan(body, (hidden, pool), (lp_group, offs))
            return hidden, pool

        if Ld > 0:
            hidden, pool = group(hidden, pool, params["dense_layers"], 0, Ld, False)
        if c.num_layers - Ld > 0:
            hidden, pool = group(
                hidden, pool, params["moe_layers"], Ld, c.num_layers - Ld, True
            )
        return hidden, pool

    # ---------------- public forward API (ModelRunner contract) ----------------

    def prefill(self, params, kv_cache, tokens, positions, page_table, valid, last_idx,
                input_embeds=None, embeds_mask=None, rope_positions=None):
        # rope_positions (M-RoPE) is accepted for runner-contract parity but
        # unused: no multimodal MLA family exists
        c = self.config
        pool = kv_cache["ckv"]
        page_size = pool.shape[1]
        num_pages = pool.shape[0] // c.num_layers
        phys = jnp.where(valid, page_table[positions // page_size], 0)
        offsets = jnp.where(valid, positions % page_size, 0)
        hidden = params["embed"][tokens].astype(c.dtype)
        if input_embeds is not None:  # multimodal embedding overrides
            hidden = jnp.where(embeds_mask[:, None], input_embeds.astype(c.dtype), hidden)
        hidden, pool = self._forward(
            params, pool, hidden, positions, phys, offsets, page_table, num_pages
        )
        logits = self._unembed(params, hidden[last_idx][None, :])[0]
        return logits, {"ckv": pool}

    def decode(self, params, kv_cache, tokens, positions, page_tables, active, rope_deltas=None):
        c = self.config
        pool = kv_cache["ckv"]
        page_size = pool.shape[1]
        num_pages = pool.shape[0] // c.num_layers
        B = tokens.shape[0]
        logical = positions // page_size
        phys = jnp.where(active, page_tables[jnp.arange(B), logical], 0)
        offsets = jnp.where(active, positions % page_size, 0)
        hidden = params["embed"][tokens].astype(c.dtype)
        hidden, pool = self._forward(
            params, pool, hidden, positions, phys, offsets, page_tables, num_pages
        )
        logits = self._unembed(params, hidden)
        return logits, {"ckv": pool}

    def verify(self, params, kv_cache, tokens, positions, page_tables, valid):
        """Speculative verification (ModelRunner contract, see
        LlamaModel.verify): [B, T] anchor+draft tokens at consecutive
        positions, one weight pass, logits at ALL rows. Latent rows for
        invalid positions scatter to the trash page; each lane's attention
        runs the absorbed-MLA reference path against its own page table.

        Returns (logits [B, T, V], updated kv_cache)."""
        c = self.config
        pool = kv_cache["ckv"]
        page_size = pool.shape[1]
        num_pages = pool.shape[0] // c.num_layers
        B, T = tokens.shape
        lane = jnp.arange(B)
        phys = jnp.where(valid, page_tables[lane[:, None], positions // page_size], 0)
        offsets = jnp.where(valid, positions % page_size, 0)
        hidden = params["embed"][tokens.reshape(B * T)].astype(c.dtype)
        hidden, pool = self._forward(
            params, pool, hidden, positions.reshape(B * T),
            phys.reshape(B * T), offsets.reshape(B * T), page_tables, num_pages,
            verify_T=T,
        )
        logits = self._unembed(params, hidden)  # [B*T, V]
        return logits.reshape(B, T, -1), {"ckv": pool}
