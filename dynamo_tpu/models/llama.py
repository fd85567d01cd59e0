"""Llama-family model (Llama 2/3, DeepSeek-R1-Distill-Llama) in pure JAX with a
paged KV cache.

Design notes (TPU-first):
  - Layers are scan-stacked: every weight carries a leading ``[L]`` axis and the
    forward pass is one ``lax.scan`` over layers — a single compiled layer body
    and fast compiles.
  - The KV cache is a **flat page pool** ``{"k","v"}`` of shape
    ``[num_layers * num_pages, page_size, Hkv, D]`` each (layer l's page p at
    flat index ``l * num_pages + p``), carried through the layer scan and
    donated to the step functions so XLA scatters new tokens in place. See
    dynamo_tpu/ops/attention.py for why flat beats a per-layer [L, ...] cache
    threaded through scan xs/ys (3x decode step time on v5e).
  - Tensor parallelism is expressed purely as NamedSharding on params/cache
    (head-sharded) + GSPMD propagation; no explicit collectives in model code.
  - Weight layout is ``[in, out]`` so the hot path is plain ``h @ w`` (MXU).

This is the serving engine slot that the reference fills with external GPU
engines (reference: lib/llm/src/engines/vllm/worker.rs, SURVEY.md §7 step 3) —
here it is native.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.models.paged import DecodeStep, Pack, PagedModel, last_rows, prefill_attend
from dynamo_tpu.ops.attention import scatter_kv
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.rotary import apply_mrope, apply_rope
from dynamo_tpu.quant import (
    QUANT_MODES,
    QuantizedPages,
    init_quantized_pages,
    qlinear,
    quantize_shardings_int8,
    quantize_tree_int8,
)
from dynamo_tpu.quant.kv import kv_page_bytes as _kv_page_bytes, quantize_kv_rows


def _resolve_tp_axis(mesh: Mesh, tp_axis: str):
    """tp axis name if present; None for sp-only meshes (params replicated by
    design there); otherwise keep the name so NamedSharding raises loudly."""
    if tp_axis in mesh.axis_names:
        return tp_axis
    if "sp" in mesh.axis_names or "pp" in mesh.axis_names:
        return None  # sp/pp-only meshes replicate the tp dims by design
    return tp_axis  # unknown axis -> NamedSharding raises


def lora_delta(x: jnp.ndarray, entry: dict, ids, scales: jnp.ndarray) -> jnp.ndarray:
    """Gathered per-slot LoRA pass: ``scale[ids] * (x @ A[ids]) @ B[ids]``.

    ``entry`` is one module's slot-stacked planes {"a": [S, in, r], "b":
    [S, r, out]} (slot 0 = the zero adapter, so base-only lanes ride the same
    gather instead of a trace branch). ``ids`` is a per-token [T] vector (a
    mixed-adapter batch) or a scalar (a whole single-sequence chunk shares
    one adapter — the gather degenerates to a slice and the two einsums to
    plain matmuls). The f32 pool keeps the delta algebra exact against a
    merged-weight f32 reference; the result casts back to x's dtype."""
    xf = x.astype(jnp.float32)
    a = entry["a"][ids]
    b = entry["b"][ids]
    if jnp.ndim(ids) == 0:
        d = ((xf @ a) @ b) * scales[ids]
    else:
        xr = jnp.einsum("ti,tir->tr", xf, a)
        d = jnp.einsum("tr,tro->to", xr, b) * scales[ids][:, None]
    return d.astype(x.dtype)


def parse_dtype(value) -> Any:
    """Accept a jnp dtype or its string alias in tiny:{...} config overrides."""
    if isinstance(value, str):
        return {
            "bf16": jnp.bfloat16,
            "bfloat16": jnp.bfloat16,
            "f32": jnp.float32,
            "float32": jnp.float32,
        }[value]
    return value


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    attention_bias: bool = False  # Qwen2-style qkv biases
    # M-RoPE (Qwen2-VL): (temporal, row, col) frequency sections summing to
    # head_dim // 2. None = plain 1D RoPE. With equal position components
    # (all text) M-RoPE reduces exactly to 1D RoPE (ops/rotary.py).
    mrope_section: Any = None
    # weight-only quantization mode: None (full precision) or "int8_wo" —
    # the big linear weights become int8 + per-output-channel f32 scales at
    # load time; embeddings/lm_head/norms/biases stay at `dtype`
    # (dynamo_tpu/quant/int8.py)
    quantize: Any = None
    # KV cache storage dtype: None / "bf16" (the model dtype) or "int8" —
    # pages stored int8 with one f32 scale per (page, token row)
    # (dynamo_tpu/quant/kv.py QuantizedPages). Halves attention HBM traffic
    # and doubles page capacity at the same HBM budget; composes with
    # `quantize` (weights and cache quantize independently).
    kv_cache_dtype: Any = None
    dtype: Any = jnp.bfloat16

    @property
    def kv_quantized(self) -> bool:
        return self.kv_cache_dtype == "int8"

    @property
    def kv_folded(self) -> bool:
        """KV page rows store heads FOLDED into the lane dim ([ps, Hkv*D]
        instead of [ps, Hkv, D]) when head_dim isn't 128-lane aligned:
        Mosaic cannot DMA-slice an HBM pool whose minor dim is under the
        128-lane tile, and reshaping the (donated, scatter-updated) pool at
        attention time materializes a full-pool copy per layer per step.
        TinyLlama / Qwen2-small shapes (D=64) hit this; D=128 models don't."""
        return self.head_dim % 128 != 0

    @classmethod
    def from_hf_config(cls, d: dict) -> "LlamaConfig":
        """Build from a HuggingFace config.json dict (Llama / Qwen2 families)."""
        num_heads = d["num_attention_heads"]
        is_qwen = "qwen" in str(d.get("model_type", "")).lower()
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=d.get("head_dim", d["hidden_size"] // num_heads),
            rope_theta=d.get("rope_theta", 10000.0),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            attention_bias=d.get("attention_bias", is_qwen),
        )

    @classmethod
    def tiny(cls, **overrides) -> "LlamaConfig":
        """Small config for tests (runs on the virtual CPU mesh in seconds)."""
        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        base = cls(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            dtype=jnp.float32,
        )
        return replace(base, **overrides)


def _on_pools(attend):
    """A paged attention (`attn_fn(q, k_pool, v_pool)`, models/paged.py) as
    the `attn_fn` `_layer` calls: the chunk's fresh K and V rows are the ring
    path's alone."""
    return lambda q, k_new, v_new, k_pool, v_pool: attend(q, k_pool, v_pool)


class LlamaModel(PagedModel):
    """Stateless forward functions over a params pytree (models/paged.py's
    contract). `attn_mesh` is set by ModelRunner for tp > 1 so the Pallas
    decode kernel can run under shard_map (GSPMD cannot partition a
    pallas_call)."""

    #: per-layer weights eligible for weight-only quantization — the decode
    #: hot path's big matmuls; norms/biases (and embed/lm_head outside the
    #: layer stack) stay at config.dtype
    QUANT_WEIGHT_NAMES = frozenset({"wq", "wk", "wv", "wo", "gate", "up", "down"})

    #: llama-family layers take the gathered LoRA pass (dynamo_tpu/lora/):
    #: q/k/v/o + gated-MLP deltas ride slot-stacked pools through every
    #: forward. Subclasses with their own _layer (mixtral's MoE block,
    #: deepseek's absorbed attention) opt out until they thread it.
    SUPPORTS_LORA = True

    @property
    def kv_folded(self) -> bool:
        """The pools' layout for THIS engine: config.kv_folded (sub-128
        head_dim), or too few kv heads per tensor-parallel shard. Mosaic
        tiles a page's [Hkv, D] minor dims in sublane packs (2 rows for
        bf16, 4 for int8) and refuses to DMA-slice a page whose per-device
        head count is not a whole pack — Qwen2.5-7B's 4 kv heads at tp=4
        leave one per chip (asked of the chip's compiler:
        tests/test_tpu_compile.py). Folded, the shard's page is
        [ps, (Hkv/tp)*D] and the folded kernels serve it."""
        c = self.config
        if c.kv_folded or self.attn_mesh is None:
            return c.kv_folded
        pack = 4 // (1 if c.kv_quantized else jnp.dtype(c.dtype).itemsize)
        tp = self.attn_mesh.shape.get("tp", 1)
        return pack > 1 and tp > 1 and (c.num_kv_heads // tp) % pack != 0

    # ---------------- params ----------------

    def quantize_params(self, params: dict) -> dict:
        """Apply config.quantize to a full-precision params tree (no-op when
        unset). Loaders call this after filling checkpoint weights; the
        subclass's QUANT_WEIGHT_NAMES picks the leaves."""
        mode = self.config.quantize
        if not mode:
            return params
        if mode not in QUANT_MODES:
            raise ValueError(f"unknown quantize mode {mode!r} (supported: {QUANT_MODES})")
        params = dict(params)
        params["layers"] = quantize_tree_int8(params["layers"], self.QUANT_WEIGHT_NAMES)
        return params

    def _quantize_shardings(self, shardings: dict) -> dict:
        """Mirror quantize_params onto the sharding tree: int8 weights keep
        the bf16 leaf's sharding, scales drop its contracted-axis entry (so
        they follow the weight's output-channel sharding and replicate over
        a row-parallel split)."""
        if not self.config.quantize:
            return shardings
        shardings = dict(shardings)
        shardings["layers"] = quantize_shardings_int8(
            shardings["layers"], self.QUANT_WEIGHT_NAMES
        )
        return shardings

    def init_params(self, rng: jax.Array, quantize: bool = True) -> dict:
        """quantize=False yields the raw full-precision tree even when the
        config requests quantization — the loader's allocation template
        (models/loader.py fills f32 arrays, then quantizes once at the end)."""
        params = self._init_raw_params(rng)
        return self.quantize_params(params) if quantize else params

    def _init_raw_params(self, rng: jax.Array) -> dict:
        c = self.config
        keys = iter(jax.random.split(rng, 16))

        def dense(key, shape, scale_axis):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            return (jax.random.normal(key, shape, jnp.float32) * scale).astype(c.dtype)

        L, D, H, Hkv, Dh, F, V = (
            c.num_layers,
            c.hidden_size,
            c.num_heads,
            c.num_kv_heads,
            c.head_dim,
            c.intermediate_size,
            c.vocab_size,
        )
        params = {
            "embed": dense(next(keys), (V, D), 1),
            "layers": {
                "input_norm": jnp.ones((L, D), c.dtype),
                "wq": dense(next(keys), (L, D, H * Dh), 1),
                "wk": dense(next(keys), (L, D, Hkv * Dh), 1),
                "wv": dense(next(keys), (L, D, Hkv * Dh), 1),
                "wo": dense(next(keys), (L, H * Dh, D), 1),
                "post_norm": jnp.ones((L, D), c.dtype),
                "gate": dense(next(keys), (L, D, F), 1),
                "up": dense(next(keys), (L, D, F), 1),
                "down": dense(next(keys), (L, F, D), 1),
            },
            "final_norm": jnp.ones((D,), c.dtype),
        }
        if c.attention_bias:
            params["layers"]["bq"] = dense(next(keys), (L, H * Dh), 0)
            params["layers"]["bk"] = dense(next(keys), (L, Hkv * Dh), 0)
            params["layers"]["bv"] = dense(next(keys), (L, Hkv * Dh), 0)
        if not c.tie_word_embeddings:
            params["lm_head"] = dense(next(keys), (V, D), 1)
        return params

    def param_shardings(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        """NamedSharding pytree: attention heads and MLP hidden sharded on tp
        (replicated when the mesh is sp-only; any other missing axis raises
        so a misnamed tp mesh can't silently replicate a real model)."""
        tp_axis = _resolve_tp_axis(mesh, tp_axis)

        def ns(*spec):
            return NamedSharding(mesh, P(*spec))

        shardings = {
            "embed": ns(None, None),
            "layers": {
                "input_norm": ns(None, None),
                "wq": ns(None, None, tp_axis),
                "wk": ns(None, None, tp_axis),
                "wv": ns(None, None, tp_axis),
                "wo": ns(None, tp_axis, None),
                "post_norm": ns(None, None),
                "gate": ns(None, None, tp_axis),
                "up": ns(None, None, tp_axis),
                "down": ns(None, tp_axis, None),
            },
            "final_norm": ns(None),
        }
        if self.config.attention_bias:
            shardings["layers"]["bq"] = ns(None, tp_axis)
            shardings["layers"]["bk"] = ns(None, tp_axis)
            shardings["layers"]["bv"] = ns(None, tp_axis)
        if not self.config.tie_word_embeddings:
            shardings["lm_head"] = ns(tp_axis, None)
        return self._quantize_shardings(shardings)

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        """Shape of each of the two flat page pools (the "k" and "v" leaves).
        See kv_folded for the folded layout."""
        c = self.config
        if self.kv_folded:
            return (c.num_layers * num_pages, page_size, c.num_kv_heads * c.head_dim)
        return (c.num_layers * num_pages, page_size, c.num_kv_heads, c.head_dim)

    #: llama-family pools support the int8 KV cache (deepseek's latent cache
    #: does not — its compression IS its cache optimization)
    SUPPORTS_KV_INT8 = True

    def init_kv_cache(self, num_pages: int, page_size: int) -> dict:
        shape = self.kv_cache_shape(num_pages, page_size)
        if self.config.kv_quantized:
            # int8 pools + per-(page, token-row) f32 scale planes; the dict
            # keeps its {"k","v"} structure — QuantizedPages is a pytree
            # node, so the scan carry / donation / device_put paths are
            # unchanged (quant/kv.py)
            return {
                "k": init_quantized_pages(shape),
                "v": init_quantized_pages(shape),
            }
        return {
            "k": jnp.zeros(shape, self.config.dtype),
            "v": jnp.zeros(shape, self.config.dtype),
        }

    def kv_page_bytes(self, page_size: int) -> int:
        """HBM bytes one allocator page costs across all layers (K + V and,
        for int8, the scale planes) — the capacity/telemetry number."""
        c = self.config
        return _kv_page_bytes(
            page_size, c.num_kv_heads, c.head_dim, c.num_layers,
            "int8" if c.kv_quantized else None,
            itemsize=jnp.dtype(c.dtype).itemsize,
        )

    def kv_cache_sharding(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        tp_axis = _resolve_tp_axis(mesh, tp_axis)
        if self.kv_folded:
            # folded lane dim is head-major, so a tp split that divides Hkv
            # stays head-aligned
            ns = NamedSharding(mesh, P(None, None, tp_axis))
        else:
            ns = NamedSharding(mesh, P(None, None, tp_axis, None))
        if self.config.kv_quantized:
            # per-row scales are head-independent: replicated over tp
            ns = QuantizedPages(ns, NamedSharding(mesh, P(None, None)))
        return {"k": ns, "v": ns}

    def _layer_offsets(self, num_pages: int) -> jnp.ndarray:
        """[L] flat-pool offset of each layer's page 0 (its trash page)."""
        return jnp.arange(self.config.num_layers, dtype=jnp.int32) * num_pages

    # ---------------- disagg / offload wire format ----------------
    # The wire layout is the model's canonical block serialization for DCN
    # transfer and host offload; flat_ids is [L, n] (per-layer flat page ids).
    # `wire_n_axis` (2, the contract's default) is the axis of the per-page (n)
    # dimension in the wire arrays below.

    def gather_pages_wire(self, kv: dict, flat_ids: jnp.ndarray):
        """-> [L, 2, n, page_size, Hkv, D] ([..., Hkv*D] when kv_folded;
        scatter_pages_wire takes either layout from a peer).

        Int8 caches return ``{"q": int8 [L, 2, n, ps, ...], "s": f32
        [L, 2, n, ps]}`` — the scale plane travels WITH the pages (half the
        wire/host bytes; scales ride disagg part headers and host-pool
        entries, see quant/kv.py wire helpers)."""
        if isinstance(kv["k"], QuantizedPages):
            return {
                "q": jnp.stack([kv["k"].q[flat_ids], kv["v"].q[flat_ids]], axis=1),
                "s": jnp.stack([kv["k"].s[flat_ids], kv["v"].s[flat_ids]], axis=1),
            }
        return jnp.stack([kv["k"][flat_ids], kv["v"][flat_ids]], axis=1)

    def scatter_pages_wire(self, kv: dict, flat_ids: jnp.ndarray, data) -> dict:
        # a peer at another tp degree may hold the other pool layout (see
        # kv_folded): fold or unfold its blocks to ours
        tail = (kv["k"].q if isinstance(kv["k"], QuantizedPages) else kv["k"]).shape[2:]
        if isinstance(data, dict):
            data = dict(data, q=data["q"].reshape(data["q"].shape[:4] + tail))
        else:
            data = data.reshape(data.shape[:4] + tail)
        if isinstance(kv["k"], QuantizedPages):
            if isinstance(data, dict):
                q = data["q"].astype(jnp.int8)
                s = data["s"].astype(jnp.float32)
            else:
                # full-precision wire into an int8 cache (a bf16 peer, the
                # legacy inline path): quantize per token row on the way in
                rows = data.reshape(-1, data.shape[-1] if data.ndim == 5 else
                                    data.shape[-2] * data.shape[-1])
                qr, sr = quantize_kv_rows(rows)
                q = qr.reshape(data.shape).astype(jnp.int8)
                s = sr.reshape(data.shape[:4])
            return {
                "k": QuantizedPages(
                    kv["k"].q.at[flat_ids].set(q[:, 0]),
                    kv["k"].s.at[flat_ids].set(s[:, 0]),
                ),
                "v": QuantizedPages(
                    kv["v"].q.at[flat_ids].set(q[:, 1]),
                    kv["v"].s.at[flat_ids].set(s[:, 1]),
                ),
            }
        dt = kv["k"].dtype
        if isinstance(data, dict):
            # int8 wire into a full-precision cache: dequantize the rows
            s = data["s"].astype(jnp.float32)
            data = data["q"].astype(jnp.float32) * s.reshape(
                s.shape + (1,) * (data["q"].ndim - s.ndim)
            )
        return {
            "k": kv["k"].at[flat_ids].set(data[:, 0].astype(dt)),
            "v": kv["v"].at[flat_ids].set(data[:, 1].astype(dt)),
        }

    def wire_sharding(self, mesh: Mesh, tp_axis: str = "tp"):
        tp_axis = _resolve_tp_axis(mesh, tp_axis)
        if self.kv_folded:
            ns = NamedSharding(mesh, P(None, None, None, None, tp_axis))
        else:
            ns = NamedSharding(mesh, P(None, None, None, None, tp_axis, None))
        if self.config.kv_quantized:
            # dict wire: int8 data shards like the pool; scales replicate
            return {"q": ns, "s": NamedSharding(mesh, P())}
        return ns

    # ---------------- forward ----------------

    def _unembed(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        c = self.config
        with jax.named_scope("lm_head"):
            h = rms_norm(hidden, params["final_norm"], c.rms_norm_eps)
            head = params["embed"] if c.tie_word_embeddings else params["lm_head"]
            # bf16 MXU matmul with f32 accumulation — no materialized f32 cast
            # of the [V, D] head (bf16 products are exact in the f32 accumulator)
            return jax.lax.dot_general(
                h, head, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )

    def _layer(
        self,
        lp: dict,
        hidden: jnp.ndarray,  # [T, D]
        k_pool: jnp.ndarray,  # [LP, ps, Hkv, D] full flat pool (carried)
        v_pool: jnp.ndarray,  # [LP, ps, Hkv, D]
        positions: jnp.ndarray,  # [T] sequential positions (KV addressing)
        flat_phys: jnp.ndarray,  # [T] flat page per token (layer trash for invalid)
        offsets: jnp.ndarray,  # [T]
        attn_fn,
        rope_positions: jnp.ndarray | None = None,  # [T, 3] M-RoPE components
        tp_axis: str | None = None,  # set inside an explicit (pp, tp) shard_map
        sp_axis: str | None = None,  # set inside a composed (pp, sp[, tp]) shard_map
        lora_mods: dict | None = None,  # this layer's slot-stacked LoRA planes
        lora_ids=None,  # [T] per-token adapter slot ids (or scalar)
        lora_scales: jnp.ndarray | None = None,  # [S] per-slot alpha/r
    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """One transformer layer. Under GSPMD (pp == 1) the tp sharding is
        handled by the compiler; inside an explicit shard_map over a composed
        (pp, tp) mesh this runs on the LOCAL head shard (wq/wk/wv column
        shards, wo/down row shards) and ``tp_axis`` names the axis for the
        two Megatron-style psums that complete each residual branch.

        ``sp_axis`` (composed pp x sp ring prefill): the token dim is sharded
        over sp, so before the pool scatter the fresh K/V rows (+ their page
        addresses) all-gather over sp — every sp peer writes ALL the chunk's
        rows and the stage's pool replicas stay bit-identical, which the
        decode path (replicated over sp) depends on. This mirrors the
        all-gather GSPMD inserts on the pure-sp path for the same scatter."""
        c = self.config
        T = hidden.shape[0]
        # named scopes: an operation's metadata in a profiler trace says which
        # PART of the step it belongs to (they change no computation). The
        # innermost name of the closed vocabulary (benchmark/trace_parts.py
        # PARTS) counts: rms_norm is `norm`, rope and the cache write are
        # `attn_kv`, the attention dispatch is `attn`, by their own scopes
        h = rms_norm(hidden, lp["input_norm"], c.rms_norm_eps)
        with jax.named_scope("attn_proj"):
            # qlinear == `h @ w` for full-precision weights; int8 weight-only
            # leaves dequantize inside the fused dot (dynamo_tpu/quant/int8.py)
            q_flat = qlinear(h, lp["wq"])
            k_flat = qlinear(h, lp["wk"])
            v_flat = qlinear(h, lp["wv"])
            if lora_mods is not None:
                # the adapter delta rides ON TOP of qlinear unchanged (int8 base
                # weights compose: dequant-in-matmul below, f32 delta here); k/v
                # deltas land BEFORE rope + the pool scatter, so cached pages are
                # adapter-specific — which the lora-salted block identity encodes
                q_flat = q_flat + lora_delta(h, lora_mods["wq"], lora_ids, lora_scales)
                k_flat = k_flat + lora_delta(h, lora_mods["wk"], lora_ids, lora_scales)
                v_flat = v_flat + lora_delta(h, lora_mods["wv"], lora_ids, lora_scales)
            if c.attention_bias:
                q_flat = q_flat + lp["bq"]
                k_flat = k_flat + lp["bk"]
                v_flat = v_flat + lp["bv"]
            # head counts from the weight shard, not the config: inside a tp
            # shard_map each device sees num_heads / tp of them
            q = q_flat.reshape(T, -1, c.head_dim)
            k = k_flat.reshape(T, -1, c.head_dim)
            v = v_flat.reshape(T, -1, c.head_dim)
        with jax.named_scope("attn_kv"):
            if c.mrope_section is not None:
                pos3 = (
                    rope_positions
                    if rope_positions is not None
                    else jnp.stack([positions] * 3, axis=-1)
                )
                q = apply_mrope(q, pos3, tuple(c.mrope_section), c.rope_theta)
                k = apply_mrope(k, pos3, tuple(c.mrope_section), c.rope_theta)
            else:
                q = apply_rope(q, positions, c.rope_theta)
                k = apply_rope(k, positions, c.rope_theta)
            # scatter_kv folds the new rows itself when the pool is lane-folded
            if sp_axis is not None:
                k_all = jax.lax.all_gather(k, sp_axis, axis=0, tiled=True)
                v_all = jax.lax.all_gather(v, sp_axis, axis=0, tiled=True)
                phys_all = jax.lax.all_gather(flat_phys, sp_axis, axis=0, tiled=True)
                off_all = jax.lax.all_gather(offsets, sp_axis, axis=0, tiled=True)
                k_pool, v_pool = scatter_kv(k_pool, v_pool, k_all, v_all, phys_all, off_all)
            else:
                k_pool, v_pool = scatter_kv(k_pool, v_pool, k, v, flat_phys, offsets)
        with jax.named_scope("attn"):
            # attn_fn sees both the updated pools (paged paths) and the chunk's
            # fresh rows (ring/SP path, which never reads the pool)
            attn = attn_fn(q, k, v, k_pool, v_pool)
        with jax.named_scope("attn_proj"):
            attn_flat = attn.reshape(T, -1)
            attn_out = qlinear(attn_flat, lp["wo"])
            if lora_mods is not None:
                attn_out = attn_out + lora_delta(
                    attn_flat, lora_mods["wo"], lora_ids, lora_scales
                )
            if tp_axis is not None:
                attn_out = jax.lax.psum(attn_out, tp_axis)
            hidden = hidden + attn_out
        h = rms_norm(hidden, lp["post_norm"], c.rms_norm_eps)
        with jax.named_scope("mlp"):
            g = qlinear(h, lp["gate"])
            u = qlinear(h, lp["up"])
            if lora_mods is not None:
                g = g + lora_delta(h, lora_mods["gate"], lora_ids, lora_scales)
                u = u + lora_delta(h, lora_mods["up"], lora_ids, lora_scales)
            prod = jax.nn.silu(g) * u
            mlp = qlinear(prod, lp["down"])
            if lora_mods is not None:
                mlp = mlp + lora_delta(prod, lora_mods["down"], lora_ids, lora_scales)
            if tp_axis is not None:
                mlp = jax.lax.psum(mlp, tp_axis)
            hidden = hidden + mlp
        return hidden, k_pool, v_pool

    def _prefill_common(
        self, params, kv_cache, tokens, positions, page_table, valid, last_idx, make_attn_fn,
        input_embeds=None, embeds_mask=None, rope_positions=None,
        lora=None, lora_id=None,
    ) -> tuple[jnp.ndarray, dict]:
        """Shared prefill machinery; make_attn_fn(off) -> attn_fn for a layer
        (off = the layer's flat-pool offset). input_embeds [T, D] + embeds_mask
        [T] override the token embeddings where the mask is set (multimodal:
        vision-tower outputs replace image-slot virtual tokens). ``lora``
        (the slot-stacked adapter pool) + scalar ``lora_id`` apply one
        adapter's delta to the whole chunk (a chunk belongs to one sequence;
        id 0 gathers the zero adapter)."""
        c = self.config
        k_pool, v_pool = kv_cache["k"], kv_cache["v"]
        page_size = k_pool.shape[1]
        num_pages = k_pool.shape[0] // c.num_layers
        with jax.named_scope("attn_kv"):  # where each row's K and V go
            phys = jnp.where(valid, page_table[positions // page_size], 0)
            offsets = jnp.where(valid, positions % page_size, 0)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(c.dtype)
            if input_embeds is not None:
                hidden = jnp.where(embeds_mask[:, None], input_embeds.astype(c.dtype), hidden)

        def body(carry, xs):
            h, kp, vp = carry
            lp, off = xs[0], xs[1]
            lkw = {}
            if lora is not None:
                lkw = dict(
                    lora_mods=xs[2], lora_ids=lora_id, lora_scales=lora["scales"]
                )
            h, kp, vp = self._layer(
                lp, h, kp, vp, positions, off + phys, offsets, make_attn_fn(off),
                rope_positions=rope_positions, **lkw,
            )
            return (h, kp, vp), None

        xs_all = (params["layers"], self._layer_offsets(num_pages))
        if lora is not None:
            xs_all = xs_all + (lora["mods"],)
        (hidden, k_pool, v_pool), _ = jax.lax.scan(
            body, (hidden, k_pool, v_pool), xs_all
        )
        logits = self._unembed(params, hidden[last_idx][None, :])[0]
        return logits, {"k": k_pool, "v": v_pool}

    def prefill(
        self,
        params: dict,
        kv_cache: dict,  # {"k","v"} flat pools (donated)
        tokens: jnp.ndarray,  # [T] padded chunk
        positions: jnp.ndarray,  # [T] absolute positions
        page_table: jnp.ndarray,  # [max_pages] logical (per-layer) page ids
        valid: jnp.ndarray,  # [T] bool
        last_idx: jnp.ndarray,  # scalar: index of the final real token in chunk
        input_embeds: jnp.ndarray | None = None,  # [T, D] mm embedding overrides
        embeds_mask: jnp.ndarray | None = None,  # [T] bool
        rope_positions: jnp.ndarray | None = None,  # [T, 3] M-RoPE components
        lora: dict | None = None,  # slot-stacked adapter pool (lora/store.py)
        lora_id=None,  # scalar adapter slot for this chunk (0 = base)
    ) -> tuple[jnp.ndarray, dict]:
        """One (possibly chunked) prefill pass for a single sequence.

        Returns (logits[V] at last_idx, updated kv_cache).
        """

        def make_attn_fn(off):
            return _on_pools(prefill_attend(page_table, positions, self.attn_mesh, off))

        return self._prefill_common(
            params, kv_cache, tokens, positions, page_table, valid, last_idx, make_attn_fn,
            input_embeds=input_embeds, embeds_mask=embeds_mask,
            rope_positions=rope_positions, lora=lora, lora_id=lora_id,
        )

    def prefill_packed(
        self,
        params: dict,
        kv_cache: dict,  # {"k","v"} flat pools (donated)
        tokens: jnp.ndarray,  # [N, T] bucket-padded chunks, one per lane
        positions: jnp.ndarray,  # [N, T] absolute positions per lane
        page_tables: jnp.ndarray,  # [N, max_pages] logical page ids per lane
        valid: jnp.ndarray,  # [N, T] bool
        last_idx: jnp.ndarray,  # [N] index of each lane's final real token
        lora: dict | None = None,  # slot-stacked adapter pool
        lora_ids: jnp.ndarray | None = None,  # [N] per-lane adapter slots
    ) -> tuple[jnp.ndarray, dict]:
        """Cross-request packed prefill: N lanes flattened into one [N*T]
        token stream so the layer matmuls read the weights ONCE per call
        instead of once per request — the per-call overhead and weight
        traffic of N short prefills for the price of one (the reference's
        engines batch prefills the same way; vLLM scheduler: SURVEY.md §2.4).
        A lane is T consecutive rows of one sequence with that sequence's
        page table. Lanes need NOT be distinct sequences: every layer
        scatters all lanes' new rows into the pages before any lane's
        attention reads them, so a sequence's chunk rides as consecutive
        lanes one block apart (the scheduler's block packer), each reading
        the blocks before it back from the pages like any older context.

        Returns (logits [N, V] at each lane's last_idx, updated kv_cache)."""
        N, T = tokens.shape
        hidden, kv_cache = self._packed_forward(
            params, kv_cache, tokens, positions, page_tables, valid,
            lora=lora, lora_ids=lora_ids,
        )
        logits = self._unembed(params, last_rows(hidden, T, last_idx))  # [N, V]
        return logits, kv_cache

    def _packed_forward(
        self,
        params: dict,
        kv_cache: dict,
        tokens: jnp.ndarray,  # [N, T]
        positions: jnp.ndarray,  # [N, T]
        page_tables: jnp.ndarray,  # [N, max_pages]
        valid: jnp.ndarray,  # [N, T]
        lora: dict | None = None,
        lora_ids: jnp.ndarray | None = None,  # [N] per-lane adapter slots
    ) -> tuple[jnp.ndarray, dict]:
        """Shared N-lane layer stack for prefill_packed and verify: one weight
        pass over the flattened [N*T] token stream, per-lane paged attention.
        A mixed-adapter pack broadcasts each lane's slot id over its tokens —
        one gathered dispatch, not N per-adapter calls. The attention is N
        copies of the kernel call, not one instance under `jax.lax.map` (as
        models/cohere2_moe.py has it for 128 query heads): at 16 heads 8
        copies compile in 0.4 s more a program and run 1.6-2.5% faster a pack
        (tools/profile_prefill_pack.py, PERF.md section 5, PR 40).
        Returns (hidden [N*T, D], updated kv_cache)."""
        c = self.config
        k_pool, v_pool = kv_cache["k"], kv_cache["v"]
        N, T = tokens.shape
        pack = Pack(page_tables, positions, valid, k_pool.shape[1], self.attn_mesh, flat=())
        pos_flat = pack.flat_positions
        num_pages = k_pool.shape[0] // c.num_layers
        with jax.named_scope("embed"):
            hidden = params["embed"][tokens.reshape(N * T)].astype(c.dtype)
        ids_flat = None
        if lora is not None:
            ids_flat = jnp.repeat(
                lora_ids.astype(jnp.int32)
                if lora_ids is not None
                else jnp.zeros(N, jnp.int32),
                T,
            )

        def body(carry, xs):
            h, kp, vp = carry
            lp, off = xs[0], xs[1]
            lkw = {}
            if lora is not None:
                lkw = dict(
                    lora_mods=xs[2], lora_ids=ids_flat, lora_scales=lora["scales"]
                )
            h, kp, vp = self._layer(
                lp, h, kp, vp, pos_flat,
                off + pack.phys.reshape(N * T), pack.offsets.reshape(N * T),
                _on_pools(pack.attend(off)), **lkw,
            )
            return (h, kp, vp), None

        xs_all = (params["layers"], self._layer_offsets(num_pages))
        if lora is not None:
            xs_all = xs_all + (lora["mods"],)
        (hidden, k_pool, v_pool), _ = jax.lax.scan(
            body, (hidden, k_pool, v_pool), xs_all
        )
        return hidden, {"k": k_pool, "v": v_pool}

    def verify(
        self,
        params: dict,
        kv_cache: dict,  # {"k","v"} flat pools (donated)
        tokens: jnp.ndarray,  # [B, T] anchor + draft tokens per slot
        positions: jnp.ndarray,  # [B, T] consecutive fed positions per slot
        page_tables: jnp.ndarray,  # [B, max_pages] logical page ids per slot
        valid: jnp.ndarray,  # [B, T] bool (invalid rows -> trash page)
        lora: dict | None = None,  # slot-stacked adapter pool
        lora_ids: jnp.ndarray | None = None,  # [B] per-slot adapter slots
    ) -> tuple[jnp.ndarray, dict]:
        """Speculative verification: every slot feeds T = k+1 tokens at
        consecutive positions through the paged context in ONE weight pass
        (the multi-query-position generalization of decode — structurally the
        packed-prefill path with tiny chunks, so causal masking against the
        page table comes for free) and unembeds ALL rows.

        Returns (logits [B, T, V], updated kv_cache): logits[:, i] is the
        next-token distribution after the token fed at positions[:, i]. KV
        rows for invalid/rejected positions land on the trash page or are
        overwritten by the next pass at the advanced anchor."""
        B, T = tokens.shape
        hidden, kv_cache = self._packed_forward(
            params, kv_cache, tokens, positions, page_tables, valid,
            lora=lora, lora_ids=lora_ids,
        )
        logits = self._unembed(params, hidden)  # [B*T, V]
        return logits.reshape(B, T, -1), kv_cache

    def prefill_sp(
        self,
        params: dict,
        kv_cache: dict,  # {"k","v"} flat pools (donated)
        tokens: jnp.ndarray,  # [T] padded FULL prompt, T % sp == 0, start at pos 0
        positions: jnp.ndarray,  # [T] == arange(T)
        page_table: jnp.ndarray,  # [max_pages]
        valid: jnp.ndarray,  # [T] bool
        last_idx: jnp.ndarray,
        mesh: Mesh,
        sp_axis: str = "sp",
        lora: dict | None = None,
        lora_id=None,  # scalar adapter slot for this whole-prompt chunk
    ) -> tuple[jnp.ndarray, dict]:
        """Sequence-parallel prefill: the chunk's attention runs as ring
        attention over the ``sp`` mesh axis (K/V shards rotate via ppermute on
        ICI; no chip ever holds the full sequence's working set — the
        long-context path the reference lacks, SURVEY.md §2.8). The per-token
        projections stay GSPMD-sharded on the token axis; the paged-pool
        scatter reshards rows automatically. Only whole-prompt chunks
        (cached_len 0) qualify — ring attention derives global positions from
        ring offsets, so the chunk must start at position 0.

        Returns (logits[V] at last_idx, updated kv_cache)."""
        from dynamo_tpu.ops.ring_attention import ring_attention

        def make_attn_fn(off):
            def attn_fn(q, k_new, v_new, kp_, vp_):
                # ring attention consumes the chunk's own fresh K/V rows
                # directly; the pool is write-only on this path
                return ring_attention(q, k_new, v_new, mesh, axis=sp_axis)

            return attn_fn

        return self._prefill_common(
            params, kv_cache, tokens, positions, page_table, valid, last_idx,
            make_attn_fn, lora=lora, lora_id=lora_id,
        )

    def decode(
        self,
        params: dict,
        kv_cache: dict,  # {"k","v"} flat pools (donated)
        tokens: jnp.ndarray,  # [B] current token per slot
        positions: jnp.ndarray,  # [B] its absolute position
        page_tables: jnp.ndarray,  # [B, max_pages] logical (per-layer) page ids
        active: jnp.ndarray,  # [B] bool
        rope_deltas: jnp.ndarray | None = None,  # [B] M-RoPE position offsets
        lora: dict | None = None,  # slot-stacked adapter pool
        lora_ids: jnp.ndarray | None = None,  # [B] per-slot adapter ids
    ) -> tuple[jnp.ndarray, dict]:
        """One decode step for the whole batch. Returns (logits[B, V], kv_cache).

        rope_deltas (M-RoPE models): the decode rope position is
        ``positions + rope_deltas`` on every component — the per-sequence
        offset between sequential KV positions and the 3D rope timeline that
        image grids introduced during prefill."""
        c = self.config
        k_pool, v_pool = kv_cache["k"], kv_cache["v"]
        num_pages = k_pool.shape[0] // c.num_layers
        B = tokens.shape[0]
        step = DecodeStep(page_tables, positions, active, k_pool, c.head_dim, self.attn_mesh,
                          logical_first=True)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(c.dtype)
        rope_pos3 = None
        if c.mrope_section is not None and rope_deltas is not None:
            rp = positions + rope_deltas
            rope_pos3 = jnp.stack([rp] * 3, axis=-1)

        def body(carry, xs):
            h, kp, vp = carry
            lp, off = xs[0], xs[1]
            lkw = {}
            if lora is not None:
                lkw = dict(
                    lora_mods=xs[2],
                    lora_ids=lora_ids
                    if lora_ids is not None
                    else jnp.zeros(B, jnp.int32),
                    lora_scales=lora["scales"],
                )
            h, kp, vp = self._layer(
                lp, h, kp, vp, positions, off + step.phys, step.offsets,
                _on_pools(step.attend(off)), rope_positions=rope_pos3, **lkw,
            )
            return (h, kp, vp), None

        xs_all = (params["layers"], self._layer_offsets(num_pages))
        if lora is not None:
            xs_all = xs_all + (lora["mods"],)
        (hidden, k_pool, v_pool), _ = jax.lax.scan(
            body, (hidden, k_pool, v_pool), xs_all
        )
        logits = self._unembed(params, hidden)
        return logits, {"k": k_pool, "v": v_pool}
