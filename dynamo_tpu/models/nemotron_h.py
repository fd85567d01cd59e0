"""NemotronH (`NemotronHForCausalLM`): a hybrid decoder whose blocks are NOT one
scanned stack. `hybrid_override_pattern` gives each block ONE mixer:

  `M`  Mamba-2: a state-space layer with a fixed-size state per sequence
       (ops/ssm.py; the one-token update is a kernel, ops/pallas/ssm_update.py)
  `*`  attention: grouped-query, causal, NO rotary embedding, on the paged KV
       pool and the same kernels as the Llama family (ops/attention.py)
  `E`  latent experts: sigmoid router over ALL experts routed over, experts in
       a latent width, `relu(x)^2`, one shared expert (ops/moe.py)

and every block is `h = h + mixer(RMSNorm(h))` on a residual in the model's
dtype; then `norm_f` and an untied head.

Two caches. The paged KV pool holds the attention blocks only (flat over them,
as models/llama.py lays it out). Beside it every Mamba block keeps, per DECODE
SLOT and not per page, a float32 state [H, P, N] and the last `conv_kernel - 1`
inputs of its convolution: rows of two flat arrays, `slot` of block m at row
`m * (max_seqs + 1) + slot`, the last row of each block being a trash row for
a prefill pack's padding lanes (a decode step serves its live rows only:
`ops/live_rows.py`). A chunk that starts at position 0
starts from zeros, so a slot needs no clearing between sequences; a later chunk
and every decode step continue from what the row holds. The engine gives the
slot (`state_slot(s)`); nothing here knows about requests.

An expert layer may hold a share of the experts (`n_routed_experts` held, from
`moe_expert_offset`, of `moe_routed_over`): see ops/moe.py.
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
from dynamo_tpu.ops.moe import grouped_matmul, moe_dispatch, relu2, sigmoid_topk_routing
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.ssm import causal_conv, ssd_chunked, ssm_state_update


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "MEMEMEM*EME"  # hybrid_override_pattern
    # attention blocks
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2 blocks
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    # expert blocks: experts HELD here, of how many routed over, from which id
    n_routed_experts: int = 512
    moe_routed_over: int = 512
    moe_expert_offset: int = 0
    num_experts_per_tok: int = 22
    moe_latent_size: int = 1024
    moe_intermediate_size: int = 2688
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def routed_per_token(self) -> int:
        """Expert assignments one token makes through the whole model."""
        return self.num_experts_per_tok * self.count("E")

    @classmethod
    def from_hf_config(cls, d: dict) -> "NemotronHConfig":
        pattern = d["hybrid_override_pattern"]
        if len(pattern) != d["num_hidden_layers"] or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} must name num_hidden_layers="
                f"{d['num_hidden_layers']} blocks, each M, E or *"
            )
        unsupported = {
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "mlp_hidden_act": "relu2", "n_shared_experts": 1,
            "use_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": False, "mlp_bias": False,
        }
        for key, want in unsupported.items():
            if d.get(key, want) != want:
                raise ValueError(f"nemotron_h: {key}={d[key]!r} is not supported (only {want!r})")
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            pattern=pattern,
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"],
            mamba_num_heads=d["mamba_num_heads"],
            mamba_head_dim=d["mamba_head_dim"],
            ssm_state_size=d["ssm_state_size"],
            n_groups=d["n_groups"],
            conv_kernel=d["conv_kernel"],
            chunk_size=d.get("chunk_size", 128),
            n_routed_experts=d["n_routed_experts"],
            moe_routed_over=d.get("moe_routed_over", d["n_routed_experts"]),
            moe_expert_offset=d.get("moe_expert_offset", 0),
            num_experts_per_tok=d["num_experts_per_tok"],
            moe_latent_size=d["moe_latent_size"],
            moe_intermediate_size=d["moe_intermediate_size"],
            moe_shared_expert_intermediate_size=d["moe_shared_expert_intermediate_size"],
            routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
            rms_norm_eps=d.get("layer_norm_epsilon", d.get("norm_eps", 1e-5)),
            dtype=parse_dtype(d.get("torch_dtype") or "bfloat16"),
        )

    @classmethod
    def tiny(cls, **overrides) -> "NemotronHConfig":
        """Small config for tests: every kind of block, a share of the experts."""
        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        base = cls(
            vocab_size=256, hidden_size=64, pattern="ME*E",
            num_heads=4, num_kv_heads=2, head_dim=16,
            mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16, n_groups=2,
            conv_kernel=4, chunk_size=16,
            n_routed_experts=4, moe_routed_over=8, moe_expert_offset=0,
            num_experts_per_tok=3, moe_latent_size=32, moe_intermediate_size=48,
            moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
            dtype=jnp.float32,
        )
        return replace(base, **overrides)


class NemotronHModel(PackedPrefillModel):
    """Stateless forward functions over a params pytree (models/paged.py's
    contract; `prefill` and `prefill_packed` are `PackedPrefillModel`'s over
    `_packed_forward`, with the per-slot state: `state_slot(s)`). One chip:
    expert parallelism across chips is not built, so `attn_mesh` stays None."""

    recurrent = True

    # ---------------- params ----------------

    def init_params(self, rng: jax.Array) -> dict:
        c = self.config
        keys = iter(jax.random.split(rng, 16 * c.num_layers + 4))

        def dense(shape, scale_axis=0, dtype=None):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            w = jax.random.normal(next(keys), shape, jnp.float32) * scale
            return w.astype(dtype or c.dtype)

        def small(shape):
            return jax.random.normal(next(keys), shape, jnp.float32) * 0.5

        D, H = c.hidden_size, c.mamba_num_heads
        inner, cd = c.mamba_inner, c.conv_dim
        Z, F, Fs = c.moe_latent_size, c.moe_intermediate_size, c.moe_shared_expert_intermediate_size
        blocks = []
        for kind in c.pattern:
            bp = {"norm": jnp.ones((D,), c.dtype)}
            if kind == "M":
                bp.update(
                    in_proj=dense((D, inner + cd + H)),
                    conv_w=small((c.conv_kernel, cd)),
                    conv_b=small((cd,)),
                    dt_bias=small((H,)),
                    A_log=small((H,)),
                    D=small((H,)) + 1.0,
                    mixer_norm=jnp.ones((inner,), c.dtype),
                    out_proj=dense((inner, D)),
                )
            elif kind == "*":
                bp.update(
                    wq=dense((D, c.num_heads * c.head_dim)),
                    wk=dense((D, c.num_kv_heads * c.head_dim)),
                    wv=dense((D, c.num_kv_heads * c.head_dim)),
                    wo=dense((c.num_heads * c.head_dim, D)),
                )
            else:
                bp.update(
                    router=dense((D, c.moe_routed_over), dtype=jnp.float32),
                    router_bias=small((c.moe_routed_over,)) * 0.1,
                    lat_down=dense((D, Z)),
                    lat_up=dense((Z, D)),
                    w1=dense((c.n_routed_experts, Z, F), 1),
                    w2=dense((c.n_routed_experts, F, Z), 1),
                    shared_up=dense((D, Fs)),
                    shared_down=dense((Fs, D)),
                )
            blocks.append(bp)
        return {
            "embed": dense((c.vocab_size, D), 1),
            "blocks": blocks,
            "final_norm": jnp.ones((D,), c.dtype),
            "lm_head": dense((c.vocab_size, D), 1),
        }

    # ---------------- the paged KV pool (attention blocks only) ----------------

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        c = self.config
        return (c.count("*") * num_pages, page_size, c.num_kv_heads, c.head_dim)

    # ---------------- the per-slot state cache (Mamba blocks) ----------------

    window_counters = ExpertCounts.NAMES

    def init_state_cache(self, max_seqs: int) -> dict:
        """`ssm` and `conv`, a row per (Mamba block, slot) plus each block's
        trash row, and the expert counters."""
        c = self.config
        rows = c.count("M") * (max_seqs + 1)
        return {
            "ssm": jnp.zeros(
                (rows, c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size), jnp.float32
            ),
            "conv": jnp.zeros((rows, c.conv_kernel - 1, c.conv_dim), c.dtype),
            **ExpertCounts.leaves(c.n_routed_experts),
        }

    # ---------------- blocks ----------------

    #: the residual add after a block counts with the projection that feeds it
    RESIDUAL_PART = {"M": "ssm_proj", "*": "attn_proj", "E": "shared_experts"}

    def _split_proj(self, proj):
        c = self.config
        z = proj[..., : c.mamba_inner]
        xbc = proj[..., c.mamba_inner : c.mamba_inner + c.conv_dim]
        dt = proj[..., c.mamba_inner + c.conv_dim :]
        return z, xbc, dt

    def _split_xbc(self, xbc):
        """silu(conv) [..., conv_dim] float32 -> x [..., H, P], B, C [..., G, N]."""
        c = self.config
        gn = c.n_groups * c.ssm_state_size
        lead = xbc.shape[:-1]
        x = xbc[..., : c.mamba_inner].reshape(*lead, c.mamba_num_heads, c.mamba_head_dim)
        B = xbc[..., c.mamba_inner : c.mamba_inner + gn].reshape(*lead, c.n_groups, c.ssm_state_size)
        C = xbc[..., c.mamba_inner + gn :].reshape(*lead, c.n_groups, c.ssm_state_size)
        return x, B, C

    def _mamba_out(self, bp, y, z):
        """`GroupRMSNorm(y * silu(z)) * w` (the gate BEFORE the norm, one norm
        per group of inner / n_groups), then the output projection."""
        c = self.config
        with jax.named_scope("ssm"):
            g = y * jax.nn.silu(z.astype(jnp.float32))
            gg = g.reshape(*g.shape[:-1], c.n_groups, c.mamba_inner // c.n_groups)
            gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True) + c.rms_norm_eps)
            g = gg.reshape(g.shape) * bp["mixer_norm"].astype(jnp.float32)
        with jax.named_scope("ssm_proj"):
            return g.astype(c.dtype) @ bp["out_proj"]

    def _mamba_prefill(self, bp, h, cache, rows, fresh, valid):
        """h [L, T, D]; rows [L] this block's state row per lane; fresh [L]:
        the lane starts its sequence; valid [L, T]."""
        c = self.config
        # parts by scope (benchmark/trace_parts.py PARTS): the projections are
        # `ssm_proj`; convolution, scan and state rows are `ssm`
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = self._split_proj(h @ bp["in_proj"])
        with jax.named_scope("ssm"):
            window = jnp.where(fresh[:, None, None], 0, cache["conv"][rows])
            n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
            xbc, window = causal_conv(xbc, window, bp["conv_w"], bp["conv_b"], n_valid)
            x, B, C = self._split_xbc(jax.nn.silu(xbc))
            # padding: dt = 0 is the identity on the state
            dt = jax.nn.softplus(dt.astype(jnp.float32) + bp["dt_bias"]) * valid[..., None]
            state = jnp.where(fresh[:, None, None, None], 0.0, cache["ssm"][rows])
            y, state = ssd_chunked(
                x, dt, -jnp.exp(bp["A_log"]), B, C, bp["D"], state, c.chunk_size
            )
            cache = dict(
                cache,
                ssm=cache["ssm"].at[rows].set(state),
                conv=cache["conv"].at[rows].set(window),
            )
        return self._mamba_out(bp, y.reshape(*y.shape[:2], c.mamba_inner), z), cache

    def _mamba_decode(self, bp, h, cache, base, live):
        """h [B, D]; batch row b's state is row base + b; rows that are not
        live (the step's `LiveRows`) leave state and window as they were."""
        c = self.config
        nb = h.shape[0]
        with jax.named_scope("ssm_proj"):
            z, xbc, dt = self._split_proj(h @ bp["in_proj"])
        with jax.named_scope("ssm"):
            mine = base + jnp.arange(nb)
            xbc, window = causal_conv(
                xbc[:, None, :], cache["conv"][mine], bp["conv_w"], bp["conv_b"],
                live.mask.astype(jnp.int32),
            )
            x, B, C = self._split_xbc(jax.nn.silu(xbc[:, 0]))
            dt = jax.nn.softplus(dt.astype(jnp.float32) + bp["dt_bias"])
            y, ssm = ssm_state_update(
                cache["ssm"], mine, x, dt, -jnp.exp(bp["A_log"]), B, C, bp["D"], live,
            )
            cache = dict(cache, ssm=ssm, conv=cache["conv"].at[mine].set(window))
        return self._mamba_out(bp, y.reshape(nb, c.mamba_inner), z), cache

    def _attention(self, bp, h, kv, flat_phys, offsets, attn_fn):
        """h [T, D]. No rotary embedding: the published model applies none."""
        c = self.config
        T = h.shape[0]
        with jax.named_scope("attn_proj"):
            q = (h @ bp["wq"]).reshape(T, c.num_heads, c.head_dim)
            k = (h @ bp["wk"]).reshape(T, c.num_kv_heads, c.head_dim)
            v = (h @ bp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)
        k_pool, v_pool = scatter_kv(kv["k"], kv["v"], k, v, flat_phys, offsets)  # `attn_kv`
        with jax.named_scope("attn"):
            attn = attn_fn(q, k_pool, v_pool)
        with jax.named_scope("attn_proj"):
            return attn.reshape(T, -1) @ bp["wo"], dict(kv, k=k_pool, v=v_pool)

    def _experts(self, bp, h, count_rows=None):
        """h [T, D] -> (out [T, D], the held experts' assignment counts over
        the rows of `count_rows` (all rows when None))."""
        c = self.config
        weights, idx = route(
            h, bp["router"],
            lambda logits: sigmoid_topk_routing(
                logits, bp["router_bias"], c.num_experts_per_tok, c.routed_scaling_factor),
            count_rows,
        )

        def ffn(rows, group_sizes):  # `moe_dispatch` calls it under `moe_experts`
            mid = relu2(grouped_matmul(rows, bp["w1"], group_sizes))
            return grouped_matmul(mid, bp["w2"], group_sizes)

        # the latent projections around the experts are dense matrices every
        # row meets: they count with the shared expert
        with jax.named_scope("shared_experts"):
            latent = h @ bp["lat_down"]
        routed, counts = moe_dispatch(
            latent, weights, idx, ffn,
            num_held=c.n_routed_experts, offset=c.moe_expert_offset,
        )
        with jax.named_scope("shared_experts"):
            out = routed.astype(c.dtype) @ bp["lat_up"]
            return out + relu2(h @ bp["shared_up"]) @ bp["shared_down"], counts

    def _unembed(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        with jax.named_scope("lm_head"):
            h = rms_norm(hidden, params["final_norm"], self.config.rms_norm_eps)
            return jax.lax.dot_general(
                h, params["lm_head"], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    # ---------------- forward ----------------

    def _packed_forward(self, params, cache, tokens, positions, page_tables, valid, state_slots):
        """N lanes (chunks of N different sequences) through every block.
        Returns (hidden [N*T, D], cache)."""
        c = self.config
        N, T = tokens.shape
        num_pages = cache["k"].shape[0] // max(1, c.count("*"))
        slot_rows = cache["ssm"].shape[0] // max(1, c.count("M"))
        pack = Pack(page_tables, positions, valid, cache["k"].shape[1], self.attn_mesh)
        fresh, slots = state_rows(state_slots, slot_rows, positions)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens.reshape(N * T)].astype(c.dtype)
        m = a = 0
        for kind, bp in zip(c.pattern, params["blocks"]):
            h = rms_norm(hidden, bp["norm"], c.rms_norm_eps)
            if kind == "M":
                out, cache = self._mamba_prefill(
                    bp, h.reshape(N, T, -1), cache, m * slot_rows + slots, fresh, valid
                )
                out = out.reshape(N * T, -1)
                m += 1
            elif kind == "*":
                off = a * num_pages
                out, cache = self._attention(
                    bp, h, cache, off + pack.phys.reshape(N * T), pack.offsets, pack.attend(off)
                )
                a += 1
            else:
                out, _ = self._experts(bp, h)
            with jax.named_scope(self.RESIDUAL_PART[kind]):
                hidden = hidden + out
        return hidden, cache

    def decode(self, params, kv_cache, tokens, positions, page_tables, active,
               rope_deltas=None):
        """One decode step for the whole batch; batch row b is decode slot b.
        Returns (logits [B, V], cache)."""
        c = self.config
        cache = kv_cache
        num_pages = cache["k"].shape[0] // max(1, c.count("*"))
        slot_rows = cache["ssm"].shape[0] // max(1, c.count("M"))
        step = DecodeStep(page_tables, positions, active, cache["k"], c.head_dim, self.attn_mesh)

        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(c.dtype)
        routed = ExpertCounts(cache)
        m = a = 0
        for kind, bp in zip(c.pattern, params["blocks"]):
            h = rms_norm(hidden, bp["norm"], c.rms_norm_eps)
            if kind == "M":
                out, cache = self._mamba_decode(bp, h, cache, m * slot_rows, step.live)
                m += 1
            elif kind == "*":
                off = a * num_pages
                out, cache = self._attention(
                    bp, h, cache, off + step.phys, step.offsets, step.attend(off))
                a += 1
            else:
                out, n = self._experts(bp, h, count_rows=active)
                routed.add(n)
            with jax.named_scope(self.RESIDUAL_PART[kind]):
                hidden = hidden + out
        return self._unembed(params, hidden), routed.into(cache)
