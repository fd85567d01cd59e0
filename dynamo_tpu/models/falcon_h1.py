"""Falcon-H1 (`FalconH1ForCausalLM`, tiiuae): a hybrid decoder whose every block
runs TWO mixers on the same normed input and adds them, then a dense SwiGLU:

    u = RMSNorm_input(h)
    h = h + mamba(u) * ssm_out_multiplier + attention(u) * attention_out_multiplier
    h = h + swiglu(RMSNorm_pre_ff(h))

  mamba      Mamba-2 (ops/ssm.py; the one-token update is a kernel,
             ops/pallas/ssm_update.py): `[z | x B C | dt] = (W_in (u *
             ssm_in_multiplier)) * m`, m = `ssm_multipliers` over the five
             segments; x, B, C through a causal depthwise convolution and
             silu; `y = GroupRMSNorm(scan(x, B, C, dt) * silu(z))` (the gate
             BEFORE the norm, one norm a group); `W_out y`
  attention  grouped-query, causal, rope by halves over the whole head, the
             keys times `key_multiplier`, on the paged KV pool and the kernels
             of the Llama family (ops/attention.py)
  swiglu     `W_down (silu((W_gate f) * mlp_multipliers[0]) * (W_up f)) *
             mlp_multipliers[1]`

with `embedding_multiplier` on the embedding and `lm_head_multiplier` on the
logits of an untied head. Each multiplier is applied where the published
`modeling_falcon_h1` applies it, in float32 on the product it scales; none is
folded into a matrix.

All blocks are alike, so the stack is one scanned layer (models/llama.py), and
BOTH caches are over all L layers: the paged KV pool flat over layers as
models/llama.py lays it out, and beside it, per DECODE SLOT and not per page, a
float32 state [H, P, N] and the last `mamba_d_conv - 1` inputs of the
convolution, rows of two flat arrays laid out as models/nemotron_h.py lays its
state (`slot` of layer l at row `l * (max_seqs + 1) + slot`, the last row of
each layer a trash row for a pack's padding lanes, while a decode step serves
its live rows only (`ops/live_rows.py`); a chunk
that starts at position 0 starts from zeros, so a slot needs no clearing
between sequences). Every decode step of every layer reads a sequence's pages
AND reads and writes its state row. The engine gives the slot
(`state_slot(s)`); nothing here knows about requests.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.llama import parse_dtype
from dynamo_tpu.models.paged import DecodeStep, Pack, PackedPrefillModel, state_rows
from dynamo_tpu.ops.attention import scatter_kv
from dynamo_tpu.ops.norms import rms_norm
from dynamo_tpu.ops.rotary import apply_rope
from dynamo_tpu.ops.ssm import causal_conv, ssd_chunked, ssm_state_update

#: the published config's keys that hold a forward multiplier (two of them a
#: list: `ssm_multipliers` over z, x, B, C, dt; `mlp_multipliers` gate, down)
MULTIPLIER_KEYS = (
    "embedding_multiplier", "lm_head_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
)


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    hidden_size: int = 5120
    num_layers: int = 72
    intermediate_size: int = 21504
    # attention mixer
    num_heads: int = 20
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    # Mamba-2 mixer
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    # forward multipliers
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)  # z, x, B, C, dt
    mlp_multipliers: tuple = (1.0, 1.0)  # on the gate's product, on the down product
    rms_norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_proj_width(self) -> int:
        return self.mamba_inner + self.conv_dim + self.mamba_n_heads

    @classmethod
    def from_hf_config(cls, d: dict) -> "FalconH1Config":
        only = {
            "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": False,
            "mlp_bias": False, "projectors_bias": False, "mamba_rms_norm": True,
            "mamba_norm_before_gate": False, "rope_scaling": None,
            "attn_layer_indices": None, "tie_word_embeddings": False, "hidden_act": "silu",
        }
        for key, want in only.items():
            if d.get(key, want) != want:
                raise ValueError(f"falcon_h1: {key}={d[key]!r} is not supported (only {want!r})")
        H, Pd = d["mamba_n_heads"], d["mamba_d_head"]
        if d.get("mamba_d_ssm", H * Pd) != H * Pd:
            raise ValueError(
                f"falcon_h1: mamba_d_ssm={d['mamba_d_ssm']} is not mamba_n_heads x mamba_d_head "
                f"= {H} x {Pd}"
            )
        if H % d["mamba_n_groups"] or len(d["ssm_multipliers"]) != 5 or len(d["mlp_multipliers"]) != 2:
            raise ValueError(
                "falcon_h1: mamba_n_heads must divide into mamba_n_groups, ssm_multipliers "
                "name five segments (z, x, B, C, dt) and mlp_multipliers two products"
            )
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            num_layers=d["num_hidden_layers"],
            intermediate_size=d["intermediate_size"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d["num_key_value_heads"],
            head_dim=d.get("head_dim") or d["hidden_size"] // d["num_attention_heads"],
            rope_theta=float(d.get("rope_theta", 1e11)),
            mamba_n_heads=H,
            mamba_d_head=Pd,
            mamba_d_state=d["mamba_d_state"],
            mamba_n_groups=d["mamba_n_groups"],
            mamba_d_conv=d["mamba_d_conv"],
            mamba_chunk_size=d.get("mamba_chunk_size", 128),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            dtype=parse_dtype(d.get("torch_dtype") or "bfloat16"),
            **{k: (tuple(float(x) for x in d[k]) if isinstance(d[k], (list, tuple)) else float(d[k]))
               for k in MULTIPLIER_KEYS},
        )

    @classmethod
    def tiny(cls, **overrides) -> "FalconH1Config":
        """Small config for tests: two groups, five query heads a key head,
        and no multiplier at one."""
        if "dtype" in overrides:
            overrides["dtype"] = parse_dtype(overrides["dtype"])
        for key in ("ssm_multipliers", "mlp_multipliers"):
            if key in overrides:
                overrides[key] = tuple(overrides[key])
        base = cls(
            vocab_size=256, hidden_size=64, num_layers=3, intermediate_size=96,
            num_heads=10, num_kv_heads=2, head_dim=16, rope_theta=1e4,
            mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2,
            mamba_d_conv=4, mamba_chunk_size=16,
            embedding_multiplier=2.5, lm_head_multiplier=0.5, attention_in_multiplier=0.9,
            attention_out_multiplier=0.6, key_multiplier=0.7, ssm_in_multiplier=0.8,
            ssm_out_multiplier=0.75, ssm_multipliers=(0.7, 0.8, 0.6, 0.9, 0.65),
            mlp_multipliers=(0.85, 0.55), dtype=jnp.float32,
        )
        return replace(base, **overrides)


def _scaled(x: jnp.ndarray, multiplier: float) -> jnp.ndarray:
    """`x * multiplier` in float32, back in x's dtype; x itself at 1."""
    if multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def _rows_times(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """x [T, in] times w [out, in]: `wq`, `wk` and `wv` are kept as the
    checkpoint has them. Kept [in, out], the compiler transposed each of them
    in every layer of every decode step before its product (26 MB and twice
    5 MB a layer, `copy` instructions with no scope: 0.58 ms of a 24.7 ms step
    and most of the 2.0% `unnamed_share_of_busy.falcon` first read; PR 45,
    `tools/tpu_compile.py --steps` shows them in the program's text)."""
    return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())))


def _rows_of(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """`table[rows]` for a handful of rows, a dynamic slice each. Inside the
    layer scan a gather from the carried state made XLA copy the whole 2.4 GB
    array twice a prefill step (a slice of the gathered rows is moved onto the
    operand: `tools/tpu_compile.py --steps`, PR 45); a slice of a slice stays
    a slice."""
    return jnp.stack([jax.lax.dynamic_index_in_dim(table, rows[j], keepdims=False)
                      for j in range(rows.shape[0])])


def _with_rows(table: jnp.ndarray, rows: jnp.ndarray, values: jnp.ndarray) -> jnp.ndarray:
    """`table.at[rows].set(values)` in place, a dynamic update each (lanes
    that share a row, padding on the trash row, write it one after another)."""
    for j in range(rows.shape[0]):
        table = jax.lax.dynamic_update_index_in_dim(table, values[j], rows[j], 0)
    return table


class FalconH1Model(PackedPrefillModel):
    """Stateless forward functions over a params pytree (models/paged.py's
    contract; `prefill` and `prefill_packed` are `PackedPrefillModel`'s over
    `_packed_forward`, with the per-slot state: `state_slot(s)`). One chip
    (model_runner.recurrent_refusal), so `attn_mesh` stays None."""

    recurrent = True

    def __init__(self, config: FalconH1Config):
        super().__init__(config)
        c = config
        gn = c.mamba_n_groups * c.mamba_d_state
        #: `ssm_multipliers`' x, B and C over the columns of x | B | C
        self._xbc_multipliers = np.repeat(
            np.asarray(c.ssm_multipliers[1:4], np.float32), [c.mamba_inner, gn, gn])

    # ---------------- params ----------------

    def init_params(self, rng: jax.Array) -> dict:
        c = self.config
        keys = iter(jax.random.split(rng, 24))
        L, D, F, H = c.num_layers, c.hidden_size, c.intermediate_size, c.mamba_n_heads
        A, KV = c.num_heads * c.head_dim, c.num_kv_heads * c.head_dim

        def dense(shape, scale_axis):
            scale = 1.0 / jnp.sqrt(jnp.float32(shape[scale_axis]))
            w = jax.random.normal(next(keys), shape, jnp.float32) * scale
            return w.astype(c.dtype)

        def small(shape):
            return jax.random.normal(next(keys), shape, jnp.float32) * 0.5

        return {
            "embed": dense((c.vocab_size, D), 1),
            "layers": {
                "input_norm": jnp.ones((L, D), c.dtype),
                # `mamba.in_proj`'s rows z | x B C | dt as three matrices: one of
                # 9248 columns (72.25 lane rows) was copied whole, 568 MB, at the
                # start of every decode window (PR 45: `copy` with no scope, 1.9 ms
                # a window, most of what `unnamed_share_of_busy.falcon` then read)
                "in_z": dense((L, D, c.mamba_inner), 1),
                "in_xbc": dense((L, D, c.conv_dim), 1),
                "in_dt": dense((L, D, H), 1),
                "conv_w": small((L, c.mamba_d_conv, c.conv_dim)),
                "conv_b": small((L, c.conv_dim)),
                "dt_bias": small((L, H)),
                "A_log": small((L, H)),
                "D": small((L, H)) + 1.0,
                "mixer_norm": jnp.ones((L, c.mamba_inner), c.dtype),
                "out_proj": dense((L, c.mamba_inner, D), 1),
                "wq": dense((L, A, D), 2),
                "wk": dense((L, KV, D), 2),
                "wv": dense((L, KV, D), 2),
                "wo": dense((L, A, D), 1),
                "pre_ff_norm": jnp.ones((L, D), c.dtype),
                "gate": dense((L, D, F), 1),
                "up": dense((L, D, F), 1),
                "down": dense((L, F, D), 1),
            },
            "final_norm": jnp.ones((D,), c.dtype),
            "lm_head": dense((c.vocab_size, D), 1),
        }

    # ---------------- the paged KV pool (every layer) ----------------

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        c = self.config
        return (c.num_layers * num_pages, page_size, c.num_kv_heads, c.head_dim)

    # ---------------- the per-slot state cache (every layer) ----------------

    def init_state_cache(self, max_seqs: int) -> dict:
        """The leaves the engine keeps beside the KV pools, in the same
        donated bundle: `ssm` and `conv`, a row per (layer, slot) plus each
        layer's trash row."""
        c = self.config
        rows = c.num_layers * (max_seqs + 1)
        return {
            "ssm": jnp.zeros((rows, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state), jnp.float32),
            "conv": jnp.zeros((rows, c.mamba_d_conv - 1, c.conv_dim), c.dtype),
        }

    # ---------------- the two mixers ----------------
    # parts by scope (benchmark/trace_parts.py PARTS): a multiplier counts with
    # the product it scales; the Mamba projections are `ssm_proj`, convolution,
    # scan, gate, norm and state rows `ssm`; rope and the cache write `attn_kv`

    def _mamba_in(self, lp, u):
        """u [..., D] -> z [..., inner], xBC [..., conv_dim], dt [..., H], float32."""
        c = self.config
        with jax.named_scope("ssm_proj"):
            u = _scaled(u, c.ssm_in_multiplier)
            m = c.ssm_multipliers
            return ((u @ lp["in_z"]).astype(jnp.float32) * m[0],
                    (u @ lp["in_xbc"]).astype(jnp.float32) * self._xbc_multipliers,
                    (u @ lp["in_dt"]).astype(jnp.float32) * m[4])

    def _split_xbc(self, xbc):
        """silu(conv) [..., conv_dim] float32 -> x [..., H, P], B, C [..., G, N]."""
        c = self.config
        gn = c.mamba_n_groups * c.mamba_d_state
        lead = xbc.shape[:-1]
        x = xbc[..., : c.mamba_inner].reshape(*lead, c.mamba_n_heads, c.mamba_d_head)
        B = xbc[..., c.mamba_inner : c.mamba_inner + gn].reshape(*lead, c.mamba_n_groups, c.mamba_d_state)
        C = xbc[..., c.mamba_inner + gn :].reshape(*lead, c.mamba_n_groups, c.mamba_d_state)
        return x, B, C

    def _mamba_out(self, lp, y, z):
        """`GroupRMSNorm(y * silu(z)) * w`, then the output projection."""
        c = self.config
        with jax.named_scope("ssm"):
            g = y * jax.nn.silu(z)
            gg = g.reshape(*g.shape[:-1], c.mamba_n_groups, c.mamba_inner // c.mamba_n_groups)
            gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True) + c.rms_norm_eps)
            g = gg.reshape(g.shape) * lp["mixer_norm"].astype(jnp.float32)
        with jax.named_scope("ssm_proj"):
            return g.astype(c.dtype) @ lp["out_proj"]

    def _mamba_prefill(self, lp, u, ssm, conv, rows, fresh, valid):
        """u [N, T, D]; rows [N] this layer's state row per lane; fresh [N]:
        the lane starts its sequence; valid [N, T]."""
        c = self.config
        z, xbc, dt = self._mamba_in(lp, u)
        with jax.named_scope("ssm"):
            window = jnp.where(fresh[:, None, None], 0, _rows_of(conv, rows))
            n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
            xbc, window = causal_conv(xbc, window, lp["conv_w"], lp["conv_b"], n_valid)
            x, B, C = self._split_xbc(jax.nn.silu(xbc))
            # padding: dt = 0 is the identity on the state
            dt = jax.nn.softplus(dt + lp["dt_bias"]) * valid[..., None]
            state = jnp.where(fresh[:, None, None, None], 0.0, _rows_of(ssm, rows))
            y, state = ssd_chunked(
                x, dt, -jnp.exp(lp["A_log"]), B, C, lp["D"], state, c.mamba_chunk_size
            )
            ssm, conv = _with_rows(ssm, rows, state), _with_rows(conv, rows, window)
        return self._mamba_out(lp, y.reshape(*y.shape[:2], c.mamba_inner), z), ssm, conv

    def _mamba_decode(self, lp, u, ssm, conv, base, live):
        """u [B, D]; batch row b's state is row base + b; rows that are not
        live (the step's `LiveRows`) leave state and window as they were."""
        c = self.config
        nb = u.shape[0]
        z, xbc, dt = self._mamba_in(lp, u)
        with jax.named_scope("ssm"):
            mine = base + jnp.arange(nb)
            xbc, window = causal_conv(
                xbc[:, None, :], jax.lax.dynamic_slice_in_dim(conv, base, nb), lp["conv_w"],
                lp["conv_b"], live.mask.astype(jnp.int32),
            )
            x, B, C = self._split_xbc(jax.nn.silu(xbc[:, 0]))
            dt = jax.nn.softplus(dt + lp["dt_bias"])
            y, ssm = ssm_state_update(
                ssm, mine, x, dt, -jnp.exp(lp["A_log"]), B, C, lp["D"], live,
            )
            conv = jax.lax.dynamic_update_slice_in_dim(conv, window, base, 0)
        return self._mamba_out(lp, y.reshape(nb, c.mamba_inner), z), ssm, conv

    def _attention(self, lp, u, k_pool, v_pool, positions, flat_phys, offsets, attn_fn):
        """u [T, D], positions [T]."""
        c = self.config
        T = u.shape[0]
        with jax.named_scope("attn_proj"):
            u = _scaled(u, c.attention_in_multiplier)
            q = _rows_times(u, lp["wq"]).reshape(T, c.num_heads, c.head_dim)
            k = (_rows_times(u, lp["wk"]).astype(jnp.float32) * c.key_multiplier).reshape(
                T, c.num_kv_heads, c.head_dim)
            v = _rows_times(u, lp["wv"]).reshape(T, c.num_kv_heads, c.head_dim)
        q = apply_rope(q, positions, c.rope_theta)  # `attn_kv`, as the cache write
        k = apply_rope(k, positions, c.rope_theta).astype(c.dtype)
        k_pool, v_pool = scatter_kv(k_pool, v_pool, k, v, flat_phys, offsets)
        with jax.named_scope("attn"):
            attn = attn_fn(q, k_pool, v_pool)
        with jax.named_scope("attn_proj"):
            return attn.reshape(T, -1) @ lp["wo"], k_pool, v_pool

    def _mix(self, hidden, m_out, a_out):
        """The residual after the two mixers (the sum counts with the
        projections that feed it)."""
        c = self.config
        with jax.named_scope("ssm_proj"):
            return (hidden.astype(jnp.float32)
                    + m_out.astype(jnp.float32) * c.ssm_out_multiplier
                    + a_out.astype(jnp.float32) * c.attention_out_multiplier).astype(c.dtype)

    def _mlp(self, lp, hidden):
        c = self.config
        f = rms_norm(hidden, lp["pre_ff_norm"], c.rms_norm_eps)
        with jax.named_scope("mlp"):
            g = (f @ lp["gate"]).astype(jnp.float32) * c.mlp_multipliers[0]
            prod = (jax.nn.silu(g) * (f @ lp["up"]).astype(jnp.float32)).astype(c.dtype)
            out = (prod @ lp["down"]).astype(jnp.float32) * c.mlp_multipliers[1]
            return (hidden.astype(jnp.float32) + out).astype(c.dtype)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return _scaled(params["embed"][tokens].astype(self.config.dtype),
                           self.config.embedding_multiplier)

    def _unembed(self, params: dict, hidden: jnp.ndarray) -> jnp.ndarray:
        c = self.config
        with jax.named_scope("lm_head"):
            h = rms_norm(hidden, params["final_norm"], c.rms_norm_eps)
            return jax.lax.dot_general(
                h, params["lm_head"], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * c.lm_head_multiplier

    # ---------------- forward ----------------

    def _packed_forward(self, params, cache, tokens, positions, page_tables, valid, state_slots):
        """N lanes (chunks of N different sequences) through every layer.
        Returns (hidden [N*T, D], cache)."""
        c = self.config
        N, T = tokens.shape
        num_pages = cache["k"].shape[0] // c.num_layers
        slot_rows = cache["ssm"].shape[0] // c.num_layers
        pack = Pack(page_tables, positions, valid, cache["k"].shape[1], self.attn_mesh,
                    flat=("phys", "offsets"))
        fresh, slots = state_rows(state_slots, slot_rows, positions)
        flat_pos = pack.flat_positions

        def body(carry, xs):
            hidden, k_pool, v_pool, ssm, conv = carry
            lp, l = xs
            off = l * num_pages
            u = rms_norm(hidden, lp["input_norm"], c.rms_norm_eps)
            m_out, ssm, conv = self._mamba_prefill(
                lp, u.reshape(N, T, -1), ssm, conv, l * slot_rows + slots, fresh, valid
            )
            a_out, k_pool, v_pool = self._attention(
                lp, u, k_pool, v_pool, flat_pos, off + pack.phys, pack.offsets, pack.attend(off)
            )
            hidden = self._mlp(lp, self._mix(hidden, m_out.reshape(N * T, -1), a_out))
            return (hidden, k_pool, v_pool, ssm, conv), None

        hidden = self._embed(params, tokens.reshape(N * T))
        (hidden, k_pool, v_pool, ssm, conv), _ = jax.lax.scan(
            body, (hidden, cache["k"], cache["v"], cache["ssm"], cache["conv"]),
            (params["layers"], jnp.arange(c.num_layers, dtype=jnp.int32)),
        )
        return hidden, dict(cache, k=k_pool, v=v_pool, ssm=ssm, conv=conv)

    def decode(self, params, kv_cache, tokens, positions, page_tables, active,
               rope_deltas=None):
        """One decode step for the whole batch; batch row b is decode slot b.
        Returns (logits [B, V], cache)."""
        c = self.config
        cache = kv_cache
        num_pages = cache["k"].shape[0] // c.num_layers
        slot_rows = cache["ssm"].shape[0] // c.num_layers
        # `step.live` serves every layer's two kernels
        step = DecodeStep(page_tables, positions, active, cache["k"], c.head_dim, self.attn_mesh)

        def body(carry, xs):
            hidden, k_pool, v_pool, ssm, conv = carry
            lp, l = xs
            off = l * num_pages
            u = rms_norm(hidden, lp["input_norm"], c.rms_norm_eps)
            m_out, ssm, conv = self._mamba_decode(lp, u, ssm, conv, l * slot_rows, step.live)
            a_out, k_pool, v_pool = self._attention(
                lp, u, k_pool, v_pool, positions, off + step.phys, step.offsets, step.attend(off)
            )
            hidden = self._mlp(lp, self._mix(hidden, m_out, a_out))
            return (hidden, k_pool, v_pool, ssm, conv), None

        hidden = self._embed(params, tokens)
        (hidden, k_pool, v_pool, ssm, conv), _ = jax.lax.scan(
            body, (hidden, cache["k"], cache["v"], cache["ssm"], cache["conv"]),
            (params["layers"], jnp.arange(c.num_layers, dtype=jnp.int32)),
        )
        return self._unembed(params, hidden), dict(cache, k=k_pool, v=v_pool, ssm=ssm, conv=conv)
