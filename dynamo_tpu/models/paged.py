"""What a model is to the engine, and the step's plumbing that is not the model.

**The contract** (`PagedModel`): every name the engine reads of a model, with
its default. A model class subclasses it and sets what differs; the engine
reads plain attributes (`tests/test_model_contract.py` holds that no
`getattr(model, name, default)` stands outside `models/`).

**The plumbing** of a step over the paged KV pool, once for every model file:
where a step's K and V rows go, the live rows and the tile runs a decode step
makes once for its layers, the closures around the two attention dispatches
(`DecodeStep`, `Pack`), the state rows of a pack (`state_rows`), the router's
float32 pass (`route`), the window's expert counters (`ExpertCounts`). A change
to what the attention kernels take edits `ops/` and this file, no model file.

All of it is Python that runs once, at trace time. Each helper emits the
operations the model files emitted before it existed (PR 48), in their order
and under their `jax.named_scope`s (the parts of a trace,
benchmark/trace_parts.py): the compiled programs are the same programs. That
is why `Pack` hands `phys` and `offsets` out flat or by lane and `DecodeStep`
has `logical_first`: each model file keeps the order it always had.
"""

from __future__ import annotations

import copy
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.ops.attention import (
    decode_tile_runs,
    dispatch_paged_decode_attention,
    dispatch_paged_prefill_attention,
)
from dynamo_tpu.ops.live_rows import live_rows

# ---------------- the contract ----------------


class PagedModel:
    """Base of every model class in models/registry.py: stateless forward
    functions over a params pytree and a donated cache bundle (the page pools
    plus what `init_state_cache` adds). ARCHITECTURE.md, "What a model owes
    the engine", has this as a table."""

    def __init__(self, config: Any):
        #: the config dataclass: `ModelRunner` and the scheduler read geometry off it
        self.config = config

    #: a fixed-size state per DECODE SLOT beside the pages (`init_state_cache`):
    #: `ModelRunner` refuses what would copy it (`recurrent_refusal`), the
    #: engine matches no prefix, migration and disagg are refused
    recurrent = False
    #: page tables a sequence has: 1, or one per attention layer, table-major
    #: wherever the runner carries one (`layer_group_refusal`)
    kv_tables = 1
    #: None, or the `LayerGroup`s of layers that keep different tokens: the
    #: engine takes `GroupedPageAllocator` and refuses page transfers
    layer_groups = None
    #: axis of the pages in `gather_pages_wire`'s arrays: the host and disk
    #: tiers, disagg and migration split and join blocks along it
    wire_n_axis = 2
    #: state-cache leaves a decode window zeroes, adds to and hands back
    #: (`ModelRunner._decode_window_impl`)
    window_counters = ()
    #: every step takes `lora=`, `lora_ids=`; else `lora_adapters` is refused
    SUPPORTS_LORA = False
    #: the pools may be int8 `QuantizedPages`; else `kv_cache_dtype="int8"` is
    #: refused (`ModelRunner`, `registry.load_model`)
    SUPPORTS_KV_INT8 = False
    #: `ModelRunner` sets it to a mesh of several devices, for expert banks
    #: sharded over it (ops/moe.grouped_matmul)
    expert_mesh = None
    #: `ModelRunner` sets it at tp > 1: the attention kernels run under
    #: shard_map on it (GSPMD cannot partition a pallas_call)
    attn_mesh = None
    #: optional step programs. None: sp > 1 is refused; no packed prefill
    #: (`ModelRunner.packed_prefill_mode`)
    prefill_sp = None
    prefill_packed = None

    # ---- what every model gives ----

    def init_params(self, rng: jax.Array) -> dict:
        raise NotImplementedError

    def kv_cache_shape(self, num_pages: int, page_size: int) -> tuple[int, ...]:
        """Shape of each page pool (layer l's page p at `l * num_pages + p`
        where one page id serves every layer)."""
        raise NotImplementedError

    def prefill(self, params, kv_cache, tokens, positions, page_table, valid, last_idx, **kw):
        """One chunk of one sequence -> (logits [V] at `last_idx`, cache)."""
        raise NotImplementedError

    def decode(self, params, kv_cache, tokens, positions, page_tables, active, rope_deltas=None):
        """One step of the whole slot table -> (logits [B, V], cache)."""
        raise NotImplementedError

    # ---- defaults: everything replicated, two pools of `kv_cache_shape` ----
    # (llama shards heads over tp and has int8 pages, deepseek a latent cache)

    def param_shardings(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        shapes = jax.eval_shape(self.init_params, jax.random.key(0))
        return jax.tree.map(lambda _: NamedSharding(mesh, P()), shapes)

    def init_kv_cache(self, num_pages: int, page_size: int) -> dict:
        shape = self.kv_cache_shape(num_pages, page_size)
        return {"k": jnp.zeros(shape, self.config.dtype), "v": jnp.zeros(shape, self.config.dtype)}

    def kv_page_bytes(self, page_size: int) -> int:
        """Device bytes one allocator page costs, K and V: what the host tier's
        byte budget, the meter, the gauges and the roofline price a page at."""
        return (2 * math.prod(self.kv_cache_shape(1, page_size))
                * jnp.dtype(self.config.dtype).itemsize)

    def kv_cache_sharding(self, mesh: Mesh, tp_axis: str = "tp") -> dict:
        ns = NamedSharding(mesh, P())
        return {"k": ns, "v": ns}

    # ---- what rides beside the pools, in the same donated bundle ----

    def init_state_cache(self, max_seqs: int) -> dict:
        """Leaves `ModelRunner` adds to the cache bundle: a state per slot, the
        `window_counters`."""
        return {}

    def state_cache_sharding(self, mesh: Mesh) -> dict:
        ns = NamedSharding(mesh, P())
        return {name: ns for name in jax.eval_shape(lambda: self.init_state_cache(1))}

    def state_bytes(self, max_seqs: int) -> int:
        """Device bytes of the per-slot state (the `hbm_state_bytes` gauge)."""
        leaves = jax.eval_shape(lambda: self.init_state_cache(max_seqs))
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for name, x in leaves.items() if name not in self.window_counters)


class PackedPrefillModel(PagedModel):
    """A model whose every prefill is a pack. It gives `_unembed` and
    `_packed_forward(params, cache, tokens, positions, page_tables, valid[,
    state_slots]) -> (hidden [N*T, D], cache)`."""

    def prefill_packed(self, params, kv_cache, tokens, positions, page_tables, valid,
                       last_idx, state_slots=None):
        """models/llama.py's `prefill_packed`; a `recurrent` model also takes
        `state_slots` [N]: the decode slot whose state each lane continues (or,
        from position 0, starts). Returns (logits [N, V], cache)."""
        N, T = tokens.shape
        extra = ()
        if self.recurrent:
            if state_slots is None:
                state_slots = jnp.full((N,), -1, jnp.int32)
            extra = (state_slots,)
        hidden, kv_cache = self._packed_forward(
            params, kv_cache, tokens, positions, page_tables, valid, *extra
        )
        return self._unembed(params, last_rows(hidden, T, last_idx)), kv_cache

    def prefill(self, params, kv_cache, tokens, positions, page_table, valid, last_idx,
                input_embeds=None, embeds_mask=None, rope_positions=None, state_slot=None):
        """One chunk of one sequence: a pack of one lane."""
        if input_embeds is not None or rope_positions is not None:
            raise ValueError(f"{type(self).__module__.rpartition('.')[2]} is text-only")
        kw = {}
        if self.recurrent:
            kw["state_slots"] = None if state_slot is None else jnp.reshape(state_slot, (1,))
        logits, kv_cache = self.prefill_packed(
            params, kv_cache, tokens[None], positions[None], page_table[None],
            valid[None], jnp.reshape(last_idx, (1,)), **kw,
        )
        return logits[0], kv_cache


def last_rows(hidden: jnp.ndarray, T: int, last_idx: jnp.ndarray) -> jnp.ndarray:
    """hidden [N*T, D] -> [N, D]: each lane's row at `last_idx`."""
    return hidden[jnp.arange(last_idx.shape[0]) * T + last_idx]


# ---------------- the plumbing of a step ----------------


def split_tables(page_tables: jnp.ndarray, kv_tables: int) -> jnp.ndarray:
    """[rows, kv_tables * width] table-major -> [kv_tables, rows, width]."""
    rows = page_tables.shape[0]
    return page_tables.reshape(rows, kv_tables, -1).transpose(1, 0, 2)


class DecodeStep:
    """What one decode step makes once for its layers: where each slot's new
    K and V go (`phys`, `offsets`; an inactive slot writes the null page), the
    live rows (`live`, ops/live_rows.py), which tiles of the tables are runs of
    the pool (`runs`), and the `attn_fn(q, k_pool, v_pool)` a layer calls.
    `kv_tables` > 1 (a table a layer, side by side): the step holds what the
    layers share and `layer(l)` is the step at layer l, with its `phys`."""

    def __init__(self, page_tables, positions, active, k_pool, head_dim, mesh, kv_tables=1,
                 logical_first=False):
        self.positions, self.active, self.mesh = positions, active, mesh
        self.page_size = k_pool.shape[1]
        shared = kv_tables == 1
        #: [B, W], or [kv_tables, B, W] until `layer` picks one
        self.tables = page_tables if shared else split_tables(page_tables, kv_tables)
        self._layer, self._logical_first = None, logical_first
        with jax.named_scope("attn_kv"):
            if shared:
                self.phys = self._pages()
            self.offsets = jnp.where(active, positions % self.page_size, 0)
        self.live = live_rows(active)
        if shared:
            self.runs = decode_tile_runs(page_tables, k_pool, head_dim, mesh)
        else:  # a row per layer's table (the grouped allocator gives no runs: all zero)
            runs = decode_tile_runs(self.tables.reshape(-1, self.tables.shape[-1]), k_pool,
                                    head_dim, mesh)
            self.runs = None if runs is None else runs.reshape(kv_tables, -1)

    def _pages(self):
        B = self.positions.shape[0]
        if self._logical_first:  # models/llama.py's order of the same operations
            logical = self.positions // self.page_size
            return jnp.where(self.active, self.tables[jnp.arange(B), logical], 0)
        return jnp.where(self.active, self.tables[jnp.arange(B), self.positions // self.page_size], 0)

    def layer(self, l: int) -> "DecodeStep":
        at = copy.copy(self)
        at.tables, at._layer = self.tables[l], l
        with jax.named_scope("attn_kv"):
            at.phys = at._pages()
        return at

    def attend(self, off=None, window: int = 0):
        """The attention of the layer whose pages start at flat offset `off`
        of the pool (None: the tables hold pool rows as they are)."""

        def attn_fn(q, k_pool, v_pool):
            runs = self.runs
            if runs is not None and self._layer is not None:
                runs = runs[self._layer]
            return dispatch_paged_decode_attention(
                q, k_pool, v_pool, self.tables if off is None else off + self.tables,
                self.positions, mesh=self.mesh, window=window, live=self.live, runs=runs,
            )

        return attn_fn


def prefill_attend(page_table, positions, mesh, off=None, window: int = 0):
    """`attn_fn(q, k_pool, v_pool)` of one sequence's chunk."""

    def attn_fn(q, k_pool, v_pool):
        return dispatch_paged_prefill_attention(
            q, k_pool, v_pool, page_table if off is None else off + page_table, positions,
            mesh=mesh, window=window,
        )

    return attn_fn


class Pack:
    """What one packed prefill step makes once for its layers: N lanes of T
    rows, where each row's K and V go (`phys`, `offsets`; a padding row writes
    the null page), and the `attn_fn(q, k_pool, v_pool)` a layer calls: N
    copies of the kernel call (`attend`) or one under `jax.lax.map`
    (`attend_mapped`), the model file's choice (PR 40 measured each).
    `flat` names which of `phys` and `offsets` are [N*T] from here on (the
    other stays [N, T] for the model to flatten where its layer takes it);
    `kv_tables` > 1 as in `DecodeStep`."""

    def __init__(self, page_tables, positions, valid, page_size, mesh, kv_tables=1,
                 flat=("offsets",)):
        self.N, self.T = positions.shape
        self.positions, self.valid, self.page_size, self.mesh = positions, valid, page_size, mesh
        shared = kv_tables == 1
        self.tables = page_tables if shared else split_tables(page_tables, kv_tables)
        self._lane = jnp.arange(self.N)
        self._flat_phys = "phys" in flat
        with jax.named_scope("attn_kv"):
            if shared:
                self.phys = self._pages()
            self.offsets = jnp.where(valid, positions % page_size, 0)
            if "offsets" in flat:
                self.offsets = self.offsets.reshape(self.N * self.T)

    def _pages(self):
        phys = jnp.where(
            self.valid, self.tables[self._lane[:, None], self.positions // self.page_size], 0)
        return phys.reshape(self.N * self.T) if self._flat_phys else phys

    @property
    def flat_positions(self):
        return self.positions.reshape(self.N * self.T)

    def layer(self, l: int) -> "Pack":
        at = copy.copy(self)
        at.tables = self.tables[l]
        with jax.named_scope("attn_kv"):
            at.phys = at._pages()
        return at

    def attend(self, off=None, window: int = 0):
        N, T = self.N, self.T

        def attn_fn(q, k_pool, v_pool):
            qs = q.reshape(N, T, *q.shape[1:])
            return jnp.concatenate([
                dispatch_paged_prefill_attention(
                    qs[j], k_pool, v_pool, self.tables[j] if off is None else off + self.tables[j],
                    self.positions[j], mesh=self.mesh, window=window,
                )
                for j in range(N)
            ], axis=0)

        return attn_fn

    def attend_mapped(self, window: int = 0):
        N, T = self.N, self.T

        def attn_fn(q, k_pool, v_pool):
            def lane(args):
                q_j, table_j, positions_j = args
                return dispatch_paged_prefill_attention(
                    q_j, k_pool, v_pool, table_j, positions_j, mesh=self.mesh, window=window)

            out = jax.lax.map(lane, (q.reshape(N, T, *q.shape[1:]), self.tables, self.positions))
            return out.reshape(N * T, *q.shape[1:])

        return attn_fn


def state_rows(state_slots, slot_rows: int, positions):
    """The state rows of a `recurrent` model's pack -> (fresh [N]: the lane
    starts its sequence, from zeros; slots [N]: the row of a layer's
    `slot_rows` each lane continues). A slot the engine does not name (padding
    lanes, warm-up) is the trash row, a layer's last."""
    with jax.named_scope("ssm"):
        fresh = positions[:, 0] == 0
        slots = jnp.where((state_slots >= 0) & (state_slots < slot_rows - 1),
                          state_slots, slot_rows - 1)
    return fresh, slots


def route(h, router, score, count_rows=None):
    """An expert layer's router; `score(logits) -> (weights, idx)` is the
    model's own. Rows outside `count_rows` (None: all) go to no expert."""
    with jax.named_scope("moe_router"):
        # float32 on the hidden state, at full precision (a bf16 pass would
        # move the choice of expert, not just a weight)
        logits = jnp.dot(h.astype(jnp.float32), router, precision=jax.lax.Precision.HIGHEST)
        weights, idx = score(logits)
        if count_rows is not None:
            idx = jnp.where(count_rows[:, None], idx, -1)  # held nowhere
    return weights, idx


class ExpertCounts:
    """A routing model's `window_counters` over one decode step: `moe_counts`,
    the assignments each held expert received, and `moe_touched`, the (layer,
    held expert) pairs that received a row. Absent where no engine keeps them."""

    NAMES = ("moe_counts", "moe_touched")

    @staticmethod
    def leaves(held: int) -> dict:
        """What `init_state_cache` of such a model adds for them."""
        return {"moe_counts": jnp.zeros((held,), jnp.int32),
                "moe_touched": jnp.zeros((1,), jnp.int32)}

    def __init__(self, cache: dict):
        self.counts, self.touched = cache.get("moe_counts"), cache.get("moe_touched")

    def add(self, n) -> None:
        """n [held]: one expert layer's assignment counts."""
        if self.counts is not None:
            self.counts = self.counts + n
            self.touched = self.touched + jnp.sum(n > 0, dtype=jnp.int32)

    def into(self, cache: dict) -> dict:
        if self.counts is None:
            return cache
        return dict(cache, moe_counts=self.counts, moe_touched=self.touched)
