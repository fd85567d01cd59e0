"""ModelRunner: owns the device mesh, sharded params, the donated paged KV
cache, the device-resident token-feedback buffer, and the jitted
prefill/decode+sample step functions.

TPU execution notes:
  - prefill chunks are padded to config.prefill_buckets so jit caches one
    executable per bucket (static shapes, no recompiles per request)
  - the KV cache is donated on every step — XLA aliases it in place
  - sampling is fused into the step so only the sampled token ids (a few bytes)
    cross back to host per step
  - the last sampled token per slot lives in a donated device state bundle
    (``slot_state``, with the penalty counters): a sampling prefill writes
    its slot's first token there,
    and decode windows read/update it on device. The host therefore never has
    to sync on a window's results before dispatching the next one — the
    scheduler runs windows dispatch-ahead and reconciles token results as they
    arrive (hides dispatch/transfer latency)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.sampling import MAX_EOS_IDS, SamplingParams, accept_speculative, apply_penalties, fold_seed, sample_tokens, sample_tokens_with_logprobs
from dynamo_tpu.utils import get_logger

log = get_logger("engine.runner")


def recurrent_refusal(config: EngineConfig) -> Optional[str]:
    """Why this engine configuration cannot serve a model with recurrent
    layers, or None. Each of these would have to copy, ship or roll back the
    per-slot state together with the KV pages it belongs to, and nothing here
    does that yet (state snapshots at block boundaries are a later PR); to
    run them on the pages alone would serve another model, silently."""
    if config.speculative:
        return ("speculative decoding is refused: a rejected draft would have "
                "to roll the recurrent state back, and no snapshot is kept")
    if config.host_cache_blocks > 0 or config.host_cache_bytes > 0 or config.disk_cache_bytes > 0:
        return ("the host and disk KV tiers are refused: a restored prefix has "
                "pages and no recurrent state to go with them")
    if config.tp > 1 or config.pp > 1 or config.sp > 1:
        return ("tp/pp/sp > 1 are refused: the state cache and the expert "
                "dispatch run on one chip (expert parallelism across chips, "
                "with its exchange, is not built)")
    if config.lora_adapters:
        return "LoRA adapters are refused: the blocks carry no adapter pass"
    if config.kv_cache_dtype == "int8":
        return "the int8 KV cache is refused: the state cache has no 8-bit form"
    return None


def layer_group_refusal(config: EngineConfig) -> Optional[str]:
    """Why this engine configuration cannot serve a model whose attention
    layers come in groups with a page table each (a sliding window beside full
    attention: models/cohere2_moe.py), or None. Each of these moves, copies or
    splits pages by ONE id a block for every layer, and nothing here has been
    made to carry a table per layer yet; to run them would read another
    layer's pages, silently."""
    if config.speculative:
        return ("speculative decoding is refused: the verify pass and the draft "
                "cache have not been made to work with a page table per layer")
    if config.host_cache_blocks > 0 or config.host_cache_bytes > 0 or config.disk_cache_bytes > 0:
        return ("the host and disk KV tiers are refused: a block is several "
                "pages, one per layer of its group, and the tiers move one")
    if config.tp > 1 or config.pp > 1 or config.sp > 1:
        return ("tp/pp/sp > 1 are refused: layer groups run on one chip (a "
                "table per layer under a mesh is not built)")
    if config.lora_adapters:
        return "LoRA adapters are refused: the blocks carry no adapter pass"
    if config.kv_cache_dtype == "int8":
        return "the int8 KV cache is refused: the window kernels take no 8-bit pages"
    if config.prefill_lanes <= 1:
        return ("prefill_lanes <= 1 is refused: only the packed prefill path "
                "takes and gives back a window layer's pages chunk by chunk")
    return None


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        model,
        params,
        mesh: Optional[Mesh] = None,
    ):
        self.config = config
        self.model = model
        if config.sp > 1 and config.pp > 1:
            # composed pp x sp (long-context: depth over pp, length over sp)
            # runs ring prefill inside the pipeline shard_map; the layer must
            # support the sp row all-gather before its pool scatter
            import inspect

            if "sp_axis" not in inspect.signature(model._layer).parameters:
                raise ValueError(
                    f"model {type(model).__name__} does not support the "
                    "composed pp x sp mesh (no _layer sp_axis)"
                )
        if config.sp > 1 and config.tp > 1:
            h = getattr(model.config, "num_heads", None)
            hkv = getattr(model.config, "num_kv_heads", None)
            if h is None or hkv is None:
                # a model without per-head attention geometry (e.g. a latent-
                # attention variant) must fail HERE, not inside a traced
                # shard_map later — 0 % tp == 0 would slip through the gate
                raise ValueError(
                    f"model {type(model).__name__} config lacks num_heads/"
                    "num_kv_heads; composed sp x tp needs per-head geometry"
                )
            if h % config.tp or hkv % config.tp:
                raise ValueError(
                    f"tp={config.tp} must divide num_heads={h} and "
                    f"num_kv_heads={hkv} for the composed sp x tp mesh"
                )
        if getattr(model.config, "kv_quantized", False):
            if not model.SUPPORTS_KV_INT8:
                raise ValueError(
                    f"model {type(model).__name__} does not support the int8 KV cache"
                )
            if config.pp > 1:
                # the stage-sharded pool split has no QuantizedPages wiring
                # yet (EngineConfig also gates this; a tiny:{...} override
                # JSON could otherwise sneak the combination past it)
                raise ValueError("int8 KV cache does not compose with pp > 1 yet")
        if config.pp > 1:
            if model.config.num_layers % config.pp:
                raise ValueError(
                    f"num_layers={model.config.num_layers} not divisible by pp={config.pp}"
                )
            if len(jax.devices()) < config.pp * config.tp:
                raise ValueError(
                    f"pp={config.pp} x tp={config.tp} but only "
                    f"{len(jax.devices())} devices available"
                )
            if any(b % config.pp for b in config.prefill_buckets):
                raise ValueError(
                    f"every prefill bucket must divide into pp={config.pp} microbatches"
                )
            if config.max_seqs % config.pp:
                raise ValueError(f"max_seqs must be divisible by pp={config.pp}")
            if config.tp > 1:
                h = getattr(model.config, "num_heads", None)
                hkv = getattr(model.config, "num_kv_heads", None)
                if h is None or hkv is None:
                    raise ValueError(
                        f"model {type(model).__name__} config lacks num_heads/"
                        "num_kv_heads; composed pp x tp needs per-head geometry"
                    )
                if h % config.tp or hkv % config.tp:
                    raise ValueError(
                        f"tp={config.tp} must divide num_heads={h} and "
                        f"num_kv_heads={hkv} for the composed pp x tp mesh"
                    )
                import inspect

                if "tp_axis" not in inspect.signature(model._layer).parameters:
                    # fail at init, not at first traced prefill: the layer
                    # must run on a head shard with in-layer psums
                    raise ValueError(
                        f"model {type(model).__name__} does not support tp "
                        "inside the pipeline shard_map (no _layer tp_axis)"
                    )
        if config.sp > 1:
            if model.prefill_sp is None:
                raise ValueError(
                    f"model {type(model).__name__} has no sequence-parallel prefill"
                )
            if len(jax.devices()) < config.pp * config.sp * config.tp:
                raise ValueError(
                    f"pp={config.pp} x sp={config.sp} x tp={config.tp} but only "
                    f"{len(jax.devices())} devices available"
                )
            if not any(b % config.sp == 0 for b in config.prefill_buckets):
                raise ValueError(
                    f"sp={config.sp} divides none of prefill_buckets="
                    f"{config.prefill_buckets}; SP prefill would never engage"
                )
        #: a model with recurrent layers (models/nemotron_h.py) keeps a
        #: fixed-size state per DECODE SLOT beside the paged KV: its size
        #: follows max_seqs, and it exists because the model has such layers
        self.recurrent = bool(model.recurrent)
        if self.recurrent:
            why = recurrent_refusal(config)
            if why:
                raise ValueError(f"model {type(model).__name__}: {why}")
        #: page tables a sequence has: one, or one per attention layer of a
        #: model with layer groups (its tables ride side by side, table-major,
        #: wherever this file carries one: `_flat_table`)
        self.kv_tables = int(model.kv_tables)
        if self.kv_tables > 1:
            why = layer_group_refusal(config)
            if why:
                raise ValueError(f"model {type(model).__name__}: {why}")
        if mesh is None:
            if config.pp > 1 and config.sp > 1:
                # composed stage x sequence (x head) mesh: sp between pp and
                # tp so a ring's peers stay ICI-adjacent within their stage
                n = config.pp * config.sp * config.tp
                devices = jax.devices()[:n]
                if config.tp > 1:
                    mesh = Mesh(
                        np.array(devices).reshape(config.pp, config.sp, config.tp),
                        ("pp", "sp", "tp"),
                    )
                else:
                    mesh = Mesh(
                        np.array(devices).reshape(config.pp, config.sp), ("pp", "sp")
                    )
            elif config.pp > 1 and config.tp > 1:
                # composed stage x head mesh: tp is the minor (fastest-
                # varying) axis so a head shard's peers are ICI neighbors
                devices = jax.devices()[: config.pp * config.tp]
                mesh = Mesh(
                    np.array(devices).reshape(config.pp, config.tp), ("pp", "tp")
                )
            elif config.sp > 1 and config.tp > 1:
                # composed sequence x head mesh: each tp head shard runs its
                # own independent sp ring (attention is head-local)
                devices = jax.devices()[: config.sp * config.tp]
                mesh = Mesh(
                    np.array(devices).reshape(config.sp, config.tp), ("sp", "tp")
                )
            elif config.pp > 1:
                devices = jax.devices()[: config.pp]
                mesh = Mesh(np.array(devices).reshape(len(devices)), ("pp",))
            elif config.sp > 1:
                devices = jax.devices()[: config.sp]
                mesh = Mesh(np.array(devices).reshape(len(devices)), ("sp",))
            else:
                devices = jax.devices()[: config.tp]
                mesh = Mesh(np.array(devices).reshape(len(devices)), ("tp",))
        self.mesh = mesh
        if mesh.size > 1:
            # expert banks may be sharded over it: ops/moe.grouped_matmul
            model.expert_mesh = mesh
        if config.tp > 1 and config.pp == 1:
            # the Pallas decode kernel runs under shard_map on this mesh
            # (attention is head-parallel; no collectives inside). With pp > 1
            # attention runs INSIDE the pipeline's own (pp, tp) shard_map on
            # local pool shards, so the dispatcher must not re-wrap it.
            model.attn_mesh = mesh
        if config.pp > 1:
            # stage sharding: layer stack + layer-major KV pool split over pp
            from dynamo_tpu.parallel.pipeline import (
                stage_kv_sharding,
                stage_param_shardings,
            )

            shardings = stage_param_shardings(model, mesh)
            kv_sharding = stage_kv_sharding(
                mesh, folded=getattr(model.config, "kv_folded", False)
            )
            probe = jax.eval_shape(
                lambda: model.init_kv_cache(config.num_pages, config.page_size)
            )
            if set(probe) != {"k", "v"}:
                raise ValueError(
                    "pp currently supports the k/v page-pool model families"
                )
        else:
            shardings = model.param_shardings(mesh)
            kv_sharding = model.kv_cache_sharding(mesh)
        self.params = jax.device_put(params, shardings)
        cache = model.init_kv_cache(config.num_pages, config.page_size)
        # what a model keeps beside the page pools (a recurrent state per
        # slot, the expert counters; most keep nothing) rides the same donated
        # bundle, so every step function carries it unchanged
        cache.update(model.init_state_cache(config.max_seqs))
        #: device bytes of that state, the counters aside (the engine's gauge)
        self.state_bytes = model.state_bytes(config.max_seqs)
        kv_sharding = dict(kv_sharding, **model.state_cache_sharding(mesh))
        self.kv_cache = jax.device_put(cache, kv_sharding)
        #: the last decode window's extra device output (the leaves a model
        #: names in `window_counters`: a routing model's `moe_counts` and
        #: `moe_touched`), or None
        self.window_aux = None
        self._replicated = NamedSharding(mesh, P())
        self._key = jax.random.key(0)
        # device-resident per-slot state, donated through every step:
        #   tokens — last sampled token (the decode feedback loop)
        #   counts — output-token occurrence counts (frequency/presence)
        #   seen   — token appeared in prompt or output (repetition)
        # counts/seen ([max_seqs, V] — up to tens of MB for large vocabs) are
        # allocated lazily on the first penalty-enabled request; until then the
        # bundle is just the token feedback buffer and penalty-free traffic
        # never pays the HBM or donation traffic.
        self.slot_state = {"tokens": jnp.zeros(config.max_seqs, jnp.int32)}
        # multi-LoRA multiplexing (dynamo_tpu/lora/): device-resident stacked
        # adapter pools + the LRU slot store. The pool rides every forward as
        # a read-only (never donated) pytree; per-slot adapter ids live in
        # slot_state["lora"] next to the token-feedback buffer so decode
        # windows read them on device with no extra H2D. None = disabled and
        # every trace is byte-identical to the pre-LoRA engine.
        self.lora = None
        self.lora_store = None
        if config.lora_adapters:
            from dynamo_tpu.lora import LoraStore, init_lora_pool

            if not model.SUPPORTS_LORA:
                raise ValueError(
                    f"model {type(model).__name__} does not support LoRA adapters"
                )
            if config.pp > 1:
                # config gates this too; a tiny:{...} override JSON must not
                # sneak the combination past it
                raise ValueError("lora_adapters do not compose with pp > 1 yet")
            pool = init_lora_pool(model, config.max_loras, config.lora_rank)
            self.lora = jax.device_put(pool, NamedSharding(mesh, P()))
            self.slot_state["lora"] = jnp.zeros(config.max_seqs, jnp.int32)

            def _lora_write_impl(pool, slot, tree, scale):
                mods = {
                    m: {
                        "a": pool["mods"][m]["a"].at[:, slot].set(tree[m]["a"]),
                        "b": pool["mods"][m]["b"].at[:, slot].set(tree[m]["b"]),
                    }
                    for m in pool["mods"]
                }
                return {"scales": pool["scales"].at[slot].set(scale), "mods": mods}

            self._lora_write = jax.jit(_lora_write_impl, donate_argnums=(0,))

            def _set_lora_impl(st, slot, val):
                return dict(st, lora=st["lora"].at[slot].set(val, mode="drop"))

            self._set_lora = jax.jit(_set_lora_impl, donate_argnums=(0,))
            self.lora_store = LoraStore(config, model, self.load_lora_slot)

        # compile-churn telemetry: every serving-path jit is wrapped so a
        # recompile storm (the top TPU serving hazard — a stray dynamic shape
        # mid-traffic) shows up as a climbing compile counter + seconds in the
        # engine resource gauges, not as unexplained latency
        from dynamo_tpu.utils.compile_monitor import CompileMonitor, monitored_jit

        self.compile_monitor = CompileMonitor()

        def _mjit(label, impl, **jit_args):
            """``jax.jit(impl)`` under the compile monitor, compiled as the
            module ``dynamo_<label>``: a profiler's `XLA Modules` line names
            the step by what it is, whatever the method is called. jit takes
            the module's name from the function's ``__name__`` (a bound
            method's is its function's), so that is what is set; the name is
            fixed text, since the persistent compile cache keys on it. (A
            wrapper function with the name cost 1.7 s more at the first call
            of every prefill variant on the chip: PERF.md, PR 25.) The three
            step programs are decorated `jax.named_scope("step")`: whatever
            they do outside the model's and the sampler's own scopes is the
            part `step` of a trace (benchmark/trace_parts.py PARTS)."""
            fn = getattr(impl, "__func__", impl)
            fn.__name__ = fn.__qualname__ = f"dynamo_{label}"
            return monitored_jit(
                jax.jit(impl, **jit_args), label, self.compile_monitor
            )

        self._prefill = _mjit(
            "prefill", self._prefill_impl, donate_argnums=(1, 2),
            static_argnames=("want_lp", "want_pen", "want_seed", "want_eos_mask", "mp"),
        )
        # cross-request packed prefill (one weight pass for N lanes); one
        # executable per (N, bucket, table width) actually used
        self._prefill_packed = _mjit(
            "prefill_packed", self._prefill_packed_impl, donate_argnums=(1, 2),
            static_argnames=("want_lp", "want_pen", "want_seed", "want_eos_mask", "mp"),
        )
        # multimodal vision encode (compiled lazily; text-only models never
        # pay for it — the mm prefill variant is _prefill traced with embeds)
        self._encode_images = _mjit(
            "encode_images",
            lambda params, patches, rows, cols, valid, segments: self.model.encode_images(
                params, patches, rows, cols, valid, segments=segments
            ),
        )
        if config.sp > 1:
            # sequence-parallel whole-prompt prefill (ring attention over sp)
            self._prefill_sp = _mjit(
                "prefill_sp", self._prefill_sp_impl, donate_argnums=(1, 2),
                static_argnames=("want_lp", "want_pen", "want_seed", "want_eos_mask", "mp"),
            )
        self._decode_window = _mjit(
            "decode_window", self._decode_window_impl, donate_argnums=(1, 2),
            static_argnames=("num_steps", "want_lp", "want_pen", "want_seed", "want_eos_mask"),
        )
        # speculative verify step (spec subsystem): ONE trace regardless of
        # sampling features — seeds/filters are neutral-input no-ops, and
        # penalties/logprobs requests never ride this path (the scheduler
        # routes them through classic windows)
        self._verify = _mjit("verify", self._verify_impl, donate_argnums=(1,))
        # draft-model speculation: a second model with its own paged KV pool
        # and a batched k-token drafting dispatch (spec/draft.py). Loaded
        # through the registry with THIS engine's quantize/kv_cache_dtype so
        # the draft composes with int8 weights and the int8 KV cache.
        self.draft = None
        spec = config.spec
        if spec is not None and spec.kind == "draft":
            from dynamo_tpu.spec.draft import DraftModelRunner

            self.draft = DraftModelRunner(
                config, spec, compile_monitor=self.compile_monitor
            )
        def _write_tokens_impl(st, idx, vals):
            return dict(st, tokens=st["tokens"].at[idx].set(vals, mode="drop"))

        self._write_tokens = jax.jit(_write_tokens_impl, donate_argnums=(0,))

        def _seed_pen_impl2(st, slot, prompt_ids, output_ids):
            # reset the slot's penalty state, mark prompt+output tokens seen,
            # and restore output occurrence counts (preemption resume); both
            # id arrays are bucket-padded with V (dropped by the OOB scatter)
            counts = st["counts"].at[slot].set(0)
            counts = counts.at[slot, output_ids].add(1, mode="drop")
            seen = st["seen"].at[slot].set(False)
            seen = seen.at[slot, prompt_ids].set(True, mode="drop")
            return dict(st, counts=counts, seen=seen)

        self._seed_pen = jax.jit(_seed_pen_impl2, donate_argnums=(0,))
        # block-granularity KV IO for disaggregation / offload
        # (the NIXL-slot replacement, reference: patch nixl.py register_kv_caches).
        # The model defines its canonical wire layout (llama: [L,2,n,ps,Hkv,D];
        # MLA: [L,n,ps,latent_padded]); on device the pools are flat [L*P, ...].
        L = model.config.num_layers
        Pn = config.num_pages

        def _flat_ids(ids):  # [n] logical -> [L, n] flat
            return ids[None, :] + (jnp.arange(L, dtype=jnp.int32) * Pn)[:, None]

        self._gather_pages = _mjit(
            "gather_pages",
            lambda kv, ids: model.gather_pages_wire(kv, _flat_ids(ids)),
        )
        self._scatter_pages = _mjit(
            "scatter_pages",
            lambda kv, ids, data: model.scatter_pages_wire(kv, _flat_ids(ids), data),
            donate_argnums=(0,),
        )

    # ---------------- jitted bodies ----------------

    def _model_prefill(self, params, kv, tokens, positions, page_table, valid, last, embeds=None, emask=None, rope_pos=None, lora=None, lora_id=None, state_slot=None):
        """model.prefill, or its GPipe-pipelined form when pp > 1 (which has
        no LoRA threading — the lora+pp combination is gated at init)."""
        if self.config.pp > 1:
            from dynamo_tpu.parallel.pipeline import prefill_pipelined

            return prefill_pipelined(
                self.model, params, kv, tokens, positions, page_table, valid, last,
                self.mesh, input_embeds=embeds, embeds_mask=emask,
                rope_positions=rope_pos,
            )
        lkw = {} if lora is None else dict(lora=lora, lora_id=lora_id)
        if state_slot is not None:
            lkw["state_slot"] = state_slot
        return self.model.prefill(
            params, kv, tokens, positions, page_table, valid, last,
            input_embeds=embeds, embeds_mask=emask, rope_positions=rope_pos, **lkw,
        )

    def _model_decode(self, params, kv, tokens, positions, page_tables, active, rope_deltas=None, lora=None, lora_ids=None):
        if self.config.pp > 1:
            from dynamo_tpu.parallel.pipeline import decode_pipelined

            return decode_pipelined(
                self.model, params, kv, tokens, positions, page_tables, active,
                self.mesh, rope_deltas=rope_deltas,
            )
        lkw = {} if lora is None else dict(lora=lora, lora_ids=lora_ids)
        return self.model.decode(
            params, kv, tokens, positions, page_tables, active,
            rope_deltas=rope_deltas, **lkw,
        )

    @jax.named_scope("step")
    def _prefill_impl(self, params, kv, slot_state, ints, flts, key, embeds=None, emask=None, rope_pos=None, lora=None, want_lp=False, want_pen=False, want_seed=False, want_eos_mask=False, mp=None):
        """ints [bucket + mp + 6 + MAX_EOS_IDS] = token buf, page
        table, (start_pos, n_real, top_k, slot, seed, lora_slot), then the
        request's EOS ids (V-padded); flts [6] = (temperature, top_p, min_p,
        presence, frequency, repetition). Positions and the valid mask derive
        on device — one packed H2D per chunk. The sampled token is written into
        ``slot_state["tokens"][slot]`` (slot >= max_seqs drops the write) so a
        following decode window can consume it without any host round trip.

        ``mp`` is the page-table width this trace is compiled for — a rung
        of the config's table-width ladder, not the dense max_pages_per_seq.
        Multimodal chunks pass ``embeds`` [bucket, D] + ``emask`` [bucket];
        ``lora`` (the adapter pool; chunk's slot id rides the ints) applies
        one adapter's delta to the whole chunk — slot 0 is the zero adapter;
        want_lp/want_pen/want_seed/want_eos_mask gate logprobs, penalties,
        seeded streams, and min_tokens EOS suppression out of the default
        trace."""
        if mp is None:
            mp = self.config.max_pages_per_seq
        bucket = ints.shape[0] - mp - 6 - MAX_EOS_IDS - int(self.recurrent)
        tokens = ints[:bucket]
        page_table = ints[bucket : bucket + mp]
        start_pos = ints[bucket + mp]
        n = ints[bucket + mp + 1]
        top_k = ints[bucket + mp + 2]
        slot = ints[bucket + mp + 3]
        seed = ints[bucket + mp + 4]
        lora_id = ints[bucket + mp + 5]
        eos_ids = ints[bucket + mp + 6 : bucket + mp + 6 + MAX_EOS_IDS]
        positions = start_pos + jnp.arange(bucket, dtype=jnp.int32)
        valid = jnp.arange(bucket) < n
        logits, kv = self._model_prefill(
            params, kv, tokens, positions, page_table, valid, n - 1,
            embeds=embeds, emask=emask, rope_pos=rope_pos,
            lora=lora, lora_id=lora_id,
            # the row's last int: the decode slot whose state this chunk
            # continues (every chunk's, not only the sampling one's)
            state_slot=ints[-1] if self.recurrent else None,
        )
        tok, lp, slot_state = self._sample_one(
            logits, key, flts, top_k, slot, seed, start_pos + n - 1, slot_state,
            want_lp, want_pen, want_seed,
            eos_ids=eos_ids if want_eos_mask else None,
        )
        return tok, lp, kv, slot_state

    def _sample_one(self, logits, key, flts, top_k, slot, seed, sample_pos,
                    slot_state, want_lp, want_pen, want_seed, eos_ids=None):
        """Shared prefill-side sampling tail: penalties (against the slot's
        state), logprobs, seeded streams, token feedback write. ``eos_ids``
        (min_tokens requests): the first sampled token is generation #1, so
        EOS logits are suppressed outright here."""
        raw_b = logits[None, :]
        if eos_ids is not None:
            logits = logits.at[eos_ids].add(jnp.float32(-1e30), mode="drop")
        logits_b = logits[None, :]
        if want_pen:
            counts = slot_state["counts"][slot][None]
            seen = slot_state["seen"][slot][None]
            logits_b = apply_penalties(
                logits_b, counts, seen, flts[3:4], flts[4:5], flts[5:6]
            )
        kwargs = {}
        if want_seed:
            kwargs = dict(seeds=seed[None], positions=sample_pos[None])
        if want_lp:
            toks, chosen, tids, tvals = sample_tokens_with_logprobs(
                logits_b, key, flts[:1], top_k[None], flts[1:2],
                raw_logits=raw_b, min_p=flts[2:3], **kwargs
            )
            lp = (chosen[0], tids[0], tvals[0])
        else:
            toks = sample_tokens(
                logits_b, key, flts[:1], top_k[None], flts[1:2], min_p=flts[2:3], **kwargs
            )
            lp = None
        tok = toks[0]
        tokens = slot_state["tokens"].at[slot].set(tok, mode="drop")
        slot_state = dict(slot_state, tokens=tokens)
        if want_pen:
            counts = slot_state["counts"].at[slot, tok].add(1, mode="drop")
            seen = slot_state["seen"].at[slot, tok].set(True, mode="drop")
            slot_state = dict(slot_state, counts=counts, seen=seen)
        return tok, lp, slot_state

    @jax.named_scope("step")
    def _prefill_packed_impl(self, params, kv, slot_state, ints, flts, key, lora=None, want_lp=False, want_pen=False, want_seed=False, want_eos_mask=False, mp=None):
        """Cross-request packed prefill: ints [N, bucket + mp + 6 +
        MAX_EOS_IDS] — N lanes of the SAME per-lane row layout as
        _prefill_impl (``mp`` = the call's ladder table width); flts [6, N].
        Every lane's last-row logits are sampled
        ([N] tokens); the host ignores tokens of lanes that weren't a final
        chunk (their slot is out-of-range so the feedback write drops too).
        A mixed-adapter pack stays ONE dispatch: each lane's lora slot id
        gathers its adapter planes inside the shared weight pass."""
        if mp is None:
            mp = self.config.max_pages_per_seq
        N = ints.shape[0]
        bucket = ints.shape[1] - mp - 6 - MAX_EOS_IDS - int(self.recurrent)
        tokens = ints[:, :bucket]
        page_tables = ints[:, bucket : bucket + mp]
        start_pos = ints[:, bucket + mp]
        n = ints[:, bucket + mp + 1]
        top_ks = ints[:, bucket + mp + 2]
        slots = ints[:, bucket + mp + 3]
        seeds = ints[:, bucket + mp + 4]
        lora_ids = ints[:, bucket + mp + 5]
        eos_ids = ints[:, bucket + mp + 6 : bucket + mp + 6 + MAX_EOS_IDS]  # V-padded
        positions = start_pos[:, None] + jnp.arange(bucket, dtype=jnp.int32)[None, :]
        valid = jnp.arange(bucket)[None, :] < n[:, None]
        lkw = {} if lora is None else dict(lora=lora, lora_ids=lora_ids)
        if self.recurrent:
            lkw = dict(state_slots=ints[:, -1])  # each lane's decode slot
        logits, kv = self.model.prefill_packed(
            params, kv, tokens, positions, page_tables, valid, n - 1, **lkw
        )
        raw_b = logits  # [N, V]
        if want_eos_mask:
            rows = jnp.arange(N)[:, None]
            logits = logits.at[rows, eos_ids].add(jnp.float32(-1e30), mode="drop")
        if want_pen:
            # out-of-range slots (non-final lanes) clip to an arbitrary row;
            # their sampled token is discarded, so the penalty values applied
            # don't matter — only the UPDATE below must drop, and it does.
            counts = jnp.take(slot_state["counts"], slots, axis=0, mode="clip")
            seen = jnp.take(slot_state["seen"], slots, axis=0, mode="clip")
            logits = apply_penalties(
                logits, counts, seen, flts[3], flts[4], flts[5]
            )
        kwargs = dict(min_p=flts[2])
        if want_seed:
            kwargs.update(seeds=seeds, positions=start_pos + n - 1)
        if want_lp:
            toks, chosen, tids, tvals = sample_tokens_with_logprobs(
                logits, key, flts[0], top_ks, flts[1], raw_logits=raw_b, **kwargs
            )
            lp = (chosen, tids, tvals)
        else:
            toks = sample_tokens(logits, key, flts[0], top_ks, flts[1], **kwargs)
            lp = None
        slot_state = dict(
            slot_state, tokens=slot_state["tokens"].at[slots].set(toks, mode="drop")
        )
        if want_pen:
            counts = slot_state["counts"].at[slots, toks].add(1, mode="drop")
            seen = slot_state["seen"].at[slots, toks].set(True, mode="drop")
            slot_state = dict(slot_state, counts=counts, seen=seen)
        return toks, lp, kv, slot_state

    def pack_prefill_lanes(
        self,
        lanes: list,  # [(tokens np[int32], start_pos, page_table, slot_or_-1, sampling, eos_ids, is_final[, lora_slot])]
        N: int,  # lane count the executable is compiled for (>= len(lanes))
        bucket: int,  # rows a lane is padded to (>= the longest lane)
    ):
        """Host-prep half of :meth:`prefill_chunk_batch`: build the packed
        int/float control arrays on the host (no device work). Returns
        (ints, flts, want_extras, mp)."""
        V = self.model.config.vocab_size
        # table width for THIS call: the widest lane's ladder bucket (narrow
        # lanes zero-pad into the trash page) — short packs keep their
        # narrow executable; only packs containing a deep sequence go wide
        width = self.config.table_bucket_for(max(l[2].shape[-1] for l in lanes))
        mp = self.kv_tables * width
        ints = np.full((N, bucket + mp + 6 + MAX_EOS_IDS + int(self.recurrent)), V, np.int32)
        ints[:, :bucket] = 0
        ints[:, bucket : bucket + mp] = 0
        flts = np.zeros((6, N), np.float32)
        flts[1] = 1.0  # top_p neutral
        flts[5] = 1.0  # repetition neutral
        want_extras = False
        for j, lane in enumerate(lanes):
            tokens, start_pos, page_table, slot, sampling, eos_ids, is_final = lane[:7]
            lora_slot = lane[7] if len(lane) > 7 else 0
            n = len(tokens)
            ints[j, :n] = tokens
            ints[j, bucket : bucket + mp].reshape(self.kv_tables, width)[
                :, : page_table.shape[-1]] = page_table
            ints[j, bucket + mp] = start_pos
            ints[j, bucket + mp + 1] = n
            ints[j, bucket + mp + 2] = sampling.top_k
            ints[j, bucket + mp + 3] = slot if (is_final and slot >= 0) else self.config.max_seqs
            ints[j, bucket + mp + 4] = fold_seed(sampling.seed)
            ints[j, bucket + mp + 5] = lora_slot
            if self.recurrent:
                ints[j, -1] = slot  # state slot of EVERY chunk; -1 = the trash row
            want_eos = bool(
                is_final and eos_ids and sampling.min_tokens >= 1
                and not sampling.ignore_eos
            )
            if want_eos:
                if len(eos_ids) > MAX_EOS_IDS:
                    log.warning(
                        "min_tokens: %d EOS ids exceed the device limit %d; ids "
                        "beyond the limit are not suppressed",
                        len(eos_ids), MAX_EOS_IDS,
                    )
                ids = np.asarray(eos_ids, np.int32)[:MAX_EOS_IDS]
                ints[j, bucket + mp + 6 : bucket + mp + 6 + len(ids)] = ids
            flts[0, j] = sampling.temperature
            flts[1, j] = sampling.top_p
            flts[2, j] = sampling.min_p
            flts[3, j] = sampling.presence_penalty
            flts[4, j] = sampling.frequency_penalty
            flts[5, j] = sampling.repetition_penalty
            want_extras = want_extras or want_eos or (
                is_final and (sampling.needs_penalties or sampling.seed is not None)
            )
        # pad lanes: n=0 (valid all-False), start 0, page table 0 (every read
        # lands in the in-bounds trash page — the V fill would DMA out of the
        # pool), slot out-of-range so the feedback write drops, lora slot 0
        # (the zero adapter)
        for j in range(len(lanes), N):
            ints[j, bucket : bucket + mp + 6] = 0
            ints[j, bucket + mp + 3] = self.config.max_seqs
            if self.recurrent:
                ints[j, -1] = -1
        return ints, flts, want_extras, mp

    def prefill_chunk_batch(
        self,
        lanes: list,
        N: int,
        bucket: int,
        want_logprobs: bool = False,
    ):
        """Dispatch ONE packed prefill of up to N lanes of ``bucket`` rows:
        whole chunks of distinct sequences (a model with recurrent layers),
        or blocks of rows of which several may be one sequence's (pad lanes
        are all-invalid; see :meth:`pack_prefill_lanes` for the lane tuple
        contract). Returns the [N] device token array (async copy started) —
        callers read only final lanes — plus the logprob arrays when
        requested."""
        ints, flts, want_extras, mp = self.pack_prefill_lanes(lanes, N, bucket)
        if want_extras:
            self._ensure_penalty_state()
        toks, lp, self.kv_cache, self.slot_state = self._prefill_packed(
            self.params,
            self.kv_cache,
            self.slot_state,
            jnp.asarray(ints),
            jnp.asarray(flts),
            self._next_key(),
            lora=self.lora,
            want_lp=want_logprobs,
            want_pen=want_extras,
            want_seed=want_extras,
            want_eos_mask=want_extras,
            mp=mp,
        )
        try:
            toks.copy_to_host_async()
            if lp is not None:
                for a in lp:
                    a.copy_to_host_async()
        except Exception:
            pass
        return (toks, lp) if want_logprobs else toks

    def _prefill_sp_impl(self, params, kv, slot_state, ints, flts, key, lora=None, want_lp=False, want_pen=False, want_seed=False, want_eos_mask=False, mp=None):
        """Same packed-ints contract as _prefill_impl, but the whole-prompt
        chunk runs sequence-parallel (model.prefill_sp: ring attention over
        the sp mesh axis). Only called with start_pos == 0."""
        if mp is None:
            mp = self.config.max_pages_per_seq
        bucket = ints.shape[0] - mp - 6 - MAX_EOS_IDS
        tokens = ints[:bucket]
        page_table = ints[bucket : bucket + mp]
        n = ints[bucket + mp + 1]
        top_k = ints[bucket + mp + 2]
        slot = ints[bucket + mp + 3]
        seed = ints[bucket + mp + 4]
        lora_id = ints[bucket + mp + 5]
        eos_ids = ints[bucket + mp + 6 :]
        positions = jnp.arange(bucket, dtype=jnp.int32)
        valid = positions < n
        if self.config.pp > 1:
            # composed pp x sp: ring attention inside the GPipe shard_map
            from dynamo_tpu.parallel.pipeline import prefill_pipelined_ring

            logits, kv = prefill_pipelined_ring(
                self.model, params, kv, tokens, positions, page_table, valid,
                n - 1, self.mesh,
            )
        else:
            lkw = {} if lora is None else dict(lora=lora, lora_id=lora_id)
            logits, kv = self.model.prefill_sp(
                params, kv, tokens, positions, page_table, valid, n - 1,
                mesh=self.mesh, **lkw,
            )
        tok, lp, slot_state = self._sample_one(
            logits, key, flts, top_k, slot, seed, n - 1, slot_state,
            want_lp, want_pen, want_seed,
            eos_ids=eos_ids if want_eos_mask else None,
        )
        return tok, lp, kv, slot_state

    @jax.named_scope("step")
    def _decode_window_impl(self, params, kv, slot_state, ints, flts, key, lora=None, num_steps=1, want_lp=False, want_pen=False, want_seed=False, want_eos_mask=False):
        """num_steps fused decode steps; the sampled-token feedback loop starts
        from the device-resident ``slot_state["tokens"]`` buffer, so the host can
        dispatch windows back-to-back without reading any results in between.

        All small per-slot inputs ride in two packed arrays (one H2D transfer
        each):
        ``ints`` [7 + MAX_EOS_IDS + max_pages, B] = positions, limits, active,
        top_ks, rope_deltas, seeds, eos_allowed_from, the per-slot EOS id rows
        (V-padded), then the transposed page tables; ``flts`` [6, B] = temps,
        top_ps, min_ps, presence, frequency, repetition. Page
        tables are static across the window — the host pre-allocates pages to
        cover positions + num_steps - 1 before calling, and a sequence freezes
        once its fed position would pass ``limits`` (no writes past its
        capacity)."""
        positions, limits = ints[0], ints[1]
        active = ints[2].astype(bool)
        top_ks = ints[3]
        rope_deltas = ints[4]  # M-RoPE per-slot offsets (zeros for text models)
        seeds = ints[5]  # per-request sampling seeds (0 = unseeded)
        eos_allowed_from = ints[6]  # fed position where EOS unblocks (min_tokens)
        eos_ids = ints[7 : 7 + MAX_EOS_IDS].T  # [B, MAX_EOS_IDS], V-padded
        page_tables = ints[7 + MAX_EOS_IDS :].T  # [B, max_pages]
        temps, top_ps, min_ps = flts[0], flts[1], flts[2]
        pres, freq, reps = flts[3], flts[4], flts[5]
        keys = jax.random.split(key, num_steps)
        # what a model that routes counts over the window, by its own
        # declaration (`window_counters`: leaves of its state cache): zeroed
        # here, the steps below add
        counters = self.model.window_counters
        kv = dict(kv, **{k: jnp.zeros_like(kv[k]) for k in counters})

        def body(carry, k):
            kv, st, positions, act = carry
            logits, kv = self._model_decode(
                params, kv, st["tokens"], positions, page_tables, act,
                rope_deltas=rope_deltas if getattr(self.model.config, "mrope_section", None) is not None else None,
                # per-slot adapter ids live in the donated slot_state bundle
                # (written once at admission), so a mixed-adapter window
                # reads them on device with zero extra H2D per dispatch
                lora=lora,
                lora_ids=st["lora"] if lora is not None else None,
            )
            raw_logits = logits
            if want_pen:
                logits = apply_penalties(logits, st["counts"], st["seen"], pres, freq, reps)
            if want_eos_mask:
                # min_tokens: ban the slot's EOS ids until its fed position
                # reaches eos_allowed_from
                rows = jnp.arange(logits.shape[0])[:, None]
                pen = jnp.where(positions >= eos_allowed_from, 0.0, -1e30)
                logits = logits.at[rows, eos_ids].add(pen[:, None], mode="drop")
            kwargs = dict(min_p=min_ps)
            if want_seed:
                kwargs.update(seeds=seeds, positions=positions)
            if want_lp:
                toks, chosen, tids, tvals = sample_tokens_with_logprobs(
                    logits, k, temps, top_ks, top_ps, raw_logits=raw_logits, **kwargs
                )
                ys = (toks, chosen, tids, tvals)
            else:
                # logprobs gated out of the trace: no full-vocab log_softmax or
                # top_k rides the hot path unless some request asked for them
                toks = sample_tokens(logits, k, temps, top_ks, top_ps, **kwargs)
                ys = (toks,)
            tokens = jnp.where(act, toks, st["tokens"])
            st = dict(st, tokens=tokens)
            if want_pen:
                rows = jnp.arange(tokens.shape[0])
                counts = st["counts"].at[rows, toks].add(act.astype(jnp.int32))
                # keep `seen` exact: only rows that actually emitted this step
                seen_tok = st["seen"].at[rows, toks].get() | act
                seen = st["seen"].at[rows, toks].set(seen_tok)
                st = dict(st, counts=counts, seen=seen)
            positions = positions + act.astype(positions.dtype)
            act = act & (positions <= limits)
            return (kv, st, positions, act), ys

        (kv, slot_state, _, _), ys = jax.lax.scan(
            body, (kv, slot_state, positions, active), keys
        )
        all_toks = ys[0]
        lp = (ys[1], ys[2], ys[3]) if want_lp else None
        # [num_steps, B] tokens (+ ([num_steps, B], [num_steps, B, K] x2) lp);
        # last, what the model counted over the window (None: an empty output)
        return all_toks, lp, kv, slot_state, {k: kv[k] for k in counters} or None

    def _verify_impl(self, params, kv, ints, flts, key, draft_probs=None, lora=None):
        """Speculative verify step: every slot feeds its anchor token plus up
        to K drafts at consecutive positions through the model's multi-query
        ``verify`` pass, then acceptance runs on device so only the tiny
        [B, K+1] token matrix and [B] emit counts cross back to the host.

        ``ints`` [6 + (K+1) + max_pages, B] = positions (anchor fed position),
        active, top_ks, seeds, n_drafts, lora slot ids, the K+1 fed-token
        rows, then the transposed page tables (K is derived from the array
        shape — one executable per configured k). ``flts`` [3, B] = temps,
        top_ps, min_ps.
        ``draft_probs`` ([B, K, V] device array from dispatch_draft, never
        staged through the host): the real draft distributions temperature>0
        acceptance divides by; None = one-hot (n-gram) proposals.
        ``lora``: a mixed-adapter verify round gathers each slot's adapter
        inside the one shared pass (the verify side must see the same
        adapter the sequence decodes with, or acceptance silently drops).
        Rows beyond a slot's n_drafts scatter their KV to the trash page, so a
        slot proposing fewer than K drafts never writes past its pages."""
        # K is config-static (one executable per configured k), so the page-
        # table width — which now varies with the ladder — falls out of the
        # array shape instead of being pinned to the dense max_pages_per_seq
        spec = self.config.spec
        K1 = (
            spec.k + 1
            if spec is not None
            else ints.shape[0] - 6 - self.config.max_pages_per_seq
        )
        positions = ints[0]
        active = ints[1].astype(bool)
        top_ks = ints[2]
        seeds = ints[3]
        n_drafts = ints[4]
        lora_ids = ints[5]
        fed = ints[6 : 6 + K1].T  # [B, K1]
        page_tables = ints[6 + K1 :].T  # [B, max_pages]
        temps, top_ps, min_ps = flts[0], flts[1], flts[2]
        t_idx = jnp.arange(K1, dtype=jnp.int32)
        pos_mat = positions[:, None] + t_idx[None, :]
        row_valid = active[:, None] & (t_idx[None, :] <= n_drafts[:, None])
        lkw = {} if lora is None else dict(lora=lora, lora_ids=lora_ids)
        logits, kv = self.model.verify(
            params, kv, fed, pos_mat, page_tables, row_valid, **lkw
        )
        out, n_emit = accept_speculative(
            logits, fed[:, 1:], n_drafts, key, temps, top_ks, top_ps,
            min_p=min_ps, seeds=seeds, positions=positions,
            draft_probs=draft_probs,
        )
        n_emit = jnp.where(active, n_emit, 0)
        return out, n_emit, kv

    # ---------------- host API (engine thread) ----------------

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def prefill_chunk(
        self,
        tokens: np.ndarray,  # [n] real tokens for this chunk
        start_pos: int,
        page_table: np.ndarray,  # [max_pages_per_seq]
        sample: bool,
        temperature: float,
        top_k: int,
        top_p: float,
        slot: int = -1,  # decode slot to seed with the sampled token (device side)
        sync: bool = True,
        embeds: Optional[np.ndarray] = None,  # [n, D] mm overrides for this chunk
        embeds_mask: Optional[np.ndarray] = None,  # [n] bool
        rope_pos: Optional[np.ndarray] = None,  # [n, 3] M-RoPE positions
        want_logprobs: bool = False,  # sync=False only: also return lp arrays
        sampling=None,  # SamplingParams: penalties / min_p / seed (optional)
        eos_ids=None,  # request EOS ids (min_tokens device-side suppression)
        lora_slot: int = 0,  # adapter slot for this chunk (0 = base/zero)
        state_slot: int = -1,  # recurrent models: the sequence's decode slot, on EVERY chunk
    ):
        """Run one prefill chunk.

        When ``sample``: returns the sampled next token — as a host int when
        ``sync``, else as a device scalar (dispatch-ahead mode; an async
        device-to-host copy is already in flight). When ``slot >= 0`` the token
        is also written into ``slot_state["tokens"][slot]`` on device so decode windows
        can start without waiting for the host to see it."""
        n = len(tokens)
        bucket = self.config.bucket_for(n)
        # the caller's table is already sized to a ladder bucket (scheduler/
        # engine build them via table_bucket_for); its width picks the trace
        page_table = page_table.reshape(-1)  # a table per layer: side by side
        mp = len(page_table)
        V = self.model.config.vocab_size
        ints = np.full(bucket + mp + 6 + MAX_EOS_IDS + int(self.recurrent), V, np.int32)  # tail = eos pad
        if self.recurrent:
            ints[-1] = state_slot
        ints[:bucket] = 0
        ints[:n] = tokens
        ints[bucket : bucket + mp] = page_table[:mp]
        ints[bucket + mp] = start_pos
        ints[bucket + mp + 1] = n
        ints[bucket + mp + 2] = top_k
        # out-of-bounds slot => scatter mode="drop" skips the token write
        ints[bucket + mp + 3] = slot if (sample and slot >= 0) else self.config.max_seqs
        ints[bucket + mp + 4] = fold_seed(sampling.seed) if sampling is not None else 0
        ints[bucket + mp + 5] = lora_slot
        want_pen = sampling is not None and sampling.needs_penalties
        want_seed = sampling is not None and sampling.seed is not None
        # min_tokens >= 1: the first sampled token (generation #1) must not be
        # EOS -> suppress the request's EOS logits on device. Matches vLLM:
        # EOS is suppressed while generated < min_tokens, so min_tokens=1
        # guarantees one non-EOS token.
        want_eos = bool(
            sample
            and eos_ids is not None
            and len(eos_ids) > 0
            and sampling is not None
            and sampling.min_tokens >= 1
            and not sampling.ignore_eos
        )
        if want_eos:
            if len(eos_ids) > MAX_EOS_IDS:
                log.warning(
                    "min_tokens: %d EOS ids exceed the device limit %d; ids "
                    "beyond the limit are not suppressed",
                    len(eos_ids), MAX_EOS_IDS,
                )
            ids = np.asarray(eos_ids, np.int32)[:MAX_EOS_IDS]
            ints[bucket + mp + 6 : bucket + mp + 6 + len(ids)] = ids
        flts = np.array(
            [
                temperature,
                top_p,
                sampling.min_p if sampling is not None else 0.0,
                sampling.presence_penalty if sampling is not None else 0.0,
                sampling.frequency_penalty if sampling is not None else 0.0,
                sampling.repetition_penalty if sampling is not None else 1.0,
            ],
            np.float32,
        )
        mm_args = ()
        if embeds is not None or rope_pos is not None:
            # multimodal chunk: embeds/rope-override trace of _prefill (paged
            # path only; the sp/ring path is text-only for now)
            D = embeds.shape[1] if embeds is not None else 1
            emb = np.zeros((bucket, D), np.float32)
            msk = np.zeros(bucket, bool)
            if embeds is not None:
                emb[:n] = embeds
                msk[:n] = embeds_mask
            rp = None
            if rope_pos is not None:
                rp_pad = np.zeros((bucket, 3), np.int32)
                rp_pad[:n] = rope_pos
                rp = jnp.asarray(rp_pad)
            mm_args = (jnp.asarray(emb) if embeds is not None else None,
                       jnp.asarray(msk) if embeds is not None else None,
                       rp)
        # whole-prompt chunks go sequence-parallel when configured (ring
        # attention assumes the chunk starts at position 0)
        use_sp = (
            embeds is None
            and rope_pos is None
            and self.config.sp > 1
            and start_pos == 0
            and bucket % self.config.sp == 0
        )
        prefill_fn = self._prefill_sp if use_sp else self._prefill
        # same trace collapse as dispatch_decode_window: penalties/seeds/EOS
        # masking share one feature-bearing variant (neutral inputs are no-ops)
        want_extras = bool((want_pen and sample) or (want_seed and sample) or want_eos)
        if want_extras:
            self._ensure_penalty_state()
        tok, lp, self.kv_cache, self.slot_state = prefill_fn(
            self.params,
            self.kv_cache,
            self.slot_state,
            jnp.asarray(ints),
            jnp.asarray(flts),
            self._next_key(),
            *mm_args,
            lora=self.lora,
            # only the sampling (final) chunk's outputs are ever consumed
            want_lp=want_logprobs and sample,
            want_pen=want_extras,
            want_seed=want_extras,
            want_eos_mask=want_extras,
            mp=mp,
        )
        if not sample:
            return None
        if sync:
            return int(jax.device_get(tok))  # graftlint: sync-ok sync chunk path: caller asked for the token synchronously
        try:
            tok.copy_to_host_async()
            if lp is not None:
                for a in lp:
                    a.copy_to_host_async()
        except Exception:
            pass
        if want_logprobs:
            return tok, lp
        return tok

    VISION_BUCKETS = (64, 256, 1024, 4096, 16384)

    def encode_images(self, images: list) -> list[np.ndarray]:
        """Run the vision tower over a request's ImageInputs; returns per-image
        [num_tokens, D] float32 embeddings.

        All images pack into ONE bucket-padded call (attention is masked
        block-diagonal via segment ids), so a multi-image prompt costs a
        single dispatch. Falls back to per-image calls only when
        the combined patch count exceeds the largest bucket."""
        if not images:
            return []
        total = sum(im.patches.shape[0] for im in images)
        bucket = next((b for b in self.VISION_BUCKETS if b >= total), None)
        if bucket is None:
            if len(images) == 1:
                raise ValueError(f"image has {total} patches > max bucket")
            # too big combined: split the batch in half recursively
            mid = len(images) // 2
            return self.encode_images(images[:mid]) + self.encode_images(images[mid:])
        patch_dim = images[0].patches.shape[1]
        patches = np.zeros((bucket, patch_dim), np.float32)
        rows = np.zeros(bucket, np.int32)
        cols = np.zeros(bucket, np.int32)
        valid = np.zeros(bucket, bool)
        # single image: skip the pairwise segment mask entirely (it would be
        # an [N, N] f32 bias held across every tower layer)
        segments = None if len(images) == 1 else np.full(bucket, -1, np.int32)
        offset = 0
        spans = []
        for idx, im in enumerate(images):
            n = im.patches.shape[0]
            patches[offset : offset + n] = im.patches
            rows[offset : offset + n] = im.rows
            cols[offset : offset + n] = im.cols
            valid[offset : offset + n] = True
            if segments is not None:
                segments[offset : offset + n] = idx
            spans.append((offset, n))
            offset += n
        emb = np.asarray(  # graftlint: sync-ok vision embeds materialize once per request at admission
            jax.device_get(
                self._encode_images(
                    self.params,
                    jnp.asarray(patches),
                    jnp.asarray(rows),
                    jnp.asarray(cols),
                    jnp.asarray(valid),
                    jnp.asarray(segments) if segments is not None else None,
                )
            ),
            np.float32,
        )
        m2 = self.model.config.vision.spatial_merge_size ** 2
        return [
            emb[off // m2 : off // m2 + im.num_tokens]
            for (off, n), im in zip(spans, images)
        ]

    def write_token_slots(self, slots: np.ndarray, tokens: np.ndarray) -> None:
        """Host-known tokens (e.g. disagg adoption) -> slot token feedback."""
        self.slot_state = self._write_tokens(
            self.slot_state, jnp.asarray(slots, jnp.int32), jnp.asarray(tokens, jnp.int32)
        )

    def set_slot_lora(self, slot: int, lora_slot: int) -> None:
        """Pin a decode slot's adapter id in the device-resident slot_state
        (written once at admission; decode windows gather it per step).
        No-op on a LoRA-disabled engine."""
        if self.lora is None:
            return
        self.slot_state = self._set_lora(
            self.slot_state,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(lora_slot, jnp.int32),
        )

    def load_lora_slot(self, slot: int, host_tree: dict, scale: float) -> None:
        """Scatter one adapter's A/B planes into pool slot ``slot`` (donated
        in-place update; one executable total — every adapter arrives padded
        to the pool rank, so the shapes never vary)."""
        tree = {
            m: {"a": jnp.asarray(e["a"]), "b": jnp.asarray(e["b"])}
            for m, e in host_tree.items()
        }
        self.lora = self._lora_write(
            self.lora,
            jnp.asarray(slot, jnp.int32),
            tree,
            jnp.asarray(scale, jnp.float32),
        )

    def _ensure_penalty_state(self) -> None:
        if "counts" not in self.slot_state:
            V = self.model.config.vocab_size
            B = self.config.max_seqs
            self.slot_state = dict(
                self.slot_state,
                counts=jnp.zeros((B, V), jnp.int32),
                seen=jnp.zeros((B, V), bool),
            )

    def _pad_ids_bucket(self, ids: np.ndarray) -> np.ndarray:
        """Pad an id list to a prefill bucket with V (OOB -> scatter-dropped)
        so _seed_pen compiles once per bucket, not per prompt length."""
        V = self.model.config.vocab_size
        n = len(ids)
        size = next(
            (b for b in self.config.prefill_buckets if b >= n),
            max(self.config.max_model_len, n),
        )
        out = np.full(size, V, np.int32)
        out[:n] = ids
        return out

    def seed_penalty_slot(self, slot: int, token_ids, output_from: int | None = None) -> None:
        """Reset a slot's penalty state: mark all of ``token_ids`` seen; count
        the tail from ``output_from`` as output occurrences (a preempted
        request's prompt embeds its prior output — restoring the counts keeps
        presence/frequency penalties continuous across preemption)."""
        self._ensure_penalty_state()
        ids = np.asarray(token_ids, np.int32)
        out_ids = ids[output_from:] if output_from is not None else ids[:0]
        self.slot_state = self._seed_pen(
            self.slot_state,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(self._pad_ids_bucket(ids)),
            jnp.asarray(self._pad_ids_bucket(out_ids)),
        )

    def dispatch_decode_window(
        self,
        positions: np.ndarray,  # [B] fed-token position per slot
        page_tables: np.ndarray,  # [B, max_pages_per_seq]
        active: np.ndarray,  # [B] bool
        limits: np.ndarray,  # [B] max fed-token position per slot
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        num_steps: int,
        want_logprobs: bool = False,
        rope_deltas: np.ndarray | None = None,  # [B] M-RoPE offsets
        min_ps: np.ndarray | None = None,  # [B]
        penalties: np.ndarray | None = None,  # [3, B] presence/frequency/repetition
        seeds: np.ndarray | None = None,  # [B] int32 (0 = unseeded)
        eos_allowed_from: np.ndarray | None = None,  # [B] fed pos (min_tokens)
        eos_ids: np.ndarray | None = None,  # [B, MAX_EOS_IDS] V-padded
    ):
        """Dispatch one fused decode window WITHOUT waiting for results.

        Returns the [num_steps, B] device token array with an async
        device-to-host copy already started; the caller materializes it later
        (np.asarray) while further windows run on device."""
        B = positions.shape[0]
        V = self.model.config.vocab_size
        page_tables = page_tables.reshape(B, -1)  # a table per layer: side by side
        ints = np.empty((7 + MAX_EOS_IDS + page_tables.shape[1], B), np.int32)
        ints[0] = positions
        ints[1] = limits
        ints[2] = active
        ints[3] = top_ks
        ints[4] = rope_deltas if rope_deltas is not None else 0
        ints[5] = seeds if seeds is not None else 0
        ints[6] = eos_allowed_from if eos_allowed_from is not None else 0
        ints[7 : 7 + MAX_EOS_IDS] = eos_ids.T if eos_ids is not None else V
        ints[7 + MAX_EOS_IDS :] = page_tables.T
        flts = np.empty((6, B), np.float32)
        flts[0] = temps
        flts[1] = top_ps
        flts[2] = min_ps if min_ps is not None else 0.0
        flts[3:6] = penalties if penalties is not None else np.array([[0.0], [0.0], [1.0]])
        # penalties / seeded streams / min_tokens EOS masking collapse into ONE
        # feature-bearing trace: all their neutral inputs are no-ops (penalty
        # (0,0,1), seed 0, V-padded EOS rows dropped by the OOB scatter), so
        # 2^3 flag combinations become 2 and a request introducing a new
        # combination mid-serving can't hit a multi-second cold XLA compile.
        want_extras = (
            penalties is not None
            or (seeds is not None and bool(np.any(seeds)))
            or eos_ids is not None
        )
        if want_extras:
            self._ensure_penalty_state()
        toks, lp, self.kv_cache, self.slot_state, self.window_aux = self._decode_window(
            self.params,
            self.kv_cache,
            self.slot_state,
            jnp.asarray(ints),
            jnp.asarray(flts),
            self._next_key(),
            lora=self.lora,
            num_steps=num_steps,
            want_lp=want_logprobs,
            want_pen=want_extras,
            want_seed=want_extras,
            want_eos_mask=want_extras,
        )
        try:
            toks.copy_to_host_async()
            if want_logprobs:
                for a in lp:
                    a.copy_to_host_async()
            for a in jax.tree.leaves(self.window_aux):
                a.copy_to_host_async()
        except Exception:
            pass
        return (toks, lp) if want_logprobs else toks

    def dispatch_verify(
        self,
        positions: np.ndarray,  # [B] anchor fed position per slot
        page_tables: np.ndarray,  # [B, max_pages_per_seq]
        active: np.ndarray,  # [B] bool
        fed_tokens: np.ndarray,  # [B, K+1] anchor + (padded) draft tokens
        n_drafts: np.ndarray,  # [B] real draft count per slot
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        min_ps: np.ndarray | None = None,
        seeds: np.ndarray | None = None,  # [B] int32 (0 = unseeded)
        draft_probs=None,  # [B, K, V] device array from dispatch_draft
        lora_slots: np.ndarray | None = None,  # [B] adapter slot ids
    ):
        """Dispatch one speculative verify pass; returns the (tokens [B, K+1],
        n_emit [B]) device arrays with async host copies already started. The
        caller materializes both (the proposer needs the accepted tokens
        before it can draft the next round, so verify rounds are synchronous
        per slot — the win is k+1 tokens per weight pass, not dispatch-ahead).
        ``draft_probs`` rides through to the on-device acceptance untouched
        (draft-model rounds); None keeps the one-hot (n-gram) rule."""
        B = positions.shape[0]
        K1 = fed_tokens.shape[1]
        ints = np.empty((6 + K1 + page_tables.shape[1], B), np.int32)
        ints[0] = positions
        ints[1] = active
        ints[2] = top_ks
        ints[3] = seeds if seeds is not None else 0
        ints[4] = n_drafts
        ints[5] = lora_slots if lora_slots is not None else 0
        ints[6 : 6 + K1] = fed_tokens.T
        ints[6 + K1 :] = page_tables.T
        flts = np.empty((3, B), np.float32)
        flts[0] = temps
        flts[1] = top_ps
        flts[2] = min_ps if min_ps is not None else 0.0
        out, n_emit, self.kv_cache = self._verify(
            self.params,
            self.kv_cache,
            jnp.asarray(ints),
            jnp.asarray(flts),
            self._next_key(),
            draft_probs,
            lora=self.lora,
        )
        try:
            out.copy_to_host_async()
            n_emit.copy_to_host_async()
        except Exception:
            pass
        return out, n_emit

    def dispatch_draft(self, *args, **kwargs):
        """One batched draft round across every spec-mode lane (draft-model
        speculation only; see spec/draft.py DraftModelRunner.dispatch_draft).
        Returns (draft tokens [B, K] dev, draft probs [B, K, V] dev)."""
        if self.draft is None:
            raise RuntimeError("dispatch_draft requires speculative='draft:...'")
        return self.draft.dispatch_draft(*args, **kwargs)

    def warmup(self) -> None:
        """Pre-compile every trace variant synchronously (core + extras)."""
        import time as _time

        t0 = _time.monotonic()
        self.warmup_core()
        for thunk in self.warmup_extra_thunks():
            thunk()
        log.info("warmup: trace variants compiled in %.1fs", _time.monotonic() - t0)

    @property
    def packed_prefill_mode(self) -> bool:
        """True when the scheduler packs prefill chunks through the packed
        trace (the single definition of the gate; the scheduler adds a
        per-request `not req.images` condition on top)."""
        return (
            self.config.prefill_lanes > 1
            and self.config.pp == 1
            and self.config.sp == 1
            and self.model.prefill_packed is not None
        )

    def _table_shape(self, width: int) -> tuple:
        """One sequence's page table at ladder width `width`."""
        return (self.kv_tables, width) if self.kv_tables > 1 else (width,)

    def _warmup_shapes(self, table_width: Optional[int] = None):
        B = self.config.max_seqs
        # narrow (first-rung) tables are the hot path for a fresh engine —
        # deep sequences promote into the wider ladder variants, which
        # compile via warmup_extra_thunks
        mp = table_width or self.config.table_buckets[0]
        return {
            "zeros_i": np.zeros(B, np.int32),
            "pt": np.zeros((B, *self._table_shape(mp)), np.int32),
            "inactive": np.zeros(B, bool),
            "temps": np.zeros(B, np.float32),
            "ones_f": np.ones(B, np.float32),
            "neutral_pen": np.tile(
                np.array([[0.0], [0.0], [1.0]], np.float32), (1, B)
            ),
        }

    def warmup_core(self) -> None:
        """Blocking pre-compile of the traces the FIRST requests need: the
        default decode window plus every prefill bucket's default trace (per-
        request and packed). All slots are inactive / writes target the
        reserved null page 0, so the calls execute harmlessly; what matters is
        that the XLA executables land in the jit cache before live traffic.

        Feature variants (logprobs/penalties) compile via
        ``warmup_extra_thunks`` — in the background on a serving engine
        (first deploy of a new geometry used to block on compiles of
        variants most traffic never touches)."""
        import time as _time

        t0 = _time.monotonic()
        # Allocate the penalty buffers FIRST: slot_state's pytree structure is
        # part of the jit cache key, so every variant must compile against the
        # final (counts-bearing) structure or live traffic re-traces them all.
        self._ensure_penalty_state()
        sh = self._warmup_shapes()
        K = self.config.decode_steps
        rungs = self.config.table_buckets
        for width in rungs if self.warms_every_rung else rungs[:1]:
            shw = self._warmup_shapes(table_width=width)
            out = self.dispatch_decode_window(
                shw["zeros_i"], shw["pt"], shw["inactive"], shw["zeros_i"],
                shw["temps"], shw["zeros_i"], shw["ones_f"], K,
            )
            jax.block_until_ready(out)  # graftlint: sync-ok warmup: compile gate, not serving traffic
        spec = self.config.spec
        if spec is not None:
            # one verify executable per configured k (all slots inactive, KV
            # rows land on the trash page — harmless, compiles the trace);
            # draft mode compiles the draft-probs-bearing variant plus the
            # draft runner's own step/prefill executables
            B = self.config.max_seqs
            dp = None
            if self.draft is not None:
                self.draft.warmup()
                V = self.model.config.vocab_size
                dp = jnp.zeros((B, spec.k, V), jnp.float32)
            out = self.dispatch_verify(
                sh["zeros_i"], sh["pt"], sh["inactive"],
                np.zeros((B, spec.k + 1), np.int32), sh["zeros_i"],
                sh["temps"], sh["zeros_i"], sh["ones_f"], draft_probs=dp,
            )
            jax.block_until_ready(out)  # graftlint: sync-ok warmup: compile gate, not serving traffic
        if self.packed_prefill_mode:
            # the packed programs a first request can reach; the rest, the
            # feature variants and the per-request trace (still reached by
            # disagg remote prefill and image requests) compile via the
            # extras thunks
            for N, T, width in self.packed_warmup_shapes()[0]:
                self._warm_packed(N, T, width)
        else:
            for b in self.config.prefill_buckets:
                self.prefill_chunk(
                    np.zeros(b, np.int32), 0, sh["pt"][0], sample=True,
                    temperature=0.0, top_k=0, top_p=1.0, slot=-1, sync=True,
                )
        log.info("warmup(core): compiled in %.1fs", _time.monotonic() - t0)

    @property
    def warms_every_rung(self) -> bool:
        """True where warm-up compiles the default step programs of EVERY
        rung of the page-table ladder before readiness: a model with
        recurrent layers on a ladder of up to three rungs. Such a model takes
        no prefix from the cache, so a long prompt arrives whole and its
        chunks and decode windows run on the wider rungs from the first
        second of traffic; which (lanes, bucket) they meet there turns on how
        arrivals fall together. (`lfm2-8b-a1b-d16.rag-over`, PR 44: with the
        first rung's eight alone before readiness, the benchmark's warm
        bursts met the same 20 further rectangles in each of four runs and
        never five of those the packer could then emit, four lanes beside a
        prompt of over 2048 tokens, which a walk of the packer under that mix
        meets in one window of ten. Those five no longer exist:
        `EngineConfig.lanes_for`.) A short ladder makes the whole set cheap
        to have, 27 + 3 programs at (128, 256, 320), all of which a first
        hour of traffic loads anyway. On a longer one (contexts past 8192
        tokens at a page of 16) rectangles x rungs would hold readiness for
        every one of them, and the wider rungs compile behind it as they did."""
        return self.recurrent and len(self.config.table_buckets) <= 3

    def packed_prefill_shapes(self, wide: bool = False) -> list:
        """(N, T) of every packed prefill program the scheduler's packer can
        emit: blocks of `prefill_block` rows, 1 to `pack_blocks` of them; for
        a model with recurrent layers a rectangle per bucket, its lane count
        a power of two up to `lanes_for` (on a rung beyond the first, `wide`,
        up to two)."""
        c = self.config
        if not self.recurrent:
            return [(n, c.prefill_block) for n in range(1, c.pack_blocks + 1)]
        shapes = []
        for b in c.prefill_buckets:
            lanes = c.lanes_for(b, wide)
            shapes += [(n, b) for n in sorted({min(lanes, 1 << k) for k in range(lanes.bit_length() + 1)})]
        return shapes

    def packed_warmup_shapes(self) -> tuple:
        """(N, T, table width) of the packed prefill programs warm-up
        compiles: (before readiness, behind it), the default variant of each.

        Blocks: every N on the first rung of the page-table ladder (where a
        fresh engine's prompts run) and on the last (where a deployment whose
        max_model_len was set for long contexts runs them) before readiness,
        because traffic reaches any N from its first second and a program met
        first in traffic stalls every stream for its compile; the rungs
        between compile behind readiness.

        Rectangles: on a ladder of up to three rungs (`warms_every_rung`)
        every rectangle on every rung before readiness. On a longer ladder
        N = 1 and N = lanes_for(b) of every bucket on the first rung before
        readiness; the powers of two between, and on each wider rung the one
        chunk the depth-aware planner runs at that depth (chunk_len_for
        shrinks chunks as context grows) at N = 1, behind."""
        c = self.config
        rungs = c.table_buckets
        shapes = self.packed_prefill_shapes()
        if not self.recurrent:
            first = sorted({rungs[0], rungs[-1]})
            return (
                [(n, t, w) for w in first for n, t in shapes],
                [(n, t, w) for w in rungs[1:-1] for n, t in shapes],
            )
        if self.warms_every_rung:
            return [(n, t, w) for w in rungs
                    for n, t in self.packed_prefill_shapes(wide=w != rungs[0])], []
        core = [(n, t, rungs[0]) for n, t in shapes if n in (1, c.lanes_for(t))]
        later = [(n, t, rungs[0]) for n, t in shapes if n not in (1, c.lanes_for(t))]
        later += [(1, c.chunk_len_for((w // 2) * c.page_size), w) for w in rungs[1:]]
        return core, later

    def _warm_packed(self, N: int, T: int, width: int, sampling=None, want_lp: bool = False) -> None:
        """Compile (or read back) one packed prefill program by running it on
        one all-zero lane whose writes land on the reserved null page."""
        lane = (
            np.zeros(T, np.int32), 0, np.zeros(self._table_shape(width), np.int32), -1,
            sampling or SamplingParams(temperature=0.0),
            (0,) if sampling is not None else (),
            sampling is not None,
        )
        out = self.prefill_chunk_batch([lane], N=N, want_logprobs=want_lp, bucket=T)
        jax.block_until_ready(out)  # graftlint: sync-ok warmup: compile gate, not serving traffic

    def warmup_extra_thunks(self) -> list:
        """Thunks compiling the feature-bearing trace variants — decode
        windows with penalties/logprobs, prefill extras/logprobs traces, and
        the packed equivalents. Each runs harmlessly against inactive slots;
        a serving engine executes them one by one between steps (via
        run_on_engine) so readiness never waits on them."""
        sh = self._warmup_shapes()
        K = self.config.decode_steps
        thunks = []

        def window(kwargs):
            def run():
                out = self.dispatch_decode_window(
                    sh["zeros_i"], sh["pt"], sh["inactive"], sh["zeros_i"],
                    sh["temps"], sh["zeros_i"], sh["ones_f"], K, **kwargs,
                )
                jax.block_until_ready(out)  # graftlint: sync-ok warmup: compile gate, not serving traffic
            return run

        for kwargs in (
            {"penalties": sh["neutral_pen"]},
            {"want_logprobs": True},
            {"want_logprobs": True, "penalties": sh["neutral_pen"]},
        ):
            thunks.append(window(kwargs))

        def chunk(bucket, sampling, want_lp):
            def run():
                out = self.prefill_chunk(
                    np.zeros(bucket, np.int32), 0, sh["pt"][0], sample=True,
                    temperature=0.0, top_k=0, top_p=1.0, slot=-1,
                    sync=not want_lp, want_logprobs=want_lp, sampling=sampling,
                    eos_ids=(0,) if sampling is not None else None,
                )
                if want_lp:
                    jax.block_until_ready(out)  # graftlint: sync-ok warmup: compile gate, not serving traffic
            return run

        def packed(N, T, width, sampling=None, want_lp=False):
            return lambda: self._warm_packed(N, T, width, sampling, want_lp)

        bucket = self.config.prefill_buckets[0]
        for sampling, want_lp in (
            (None, True),
            (SamplingParams(presence_penalty=0.1, min_tokens=1), False),
            (SamplingParams(presence_penalty=0.1, min_tokens=1), True),
        ):
            thunks.append(chunk(bucket, sampling, want_lp))
        if self.packed_prefill_mode:
            # the per-request trace is NOT dead in packed mode: disagg remote
            # prefill (run_prefill_chunks) and image-bearing requests still
            # dispatch it — compile its default per-bucket traces here
            def per_request(b):
                def run():
                    self.prefill_chunk(
                        np.zeros(b, np.int32), 0, sh["pt"][0], sample=True,
                        temperature=0.0, top_k=0, top_p=1.0, slot=-1, sync=True,
                    )
                return run

            for b in self.config.prefill_buckets:
                thunks.append(per_request(b))
        # packed-prefill executables: every shape the packer can emit, the
        # neutral AND the feature-bearing variants (want_* are static jit
        # args — every combo is a distinct executable). Without these the
        # first packed shape cold-compiles mid-traffic, a stall that can
        # exceed HTTP client timeouts.
        if self.packed_prefill_mode:
            w0 = self.config.table_buckets[0]
            later = self.packed_warmup_shapes()[1]
            thunks += [packed(*shape) for shape in later if shape[2] == w0]
            for N, T in self.packed_prefill_shapes():
                for sampling, want_lp in (
                    (None, True),
                    (SamplingParams(presence_penalty=0.1, min_tokens=1), False),
                    (SamplingParams(presence_penalty=0.1, min_tokens=1), True),
                ):
                    thunks.append(packed(N, T, w0, sampling, want_lp))
        # page-table ladder: wider-table variants for the traces a DEEP
        # sequence promotes into mid-serving — the default decode window and
        # the prefill bucket the depth-aware planner runs at that depth
        # (chunk_len_for shrinks chunks as context grows, so the (chunk,
        # width) pairs compiled here are the ones live traffic reaches)
        def wide_window(width):
            shw = self._warmup_shapes(table_width=width)

            def run():
                out = self.dispatch_decode_window(
                    shw["zeros_i"], shw["pt"], shw["inactive"], shw["zeros_i"],
                    shw["temps"], shw["zeros_i"], shw["ones_f"], K,
                )
                jax.block_until_ready(out)  # graftlint: sync-ok warmup: compile gate, not serving traffic
            return run

        def wide_chunk(width, b):
            def run():
                self.prefill_chunk(
                    np.zeros(b, np.int32), 0, np.zeros(self._table_shape(width), np.int32),
                    sample=True, temperature=0.0, top_k=0, top_p=1.0, slot=-1, sync=True,
                )
            return run

        for w in self.config.table_buckets[1:]:
            if not self.warms_every_rung:  # else warmup_core has it
                thunks.append(wide_window(w))
            if self.packed_prefill_mode:
                thunks += [packed(*shape) for shape in later if shape[2] == w]
            else:
                depth = (w // 2) * self.config.page_size  # where this rung starts
                thunks.append(wide_chunk(w, self.config.chunk_len_for(depth)))
        return thunks

    def extract_pages_device(self, page_ids: np.ndarray) -> jax.Array:
        """Gather KV blocks into a device array [L, 2, n, page_size, Hkv, D]
        WITHOUT a host copy — the same-pod (ICI) transfer path: the consumer
        reshards it onto its own mesh with jax.device_put, so on multi-chip
        hardware the blocks ride the interconnect, never host DRAM."""
        return self._gather_pages(self.kv_cache, jnp.asarray(page_ids, jnp.int32))

    def extract_pages(self, page_ids: np.ndarray):
        """Pull KV blocks to host: [L, 2, n, page_size, Hkv, D] numpy — or,
        with an int8 cache, the {"q", "s"} wire dict (quant/kv.py): int8
        page data plus its per-row scale plane, half the host bytes.

        The device gather runs jitted; the host copy is the DCN-transfer
        staging step (same-pod ICI transfers use extract_pages_device).
        """
        return jax.tree.map(np.asarray, jax.device_get(self.extract_pages_device(page_ids)))  # graftlint: sync-ok DCN staging: deliberate D2H export priced by kv_stream metrics

    def extract_pages_async(self, page_ids: np.ndarray):
        """Chunk-streamed export: dispatch the device gather NOW (on the
        engine thread, so it enqueues right behind the prefill chunk that
        finalized these pages) and resolve the blocking device->host copy on
        a two-worker side pool. Returns a concurrent.futures.Future of the
        host numpy array (or {"q","s"} wire dict for int8 caches).
        Double-buffered by construction: the engine thread
        is free to dispatch chunk i+1's compute while chunk i's pages drain
        to host, and at most two pulls are ever in flight."""
        dev = self.extract_pages_device(page_ids)
        pool = getattr(self, "_d2h_pool", None)
        if pool is None:
            from concurrent.futures import ThreadPoolExecutor

            pool = self._d2h_pool = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="kv-d2h"
            )
        return pool.submit(lambda: jax.tree.map(np.asarray, jax.device_get(dev)))  # graftlint: sync-ok D2H resolved on the side pool, engine thread stays free

    def inject_pages_bucketed(self, page_ids: np.ndarray, data, axis=None) -> None:
        """Scatter a PARTIAL run of pages, padded to a power-of-two id count
        (the HostKvPool.load_many trick): pad ids are out of range so the
        donated scatter drops them. Streamed KV parts and prefix restores
        arrive in arbitrary sizes; without bucketing every distinct size
        would compile its own scatter executable."""
        from dynamo_tpu.quant.kv import wire_pad

        if axis is None:
            axis = self.model.wire_n_axis
        ids = np.asarray(page_ids, np.int32)
        n = len(ids)
        if n == 0:
            return
        bucket = 1 << (n - 1).bit_length()
        if bucket > n:
            padded = np.full(bucket, np.iinfo(np.int32).max // 2, np.int32)
            padded[:n] = ids
            ids = padded
            data = wire_pad(data, axis, bucket - n)
        self.inject_pages(ids, data)

    def inject_pages(self, page_ids: np.ndarray, data) -> None:
        """Write KV blocks received from a peer into our pages (donated
        scatter). ``data`` may be host numpy (DCN path), a device array from
        a peer engine (ICI path) — device_put reshards it onto our mesh —
        or the int8 {"q","s"} wire dict (host or device leaves). Dtype
        conversion happens inside the model's scatter_pages_wire: a
        full-precision wire block quantizes into an int8 cache and an int8
        block dequantizes into a full-precision one, so mixed-dtype disagg
        pairs stay interoperable."""
        if isinstance(data, dict):
            leaves = list(data.values())
            if any(isinstance(x, jax.Array) for x in leaves):
                ws = self.model.wire_sharding(self.mesh)
                if not isinstance(ws, dict):
                    # int8 wire from a peer into a full-precision cache
                    ws = {"q": ws, "s": NamedSharding(self.mesh, P())}
                data = jax.device_put(data, ws)
            else:
                data = {k: jnp.asarray(v) for k, v in data.items()}
        elif isinstance(data, jax.Array):
            ws = self.model.wire_sharding(self.mesh)
            if isinstance(ws, dict):
                ws = ws["q"]  # plain-array wire into an int8 cache
            data = jax.device_put(data, ws)
        else:
            data = jnp.asarray(data)
        self.kv_cache = self._scatter_pages(
            self.kv_cache, jnp.asarray(page_ids, jnp.int32), data
        )

    def hbm_stats(self) -> dict:
        """Device memory gauges: live/peak bytes summed over local devices via
        ``jax.Device.memory_stats()`` (TPU/GPU); graceful zeros on CPU, where
        the runtime reports nothing."""
        live = peak = limit = 0
        devices = 0
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            devices += 1
            live += int(stats.get("bytes_in_use", 0))
            peak += int(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0)))
            limit += int(stats.get("bytes_limit", 0))
        return {
            "hbm_bytes_in_use": live,
            "hbm_peak_bytes_in_use": peak,
            "hbm_bytes_limit": limit,
            "hbm_reporting_devices": devices,
        }

    def decode_steps(
        self,
        tokens: np.ndarray,  # [B]
        positions: np.ndarray,  # [B]
        page_tables: np.ndarray,  # [B, max_pages_per_seq]
        active: np.ndarray,  # [B] bool
        limits: np.ndarray,  # [B] max fed-token position per slot
        temps: np.ndarray,
        top_ks: np.ndarray,
        top_ps: np.ndarray,
        num_steps: int,
    ) -> np.ndarray:
        """Synchronous fused multi-step decode with host-provided feed tokens:
        seeds the token feedback, runs one window, returns [num_steps, B] tokens.

        Accepts any B <= max_seqs; inputs are padded to the max_seqs batch the
        window executable is compiled for (extra slots inactive)."""
        B = tokens.shape[0]
        S = self.config.max_seqs
        if B > S:
            raise ValueError(f"batch {B} exceeds max_seqs {S}")
        if B < S:
            pad = S - B
            tokens = np.concatenate([tokens, np.zeros(pad, tokens.dtype)])
            positions = np.concatenate([positions, np.zeros(pad, positions.dtype)])
            page_tables = np.concatenate(
                [page_tables, np.zeros((pad, page_tables.shape[1]), page_tables.dtype)]
            )
            active = np.concatenate([active, np.zeros(pad, bool)])
            limits = np.concatenate([limits, np.zeros(pad, limits.dtype)])
            temps = np.concatenate([temps, np.zeros(pad, temps.dtype)])
            top_ks = np.concatenate([top_ks, np.zeros(pad, top_ks.dtype)])
            top_ps = np.concatenate([top_ps, np.ones(pad, top_ps.dtype)])
        self.write_token_slots(np.arange(S, dtype=np.int32), tokens)
        toks = self.dispatch_decode_window(
            positions, page_tables, active, limits, temps, top_ks, top_ps, num_steps
        )
        return np.asarray(jax.device_get(toks))[:, :B]  # graftlint: sync-ok sync decode helper for bench/tests, not the serving loop
