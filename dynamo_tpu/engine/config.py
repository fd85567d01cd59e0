"""Engine configuration."""

from __future__ import annotations

from dataclasses import dataclass


@dataclass
class EngineConfig:
    model_id: str = "tiny"
    # paged KV cache; page_size doubles as the KV block size for hashing/routing
    page_size: int = 16
    num_pages: int = 512  # includes the reserved null page 0
    max_seqs: int = 8  # decode batch slots
    max_model_len: int = 2048
    prefill_buckets: tuple = (64, 128, 256, 512)  # padded prefill chunk lengths
    # long context — page-table width ladder (in PAGES). Every dispatch used
    # to pad page tables to the dense max_pages_per_seq width; at 128K/page 16
    # that is 8192 entries of H2D + gather per call even for a 200-token
    # chat. With a ladder, each sequence's table is padded only to its
    # current pow2 bucket, so short sequences keep their narrow traces and
    # only deep sequences pay wide gathers (one jit variant per width,
    # compiled via the warmup machinery). () = auto: min(128,
    # max_pages_per_seq) doubling up to max_pages_per_seq — a single width
    # (the pre-ladder behavior) whenever max_pages_per_seq <= 128.
    page_table_buckets: tuple = ()
    # depth-aware chunked prefill: a chunk's attention work scales with
    # chunk_len * context_depth, so fixed-size chunks get linearly slower as
    # prefill advances into a long prompt — starving colocated decode windows
    # and bloating per-chunk latency. The planner shrinks the chunk bucket
    # once depth * chunk would exceed max_prefill_chunk * prefill_flat_depth
    # (keeping per-chunk work roughly flat past that point, floored at the
    # smallest bucket). The default holds full-size chunks through the first
    # ~8K of context, so short-context configs chunk exactly as before.
    # 0 disables (always max_prefill_chunk).
    prefill_flat_depth: int = 8192
    tp: int = 1  # tensor-parallel degree over the mesh
    # sequence-parallel degree: >1 runs whole-prompt prefill as ring attention
    # over an "sp" mesh axis (long-context path; decode is unaffected).
    # Composes with tp (each tp head shard runs its own sp ring on the
    # (sp, tp) mesh); not with pp.
    sp: int = 1
    # pipeline-parallel stages: >1 shards the layer stack (and its KV pages)
    # over a "pp" mesh axis and runs GPipe microbatch rotation for both
    # prefill and decode (dynamo_tpu/parallel/pipeline.py). Composes with tp
    # (Megatron head split inside each stage on the (pp, tp) mesh); not with
    # sp. Requires num_layers % pp == 0.
    pp: int = 1
    # weight-only quantization mode applied at model-load time:
    #   None      — serve at the model's native dtype (bf16)
    #   "int8_wo" — big linear weights stored int8 + per-output-channel f32
    #               scales, dequantized inside the matmul; embeddings /
    #               lm_head / norms / routers stay bf16. Halves the weight
    #               HBM stream the decode roofline is made of
    #               (dynamo_tpu/quant/int8.py).
    quantize: str | None = None
    # KV cache storage dtype:
    #   None / "bf16" — pages at the model's native dtype
    #   "int8"        — pages stored int8 with one f32 scale per (page,
    #                   token row) (dynamo_tpu/quant/kv.py): halves the
    #                   attention HBM stream on both kernel families, ~2x
    #                   pages at the same HBM budget, half the disagg wire /
    #                   host-offload bytes. Composes with `quantize` (weights
    #                   and cache quantize independently). Llama-family
    #                   pools only (MLA's latent cache raises); not yet
    #                   composable with pp (the stage-sharded pool split).
    kv_cache_dtype: str | None = None
    # speculative decoding (dynamo_tpu/spec/): verify k draft tokens plus
    # one bonus token in ONE multi-query forward pass, advancing 1..k+1
    # tokens per round with no quality change. Two proposer kinds:
    #   "ngram:k"            — prompt-lookup over the sequence's own history
    #                          (incremental suffix index; repetition-heavy
    #                          workloads only)
    #   "draft:<model>:<k>"  — a second, smaller registry model drafts k
    #                          tokens per round in one batched on-device
    #                          dispatch with its own paged KV pool; real
    #                          draft probabilities make temperature>0
    #                          acceptance the exact Leviathan/Chen rule.
    #                          The draft loads with this engine's quantize /
    #                          kv_cache_dtype (int8 weights + int8 KV
    #                          compose).
    # None = classic one-token decode. Requests with penalties, logprobs,
    # min_tokens, or images fall back to the classic decode windows
    # automatically.
    speculative: str | None = None
    # multi-LoRA multiplexing (dynamo_tpu/lora/): adapter specs served by
    # this engine as ``<base>:<name>`` model names. Each spec is ``name``
    # (deterministic synthetic adapter — tests/bench), ``name=<dir>`` (the
    # canonical npz layer-stacked format), or ``name=random:<seed>``.
    # Adapters load into device-resident stacked pools [L, max_loras+1, ...]
    # and a mixed-adapter batch decodes in ONE gathered dispatch
    # (y += scale * (x @ A[ids]) @ B[ids]; slot 0 = the zero adapter for
    # base-only lanes). Non-resident adapters load asynchronously (their
    # requests wait; everyone else keeps serving) and LRU-evict to host.
    # () = LoRA disabled (no pool, traces unchanged).
    lora_adapters: tuple = ()
    # device adapter slots (excluding the reserved zero slot): more adapters
    # than slots multiplex through LRU eviction/hot-swap
    max_loras: int = 4
    # pool rank: adapters with smaller r zero-pad (exact); larger r rejected
    lora_rank: int = 8
    # cross-process disaggregation data plane (dynamo_tpu/disagg/dataplane.py):
    # stream KV to the decode worker per finished prefill chunk (v2 multi-part
    # wire protocol) instead of one monolithic post-prefill send. Streaming
    # overlaps the D2H staging + socket transfer of chunk i with chunk i+1's
    # compute, so the decode side holds most KV bytes by the time the
    # completion notification lands. False = legacy single-payload send.
    kv_stream: bool = True
    # parallel data-plane connections per destination; parts stripe across
    # lanes so one long prompt's multi-MB parts never head-of-line-block
    # other requests' transfers behind a single per-destination socket
    kv_stream_lanes: int = 2
    # fleet-wide prefix cache (disagg/prefix_fetch.py): when the KV router
    # attaches a remote prefix holder to a request (kv_holder_addr/blocks),
    # pull the matching KV pages from that peer over the dataplane instead of
    # recomputing them. The sequence waits in a FETCHING_KV state bounded by
    # prefix_fetch_timeout_s; any failure (timeout, dead peer, "gone")
    # degrades to recompute — never an error to the client.
    prefix_fetch: bool = True
    prefix_fetch_timeout_s: float = 5.0
    # live sequence migration (disagg/migrate.py): this engine may hand its
    # in-flight sequences to a peer mid-decode (drain/rebalance) and adopt a
    # peer's. The committed KV rides the pull dataplane via the seq_handoff
    # kind; a failed handoff resumes locally / recomputes from history, so
    # migration is never worse than preempt+recompute. False = the engine
    # refuses adoptions and drain degrades to attrition (and a draining
    # frontend answers a retriable 503 instead).
    migration: bool = True
    # deadline belt on one handoff: the destination's KV pull AND the
    # source's wait for the destination's first continuation token are both
    # bounded by this — on expiry the source resumes decoding locally
    migration_timeout_s: float = 10.0
    # only fetch when the holder's advantage over the local prefix cache is at
    # least this many blocks (a one-block pull rarely beats its own overhead)
    prefix_fetch_min_blocks: int = 1
    # multi-tenant QoS (utils/qos.py): priority-class scheduling — admission
    # order by class, priority weights composed with the prefill fairness
    # cap, preemption victims lowest-class-first, and a waiting critical
    # request may evict a lower-class lane (preferring live migration over
    # preempt+recompute when a peer can adopt). False = classes ignored:
    # pure FIFO admission and recency-only victims (the pre-QoS behavior).
    qos: bool = True
    # how long a critical request must sit queued with no free slot before
    # the scheduler evicts a lower-class lane for it (the anti-thrash gate)
    qos_preempt_wait_ms: float = 250.0
    worker_id: str = "worker-0"
    # SLO targets (milliseconds; None = untargeted). With any target set the
    # engine attaches an SloTracker (utils/slo.py) to the scheduler: rolling
    # TTFT/queue-wait percentiles + error-budget gauges ride worker stats and
    # /metrics. The DYNTPU_SLO_TTFT_MS / DYNTPU_SLO_ITL_MS /
    # DYNTPU_SLO_QUEUE_WAIT_MS env knobs fill unset fields.
    slo_ttft_ms: float | None = None
    slo_itl_ms: float | None = None
    # fraction of pages that must stay free for decode growth before admitting
    # a new sequence (simple admission control)
    watermark: float = 0.05
    # host-DRAM KV offload tier capacity in blocks (0 = disabled)
    host_cache_blocks: int = 0
    # host-DRAM KV tier budget in BYTES (0 = unset): resolved to blocks at
    # engine init using the model's ACTUAL per-page wire cost
    # (model.kv_page_bytes — an int8 cache's host blocks are int8 pages +
    # scale planes, ~half the bf16 bytes, so the same DRAM budget holds ~2x
    # blocks). When both knobs are set the larger resolved capacity wins;
    # sizing by bytes is the one that stays truthful across kv_cache_dtype.
    host_cache_bytes: int = 0
    # disk KV tier budget in BYTES (0 = disabled; requires a host tier —
    # the ladder demotes HBM -> host -> disk, never skips a rung). Host-pool
    # LRU victims spill to disk int8-compressed (engine/kv_store.py), so a
    # disk byte holds ~2x the bf16 context; restores ride the FETCHING_KV
    # deferred-admission path and never block the engine loop.
    disk_cache_bytes: int = 0
    # where the disk tier's block files live ("" = the DYNTPU_KV_DISK_DIR
    # env var, else a fresh tempdir owned — and cleaned — by the store)
    disk_cache_dir: str = ""
    # pressure-driven host offload (host_cache_blocks > 0 only): once page-
    # pool occupancy crosses this fraction, the scheduler proactively drains
    # the coldest refcount-0 cached blocks to the host tier in BATCHED saves
    # (one device gather per batch) — keeping the free list ahead of decode
    # growth so long-running sequences hit batched restores instead of
    # per-block reclaim round trips or whole-sequence preempt+recompute.
    # >= 1.0 disables the proactive drain (reclaim still batches on demand).
    offload_watermark: float = 0.90
    offload_drain_batch: int = 32
    # decode steps fused into one device call (lax.scan over steps with the
    # sampled-token feedback kept on device); amortizes dispatch + host<->device
    # transfer overhead. 1 = classic one-step decode. The window is the unit
    # of admission: a request lies in the inbox for half a window, its prefill
    # waits behind the whole window that is running, and a finished sequence's
    # slot is dead until its window ends; streaming granularity and wasted
    # decode past EOS scale with it too. 4, not 8, by a rule fixed before the
    # runs (ISSUE 36: the smallest K of {2, 4} that costs no throughput,
    # TPOT or set-up, leaves the device under 0.1% idle and the engine thread
    # at most 20% host work in every benchmark cell). On the v5e K = 8 -> 4 ->
    # 2 read a median first token of 195 -> 106 -> 62 ms and a TPOT p95 of 15.2
    # -> 14.9 -> 14.7 ms in qwen2.5-3b.chat, 2242 -> 2339 -> 2243 tokens/s in
    # chat-over; 4 held all six lines, 2 fell on the idle line (the host comes
    # up to 12 ms late for nemotron3-super-ep4's 47 ms window: 0.49% idle).
    # PERF.md sections 5 and 6, PR 36; tests/test_prefill_pipeline.py pins it.
    decode_steps: int = 4
    # decode windows in flight ahead of result materialization (the token
    # feedback lives on device, so window N+1 never waits for window N's
    # tokens to reach the host). 2 = double buffering: the window that runs
    # and one that waits, which is all it takes to hide the host's refill (a
    # tenth of a 4-step window). Every further window stands ahead of each
    # new prompt's prefill on the device's FIFO queue and holds a finished
    # sequence's slot one window longer; a third bought no throughput
    # (PERF.md, PR 32). 1 = fully synchronous.
    pipeline_depth: int = 2
    # cross-request prefill packing: chunks of several sequences ride one
    # prefill call (one weight pass). 1 = disabled (per-request prefill). For
    # a model with recurrent layers it is also the most lanes of a pack (each
    # a whole chunk, row-budgeted per bucket by lanes_for()); every other
    # model's pack is made of blocks (prefill_block, pack_blocks) and takes
    # no count from here.
    prefill_lanes: int = 4
    # packed prefill calls dispatched ahead of result materialization (the
    # prefill analogue of pipeline_depth): call N+1's host prep + dispatch
    # overlap call N's device time, so the per-call fixed cost
    # (StepAnatomy.prefill_fixed_ms) stops serializing with the kernel. 1 =
    # strict reconcile-before-next-dispatch — the old behavior in the mixed
    # decode+prefill regime (tests/test_prefill_pipeline.py compares both).
    prefill_pipeline_depth: int = 2
    # admission fairness: at most this many (packed) prefill calls dispatch
    # per scheduler step before decode windows get the chip again. A request
    # burst otherwise serializes ALL its prefill passes ahead of any decode
    # window, stalling every running stream's ITL for the whole burst (and
    # the burst's own later requests gain nothing — their prefills still
    # queue). 0 = unbounded (pre-r5 behavior).
    prefill_batches_per_step: int = 2
    # cost attribution (utils/metering.py): per-(tenant, adapter, priority)
    # device-seconds at the step-anatomy seams + per-tenant KV byte-seconds
    # on every tier's allocate/free/demote/restore edges, conservation-
    # checked against the anatomy wall totals and the pool-occupancy
    # integrals. False = no MeterLedger anywhere: every hook is a
    # `meter is None` check, so the off path adds zero work per dispatch.
    metering: bool = True
    # pre-compile trace variants at startup so the first feature-bearing
    # request never hits a cold multi-second XLA compile mid-serving.
    #   False        — lazy (tests, short-lived engines)
    #   True         — everything blocking before start() returns
    #   "background" — core traces (default window + every bucket) blocking,
    #                  feature variants (logprobs/penalties) compiled between
    #                  serving steps after startup: first deploy of a new
    #                  geometry reaches readiness in roughly half the cold
    #                  compile time
    warmup: bool | str = False

    def __post_init__(self) -> None:
        if not isinstance(self.warmup, bool) and self.warmup != "background":
            # any other string would silently degrade to the FULL blocking
            # warmup (truthy), the opposite of what a typo'd "bg" intended
            raise ValueError(
                f"warmup must be True, False, or 'background'; got {self.warmup!r}"
            )
        if self.quantize is not None:
            from dynamo_tpu.quant import QUANT_MODES

            if self.quantize not in QUANT_MODES:
                raise ValueError(
                    f"quantize must be None or one of {QUANT_MODES}; got {self.quantize!r}"
                )
        if self.prefix_fetch_timeout_s <= 0:
            raise ValueError(
                f"prefix_fetch_timeout_s must be > 0; got {self.prefix_fetch_timeout_s}"
            )
        if self.migration_timeout_s <= 0:
            raise ValueError(
                f"migration_timeout_s must be > 0; got {self.migration_timeout_s}"
            )
        if self.qos_preempt_wait_ms < 0:
            raise ValueError(
                f"qos_preempt_wait_ms must be >= 0; got {self.qos_preempt_wait_ms}"
            )
        if self.kv_stream_lanes < 1:
            raise ValueError(
                f"kv_stream_lanes must be >= 1; got {self.kv_stream_lanes}"
            )
        if self.prefill_pipeline_depth < 1:
            raise ValueError(
                f"prefill_pipeline_depth must be >= 1; "
                f"got {self.prefill_pipeline_depth}"
            )
        if self.kv_cache_dtype is not None:
            from dynamo_tpu.quant import KV_CACHE_DTYPES

            if self.kv_cache_dtype not in KV_CACHE_DTYPES:
                raise ValueError(
                    f"kv_cache_dtype must be None or one of {KV_CACHE_DTYPES}; "
                    f"got {self.kv_cache_dtype!r}"
                )
            if self.kv_cache_dtype == "int8" and self.pp > 1:
                # the stage-sharded pool split (parallel/pipeline.py) has no
                # QuantizedPages wiring yet; fail at config time
                raise ValueError("kv_cache_dtype='int8' does not compose with pp > 1 yet")
        if self.offload_drain_batch < 1:
            raise ValueError(
                f"offload_drain_batch must be >= 1; got {self.offload_drain_batch}"
            )
        if self.host_cache_bytes < 0 or self.host_cache_blocks < 0:
            raise ValueError(
                "host cache capacity must be >= 0; got "
                f"blocks={self.host_cache_blocks} bytes={self.host_cache_bytes}"
            )
        if self.disk_cache_bytes < 0:
            raise ValueError(
                f"disk_cache_bytes must be >= 0; got {self.disk_cache_bytes}"
            )
        if self.disk_cache_bytes > 0 and not (
            self.host_cache_blocks > 0 or self.host_cache_bytes > 0
        ):
            raise ValueError(
                "disk_cache_bytes requires a host cache tier "
                "(host_cache_blocks or host_cache_bytes > 0): the KV ladder "
                "demotes HBM -> host -> disk and never skips a rung"
            )
        if any(b <= 0 for b in self.page_table_buckets):
            raise ValueError(
                f"page_table_buckets must be positive; got {self.page_table_buckets}"
            )
        if self.lora_adapters:
            if isinstance(self.lora_adapters, str):
                # yaml/CLI comma form normalizes here so every consumer sees
                # a tuple of specs
                self.lora_adapters = tuple(
                    s.strip() for s in self.lora_adapters.split(",") if s.strip()
                )
            else:
                self.lora_adapters = tuple(self.lora_adapters)
            if self.max_loras < 1:
                raise ValueError(f"max_loras must be >= 1; got {self.max_loras}")
            if self.lora_rank < 1:
                raise ValueError(f"lora_rank must be >= 1; got {self.lora_rank}")
            if self.pp > 1:
                # the pipeline shard_map's explicit _layer path has no LoRA
                # threading yet; fail at config time
                raise ValueError("lora_adapters do not compose with pp > 1 yet")
            from dynamo_tpu.lora.adapter import parse_adapter_specs

            parse_adapter_specs(self.lora_adapters)  # bad specs fail HERE
        # a bad speculative spec must fail at config time, not mid-serving
        self.spec  # noqa: B018 — parse_speculative raises on invalid input

    @property
    def spec(self):
        """Parsed SpecConfig for ``speculative`` (None when disabled)."""
        from dynamo_tpu.spec import parse_speculative

        return parse_speculative(self.speculative)

    @property
    def kv_quantized(self) -> bool:
        return self.kv_cache_dtype == "int8"

    @property
    def lora_enabled(self) -> bool:
        return bool(self.lora_adapters)

    @property
    def max_pages_per_seq(self) -> int:
        return -(-self.max_model_len // self.page_size)

    @property
    def table_buckets(self) -> tuple:
        """Resolved page-table width ladder (ascending, last ==
        max_pages_per_seq). Explicit ``page_table_buckets`` entries clamp to
        the dense width; auto mode doubles from min(128, max_pages_per_seq),
        which degenerates to the single dense width for short contexts."""
        mp = self.max_pages_per_seq
        if self.page_table_buckets:
            ladder = sorted({min(int(b), mp) for b in self.page_table_buckets if b > 0})
            if not ladder or ladder[-1] != mp:
                ladder.append(mp)
            return tuple(ladder)
        widths = []
        w = min(128, mp)
        while w < mp:
            widths.append(w)
            w *= 2
        widths.append(mp)
        return tuple(widths)

    def table_bucket_for(self, n_pages: int) -> int:
        """Smallest ladder width holding ``n_pages`` page-table entries."""
        for w in self.table_buckets:
            if n_pages <= w:
                return w
        raise ValueError(
            f"{n_pages} pages exceed max_pages_per_seq {self.max_pages_per_seq}"
        )

    @property
    def max_prefill_chunk(self) -> int:
        return max(self.prefill_buckets)

    def chunk_len_for(self, depth: int, backlog_rows: int = 0) -> int:
        """Depth-aware prefill chunk bucket for a chunk starting at context
        ``depth`` tokens: the largest bucket b with b * (depth + b) within
        the flat-depth work budget, floored at the smallest bucket — so
        per-chunk latency stays roughly flat as prefill advances into a long
        prompt instead of growing linearly with context.

        ``backlog_rows`` (total un-prefilled rows pending across sequences)
        promotes the bucket under a deep backlog by doubling the work
        budget: every dispatch pays the same fixed per-call cost, so when
        far more work is queued than one flat-latency chunk, fewer, larger
        dispatches win — the chunk-latency flatness the shrink buys is moot
        while the backlog itself dominates any single stream's TTFT."""
        top = self.max_prefill_chunk
        if self.prefill_flat_depth <= 0:
            return top
        budget = top * max(self.prefill_flat_depth, top)
        if backlog_rows >= 2 * top:
            budget *= 2
        best = min(self.prefill_buckets)
        for b in self.prefill_buckets:
            if b * (depth + b) <= budget:
                best = max(best, b)
        return best

    def lanes_for(self, bucket: int, wide: bool = False) -> int:
        """Packed-prefill lane count for a bucket, on the path that keeps the
        rectangle (a model with recurrent layers) and in warm-up: bounded by
        prefill_lanes and a 1024-row budget. On a v5e one `qwen2.5-3b` pack
        (tools/profile_prefill_pack.py, PR 40, best of 5 in one jit chain)
        costs 10.4 / 11.9 ms at 1 / 2 blocks of 128 rows whatever it holds
        (the weights' stream), then about 5 ms a block: 16.9, 21.9, 26.9,
        31.8, 36.7, 40.2 ms at 3 to 8 blocks; a rectangle costs what its rows
        cost as blocks ([1,512] 23.0, [2,256] 21.9, [2,512] 40.9, [4,256]
        41.2). A row is 42.7 us in a pack of 512 and 39.2 in one of 1024, so
        packing pays up to the budget; more rows a call lengthen the stall a
        decode stream sees.

        ``wide``: the pack holds a lane whose page table is beyond the first
        rung of the ladder. Such a pack takes two lanes at most (PR 44). Every
        (lanes, bucket, rung) is a program of its own, a sequence that deep
        runs chunks of the largest bucket, two a pack, until its tail, and
        four short chunks of which one is a deep sequence's tail come
        together about once in ten windows of `lfm2-8b-a1b-d16.rag-over`
        (`PERF.md` section 6): rare enough that no warm-up traffic meets
        them, often enough that one run in some dozens compiled such a
        program in mid-traffic. Two packs of two cost 2.6 ms more than one of
        four by the figures above; three programs fewer a wider rung are
        10 s of every start and 60 s of a first one."""
        lanes = max(1, min(self.prefill_lanes, 1024 // bucket))
        return min(lanes, 2) if wide else lanes

    @property
    def prefill_block(self) -> int:
        """Rows of one block of a packed prefill (a model with no recurrent
        layers: `Scheduler._dispatch_prefill_batches`): the smallest bucket
        that is a multiple of 128, the flash kernels' query block, else (the
        tiny test models' `(16, 32)`) the smallest bucket. Derived, not
        configured."""
        whole = [b for b in self.prefill_buckets if b % 128 == 0]
        return min(whole or self.prefill_buckets)

    @property
    def pack_blocks(self) -> int:
        """Blocks a packed prefill holds at most: 8, inside the 1024 rows
        `lanes_for` allows a rectangle."""
        return max(1, min(8, 1024 // self.prefill_block))

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= n (n must be <= max bucket)."""
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"chunk {n} exceeds max prefill bucket {self.max_prefill_chunk}")

    @classmethod
    def for_model(cls, model_id: str | None, **overrides) -> "EngineConfig":
        return cls(model_id=model_id or "tiny", **overrides)
