"""AsyncJaxEngine: asyncio facade over the engine step loop.

The step loop runs on a dedicated thread (JAX dispatch blocks); results cross
back via loop.call_soon_threadsafe into per-request asyncio queues. This is the
native analogue of the reference's engine subprocess + ZMQ output loop
(reference: lib/llm/src/engines/vllm/worker.rs _output_loop) with the process
boundary removed.
"""

from __future__ import annotations

import asyncio
import queue as thread_queue
import threading
import time

import numpy as np
from dataclasses import dataclass
from typing import AsyncIterator, Callable, Optional

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.page_table import GroupedPageAllocator, PageAllocator
from dynamo_tpu.engine.scheduler import EngineRequest, Scheduler, StepOutput
from dynamo_tpu.llm.kv_events import KvCacheEvent
from dynamo_tpu.ops.attention import decode_tile_of
from dynamo_tpu.runtime.context import current_context
from dynamo_tpu.utils import events, get_logger, tracing
from dynamo_tpu.utils.goodput import GoodputTracker
from dynamo_tpu.utils.health import HealthMonitor
from dynamo_tpu.utils.prometheus import Histogram
from dynamo_tpu.utils.slo import SloTracker, targets_from_env

log = get_logger("engine")

# engine-loop watchdog cadence: cheap checks, no need to run per step
_WATCHDOG_INTERVAL_S = 1.0

# migration pause (freeze -> first continuation token): localhost handoffs
# are tens of ms; a cross-host pull of a deep sequence reaches seconds
_MIGRATION_PAUSE_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                            1.0, 2.5, 5.0, 10.0, 30.0)


def _resolve(fut: asyncio.Future, result, exc) -> None:
    if fut.done():
        return
    if exc is not None:
        fut.set_exception(exc)
    else:
        fut.set_result(result)


@dataclass
class ForwardPassMetrics:
    """Worker load metrics for the KV router
    (reference: lib/llm/src/kv_router/protocols.rs:19-33)."""

    request_active_slots: int = 0
    request_total_slots: int = 0
    kv_active_blocks: int = 0
    kv_total_blocks: int = 0
    num_requests_waiting: int = 0
    gpu_cache_usage_perc: float = 0.0  # name kept for wire compat; TPU HBM here
    gpu_prefix_cache_hit_rate: float = 0.0

    def to_wire(self) -> dict:
        return self.__dict__.copy()


class AsyncJaxEngine:
    """Tokens-in/tokens-out streaming engine (the ExecutionContext contract)."""

    def __init__(self, config: EngineConfig, kv_event_sink: Optional[Callable[[KvCacheEvent], None]] = None):
        self.config = config
        self._extra_kv_sink = kv_event_sink
        self._kv_events: list[KvCacheEvent] = []
        self._inbox: thread_queue.Queue = thread_queue.Queue()
        self._cancel_box: thread_queue.Queue = thread_queue.Queue()
        self._cmd_box: thread_queue.Queue = thread_queue.Queue()
        self._outputs: dict[str, asyncio.Queue] = {}
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._stopping = threading.Event()
        self._started = False
        self.scheduler: Optional[Scheduler] = None
        self.allocator: Optional[PageAllocator] = None
        self.runner = None
        self.model = None
        self.step_count = 0
        # fleet health plane: lifecycle state + engine-loop heartbeats +
        # stuck-request watchdog (utils/health.py); rolling SLO percentiles
        # for queue-wait/TTFT (utils/slo.py, attached to the scheduler)
        self.health = HealthMonitor("engine")
        self.slo = SloTracker(
            targets_from_env({"ttft": config.slo_ttft_ms, "itl": config.slo_itl_ms})
        )
        # goodput plane (utils/goodput.py): every naturally-finished request
        # emits one RequestOutcome from the scheduler; budgets default to the
        # engine's SLO targets (untargeted engines still count errors)
        self.goodput = GoodputTracker(
            ttft_budget_s=self.slo.targets.get("ttft"),
            itl_budget_s=self.slo.targets.get("itl"),
        )
        # cost-attribution plane (utils/metering.py): ONE ledger per engine —
        # the scheduler's dispatch bills, every KV tier's residency edges,
        # and the queued/admitted/consumed token charges all post here.
        # None when config.metering is off: every hook degrades to a
        # `meter is None` check (the zero-cost path the tests pin).
        if config.metering:
            from dynamo_tpu.utils.metering import MeterLedger

            self.meter = MeterLedger()
        else:
            self.meter = None
        # multi-tenant QoS (utils/qos.py): measured queue-drain rate — every
        # finished request feeds it via the outcome sink, and both retriable
        # status paths (draining 503, backpressure 429) price Retry-After
        # from it instead of a constant
        from dynamo_tpu.utils.qos import DrainRateEstimator

        self.drain_estimator = DrainRateEstimator()
        self._next_watchdog = 0.0
        # fleet-wide prefix cache (disagg/prefix_fetch.py): the pull client
        # the scheduler fetches remote prefixes with, and the export server
        # peers pull OUR prefixes from — both attached by the hosting worker
        self.prefix_fetcher = None
        self.kv_pull_server = None
        # live migration (disagg/migrate.py): pause = freeze -> the
        # destination's first continuation token reaches the client stream
        self.migration_pause_hist = Histogram(
            "dynamo_migration_pause_seconds",
            "client-visible stream pause of one live migration, sequence "
            "freeze to the destination's first relayed token",
            _MIGRATION_PAUSE_BUCKETS,
        )

    # ---------------- lifecycle ----------------

    async def start(self) -> None:
        if self._started:
            return
        self._loop = asyncio.get_running_loop()
        await self._loop.run_in_executor(None, self._initialize)
        self._thread = threading.Thread(target=self._run_loop, name="engine-loop", daemon=True)
        self._thread.start()
        self._started = True
        if self.config.warmup == "background":
            self._warmup_task = asyncio.create_task(self._background_warmup())

    async def _background_warmup(self) -> None:
        """Compile the feature trace variants on the engine thread, one per
        idle gap: each thunk runs via run_on_engine (the thread that owns the
        donated state), and we yield to live traffic between thunks so a
        request arriving mid-warmup waits for at most one compile."""
        for thunk in self.runner.warmup_extra_thunks():
            while self.scheduler is not None and self.scheduler.has_work():
                await asyncio.sleep(0.05)
            if self._stopping.is_set():
                return
            try:
                await self.run_on_engine(thunk)
            except asyncio.CancelledError:
                raise
            except Exception:
                # one failed variant compile must not kill serving OR abandon
                # the remaining variants; this one will lazily compile (with
                # a stall) if traffic ever needs it
                log.exception("background warmup variant failed; continuing")
        log.info("background warmup: trace variants compiled")

    def _initialize(self) -> None:
        from dynamo_tpu.engine.model_runner import ModelRunner
        from dynamo_tpu.models.registry import load_model

        t0 = time.monotonic()
        self.model, params = load_model(
            self.config.model_id, quantize=self.config.quantize,
            kv_cache_dtype=self.config.kv_cache_dtype,
        )
        self.runner = ModelRunner(self.config, self.model, params)
        if self.runner.recurrent and self.config.migration:
            # refused, not an error: migration is on by default, and a peer
            # could adopt this model's pages but not its per-slot state
            log.warning(
                "live migration is refused for %s: a sequence's recurrent "
                "state has no wire form yet; drain degrades to attrition",
                type(self.model).__name__,
            )
            self.config.migration = False
        groups = self.model.layer_groups
        if groups and (self.config.migration or self.config.prefix_fetch):
            # refused, not an error: both are on by default, and both move a
            # block between engines as ONE page id for every layer
            log.warning(
                "live migration and the fleet prefix fetch are refused for %s: "
                "its layers come in groups with a page table each, and the "
                "transfer paths carry one table", type(self.model).__name__,
            )
            self.config.migration = False
            self.config.prefix_fetch = False
        offload = None
        if self.config.host_cache_blocks > 0 or self.config.host_cache_bytes > 0:
            from dynamo_tpu.engine.offload import (
                HostKvPool,
                resolve_host_capacity_blocks,
            )

            # byte budgets resolve at the model's ACTUAL per-page wire cost
            # (int8 host blocks are ~half the bf16 bytes -> ~2x blocks for
            # the same DRAM budget); the drain watermarks then operate on a
            # truthful block capacity
            page_bytes = self.model.kv_page_bytes(self.config.page_size)
            blocks = resolve_host_capacity_blocks(
                self.config.host_cache_blocks,
                # a model that prices its page at 0 (deepseek) can't honor a
                # byte budget — fall back to the explicit block knob only
                self.config.host_cache_bytes if page_bytes else 0,
                page_bytes,
            )
            if blocks > 0:
                offload = HostKvPool(self.runner, blocks, block_bytes=page_bytes)
        if offload is not None and self.config.disk_cache_bytes > 0:
            # third tier: host-pool LRU victims demote to disk (int8 wire,
            # xxh3-checksummed files) instead of dropping; restores ride the
            # FETCHING_KV deferred-admission path (engine/kv_store.py)
            from dynamo_tpu.engine.kv_store import DiskKvStore, disk_block_bytes

            mcfg = self.model.config
            block_bytes = (
                disk_block_bytes(
                    self.config.page_size, mcfg.num_kv_heads, mcfg.head_dim,
                    mcfg.num_layers,
                )
                if all(hasattr(mcfg, a) for a in ("num_kv_heads", "head_dim", "num_layers"))
                else 0
            )
            offload.disk = DiskKvStore(
                directory=self.config.disk_cache_dir or None,
                budget_bytes=self.config.disk_cache_bytes,
                page_axis=self.model.wire_n_axis,
                block_bytes=block_bytes,
            )
        self.offload = offload
        if groups:
            self.allocator = GroupedPageAllocator(
                self.config.num_pages, self.config.page_size, groups,
                event_sink=self._on_kv_event,
            )
        else:
            # a sequence grows by runs of the tile the decode kernel walks
            # its pages by, derived from the pools' shapes as the kernel does
            k_pool = self.runner.kv_cache.get("k")
            head_dim = getattr(self.model.config, "head_dim", 0)
            known = k_pool is not None and head_dim  # else: single pages, as ever
            self.allocator = PageAllocator(
                self.config.num_pages,
                self.config.page_size,
                event_sink=self._on_kv_event,
                offload=offload,
                match_prefix=not self.runner.recurrent,
                tile_pages=decode_tile_of(k_pool, head_dim, max(1, self.config.tp)) if known else 1,
            )
        self.scheduler = Scheduler(self.config, self.runner, self.allocator)
        self.scheduler.slo = self.slo
        self.scheduler.outcome_sink = self._observe_outcome
        self.scheduler.prefix_fetcher = self.prefix_fetcher
        if self.meter is not None:
            # wire the cost ledger into every plane that generates charges:
            # anatomy phases split across dispatch bill rows, HBM pages price
            # at the model's actual per-page wire cost, and the host/disk
            # tiers meter their own residency edges
            self.scheduler.meter = self.meter
            self.scheduler.anatomy.meter = self.meter
            self.allocator.meter = self.meter
            self.allocator.meter_page_bytes = self.model.kv_page_bytes(self.config.page_size)
            if offload is not None:
                offload.meter = self.meter
                if offload.disk is not None:
                    offload.disk.meter = self.meter
        if self.config.warmup == "background":
            # readiness waits only for the traces first requests need; the
            # feature variants (logprobs/penalties, extras prefill) compile
            # between serving steps via run_on_engine — see start()
            self.runner.warmup_core()
        elif self.config.warmup:
            self.runner.warmup()
        log.info(
            "engine ready: model=%s quantize=%s kv_dtype=%s tp=%d pp=%d sp=%d pages=%d device=%s (%.1fs)",
            self.config.model_id,
            self.config.quantize or "none",
            self.config.kv_cache_dtype or "bf16",
            self.config.tp,
            self.config.pp,
            self.config.sp,
            self.config.num_pages,
            self.device_info(),
            time.monotonic() - t0,
        )
        self.health.set_state("ready", "engine initialized")

    def device_info(self) -> dict:
        """The device this engine runs on, as JAX reports it: platform, kind
        and count of what the process sees, plus the ids its mesh uses (the
        colocated frontend's /ready carries this; chip_smoke.py reports it)."""
        import os

        import jax

        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "mesh_device_ids": [int(d.id) for d in self.runner.mesh.devices.flat],
            "visible": os.environ.get("TPU_VISIBLE_DEVICES"),
        }

    async def shutdown(self, join_timeout: float = 120.0) -> None:
        self.health.set_state("draining", "shutdown requested")
        self._stopping.set()
        task = getattr(self, "_warmup_task", None)
        if task is not None and not task.done():
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
        if self._thread is not None:
            await asyncio.get_running_loop().run_in_executor(
                None, lambda: self._thread.join(join_timeout)
            )
            if self._thread.is_alive():
                # the loop thread is wedged (a hung device op): it's a
                # daemon thread, so give up on it rather than hanging the
                # caller's teardown forever
                log.error("engine loop did not exit within %.0fs; abandoning thread", join_timeout)
        disk = getattr(getattr(self, "offload", None), "disk", None)
        if disk is not None:
            # drain the disk tier's write queue and stop its worker (a store
            # that owns its tempdir also cleans it up)
            await asyncio.get_running_loop().run_in_executor(None, disk.close)
        self.health.set_state("dead", "shutdown complete")

    # ---------------- request API ----------------

    async def generate(self, request: EngineRequest) -> AsyncIterator[StepOutput]:
        """Submit a request; yields StepOutputs until finished."""
        async for batch in self.generate_batched(request):
            for item in batch:
                yield item

    async def generate_batched(self, request: EngineRequest) -> AsyncIterator[list[StepOutput]]:
        """Submit a request; yields LISTS of StepOutputs (one list per decode
        window arrival). The engine loop reconciles decode_steps tokens per
        window, so batching here collapses the per-token thread crossings,
        detokenizer calls, and SSE writes that dominated the serving-stack
        overhead (reference's HTTP frontend is an explicitly thin layer:
        lib/llm/src/http/service/openai.rs:132-214)."""
        self._stamp_submission(request)
        self._register_stream(request.request_id)
        self._inbox.put(request)
        async for batch in self._drain_stream_batched(request.request_id):
            yield batch

    @staticmethod
    def _stamp_submission(request: EngineRequest) -> None:
        """Observability stamps at the engine boundary: submission time (the
        queue-wait/TTFT zero point) and the edge trace id the engine thread's
        spans stitch to (the engine loop runs outside the request context)."""
        if not request.enqueue_ts:
            request.enqueue_ts = time.monotonic()
        if request.trace_id is None:
            ctx = current_context()
            if ctx is not None:
                request.trace_id = ctx.trace_id
        events.emit(
            "request.enqueued",
            request_id=request.request_id, trace_id=request.trace_id,
            tenant=request.tenant, priority=request.priority or "",
            prompt_tokens=len(request.token_ids),
        )

    def _register_stream(self, request_id: str) -> None:
        """Open the output channel for a request without scheduling it (the
        disagg decode path schedules via adoption instead)."""
        if not self._started:
            raise RuntimeError("engine not started")
        out_q: asyncio.Queue = asyncio.Queue()
        # Capture the caller's loop per request: generate() may be called from a
        # different event loop than start() (each call_soon_threadsafe must
        # target the loop that owns the queue).
        self._outputs[request_id] = (asyncio.get_running_loop(), out_q)

    async def _drain_stream(self, request_id: str) -> AsyncIterator[StepOutput]:
        async for batch in self._drain_stream_batched(request_id):
            for item in batch:
                yield item

    async def _drain_stream_batched(self, request_id: str) -> AsyncIterator[list[StepOutput]]:
        """Queue items are single StepOutputs or lists of them (one decode
        window's tokens for this request, posted in one thread crossing)."""
        _, out_q = self._outputs[request_id]
        try:
            while True:
                item = await out_q.get()
                if isinstance(item, Exception):
                    raise item
                batch = item if isinstance(item, list) else [item]
                done = False
                for i, o in enumerate(batch):
                    if o.finished:  # belt: nothing rides past a finish
                        batch, done = batch[: i + 1], True
                        break
                yield batch
                if done:
                    return
        finally:
            self._outputs.pop(request_id, None)
            self._cancel_box.put(request_id)

    async def run_on_engine(self, fn):
        """Run fn() on the engine thread (it owns the KV cache/allocator/
        scheduler). fn may return (value, [StepOutput...]) to also emit stream
        items; returns the value."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._cmd_box.put((fn, loop, fut))
        return await fut

    # ---------------- disaggregation (run via run_on_engine) ----------------
    # The decode side allocates pages and adopts; the prefill side computes KV
    # in its own cache and extracts blocks. See dynamo_tpu/disagg/.

    def transfer_refusal(self) -> Optional[str]:
        """Why this engine's pages cannot be moved to or from another engine
        (disaggregated prefill, prefix pulls, migration), or None. The disagg
        workers ask at their start-up; the entry points below ask again."""
        if self.model.layer_groups:
            return (f"{type(self.model).__name__}: its attention layers come in groups "
                    "with a page table each, and the transfer paths carry one table "
                    "(disaggregated prefill, prefix pulls and migration are refused)")
        return None

    def _refuse_transfer(self) -> None:
        why = self.transfer_refusal()
        if why:
            raise ValueError(why)

    def sync_lookup_prefix(self, token_ids: list[int], salt: int = 0) -> int:
        return self.allocator.lookup_prefix(token_ids, salt=salt)

    def attach_prefix_fetch(self, fetcher) -> None:
        """Wire the fleet prefix-cache pull client into the scheduler (safe
        before or after start — _initialize copies it through)."""
        self.prefix_fetcher = fetcher
        if self.scheduler is not None:
            self.scheduler.prefix_fetcher = fetcher

    def sync_export_prefix(self, hashes: list[int]):
        """Engine thread: serve a peer's prefix pull (disagg/prefix_fetch.py
        KvPullServer). Walks the contiguous leading run of the requested
        chained block hashes down the tier ladder — HBM pages (the device
        gather is dispatched HERE, atomically with the lookup, so a later
        scatter can't reuse a page before the gather captured it; XLA orders
        the buffers), then host-pool blocks. Returns ``(n_dev_blocks,
        dev_host_future_or_None, host_blocks, cat_axis)``; None = leading
        block in no tier (the server answers with a clean "gone")."""
        self._refuse_transfer()
        alloc, runner = self.allocator, self.runner
        if alloc is None or runner is None:
            return None
        pages: list[int] = []
        for h in hashes:
            page = alloc.cached_page(h)
            if page is None:
                break
            pages.append(page)
        host_blocks: list = []
        offload = getattr(self, "offload", None)
        if offload is not None:
            for h in hashes[len(pages):]:
                data = offload.peek(h)
                if data is None:
                    break
                host_blocks.append(data)
        if not pages and not host_blocks:
            return None
        fut = (
            runner.extract_pages_async(np.asarray(pages, np.int32))
            if pages
            else None
        )
        axis = runner.model.wire_n_axis
        return len(pages), fut, host_blocks, axis

    # ---------------- live migration (disagg/migrate.py) ----------------
    # MIGRATING_OUT: the source freezes a sequence, ships its manifest, and
    # relays the destination's continuation tokens into the original output
    # stream. ADOPTING: the destination re-enters the sequence through
    # normal admission, pulling committed KV via the seq_handoff fetch kind
    # (FETCHING_KV) with chunked recompute from history as the fallback.

    def sync_export_sequence(self, seq_id: str, hashes: list[int]):
        """Engine thread: serve a ``seq_handoff`` pull — the named LIVE
        sequence's own page run for the requested chained hashes. Unlike
        ``sync_export_prefix`` this walks the sequence's pages directly, so
        decode-written blocks whose cache registration deduped onto another
        sequence's page still export OUR copy; a sequence already released
        (source raced ahead) falls back to the shared prefix cache, which
        usually still holds the committed blocks."""
        self._refuse_transfer()
        alloc, runner = self.allocator, self.runner
        if alloc is None or runner is None or not hashes:
            return None
        state = alloc._seqs.get(seq_id)
        if state is None or state.token_seq is None:
            return self.sync_export_prefix(hashes)
        chain = [b.sequence_hash for b in state.token_seq.blocks]
        try:
            start = chain.index(hashes[0])
        except ValueError:
            # the requested run is not in this sequence's chain (destination
            # cached a different leading run): the prefix cache may still
            # resolve it
            return self.sync_export_prefix(hashes)
        pages: list[int] = []
        for i, h in enumerate(hashes):
            j = start + i
            if j >= len(chain) or chain[j] != h or j >= len(state.pages):
                break
            pages.append(state.pages[j])
        if not pages:
            return None
        fut = runner.extract_pages_async(np.asarray(pages, np.int32))
        axis = runner.model.wire_n_axis
        return len(pages), fut, [], axis

    def sync_snapshot_for_migration(self, request_id: str):
        """Engine thread: freeze one in-flight decode sequence
        (MIGRATING_OUT) and build its authoritative manifest. Returns
        ``(manifest_or_None, drained_outputs)``; None = not migratable right
        now (unknown/finished/already migrating/still prefilling/fetching/
        multimodal) — including the double-migration race, where the second
        caller simply gets None."""
        sched = self.scheduler
        seq = next(
            (s for s in sched.slots
             if s is not None and s.req.request_id == request_id),
            None,
        )
        if (
            seq is None or seq.finished or seq.migrating
            or seq.prefill_pos is not None or seq.fetch is not None
            or not seq.generated
        ):
            return None, []
        if seq.req.images:
            # multimodal sequences don't migrate: mm_embeds (device-resident
            # vision encodings) don't ride the ~1KB manifest, and a silent
            # handoff would rebuild the prompt WITHOUT them on any KV-pull
            # miss — wrong tokens, not a slow path. Reject structurally so
            # the caller (and the planner's rebalancer) can pick another
            # victim instead of reading "not migratable right now".
            return "multimodal", []
        # drain the dispatch-ahead pipeline: seq.generated must be the
        # complete materialized history before it becomes the manifest
        outputs = sched._reconcile(block=True, drain=True)
        if seq.finished:
            return None, outputs  # EOS/length landed during the drain
        seq.migrating = True
        events.emit(
            "migration.freeze",
            request_id=seq.req.request_id, trace_id=seq.req.trace_id,
            tenant=seq.req.tenant, priority=seq.req.priority or "",
            generated=len(seq.generated),
        )
        return self._build_manifest(seq), outputs

    def _build_manifest(self, seq):
        import dataclasses

        from dynamo_tpu.disagg.migrate import SequenceManifest

        req = seq.req
        ps = self.config.page_size
        hist_len = seq.prompt_len + len(seq.generated)
        state = self.allocator._seqs.get(req.request_id)
        kv_blocks = 0
        if state is not None and state.token_seq is not None:
            # exportable = full blocks whose KV is complete (the newest
            # token's KV is not written — it is the next decode input)
            kv_blocks = min(
                (hist_len - 1) // ps,
                len(state.token_seq.blocks),
                len(state.pages),
            )
        addr = self.kv_pull_server.address if self.kv_pull_server is not None else ""
        age = (
            max(0.0, time.monotonic() - req.enqueue_ts) if req.enqueue_ts else 0.0
        )
        return SequenceManifest(
            request_id=req.request_id,
            prompt_tokens=list(req.token_ids),
            generated=list(seq.generated),
            sampling=dataclasses.asdict(req.sampling),
            eos_token_ids=list(req.eos_token_ids),
            lora_name=req.lora_name,
            logprobs=req.logprobs,
            penalty_output_from=(
                req.penalty_output_from
                if req.penalty_output_from is not None
                else seq.prompt_len
            ),
            trace_id=req.trace_id,
            tenant=req.tenant,
            scenario=req.scenario,
            priority=req.priority,
            source_addr=addr if kv_blocks > 0 else "",
            kv_blocks=kv_blocks,
            age_s=age,
        )

    def sync_commit_migration(self, request_id: str):
        """Engine thread: the destination's continuation is live — release
        the frozen local sequence WITHOUT a finish output or a goodput
        outcome (the destination records the request's one outcome).
        Returns False when the sequence already ended locally (cancel/EOS
        raced the handoff) — the caller must drop the destination stream."""
        sched = self.scheduler
        seq = next(
            (s for s in sched.slots
             if s is not None and s.req.request_id == request_id),
            None,
        )
        if seq is None or seq.finished or not seq.migrating:
            return False, []
        sched._release(seq, count_finished=False)
        return True, []

    def sync_abort_migration(self, request_id: str):
        """Engine thread: the handoff failed before any continuation token —
        un-freeze the sequence so local decode resumes (never worse than
        preempt+recompute; here not even that)."""
        sched = self.scheduler
        seq = next(
            (s for s in sched.slots
             if s is not None and s.req.request_id == request_id),
            None,
        )
        if seq is None or seq.finished or not seq.migrating:
            return False, []
        seq.migrating = False
        return True, []

    def sync_resume_migration(self, manifest, relayed: list):
        """Engine thread: the destination died AFTER continuation tokens
        were already relayed to the client — requeue a preempt-style resume
        request over history + relayed tokens, so the stream continues
        locally, token-identically (the prefix cache usually still holds
        the committed blocks)."""
        req = manifest.to_resume_request(list(relayed), time.monotonic())
        self.scheduler.waiting.appendleft(req)
        return True, []

    async def migrate_out(self, request_id: str, adopter, timeout_s=None) -> dict:
        """Hand one in-flight sequence to a peer mid-decode and re-pin its
        output stream to the peer's continuation.

        ``adopter(manifest)`` is an async iterator of StepOutputs — the
        in-process form is another engine's ``adopt_migrated``; the worker
        wraps its peer's ``migrate`` endpoint in the same shape. The failure
        ladder: a handoff that dies before the first continuation token
        un-freezes the sequence (local decode resumes); one that dies after
        relaying tokens requeues a preempt-style resume over history +
        relayed tokens. Returns a status dict; "ok" means the stream now
        lives on the destination."""
        timeout = timeout_s or self.config.migration_timeout_s
        sched = self.scheduler
        if not self.config.migration:
            return {"status": "skipped", "reason": "migration disabled"}
        manifest = await self.run_on_engine(
            lambda: self.sync_snapshot_for_migration(request_id)
        )
        if manifest == "multimodal":
            # structured VL rejection (PR 14 follow-up): distinct from the
            # transient "not migratable right now" — this sequence will
            # NEVER migrate; callers must not retry it
            events.emit(
                "migration.fallback", request_id=request_id,
                arm="multimodal_rejected",
            )
            return {
                "status": "rejected",
                "reason": "multimodal_sequence",
                "detail": "mm_embeds do not ride the manifest; "
                          "migrating would silently drop vision context",
            }
        if manifest is None:
            return {"status": "skipped", "reason": "not migratable"}
        t0 = time.monotonic()
        gen = None
        first = None
        try:
            gen = adopter(manifest).__aiter__()
            first = await asyncio.wait_for(gen.__anext__(), timeout)
            if first.finished and first.finish_reason == "error":
                raise RuntimeError("destination rejected the adoption")
        except asyncio.CancelledError:
            raise
        except Exception as e:
            await self._aclose(gen)
            await self.run_on_engine(
                lambda: self.sync_abort_migration(request_id)
            )
            sched.migration_out_failed += 1
            log.warning("migration of %s failed before handoff: %s", request_id, e)
            events.emit(
                "migration.fallback",
                request_id=request_id, trace_id=manifest.trace_id,
                tenant=manifest.tenant, priority=manifest.priority or "",
                arm="abort_unfreeze", error=type(e).__name__,
            )
            return {"status": "failed", "error": f"{type(e).__name__}: {e}"}
        pause = time.monotonic() - t0
        committed = await self.run_on_engine(
            lambda: self.sync_commit_migration(request_id)
        )
        if not committed:
            # cancel/EOS raced the handoff: the local stream already ended;
            # the destination's adopted copy is orphaned — drop it
            await self._aclose(gen)
            return {"status": "skipped", "reason": "sequence ended locally"}
        self.migration_pause_hist.observe(pause)
        tracing.record_span(
            "engine.migrate_out", t0, duration=pause,
            request_id=request_id, trace_id=manifest.trace_id,
            attrs={"kv_blocks": manifest.kv_blocks,
                   "generated": len(manifest.generated)},
        )
        events.emit(
            "migration.handoff",
            request_id=request_id, trace_id=manifest.trace_id,
            tenant=manifest.tenant, priority=manifest.priority or "",
            pause_ms=round(pause * 1e3, 3), kv_blocks=manifest.kv_blocks,
        )
        relayed: list[int] = []
        item = first
        try:
            while True:
                if item.finished and item.finish_reason == "error":
                    raise RuntimeError("destination errored mid-continuation")
                if item.token is not None:
                    relayed.append(item.token)
                self._post(request_id, item)
                if item.finished:
                    sched.migration_out += 1
                    return {
                        "status": "ok", "pause_s": pause,
                        "tokens_relayed": len(relayed),
                        "kv_blocks": manifest.kv_blocks,
                    }
                item = await gen.__anext__()
        except asyncio.CancelledError:
            raise
        except Exception as e:  # incl. StopAsyncIteration without a finish
            await self._aclose(gen)
            sched.migration_out_failed += 1
            if request_id in self._outputs:
                # destination died mid-stream: continue locally from
                # history + everything already relayed (never worse than
                # preempt+recompute)
                log.warning(
                    "migration of %s lost the destination after %d relayed "
                    "tokens (%s); resuming locally",
                    request_id, len(relayed), e,
                )
                await self.run_on_engine(
                    lambda: self.sync_resume_migration(manifest, relayed)
                )
                events.emit(
                    "migration.fallback",
                    request_id=request_id, trace_id=manifest.trace_id,
                    tenant=manifest.tenant, priority=manifest.priority or "",
                    arm="resume_relayed", tokens_relayed=len(relayed),
                    error=type(e).__name__,
                )
                return {"status": "resumed", "tokens_relayed": len(relayed)}
            # the client is gone too: nothing to resume for
            events.emit(
                "migration.fallback",
                request_id=request_id, trace_id=manifest.trace_id,
                tenant=manifest.tenant, priority=manifest.priority or "",
                arm="client_gone", error=type(e).__name__,
            )
            return {"status": "failed", "error": f"{type(e).__name__}: {e}"}

    @staticmethod
    async def _aclose(gen) -> None:
        if gen is not None:
            try:
                await gen.aclose()
            except Exception:
                pass

    async def adopt_migrated(self, manifest) -> AsyncIterator[StepOutput]:
        """ADOPTING side: re-enter a migrated sequence through the normal
        admission path. The manifest's history is the prompt; committed KV
        pulls from the source via seq_handoff (FETCHING_KV) with chunked
        recompute as the fallback; sampling continues positionally, so the
        continuation is token-identical for greedy and seeded lanes."""
        if not self.config.migration:
            raise RuntimeError("migration is disabled on this engine")
        req = manifest.to_engine_request(now=time.monotonic())
        events.emit(
            "migration.adopted",
            request_id=req.request_id, trace_id=req.trace_id,
            tenant=req.tenant, priority=req.priority or "",
            kv_blocks=manifest.kv_blocks, generated=len(manifest.generated),
            age_ms=round(manifest.age_s * 1e3, 3),
        )
        self._stamp_submission(req)
        self._register_stream(req.request_id)
        self._inbox.put(req)
        async for batch in self._drain_stream_batched(req.request_id):
            for item in batch:
                yield item

    def sync_allocate_remote(
        self, request_id: str, token_ids: list[int]
    ) -> tuple[int, int, list[int]]:
        """Decode side: allocate pages for a remote-prefill sequence.
        Returns (cached_len, shared_prefix_pages, page_ids) — the page ids in
        logical order, so the caller can scatter streamed KV parts into them
        as the parts land, before adoption."""
        self._refuse_transfer()
        cached_len, state = self.allocator.allocate_sequence(request_id, token_ids)
        return cached_len, state.shared_prefix_pages, list(state.pages)

    def sync_abort_remote(self, request_id: str) -> None:
        """Abort a remote-prefill request at ANY stage: adoption may already
        have completed on this thread even though the caller saw a
        cancellation, in which case the sequence sits in a decode slot and
        only scheduler.cancel releases both the slot and its pages (freeing
        pages while the slot keeps decoding would corrupt their next owner)."""
        if not self.scheduler.cancel(request_id):
            if request_id in self.allocator._seqs:
                self.allocator.free_sequence(request_id)

    def sync_remote_prefill(
        self, rp, device: bool = False, mode: str | None = None, on_part=None
    ):
        """Prefill side: full chunked prefill in our own cache (prefix cache
        applies), then extract the requested block range.

        Returns ``(PrefillResult, host_data_or_None)``. mode:
          - "inline" — KV staged to host and serialized into the result
            (legacy / tiny transfers)
          - "ici" — same-process handoff: KV gathered into a device array
            parked in the ici hub; result carries kv_transfer_id
          - "socket" — KV staged to host and RETURNED alongside the result;
            the caller ships it over the dedicated data plane
            (disagg/dataplane.py) while the result message becomes the
            completion notification

        ``on_part`` (socket mode only) switches to the CHUNK-STREAMED export:
        instead of one monolithic post-prefill pull, pages finalized by each
        prefill chunk are gathered immediately (D2H resolved off this thread,
        see ModelRunner.extract_pages_async) and handed to
        ``on_part(part_seq, part_total, page_from, page_to, host_future)``
        while the next chunk computes. The result then carries
        ``kv_parts == part_total`` and no host_data."""
        self._refuse_transfer()
        from dynamo_tpu.disagg import ici
        from dynamo_tpu.disagg.dataplane import stream_part_plan
        from dynamo_tpu.engine.sampling import SamplingParams
        from dynamo_tpu.llm.remote_prefill import PrefillResult

        if mode is None:
            mode = "ici" if device else "inline"
        rid = f"rp-{rp.request_id}"
        prompt_len = len(rp.token_ids)
        cached_len, state = self.allocator.allocate_sequence(rid, list(rp.token_ids))
        # fleet prefix pull BEFORE recomputing:
        # when the router attached a holder whose cached prefix beats ours,
        # pull the missing leading blocks over the dataplane — the same
        # timeout -> recompute fallback the decode-side FETCHING_KV path
        # uses, synchronous here because the prefill worker's engine thread
        # has nothing to interleave with this request anyway
        if getattr(rp, "kv_holder_addr", ""):
            cached_len = self._pull_remote_prefix(
                rp.kv_holder_addr, int(getattr(rp, "kv_holder_blocks", 0) or 0),
                state, cached_len, prompt_len, trace_id=rp.trace_id or None,
            )
        ps = self.config.page_size
        start_page = rp.skip_leading_tokens // ps
        n_pages = -(-prompt_len // ps)
        plan = (
            stream_part_plan(
                start_page, cached_len, prompt_len, ps, self.config.max_prefill_chunk
            )
            if (mode == "socket" and on_part is not None)
            else []
        )
        try:
            page_table = self._page_table_for(state)
            req = EngineRequest(
                request_id=rid,
                token_ids=list(rp.token_ids),
                sampling=SamplingParams(
                    temperature=rp.temperature, top_k=rp.top_k, top_p=rp.top_p, max_tokens=1
                ),
                trace_id=rp.trace_id or None,
            )
            data = None
            if plan:
                total = len(plan)
                next_part = [0]

                def flush(tokens_final: int, last: bool) -> None:
                    limit = n_pages if last else tokens_final // ps
                    while next_part[0] < total and plan[next_part[0]][1] <= limit:
                        pf, pt = plan[next_part[0]]
                        ids = np.asarray(state.pages[pf:pt], np.int32)
                        with tracing.span(
                            "disagg.kv_extract", request_id=rp.request_id,
                            trace_id=req.trace_id, pages=len(ids), mode="socket",
                            part=next_part[0],
                        ):
                            fut = self.runner.extract_pages_async(ids)
                        on_part(next_part[0], total, pf, pt, fut)
                        next_part[0] += 1

                # prefix-cached pages below cached_len are final already;
                # everything else ships as its finalizing chunk completes
                flush(cached_len, False)
                first_token = self.scheduler.run_prefill_chunks(
                    req, page_table, cached_len, prompt_len,
                    on_chunk=lambda s, e: flush(e, e == prompt_len),
                )
                self.allocator.commit_prefilled(rid, prompt_len)
            else:
                first_token = self.scheduler.run_prefill_chunks(
                    req, page_table, cached_len, prompt_len
                )
                self.allocator.commit_prefilled(rid, prompt_len)
                ids = state.pages[start_page:n_pages]
                if ids:
                    with tracing.span(
                        "disagg.kv_extract", request_id=rp.request_id,
                        trace_id=req.trace_id, pages=len(ids), mode=mode,
                    ):
                        if mode == "ici":
                            data = self.runner.extract_pages_device(np.asarray(ids, np.int32))
                        else:
                            data = self.runner.extract_pages(np.asarray(ids, np.int32))
        finally:
            self.allocator.free_sequence(rid)  # full blocks stay cached for reuse

        transfer_id = ""
        if mode == "ici" and data is not None:
            transfer_id = ici.transfer_key(rp.decode_worker_id, rp.request_id)
            if not ici.put_transfer(transfer_id, data):
                transfer_id = ""  # consumer abandoned the request already
        # int8 caches export the {"q","s"} wire dict: shape/dtype describe
        # the int8 payload; the scale plane rides its own result fields on
        # the inline path (sockets carry it in part headers instead)
        from dynamo_tpu.quant.kv import is_quantized_wire

        payload = data["q"] if is_quantized_wire(data) else data
        inline = data is not None and mode == "inline"
        scales = data["s"] if (inline and is_quantized_wire(data)) else None
        result = PrefillResult(
            request_id=rp.request_id,
            first_token=int(first_token),
            prompt_len=prompt_len,
            skip_leading_tokens=start_page * ps,
            kv_shape=tuple(payload.shape) if data is not None else (),
            kv_dtype=str(payload.dtype) if data is not None else "",
            kv_bytes=payload.tobytes() if inline else b"",
            kv_transfer_id=transfer_id,
            kv_mode="socket" if plan else (mode if data is not None else "inline"),
            kv_parts=len(plan),
            kv_scales_bytes=scales.tobytes() if scales is not None else b"",
            kv_scales_shape=tuple(scales.shape) if scales is not None else (),
            kv_scales_dtype=str(scales.dtype) if scales is not None else "",
        )
        return result, (data if mode == "socket" else None)

    def _pull_remote_prefix(
        self, holder_addr: str, holder_blocks: int, state, cached_len: int,
        prompt_len: int, trace_id=None,
    ) -> int:
        """Prefill-side fleet prefix pull: fetch the contiguous leading
        blocks past our local cache from ``holder_addr`` and scatter them
        into the sequence's pre-allocated pages. Returns the new cached_len;
        ANY failure (no fetcher, timeout, gone, partial scatter) returns the
        original — the caller recomputes, never errors."""
        sched, cfg = self.scheduler, self.config
        fetcher = self.prefix_fetcher
        if fetcher is None or not cfg.prefix_fetch or holder_blocks <= 0:
            return cached_len
        ps = cfg.page_size
        base = cached_len // ps
        # the final prompt token must prefill so the model emits logits
        want_to = min(holder_blocks, (prompt_len - 1) // ps)
        if want_to - base < max(1, cfg.prefix_fetch_min_blocks):
            return cached_len
        hashes = [b.sequence_hash for b in state.token_seq.blocks[base:want_to]]
        if not hashes:
            return cached_len
        t0 = time.monotonic()
        try:
            fut = fetcher.fetch(holder_addr, hashes, timeout_s=cfg.prefix_fetch_timeout_s)
            res = fut.result(timeout=cfg.prefix_fetch_timeout_s + 2.0)
        except Exception:
            log.exception("prefill-side prefix pull from %s failed", holder_addr)
            sched.prefix_fetch_fallbacks += 1
            return cached_len
        dt = time.monotonic() - t0
        sched.stage_hist["prefix_fetch"].observe(dt)
        applied = 0
        if getattr(res, "status", "") == "hit" and res.blocks:
            try:
                for part in res.parts:
                    if part.block_from != applied:
                        break  # hole: only the contiguous leading run counts
                    ids = np.asarray(
                        state.pages[base + part.block_from : base + part.block_to],
                        np.int32,
                    )
                    if len(ids) != part.block_to - part.block_from:
                        break
                    self.runner.inject_pages_bucketed(ids, part.data, axis=part.cat_axis)
                    applied = part.block_to
            except Exception:
                log.exception("scatter of pulled prefix failed; recomputing")
                applied = 0
        if not applied:
            sched.prefix_fetch_fallbacks += 1
            return cached_len
        new_cached = (base + applied) * ps
        sched.prefix_fetch_hits += 1
        sched.prefix_fetch_blocks += applied
        sched.prefix_fetch_bytes += res.bytes
        sched.prefix_fetch_tokens += max(0, new_cached - cached_len)
        tracing.record_span(
            "engine.prefix_fetch", t0, duration=dt, trace_id=trace_id,
            attrs={"blocks": applied, "bytes": res.bytes, "holder": holder_addr,
                   "side": "prefill"},
        )
        return max(cached_len, new_cached)

    def sync_adopt_prefilled(
        self, req: EngineRequest, result, cached_len: int, kv_data=None,
        injected_pages: int = 0,
    ):
        """Decode side: inject received KV blocks into the pre-allocated pages
        and enter the sequence into decode. KV arrives as wire bytes (inline),
        as a device array via the ici hub (same-pod path), as a host array
        the caller already pulled off the dedicated data-plane socket
        (``kv_data``), or — the streamed path — scattered incrementally as
        parts landed, in which case ``injected_pages`` says how many pages
        the caller already wrote and this adopt only validates the count."""
        self._refuse_transfer()
        from dynamo_tpu.disagg import ici

        state = self.allocator._seqs[req.request_id]
        ps = self.config.page_size
        data = kv_data
        if data is None and result.kv_transfer_id:
            data = ici.pop_transfer(result.kv_transfer_id)
            if data is None:
                raise RuntimeError(
                    f"ici transfer {result.kv_transfer_id} missing for {req.request_id}"
                )
        elif data is None and result.kv_bytes:
            data = result.kv_array()
        start_page = result.skip_leading_tokens // ps
        n_pages = -(-result.prompt_len // ps)
        ids = state.pages[start_page:n_pages]
        if data is not None:
            with tracing.span(
                "disagg.kv_inject", request_id=req.request_id,
                trace_id=req.trace_id, pages=len(ids), mode=result.kv_mode,
            ):
                self.runner.inject_pages(np.asarray(ids, np.int32), data)
        elif injected_pages:
            # streamed adoption: every part was scattered on arrival; a count
            # mismatch means a part was lost — decoding from the hole's
            # uninitialized pages would be silent corruption
            if injected_pages != len(ids):
                raise RuntimeError(
                    f"streamed KV for {req.request_id} injected "
                    f"{injected_pages} pages, expected {len(ids)}"
                )
        elif ids:
            # pages were expected to be filled remotely but the result carried
            # no KV (e.g. a swallowed transfer): adopting would decode from
            # uninitialized pages — fail the request loudly instead
            raise RuntimeError(
                f"prefill result for {req.request_id} carried no KV for "
                f"{len(ids)} pending pages"
            )
        self.allocator.commit_prefilled(req.request_id, result.prompt_len)
        outputs = self.scheduler.adopt_prefilled(req, result.first_token, cached_len)
        return None, outputs  # (value, stream outputs) convention

    def _page_table_for(self, state) -> "np.ndarray":
        # sized to the sequence's ladder rung, not the dense width — the
        # remote-prefill path dispatches the same bucketed traces the local
        # scheduler does
        width = self.config.table_bucket_for(max(1, len(state.pages)))
        page_table = np.zeros(width, np.int32)
        page_table[: len(state.pages)] = state.pages
        return page_table

    # ---------------- metrics / events ----------------

    def metrics(self) -> ForwardPassMetrics:
        alloc, sched = self.allocator, self.scheduler
        if alloc is None or sched is None:
            return ForwardPassMetrics()
        hit_rate = (
            alloc.cache_hit_blocks / alloc.cache_query_blocks
            if alloc.cache_query_blocks
            else 0.0
        )
        return ForwardPassMetrics(
            request_active_slots=sched.num_running,
            request_total_slots=self.config.max_seqs,
            kv_active_blocks=alloc.active_pages,
            kv_total_blocks=self.config.num_pages - 1,
            num_requests_waiting=len(sched.waiting),
            gpu_cache_usage_perc=alloc.used_pages / max(1, self.config.num_pages - 1),
            gpu_prefix_cache_hit_rate=hit_rate,
        )

    def resource_snapshot(self) -> dict:
        """Engine resource gauges for stats broadcasts + Prometheus: KV
        page-pool occupancy/high-watermark, prefix-cache hit/miss,
        preemption/offload counters, device HBM live/peak bytes, and the
        monitored-jit compile churn (count + cumulative seconds)."""
        alloc, sched, runner = self.allocator, self.scheduler, self.runner
        if alloc is None or sched is None:
            return {}
        # actual-dtype KV byte accounting: the page-size arithmetic everyone
        # downstream (dynotop, capacity planning) used to do assuming bf16
        model = runner.model if runner is not None else None  # None: a metrics-surface test
        page_bytes = model.kv_page_bytes(self.config.page_size) if model is not None else 0
        recurrent = runner is not None and runner.recurrent
        snap = {
            "kv_cache_dtype": self.config.kv_cache_dtype or "bf16",
            "kv_page_bytes": page_bytes,
            "kv_pool_bytes_total": page_bytes * (self.config.num_pages - 1),
            "kv_pool_bytes_used": page_bytes * alloc.used_pages,
            "kv_pages_total": self.config.num_pages - 1,
            "kv_pages_used": alloc.used_pages,
            "kv_pages_active": alloc.active_pages,
            "kv_pages_free": alloc.free_pages,
            "kv_pages_peak": alloc.peak_used_pages,
            # the unwritten rest of the runs that running sequences grow into
            # (taken back before the pool refuses anyone), and their tiles by
            # whether the decode kernel fetches them in one copy
            "kv_pages_reserved": alloc.reserved_pages,
            "kv_tiles_run": alloc.run_tiles,
            "kv_tiles_scattered": alloc.tiles - alloc.run_tiles,
            "prefix_cache_hit_blocks": alloc.cache_hit_blocks,
            "prefix_cache_miss_blocks": max(
                0, alloc.cache_query_blocks - alloc.cache_hit_blocks
            ),
            "prefix_cache_query_blocks": alloc.cache_query_blocks,
            "prefix_cache_refused": alloc.prefix_refused,
            # layer groups (a window beside full attention): pages by group,
            # and what running sequences gave back behind a window
            "kv_group_pages": {
                g: dict(states, decoding=sched.decode_group_pages.get(g, 0))
                for g, states in alloc.group_pages().items()
            } if hasattr(alloc, "group_pages") else {},
            "kv_window_pages_released": getattr(alloc, "window_pages_released", 0),
            # the second kind of cache: per-slot recurrent state (zeros for
            # a model with no recurrent layers)
            "state_slots_total": self.config.max_seqs if recurrent else 0,
            "state_slots_active": sched.state_slots_active,
            "hbm_state_bytes": runner.state_bytes if recurrent else 0,
            "moe_assignments": sched.moe_assignments,
            "moe_routed": sched.moe_routed,
            "moe_experts_touched": sched.moe_experts_touched,
            "moe_busiest_over_mean": round(sched.moe_busiest_over_mean, 4),
            # fleet prefix cache: remote pulls this engine issued (requester
            # side; the pull SERVER's counters ride the worker's kv_pull stats)
            "prefix_fetch_hits": sched.prefix_fetch_hits,
            "prefix_fetch_fallbacks": sched.prefix_fetch_fallbacks,
            "prefix_fetch_blocks": sched.prefix_fetch_blocks,
            "prefix_fetch_bytes": sched.prefix_fetch_bytes,
            "prefix_fetch_tokens": sched.prefix_fetch_tokens,
            # live migration (disagg/migrate.py): both roles' counters ride
            # worker stats -> /cluster/status -> dynotop's MIG column
            "migration_out": sched.migration_out,
            "migration_out_failed": sched.migration_out_failed,
            "migration_in": sched.migration_in,
            "migration_in_pulled": sched.migration_in_pulled,
            "migration_in_recomputed": sched.migration_in_recomputed,
            "migration_tokens_salvaged": sched.migration_tokens_salvaged,
            "preemptions": sched.preempt_count,
            "pressure_drains": sched.pressure_drain_count,
            # multi-tenant QoS: running lanes per priority class, per-class
            # preemption victims, and critical-triggered sheds (dynotop QOS
            # column + the enforcement audit trail)
            "qos": {
                "enabled": self.config.qos,
                "running": self._qos_running_classes(sched),
                "preempted": dict(sched.qos_preempted),
                "sheds": sched.qos_sheds,
                "shed_migrations": sched.qos_shed_migrations,
            },
            # long-context: table-width ladder + depth-aware chunking +
            # watermark-driven cold-KV drain (str keys: JSON-safe on the wire)
            "context_table_promotions": sched.table_promotions,
            "context_table_dispatches": {
                str(w): n for w, n in sorted(sched.table_dispatches.items())
            },
            "context_chunk_dispatches": {
                str(b): n for b, n in sorted(sched.chunk_dispatches.items())
            },
            "offload_pressure_blocks": sched.offload_pressure_blocks,
            "requests_waiting": len(sched.waiting),
            "oldest_waiting_age_s": round(sched.oldest_waiting_age(), 3),
            "engine_steps": self.step_count,
            # step-anatomy plane (utils/step_anatomy.py): per-kind phase
            # seconds, host/roofline fractions, decode dispatch cadence —
            # nested dict rides /cluster/status + dynotop STEP/ROOF columns
            "step_anatomy": sched.anatomy.snapshot(),
            # cost-attribution plane (utils/metering.py): per-tenant device-
            # seconds / KV byte-seconds / token charges — rides worker stats
            # -> /cluster/costs, dynotop's COST column, and the planner's
            # per-tenant demand signal. None-safe: {} when metering is off.
            "costs": self.meter.snapshot() if self.meter is not None else {},
            # graceful zeros when no runner reports (CPU, or pre-init)
            "hbm_bytes_in_use": 0,
            "hbm_peak_bytes_in_use": 0,
            "hbm_bytes_limit": 0,
            "hbm_reporting_devices": 0,
        }
        offload = getattr(self, "offload", None)
        if offload is not None:
            snap.update(
                offload_saves=offload.saves,
                offload_loads=offload.loads,
                offload_drops=offload.drops,
                offload_blocks_resident=len(offload),
                offload_capacity_blocks=offload.capacity_blocks,
                # at the ACTUAL wire dtype (int8 host blocks ~half of bf16)
                offload_block_bytes=offload.block_bytes,
                offload_bytes_resident=offload.bytes_resident,
            )
            disk = getattr(offload, "disk", None)
            if disk is not None:
                snap.update(
                    disk_spills=disk.spills,
                    disk_restores=disk.restores,
                    disk_drops=disk.drops,
                    disk_io_errors=disk.io_errors,
                    disk_blocks_resident=len(disk),
                    disk_bytes_resident=disk.bytes_resident,
                    disk_budget_bytes=disk.budget_bytes,
                    disk_restore_s=round(disk.restore_s, 4),
                    disk_restore_hits=sched.disk_restore_hits,
                    disk_restore_fallbacks=sched.disk_restore_fallbacks,
                    disk_restore_blocks=sched.disk_restore_blocks,
                    disk_restore_tokens=sched.disk_restore_tokens,
                )
        spec = self.config.spec
        if spec is not None:
            st = sched.stage
            snap["spec_proposer"] = spec.kind
            snap["spec_acceptance_rate"] = round(
                st.spec_accepted / max(1, st.spec_proposed), 4
            )
            draft = getattr(runner, "draft", None) if runner is not None else None
            if draft is not None:
                # the draft model's OWN paged pool (acceptance criterion:
                # draft KV pages visible in resource_snapshot)
                snap["spec_draft_pages_total"] = draft.pages_total
                snap["spec_draft_pages_used"] = draft.pages_used
                snap["spec_draft_model"] = spec.model
        store = getattr(runner, "lora_store", None) if runner is not None else None
        if store is not None:
            # multi-LoRA: device slot occupancy, eviction/load churn, and
            # per-adapter demand (dynotop's LORA column + dynamo_lora_*)
            ls = store.metrics_snapshot()
            snap["lora_resident"] = ls["resident"]
            snap["lora_capacity"] = ls["capacity"]
            snap["lora_evictions"] = ls["evictions"]
            snap["lora_loads"] = ls["loads"]
            snap["lora_load_seconds"] = ls["load_seconds"]
            snap["lora_requests"] = ls["requests"]
            snap["lora_hot"] = ls["hot"]
        if runner is not None:
            snap.update(runner.hbm_stats())
            cm = getattr(runner, "compile_monitor", None)
            if cm is not None:
                c = cm.snapshot()
                snap["xla_compiles"] = c["compiles"]
                snap["xla_compile_s"] = c["compile_s"]
        return snap

    @staticmethod
    def _qos_running_classes(sched) -> dict:
        out: dict = {}
        for s in sched.slots:
            if s is not None and not s.finished:
                cls = s.req.priority or "standard"
                out[cls] = out.get(cls, 0) + 1
        return out

    def slo_snapshot(self) -> dict:
        return self.slo.snapshot()

    def events_snapshot(self, limit: int = 32) -> dict:
        """Flight-recorder summary for worker stats broadcasts (the fleet
        /cluster/events merge + dynotop's EVT column read this)."""
        return events.JOURNAL.snapshot(limit=limit)

    def debug_steps(self, limit: int = 128, kind: Optional[str] = None) -> dict:
        """The ``/debug/steps`` payload: recent per-dispatch StepRecords
        (newest last) + the summary fractions — where the milliseconds of a
        live engine's steps went, inspectable without tracing enabled."""
        if self.scheduler is None:
            return {"records": [], "summary": {}}
        anatomy = self.scheduler.anatomy
        return {
            "records": anatomy.records(limit=limit, kind=kind),
            "summary": anatomy.snapshot(),
        }

    def goodput_snapshot(self) -> dict:
        """Windowed goodput per scenario/tenant (worker stats broadcasts +
        dynotop's GOODPUT column)."""
        return self.goodput.snapshot()

    def cost_snapshot(self) -> dict:
        """Cost-attribution rollup (utils/metering.py MeterLedger.snapshot):
        per-tenant device-seconds by dispatch kind, per-tier KV byte-seconds
        and residency, queued-seconds, and the admitted-vs-consumed token
        counters. {} when metering is off."""
        return self.meter.snapshot() if self.meter is not None else {}

    def request_cost(self, request_id: str) -> Optional[dict]:
        """Per-request cost footer for /debug/requests/{id}: device-ms by
        dispatch kind + peak resident KV bytes per tier. None when metering
        is off or the footer LRU already evicted the request."""
        if self.meter is None:
            return None
        return self.meter.request_cost(request_id)

    def _observe_outcome(self, outcome) -> None:
        """Scheduler outcome sink: goodput accounting + the drain-rate
        sample every Retry-After estimate prices from."""
        self.drain_estimator.note_finish()
        self.goodput.observe(outcome)

    def backpressure_snapshot(self) -> dict:
        """The frontend's engine-backpressure view (utils/qos.py): queue
        depth, measured drain rate, and the estimated wait a NEW request
        faces — the shed check compares est_wait_s against the TTFT budget
        and sheds batch-class load first. est_wait_s is None until anything
        has finished (a cold engine must not shed on a fake rate)."""
        sched = self.scheduler
        depth = len(sched.waiting) if sched is not None else 0
        rate = self.drain_estimator.rate_rps()
        return {
            "queue_depth": depth,
            "drain_rps": round(rate, 4) if rate is not None else None,
            "est_wait_s": (
                round(depth / rate, 4) if rate and rate > 0 else None
            ),
            "retry_after_s": self.drain_estimator.retry_after_s(depth),
        }

    def stage_snapshot(self) -> dict:
        """Per-stage latency attribution totals (scheduler StageStats plus the
        host-KV-offload transfer leg)."""
        if self.scheduler is None:
            return {}
        snap = self.scheduler.stage.snapshot()
        offload = getattr(self, "offload", None)
        if offload is not None:
            snap["kv_offload_s"] = round(offload.transfer_s, 4)
            snap["kv_offload_blocks"] = offload.saves + offload.loads
        return snap

    def render_stage_metrics(self) -> str:
        """Prometheus text for the engine-stage histograms (queue wait, TTFT,
        prefill, decode-window dispatch, reconcile wait) + stage-seconds
        counters; mounted under the serving /metrics endpoint."""
        if self.scheduler is None:
            return ""
        from dynamo_tpu.utils.prometheus import render_family

        parts = [h.render() for h in self.scheduler.stage_hist.values()]
        stage_seconds = {
            "queue_wait": self.scheduler.stage.queue_wait_s,
            "prefill": self.scheduler.stage.prefill_s,
            "decode_dispatch": self.scheduler.stage.decode_dispatch_s,
            "reconcile_wait": self.scheduler.stage.reconcile_wait_s,
        }
        offload = getattr(self, "offload", None)
        if offload is not None:
            stage_seconds["kv_offload"] = offload.transfer_s
        st = self.scheduler.stage
        if st.spec_rounds:
            stage_seconds["spec_verify"] = st.spec_dispatch_s
        parts.append(render_family(
            "dynamo_engine_stage_seconds_total", "counter",
            "cumulative engine-thread seconds attributed to each stage",
            [({"stage": k}, v) for k, v in sorted(stage_seconds.items())],
        ))
        # the order of the device's queue, as a quotient: windows ahead /
        # dispatches = the decode windows a new prompt's prefill waits behind
        parts.append(render_family(
            "dynamo_engine_prefill_dispatches_total", "counter",
            "prefill calls dispatched to the device (packed and per-request)",
            [({}, st.prefill_calls)],
        ))
        # a pack's fill, as a quotient: rows / padded rows
        parts.append(render_family(
            "dynamo_engine_prefill_rows_total", "counter",
            "prompt rows prefilled on this engine's device",
            [({}, st.prefill_rows)],
        ))
        parts.append(render_family(
            "dynamo_engine_prefill_padded_rows_total", "counter",
            "rows the prefill programs computed for them, padding included "
            "(a pack's blocks or lanes x bucket, a chain's chunk buckets)",
            [({}, st.prefill_padded_rows)],
        ))
        parts.append(render_family(
            "dynamo_engine_prefill_windows_ahead_total", "counter",
            "decode windows in flight at the moment of each prefill "
            "dispatch, summed over the dispatches",
            [({}, st.prefill_windows_ahead)],
        ))
        if self.config.speculative is not None:
            parts.append(render_family(
                "dynamo_spec_proposed_total", "counter",
                "draft tokens proposed by the speculative proposer",
                [({}, st.spec_proposed)],
            ))
            parts.append(render_family(
                "dynamo_spec_accepted_total", "counter",
                "proposed draft tokens accepted by batched verification",
                [({}, st.spec_accepted)],
            ))
            spec = self.config.spec
            # acceptance labeled by proposer kind: dashboards comparing an
            # ngram fleet against a draft-model fleet read ONE family
            parts.append(render_family(
                "dynamo_spec_acceptance_ratio", "gauge",
                "accepted/proposed draft tokens, labeled by proposer kind",
                [({"proposer": spec.kind},
                  round(st.spec_accepted / max(1, st.spec_proposed), 4))],
            ))
            if spec.kind == "draft":
                parts.append(render_family(
                    "dynamo_spec_draft_seconds_total", "counter",
                    "engine-thread seconds in the draft model, by phase "
                    "(dispatch = the batched per-round drafting call; "
                    "prefill = draft-cache builds at admission/resume)",
                    [({"phase": "dispatch"}, round(st.spec_draft_s, 4)),
                     ({"phase": "prefill"}, round(st.spec_draft_prefill_s, 4))],
                ))
                parts.append(render_family(
                    "dynamo_spec_draft_dispatch_total", "counter",
                    "batched draft-model drafting dispatches (one per spec "
                    "round with >= 1 live draft lane)",
                    [({}, st.spec_draft_calls)],
                ))
                parts.append(render_family(
                    "dynamo_spec_draft_prefill_total", "counter",
                    "draft-cache prefills (admission, preemption resume, "
                    "offload restore, and catch-up rebuilds)",
                    [({}, st.spec_draft_prefills)],
                ))
        # step-anatomy families: dynamo_step_seconds_total{phase,kind} +
        # dynamo_step_dispatch_total{kind} + dynamo_engine_roofline_fraction
        parts.append(self.scheduler.anatomy.render_metrics())
        # cost-attribution families: the five dynamo_cost_* (utils/metering.py)
        if self.meter is not None:
            parts.append(self.meter.render_metrics())
        parts.append(self._render_resource_metrics())
        # fleet prefix cache: wire-side client/server families join the
        # engine surface when the hosting worker attached them
        if self.prefix_fetcher is not None:
            parts.append(self.prefix_fetcher.render_metrics())
        if self.kv_pull_server is not None:
            parts.append(self.kv_pull_server.render_metrics())
        parts.append(self.health.render_metrics())
        # engine-scoped prefix: a colocated HTTP frontend renders its own
        # tracker under dynamo_slo_*; sharing that name here would emit
        # duplicate families in the combined exposition
        parts.append(self.slo.render_metrics(prefix="dynamo_engine_slo"))
        # goodput plane, same prefix logic (the frontend owns dynamo_goodput_*)
        parts.append(self.goodput.render_metrics(prefix="dynamo_engine_goodput"))
        return "".join(parts)

    def _render_resource_metrics(self) -> str:
        """Resource gauge families from resource_snapshot(): page pool,
        prefix cache, preemptions, offload, HBM, compile churn."""
        from dynamo_tpu.utils.prometheus import render_family

        r = self.resource_snapshot()
        if not r:
            return ""
        parts = [
            render_family(
                "dynamo_engine_kv_pages", "gauge",
                "KV page-pool occupancy by state (total excludes the null page)",
                [({"state": s}, r[f"kv_pages_{s}"])
                 for s in ("total", "used", "active", "free", "peak", "reserved")],
            ),
            render_family(
                "dynamo_engine_kv_tiles", "gauge",
                "tiles of the running sequences' pages (what the decode attention "
                "kernel walks at a time) by whether their pages are one run of the pool",
                [({"state": s}, r[f"kv_tiles_{s}"]) for s in ("run", "scattered")],
            ),
            render_family(
                "dynamo_engine_prefix_cache_blocks_total", "counter",
                "prefix-cache lookups by result (block granularity)",
                [({"result": "hit"}, r["prefix_cache_hit_blocks"]),
                 ({"result": "miss"}, r["prefix_cache_miss_blocks"])],
            ),
            render_family(
                "dynamo_prefix_fetch_requests_total", "counter",
                "remote prefix pulls resolved by this engine, by outcome "
                "(hit = blocks scattered and recompute skipped; fallback = "
                "timeout/gone/error degraded to recompute)",
                [({"result": "hit"}, r["prefix_fetch_hits"]),
                 ({"result": "fallback"}, r["prefix_fetch_fallbacks"])],
            ),
            render_family(
                "dynamo_prefix_fetch_blocks_total", "counter",
                "KV blocks pulled from peers and scattered into local pages",
                [({}, r["prefix_fetch_blocks"])],
            ),
            render_family(
                "dynamo_prefix_fetch_bytes_total", "counter",
                "KV payload bytes pulled from peers (at the wire KV dtype)",
                [({}, r["prefix_fetch_bytes"])],
            ),
            render_family(
                "dynamo_prefix_fetch_tokens_total", "counter",
                "prompt tokens whose prefill recompute a remote pull skipped",
                [({}, r["prefix_fetch_tokens"])],
            ),
            # live migration: handoffs out (ok = stream re-pinned to the
            # destination; failed = resumed locally) and adoptions in
            # (pulled = committed KV arrived over seq_handoff; recomputed =
            # timeout/gone/corrupt degraded to chunked recompute)
            render_family(
                "dynamo_migration_requests_total", "counter",
                "live sequence migrations by role and terminal result",
                [({"role": "out", "result": "ok"}, r["migration_out"]),
                 ({"role": "out", "result": "failed"}, r["migration_out_failed"]),
                 ({"role": "in", "result": "pulled"}, r["migration_in_pulled"]),
                 ({"role": "in", "result": "recomputed"}, r["migration_in_recomputed"])],
            ),
            render_family(
                "dynamo_migration_tokens_salvaged_total", "counter",
                "history tokens whose prefill recompute a seq_handoff KV "
                "pull skipped at adoption",
                [({}, r["migration_tokens_salvaged"])],
            ),
            self.migration_pause_hist.render(),
            render_family(
                "dynamo_engine_preemptions_total", "counter",
                "sequences bounced back to the waiting queue by page pressure",
                [({}, r["preemptions"])],
            ),
            # multi-tenant QoS: victims by priority class (page pressure AND
            # critical-triggered sheds; result=migrated = the victim went via
            # live migration instead of preempt+recompute)
            render_family(
                "dynamo_qos_preemptions_total", "counter",
                "preemption/shed victims by priority class (multi-tenant "
                "QoS: batch lanes pay before standard, standard before "
                "critical; migrated = victim handed to a peer instead of "
                "recomputed)",
                [({"class": c, "result": "preempted"}, n)
                 for c, n in sorted(r["qos"]["preempted"].items())]
                + [({"class": "any", "result": "migrated"},
                    r["qos"]["shed_migrations"])],
            ),
            render_family(
                "dynamo_engine_pressure_drains_total", "counter",
                "pipeline drains forced by ensure_capacity misses",
                [({}, r["pressure_drains"])],
            ),
            # long-context families: the page-table width ladder (dispatches
            # by width + mid-flight rung promotions), depth-aware prefill
            # chunk buckets, and the watermark-driven cold-KV host drain
            render_family(
                "dynamo_engine_context_table_dispatch_total", "counter",
                "engine dispatches by page-table width (the pow2 ladder "
                "rung the call's widest sequence needed)",
                [({"width": w}, n)
                 for w, n in sorted(r["context_table_dispatches"].items(),
                                    key=lambda kv: int(kv[0]))]
                or [({"width": str(self.config.table_buckets[0])}, 0)],
            ),
            render_family(
                "dynamo_engine_context_table_promotions_total", "counter",
                "sequences promoted to a wider page-table ladder rung "
                "mid-flight (decode growth past their current width)",
                [({}, r["context_table_promotions"])],
            ),
            render_family(
                "dynamo_engine_context_chunk_total", "counter",
                "prefill chunks by padded bucket length (the depth-aware "
                "planner shrinks chunks as context deepens)",
                [({"len": b}, n)
                 for b, n in sorted(r["context_chunk_dispatches"].items(),
                                    key=lambda kv: int(kv[0]))]
                or [({"len": str(min(self.config.prefill_buckets))}, 0)],
            ),
            render_family(
                "dynamo_engine_offload_pressure_blocks_total", "counter",
                "cold refcount-0 KV blocks drained to the host tier by the "
                "occupancy-watermark pressure path (batched gathers)",
                [({}, r["offload_pressure_blocks"])],
            ),
            render_family(
                "dynamo_engine_hbm_bytes", "gauge",
                "device memory summed over local devices (zeros on CPU)",
                [({"kind": "live"}, r["hbm_bytes_in_use"]),
                 ({"kind": "peak"}, r["hbm_peak_bytes_in_use"]),
                 ({"kind": "limit"}, r["hbm_bytes_limit"]),
                 ({"kind": "state"}, r["hbm_state_bytes"])],
            ),
            render_family(
                "dynamo_engine_state_slots", "gauge",
                "decode slots of the per-slot recurrent state cache (total 0: "
                "the model has no recurrent layers)",
                [({"state": "active"}, r["state_slots_active"]),
                 ({"state": "total"}, r["state_slots_total"])],
            ),
            render_family(
                "dynamo_engine_state_bytes", "gauge",
                "device bytes of the per-slot recurrent state cache beside the "
                "page pool's, and what one slot costs before its first page "
                "(zeros: the model has no recurrent layers)",
                [({"cache": "state"}, r["hbm_state_bytes"]),
                 ({"cache": "pages"}, r["kv_pool_bytes_total"]),
                 ({"cache": "state_per_slot"},
                  r["hbm_state_bytes"] // (r["state_slots_total"] + 1) if r["state_slots_total"] else 0)],
            ),
            render_family(
                "dynamo_engine_prefix_cache_refused_total", "counter",
                "sequences whose cached prefix was withheld: the model has "
                "recurrent layers (pages without state), or a window layer no "
                "longer holds the blocks behind the match (another model, silently)",
                [({}, r["prefix_cache_refused"])],
            ),
            render_family(
                "dynamo_engine_kv_group_pages", "gauge",
                "single-layer pages by layer group (a model whose attention "
                "layers keep different tokens): active = held by running "
                "sequences, cached = evictable, free_equivalent = what the "
                "free pool gives the group in whole blocks, whole = what the "
                "running sequences would hold of it with no window, decoding = "
                "held by the sequences of the last decode window",
                [({"group": g, "state": st}, n)
                 for g, states in r.get("kv_group_pages", {}).items() for st, n in states.items()],
            ),
            render_family(
                "dynamo_engine_kv_window_pages_released_total", "counter",
                "pages running sequences gave back because every token of them "
                "lay behind a sliding window",
                [({}, r.get("kv_window_pages_released", 0))],
            ),
            render_family(
                "dynamo_engine_moe_assignments_total", "counter",
                "decode-step expert assignments that landed on experts held here",
                [({}, r["moe_assignments"])],
            ),
            render_family(
                "dynamo_engine_moe_routed_total", "counter",
                "decode-step expert assignments routed: tokens x experts a "
                "token x expert blocks, held here or not",
                [({}, r["moe_routed"])],
            ),
            render_family(
                "dynamo_engine_moe_experts_touched_total", "counter",
                "held experts that received a row, summed over decode steps "
                "and expert layers (0 for a model without expert layers)",
                [({}, r["moe_experts_touched"])],
            ),
            render_family(
                "dynamo_engine_moe_busiest_over_mean", "gauge",
                "the last decode window's busiest held expert over the mean "
                "of the held experts' assignment counts",
                [({}, r["moe_busiest_over_mean"])],
            ),
            # KV cache bytes at the ACTUAL storage dtype (int8 pages cost
            # half + scale planes; pre-r6 consumers assumed bf16)
            render_family(
                "dynamo_engine_kv_cache_bytes", "gauge",
                "KV page-pool bytes at the configured kv_cache_dtype",
                [({"kind": "total"}, r["kv_pool_bytes_total"]),
                 ({"kind": "used"}, r["kv_pool_bytes_used"])],
            ),
            render_family(
                "dynamo_engine_kv_cache_page_bytes", "gauge",
                "bytes one KV page costs across all layers (K+V, incl. int8 "
                "scale planes), labeled with the cache storage dtype",
                [({"dtype": r["kv_cache_dtype"]}, r["kv_page_bytes"])],
            ),
        ]
        if "xla_compiles" in r:
            parts.append(render_family(
                "dynamo_engine_xla_compiles_total", "counter",
                "XLA compilations observed by the monitored-jit wrappers "
                "(a climbing value mid-serving is a recompile storm)",
                [({}, r["xla_compiles"])],
            ))
            parts.append(render_family(
                "dynamo_engine_xla_compile_seconds_total", "counter",
                "cumulative seconds engine calls spent tracing + compiling",
                [({}, round(r["xla_compile_s"], 4))],
            ))
        if "offload_saves" in r:
            parts.append(render_family(
                "dynamo_engine_offload_blocks_total", "counter",
                "host-DRAM KV tier block movement by operation",
                [({"op": "save"}, r["offload_saves"]),
                 ({"op": "load"}, r["offload_loads"]),
                 ({"op": "drop"}, r["offload_drops"])],
            ))
            parts.append(render_family(
                "dynamo_engine_offload_bytes_resident", "gauge",
                "host-DRAM KV tier bytes resident at the ACTUAL wire dtype "
                "(int8 blocks cost ~half of bf16)",
                [({}, r["offload_bytes_resident"])],
            ))
        if "disk_blocks_resident" in r:
            # disk KV tier (engine/kv_store.py): the third rung of the
            # ladder — resident blocks/bytes against the byte budget plus
            # spill/restore churn and cumulative restore wall time
            parts.append(render_family(
                "dynamo_engine_disk_blocks", "gauge",
                "disk KV tier blocks resident (int8-compressed block files "
                "keyed by chained sequence hash)",
                [({}, r["disk_blocks_resident"])],
            ))
            parts.append(render_family(
                "dynamo_engine_disk_bytes", "gauge",
                "disk KV tier bytes: resident payload vs the configured "
                "byte budget (disk_cache_bytes)",
                [({"kind": "resident"}, r["disk_bytes_resident"]),
                 ({"kind": "budget"}, r["disk_budget_bytes"])],
            ))
            parts.append(render_family(
                "dynamo_engine_disk_spills_total", "counter",
                "disk KV tier block writes by outcome (spill = host-pool "
                "victim demoted; drop = budget eviction — the block left "
                "its last tier)",
                [({"op": "spill"}, r["disk_spills"]),
                 ({"op": "drop"}, r["disk_drops"])],
            ))
            parts.append(render_family(
                "dynamo_engine_disk_restores_total", "counter",
                "disk KV tier blocks restored (ok = verified + promoted to "
                "device; error = read/checksum failures that fell back to "
                "recompute)",
                [({"outcome": "ok"}, r["disk_restores"]),
                 ({"outcome": "error"}, r["disk_io_errors"])],
            ))
            parts.append(render_family(
                "dynamo_engine_disk_restore_seconds", "counter",
                "cumulative wall seconds the disk worker spent reading, "
                "verifying, and dequantizing restore runs (off the engine "
                "loop — restores park in FETCHING_KV)",
                [({}, r["disk_restore_s"])],
            ))
        if "lora_resident" in r:
            # multi-LoRA adapter pool: slot occupancy, LRU eviction and
            # host-load churn, and per-adapter request demand
            parts.append(render_family(
                "dynamo_lora_slots", "gauge",
                "LoRA adapter device slots (resident = adapters currently "
                "holding a slot; capacity excludes the reserved zero slot)",
                [({"state": "resident"}, r["lora_resident"]),
                 ({"state": "capacity"}, r["lora_capacity"])],
            ))
            parts.append(render_family(
                "dynamo_lora_evictions_total", "counter",
                "adapters LRU-evicted from device slots (host copy kept; a "
                "hot-swap back costs one scatter, not a reload)",
                [({}, r["lora_evictions"])],
            ))
            parts.append(render_family(
                "dynamo_lora_loads_total", "counter",
                "adapter host-weight loads (async; requests wait without "
                "blocking other traffic)",
                [({}, r["lora_loads"])],
            ))
            parts.append(render_family(
                "dynamo_lora_load_seconds_total", "counter",
                "cumulative seconds spent loading adapter host weights",
                [({}, round(r["lora_load_seconds"], 4))],
            ))
            parts.append(render_family(
                "dynamo_lora_requests_total", "counter",
                "sequences admitted per adapter (slot acquisitions)",
                [({"adapter": name}, n)
                 for name, n in sorted(r["lora_requests"].items())]
                or [({"adapter": ""}, 0)],
            ))
        if "spec_draft_pages_total" in r:
            # the draft model's OWN paged pool — separate from the target's
            # dynamo_engine_kv_pages (acceptance criterion: draft KV pages
            # visible alongside the target pool's occupancy)
            parts.append(render_family(
                "dynamo_spec_draft_pages", "gauge",
                "draft-model KV page-pool occupancy (its own pool, separate "
                "from the target cache; total excludes the trash page)",
                [({"state": "total"}, r["spec_draft_pages_total"]),
                 ({"state": "used"}, r["spec_draft_pages_used"])],
            ))
        return "".join(parts)

    def _on_kv_event(self, event: KvCacheEvent) -> None:
        if self._extra_kv_sink is not None:
            self._extra_kv_sink(event)

    # ---------------- engine thread ----------------

    def _run_loop(self) -> None:
        while not self._stopping.is_set():
            self.health.beat()
            now = time.monotonic()
            if now >= self._next_watchdog:
                # stuck-request watchdog: degrade (and auto-recover) on a
                # too-old waiting queue or a frozen progress marker while
                # work exists — the signals a wedged device op produces
                self._next_watchdog = now + _WATCHDOG_INTERVAL_S
                self.health.check(
                    oldest_waiting_age=self.scheduler.oldest_waiting_age(now),
                    has_work=self.scheduler.has_work(),
                    progress_marker=self.scheduler.progress_marker(),
                )
            did_work = self._drain_inboxes()
            if self.scheduler.has_work():
                try:
                    # the parent of this step's anatomy phases; what the
                    # engine thread does outside every phase shows as its
                    # uncovered part in a profiler trace
                    with tracing.span("engine.step", step=self.step_count):
                        outputs = self.scheduler.step()
                    self.step_count += 1
                except Exception as e:  # engine-step failure: fail all running
                    log.exception("engine step failed")
                    # the black box: record the crash, then dump the journal
                    # ring to a JSONL post-mortem BEFORE failing requests, so
                    # the dump holds the events that led here
                    try:
                        events.emit(
                            "engine.crash", request_id="",
                            error=type(e).__name__, step=self.step_count,
                        )
                        path = events.JOURNAL.dump_post_mortem(
                            f"engine step failed: {type(e).__name__}: {e}"
                        )
                        if path:
                            log.error("flight-recorder post-mortem: %s", path)
                    except Exception:
                        log.exception("post-mortem dump failed")
                    self._fail_all(e)
                    continue
                with tracing.span("engine.post", outputs=len(outputs)):
                    self._post_grouped(outputs)
            elif not did_work:
                # nothing to run: a device gap under this span is "no
                # request", not time the host took
                with tracing.span("engine.wait_for_work"):
                    try:
                        req = self._inbox.get(timeout=0.02)
                    except thread_queue.Empty:
                        req = None
                if req is not None:
                    self.scheduler.add_request(req)

    def _drain_inboxes(self) -> bool:
        got = False
        while True:
            try:
                req = self._inbox.get_nowait()
                self.scheduler.add_request(req)
                got = True
            except thread_queue.Empty:
                break
        while True:
            try:
                fn, loop, fut = self._cmd_box.get_nowait()
                got = True
                try:
                    result = fn()
                    outputs = []
                    if isinstance(result, tuple) and len(result) == 2 and isinstance(result[1], list):
                        result, outputs = result
                    self._post_grouped(outputs)
                    loop.call_soon_threadsafe(_resolve, fut, result, None)
                except Exception as e:
                    log.exception("engine command failed")
                    loop.call_soon_threadsafe(_resolve, fut, None, e)
            except thread_queue.Empty:
                break
        while True:
            try:
                rid = self._cancel_box.get_nowait()
                self.scheduler.cancel(rid)
            except thread_queue.Empty:
                break
        return got

    def _post_grouped(self, outputs: list) -> None:
        """Post a step's outputs grouped per request: one call_soon_threadsafe
        (and one queue wakeup) per request per decode window instead of per
        token. Order within a request is preserved (dict insertion order)."""
        if not outputs:
            return
        by_rid: dict[str, list] = {}
        for out in outputs:
            by_rid.setdefault(out.request_id, []).append(out)
        for rid, group in by_rid.items():
            self._post(rid, group if len(group) > 1 else group[0])

    def _post(self, request_id: str, item) -> None:
        entry = self._outputs.get(request_id)
        if entry is None:
            return
        loop, q = entry
        try:
            loop.call_soon_threadsafe(q.put_nowait, item)
        except RuntimeError:
            # caller's loop is gone; treat as cancelled
            self._outputs.pop(request_id, None)
            self._cancel_box.put(request_id)

    def _fail_all(self, exc: Exception) -> None:
        """Fail every request the scheduler knows about. Includes the waiting
        queue: a step can die while admitting (e.g. a trace error on the very
        first prefill), before the request ever reaches a slot — those callers
        must not be left waiting forever."""
        sched = self.scheduler
        rids = {s.req.request_id for s in sched.slots if s is not None}
        rids.update(s.req.request_id for s in sched.adopted_waiting)
        rids.update(r.request_id for r in sched.waiting)
        for rid in rids:
            sched.cancel(rid)
            self._post(rid, exc)
