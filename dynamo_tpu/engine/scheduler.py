"""Continuous-batching scheduler: admission, chunked prefill with prefix-cache
reuse, pipelined batched decode, preemption.

Policy (deliberately simple admission; aggressive latency hiding):
  - admit waiting requests whenever a decode slot and enough pages exist
    (watermark guard keeps headroom for decode growth)
  - prefill runs chunk-by-chunk through bucket-padded jit calls; the cached
    prefix (from the page allocator) is skipped, mirroring the reference's
    prefix-hit accounting used for routing/disagg decisions
  - decode runs as fused K-step windows dispatched **ahead** of result
    materialization: the sampled token feedback lives on device
    (ModelRunner.tokens_dev), so the host never syncs between windows. The
    device's queue is FIFO, so every window committed to it stands ahead of
    a prompt nobody has sent yet. The invariant (_window_room): besides the
    entry the device is running, at most config.pipeline_depth - 1 windows
    that have not started stand on its queue — ONE at the default of 2,
    double buffering. The host refills the queue in a few percent of a
    window, so one waiting window hides it; a second one bought no
    throughput and put 90 ms in front of every first token (PERF.md, PR 32).
    Results are reconciled in dispatch order; EOS is therefore discovered up
    to two windows (2 * K steps) late, the device wastes at most that much
    work per finished sequence, and its slot is held through one window in
    which it plans no step.
  - step() never blocks on the device while it holds tokens: whatever a
    non-blocking reconcile (or the prefill gate) materialized is returned
    once the queue is refilled, the engine loop posts it and calls again,
    and the blocking wait happens on that next call
  - on page exhaustion mid-decode the pipeline is drained, then the
    most-recently-admitted sequence is preempted back to the waiting queue
    (prompt = original + generated so far)

Scheduled-vs-materialized positions: `seq.sched_len` counts tokens that exist
in the *scheduled* timeline (prefill's first token + every window step), while
`seq.generated` holds materialized tokens only. Device-side positions are
deterministic given the dispatched control arrays, so the host tracks them
exactly without reading anything back.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.page_table import PageAllocator
from dynamo_tpu.engine.sampling import MAX_EOS_IDS, SamplingParams, fold_seed
from dynamo_tpu.ops.attention import prefill_tiles
from dynamo_tpu.spec import make_proposer
from dynamo_tpu.utils import events, get_logger, tracing
from dynamo_tpu.utils.goodput import MAX_ITL_SAMPLES, RequestOutcome
from dynamo_tpu.utils.prometheus import Histogram
from dynamo_tpu.utils.qos import priority_rank, priority_weight
from dynamo_tpu.utils.step_anatomy import StepAnatomy, roofline_for_runner

log = get_logger("engine.sched")


@dataclass
class EngineRequest:
    """Tokens-in/tokens-out request (the ExecutionContext contract,
    reference: lib/llm/src/backend.rs:60-63)."""

    request_id: str
    token_ids: list[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    eos_token_ids: tuple[int, ...] = ()
    # multimodal: images (llm/multimodal.py ImageInput, offsets into token_ids
    # where each image's virtual-token run sits) + their encoded embeddings
    # ([num_tokens, D] float32 each), filled by the engine at admission
    images: list = field(default_factory=list)
    mm_embeds: Optional[list] = None
    # OpenAI logprobs: None = off, 0 = chosen token only, n>0 = n top
    # alternatives per token (capped at sampling.LOGPROBS_K on device)
    logprobs: Optional[int] = None
    # M-RoPE (filled at admission for mrope models with images): [T, 3]
    # positions for the prompt + the scalar decode-time offset
    mrope_pos: Optional[object] = None
    mrope_delta: int = 0
    # preemption resume: token_ids[penalty_output_from:] were previously
    # GENERATED (their occurrence counts restore at re-admission so
    # presence/frequency penalties stay continuous)
    penalty_output_from: Optional[int] = None
    # observability: monotonic submission time (queue-wait/TTFT attribution)
    # and the edge-stamped trace id engine spans stitch to — both optional,
    # filled by AsyncJaxEngine at submission
    enqueue_ts: float = 0.0
    trace_id: Optional[str] = None
    # fleet-wide prefix cache: the KV router's best remote holder for this
    # prompt — that worker's pull-server address and its matched prefix
    # length in blocks. When the holder's advantage over the local prefix
    # cache clears prefix_fetch_min_blocks, admission pulls the pages over
    # the dataplane (FETCHING_KV) instead of recomputing them.
    kv_holder_addr: str = ""
    kv_holder_blocks: int = 0
    # live migration (disagg/migrate.py): non-empty = this request is the
    # ADOPTING side of a handoff — token_ids are a migrated sequence's full
    # history, and admission pulls its committed KV from kv_holder_addr via
    # the seq_handoff fetch kind (naming the source sequence here) instead
    # of the shared-prefix kind. Any pull failure recomputes from history.
    kv_handoff_seq: str = ""
    # multi-LoRA: the adapter this request serves ("" = base model). The
    # scheduler pins a device pool slot at admission (waiting while the
    # adapter loads — never blocking other requests) and salts the
    # sequence's KV block identity with the adapter uid.
    lora_name: str = ""
    # goodput accounting tags (utils/goodput.py): the tenant this request
    # bills to and the replay scenario that generated it — both ride the
    # per-request RequestOutcome and the tenant-labeled SLO series ("" =
    # untagged organic traffic)
    tenant: str = ""
    scenario: str = ""
    # multi-tenant QoS (utils/qos.py): priority class — critical | standard
    # | batch ("" = standard). Orders admission, weights the prefill
    # fairness cap, and orders preemption victims (batch lanes go first).
    priority: str = ""
    # cost metering (utils/metering.py): True once this request's admitted-
    # token charge posted to the ledger — carried through preemption requeues
    # so re-admission never double-bills the tenant's admitted count
    cost_admitted: bool = False


@dataclass
class StepOutput:
    request_id: str
    token: Optional[int] = None
    finished: bool = False
    finish_reason: Optional[str] = None  # stop | length | error | preempted
    cached_tokens: int = 0  # prefix-cache hit length (first output only)
    logprob: Optional[float] = None  # chosen-token logprob (when requested)
    top_logprobs: Optional[list] = None  # [(token_id, logprob), ...]


@dataclass
class RunningSeq:
    req: EngineRequest
    slot: int
    prompt_len: int
    cached_len: int
    generated: list[int] = field(default_factory=list)  # materialized tokens
    page_table: np.ndarray = None  # [max_pages_per_seq]
    admitted_order: int = 0
    sched_len: int = 0  # tokens in the scheduled timeline (>= len(generated))
    finished: bool = False
    # packed-prefill progress: next chunk start, or None when all chunks are
    # dispatched (decode windows only pick up seqs with prefill_pos None)
    prefill_pos: Optional[int] = None
    # speculative decoding: True = this sequence advances via verify rounds
    # (spec-eligible request on a spec-enabled engine); False = classic
    # dispatch-ahead decode windows. Fixed at admission so a sequence never
    # switches mid-stream between the sync (materialized) and dispatch-ahead
    # (scheduled) position-tracking regimes.
    spec_mode: bool = False
    # n-gram speculation: the sequence's incremental suffix index
    # (spec/proposer.py NgramIndex), built lazily at its first round and
    # extended with ACCEPTED tokens only — proposing costs O(new tokens),
    # not a full history rescan per round
    ngram: Optional[object] = None
    # draft-model speculation: how many tokens of this sequence's history
    # the draft model's KV has fed (None = draft cache not built yet);
    # draft_dead = the draft pool couldn't hold this sequence — it keeps
    # verifying (correct, 1 token/round) with no proposals
    draft_pos: Optional[int] = None
    draft_dead: bool = False
    # FETCHING_KV: an in-flight remote-prefix pull (_PrefixFetch). While set,
    # no prefill chunk dispatches for this sequence; resolution either
    # advances prefill_pos past the pulled prefix or falls back to recompute.
    fetch: Optional["_PrefixFetch"] = None
    # MIGRATING_OUT (disagg/migrate.py): frozen for handoff — no window,
    # spec round, or prefill dispatch touches it; pages stay resident so the
    # destination's seq_handoff pull can export them. Cleared if the handoff
    # fails (decode resumes locally); released without a finish when the
    # destination's continuation stream takes over.
    migrating: bool = False
    # multi-LoRA: the device pool slot this sequence's adapter is pinned in
    # (0 = base / no adapter). >0 implies one LoraStore ref held until the
    # sequence releases or is preempted — a pinned slot is never hot-swapped
    # under an in-flight sequence.
    lora_slot: int = 0
    # goodput outcome accounting (utils/goodput.py): admission queue wait,
    # first/last materialized-token walls, and the per-token inter-arrival
    # gaps after the first token. The gaps are client-shaped — a decode
    # window's tokens materialize together, so the series is bursty and its
    # per-request p99 is the honest stall signal the SLO verdict uses.
    queue_wait_s: Optional[float] = None
    first_token_wall: float = 0.0
    last_token_wall: float = 0.0
    # the chain of a first token, on time.monotonic(): this admission
    # (_start_sequence), and the end of the runner call that dispatched the
    # prefill chunk ending at prompt_len. With req.enqueue_ts before them and
    # first_token_wall after, they split the engine's ttft into queue_wait +
    # prefill_hold + first_token_wait (_emit_token). 0.0 = not stamped: a
    # sequence adopted with its prompt prefilled elsewhere has neither.
    admitted_ts: float = 0.0
    prefill_dispatched_ts: float = 0.0
    itl_gaps: list = field(default_factory=list)

    @property
    def pos(self) -> int:
        """Materialized position of the next token to be decoded."""
        return self.prompt_len + len(self.generated)

    @property
    def next_fed_pos(self) -> int:
        """Position where the next scheduled window's first KV write lands."""
        return self.prompt_len + self.sched_len - 1


@dataclass
class _PrefixFetch:
    """Handle for one sequence's FETCHING_KV wait."""

    fut: object  # concurrent.futures.Future[PrefixFetchResult]
    base_block: int  # first requested block's index in the sequence
    t0: float
    # belt over the client's own wait_for: if the fetcher's loop dies and the
    # future never resolves, the scheduler still unwedges admission here
    belt_deadline: float
    # seq_handoff pull of a migrated sequence's pages (ADOPTING side):
    # resolution feeds the migration counters instead of the prefix ones
    handoff: bool = False
    # disk-tier restore (engine/kv_store.py): same FETCHING_KV parking, but
    # resolution promotes blocks disk->device and feeds the disk counters
    disk: bool = False


@dataclass
class _InFlight:
    kind: str  # "first" | "window"
    dev: object  # device array (async copy already started)
    # first: (seq, cached_len); window: [(seq, slot_idx, steps), ...]
    seqs: list = field(default_factory=list)
    cached_len: int = 0
    lp: object = None  # (chosen, top_ids, top_lps) device arrays, if requested
    # step-anatomy record of the dispatch that produced this entry: the
    # reconcile's device-wait/emission time attributes back to it
    rec: object = None
    # window: what the model counted on the device over the window (the held
    # experts' assignment counts), async copy started; None for most models
    aux: object = None


def _mm_chunk_overrides(req: EngineRequest, start: int, end: int):
    """Dense [n, D] embedding overrides + mask for the chunk [start, end):
    rows from every image whose virtual-token run intersects the chunk."""
    if not req.images or req.mm_embeds is None:
        return None, None
    n = end - start
    embeds = None
    mask = np.zeros(n, bool)
    for im, emb in zip(req.images, req.mm_embeds):
        lo = max(start, im.offset)
        hi = min(end, im.offset + im.num_tokens)
        if lo >= hi:
            continue
        if embeds is None:
            embeds = np.zeros((n, emb.shape[1]), np.float32)
        embeds[lo - start : hi - start] = emb[lo - im.offset : hi - im.offset]
        mask[lo - start : hi - start] = True
    if embeds is None:
        return None, None  # pure-text chunk: reuse the text prefill executable
    return embeds, mask


def plan_block_pack(chunks: list, block: int, budget: int) -> list:
    """The blocks of one packed prefill, as a function of the pending set.
    ``chunks`` are the next chunks of the pending sequences in admission
    order, ``(start, end)`` in each sequence's own positions; a block is up
    to ``block`` rows of one chunk and a pack holds ``budget`` of them. Every
    chunk is cut into whole blocks (only its last may be short), a chunk
    that does not fit what is left of the budget is cut at a block boundary
    (its rest rides the next call), and what comes after a full budget
    waits. Returns ``(index into chunks, start, end)`` for each block."""
    blocks = []
    for i, (start, end) in enumerate(chunks):
        left = budget - len(blocks)
        if left <= 0:
            break
        end = min(end, start + left * block)
        blocks.extend((i, b, min(b + block, end)) for b in range(start, end, block))
    return blocks


def _is_ready(arr) -> bool:
    try:
        return bool(arr.is_ready())
    except Exception:
        return False


@dataclass
class StageStats:
    """Cumulative per-stage engine-time attribution (seconds + counts).

    Always on — the cost is a handful of monotonic() reads per window against
    ms-scale stages — so worker stats and /metrics can break a round's
    wall time into queue wait / prefill / decode dispatch / device sync
    without enabling tracing. Spans (DYNTPU_TRACE) add the per-request
    timeline on top of these aggregates.
    """

    queue_wait_s: float = 0.0
    queue_wait_n: int = 0
    prefill_s: float = 0.0  # dispatch time of prefill calls (packed + chained)
    prefill_calls: int = 0
    prefill_rows: int = 0
    # the rows the prefill programs computed for them: blocks or lanes x
    # bucket of a pack, the chunk buckets of a per-request chain
    prefill_padded_rows: int = 0
    # decode windows in flight at each prefill dispatch, summed: over
    # prefill_calls, the windows a new prompt's prefill stands behind
    prefill_windows_ahead: int = 0
    decode_dispatch_s: float = 0.0
    decode_windows: int = 0
    decode_steps: int = 0
    reconcile_wait_s: float = 0.0  # host blocked on device results
    reconcile_waits: int = 0
    # blocking reconciles forced by the prefill pipeline gate: with
    # prefill_pipeline_depth=1 every packed call stalls here before the next
    # dispatches; dispatch-ahead exists to shrink this count
    prefill_stalls: int = 0
    ttft_s: float = 0.0  # submission -> first materialized token
    ttft_n: int = 0
    # speculative decoding (spec rounds are synchronous verify passes, so
    # dispatch + device sync land in one number): draft tokens proposed,
    # drafts accepted by verification, and tokens actually emitted (accepted
    # + the per-round correction/bonus token)
    spec_rounds: int = 0
    spec_dispatch_s: float = 0.0
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_emitted: int = 0
    # draft-model speculation: the batched on-device drafting dispatches and
    # the per-sequence draft-cache prefills (both separate from the verify
    # pass's spec_dispatch_s so the round's cost splits draft vs verify)
    spec_draft_calls: int = 0
    spec_draft_s: float = 0.0
    spec_draft_prefills: int = 0
    spec_draft_prefill_s: float = 0.0

    def snapshot(self) -> dict:
        snap = {
            "queue_wait_s": round(self.queue_wait_s, 4),
            "queue_wait_n": self.queue_wait_n,
            "prefill_s": round(self.prefill_s, 4),
            "prefill_calls": self.prefill_calls,
            "prefill_rows": self.prefill_rows,
            "prefill_padded_rows": self.prefill_padded_rows,
            "prefill_windows_ahead": self.prefill_windows_ahead,
            "decode_dispatch_s": round(self.decode_dispatch_s, 4),
            "decode_windows": self.decode_windows,
            "decode_steps": self.decode_steps,
            "reconcile_wait_s": round(self.reconcile_wait_s, 4),
            "reconcile_waits": self.reconcile_waits,
            "prefill_stalls": self.prefill_stalls,
            "ttft_s": round(self.ttft_s, 4),
            "ttft_n": self.ttft_n,
        }
        if self.spec_rounds:
            snap.update(
                spec_rounds=self.spec_rounds,
                spec_dispatch_s=round(self.spec_dispatch_s, 4),
                spec_proposed=self.spec_proposed,
                spec_accepted=self.spec_accepted,
                spec_emitted=self.spec_emitted,
                spec_acceptance_rate=round(
                    self.spec_accepted / max(1, self.spec_proposed), 4
                ),
            )
        if self.spec_draft_calls or self.spec_draft_prefills:
            snap.update(
                spec_draft_calls=self.spec_draft_calls,
                spec_draft_s=round(self.spec_draft_s, 4),
                spec_draft_prefills=self.spec_draft_prefills,
                spec_draft_prefill_s=round(self.spec_draft_prefill_s, 4),
            )
        return snap


# bucket ladders for the engine-stage histograms: queue wait and TTFT reach
# into tens of seconds under overload; dispatch/sync stages are ms-scale
_WAIT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
_STAGE_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                  0.25, 0.5, 1.0, 2.5, 5.0)


def _stage_histograms() -> dict[str, Histogram]:
    return {
        "queue_wait": Histogram(
            "dynamo_engine_queue_wait_seconds",
            "time from engine submission to scheduler admission",
            _WAIT_BUCKETS,
        ),
        "ttft": Histogram(
            "dynamo_engine_ttft_seconds",
            "time from engine submission to first materialized token",
            _WAIT_BUCKETS,
        ),
        # the two stages between admission and the first token; with
        # queue_wait they add up to ttft, request by request (_emit_token)
        "prefill_hold": Histogram(
            "dynamo_engine_prefill_hold_seconds",
            "time from scheduler admission to the dispatch of the prefill "
            "chunk that ends the prompt (chunking, the prefill pipeline "
            "gate, prefix fetch)",
            _WAIT_BUCKETS,
        ),
        "first_token_wait": Histogram(
            "dynamo_engine_first_token_wait_seconds",
            "time from the dispatch of the last prefill chunk to the first "
            "materialized token (device backlog, prefill compute, reconcile "
            "order)",
            _WAIT_BUCKETS,
        ),
        "prefill": Histogram(
            "dynamo_engine_prefill_seconds",
            "per-request prefill dispatch time across all chunks",
            _STAGE_BUCKETS,
        ),
        "decode_window": Histogram(
            "dynamo_engine_decode_window_dispatch_seconds",
            "host dispatch time of one fused multi-step decode window",
            _STAGE_BUCKETS,
        ),
        "reconcile": Histogram(
            "dynamo_engine_reconcile_wait_seconds",
            "host time blocked waiting on in-flight device results",
            _STAGE_BUCKETS,
        ),
        # fleet prefix cache: admission -> pulled-prefix-scattered (or
        # fallback) per remote fetch; the FETCHING_KV dwell time
        "prefix_fetch": Histogram(
            "dynamo_prefix_fetch_seconds",
            "remote prefix pull wall time, fetch start to scatter/fallback",
            _WAIT_BUCKETS,
        ),
        # per-round acceptance: how many draft tokens each participating
        # request had accepted in one speculative verify round (0 = only the
        # correction token advanced; k = the whole proposal held)
        "spec_accept": Histogram(
            "dynamo_spec_accepted_per_round",
            "draft tokens accepted per request per speculative verify round",
            (0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0),
        ),
    }


class Scheduler:
    def __init__(self, config: EngineConfig, runner, allocator: PageAllocator):
        self.config = config
        self.runner = runner
        self.allocator = allocator
        self.waiting: deque[EngineRequest] = deque()
        self.adopted_waiting: deque[RunningSeq] = deque()  # prefilled remotely, need a slot
        self.slots: list[Optional[RunningSeq]] = [None] * config.max_seqs
        self.in_flight: deque[_InFlight] = deque()
        self._admit_counter = 0
        self.finished_count = 0
        # structural interference counters (read by tests/metrics): what a
        # decode pool pays for colocated prefill work. Pool specialization
        # (disagg) shows up as these dropping on the decode side while
        # remote_prefills rises on its DisaggDecodeEngine wrapper.
        self.preempt_count = 0  # sequences bounced back to waiting (page pressure)
        self.pressure_drain_count = 0  # pipeline drains forced by ensure_capacity misses
        self.local_prefill_rows = 0  # prompt tokens prefilled on THIS engine's chip
        # per-stage latency attribution: cumulative aggregates (always on) +
        # Prometheus histograms (rendered by the worker's /metrics)
        self.stage = StageStats()
        self.stage_hist = _stage_histograms()
        # step-anatomy plane (utils/step_anatomy.py): per-dispatch host/device
        # phase attribution in a bounded ring + the live roofline estimator
        # priced from this runner's actual param bytes and KV page cost
        self.anatomy = StepAnatomy(
            roofline=roofline_for_runner(runner, config) if runner is not None
            else None,
        )
        self.anatomy.stage_sink = self._feed_stage
        store = getattr(runner, "lora_store", None) if runner is not None else None
        if store is not None:
            # slot loads (device scatters) record as lora_slot_load dispatches
            store.anatomy = self.anatomy
        # cost-attribution ledger (utils/metering.py MeterLedger), attached by
        # the engine when config.metering: dispatch records carry bill rows
        # (anatomy.meter splits their phases), queued-seconds and
        # admitted/consumed token charges post here directly
        self.meter = None
        # run_prefill_chunks' most recent record: the dispatch-ahead callers
        # attach it to their _InFlight entry so the reconcile's device-wait
        # attributes back to the producing prefill chain
        self._last_prefill_rec = None
        # optional SLO sink (utils/slo.SloTracker): queue-wait and TTFT
        # observations feed rolling-window percentiles when attached
        self.slo = None
        # optional per-request outcome sink (utils/goodput.GoodputTracker
        # .observe, attached by the engine): every naturally-finished
        # sequence emits ONE RequestOutcome — the goodput plane's input
        self.outcome_sink = None
        # speculative decoding: parsed config + the draft proposer (history
        # in, <= k token ids out). None when --speculative is unset.
        self.spec = config.spec
        self.proposer = make_proposer(self.spec) if self.spec is not None else None
        # fleet-wide prefix cache: the pull client (disagg/prefix_fetch.py
        # PrefixFetchClient) the worker attaches; None = fetch disabled and
        # kv_holder hints on requests are ignored
        self.prefix_fetcher = None
        self.prefix_fetch_hits = 0  # fetches that landed >= 1 remote block
        self.prefix_fetch_fallbacks = 0  # timeout/gone/error -> recompute
        self.prefix_fetch_blocks = 0  # blocks pulled and scattered
        self.prefix_fetch_bytes = 0  # payload bytes pulled (wire KV dtype)
        self.prefix_fetch_tokens = 0  # prompt tokens whose recompute was skipped
        # disk KV tier (engine/kv_store.py): scheduler-side resume counters
        # (the store itself counts spills/restores/drops/io at the file layer)
        self.disk_restore_hits = 0  # restores that landed >= 1 disk block
        self.disk_restore_fallbacks = 0  # miss/corrupt head -> recompute
        self.disk_restore_blocks = 0  # blocks promoted disk -> device
        self.disk_restore_tokens = 0  # prompt tokens whose recompute was skipped
        # live migration (disagg/migrate.py): both roles' counters live here
        # so resource_snapshot / dynamo_migration_* render from one place
        self.migration_out = 0  # sequences handed to a peer (stream re-pinned)
        self.migration_out_failed = 0  # handoffs that resumed locally instead
        self.migration_in = 0  # migrated sequences admitted (ADOPTING)
        self.migration_in_pulled = 0  # adoptions whose seq_handoff pull landed
        self.migration_in_recomputed = 0  # adoptions that rebuilt KV from history
        self.migration_tokens_salvaged = 0  # history tokens whose recompute a pull skipped
        # long-context telemetry (dynamo_engine_context_* families): the
        # page-table width ladder, depth-aware chunk planner, and the
        # watermark-driven cold-block drain to the host tier
        self.table_promotions = 0  # sequences promoted to a wider table rung
        #: the model's attention layers come in groups with a page table each
        #: (engine/page_table.py GroupedPageAllocator)
        self.grouped = hasattr(allocator, "release_behind")
        #: layer groups: pages the last decode window's sequences held, by
        #: group (what its attention calls had to read, all layers together)
        self.decode_group_pages: dict = {}
        self.table_dispatches: dict[int, int] = {}  # table width -> dispatches
        self.chunk_dispatches: dict[int, int] = {}  # chunk bucket -> chunks
        self.offload_pressure_blocks = 0  # cold blocks drained to host by watermark
        # multi-tenant QoS (utils/qos.py): per-class preemption victims and
        # critical-triggered sheds (a waiting critical request evicting a
        # lower-class lane); migrate_shed is the hosting worker's hook —
        # (request_id) -> bool — that hands the victim to a peer via live
        # migration instead of preempt+recompute when a servable peer exists
        # expert routing, from the counts a routing model returns per decode
        # window (runner.window_aux): assignments that landed on experts held
        # here, all assignments routed (tokens x experts a token x expert
        # blocks), the last window's busiest held expert over their mean, and
        # the held experts that received a row, summed over steps and expert
        # layers: the matrices a step had to stream
        self.moe_assignments = 0
        self.moe_experts_touched = 0
        self.moe_routed = 0
        self.moe_busiest_over_mean = 0.0
        self.qos_preempted: dict[str, int] = {}
        self.qos_sheds = 0
        self.qos_shed_migrations = 0
        self.migrate_shed = None
        # last time a shed went via migration: the handoff is async (the
        # victim only freezes once migrate_out reaches the engine thread),
        # so without a cooldown every scheduler step until then would
        # migrate ANOTHER lane for the same waiting critical request
        self._last_shed_migration = 0.0

    # ---------------- queue ----------------

    def add_request(self, req: EngineRequest) -> None:
        self.waiting.append(req)

    def has_work(self) -> bool:
        return (
            bool(self.waiting)
            or bool(self.adopted_waiting)
            or bool(self.in_flight)
            or any(s is not None for s in self.slots)
        )

    def oldest_waiting_age(self, now: Optional[float] = None) -> float:
        """Age of the oldest queued request (the watchdog's stuck-queue
        signal). 0 when the queue is empty or unstamped."""
        for req in self.waiting:
            if req.enqueue_ts:
                return max(0.0, (now or time.monotonic()) - req.enqueue_ts)
        return 0.0

    def progress_marker(self) -> int:
        """Monotonic count of completed engine work; a frozen marker while
        has_work() holds means the loop is wedged (watchdog no-progress)."""
        st = self.stage
        return (
            st.prefill_calls + st.decode_windows + st.spec_rounds
            + st.reconcile_waits + self.finished_count
        )

    @property
    def num_running(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    def cancel(self, request_id: str) -> bool:
        for i, s in enumerate(self.slots):
            if s is not None and s.req.request_id == request_id:
                self._release(s, count_finished=False)
                return True
        for s in list(self.adopted_waiting):
            if s.req.request_id == request_id:
                s.finished = True
                self.allocator.free_sequence(request_id)
                self.adopted_waiting.remove(s)
                return True
        for req in list(self.waiting):
            if req.request_id == request_id:
                self.waiting.remove(req)
                return True
        return False

    #: which StageStats fields and stage histogram an anatomy phase feeds:
    #: (seconds, count, histogram), by (kind, phase) or by phase alone
    _STAGE_FEEDS = {
        ("decode_window", "dispatch"): ("decode_dispatch_s", "decode_windows", "decode_window"),
        ("prefill_packed", "dispatch"): ("prefill_s", "prefill_calls", "prefill"),
        ("prefill_chunk", "dispatch"): ("prefill_s", "prefill_calls", "prefill"),
        "device_wait": ("reconcile_wait_s", "reconcile_waits", "reconcile"),
    }

    def _feed_stage(self, kind: str, phase: str, dt: float) -> None:
        """``StepAnatomy.stage_sink``: the interval a ``phase`` block timed,
        into the always-on aggregates and the Prometheus histogram."""
        feed = self._STAGE_FEEDS.get((kind, phase)) or self._STAGE_FEEDS.get(phase)
        if feed is None:
            return
        seconds, count, hist = feed
        stage = self.stage
        setattr(stage, seconds, getattr(stage, seconds) + dt)
        setattr(stage, count, getattr(stage, count) + 1)
        self.stage_hist[hist].observe(dt)

    # ---------------- main loop step ----------------

    def step(self) -> list[StepOutput]:
        outputs: list[StepOutput] = []
        self._drain_cold_to_host()
        outputs.extend(self._reconcile(block=False))
        outputs.extend(self._admit())
        dispatched = self._poll_fetches(outputs)
        dispatched += self._dispatch_prefill_batches(outputs)
        if self.spec is not None:
            dispatched += self._dispatch_spec_round(outputs)
        dispatched += self._dispatch_windows(outputs)
        if outputs:
            # tokens in hand (the opening reconcile's, or the prefill gate's):
            # the queue is refilled, so hand them over before any device
            # wait; the engine loop posts them and comes straight back
            return outputs
        if self.in_flight and not (dispatched and self._window_room()):
            # the queue holds all it may, or this call added nothing to it
            outputs.extend(self._reconcile(block=True))
        elif not dispatched and not self.in_flight and (
            self._fetching() or self._migrating()
        ):
            # FETCHING_KV / MIGRATING_OUT is the only live work: both resolve
            # on another thread's event loop, so don't hot-spin the engine
            # loop while waiting
            time.sleep(0.001)
        return outputs

    def _windows_in_flight(self) -> int:
        return sum(1 for e in self.in_flight if e.kind == "window")

    def _window_room(self) -> bool:
        """Whether the device's queue may take one more decode window: at
        most ``pipeline_depth`` in flight, and of those at most
        ``pipeline_depth - 1`` that have not started. The oldest in-flight
        entry is what the device is running; where that is a prefill, every
        window in flight is still waiting behind it."""
        depth = max(1, self.config.pipeline_depth)
        windows = self._windows_in_flight()
        if self.in_flight and self.in_flight[0].kind != "window":
            return windows < max(1, depth - 1)
        return windows < depth

    def _note_windows_ahead(self) -> int:
        """The decode windows a prefill dispatched now stands behind on the
        device's queue (in flight, not reconciled), added to the counter
        whose quotient with ``stage.prefill_calls`` says how many windows
        the scheduler commits ahead of a new prompt."""
        ahead = self._windows_in_flight()
        self.stage.prefill_windows_ahead += ahead
        return ahead

    def _prefills_in_flight(self) -> int:
        return sum(
            1 for e in self.in_flight if e.kind in ("first", "first_batch")
        )

    def _drain_cold_to_host(self) -> None:
        """Pressure-driven host offload: once page-pool occupancy crosses
        ``offload_watermark``, move the coldest refcount-0 cached blocks —
        the deep KV of long sequences nothing is actively decoding — to the
        host tier in batches (one device gather each), returning their pages
        to the free list. Allocation bursts and decode growth then find
        fresh pages instead of paying per-block reclaim round trips, or
        preempting whole sequences, at the moment of exhaustion."""
        alloc, cfg = self.allocator, self.config
        if alloc.offload is None or cfg.offload_watermark >= 1.0:
            return
        total = max(1, cfg.num_pages - 1)
        drained = 0
        t0 = time.monotonic()
        while alloc.used_pages / total > cfg.offload_watermark and alloc._reusable:
            moved = alloc.drain_to_host(cfg.offload_drain_batch)
            if not moved:
                break
            self.offload_pressure_blocks += moved
            drained += moved
        if drained:
            dt = time.monotonic() - t0
            self.anatomy.record(
                "offload_drain", dispatch_s=dt, tokens=drained, ts=t0,
            )
            tracing.record_span(
                "engine.offload.drain", t0, duration=dt,
                attrs={"blocks": drained},
            )
            events.emit(
                "offload.drain", request_id="", blocks=drained,
                occupancy=round(alloc.used_pages / total, 4),
            )

    # ---------------- page-table ladder ----------------

    def _new_table(self, state) -> np.ndarray:
        """Page table at the sequence's CURRENT ladder width (pow2 bucket of
        its page count) — not the dense max_pages_per_seq width, so a short
        request in a 128K-capable engine dispatches a narrow table. A model
        with layer groups has one row per attention layer (`[tables, width]`,
        from `GroupedSequencePages.tables`); every other model one row, which
        shows the rest of the newest pages' run too (`SequencePages.entries`:
        the decode kernel fetches a tile that is a run whole)."""
        width = self.config.table_bucket_for(max(1, state.num_pages))
        if self.grouped:
            table = np.zeros((self.allocator.num_tables, width), np.int32)
            table[:, : state.num_pages] = state.tables
            return table
        table = np.zeros(width, np.int32)
        entries = state.entries[:width]
        table[: len(entries)] = entries
        return table

    def _refresh_table(self, seq: RunningSeq) -> None:
        """Re-sync a sequence's table from the allocator, promoting it to
        the next ladder rung when its pages outgrew the current width."""
        state = self.allocator._seqs[seq.req.request_id]
        n = state.num_pages
        if n > seq.page_table.shape[-1]:
            self.table_promotions += 1
            seq.page_table = self._new_table(state)
        elif self.grouped:
            seq.page_table[:, :n] = state.tables
        else:
            entries = state.entries[: seq.page_table.shape[-1]]
            seq.page_table[: len(entries)] = entries
            seq.page_table[len(entries):] = 0  # a reserved page taken back since

    def _batch_tables(self, rows: int, seqs: list) -> tuple:
        """(zeroed page tables for a batch of `rows`, their ladder width): the
        widest of `seqs` sets the width; a model with layer groups gets
        `[rows, tables, width]` (`_flat_tables` before the runner sees it)."""
        W = self.config.table_bucket_for(max(s.page_table.shape[-1] for s in seqs))
        self._count_table_dispatch(W)
        shape = (rows, self.allocator.num_tables, W) if self.grouped else (rows, W)
        return np.zeros(shape, np.int32), W

    @staticmethod
    def _flat_tables(page_tables: np.ndarray) -> np.ndarray:
        """[rows, tables, width] -> [rows, tables * width], table-major (the
        layout models/cohere2_moe.py splits again); [rows, width] as it is."""
        return page_tables.reshape(page_tables.shape[0], -1)

    def _release_behind(self, seq: RunningSeq, position: int) -> None:
        """Layer groups: every query still to be dispatched for `seq` sits at
        `position` or later, so what lies behind a window there goes back to
        the pool (engine/page_table.py `release_behind`)."""
        if self.grouped and self.allocator.release_behind(seq.req.request_id, position):
            self._refresh_table(seq)

    def _count_table_dispatch(self, width: int) -> None:
        self.table_dispatches[width] = self.table_dispatches.get(width, 0) + 1

    # ---------------- admission + prefill ----------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def _admit(self) -> list[StepOutput]:
        outputs = []
        watermark_pages = int(self.config.watermark * self.config.num_pages)
        # adopted sequences first: their pages are already allocated and their
        # first token already emitted — they only need a decode slot
        while self.adopted_waiting:
            slot = self._free_slot()
            if slot is None:
                break
            seq = self.adopted_waiting.popleft()
            seq.slot = slot
            self.slots[slot] = seq
            # seed the device token-feedback buffer with its last token
            self.runner.write_token_slots(
                np.array([slot], np.int32), np.array([seq.generated[-1]], np.int32)
            )
            self.runner.set_slot_lora(slot, seq.lora_slot)
        # admission fairness for the PER-REQUEST prefill path (packed path
        # disabled: pp/sp meshes, multimodal, prefill_lanes=1): starting a
        # sequence there dispatches its whole prefill chain immediately, so
        # cap new starts per step like _dispatch_prefill_batches caps packed
        # calls — a burst must not serialize all its weight passes ahead of
        # running decode windows
        cap = self.config.prefill_batches_per_step
        decode_running = any(
            s is not None and not s.finished and s.prefill_pos is None
            for s in self.slots
        )
        packed_mode = self.runner.packed_prefill_mode
        started = 0
        # multi-LoRA: requests whose adapter is still loading (or whose slots
        # are all pinned) step aside WITHOUT blocking the queue behind them —
        # they re-enter at the queue front next step, so FIFO holds among
        # ready requests and an async adapter load never stalls the engine
        deferred: list[EngineRequest] = []
        try:
            while self.waiting:
                slot = self._free_slot()
                if slot is None:
                    # a waiting critical request may evict a lower-class lane
                    # (preferring live migration when a peer can adopt it)
                    if not self._shed_for_critical(outputs):
                        break
                    slot = self._free_slot()
                    if slot is None:
                        break  # shed went via async migration; slot frees later
                idx = self._next_waiting_index()
                req = self.waiting[idx]
                # reject oversized prompts BEFORE the fairness-cap break: the
                # rejection is pure host work (no chip time), so an oversized
                # prompt at the queue head must fail now, not stall behind the
                # per-step prefill cap (and stall everything queued behind it)
                if len(req.token_ids) > self.config.max_model_len:
                    del self.waiting[idx]
                    events.emit(
                        "sched.admission_rejected",
                        request_id=req.request_id, trace_id=req.trace_id,
                        tenant=req.tenant, priority=req.priority or "",
                        reason="oversized_prompt", prompt_tokens=len(req.token_ids),
                    )
                    self._record_request_error(req)
                    outputs.append(
                        StepOutput(req.request_id, finished=True, finish_reason="error")
                    )
                    continue
                if (
                    cap
                    and decode_running
                    and started >= cap
                    and not (packed_mode and not req.images)
                ):
                    break
                pages_needed = self.allocator.pages_for_prompt(len(req.token_ids))
                if self.allocator.free_pages < pages_needed + watermark_pages:
                    break
                lora_slot = 0
                if req.lora_name:
                    store = getattr(self.runner, "lora_store", None)
                    try:
                        if store is None:
                            raise KeyError("engine has no LoRA adapters configured")
                        lora_slot = store.acquire(req.lora_name)
                    except Exception as e:
                        # unknown adapter / broken source: this request can
                        # never serve — fail it, don't wedge the queue
                        log.warning(
                            "rejecting %s: %s", req.request_id, e
                        )
                        del self.waiting[idx]
                        events.emit(
                            "sched.admission_rejected",
                            request_id=req.request_id, trace_id=req.trace_id,
                            tenant=req.tenant, priority=req.priority or "",
                            reason="lora_unavailable", adapter=req.lora_name,
                        )
                        self._record_request_error(req)
                        outputs.append(StepOutput(
                            req.request_id, finished=True, finish_reason="error"
                        ))
                        continue
                    if lora_slot is None:
                        del self.waiting[idx]
                        deferred.append(req)
                        events.emit(
                            "sched.admission_deferred",
                            request_id=req.request_id, trace_id=req.trace_id,
                            tenant=req.tenant, priority=req.priority or "",
                            reason="lora_loading", adapter=req.lora_name,
                        )
                        continue
                del self.waiting[idx]
                try:
                    self._start_sequence(req, slot, lora_slot=lora_slot)
                    # priority weights compose with the fairness cap: one
                    # start consumes 1/weight cap units, so a critical burst
                    # starts more prefill chains per step than batch work at
                    # the same configured cap (all-standard traffic consumes
                    # exactly 1 each — the pre-QoS behavior)
                    started += (
                        1.0 / priority_weight(req.priority)
                        if self.config.qos else 1.0
                    )
                except MemoryError:
                    self._release_lora_name(req.lora_name, lora_slot)
                    self.waiting.appendleft(req)
                    break
                except Exception:
                    # admission died mid-flight (e.g. a trace error on the first
                    # prefill): fail THIS request — it is in no queue or slot
                    # anymore, so nothing else would ever answer its caller
                    log.exception("admission failed for %s", req.request_id)
                    events.emit(
                        "sched.admission_rejected",
                        request_id=req.request_id, trace_id=req.trace_id,
                        tenant=req.tenant, priority=req.priority or "",
                        reason="admission_error",
                    )
                    self._release_lora_name(req.lora_name, lora_slot)
                    if req.request_id in self.allocator._seqs:
                        self.allocator.free_sequence(req.request_id)
                    if self.slots[slot] is not None and self.slots[slot].req is req:
                        self.slots[slot] = None
                    self._record_request_error(req)
                    outputs.append(
                        StepOutput(req.request_id, finished=True, finish_reason="error")
                    )
        finally:
            self.waiting.extendleft(reversed(deferred))
        return outputs

    # ---------------- multi-tenant QoS (utils/qos.py) ----------------

    def _next_waiting_index(self) -> int:
        """Admission order under QoS: the first waiting request of the
        highest priority class present (FIFO within a class — all-standard
        traffic admits in exactly the pre-QoS order). QoS disabled = plain
        FIFO."""
        if not self.config.qos or len(self.waiting) < 2:
            return 0
        best_i, best_rank = 0, priority_rank(self.waiting[0].priority)
        for i, req in enumerate(self.waiting):
            if i == 0:
                continue
            r = priority_rank(req.priority)
            if r < best_rank:
                best_i, best_rank = i, r
                if r == 0:
                    break
        return best_i

    def _shed_for_critical(self, outputs: list[StepOutput]) -> bool:
        """A critical request stuck waiting (no free slot) past the
        qos_preempt_wait gate evicts the lowest-class, most-recent running
        lane. The victim goes via live migration when the hosting worker
        wired a peer hook (``migrate_shed`` — the request survives on
        another worker and the slot frees when the relay takes over),
        otherwise preempt+requeue (never worse than page-pressure
        preemption). Returns True only when a slot was freed NOW."""
        if not self.config.qos or not self.waiting:
            return False
        req = self.waiting[self._next_waiting_index()]
        if priority_rank(req.priority) != 0:
            return False
        if req.enqueue_ts and (
            time.monotonic() - req.enqueue_ts
            < self.config.qos_preempt_wait_ms / 1e3
        ):
            return False  # transient full house: don't thrash lanes
        victims = [
            s for s in self.slots
            if s is not None and not s.finished and not s.migrating
            and priority_rank(s.req.priority) > 0
        ]
        if not victims:
            return False  # never shed critical for critical
        victim = max(
            victims,
            key=lambda s: (priority_rank(s.req.priority), s.admitted_order),
        )
        now = time.monotonic()
        if self.migrate_shed is not None and (
            now - self._last_shed_migration
            < max(0.05, self.config.qos_preempt_wait_ms / 1e3)
        ):
            return False  # a shed handoff is already in flight; let it land
        self.qos_sheds += 1
        events.emit(
            "qos.shed",
            request_id=victim.req.request_id, trace_id=victim.req.trace_id,
            tenant=victim.req.tenant, priority=victim.req.priority or "",
            site="engine", waiting_critical=req.request_id,
            via="migration" if self.migrate_shed is not None else "preempt",
        )
        if self.migrate_shed is not None:
            try:
                if self.migrate_shed(victim.req.request_id):
                    self._last_shed_migration = now
                    self.qos_shed_migrations += 1
                    log.info(
                        "QoS shed: migrating %s (%s) for waiting critical %s",
                        victim.req.request_id,
                        victim.req.priority or "standard", req.request_id,
                    )
                    return False  # slot frees when the handoff completes
            except Exception:
                log.exception("migrate_shed hook failed; preempting instead")
        # preempt contract: drain so victim.generated is authoritative
        if self.in_flight:
            outputs.extend(self._reconcile(block=True, drain=True))
        if victim.finished or self.slots[victim.slot] is not victim:
            return self._free_slot() is not None  # drain finished it anyway
        log.info(
            "QoS shed: preempting %s (%s) for waiting critical %s",
            victim.req.request_id, victim.req.priority or "standard",
            req.request_id,
        )
        self._preempt(victim)
        return True

    # ---------------- multi-LoRA helpers ----------------

    def _lora_salt(self, req: EngineRequest) -> int:
        """Adapter uid folded into this request's KV block identity (0 =
        base): adapter-specific prefixes never cross-hit — locally, in the
        router radix, or over the fleet pull path."""
        if not req.lora_name:
            return 0
        from dynamo_tpu.lora.adapter import lora_uid

        return lora_uid(req.lora_name)

    def _release_lora_name(self, name: str, lora_slot) -> None:
        if name and lora_slot:
            store = getattr(self.runner, "lora_store", None)
            if store is not None:
                store.release(name)

    def _release_lora(self, seq: RunningSeq) -> None:
        self._release_lora_name(seq.req.lora_name, seq.lora_slot)
        seq.lora_slot = 0

    def _bill(self, req: EngineRequest, weight: float) -> tuple:
        """One cost-attribution bill row for a dispatch record: the meter
        splits the record's phase seconds across its rows proportional to
        ``weight`` (utils/metering.py MeterLedger.on_phase)."""
        return (
            req.request_id, req.tenant, req.lora_name,
            req.priority or "", weight,
        )

    def _charge_admission(self, req: EngineRequest, wait) -> None:
        """Post a newly-admitted request's ledger charges: queued-seconds,
        plus the SAME admitted-token cost the QoS bucket debited at the front
        door (prompt + output budget) — once per request, preemption
        re-admissions excluded (cost_admitted survives the requeue)."""
        if self.meter is None:
            return
        if wait:
            self.meter.queued(req.tenant, wait)
        if not req.cost_admitted:
            req.cost_admitted = True
            self.meter.charge_tokens(
                req.tenant, "admitted",
                len(req.token_ids) + max(0, req.sampling.max_tokens),
            )

    def _start_sequence(self, req: EngineRequest, slot: int, lora_slot: int = 0) -> None:
        wait = None
        now = time.monotonic()
        if req.enqueue_ts:
            wait = max(0.0, now - req.enqueue_ts)
            self.stage.queue_wait_s += wait
            self.stage.queue_wait_n += 1
            self.stage_hist["queue_wait"].observe(wait)
            if self.slo is not None:
                self.slo.observe(
                    "queue_wait", wait, tenant=req.tenant,
                    priority=req.priority or "",
                )
            tracing.record_span(
                "engine.queue_wait", now - wait, end=now,
                request_id=req.request_id, trace_id=req.trace_id,
            )
        events.emit(
            "sched.admitted",
            request_id=req.request_id, trace_id=req.trace_id,
            tenant=req.tenant, priority=req.priority or "",
            slot=slot, queue_wait_ms=round(wait * 1e3, 3) if wait else 0.0,
        )
        self._charge_admission(req, wait)
        cached_len, state = self.allocator.allocate_sequence(
            req.request_id, req.token_ids, salt=self._lora_salt(req),
            owner=(req.tenant, req.request_id),
        )
        prompt_len = len(req.token_ids)
        page_table = self._new_table(state)

        seq = RunningSeq(
            req=req,
            slot=slot,
            prompt_len=prompt_len,
            cached_len=cached_len,
            page_table=page_table,
            admitted_order=self._admit_counter,
            sched_len=1,  # the prefill's sampled token enters the timeline now
            spec_mode=self._spec_eligible(req),
            lora_slot=lora_slot,
            queue_wait_s=wait,
            admitted_ts=now,
        )
        self._admit_counter += 1
        # decode windows read each slot's adapter id from the device-resident
        # slot_state vector; write it once here (no per-window H2D)
        self.runner.set_slot_lora(slot, lora_slot)

        if req.kv_handoff_seq:
            self.migration_in += 1
        fetch = self._maybe_start_fetch(req, cached_len, prompt_len)
        if fetch is None:
            # no remote holder (or it lost): a cold-parked session's blocks
            # may still sit on the local disk tier — same FETCHING_KV wait
            fetch = self._maybe_start_disk_restore(req, cached_len, prompt_len)
        if self.runner.packed_prefill_mode and not req.images:
            # packed path: per-request prep now, chunk dispatch deferred to
            # _dispatch_prefill_batches so chunks of DIFFERENT sequences can
            # share one weight pass
            self._prep_prefill(req, slot, prompt_len)
            seq.prefill_pos = cached_len
            seq.fetch = fetch
            self.slots[slot] = seq
            return
        if fetch is not None:
            # FETCHING_KV on the per-request path: hold the chunk dispatch
            # until the pull resolves (hit -> prefill only the tail past the
            # pulled prefix; miss -> prefill from cached_len as if no holder)
            seq.prefill_pos = cached_len
            seq.fetch = fetch
            self.slots[slot] = seq
            return

        # dispatch-ahead: chunks run without any host sync; the final chunk
        # samples, seeds tokens_dev[slot] on device, and async-copies the token
        result = self._dispatch_prefill_chunks(
            req, page_table, cached_len, prompt_len, slot=slot, lora_slot=lora_slot
        )
        tok_dev, lp = result if isinstance(result, tuple) else (result, None)
        self.allocator.commit_prefilled(req.request_id, prompt_len)
        seq.prefill_dispatched_ts = time.monotonic()
        self.slots[slot] = seq
        self.in_flight.append(
            _InFlight(kind="first", dev=tok_dev, seqs=[seq], cached_len=cached_len,
                      lp=lp, rec=self._last_prefill_rec)
        )

    # ---------------- fleet-wide prefix fetch (FETCHING_KV) ----------------

    def _maybe_start_fetch(
        self, req: EngineRequest, cached_len: int, prompt_len: int
    ) -> Optional[_PrefixFetch]:
        """Kick a remote-prefix pull when the router attached a holder whose
        matched prefix beats our local cache by >= prefix_fetch_min_blocks.
        Returns the FETCHING_KV handle, or None (prefill proceeds normally).

        A migration adoption (req.kv_handoff_seq) rides the same machinery
        with the ``seq_handoff`` fetch kind, its own deadline belt
        (migration_timeout_s), and a 1-block advantage bar — any committed
        block the source still holds beats recomputing it."""
        handoff = bool(req.kv_handoff_seq)
        if (
            self.prefix_fetcher is None
            or not self.allocator.match_prefix  # pages without state: refused
            or not req.kv_holder_addr
            or req.kv_holder_blocks <= 0
        ):
            if handoff and (prompt_len - 1) // self.config.page_size > cached_len // self.config.page_size:
                # no pull possible: the adoption rebuilds KV from history
                self.migration_in_recomputed += 1
            return None
        if not handoff and not self.config.prefix_fetch:
            return None
        ps = self.config.page_size
        base = cached_len // ps
        # never consume the entire prompt from cache: the final token must
        # prefill so the model produces next-token logits (same rule the
        # local prefix cache applies in allocate_sequence)
        want_to = min(req.kv_holder_blocks, (prompt_len - 1) // ps)
        min_blocks = 1 if handoff else max(1, self.config.prefix_fetch_min_blocks)
        if want_to - base < min_blocks:
            return None
        state = self.allocator._seqs[req.request_id]
        hashes = [b.sequence_hash for b in state.token_seq.blocks[base:want_to]]
        if not hashes:
            return None
        timeout = (
            self.config.migration_timeout_s if handoff
            else self.config.prefix_fetch_timeout_s
        )
        try:
            fut = self.prefix_fetcher.fetch(
                req.kv_holder_addr, hashes, timeout_s=timeout,
                kind="seq_handoff" if handoff else "prefix_fetch",
                seq_id=req.kv_handoff_seq,
            )
        except Exception:
            log.exception("prefix fetch start failed for %s", req.request_id)
            if handoff:
                self.migration_in_recomputed += 1
            return None
        now = time.monotonic()
        log.debug(
            "%s for %s: blocks [%d, %d) from %s",
            "seq handoff pull" if handoff else "prefix fetch",
            req.request_id, base, want_to, req.kv_holder_addr,
        )
        return _PrefixFetch(
            fut=fut, base_block=base, t0=now, belt_deadline=now + timeout + 2.0,
            handoff=handoff,
        )

    def _maybe_start_disk_restore(
        self, req: EngineRequest, cached_len: int, prompt_len: int
    ) -> Optional[_PrefixFetch]:
        """Kick an async disk->HBM restore when the disk tier holds the
        chain past our device+host cached prefix (a cold session resuming).
        Rides the same FETCHING_KV parking as the fleet prefix pull — the
        engine loop never blocks on file I/O; the worker thread reads,
        verifies, and dequantizes, and ``_poll_fetches`` scatters the result
        exactly like a remote part."""
        disk = getattr(self.allocator.offload, "disk", None)
        if disk is None or len(disk) == 0:
            return None
        ps = self.config.page_size
        base = cached_len // ps
        # same never-consume-the-whole-prompt rule as every other tier
        want_to = (prompt_len - 1) // ps
        if want_to <= base:
            return None
        state = self.allocator._seqs[req.request_id]
        hashes = [b.sequence_hash for b in state.token_seq.blocks[base:want_to]]
        if not hashes or hashes[0] not in disk:
            return None
        fut = disk.restore_async(hashes)
        now = time.monotonic()
        log.debug(
            "disk restore for %s: blocks [%d, %d)", req.request_id, base, want_to
        )
        return _PrefixFetch(
            fut=fut, base_block=base, t0=now,
            belt_deadline=now + self.config.prefix_fetch_timeout_s + 2.0,
            disk=True,
        )

    def _fetching(self) -> bool:
        return any(
            s is not None and not s.finished and s.fetch is not None
            for s in self.slots
        )

    def _migrating(self) -> bool:
        return any(
            s is not None and not s.finished and s.migrating
            for s in self.slots
        )

    def _poll_fetches(self, outputs: list[StepOutput]) -> int:
        """Resolve FETCHING_KV sequences: scatter pulled pages and advance
        prefill_pos past them on a hit, fall back to recompute on anything
        else. Returns the number of sequences released (dispatch count for
        the step loop)."""
        resolved = 0
        for seq in list(self.slots):
            if seq is None or seq.finished or seq.fetch is None:
                continue
            f = seq.fetch
            res = None
            timed_out = False
            if f.fut.done():
                try:
                    res = f.fut.result()
                except Exception:
                    log.exception(
                        "prefix fetch future failed for %s", seq.req.request_id
                    )
            elif time.monotonic() >= f.belt_deadline:
                # the client's own timeout should have fired long ago — its
                # loop is gone; a dead fetcher must never wedge admission
                f.fut.cancel()
                timed_out = True
                log.warning(
                    "prefix fetch for %s missed the belt deadline; recomputing",
                    seq.req.request_id,
                )
            else:
                continue
            seq.fetch = None
            resolved += 1
            dt = time.monotonic() - f.t0
            if not f.disk:
                self.stage_hist["prefix_fetch"].observe(dt)
            applied = 0
            if res is not None and getattr(res, "status", "") == "hit" and res.blocks:
                applied = self._scatter_fetched(seq, f, res)
            if f.disk:
                self._resolve_disk_restore(seq, f, res, applied, dt, timed_out)
                self._resume_after_fetch(seq, outputs)
                continue
            if applied:
                ps = self.config.page_size
                new_cached = (f.base_block + applied) * ps
                self.prefix_fetch_hits += 1
                self.prefix_fetch_blocks += applied
                self.prefix_fetch_bytes += res.bytes
                self.prefix_fetch_tokens += max(0, new_cached - seq.prefill_pos)
                if f.handoff:
                    self.migration_in_pulled += 1
                    self.migration_tokens_salvaged += max(
                        0, new_cached - seq.prefill_pos
                    )
                seq.prefill_pos = max(seq.prefill_pos, new_cached)
                seq.cached_len = max(seq.cached_len, new_cached)
                tracing.record_span(
                    "engine.prefix_fetch", f.t0, duration=dt,
                    request_id=seq.req.request_id, trace_id=seq.req.trace_id,
                    attrs={"blocks": applied, "bytes": res.bytes,
                           "holder": seq.req.kv_holder_addr,
                           "handoff": f.handoff},
                )
                events.emit(
                    "prefix_fetch.hit",
                    request_id=seq.req.request_id, trace_id=seq.req.trace_id,
                    tenant=seq.req.tenant, priority=seq.req.priority or "",
                    blocks=applied, bytes=res.bytes, handoff=f.handoff,
                    holder=seq.req.kv_holder_addr,
                )
            else:
                self.prefix_fetch_fallbacks += 1
                if f.handoff:
                    self.migration_in_recomputed += 1
                status = getattr(res, "status", "dead") if res is not None else "dead"
                log.info(
                    "%s for %s fell back to recompute (%s)",
                    "seq handoff pull" if f.handoff else "prefix fetch",
                    seq.req.request_id, status,
                )
                events.emit(
                    "prefix_fetch.timeout"
                    if timed_out or status == "timeout"
                    else "prefix_fetch.fallback",
                    request_id=seq.req.request_id, trace_id=seq.req.trace_id,
                    tenant=seq.req.tenant, priority=seq.req.priority or "",
                    status=status, handoff=f.handoff, waited_ms=round(dt * 1e3, 3),
                )
            self._resume_after_fetch(seq, outputs)
        return resolved

    def _resolve_disk_restore(
        self, seq: RunningSeq, f: _PrefixFetch, res, applied: int, dt: float,
        timed_out: bool,
    ) -> None:
        """Book a resolved disk restore: promote scattered blocks
        disk->device (their advertised identity stays valid — no removed
        event), drop corrupt blocks truthfully, advance prefill past the
        restored prefix, and journal the outcome."""
        failed = list(getattr(res, "failed", ()) or ()) if res is not None else []
        if applied:
            ps = self.config.page_size
            new_cached = (f.base_block + applied) * ps
            self.disk_restore_hits += 1
            self.disk_restore_blocks += applied
            self.disk_restore_tokens += max(0, new_cached - seq.prefill_pos)
            self.allocator.promote_restored(
                seq.req.request_id, f.base_block, applied
            )
            seq.prefill_pos = max(seq.prefill_pos, new_cached)
            seq.cached_len = max(seq.cached_len, new_cached)
            tracing.record_span(
                "engine.disk_restore", f.t0, duration=dt,
                request_id=seq.req.request_id, trace_id=seq.req.trace_id,
                attrs={"blocks": applied, "bytes": res.bytes},
            )
        else:
            self.disk_restore_fallbacks += 1
            log.info(
                "disk restore for %s fell back to recompute (%s)",
                seq.req.request_id,
                "belt_timeout" if timed_out
                else getattr(res, "status", "dead") if res is not None
                else "dead",
            )
        if failed:
            # corrupt/truncated files left their last tier: one truthful
            # removed per block; the tail past them recomputes
            self.allocator.drop_disk_blocks(failed)
        events.emit(
            "offload.disk_restore",
            request_id=seq.req.request_id, trace_id=seq.req.trace_id,
            tenant=seq.req.tenant, priority=seq.req.priority or "",
            blocks=applied, corrupt=len(failed),
            waited_ms=round(dt * 1e3, 3),
            outcome="hit" if applied else "fallback",
        )

    def _scatter_fetched(self, seq: RunningSeq, f: _PrefixFetch, res) -> int:
        """Inject pulled parts into the sequence's pre-allocated pages.
        Returns the contiguous block count applied (0 on any failure — the
        recompute simply overwrites whatever partially landed)."""
        state = self.allocator._seqs.get(seq.req.request_id)
        if state is None:
            return 0
        t0 = time.monotonic()
        try:
            applied = 0
            for part in res.parts:
                if part.block_from != applied:
                    break  # hole: only the contiguous leading run is cached
                ids = np.asarray(
                    state.pages[f.base_block + part.block_from:
                                f.base_block + part.block_to],
                    np.int32,
                )
                if len(ids) != part.block_to - part.block_from:
                    break
                self.runner.inject_pages_bucketed(ids, part.data, axis=part.cat_axis)
                applied = part.block_to
            if applied:
                self.anatomy.record(
                    "prefix_fetch_scatter", dispatch_s=time.monotonic() - t0,
                    tokens=applied, ts=t0, bill=[self._bill(seq.req, 1.0)],
                )
            return applied
        except Exception:
            log.exception(
                "scatter of fetched prefix failed for %s; recomputing",
                seq.req.request_id,
            )
            return 0

    def _resume_after_fetch(self, seq: RunningSeq, outputs: list[StepOutput]) -> None:
        """Release a sequence from FETCHING_KV into its prefill path."""
        if seq.finished or self.slots[seq.slot] is not seq:
            return
        req = seq.req
        if self.runner.packed_prefill_mode and not req.images:
            return  # prefill_pos is live again; the packed dispatcher takes over
        try:
            result = self._dispatch_prefill_chunks(
                req, seq.page_table, seq.prefill_pos, seq.prompt_len, slot=seq.slot,
                lora_slot=seq.lora_slot,
            )
        except Exception:
            log.exception("prefill after prefix fetch failed for %s", req.request_id)
            outputs.extend(self._finish(seq, "error"))
            return
        tok_dev, lp = result if isinstance(result, tuple) else (result, None)
        self.allocator.commit_prefilled(req.request_id, seq.prompt_len)
        seq.prefill_pos = None
        seq.prefill_dispatched_ts = time.monotonic()
        self.in_flight.append(_InFlight(
            kind="first", dev=tok_dev, seqs=[seq], cached_len=seq.cached_len,
            lp=lp, rec=self._last_prefill_rec,
        ))

    def _dispatch_prefill_batches(self, outputs: list[StepOutput]) -> int:
        """Pack pending prefill chunks of several sequences into shared
        prefill calls (one weight pass per call — the reference's engines
        batch prefills the same way; SURVEY.md §2.4 vLLM scheduler). Each
        sequence contributes at most one chunk per call (`chunk_len_for`'s
        length: the longest stall a decode stream sees is one call's). The
        pack is made of blocks (`_pack_blocks`: each chunk padded to its own
        next block, N the number of blocks), or for a model with recurrent
        layers of whole chunks a lane (`_pack_rectangle`). A single pending
        chunk rides the packed program too, at N = its blocks (or 1).

        Fairness: dispatches at most ``config.prefill_batches_per_step``
        calls per invocation when decode work is running, so a burst of new
        prompts cannot serialize all its weight passes ahead of the decode
        windows that running streams' ITL depends on (step() alternates back
        here after the windows dispatch).

        Dispatch-ahead (``config.prefill_pipeline_depth``): every packed
        call leaves an in-flight entry, and up to depth calls ride
        unreconciled so call N+1's host prep + dispatch overlap call N's
        device time — the same pipelining decode windows get from
        ``pipeline_depth``. depth=1 block-reconciles each call before the
        next dispatches (the old mixed-regime behavior; every such forced
        wait counts in ``stage.prefill_stalls``)."""
        count = 0
        cap = self.config.prefill_batches_per_step
        depth = max(1, self.config.prefill_pipeline_depth)
        decode_running = any(
            s is not None and not s.finished and s.prefill_pos is None
            for s in self.slots
        )
        while True:
            if cap and decode_running and count >= cap:
                return count
            # prefill pipeline gate: never hold more than depth prefill
            # dispatches unreconciled. depth>=2 first drains entries whose
            # results already landed (no stall); depth=1 skips the readiness
            # poll — its contract is a strict reconcile between calls.
            while self._prefills_in_flight() >= depth:
                if depth > 1:
                    outputs.extend(self._reconcile(block=False))
                    if self._prefills_in_flight() < depth:
                        break
                self.stage.prefill_stalls += 1
                outputs.extend(self._reconcile(block=True))
            # host_prep is a span of its own (one clock pair, no record yet):
            # a device gap under it has its name in a trace
            with tracing.span("engine.prefill_packed.host_prep") as prep:
                pending = sorted(
                    (s for s in self.slots
                     if s is not None and not s.finished and s.prefill_pos is not None
                     and s.fetch is None),  # FETCHING_KV: hold until the pull resolves
                    key=lambda s: s.admitted_order,
                )
                if not pending:
                    return count
                backlog_rows = sum(s.prompt_len - s.prefill_pos for s in pending)
                # two packers, chosen by what the model carries from row to
                # row, because the needs conflict: a recurrent state flows
                # ALONG a lane, so each lane is one whole chunk of another
                # sequence (the rectangle); where the page cache is all a
                # prefill carries, a lane is a mere block of rows, several of
                # them may belong to one sequence, and the pack computes the
                # rows it holds
                if self.runner.recurrent:
                    chunks, bucket, N = self._pack_rectangle(pending, backlog_rows, outputs)
                    pieces = chunks
                else:
                    chunks, pieces = self._pack_blocks(pending, backlog_rows, outputs)
                    bucket, N = self.config.prefill_block, len(pieces)
                if not chunks:
                    return count
                lanes = []
                finals = []  # (seq, lane_idx)
                want_lp = False
                for j, (seq, start, end) in enumerate(pieces):
                    is_final = end == seq.prompt_len
                    lanes.append((
                        np.asarray(seq.req.token_ids[start:end], np.int32),
                        start,
                        seq.page_table,
                        seq.slot,
                        seq.req.sampling,
                        () if seq.req.sampling.ignore_eos else seq.req.eos_token_ids,
                        is_final,
                        seq.lora_slot,
                    ))
                    if is_final:
                        finals.append((seq, j))
                        want_lp = want_lp or seq.req.logprobs is not None
                rows = sum(end - start for _, start, end in chunks)
                self.local_prefill_rows += rows
                for _, start, end in chunks:
                    cb = self.config.bucket_for(end - start)
                    self.chunk_dispatches[cb] = self.chunk_dispatches.get(cb, 0) + 1
                width = self.config.table_bucket_for(
                    max(s.page_table.shape[-1] for s, _, _ in chunks)
                )
                self._count_table_dispatch(width)
                rec = self.anatomy.begin(
                    "prefill_packed", ts=prep.t0,
                    # cost split: each sequence pays for its own rows in the pack
                    bill=[self._bill(s.req, end - start) for s, start, end in chunks],
                )
            self.anatomy.add_phase(rec, "host_prep", prep.dt)
            try:
                with self.anatomy.phase(
                    rec, "dispatch", request_id=chunks[0][0].req.request_id,
                    trace_id=chunks[0][0].req.trace_id,
                    rows=rows, lanes=N, tile=prefill_tiles.get(width, 0),
                    # what the pack holds: the rows the program computes, and
                    # the context already in the cache under its chunks (one
                    # per sequence: a chunk's later blocks add nothing)
                    padded=N * bucket,
                    ctx=sum(start for _, start, _ in chunks),
                    packed=True, finals=len(finals),
                    windows_ahead=self._note_windows_ahead(),
                ) as ph:
                    result = self.runner.prefill_chunk_batch(
                        lanes, N=N, want_logprobs=want_lp, bucket=bucket
                    )
            except Exception:
                log.exception(
                    "packed prefill failed for %s",
                    [seq.req.request_id for seq, _, _ in chunks],
                )
                for seq, _, _ in chunks:
                    outputs.extend(self._finish(seq, "error"))
                continue
            self.stage.prefill_rows += rows
            self.stage.prefill_padded_rows += N * bucket
            self.anatomy.note_steps(rec, tokens=rows, participants=len(chunks))
            self.anatomy.note_prefill_floor(rec, rows)
            for j, (seq, start, end) in enumerate(chunks):
                if end == seq.prompt_len:
                    self.allocator.commit_prefilled(seq.req.request_id, seq.prompt_len)
                    seq.prefill_pos = None
                    seq.prefill_dispatched_ts = ph.t1
                else:
                    seq.prefill_pos = end
                # the next chunk, or the first decode step, starts at `end`
                self._release_behind(seq, end)
            toks_dev, lp = result if want_lp else (result, None)
            # EVERY pack (not just final-bearing ones) rides the in-flight
            # queue: the pipeline gate above counts it, and its reconcile
            # attributes the pack's device_wait to the dispatch that caused
            # it — a non-final pack just has no tokens to emit (empty seqs)
            self.in_flight.append(_InFlight(
                kind="first_batch", dev=toks_dev, lp=lp,
                seqs=[(seq, j, seq.cached_len) for seq, j in finals],
                rec=rec,
            ))
            count += 1

    def _pack_rectangle(self, pending: list, backlog_rows: int, outputs: list[StepOutput]):
        """The pack of a model with recurrent layers: one whole chunk a lane,
        every lane another sequence, all padded to the bucket of the longest.
        Returns (chunks [(seq, start, end)], bucket, N)."""
        # greedy bucket-aware packing in admission order: grow the lane
        # set while every taken lane still fits the (possibly enlarged)
        # bucket's row budget — one long head chunk goes alone, short
        # chunks pack together. Each lane's chunk length is depth-aware:
        # chunk_len_for shrinks it as that sequence's prefill advances
        # into a long prompt, keeping per-chunk latency roughly flat —
        # and backlog-aware: a deep pending queue promotes the bucket so
        # the burst takes fewer, larger dispatches.
        # A pack with a lane beyond the first rung of the page-table ladder
        # holds two lanes at most (`lanes_for`).
        chunks = []
        bucket = 0
        wide = False
        first_rung = self.config.table_buckets[0]
        for s in pending:
            limit = self.config.chunk_len_for(s.prefill_pos, backlog_rows=backlog_rows)
            end = min(s.prefill_pos + limit, s.prompt_len)
            cand = self.config.bucket_for(max(bucket, end - s.prefill_pos))
            cand_wide = wide or s.page_table.shape[-1] > first_rung
            if chunks and len(chunks) + 1 > self.config.lanes_for(cand, cand_wide):
                break
            if self.grouped and not self._chunk_pages(s, end, outputs):
                continue
            chunks.append((s, s.prefill_pos, end))
            bucket, wide = cand, cand_wide
        # a later lane's page pressure may have preempted an earlier one
        chunks = [c for c in chunks if self.slots[c[0].slot] is c[0] and not c[0].finished]
        if not chunks:
            return [], 0, 0
        # N rounds up to a power of two so partial packs compile at most
        # log2(lanes_max) executables per bucket, padding <= 2x on the rare
        # odd sizes
        N = min(self.config.lanes_for(bucket, wide), 1 << (len(chunks) - 1).bit_length())
        return chunks, bucket, N

    def _pack_blocks(self, pending: list, backlog_rows: int, outputs: list[StepOutput]):
        """The pack of every other model (`plan_block_pack`): blocks of
        `config.prefill_block` rows, `config.pack_blocks` of them at most,
        filled in admission order. A sequence gives its next chunk
        (`chunk_len_for`'s length, in whole blocks), which rides as so many
        lanes with the sequence's page table and start positions one block
        apart: the layer scatters every lane's new rows before any lane's
        attention reads the pages. A lone chunk takes this path too (the
        packed program at N = its blocks). Returns (chunks [(seq, start, end)],
        one per sequence, and blocks [(seq, start, end)], one per lane)."""
        block = self.config.prefill_block
        asked = []
        for s in pending[: self.config.pack_blocks]:
            limit = self.config.chunk_len_for(s.prefill_pos, backlog_rows=backlog_rows)
            limit = -(-limit // block) * block  # a limit under one block is one block
            asked.append((s.prefill_pos, min(s.prefill_pos + limit, s.prompt_len)))
        plan = plan_block_pack(asked, block, self.config.pack_blocks)
        ends = {i: end for i, _, end in plan}  # each chunk's (cut) end: its last block's
        if self.grouped:
            for i, end in ends.items():
                self._chunk_pages(pending[i], end, outputs)
        # page pressure may have preempted or finished any sequence of the plan
        live = {i for i in ends if self.slots[pending[i].slot] is pending[i]
                and not pending[i].finished}
        chunks = [(pending[i], pending[i].prefill_pos, ends[i]) for i in sorted(live)]
        blocks = [(pending[i], start, end) for i, start, end in plan if i in live]
        return chunks, blocks

    def _chunk_pages(self, seq: RunningSeq, end: int, outputs: list[StepOutput]) -> bool:
        """Layer groups: a window group's pages are taken chunk by chunk, so
        make sure `seq` has them up to `end` before its chunk is dispatched.
        Page pressure takes the decode windows' ladder: drain the pipeline,
        then preempt the youngest other sequence. False: no chunk this time."""
        rid = seq.req.request_id
        while self.slots[seq.slot] is seq and not self.allocator.ensure_capacity(rid, end):
            if self.in_flight:
                self.pressure_drain_count += 1
                outputs.extend(self._reconcile(block=True, drain=True))
                continue
            victim = self._pick_victim(exclude=seq)
            if victim is None:
                outputs.extend(self._finish(seq, "error"))
                return False
            self._preempt(victim)
        if self.slots[seq.slot] is not seq or seq.finished:
            return False
        self._refresh_table(seq)
        return True

    def _prep_prefill(
        self, req: EngineRequest, slot: int, prompt_len: int, cached_len: int = 0
    ) -> None:
        """Per-request device-state prep that must precede any of its prefill
        chunks: vision encode (skipped when every image run sits inside the
        cached prefix — a repeat request never re-runs the vision tower),
        penalty-slot seeding (restoring prior-output counts after a
        preemption; image virtual-token runs excluded — their ids are
        hash-derived arbitrary vocab ids), M-RoPE positions."""
        needs_vision = req.images and any(
            im.offset + im.num_tokens > cached_len for im in req.images
        )
        if needs_vision and req.mm_embeds is None:
            req.mm_embeds = self.runner.encode_images(req.images)
        if (
            req.sampling.min_tokens >= 1
            and not req.sampling.ignore_eos
            and len(req.eos_token_ids) > MAX_EOS_IDS
        ):
            log.warning(
                "min_tokens: %d EOS ids exceed the device limit %d for %s; "
                "the excess are not suppressed on device",
                len(req.eos_token_ids), MAX_EOS_IDS, req.request_id,
            )
        if req.sampling.needs_penalties and slot >= 0:
            pen_ids = np.asarray(req.token_ids, np.int32)
            pen_from = req.penalty_output_from
            if req.images:
                keep = np.ones(len(pen_ids), bool)
                for im in req.images:
                    keep[im.offset : im.offset + im.num_tokens] = False
                if pen_from is not None:
                    pen_from = int(keep[:pen_from].sum())
                pen_ids = pen_ids[keep]
            self.runner.seed_penalty_slot(slot, pen_ids, output_from=pen_from)
        mcfg = getattr(self.runner.model.config, "mrope_section", None)
        if req.images and mcfg is not None and req.mrope_pos is None:
            from dynamo_tpu.llm.multimodal import mrope_positions

            req.mrope_pos, req.mrope_delta = mrope_positions(
                prompt_len, req.images,
                self.runner.model.config.vision.spatial_merge_size,
            )

    def _dispatch_prefill_chunks(
        self, req: EngineRequest, page_table: np.ndarray, cached_len: int,
        prompt_len: int, slot: int, prep: bool = True, lora_slot: int = 0,
    ):
        """Dispatch-ahead chunked prefill: no host sync; the final chunk seeds
        tokens_dev[slot] and returns the token as a device scalar."""
        return self.run_prefill_chunks(
            req, page_table, cached_len, prompt_len, slot=slot, sync=False,
            want_logprobs=req.logprobs is not None, prep=prep, lora_slot=lora_slot,
        )

    def run_prefill_chunks(
        self,
        req: EngineRequest,
        page_table: np.ndarray,
        cached_len: int,
        prompt_len: int,
        slot: int = -1,
        sync: bool = True,
        want_logprobs: bool = False,
        prep: bool = True,
        on_chunk=None,
        lora_slot: int = 0,
    ):
        """Bucket-chunked prefill, skipping the cached prefix; samples the first
        output token on the final chunk. sync=True (disagg prefill-worker path)
        returns it as a host int; sync=False returns the device scalar.
        prep=False skips _prep_prefill (already run at packed-path admission).
        on_chunk(start, end) fires after each chunk's dispatch — the streamed
        disagg export hook: pages finalized by the chunk can be exported (and
        put on the wire) while the next chunk computes."""
        rows = max(0, prompt_len - cached_len)
        self.local_prefill_rows += rows
        width = self.config.table_bucket_for(page_table.shape[-1])
        if rows:
            self._count_table_dispatch(width)
        s = req.sampling
        first_token = None
        with tracing.span("engine.prefill_chunk.host_prep") as host_prep:
            rec = self._last_prefill_rec = self.anatomy.begin(
                "prefill_chunk", ts=host_prep.t0, bill=[self._bill(req, max(1, rows))],
            )
            if prep:
                self._prep_prefill(req, slot, prompt_len, cached_len=cached_len)
            # depth-aware chunk sizing: shrink the chunk as the context
            # deepens so per-chunk latency stays roughly flat at depth
            chunks = []
            start = cached_len
            while start < prompt_len:
                end = min(start + self.config.chunk_len_for(start), prompt_len)
                chunks.append((start, end))
                start = end
        self.anatomy.add_phase(rec, "host_prep", host_prep.dt)
        padded = sum(self.config.bucket_for(end - start) for start, end in chunks)
        # everything past host_prep is dispatch time (sync=True chains block
        # per chunk, so device wait folds into the same phase here)
        with self.anatomy.phase(
            rec, "dispatch", request_id=req.request_id, trace_id=req.trace_id,
            rows=rows, tile=prefill_tiles.get(width, 0), cached=cached_len, sync=sync,
            # what the chunks hold: the rows their programs compute, and the
            # context already in the cache when each starts
            padded=padded,
            ctx=sum(start for start, _ in chunks),
            windows_ahead=self._note_windows_ahead(),
        ):
            for start, end in chunks:
                is_last = end == prompt_len
                cb = self.config.bucket_for(end - start)
                self.chunk_dispatches[cb] = self.chunk_dispatches.get(cb, 0) + 1
                embeds, embeds_mask = _mm_chunk_overrides(req, start, end)
                rope_pos = req.mrope_pos[start:end] if req.mrope_pos is not None else None
                tok = self.runner.prefill_chunk(
                    np.asarray(req.token_ids[start:end], np.int32),
                    start_pos=start,
                    page_table=page_table,
                    sample=is_last,
                    temperature=s.temperature,
                    top_k=s.top_k,
                    top_p=s.top_p,
                    slot=slot if is_last else -1,
                    state_slot=slot,
                    sync=sync,
                    embeds=embeds,
                    embeds_mask=embeds_mask,
                    rope_pos=rope_pos,
                    want_logprobs=want_logprobs and not sync,
                    sampling=s,
                    eos_ids=() if s.ignore_eos else req.eos_token_ids,
                    lora_slot=lora_slot,
                )
                if is_last:
                    first_token = tok
                if on_chunk is not None:
                    on_chunk(start, end)
        self.stage.prefill_rows += rows
        self.stage.prefill_padded_rows += padded
        self.anatomy.note_steps(rec, tokens=rows, participants=1)
        self.anatomy.note_prefill_floor(rec, rows)
        return first_token

    def adopt_prefilled(
        self, req: EngineRequest, first_token: int, cached_len: int = 0
    ) -> list[StepOutput]:
        """Adopt a sequence whose prompt KV was produced remotely (disagg path).

        Pages must already be allocated in the allocator under req.request_id
        and the KV injected; this emits the first token and queues the sequence
        for a decode slot.
        """
        wait = None
        if req.enqueue_ts:
            # the adopted analogue of admission queue wait: submission (on the
            # decode worker) -> remote KV adopted into a decode slot
            now = time.monotonic()
            wait = max(0.0, now - req.enqueue_ts)
            self.stage.queue_wait_s += wait
            self.stage.queue_wait_n += 1
            self.stage_hist["queue_wait"].observe(wait)
            if self.slo is not None:
                self.slo.observe(
                    "queue_wait", wait, tenant=req.tenant,
                    priority=req.priority or "",
                )
            tracing.record_span(
                "engine.queue_wait", now - wait, end=now,
                request_id=req.request_id, trace_id=req.trace_id,
                attrs={"adopted": True},
            )
        events.emit(
            "sched.admitted",
            request_id=req.request_id, trace_id=req.trace_id,
            tenant=req.tenant, priority=req.priority or "",
            adopted=True, cached_tokens=cached_len,
        )
        self._charge_admission(req, wait)
        state = self.allocator._seqs[req.request_id]
        page_table = self._new_table(state)
        lora_slot = 0
        if req.lora_name:
            # adopted sequences arrive with their KV already computed; the
            # adapter must be pinned before any decode window. Blocking here
            # is acceptable: adoption runs rarely and the host copy is
            # usually cached (disagg routes lora requests down the local
            # path, so this is a belt for direct adopters).
            store = getattr(self.runner, "lora_store", None)
            if store is None:
                raise RuntimeError(
                    f"adopted request {req.request_id} names adapter "
                    f"{req.lora_name!r} but the engine has no LoRA adapters"
                )
            lora_slot = store.acquire_blocking(req.lora_name)
            if lora_slot is None:
                raise RuntimeError(
                    f"no free LoRA slot for adopted request {req.request_id}"
                )
        seq = RunningSeq(
            req=req,
            slot=-1,
            prompt_len=len(req.token_ids),
            cached_len=cached_len,
            page_table=page_table,
            admitted_order=self._admit_counter,
            sched_len=1,
            spec_mode=self._spec_eligible(req),
            lora_slot=lora_slot,
            queue_wait_s=wait,
        )
        self._admit_counter += 1
        slot = self._free_slot()
        if slot is not None:
            seq.slot = slot
            self.slots[slot] = seq
            self.runner.write_token_slots(
                np.array([slot], np.int32), np.array([first_token], np.int32)
            )
            self.runner.set_slot_lora(slot, lora_slot)
        else:
            self.adopted_waiting.append(seq)
        return self._emit_token(seq, first_token, cached=cached_len)

    # ---------------- speculative decode (spec rounds) ----------------

    def _spec_eligible(self, req: EngineRequest) -> bool:
        """Spec-mode eligibility, fixed at admission: penalties and logprobs
        need the window path's per-slot device state, min_tokens needs its
        EOS masking, and image requests carry M-RoPE deltas the verify pass
        doesn't model — all of those ride classic decode windows instead
        (correct, just not speculated)."""
        if self.spec is None:
            return False
        s = req.sampling
        return (
            not req.images
            and req.logprobs is None
            and not s.needs_penalties
            and s.min_tokens <= 0
        )

    def _propose_ngram(self, seq: RunningSeq, max_d: int) -> list[int]:
        """Propose via the sequence's incremental suffix index: built once
        from the prompt at the first round, extended with ACCEPTED tokens
        only — each round costs O(tokens accepted since the last round), not
        a full prompt+output rescan."""
        idx = seq.ngram
        if idx is None:
            idx = seq.ngram = self.proposer.index(seq.req.token_ids)
        for t in seq.generated[len(idx) - seq.prompt_len :]:
            idx.append(t)
        return idx.propose(max_d)

    # ---------------- draft-model speculation ----------------

    def _free_draft(self, seq: RunningSeq) -> None:
        draft = getattr(self.runner, "draft", None) if self.runner else None
        if draft is not None and seq.draft_pos is not None:
            draft.free_sequence(seq.req.request_id)
        seq.draft_pos = None

    def _drop_draft(self, seq: RunningSeq, why: str) -> None:
        """Draft pool can't serve this sequence: it keeps verifying (1 token
        per round, still exact) with no proposals for the rest of its life."""
        log.warning("draft cache dropped for %s (%s)", seq.req.request_id, why)
        self._free_draft(seq)
        seq.draft_dead = True

    def _draft_sync(self, seq: RunningSeq, K: int) -> bool:
        """Bring the draft model's KV up to the sequence's history: the
        steady state just extends capacity for this round's k draft rows;
        a fresh (or fallen-behind) sequence chunk-prefills everything but
        the newest token — admission, preemption resume, host-offload
        restores, and remote-prefill adoption all land here, so the draft
        cache is rebuilt from the authoritative token history in every case.
        Returns True when the lane can draft this round."""
        if seq.draft_dead:
            return False
        draft = self.runner.draft
        rid = seq.req.request_id
        behind = None if seq.draft_pos is None else seq.pos - seq.draft_pos
        if behind is not None and not 1 <= behind <= K + 1:
            # catch-up wider than the dispatch's K+1 rows (can't happen in
            # steady state; belt for exotic resume paths): rebuild
            self._free_draft(seq)
            behind = None
        if behind is None:
            hist = list(seq.req.token_ids) + seq.generated
            t0 = time.monotonic()
            if not draft.prefill_sequence(rid, hist[:-1]):
                self._drop_draft(seq, "draft page pool exhausted at prefill")
                return False
            dt = time.monotonic() - t0
            self.stage.spec_draft_prefills += 1
            self.stage.spec_draft_prefill_s += dt
            tracing.record_span(
                "engine.spec.draft_prefill", t0, duration=dt,
                request_id=rid, trace_id=seq.req.trace_id,
                attrs={"tokens": len(hist) - 1},
            )
            seq.draft_pos = len(hist) - 1
            return True
        # fed positions this round reach seq.pos + K - 1
        if not draft.ensure_capacity(rid, seq.pos + K):
            self._drop_draft(seq, "draft page pool exhausted")
            return False
        return True

    def _dispatch_draft_phase(self, candidates: list, K: int):
        """Batched drafting for a draft-model round: one
        ``runner.dispatch_draft`` across every lane whose draft cache is
        live. Fills each candidate's draft list in place (candidates are
        [seq, p, drafts, max_d] records) and returns the [B, K, V] draft-
        probability device array for the verify pass (None when no lane
        drafted)."""
        live = []
        for cand in candidates:
            seq, p, _, max_d = cand
            if max_d > 0 and self._draft_sync(seq, K):
                live.append(cand)
        if not live:
            return None
        B = self.config.max_seqs
        draft = self.runner.draft
        W = self.config.table_bucket_for(max(
            len(draft.table_for(s.req.request_id)) for s, _, _, _ in live
        ))
        V = self.runner.model.config.vocab_size
        positions = np.zeros(B, np.int32)
        tables = np.zeros((B, W), np.int32)
        active = np.zeros(B, bool)
        fed = np.full((B, K + 1), V, np.int32)
        n_feed = np.ones(B, np.int32)
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        top_ps = np.ones(B, np.float32)
        min_ps = np.zeros(B, np.float32)
        seeds = np.zeros(B, np.int32)
        for seq, p, _, max_d in live:
            i = seq.slot
            rid = seq.req.request_id
            table = draft.table_for(rid)
            positions[i] = seq.draft_pos
            tables[i, : len(table)] = table
            active[i] = True
            pending = seq.generated[seq.draft_pos - seq.prompt_len :]
            n_feed[i] = len(pending)
            fed[i, : len(pending)] = pending
            s = seq.req.sampling
            temps[i] = s.temperature
            top_ks[i] = s.top_k
            top_ps[i] = s.top_p
            min_ps[i] = s.min_p
            seeds[i] = fold_seed(s.seed)
        t0 = time.monotonic()
        toks_dev, qs_dev = self.runner.dispatch_draft(
            positions, tables, active, fed, n_feed, temps, top_ks, top_ps,
            min_ps=min_ps, seeds=seeds if np.any(seeds) else None,
        )
        t_disp = time.monotonic()
        toks = np.asarray(toks_dev)  # graftlint: sync-ok draft reconcile point priced by step_anatomy device_wait
        dt = time.monotonic() - t0
        self.stage.spec_draft_calls += 1
        self.stage.spec_draft_s += dt
        self.anatomy.record(
            "spec_draft", dispatch_s=t_disp - t0,
            device_wait_s=time.monotonic() - t_disp,
            steps=K, tokens=int(sum(c[3] for c in live)),
            participants=len(live), ts=t0,
            # cost split: each lane pays for the draft tokens it asked for
            bill=[self._bill(c[0].req, max(1, c[3])) for c in live],
        )
        if tracing.enabled():
            tracing.record_span(
                "engine.spec.draft", t0, duration=dt,
                request_id=live[0][0].req.request_id,
                trace_id=live[0][0].req.trace_id,
                attrs={"participants": len(live), "k": K},
            )
        for cand in live:
            seq, _, _, max_d = cand
            cand[2] = toks[seq.slot, :max_d].tolist()
        return qs_dev

    def _dispatch_spec_round(self, outputs: list[StepOutput]) -> int:
        """One speculative verify round over every spec-mode decode slot.

        Per slot: propose up to k draft tokens — from the sequence's own
        history (n-gram suffix index) or, in draft-model mode, from one
        batched on-device drafting dispatch shared by every lane — then feed
        [anchor, drafts...] at consecutive fed positions through ONE
        multi-query verify pass, and emit the accepted prefix plus the
        correction/bonus token (1..k+1 tokens). Rounds are synchronous — the
        next proposal needs this round's accepted tokens — so the host
        tracks materialized positions exactly; KV written for rejected
        drafts (in the target AND the draft cache) is overwritten by the
        next round at the advanced anchor. Returns 1 when a round ran (the
        step loop's dispatch count)."""
        K = self.spec.k
        draft_mode = self.spec.kind == "draft"
        candidates = []  # mutable [seq, p, drafts, max_d] records
        for seq in sorted(
            [s for s in self.slots if s is not None], key=lambda s: s.admitted_order
        ):
            if (
                seq.finished
                or not seq.spec_mode
                or seq.prefill_pos is not None
                or seq.migrating  # MIGRATING_OUT: frozen for handoff
                or not seq.generated  # first token still in flight
            ):
                continue
            budget = seq.req.sampling.max_tokens - len(seq.generated)
            p = seq.prompt_len + len(seq.generated) - 1  # anchor fed position
            if budget <= 0 or p >= self.config.max_model_len:
                continue
            max_d = max(0, min(K, budget - 1, self.config.max_model_len - 1 - p))
            if draft_mode:
                drafts = None  # filled by the batched draft dispatch below
            else:
                drafts = self._propose_ngram(seq, max_d) if max_d > 0 else []
                max_d = len(drafts)
            # page capacity for the fed rows (anchor..anchor+max_d);
            # same pressure ladder as the window path: drain the pipeline,
            # then preempt, then shrink the proposal to the allocated pages
            need = p + max_d + 1
            while self.slots[seq.slot] is seq and not self.allocator.ensure_capacity(
                seq.req.request_id, need
            ):
                if self.in_flight:
                    self.pressure_drain_count += 1
                    outputs.extend(self._reconcile(block=True, drain=True))
                    continue
                victim = self._pick_victim(exclude=seq)
                if victim is None:
                    cap = self.allocator._seqs[seq.req.request_id].num_pages * \
                        self.config.page_size
                    if cap > p:
                        shrunk = min(max_d, cap - 1 - p)
                        if shrunk < max_d:
                            # page pressure with no victim left: the round
                            # still runs, at a truncated proposal depth
                            events.emit(
                                "sched.spec_degraded",
                                request_id=seq.req.request_id,
                                trace_id=seq.req.trace_id,
                                tenant=seq.req.tenant,
                                priority=seq.req.priority or "",
                                proposed=max_d, degraded_to=shrunk,
                                reason="page_pressure",
                            )
                        max_d = shrunk
                        if drafts is not None:
                            drafts = drafts[:max_d]
                        break
                    outputs.extend(self._finish(seq, "error"))
                    break
                self._preempt(victim)
            if self.slots[seq.slot] is not seq or seq.finished:
                continue
            self._refresh_table(seq)
            candidates.append([seq, p, drafts, max_d])
        # a later candidate's page-pressure preemption can evict an earlier
        # one mid-pass; only still-live slots ride the verify call
        candidates = [
            c for c in candidates
            if not c[0].finished and self.slots[c[0].slot] is c[0]
        ]
        if not candidates:
            return 0

        draft_probs = None
        if draft_mode:
            draft_probs = self._dispatch_draft_phase(candidates, K)
            for c in candidates:
                if c[2] is None:  # lane did not draft (dead/empty budget)
                    c[2], c[3] = [], 0
                else:
                    c[3] = len(c[2])

        t_prep = time.monotonic()
        B = self.config.max_seqs
        # per-round table width: the widest participant's ladder rung (narrow
        # sequences zero-pad into the trash page)
        page_tables, W = self._batch_tables(B, [s for s, _, _, _ in candidates])
        positions = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        fed = np.zeros((B, K + 1), np.int32)
        n_drafts = np.zeros(B, np.int32)
        temps = np.zeros(B, np.float32)
        top_ks = np.zeros(B, np.int32)
        top_ps = np.ones(B, np.float32)
        min_ps = np.zeros(B, np.float32)
        seeds = np.zeros(B, np.int32)
        lora_slots = np.zeros(B, np.int32)
        snapshot = []
        for seq, p, drafts, _ in candidates:
            i = seq.slot
            positions[i] = p
            page_tables[i, ..., : seq.page_table.shape[-1]] = seq.page_table
            active[i] = True
            fed[i, 0] = seq.generated[-1]
            if drafts:
                fed[i, 1 : 1 + len(drafts)] = drafts
            n_drafts[i] = len(drafts)
            s = seq.req.sampling
            temps[i] = s.temperature
            top_ks[i] = s.top_k
            top_ps[i] = s.top_p
            min_ps[i] = s.min_p
            seeds[i] = fold_seed(s.seed)
            lora_slots[i] = seq.lora_slot
            snapshot.append((seq, i, len(drafts), p))

        t0 = time.monotonic()
        out_dev, n_emit_dev = self.runner.dispatch_verify(
            positions, self._flat_tables(page_tables), active, fed, n_drafts, temps, top_ks,
            top_ps, min_ps=min_ps, seeds=seeds if np.any(seeds) else None,
            draft_probs=draft_probs,
            lora_slots=lora_slots if np.any(lora_slots) else None,
        )
        t_disp = time.monotonic()
        tokens = np.asarray(out_dev)  # graftlint: sync-ok verify reconcile point priced by step_anatomy device_wait
        n_emit = np.asarray(n_emit_dev)  # graftlint: sync-ok verify reconcile: n_emit rides the same resolved dispatch
        dt = time.monotonic() - t0
        st = self.stage
        st.spec_rounds += 1
        st.spec_dispatch_s += dt
        # step anatomy: one verify round reads weights + every participant's
        # live pages once (a multi-query pass, not one read per row), so the
        # floor prices like a single decode step at the round's occupancy
        live_pages = sum(
            self.allocator._seqs[s.req.request_id].num_pages
            for s, _, _, _ in candidates
            if s.req.request_id in self.allocator._seqs
        )
        rec = self.anatomy.record(
            "spec_verify", host_prep_s=t0 - t_prep, dispatch_s=t_disp - t0,
            device_wait_s=time.monotonic() - t_disp, steps=1,
            participants=len(candidates),
            floor_bytes=self.anatomy.decode_floor_bytes(live_pages, 1), ts=t_prep,
            # cost split: each candidate pays for its verify rows (anchor +
            # drafts); the reconcile phase below rides the same bill
            bill=[self._bill(s.req, n + 1) for s, _, n, _ in snapshot],
        )
        t_rec = time.monotonic()
        round_proposed = round_accepted = round_emitted = 0
        for seq, i, proposed, p in snapshot:
            if seq.finished:
                continue  # EOS/cancel raced in via a drain above
            emitted = int(n_emit[i])
            accepted = max(0, emitted - 1)
            st.spec_proposed += proposed
            st.spec_accepted += accepted
            st.spec_emitted += emitted
            round_proposed += proposed
            round_accepted += accepted
            round_emitted += emitted
            self.stage_hist["spec_accept"].observe(accepted)
            if draft_mode and seq.draft_pos is not None:
                # accepted draft rows are already fed in the draft cache;
                # the correction/bonus token is next round's catch-up feed,
                # and rejected rows get overwritten at the advanced anchor
                seq.draft_pos = p + 1 + accepted
            for j in range(emitted):
                outputs.extend(self._emit_token(seq, int(tokens[i, j])))
                if seq.finished:
                    break  # stop/length mid-chunk: the tail tokens are dead
        self.anatomy.add_phase(rec, "reconcile", time.monotonic() - t_rec)
        self.anatomy.note_steps(rec, tokens=round_emitted)
        if tracing.enabled():
            tracing.record_span(
                "engine.spec.verify", t0, duration=dt,
                request_id=snapshot[0][0].req.request_id,
                trace_id=snapshot[0][0].req.trace_id,
                attrs={
                    "participants": len(snapshot), "k": K,
                    "proposed": round_proposed, "accepted": round_accepted,
                    "requests": [s.req.request_id for s, _, _, _ in snapshot],
                },
            )
        return 1

    # ---------------- pipelined decode ----------------

    def _dispatch_windows(self, outputs: list[StepOutput]) -> int:
        count = 0
        while self._window_room():
            if not self._dispatch_one_window(outputs):
                break
            count += 1
        return count

    def _plan_steps(self, seq: RunningSeq, K: int) -> int:
        """Steps this window can run for `seq` before budget/length bounds."""
        if seq.prefill_pos is not None:
            return 0  # prefill chunks still pending; no sampled token yet
        if seq.migrating:
            return 0  # MIGRATING_OUT: frozen for handoff, pages stay resident
        if seq.spec_mode:
            return 0  # advances via speculative verify rounds, never windows
        budget = seq.req.sampling.max_tokens - seq.sched_len
        length = self.config.max_model_len - seq.next_fed_pos
        return max(0, min(K, budget, length))

    def _dispatch_one_window(self, outputs: list[StepOutput]) -> bool:
        K = max(1, self.config.decode_steps)

        # capacity pass: every participant needs pages for its planned writes
        # (fed positions next_fed_pos .. next_fed_pos + steps - 1); page tables
        # are static inside the window
        for seq in sorted(
            [s for s in self.slots if s is not None], key=lambda s: s.admitted_order
        ):
            steps = self._plan_steps(seq, K)
            if steps <= 0:
                continue
            need = seq.next_fed_pos + steps
            while self.slots[seq.slot] is seq and not self.allocator.ensure_capacity(
                seq.req.request_id, need
            ):
                # page pressure: drain the pipeline (may free pages via EOS),
                # then preempt the most recent victim
                if self.in_flight:
                    self.pressure_drain_count += 1
                    outputs.extend(self._reconcile(block=True, drain=True))
                    continue
                victim = self._pick_victim(exclude=seq)
                if victim is None:
                    cap = self.allocator._seqs[seq.req.request_id].num_pages * \
                        self.config.page_size
                    if cap > seq.next_fed_pos:
                        break  # shorter window; limits[] freezes at capacity
                    outputs.extend(self._finish(seq, "error"))
                    break
                self._preempt(victim)
            if self.slots[seq.slot] is seq:
                if self.grouped:
                    self.allocator.release_behind(seq.req.request_id, seq.next_fed_pos)
                self._refresh_table(seq)

        # host-prep timing starts AFTER the capacity pass: a pressure drain
        # up there blocks in _reconcile, and that wait is already attributed
        # as device_wait on the drained entries' own records
        with tracing.span("engine.decode_window.host_prep") as prep:
            participants = []
            for seq in self.slots:
                if seq is None or seq.finished:
                    continue
                steps = self._plan_steps(seq, K)
                if steps <= 0:
                    continue
                cap = self.allocator._seqs[seq.req.request_id].num_pages * self.config.page_size
                steps = min(steps, cap - seq.next_fed_pos)
                if steps <= 0:
                    continue
                participants.append((seq, steps))
            if not participants:
                return False

            B = self.config.max_seqs
            # per-window table width: the widest participant's ladder rung —
            # short-sequence batches keep their narrow H2D + gather, and only
            # windows containing a deep sequence dispatch the wide executable
            page_tables, W = self._batch_tables(B, [seq for seq, _ in participants])
            if self.grouped:
                held = sum(np.count_nonzero(seq.page_table, axis=1) for seq, _ in participants)
                self.decode_group_pages = {
                    g.name: int(held[list(g.tables)].sum()) for g in self.allocator.groups
                }
            positions = np.zeros(B, np.int32)
            active = np.zeros(B, bool)
            limits = np.zeros(B, np.int32)
            temps = np.zeros(B, np.float32)
            top_ks = np.zeros(B, np.int32)
            top_ps = np.ones(B, np.float32)
            rope_deltas = np.zeros(B, np.int32)
            min_ps = np.zeros(B, np.float32)
            penalties = np.tile(np.array([[0.0], [0.0], [1.0]], np.float32), (1, B))
            seeds = np.zeros(B, np.int32)
            eos_allowed_from = np.zeros(B, np.int32)
            eos_rows = np.full((B, MAX_EOS_IDS), self.runner.model.config.vocab_size, np.int32)
            any_eos_mask = False

            snapshot = []
            for seq, steps in participants:
                i = seq.slot
                positions[i] = seq.next_fed_pos
                page_tables[i, ..., : seq.page_table.shape[-1]] = seq.page_table
                active[i] = True
                limits[i] = seq.next_fed_pos + steps - 1  # max fed position
                temps[i] = seq.req.sampling.temperature
                top_ks[i] = seq.req.sampling.top_k
                top_ps[i] = seq.req.sampling.top_p
                rope_deltas[i] = seq.req.mrope_delta
                min_ps[i] = seq.req.sampling.min_p
                penalties[0, i] = seq.req.sampling.presence_penalty
                penalties[1, i] = seq.req.sampling.frequency_penalty
                penalties[2, i] = seq.req.sampling.repetition_penalty
                seeds[i] = fold_seed(seq.req.sampling.seed)
                sam = seq.req.sampling
                if sam.min_tokens > 1 and seq.req.eos_token_ids and not sam.ignore_eos:
                    # the decode step sampling generation #k feeds position
                    # prompt_len + k - 2 (prefill sampled #1); EOS is suppressed
                    # while sampling generation #k for k <= min_tokens (vLLM
                    # semantics: min_tokens non-EOS tokens are guaranteed), so it
                    # unblocks at fed position prompt_len + min_tokens - 1
                    eos_allowed_from[i] = seq.prompt_len + sam.min_tokens - 1
                    ids = np.asarray(seq.req.eos_token_ids[:MAX_EOS_IDS], np.int32)
                    eos_rows[i, : len(ids)] = ids
                    any_eos_mask = True
                snapshot.append((seq, i, steps))
                seq.sched_len += steps

            want_lp = any(seq.req.logprobs is not None for seq, _ in participants)
            want_pen = any(seq.req.sampling.needs_penalties for seq, _ in participants)
            # step anatomy: every scanned step reads the weights + each live
            # participant's KV pages — the bytes-moved floor at this occupancy
            live_pages = sum(
                self.allocator._seqs[seq.req.request_id].num_pages
                for seq, _ in participants
                if seq.req.request_id in self.allocator._seqs
            )
            rec = self.anatomy.begin(
                "decode_window", ts=prep.t0,
                # cost split: each participant pays for its scheduled steps
                bill=[self._bill(s.req, max(1, n)) for s, _, n in snapshot],
            )
            steps_total = sum(steps for _, _, steps in snapshot)
        self.anatomy.add_phase(rec, "host_prep", prep.dt)
        with self.anatomy.phase(
            rec, "dispatch", request_id=snapshot[0][0].req.request_id,
            trace_id=snapshot[0][0].req.trace_id,
            participants=len(snapshot), k=K, steps_total=steps_total,
        ):
            result = self.runner.dispatch_decode_window(
                positions, self._flat_tables(page_tables), active, limits, temps, top_ks, top_ps, K,
                want_logprobs=want_lp, rope_deltas=rope_deltas, min_ps=min_ps,
                penalties=penalties if want_pen else None,
                seeds=seeds if np.any(seeds) else None,
                eos_allowed_from=eos_allowed_from if any_eos_mask else None,
                eos_ids=eos_rows if any_eos_mask else None,
            )
        self.stage.decode_steps += K
        self.anatomy.note_steps(
            rec, steps=K, tokens=steps_total, participants=len(snapshot),
            floor_bytes=self.anatomy.decode_floor_bytes(live_pages, K),
        )
        toks_dev, lp = result if want_lp else (result, None)
        self.in_flight.append(_InFlight(
            kind="window", dev=toks_dev, seqs=snapshot, lp=lp, rec=rec,
            aux=getattr(self.runner, "window_aux", None),
        ))
        return True

    def _reconcile(self, block: bool, drain: bool = False) -> list[StepOutput]:
        """Materialize arrived results in dispatch order and emit tokens.

        block: wait for (at least) the oldest entry. drain: wait for all."""
        outputs: list[StepOutput] = []
        while self.in_flight:
            entry = self.in_flight[0]
            ready = _is_ready(entry.dev)
            if not (block or drain) and not ready:
                break
            self.in_flight.popleft()
            # not ready: the host blocks on the device here, the sync wait
            # the dispatch-ahead pipeline exists to hide
            with contextlib.nullcontext() if ready else self.anatomy.phase(
                entry.rec, "device_wait", kind=entry.kind, drain=drain,
            ):
                data = np.asarray(entry.dev)  # graftlint: sync-ok THE priced reconcile point: step_anatomy device_wait source
            block = False
            # host-side materialization (token emission, stop scanning) of
            # this entry attributes back to the dispatch that produced it
            with self.anatomy.phase(entry.rec, "reconcile", kind=entry.kind):
                outputs.extend(self._emit_entry(entry, data))
        return outputs

    def _emit_entry(self, entry: "_InFlight", data: np.ndarray) -> list[StepOutput]:
        """The tokens of one materialized in-flight entry, emitted."""
        outputs: list[StepOutput] = []
        lp = None
        if entry.lp is not None:
            lp = tuple(np.asarray(a) for a in entry.lp)
        if entry.kind == "first":
            seq = entry.seqs[0]
            if not seq.finished:
                outputs.extend(
                    self._emit_token(
                        seq, int(data), cached=entry.cached_len,
                        lp=(lp[0][()], lp[1], lp[2]) if lp is not None else None,
                    )
                )
        elif entry.kind == "first_batch":
            for seq, lane, cached in entry.seqs:
                if seq.finished:
                    continue
                step_lp = None
                if lp is not None and seq.req.logprobs is not None:
                    step_lp = (lp[0][lane], lp[1][lane], lp[2][lane])
                outputs.extend(
                    self._emit_token(seq, int(data[lane]), cached=cached, lp=step_lp)
                )
        else:
            if entry.aux is not None:
                self._count_routing(entry)
            for seq, slot_idx, steps in entry.seqs:
                if seq.finished:
                    continue  # EOS/cancel discovered earlier; zombie tokens
                for j in range(min(steps, data.shape[0])):
                    step_lp = None
                    if lp is not None:
                        step_lp = (lp[0][j, slot_idx], lp[1][j, slot_idx], lp[2][j, slot_idx])
                    outputs.extend(
                        self._emit_token(seq, int(data[j, slot_idx]), lp=step_lp)
                    )
                    if seq.finished:
                        break
        return outputs

    def _count_routing(self, entry: "_InFlight") -> None:
        """One decode window's expert routing, from the device's counts."""
        counts = np.asarray(entry.aux["moe_counts"])
        tokens = sum(steps for _, _, steps in entry.seqs)
        self.moe_assignments += int(counts.sum())
        self.moe_experts_touched += int(np.asarray(entry.aux["moe_touched"]).sum())
        self.moe_routed += tokens * self.runner.model.config.routed_per_token
        mean = float(counts.mean())
        self.moe_busiest_over_mean = float(counts.max()) / mean if mean else 0.0

    @property
    def state_slots_active(self) -> int:
        """Decode slots whose recurrent state is live: a sequence holds its
        slot's state from its first prefill chunk to its finish or preemption
        (0 for a model with no recurrent layers)."""
        if not getattr(self.runner, "recurrent", False):
            return 0
        return sum(s is not None for s in self.slots)

    # ---------------- helpers ----------------

    def _emit_token(
        self, seq: RunningSeq, token: Optional[int], cached: int = 0, lp=None
    ) -> list[StepOutput]:
        if token is None or seq.finished:
            return []
        req = seq.req
        seq.generated.append(token)
        now = time.monotonic()
        if len(seq.generated) == 1:
            seq.first_token_wall = now
            if req.enqueue_ts:
                ttft = max(0.0, now - req.enqueue_ts)
                self.stage.ttft_s += ttft
                self.stage.ttft_n += 1
                self.stage_hist["ttft"].observe(ttft)
                if self.slo is not None:
                    self.slo.observe(
                        "ttft", ttft, tenant=req.tenant,
                        priority=req.priority or "",
                    )
                tracing.record_span(
                    "engine.ttft", req.enqueue_ts, duration=ttft,
                    request_id=req.request_id, trace_id=req.trace_id,
                    attrs={"cached": cached} if cached else None,
                )
                self._observe_first_token_chain(seq, now)
                events.emit(
                    "request.first_token",
                    request_id=req.request_id, trace_id=req.trace_id,
                    tenant=req.tenant, priority=req.priority or "",
                    ttft_ms=round(ttft * 1e3, 3), cached_tokens=cached,
                )
        else:
            # per-token inter-arrival gap at materialization time (a window's
            # tokens land together — the bursty series IS the client view);
            # capped so a 100K-token stream can't grow the record unbounded
            gap = max(0.0, now - seq.last_token_wall)
            if len(seq.itl_gaps) < MAX_ITL_SAMPLES:
                seq.itl_gaps.append(gap)
            if self.slo is not None:
                self.slo.observe(
                    "itl", gap, tenant=req.tenant, priority=req.priority or ""
                )
        seq.last_token_wall = now
        seq.sched_len = max(seq.sched_len, len(seq.generated))
        self.allocator.append_token(req.request_id, token)
        finish: Optional[str] = None
        if (
            (not req.sampling.ignore_eos)
            and req.eos_token_ids
            and token in req.eos_token_ids
            and len(seq.generated) > req.sampling.min_tokens
        ):
            finish = "stop"
        elif len(seq.generated) >= req.sampling.max_tokens:
            finish = "length"
        elif seq.pos >= self.config.max_model_len:
            finish = "length"
        out = StepOutput(req.request_id, token=token, cached_tokens=cached)
        if lp is not None and req.logprobs is not None:
            chosen, top_ids, top_vals = lp
            out.logprob = float(chosen)
            n = min(req.logprobs, len(top_ids))
            if n > 0:
                out.top_logprobs = [
                    (int(top_ids[i]), float(top_vals[i])) for i in range(n)
                ]
        if finish is not None:
            out.finished = True
            out.finish_reason = finish
            self._record_outcome(seq, finish)
            self._release(seq)
        return [out]

    def _observe_first_token_chain(self, seq: RunningSeq, now: float) -> None:
        """Where this first token's time went after admission, observed where
        ``ttft`` is: ``prefill_hold`` (admitted -> the last prefill chunk
        dispatched) and ``first_token_wait`` (that dispatch -> the token
        materialized, at ``now``). For the request, queue_wait + prefill_hold
        + first_token_wait == ttft: the four share their clock reads.

        A preempted request is admitted again as a new sequence whose
        ``enqueue_ts`` is still the client's submission, and each admission
        observes all four from there: the identity holds admission by
        admission, and the later admission's queue_wait holds the first run.
        A sequence adopted with its prompt prefilled elsewhere
        (``adopt_prefilled``) has no stamps and observes neither stage."""
        if not (seq.admitted_ts and seq.prefill_dispatched_ts):
            return
        req = seq.req
        for name, start, end in (
            ("prefill_hold", seq.admitted_ts, seq.prefill_dispatched_ts),
            ("first_token_wait", seq.prefill_dispatched_ts, now),
        ):
            self.stage_hist[name].observe(end - start)
            tracing.record_span(
                f"engine.{name}", start, end=end,
                request_id=req.request_id, trace_id=req.trace_id,
            )

    def _finish(self, seq: RunningSeq, reason: str) -> list[StepOutput]:
        self._record_outcome(seq, reason, error=(reason == "error"))
        self._release(seq)
        return [StepOutput(seq.req.request_id, finished=True, finish_reason=reason)]

    def _record_request_error(self, req: EngineRequest) -> None:
        """Outcome for a request that failed BEFORE a sequence existed
        (oversized prompt, unknown adapter, admission crash): an error is an
        SLO miss, so it must reach the goodput plane like any finish."""
        events.emit(
            "request.failed",
            request_id=req.request_id, trace_id=req.trace_id,
            tenant=req.tenant, priority=req.priority or "",
            reason="rejected",
        )
        events.JOURNAL.pin(req.request_id, "error")
        sink = self.outcome_sink
        if sink is None:
            return
        now = time.monotonic()
        try:
            sink(RequestOutcome(
                request_id=req.request_id,
                scenario=req.scenario,
                tenant=req.tenant,
                adapter=req.lora_name,
                prompt_tokens=len(req.token_ids),
                duration_s=max(0.0, now - req.enqueue_ts) if req.enqueue_ts else 0.0,
                finish_reason="error",
                error=True,
            ))
        except Exception:
            log.exception("outcome sink failed for %s", req.request_id)

    def _record_outcome(self, seq: RunningSeq, reason: str, error: bool = False) -> None:
        """Fold one finished sequence into the goodput plane (one
        RequestOutcome per natural finish; cancels and preemption re-queues
        never reach here). Sink failures must never fail the engine step."""
        req = seq.req
        now = time.monotonic()
        ttft = None
        if seq.first_token_wall and req.enqueue_ts:
            ttft = max(0.0, seq.first_token_wall - req.enqueue_ts)
        events.emit(
            "request.failed" if error else "request.finished",
            request_id=req.request_id, trace_id=req.trace_id,
            tenant=req.tenant, priority=req.priority or "",
            reason=reason, output_tokens=len(seq.generated),
            ttft_ms=round(ttft * 1e3, 3) if ttft is not None else None,
        )
        # forensics auto-pin: a request that errored or blew its TTFT/ITL
        # budget gets its event chain copied to the capture ring NOW, so
        # /debug/requests/{id} still reconstructs it after ring eviction
        if self.meter is not None:
            # consumed-vs-admitted delta: what the request ACTUALLY used,
            # against the (prompt + output budget) the QoS bucket charged
            self.meter.charge_tokens(req.tenant, "prompt", seq.prompt_len)
            self.meter.charge_tokens(req.tenant, "output", len(seq.generated))
        pin_reason = "error" if error else self._slo_pin_reason(seq, ttft)
        if pin_reason:
            events.JOURNAL.pin(req.request_id, pin_reason)
        sink = self.outcome_sink
        if sink is None:
            return
        try:
            sink(RequestOutcome(
                request_id=req.request_id,
                scenario=req.scenario,
                tenant=req.tenant,
                adapter=req.lora_name,
                queue_wait_s=seq.queue_wait_s,
                ttft_s=ttft,
                itl_s=tuple(seq.itl_gaps),
                prompt_tokens=seq.prompt_len,
                output_tokens=len(seq.generated),
                cached_tokens=seq.cached_len,
                duration_s=max(0.0, now - req.enqueue_ts) if req.enqueue_ts else 0.0,
                finish_reason=reason,
                error=error,
            ))
        except Exception:
            log.exception("outcome sink failed for %s", req.request_id)

    def _slo_pin_reason(self, seq: RunningSeq, ttft: Optional[float]) -> Optional[str]:
        """Did this finished sequence blow a configured TTFT/ITL budget?
        (the auto-pin verdict for the forensic capture ring)"""
        if self.slo is None:
            return None
        ttft_target = self.slo.targets.get("ttft")
        if ttft is not None and ttft_target is not None and ttft > ttft_target:
            return "ttft_over_budget"
        itl_target = self.slo.targets.get("itl")
        if itl_target is not None and any(g > itl_target for g in seq.itl_gaps):
            return "itl_over_budget"
        return None

    def _cancel_fetch(self, seq: RunningSeq) -> None:
        """Drop an in-flight remote-prefix pull. The fetch coroutine only
        RETURNS data (the scatter happens in _poll_fetches, which skips
        finished/evicted sequences), so cancelling here can never leave a
        write racing the pages' next owner."""
        if seq.fetch is not None:
            try:
                seq.fetch.fut.cancel()
            except Exception:
                pass
            seq.fetch = None

    def _release(self, seq: RunningSeq, count_finished: bool = True) -> None:
        seq.finished = True
        self._cancel_fetch(seq)
        self._free_draft(seq)
        self._release_lora(seq)
        self.allocator.free_sequence(seq.req.request_id)
        if seq.slot >= 0 and self.slots[seq.slot] is seq:
            self.slots[seq.slot] = None
        elif seq in self.adopted_waiting:
            self.adopted_waiting.remove(seq)
        if count_finished:
            self.finished_count += 1

    def _pick_victim(self, exclude: RunningSeq) -> Optional[RunningSeq]:
        # a MIGRATING_OUT sequence is never a preemption victim: requeueing
        # it locally while the destination continues the same stream would
        # fork the request into two generators
        candidates = [
            s for s in self.slots
            if s is not None and s is not exclude and not s.migrating
        ]
        if not candidates:
            return None
        if self.config.qos:
            # QoS victim order: lowest priority class first (batch lanes pay
            # for page pressure before standard, standard before critical),
            # most-recently-admitted within a class — so a noisy batch burst
            # can never preempt a critical stream while any lower lane runs
            victim = max(
                candidates,
                key=lambda s: (priority_rank(s.req.priority), s.admitted_order),
            )
        else:
            victim = max(candidates, key=lambda s: s.admitted_order)
        events.emit(
            "sched.victim_picked",
            request_id=victim.req.request_id, trace_id=victim.req.trace_id,
            tenant=victim.req.tenant, priority=victim.req.priority or "",
            candidates=len(candidates), qos=bool(self.config.qos),
        )
        return victim

    def _preempt(self, seq: RunningSeq) -> None:
        """Return a sequence to the waiting queue; its work restarts later
        (prefix cache usually recovers most of it). Callers must drain the
        pipeline first so seq.generated is complete."""
        log.info("preempting %s (page pressure)", seq.req.request_id)
        self.preempt_count += 1
        cls = seq.req.priority or "standard"
        self.qos_preempted[cls] = self.qos_preempted.get(cls, 0) + 1
        events.emit(
            "sched.preempted",
            request_id=seq.req.request_id, trace_id=seq.req.trace_id,
            tenant=seq.req.tenant, priority=seq.req.priority or "",
            generated=len(seq.generated), slot=seq.slot,
        )
        seq.finished = True  # stray in-flight snapshots must skip it
        self._cancel_fetch(seq)
        # the draft cache dies with the slot; re-admission rebuilds it from
        # the (prompt + generated) resume prompt at the first spec round
        self._free_draft(seq)
        # the adapter pin dies with the slot too — re-admission re-acquires
        # (the host copy is cached, so a hot-swap back is one scatter)
        self._release_lora(seq)
        self.allocator.free_sequence(seq.req.request_id)
        if seq.slot >= 0 and self.slots[seq.slot] is seq:
            self.slots[seq.slot] = None
        new_req = EngineRequest(
            request_id=seq.req.request_id,
            token_ids=list(seq.req.token_ids) + seq.generated,
            # queue-entry clock carries the ORIGINAL submission forward: the
            # resumed wait, TTFT, and goodput duration all bill from when the
            # client first enqueued — a preemption must never make a request
            # look FASTER than an uninterrupted run of the same work
            enqueue_ts=seq.req.enqueue_ts or time.monotonic(),
            trace_id=seq.req.trace_id,
            images=seq.req.images,
            mm_embeds=seq.req.mm_embeds,  # offsets are prompt-relative: still valid
            logprobs=seq.req.logprobs,
            # prior output starts where the ORIGINAL prompt ended (earlier
            # preemptions included: the original split carries forward)
            penalty_output_from=(
                seq.req.penalty_output_from
                if seq.req.penalty_output_from is not None
                else seq.prompt_len
            ),
            # mrope_pos covers the OLD prompt length only: left None so it is
            # recomputed over prompt+generated at re-admission (delta included)
            # already-generated tokens count against max_tokens on resume;
            # every other sampling field (penalties, seed, min_p, ...) carries
            sampling=dataclasses.replace(
                seq.req.sampling,
                max_tokens=max(1, seq.req.sampling.max_tokens - len(seq.generated)),
                min_tokens=max(0, seq.req.sampling.min_tokens - len(seq.generated)),
            ),
            eos_token_ids=seq.req.eos_token_ids,
            # the holder hint survives preemption: the matched prefix is a
            # prefix of the UNCHANGED original prompt, and if our own cache
            # kept the pages the min-advantage gate skips the re-fetch anyway
            kv_holder_addr=seq.req.kv_holder_addr,
            kv_holder_blocks=seq.req.kv_holder_blocks,
            lora_name=seq.req.lora_name,
            # QoS/goodput attribution must survive the requeue: the resumed
            # request bills the same tenant at the same priority class
            tenant=seq.req.tenant,
            scenario=seq.req.scenario,
            priority=seq.req.priority,
            # admitted tokens were billed at the FIRST admission; the resumed
            # request must not double-charge the tenant's admitted count
            cost_admitted=seq.req.cost_admitted,
        )
        self.waiting.appendleft(new_req)
