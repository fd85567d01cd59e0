"""Minimal Prometheus text-exposition helpers (no client library).

Shared by the HTTP service metrics, the engine stage histograms, and the
standalone metrics component so every producer emits *conformant* exposition:
exactly one ``# HELP``/``# TYPE`` pair per metric family (emitted before the
family's first sample), canonically formatted ``le`` labels (never ``repr()``),
escaped label values, and cumulative histogram buckets ending at ``+Inf``.

``check_exposition`` is the promtool-style validator the test suite runs
against every ``/metrics`` surface; keeping it next to the formatters means a
new producer can't drift from what the checker enforces.
"""

from __future__ import annotations

import threading
from typing import Iterable, Sequence

_TYPES = ("counter", "gauge", "histogram", "summary", "untyped")

#: The conformance surface for every ``dynamo_*`` metric family this system
#: exposes. Three planes pin each other through this one tuple:
#:   - ``--check`` (the lint gate) asserts the families RENDERED by
#:     ``_sample_surfaces()`` equal this set exactly — a new emitter must
#:     declare itself here, a removed one must be deleted here;
#:   - ``tools/graftlint`` (metric-conformance detector) statically checks
#:     every ``dynamo_*`` string literal at an emitting site against this
#:     tuple, and that every name here is referenced by some emitter;
#:   - the exposition tests ride the same ``_sample_surfaces()`` list.
#: So a metric-name typo, a family renamed on one side only, or a dead
#: declaration all fail CI before any cluster exists. Keep one name per line
#: (graftlint suppressions are per-line).
DECLARED_METRIC_FAMILIES: tuple = (
    "dynamo_alert_state",
    "dynamo_cost_device_seconds_total",
    "dynamo_cost_kv_byte_seconds_total",
    "dynamo_cost_kv_resident_bytes",
    "dynamo_cost_queued_seconds_total",
    "dynamo_cost_tokens_total",
    "dynamo_engine_context_chunk_total",
    "dynamo_engine_context_table_dispatch_total",
    "dynamo_engine_context_table_promotions_total",
    "dynamo_engine_decode_window_dispatch_seconds",
    "dynamo_engine_disk_blocks",
    "dynamo_engine_disk_bytes",
    "dynamo_engine_disk_restore_seconds",
    "dynamo_engine_disk_restores_total",
    "dynamo_engine_disk_spills_total",
    "dynamo_engine_first_token_wait_seconds",
    "dynamo_engine_goodput_itl_p99_seconds",
    "dynamo_engine_goodput_ratio",
    "dynamo_engine_goodput_requests_total",
    "dynamo_engine_goodput_ttft_p99_seconds",
    "dynamo_engine_hbm_bytes",
    "dynamo_engine_kv_cache_bytes",
    "dynamo_engine_kv_cache_page_bytes",
    "dynamo_engine_kv_group_pages",
    "dynamo_engine_kv_pages",
    "dynamo_engine_kv_tiles",
    "dynamo_engine_kv_window_pages_released_total",
    "dynamo_engine_moe_assignments_total",
    "dynamo_engine_moe_busiest_over_mean",
    "dynamo_engine_moe_experts_touched_total",
    "dynamo_engine_moe_routed_total",
    "dynamo_engine_offload_blocks_total",
    "dynamo_engine_offload_bytes_resident",
    "dynamo_engine_offload_pressure_blocks_total",
    "dynamo_engine_preemptions_total",
    "dynamo_engine_prefill_dispatches_total",
    "dynamo_engine_prefill_hold_seconds",
    "dynamo_engine_prefill_padded_rows_total",
    "dynamo_engine_prefill_roofline_fraction",
    "dynamo_engine_prefill_rows_total",
    "dynamo_engine_prefill_seconds",
    "dynamo_engine_prefill_windows_ahead_total",
    "dynamo_engine_prefix_cache_blocks_total",
    "dynamo_engine_prefix_cache_refused_total",
    "dynamo_engine_pressure_drains_total",
    "dynamo_engine_queue_wait_seconds",
    "dynamo_engine_reconcile_wait_seconds",
    "dynamo_engine_roofline_fraction",
    "dynamo_engine_slo_latency_seconds",
    "dynamo_engine_slo_violations_total",
    "dynamo_engine_stage_seconds_total",
    "dynamo_engine_state_bytes",
    "dynamo_engine_state_slots",
    "dynamo_engine_ttft_seconds",
    "dynamo_engine_xla_compile_seconds_total",
    "dynamo_engine_xla_compiles_total",
    "dynamo_event_captures_pinned_total",
    "dynamo_event_emitted_total",
    "dynamo_event_journal_size",
    "dynamo_goodput_itl_p99_seconds",
    "dynamo_goodput_ratio",
    "dynamo_goodput_requests_total",
    "dynamo_goodput_tenant_ratio",
    "dynamo_goodput_ttft_p99_seconds",
    "dynamo_health_heartbeat_age_seconds",
    "dynamo_health_state",
    "dynamo_health_uptime_seconds",
    "dynamo_kv_stream_bytes_received_total",
    "dynamo_kv_stream_bytes_sent_total",
    "dynamo_kv_stream_checksum_failures_total",
    "dynamo_kv_stream_dropped_total",
    "dynamo_kv_stream_lanes",
    "dynamo_kv_stream_overlap_seconds_total",
    "dynamo_kv_stream_part_bytes",
    "dynamo_kv_stream_parts_received_total",
    "dynamo_kv_stream_parts_sent_total",
    "dynamo_kv_stream_reconnects_total",
    "dynamo_kv_stream_rejected_total",
    "dynamo_kv_stream_requests_total",
    "dynamo_kv_stream_send_seconds_total",
    "dynamo_kv_stream_transfers_received_total",
    "dynamo_lora_evictions_total",
    "dynamo_lora_load_seconds_total",
    "dynamo_lora_loads_total",
    "dynamo_lora_requests_total",
    "dynamo_lora_slots",
    "dynamo_migration_pause_seconds",
    "dynamo_migration_requests_total",
    "dynamo_migration_tokens_salvaged_total",
    "dynamo_planner_rebalance_executed_total",
    "dynamo_prefix_fetch_blocks_total",
    "dynamo_prefix_fetch_bytes_total",
    "dynamo_prefix_fetch_client_blocks_total",
    "dynamo_prefix_fetch_client_bytes_total",
    "dynamo_prefix_fetch_client_requests_total",
    "dynamo_prefix_fetch_client_seconds",
    "dynamo_prefix_fetch_requests_total",
    "dynamo_prefix_fetch_seconds",
    "dynamo_prefix_fetch_served_blocks_total",
    "dynamo_prefix_fetch_served_bytes_total",
    "dynamo_prefix_fetch_served_total",
    "dynamo_prefix_fetch_tokens_total",
    "dynamo_qos_budget_fill",
    "dynamo_qos_preemptions_total",
    "dynamo_qos_requests_total",
    "dynamo_replay_inflight_requests",
    "dynamo_replay_requests_total",
    "dynamo_replay_schedule_lag_seconds",
    "dynamo_replay_tokens_total",
    "dynamo_router_radix_bytes",
    "dynamo_router_radix_evictions_total",
    "dynamo_router_radix_hits_total",
    "dynamo_router_radix_nodes",
    "dynamo_slo_burn_rate",
    "dynamo_slo_compliance_ratio",
    "dynamo_slo_error_budget_remaining",
    "dynamo_slo_latency_seconds",
    "dynamo_slo_target_seconds",
    "dynamo_slo_violations_total",
    "dynamo_spec_acceptance_ratio",
    "dynamo_spec_accepted_per_round",
    "dynamo_spec_accepted_total",
    "dynamo_spec_draft_dispatch_total",
    "dynamo_spec_draft_pages",
    "dynamo_spec_draft_prefill_total",
    "dynamo_spec_draft_seconds_total",
    "dynamo_spec_proposed_total",
    "dynamo_step_dispatch_total",
    "dynamo_step_host_fraction",
    "dynamo_step_seconds_total",
)


def fmt_value(v) -> str:
    """Canonical sample/bucket-bound formatting: shortest float that round-trips
    for exposition purposes ('0.005', '1', '60', '2.5e-05') — never repr().
    Pre-formatted strings pass through (callers pinning a decimal width)."""
    if isinstance(v, str):
        return v
    if v != v:  # NaN
        return "NaN"
    if v == float("inf"):
        return "+Inf"
    if v == float("-inf"):
        return "-Inf"
    s = f"{float(v):.12g}"
    return s


def escape_label_value(v) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def fmt_labels(labels: dict) -> str:
    if not labels:
        return ""
    inner = ",".join(
        f'{k}="{escape_label_value(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


class Histogram:
    """A labeled histogram family rendered in Prometheus text format.

    Buckets are cumulative (le-style); observe() walks a dozen floats so it is
    cheap enough for per-request hot paths. Thread-safe: the engine loop and
    the asyncio thread both observe.
    """

    def __init__(
        self,
        name: str,
        help: str,
        buckets: Sequence[float],
        label_names: Sequence[str] = (),
    ):
        self.name = name
        self.help = help
        self.buckets = tuple(buckets)
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        # labelset tuple -> ([bucket counts], sum, count)
        self._series: dict[tuple, list] = {}

    def observe(self, value: float, labels: Sequence[str] = ()) -> None:
        key = tuple(labels)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = [[0] * len(self.buckets), 0.0, 0]
                self._series[key] = s
            counts, _, _ = s
            for i, b in enumerate(self.buckets):
                if value <= b:
                    counts[i] += 1
            s[1] += value
            s[2] += 1

    @property
    def count(self) -> int:
        with self._lock:
            return sum(s[2] for s in self._series.values())

    @property
    def sum(self) -> float:
        with self._lock:
            return sum(s[1] for s in self._series.values())

    def render(self) -> str:
        lines = [
            f"# HELP {self.name} {self.help}",
            f"# TYPE {self.name} histogram",
        ]
        with self._lock:
            for key in sorted(self._series):
                counts, total, n = self._series[key]
                base = dict(zip(self.label_names, key))
                for b, c in zip(self.buckets, counts):
                    lines.append(
                        f"{self.name}_bucket{fmt_labels({**base, 'le': fmt_value(b)})} {c}"
                    )
                lines.append(f"{self.name}_bucket{fmt_labels({**base, 'le': '+Inf'})} {n}")
                lines.append(f"{self.name}_sum{fmt_labels(base)} {total:.6f}")
                lines.append(f"{self.name}_count{fmt_labels(base)} {n}")
        return "\n".join(lines) + "\n"


def render_family(
    name: str, mtype: str, help: str, samples: Iterable[tuple[dict, float]]
) -> str:
    """One complete family: HELP/TYPE then every (labels, value) sample."""
    lines = [f"# HELP {name} {help}", f"# TYPE {name} {mtype}"]
    for labels, value in samples:
        lines.append(f"{name}{fmt_labels(labels)} {fmt_value(value)}")
    return "\n".join(lines) + "\n"


def _family_of(sample_name: str, histogram_families: set[str]) -> str:
    for suffix in ("_bucket", "_sum", "_count"):
        if sample_name.endswith(suffix) and sample_name[: -len(suffix)] in histogram_families:
            return sample_name[: -len(suffix)]
    return sample_name


def check_exposition(text: str) -> list[str]:
    """Promtool-style lint of a text exposition. Returns a list of problems
    (empty = conformant). Enforced rules:

      - every sample belongs to a family with exactly one HELP and one TYPE
        line, both appearing before the family's first sample
      - TYPE values are legal; histogram families carry _bucket/_sum/_count
        samples and every ``le`` is a parseable float or ``+Inf``
      - sample values parse as floats; label strings are well-formed
    """
    problems: list[str] = []
    helps: dict[str, int] = {}
    types: dict[str, str] = {}
    first_sample_seen: set[str] = set()
    hist_families: set[str] = set()
    hist_has: dict[str, set] = {}

    for lineno, raw in enumerate(text.splitlines(), 1):
        line = raw.rstrip()
        if not line:
            continue
        if line.startswith("# HELP "):
            parts = line.split(None, 3)
            if len(parts) < 3:
                problems.append(f"line {lineno}: malformed HELP")
                continue
            fam = parts[2]
            helps[fam] = helps.get(fam, 0) + 1
            if helps[fam] > 1:
                problems.append(f"line {lineno}: duplicate HELP for {fam}")
            if fam in first_sample_seen:
                problems.append(f"line {lineno}: HELP for {fam} after its samples")
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4:
                problems.append(f"line {lineno}: malformed TYPE")
                continue
            fam, mtype = parts[2], parts[3]
            if fam in types:
                problems.append(f"line {lineno}: duplicate TYPE for {fam}")
            if mtype not in _TYPES:
                problems.append(f"line {lineno}: illegal TYPE {mtype!r} for {fam}")
            if fam in first_sample_seen:
                problems.append(f"line {lineno}: TYPE for {fam} after its samples")
            types[fam] = mtype
            if mtype == "histogram":
                hist_families.add(fam)
                hist_has[fam] = set()
            continue
        if line.startswith("#"):
            continue  # free-text comment: legal, attaches to nothing
        # sample line: name{labels} value  |  name value
        name_part, _, value_part = line.rpartition(" ")
        if not name_part:
            problems.append(f"line {lineno}: malformed sample")
            continue
        try:
            float(value_part)
        except ValueError:
            problems.append(f"line {lineno}: non-numeric value {value_part!r}")
        labels: dict[str, str] = {}
        if "{" in name_part:
            name, _, rest = name_part.partition("{")
            if not rest.endswith("}"):
                problems.append(f"line {lineno}: unterminated label set")
                continue
            body = rest[:-1]
            # simple split: label values in this codebase never contain
            # escaped quotes followed by commas; good enough for linting
            for pair in filter(None, body.split(",")):
                if "=" not in pair:
                    problems.append(f"line {lineno}: malformed label {pair!r}")
                    continue
                k, _, v = pair.partition("=")
                if not (v.startswith('"') and v.endswith('"')):
                    problems.append(f"line {lineno}: unquoted label value in {pair!r}")
                    continue
                labels[k] = v[1:-1]
        else:
            name = name_part
        fam = _family_of(name, hist_families)
        first_sample_seen.add(fam)
        if fam not in types:
            problems.append(f"line {lineno}: sample {name} has no TYPE for family {fam}")
        if fam not in helps:
            problems.append(f"line {lineno}: sample {name} has no HELP for family {fam}")
        if fam in hist_families:
            for suffix in ("_bucket", "_sum", "_count"):
                if name == fam + suffix:
                    hist_has[fam].add(suffix)
            if name == fam + "_bucket":
                le = labels.get("le")
                if le is None:
                    problems.append(f"line {lineno}: histogram bucket without le")
                elif le != "+Inf":
                    try:
                        float(le)
                    except ValueError:
                        problems.append(f"line {lineno}: unparseable le {le!r}")

    for fam, seen in hist_has.items():
        missing = {"_bucket", "_sum", "_count"} - seen
        if fam in first_sample_seen and missing:
            problems.append(f"histogram {fam} missing {sorted(missing)} samples")
    return problems


# ---------------- self-check (python -m dynamo_tpu.utils.prometheus --check) ----


def _sample_surfaces() -> list[tuple[str, str]]:
    """Build every exposition surface with representative samples, WITHOUT a
    cluster: (name, rendered text) pairs. The CI lint gate and the
    conformance test both run check_exposition over these, so a new metric
    family can't regress HELP/TYPE/label format unnoticed."""
    import time as _time

    surfaces: list[tuple[str, str]] = []

    # HTTP service metrics (request counters + latency histograms)
    from dynamo_tpu.llm.http.metrics import Metrics

    m = Metrics()
    m.inc_request("tiny", "chat_completions", "stream", "200")
    m.inflight("tiny", 1)
    m.observe_duration("tiny", "chat_completions", 0.25)
    m.observe_ttft("tiny", 0.05)
    m.observe_itl("tiny", 0.004)
    surfaces.append(("llm.http.metrics", m.render()))

    # SLO tracker + health monitor (fleet health plane)
    from dynamo_tpu.utils.health import HealthMonitor
    from dynamo_tpu.utils.slo import SloTracker

    slo = SloTracker({"ttft": 0.5, "itl": 0.05})
    for v in (0.1, 0.2, 0.7):
        slo.observe("ttft", v)
        slo.observe("itl", v / 20)
    # tenant- and priority-class-labeled series must render conformantly
    # alongside the aggregate
    slo.observe("ttft", 0.15, tenant="tenant-a")
    slo.observe("ttft", 0.12, priority="critical")
    surfaces.append(("utils.slo", slo.render_metrics()))
    # burn-rate alerting surface (dynamo_slo_burn_rate + dynamo_alert_state):
    # a separate render method because the engine re-renders the same tracker
    # under its dynamo_engine_slo prefix — burn/alert families appear exactly
    # once, on the frontend /metrics
    surfaces.append(("utils.slo.burn", slo.render_burn_metrics()))

    # flight-recorder journal exposition (utils/events.py)
    from dynamo_tpu.utils.events import EventJournal

    ej = EventJournal()
    ej.emit("request.enqueued", request_id="r-check", prompt_tokens=16)
    ej.emit("request.finished", request_id="r-check", output_tokens=4)
    ej.pin("r-check", "ttft_over_budget")
    surfaces.append(("utils.events", ej.render_metrics()))
    hm = HealthMonitor("selfcheck")
    hm.set_state("ready", "self-check")
    hm.beat()
    surfaces.append(("utils.health", hm.render_metrics()))

    # goodput plane: per-request SLO outcomes -> windowed goodput families
    # (dynamo_goodput_*), incl. a missed request and a tenant breakdown
    from dynamo_tpu.utils.goodput import GoodputTracker, RequestOutcome

    gp = GoodputTracker(ttft_budget_s=0.5, itl_budget_s=0.05)
    gp.observe(RequestOutcome(
        "r1", scenario="bursty_chat", tenant="tenant-a", ttft_s=0.1,
        itl_s=(0.004, 0.006), output_tokens=16,
    ))
    gp.observe(RequestOutcome(
        "r2", scenario="bursty_chat", ttft_s=0.9, output_tokens=4,
    ))
    gp.observe(RequestOutcome("r3", scenario="lora_churn", error=True))
    surfaces.append(("utils.goodput", gp.render_metrics()))

    # multi-tenant QoS admission plane (utils/qos.py): budgets + classes ->
    # the dynamo_qos_requests_total / dynamo_qos_budget_fill families
    from dynamo_tpu.utils.qos import AdmissionController, QosPolicy

    qos = AdmissionController(QosPolicy.from_specs(
        "tenant-a=500,tenant-b=4000", "tenant-a=batch,tenant-b=critical",
    ))
    qos.admit("tenant-a", "batch", 120)
    qos.admit("tenant-b", "critical", 64)
    for _ in range(8):  # exhaust tenant-a's burst so a throttle renders
        qos.admit("tenant-a", "batch", 400)
    qos.record_shed("tenant-a", "batch")
    surfaces.append(("utils.qos", qos.render_metrics()))

    # planner rebalance executor (components/planner.py)
    from dynamo_tpu.components.planner import PlannerService

    class _PlannerDrt:
        cplane = None

    psvc = PlannerService(_PlannerDrt(), "ns")
    psvc.rebalance_executed = 2
    psvc.rebalance_execute_failures = 1
    surfaces.append(("components.planner", psvc.render_metrics()))

    # trace-replay harness: the dynamo_replay_* client-side families
    from dynamo_tpu.loadgen.replay import ReplayMetrics

    rm = ReplayMetrics()
    rm.submitted()
    rm.observe_lag(0.002)
    rm.finished("bursty_chat", 16, error=False)
    surfaces.append(("loadgen.replay", rm.render_metrics()))

    # engine stage histograms + resource gauges (scheduler built directly on
    # a real allocator; no model/runner/device needed)
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.page_table import PageAllocator
    from dynamo_tpu.engine.scheduler import Scheduler

    # speculative = draft so the dynamo_spec_* and dynamo_spec_draft_*
    # families render (the draft runner itself is faked below — building a
    # real one would load a model, which the cluster-free gate must not do)
    cfg = EngineConfig(model_id="tiny", page_size=4, num_pages=8, max_seqs=2,
                       prefill_buckets=(16,), speculative="draft:tiny:2")
    eng = AsyncJaxEngine(cfg)
    eng.allocator = PageAllocator(cfg.num_pages, cfg.page_size)
    eng.scheduler = Scheduler(cfg, None, eng.allocator)
    for name in ("queue_wait", "ttft", "prefill_hold", "first_token_wait",
                 "prefill", "decode_window", "reconcile"):
        eng.scheduler.stage_hist[name].observe(0.01)
    eng.scheduler.stage.prefill_s = 0.5
    eng.scheduler.stage.spec_proposed = 8
    eng.scheduler.stage.spec_accepted = 6
    eng.scheduler.stage.spec_draft_calls = 2
    eng.scheduler.stage.spec_draft_s = 0.01
    # live migration: both roles' counters + a sample pause so the
    # dynamo_migration_* families render on the conformance surface
    eng.scheduler.migration_out = 2
    eng.scheduler.migration_in = 1
    eng.scheduler.migration_in_pulled = 1
    eng.scheduler.migration_tokens_salvaged = 24
    eng.migration_pause_hist.observe(0.04)
    # multi-tenant QoS: per-class victims so dynamo_qos_preemptions_total
    # renders class-labeled samples on the engine surface
    eng.scheduler.qos_preempted = {"batch": 3, "standard": 1}
    eng.scheduler.qos_sheds = 2
    eng.scheduler.qos_shed_migrations = 1

    class _DraftPool:
        pages_total, pages_used = 7, 3

    class _LoraStore:  # shape resource_snapshot actually reads
        def metrics_snapshot(self):
            return {
                "resident": 2, "capacity": 4, "evictions": 1, "loads": 3,
                "load_seconds": 0.42, "requests": {"a1": 5, "a2": 2},
                "hot": "a1",
            }

    class _CompileMonitor:  # shape resource_snapshot actually reads
        def snapshot(self):
            return {"compiles": 3, "compile_s": 0.82}

    class _SpecRunner:  # shape resource_snapshot actually reads
        draft = _DraftPool()
        lora_store = _LoraStore()
        model = None
        recurrent = False
        compile_monitor = _CompileMonitor()

        def hbm_stats(self):
            return {}

    eng.runner = _SpecRunner()

    class _Disk:  # shape resource_snapshot actually reads: puts the
        # dynamo_engine_disk_* families on the conformance surface
        spills, restores, drops, io_errors = 5, 3, 1, 1
        bytes_resident, budget_bytes = 16384, 65536
        restore_s = 0.012

        def __len__(self):
            return 4

    class _Offload:  # shape resource_snapshot actually reads: puts the
        # dynamo_engine_offload_* families on the conformance surface
        saves, loads, drops = 4, 2, 1
        capacity_blocks, block_bytes, bytes_resident = 64, 4096, 8192
        transfer_s = 0.003
        disk = _Disk()

        def __len__(self):
            return 2

    eng.offload = _Offload()
    # step-anatomy families (dynamo_step_* + dynamo_engine_roofline_fraction):
    # seed one priced decode window + a LoRA slot load so every family —
    # including the roofline gauge, which only renders once a floor-priced
    # dispatch completed — is on the conformance surface
    from dynamo_tpu.utils.step_anatomy import RooflineModel

    anat = eng.scheduler.anatomy
    anat.roofline = RooflineModel(
        param_bytes=2_600_000_000, page_bytes=4096, page_size=4,
        param_count=1_300_000_000, device_kind="TPU v5 lite",
    )
    rec = anat.begin("decode_window")
    anat.add_phase(rec, "host_prep", 0.0004)
    anat.add_phase(rec, "dispatch", 0.0021)
    anat.add_phase(rec, "device_wait", 0.0049)
    anat.add_phase(rec, "reconcile", 0.0003)
    anat.note_steps(rec, steps=4, tokens=8, participants=2,
                    floor_bytes=anat.decode_floor_bytes(64, 4))
    anat.record("lora_slot_load", dispatch_s=0.0031)
    # one priced prefill dispatch: dynamo_engine_prefill_roofline_fraction
    # renders only once note_prefill_floor has priced a packed call
    prec = anat.begin("prefill_packed")
    anat.add_phase(prec, "host_prep", 0.0006)
    anat.add_phase(prec, "dispatch", 0.0102)
    anat.note_steps(prec, tokens=256, participants=2)
    anat.note_prefill_floor(prec, 256)
    # cost-attribution families (dynamo_cost_* via utils/metering.py): the
    # engine's MeterLedger is their single emitting site, reached through
    # render_stage_metrics. Wire the anatomy's meter tap and drive one billed
    # dispatch plus each charge edge (KV residency, queue wait, token
    # charges) so every family renders labeled samples cluster-free
    anat.meter = eng.meter
    crec = anat.begin("decode_window", bill=[
        ("r-cost", "tenant-a", "a1", "critical", 4.0),
    ])
    anat.add_phase(crec, "dispatch", 0.002)
    anat.add_phase(crec, "device_wait", 0.005)
    eng.meter.kv_acquire("hbm", ("blk", 1), 4096, owner=("tenant-a", "r-cost"))
    eng.meter.kv_acquire("host", ("blk", 2), 4096, owner=("tenant-a", "r-cost"))
    eng.meter.queued("tenant-a", 0.01)
    eng.meter.charge_tokens("tenant-a", "admitted", 24)
    eng.meter.charge_tokens("tenant-a", "prompt", 16)
    eng.meter.charge_tokens("tenant-a", "output", 8)
    # the engine-scoped goodput families (dynamo_engine_goodput_*) need a
    # sample outcome to render their gauges
    eng.goodput.observe(RequestOutcome(
        "e1", scenario="bursty_chat", ttft_s=0.05, itl_s=(0.004,),
        output_tokens=8,
    ))
    surfaces.append(("engine.render_stage_metrics", eng.render_stage_metrics()))

    # disagg KV data-plane server/client + prefill worker send side
    from dynamo_tpu.disagg.dataplane import KvDataPlaneClient, KvDataPlaneServer
    from dynamo_tpu.disagg.prefill_worker import PrefillWorker

    surfaces.append(("disagg.dataplane.server", KvDataPlaneServer().render_metrics()))
    surfaces.append(("disagg.dataplane.client", KvDataPlaneClient(lanes=2).render_metrics()))

    # fleet prefix cache: pull server (export side) + fetch client (requester
    # wire side); the engine-side dynamo_prefix_fetch_* counters/histogram
    # ride the engine.render_stage_metrics surface above
    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient

    pull = KvPullServer(None)
    pull.served = 2
    pull.served_blocks["hbm"] = 8
    surfaces.append(("disagg.prefix_fetch.server", pull.render_metrics()))
    pf = PrefixFetchClient(None)
    pf.results["hit"] = 1
    pf.fetch_seconds.observe(0.02)
    surfaces.append(("disagg.prefix_fetch.client", pf.render_metrics()))

    class _Eng:
        config = model = None  # what PrefillWorker reads of an engine at start-up

    surfaces.append(("disagg.prefill_worker", PrefillWorker(_Eng(), None, "ns", "m").render_metrics()))

    # standalone metrics component: pool aggregates + federated per-worker
    # health/resource families, off an injected fleet view
    from dynamo_tpu.components.metrics import MetricsService
    from dynamo_tpu.llm.kv_router.metrics_aggregator import WorkerView
    from dynamo_tpu.llm.kv_router.scheduler import WorkerLoad

    class _Drt:
        cplane = None

    svc = MetricsService(_Drt(), "ns", "backend")
    kv = {
        "request_active_slots": 1, "request_total_slots": 8,
        "kv_active_blocks": 5, "kv_total_blocks": 100,
        "num_requests_waiting": 0, "gpu_cache_usage_perc": 0.05,
        "gpu_prefix_cache_hit_rate": 0.5,
    }
    svc.aggregator._workers[0xAB] = WorkerView(
        0xAB,
        data={
            "kv_metrics": kv,
            "health": {"state": "ready", "heartbeat_age_s": 0.01},
            "resources": {"kv_pages_used": 5, "kv_pages_total": 100,
                          "xla_compiles": 3, "hbm_bytes_in_use": 0},
            "stage_seconds": {"prefill_s": 1.0, "queue_wait_n": 2},
            # fleet per-class SLO aggregation source (one worker's
            # SloTracker.snapshot()["priorities"] shape)
            "slo": {"priorities": {"critical": {"itl": {
                "count": 4, "compliance": 0.75, "violations_total": 1,
            }}}},
        },
        load=WorkerLoad.from_wire(0xAB, kv),
        last_seen=_time.monotonic(),
    )
    svc._isl_blocks, svc._overlap_blocks = 10, 4
    # router radix-index health as relayed on the hit-rate subject: a tiny
    # bounded indexer driven past its cap so evictions/hits are nonzero
    from dynamo_tpu.llm.kv_events import KvCacheEvent, StoredBlock
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer, RouterEvent

    idx = KvIndexer(kv_block_size=4, use_native=False, max_nodes=4, num_shards=2)
    for i in range(8):
        idx.apply_event(RouterEvent(
            worker_id=0xAB,
            event=KvCacheEvent.stored(None, [StoredBlock(1000 + i, 2000 + i)]),
        ))
    idx.find_matches([2007])
    idx.find_matches([1])  # a miss, so both result labels sample
    svc._router_radix = idx.radix_stats()
    surfaces.append(("components.metrics", svc.render()))
    return surfaces


def _declaration_problems(surfaces: list[tuple[str, str]]) -> list[str]:
    """Cross-validate DECLARED_METRIC_FAMILIES against the families actually
    RENDERED by the sample surfaces: exact set equality, both directions.
    This is the runtime half of the metric-conformance contract; the static
    half (literals at emitting sites vs the same tuple) is graftlint's
    metric-conformance detector."""
    rendered: set[str] = set()
    for _, text in surfaces:
        for line in text.splitlines():
            if line.startswith("# TYPE dynamo_"):
                rendered.add(line.split()[2])
    declared = set(DECLARED_METRIC_FAMILIES)
    problems = []
    for fam in sorted(rendered - declared):
        problems.append(
            f"rendered family {fam} is not in DECLARED_METRIC_FAMILIES"
        )
    for fam in sorted(declared - rendered):
        problems.append(
            f"declared family {fam} is rendered by no sample surface — "
            "seed it in _sample_surfaces or delete the declaration"
        )
    return problems


def self_check() -> list[str]:
    """check_exposition over every cluster-free sample surface, plus the
    declared-vs-rendered family cross-validation; returns the flattened
    problem list (empty = all conformant)."""
    problems: list[str] = []
    surfaces = _sample_surfaces()
    for name, text in surfaces:
        problems.extend(f"{name}: {p}" for p in check_exposition(text))
        if not text.strip():
            problems.append(f"{name}: rendered empty exposition")
    problems.extend(_declaration_problems(surfaces))
    return problems


def _main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        description="Prometheus exposition helpers; --check validates every "
                    "metrics surface without a cluster (the CI lint step)."
    )
    p.add_argument("--check", action="store_true")
    args = p.parse_args(argv)
    if not args.check:
        p.print_help()
        return 2
    problems = self_check()
    for prob in problems:
        print(f"FAIL {prob}")
    if problems:
        return 1
    print(
        f"ok: exposition surfaces conformant; "
        f"{len(DECLARED_METRIC_FAMILIES)} declared dynamo_* families match "
        "the rendered set"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
