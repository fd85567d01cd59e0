"""Step-anatomy profiler: per-dispatch host/device time attribution with a
live roofline accounting plane.

How much of a step is the device's and how much the host's dispatch and
reconcile overhead used to come from one-off profiling scripts. This module
makes the split a *standing* measurement: every engine dispatch
(decode window, packed prefill, per-request chunk, spec draft, spec verify,
LoRA slot load, prefix-fetch scatter, offload drain) records one
:class:`StepRecord` into a bounded ring, decomposed into four phases:

  host_prep    host time building the dispatch (numpy control arrays,
               capacity passes, table refreshes) before the runner call
  dispatch     host time inside the runner call (trace lookup, H2D, XLA
               dispatch — device may already be busy underneath)
  device_wait  host time *blocked* on device results (the reconcile sync the
               dispatch-ahead pipeline exists to hide)
  reconcile    host time materializing results back into scheduler state
               (token emission, EOS/stop scanning, stream posting)

Each boundary is timed once, by ``StepAnatomy.phase``: one
``time.monotonic()`` pair that adds to the record, feeds the scheduler's
stage counters (``stage_sink``) and is a ``tracing.span`` named
``engine.<kind>.<phase>`` with the record's ``seq`` — so in a
``jax.profiler`` trace the phase stands on the engine thread's line of the
host plane, on the device's clock, and ``/debug/steps`` names the same
dispatch by the same ``seq``.

The phases are the engine thread's time spent on dispatches. Admission, the
polls between dispatches, ``_drain_inboxes``, ``_post_grouped`` and the wait
for work lie outside every phase: the engine loop times those as spans of its
own (``engine.step``, ``engine.post``, ``engine.wait_for_work`` in
``engine/engine.py``), and a profiler trace says how much of the thread no
phase covers. ``host_frac`` (everything except
device_wait, over the total) is the fraction of a serving step the host
spends NOT waiting on the chip: the overhead the multi-step decode window
drives down, and this plane is its before/after instrument.

The roofline estimator prices the bytes-moved floor of a decode step from
live state: every step re-reads the full parameter set plus each live
sequence's KV pages (``quant/kv.kv_page_bytes`` at the ACTUAL cache dtype,
so int8 KV lowers the floor exactly as it lowers HBM traffic). Dividing by
the device's HBM bandwidth (``DEVICE_PEAKS``, keyed by ``device_kind``;
``DYNTPU_HBM_GBPS`` overrides) gives a floor time; ``roofline_fraction`` =
floor / measured decode seconds, as a gauge
(``dynamo_engine_roofline_fraction``). A device that is not in the table —
the CPU of a test run, a part nobody has entered — yields no fraction at
all: the *bytes* are exact and still reported, a fraction against another
part's bandwidth would be fiction.

Prefill gets the same treatment (PR 19): a prefill dispatch is
compute-bound once the chunk is wide enough, so its floor is
``max(FLOP bound, bytes bound)`` — ~2·param_count FLOPs per prompt row
against the MXU peak (``DEVICE_PEAKS``; ``DYNTPU_MXU_TFLOPS`` overrides), vs
one weight read plus the KV the chunk writes against HBM bandwidth. Each
``prefill_packed``/``prefill_chunk`` record prices its floor at dispatch
(``note_prefill_floor``); ``prefill_roofline_fraction`` = summed floors /
measured prefill engine seconds (``dynamo_engine_prefill_roofline_fraction``)
and ``prefill_fixed_ms`` is the live per-dispatch host cost (host prep and
dispatch; the kernel's own time is the profiler trace's).

Exposed everywhere the repo already has rails: ``render_metrics`` emits
``dynamo_step_seconds_total{phase,kind}`` / ``dynamo_step_dispatch_total
{kind}`` / ``dynamo_engine_roofline_fraction`` on the engine's conformance
surface, ``snapshot()`` rides ``resource_snapshot`` -> worker stats ->
dynotop STEP/ROOF/PREFILL columns, and ``records()`` backs the
``/debug/steps`` JSON endpoint, which ``benchmark/run.py`` reads for
``decode_step_ms``, ``decode_batch_mean`` and ``host_share``.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Optional

from dynamo_tpu.utils import tracing
from dynamo_tpu.utils.logging import get_logger

log = get_logger("utils.step_anatomy")

#: dispatch kinds (the label vocabulary of dynamo_step_seconds_total{kind=})
KINDS = (
    "decode_window",
    "prefill_packed",
    "prefill_chunk",
    "spec_draft",
    "spec_verify",
    "lora_slot_load",
    "prefix_fetch_scatter",
    "offload_drain",
)

PHASES = ("host_prep", "dispatch", "device_wait", "reconcile")

#: names under which the Chrome recorder (``DYNTPU_TRACE``) has always
#: written three of the phases; ``tools/trace_view.py`` and the README's
#: tracing section read them, so the recorder keeps them. The profiler's
#: annotation is ``engine.<kind>.<phase>`` for every phase.
_RECORDER_NAMES = {
    ("decode_window", "dispatch"): "engine.decode.window",
    ("prefill_packed", "dispatch"): "engine.prefill",
    ("prefill_chunk", "dispatch"): "engine.prefill",
    "device_wait": "engine.decode.sync",
}

#: the prefill-regime dispatch kinds (the packed serving path and the
#: per-request chain) — the label set prefill_roofline_fraction and the
#: dynotop PREFILL column aggregate over
PREFILL_KINDS = ("prefill_packed", "prefill_chunk")

#: default ring capacity: at ms-scale steps this is a few seconds of recent
#: history — enough for dynotop/debug inspection without unbounded growth
DEFAULT_RING = 512

#: published per-chip peaks, keyed by the ``device_kind`` JAX reports. A
#: device that is not in this table yields no roofline fraction — never
#: another part's. ``DYNTPU_HBM_GBPS`` / ``DYNTPU_MXU_TFLOPS`` override (or
#: supply) a value for any device.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "mxu_tflops": 197.0,  # bf16
        "source": "Google Cloud documentation, 'TPU v5e': 819 GB/s HBM, "
                  "197 TFLOP/s bf16 per chip",
    },
}

_unknown_logged: set = set()


def device_peaks(device_kind: Optional[str] = None) -> tuple:
    """(HBM bytes/s | None, MXU FLOP/s | None) for ``device_kind`` (default:
    the first device JAX reports): the env override where set, else the
    table's entry, else None — and the reason is logged once per device."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    entry = DEVICE_PEAKS.get(device_kind, {})
    out = []
    for env, key, scale in (("DYNTPU_HBM_GBPS", "hbm_gbps", 1e9),
                            ("DYNTPU_MXU_TFLOPS", "mxu_tflops", 1e12)):
        try:
            value = float(os.environ[env])
        except (KeyError, ValueError):
            value = entry.get(key)
        out.append(None if value is None else value * scale)
    if None in out and device_kind not in _unknown_logged:
        _unknown_logged.add(device_kind)
        log.info(
            "no published peaks for device kind %r (known: %s): roofline "
            "fractions are not reported; bytes and FLOP counts still are",
            device_kind, sorted(DEVICE_PEAKS),
        )
    return tuple(out)


@dataclass
class RooflineModel:
    """Bytes-moved floor arithmetic for one engine's decode step.

    param_bytes: every decode step reads the full parameter set once (the
    weight-bound term; int8 weights are 1 byte/element automatically because
    the bytes come from the actual leaves).
    page_bytes: HBM cost of ONE allocator page across all layers, K and V,
    at the ACTUAL kv_cache_dtype (``quant/kv.kv_page_bytes`` — int8 pages
    include their f32 scale planes).
    """

    param_bytes: int
    page_bytes: int
    page_size: int
    # peaks of the device the engine runs on (``device_peaks``); None = not
    # known for this device, and every floor in SECONDS is then None too
    hbm_bw: Optional[float] = None
    # parameter COUNT (not bytes): the FLOP side of the prefill floor is
    # ~2 FLOPs per parameter per row regardless of storage dtype
    param_count: int = 0
    mxu_flops: Optional[float] = None
    device_kind: Optional[str] = None

    def __post_init__(self):
        if self.hbm_bw is None or self.mxu_flops is None:
            bw, flops = device_peaks(self.device_kind)
            self.hbm_bw = bw if self.hbm_bw is None else self.hbm_bw
            self.mxu_flops = flops if self.mxu_flops is None else self.mxu_flops

    def step_floor_bytes(self, live_pages: int) -> int:
        """Bytes one decode step must move: weights + the live KV pages the
        batch's attention re-reads."""
        return self.param_bytes + live_pages * self.page_bytes

    def step_floor_seconds(self, live_pages: int) -> Optional[float]:
        if self.hbm_bw is None:
            return None
        return self.step_floor_bytes(live_pages) / max(1.0, self.hbm_bw)

    def prefill_floor_bytes(self, rows: int) -> int:
        """Bytes one prefill dispatch must move: one weight read plus the KV
        pages the chunk's rows fill (attention re-reads of the context ride
        on-chip for the chunk widths the engine uses, so they are not priced
        — the floor stays a floor)."""
        pages = -(-max(0, rows) // max(1, self.page_size))
        return self.param_bytes + pages * self.page_bytes

    def prefill_floor_seconds(self, rows: int) -> Optional[float]:
        """max(MXU-FLOP bound, bytes-moved bound) for a dispatch computing
        ``rows`` prompt rows: a dense forward pass is ~2·param_count FLOPs
        per row, so wide chunks are compute-bound and narrow ones fall back
        to the same weight-read floor decode pays. None where the device's
        peaks are not known."""
        if self.hbm_bw is None or self.mxu_flops is None:
            return None
        bytes_s = self.prefill_floor_bytes(rows) / max(1.0, self.hbm_bw)
        flops_s = (
            2.0 * self.param_count * max(0, rows) / max(1.0, self.mxu_flops)
        )
        return max(bytes_s, flops_s)

    def to_dict(self) -> dict:
        return {
            "param_bytes": self.param_bytes,
            "page_bytes": self.page_bytes,
            "page_size": self.page_size,
            "hbm_bw_bytes_s": self.hbm_bw,
            "param_count": self.param_count,
            "mxu_flops_s": self.mxu_flops,
            "device_kind": self.device_kind,
        }


def roofline_for_runner(runner, config) -> Optional[RooflineModel]:
    """Build the estimator from a live ModelRunner: parameter bytes from the
    actual leaves, page bytes from the model's own accounting (the same
    ``kv_page_bytes`` the resource gauges and dynotop render). None when the
    runner/model can't price pages (external engines, test fakes)."""
    model = getattr(runner, "model", None)
    params = getattr(runner, "params", None)
    if model is None or params is None:
        return None
    try:
        import jax

        leaves = jax.tree_util.tree_leaves(params)
        param_bytes = int(sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in leaves
            if hasattr(leaf, "size") and hasattr(leaf, "dtype")
        ))
        param_count = int(sum(
            leaf.size for leaf in leaves if hasattr(leaf, "size")
        ))
        page_bytes = int(model.kv_page_bytes(config.page_size))
    except Exception:
        return None
    if param_bytes <= 0 or page_bytes <= 0:
        return None
    mesh = getattr(runner, "mesh", None)
    return RooflineModel(
        param_bytes=param_bytes, page_bytes=page_bytes,
        page_size=config.page_size, param_count=param_count,
        device_kind=mesh.devices.flat[0].device_kind if mesh is not None else None,
    )


@dataclass
class StepRecord:
    """One engine dispatch, decomposed. Mutated in place as phases land
    (device_wait/reconcile arrive at the pipelined reconcile, possibly
    several windows after the dispatch)."""

    seq: int  # monotonic record id (eviction-stable ordering)
    ts: float  # time.monotonic() at dispatch start
    kind: str
    host_prep_s: float = 0.0
    dispatch_s: float = 0.0
    device_wait_s: float = 0.0
    reconcile_s: float = 0.0
    steps: int = 0  # decode steps / verify rows this dispatch advances
    tokens: int = 0  # tokens scheduled (decode) or rows computed (prefill)
    participants: int = 0
    floor_bytes: int = 0  # bytes-moved floor estimate (decode kinds only)
    floor_s: float = 0.0  # max(FLOP, bytes) floor seconds (prefill kinds only)
    #: cost-attribution bill: (request_id, tenant, adapter, priority, weight)
    #: rows the meter splits this record's phases across (None = system work)
    bill: Optional[list] = None

    @property
    def total_s(self) -> float:
        return (self.host_prep_s + self.dispatch_s + self.device_wait_s
                + self.reconcile_s)

    @property
    def host_s(self) -> float:
        """Host time NOT blocked on the device."""
        return self.host_prep_s + self.dispatch_s + self.reconcile_s

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "ts": round(self.ts, 6),
            "kind": self.kind,
            "host_prep_ms": round(self.host_prep_s * 1e3, 4),
            "dispatch_ms": round(self.dispatch_s * 1e3, 4),
            "device_wait_ms": round(self.device_wait_s * 1e3, 4),
            "reconcile_ms": round(self.reconcile_s * 1e3, 4),
            "steps": self.steps,
            "tokens": self.tokens,
            "participants": self.participants,
            "floor_bytes": self.floor_bytes,
            "floor_ms": round(self.floor_s * 1e3, 4),
        }


class _Phase:
    """The block ``StepAnatomy.phase`` times; entering it gives the
    ``tracing.span`` whose ``.t0`` / ``.t1`` / ``.dt`` are the one clock pair."""

    __slots__ = ("anatomy", "rec", "kind", "name", "span")

    def __init__(self, anatomy, rec, kind, name, span):
        self.anatomy, self.rec, self.kind, self.name, self.span = anatomy, rec, kind, name, span

    def __enter__(self) -> tracing.span:
        return self.span.__enter__()

    def __exit__(self, *exc) -> None:
        self.span.__exit__(*exc)
        self.anatomy.add_phase(self.rec, self.name, self.span.dt)
        if self.anatomy.stage_sink is not None:
            self.anatomy.stage_sink(self.kind, self.name, self.span.dt)


class StepAnatomy:
    """Bounded ring of StepRecords + cumulative per-(phase, kind) counters.

    The engine thread is the only writer of records; ``snapshot``/
    ``render_metrics``/``records`` run on the asyncio/scrape threads, so the
    ring append and the counter updates take a lock (a handful of float adds
    per dispatch against ms-scale stages — same budget as StageStats).
    """

    def __init__(self, ring_size: int = DEFAULT_RING,
                 roofline: Optional[RooflineModel] = None):
        self._lock = threading.Lock()
        self.ring: deque[StepRecord] = deque(maxlen=ring_size)
        self._seq = 0
        # (phase, kind) -> cumulative seconds; kind -> dispatch count
        self.phase_seconds: dict[tuple[str, str], float] = {}
        self.dispatch_counts: dict[str, int] = {}
        self.steps_total: dict[str, int] = {}
        self.floor_bytes_total = 0  # cumulative priced floors
        self._floor_kinds: set[str] = set()  # kinds that recorded a floor
        # prefill plane: floors are SECONDS (max of FLOP and bytes bounds,
        # which don't share a unit) and accumulate separately so they can
        # never pollute the decode-regime roofline_fraction above
        self.prefill_floor_s_total = 0.0
        self._prefill_floor_kinds: set[str] = set()
        self.roofline = roofline
        #: optional utils/metering.MeterLedger — every clamped phase delta is
        #: forwarded to it with the record's bill, so the attributed cost
        #: plane shares this plane's samples (the conservation identity)
        self.meter = None
        #: optional ``fn(kind, phase, seconds)``, called once per ``phase``
        #: block: the scheduler's StageStats and stage histograms take the
        #: intervals they report from here, not from a clock pair of their own
        self.stage_sink = None

    # ---------------- recording (engine thread) ----------------

    def begin(self, kind: str, ts: Optional[float] = None,
              bill: Optional[list] = None) -> StepRecord:
        """Open one dispatch record and append it to the ring (it fills in
        place as phases complete). ``bill`` must be set before the first
        ``add_phase`` — phase deltas forward to the meter immediately."""
        with self._lock:
            self._seq += 1
            rec = StepRecord(seq=self._seq, ts=ts or time.monotonic(),
                             kind=kind, bill=bill)
            self.ring.append(rec)
            self.dispatch_counts[kind] = self.dispatch_counts.get(kind, 0) + 1
        return rec

    def add_phase(self, rec: Optional[StepRecord], phase: str, dt: float) -> None:
        """Attribute ``dt`` seconds of ``phase`` to a record (None-safe: a
        reconcile for an untracked dispatch still lands in the totals)."""
        if dt < 0:
            dt = 0.0
        kind = rec.kind if rec is not None else "decode_window"
        with self._lock:
            key = (phase, kind)
            self.phase_seconds[key] = self.phase_seconds.get(key, 0.0) + dt
            if rec is not None:
                setattr(rec, phase + "_s", getattr(rec, phase + "_s") + dt)
        if self.meter is not None and dt > 0:
            self.meter.on_phase(rec, phase, dt)

    def phase(self, rec: Optional[StepRecord], name: str,
              request_id: Optional[str] = None, trace_id: Optional[str] = None,
              **attrs) -> _Phase:
        """Time a block as ``name`` (one of ``PHASES``) of ``rec``: one clock
        pair, fed to ``add_phase``, to ``stage_sink`` and to a
        ``tracing.span("engine.<kind>.<phase>", seq=rec.seq, **attrs)``.
        ``with anatomy.phase(...) as ph`` gives the span, for a site that
        needs the interval's end (``ph.t1``). ``rec`` None is an untracked
        dispatch, charged to ``decode_window`` as ``add_phase`` does."""
        kind = rec.kind if rec is not None else "decode_window"
        return _Phase(self, rec, kind, name, tracing.span(
            f"engine.{kind}.{name}", request_id=request_id, trace_id=trace_id,
            alias=_RECORDER_NAMES.get((kind, name)) or _RECORDER_NAMES.get(name),
            seq=rec.seq if rec is not None else 0, **attrs,
        ))

    def record(self, kind: str, dispatch_s: float, host_prep_s: float = 0.0,
               device_wait_s: float = 0.0, reconcile_s: float = 0.0,
               steps: int = 0, tokens: int = 0, participants: int = 0,
               floor_bytes: int = 0, ts: Optional[float] = None,
               bill: Optional[list] = None) -> StepRecord:
        """One-shot record for synchronous dispatch kinds (spec rounds, LoRA
        slot loads, scatters, drains): all phases known at the call site."""
        rec = self.begin(kind, ts=ts, bill=bill)
        for phase, dt in (("host_prep", host_prep_s), ("dispatch", dispatch_s),
                          ("device_wait", device_wait_s),
                          ("reconcile", reconcile_s)):
            if dt:
                self.add_phase(rec, phase, dt)
        self.note_steps(rec, steps=steps, tokens=tokens,
                        participants=participants, floor_bytes=floor_bytes)
        return rec

    def note_steps(self, rec: StepRecord, steps: int = 0, tokens: int = 0,
                   participants: int = 0, floor_bytes: int = 0) -> None:
        with self._lock:
            rec.steps += steps
            rec.tokens += tokens
            rec.participants = max(rec.participants, participants)
            rec.floor_bytes += floor_bytes
            if steps:
                self.steps_total[rec.kind] = (
                    self.steps_total.get(rec.kind, 0) + steps
                )
            if floor_bytes:
                self.floor_bytes_total += floor_bytes
                self._floor_kinds.add(rec.kind)

    def decode_floor_bytes(self, live_pages: int, steps: int) -> int:
        """Floor bytes for a K-step decode window at the current occupancy
        (0 when no roofline model is attached)."""
        if self.roofline is None:
            return 0
        return self.roofline.step_floor_bytes(live_pages) * max(1, steps)

    def note_prefill_floor(self, rec: Optional[StepRecord], rows: int) -> None:
        """Price one prefill dispatch's max(FLOP, bytes) floor live at the
        dispatch site (no-op without a roofline model or rows)."""
        if self.roofline is None or rec is None or rows <= 0:
            return
        floor_s = self.roofline.prefill_floor_seconds(rows)
        if floor_s is None:  # unknown device: no peaks, no floor in seconds
            return
        with self._lock:
            rec.floor_s += floor_s
            self.prefill_floor_s_total += floor_s
            self._prefill_floor_kinds.add(rec.kind)

    # ---------------- derived views (any thread) ----------------

    def _ring_snapshot(self) -> list[StepRecord]:
        with self._lock:
            return list(self.ring)

    def host_fraction(self, kinds: Optional[tuple] = None) -> Optional[float]:
        """Host-side share of engine time over the cumulative counters:
        (host_prep + dispatch + reconcile) / total. None before any data."""
        with self._lock:
            items = list(self.phase_seconds.items())
        host = wait = 0.0
        for (phase, kind), s in items:
            if kinds is not None and kind not in kinds:
                continue
            if phase == "device_wait":
                wait += s
            else:
                host += s
        total = host + wait
        if total <= 0:
            return None
        return host / total

    def roofline_fraction(self) -> Optional[float]:
        """floor / measured over the priced decode-regime kinds (decode
        windows; spec verify rounds on spec engines): the fraction of the
        decode regime's engine time the HBM floor accounts for. None until a
        priced dispatch completes."""
        if self.roofline is None or self.roofline.hbm_bw is None:
            return None
        with self._lock:
            floor_bytes = self.floor_bytes_total
            measured = sum(
                s for (phase, kind), s in self.phase_seconds.items()
                if kind in self._floor_kinds
            )
        if floor_bytes <= 0 or measured <= 0:
            return None
        return (floor_bytes / self.roofline.hbm_bw) / measured

    def prefill_roofline_fraction(self) -> Optional[float]:
        """Summed per-dispatch prefill floors over measured prefill engine
        seconds — how close the prefill regime runs to max(MXU, HBM). The gap
        (1 - fraction) is per-dispatch fixed cost plus padding, the quantity
        the dispatch-ahead pipeline and bucket promotion attack. None until a
        priced prefill dispatch completes."""
        if self.roofline is None:
            return None
        with self._lock:
            floor_s = self.prefill_floor_s_total
            measured = sum(
                s for (phase, kind), s in self.phase_seconds.items()
                if kind in self._prefill_floor_kinds
            )
        if floor_s <= 0 or measured <= 0:
            return None
        return floor_s / measured

    def prefill_fixed_ms(self) -> Optional[float]:
        """Mean host-side (host_prep + dispatch) milliseconds per prefill
        dispatch: the per-call fixed cost. None before any prefill
        dispatch."""
        with self._lock:
            host = sum(
                s for (phase, kind), s in self.phase_seconds.items()
                if kind in PREFILL_KINDS and phase in ("host_prep", "dispatch")
            )
            n = sum(self.dispatch_counts.get(k, 0) for k in PREFILL_KINDS)
        if n <= 0:
            return None
        return host / n * 1e3

    def dispatch_gap_ms(self, kind: str = "decode_window",
                        q: float = 0.5) -> Optional[float]:
        """Quantile of gaps between consecutive same-kind dispatch starts in
        the ring — the host-side cadence (a fused-decode win shows up here
        as the gap growing while tokens/gap grows faster)."""
        ts = [r.ts for r in self._ring_snapshot() if r.kind == kind]
        if len(ts) < 2:
            return None
        gaps = sorted(b - a for a, b in zip(ts, ts[1:]))
        idx = min(len(gaps) - 1, max(0, int(q * (len(gaps) - 1))))
        return gaps[idx] * 1e3

    def records(self, limit: int = 128, kind: Optional[str] = None) -> list[dict]:
        """Most-recent records (newest last) as JSON-safe dicts — the
        ``/debug/steps`` payload."""
        snap = self._ring_snapshot()
        if kind is not None:
            snap = [r for r in snap if r.kind == kind]
        return [r.to_dict() for r in snap[-max(0, limit):]]

    def snapshot(self) -> dict:
        """Wire-safe summary for resource_snapshot -> worker stats ->
        dynotop: per-kind second totals, the two headline fractions, and the
        decode dispatch cadence."""
        with self._lock:
            phase_seconds = {
                f"{phase}.{kind}": round(s, 6)
                for (phase, kind), s in sorted(self.phase_seconds.items())
            }
            counts = dict(self.dispatch_counts)
            steps = dict(self.steps_total)
            floor_bytes = self.floor_bytes_total
        gap = self.dispatch_gap_ms("decode_window")
        snap = {
            "phase_seconds": phase_seconds,
            "dispatches": counts,
            "steps": steps,
            "host_frac": _round_opt(self.host_fraction()),
            "decode_host_frac": _round_opt(
                self.host_fraction(kinds=("decode_window",))
            ),
            "roofline_frac": _round_opt(self.roofline_fraction()),
            "prefill_host_frac": _round_opt(
                self.host_fraction(kinds=PREFILL_KINDS)
            ),
            "prefill_roofline_frac": _round_opt(
                self.prefill_roofline_fraction()
            ),
            "prefill_fixed_ms": _round_opt(self.prefill_fixed_ms(), 3),
            "dispatch_gap_ms_p50": round(gap, 3) if gap is not None else None,
            "floor_bytes_total": floor_bytes,
            "records": len(self.ring),
        }
        if self.roofline is not None:
            snap["roofline"] = self.roofline.to_dict()
        return snap

    def render_metrics(self) -> str:
        """Prometheus families for the engine exposition surface."""
        from dynamo_tpu.utils.prometheus import render_family

        with self._lock:
            phase_items = sorted(self.phase_seconds.items())
            counts = sorted(self.dispatch_counts.items())
        parts = [
            render_family(
                "dynamo_step_seconds_total", "counter",
                "engine-thread seconds per step-anatomy phase and dispatch "
                "kind (host_prep/dispatch/reconcile = host overhead; "
                "device_wait = host blocked on the chip)",
                [({"kind": kind, "phase": phase}, round(s, 6))
                 for (phase, kind), s in phase_items]
                or [({"kind": "decode_window", "phase": "dispatch"}, 0)],
            ),
            render_family(
                "dynamo_step_dispatch_total", "counter",
                "engine dispatches by step-anatomy kind",
                [({"kind": k}, n) for k, n in counts]
                or [({"kind": "decode_window"}, 0)],
            ),
        ]
        frac = self.roofline_fraction()
        if frac is not None:
            parts.append(render_family(
                "dynamo_engine_roofline_fraction", "gauge",
                "HBM bytes-moved floor over measured decode-window engine "
                "seconds (1.0 = running at the roofline; the r5 69.8% "
                "decomposition as a standing gauge)",
                [({}, round(frac, 4))],
            ))
        pfrac = self.prefill_roofline_fraction()
        if pfrac is not None:
            parts.append(render_family(
                "dynamo_engine_prefill_roofline_fraction", "gauge",
                "summed max(MXU-FLOP, HBM-bytes) prefill dispatch floors "
                "over measured prefill engine seconds (1.0 = every dispatch "
                "at the hardware bound; the gap is fixed per-call cost)",
                [({}, round(pfrac, 4))],
            ))
        host = self.host_fraction()
        if host is not None:
            parts.append(render_family(
                "dynamo_step_host_fraction", "gauge",
                "host-side share of attributed engine time (1 - device_wait "
                "share): the per-token overhead multi-step fused decode "
                "exists to shrink",
                [({}, round(host, 4))],
            ))
        return "".join(parts)


def _round_opt(v: Optional[float], nd: int = 4) -> Optional[float]:
    return round(v, nd) if v is not None else None
