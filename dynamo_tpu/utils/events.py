"""Flight recorder: a bounded, causally-stamped request-lifecycle event journal.

The aggregate planes (SLO percentiles, goodput windows, stage seconds) can say
*that* a tenant's ITL-p99 blew its budget; nothing before this module could
say *why request X was slow* — queue wait, a QoS shed, a preemption, a dead
prefix-fetch holder, a migration pause, or a planner-triggered drain. Every
plane now appends one small :class:`Event` at each decision point:

  - scheduler: admission (accept/reject/defer), preempt + victim pick,
    speculative-decode degrade, prefix-fetch hit/fallback/timeout, offload
    drains/restores
  - frontend QoS: admit/throttle/shed verdicts
  - migration: freeze, handoff, adopt, and every failure-ladder arm
  - health: lifecycle transitions; planner: observe/decide/execute
  - chaos: `disagg/faults.py` injections, so seeded fault runs are
    self-documenting

Design constraints, in order:

  - **bounded**: one ring of ``capacity`` (default 4096) records; eviction is
    deque-append. A separate small *capture* map pins the full event chain of
    any request that finished over budget or errored, so forensics on the
    interesting requests survive ring eviction under load.
  - **lock-cheap**: ``emit()`` is one lock acquire around a deque append +
    two dict increments; no I/O, no serialization, no ambient-context lookup
    unless the caller omitted the ids. The decode hot loop emits a handful
    of events per *request*, not per token.
  - **causal**: every event carries a process-monotonic ``seq`` plus a wall
    clock (one monotonic->epoch anchor shared with utils/tracing.py), so
    per-worker order is exact and cross-worker merges sort on (wall, seq).
  - **conformant**: ``kind`` must be a member of :data:`DECLARED_EVENT_KINDS`
    — the same three-way pin as metric families: ``emit()`` raises on an
    unknown kind, graftlint's ``event-conformance`` detector statically
    checks every ``events.emit("<kind>", ...)`` literal against the tuple
    (and that every declared kind has an emitting site), and the
    ``dynamo_event_*`` exposition rides the prometheus ``--check`` surface.

The black box: :meth:`EventJournal.dump_post_mortem` writes the ring as JSONL
(one event per line, newest last) when the engine loop crashes or a worker
dies — the path comes from ``DYNTPU_POSTMORTEM_DIR`` (default: the system
temp dir). When tracing is enabled, every emit also records a zero-duration
span named ``event.<kind>`` carrying the event's seq — the event<->span
exemplar link, so journal entries line up on the Perfetto timeline keyed by
the same ``trace_id`` the event carries.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Optional

from dynamo_tpu.utils import tracing

#: Every event kind this system may emit — the conformance surface.
#: graftlint's event-conformance detector pins emitting-site literals against
#: this tuple in both directions (mirror of DECLARED_METRIC_FAMILIES). Keep
#: one kind per line. Naming scheme: ``<plane>.<decision>``.
DECLARED_EVENT_KINDS: tuple = (
    "request.enqueued",
    "request.first_token",
    "request.finished",
    "request.failed",
    "sched.admitted",
    "sched.admission_rejected",
    "sched.admission_deferred",
    "sched.preempted",
    "sched.victim_picked",
    "sched.spec_degraded",
    "qos.admitted",
    "qos.throttled",
    "qos.shed",
    "migration.freeze",
    "migration.handoff",
    "migration.adopted",
    "migration.fallback",
    "prefix_fetch.hit",
    "prefix_fetch.fallback",
    "prefix_fetch.timeout",
    "offload.drain",
    "offload.restore",
    "offload.disk_spill",
    "offload.disk_restore",
    "offload.disk_drop",
    "health.transition",
    "planner.observe",
    "planner.decide",
    "planner.execute",
    "fault.injected",
    "engine.crash",
)

_KIND_SET = frozenset(DECLARED_EVENT_KINDS)

CAPACITY = 4096
CAPTURE_CAPACITY = 64
#: events kept per pinned capture (a pathological 30k-token stream must not
#: let one capture eat the whole budget)
CAPTURE_EVENTS = 256

POSTMORTEM_DIR_ENV = "DYNTPU_POSTMORTEM_DIR"

# monotonic->epoch anchor shared shape with utils/tracing.py: emit stamps
# monotonic (cheap, ordering-exact) and wire forms add the offset
_EPOCH_OFFSET = time.time() - time.monotonic()


def _ambient_ids() -> tuple[str, str]:
    """(request_id, trace_id) from the ambient RequestContext, ("", "")
    outside a request. Lazy import: utils loads during runtime bootstrap."""
    from dynamo_tpu.runtime.context import current_context

    ctx = current_context()
    if ctx is None:
        return "", ""
    rid = ctx.request_id or ""
    return rid, ctx.metadata.get("trace_id") or rid


@dataclass(frozen=True)
class Event:
    """One journal record. Frozen: snapshots hand out references, and a
    pinned capture must not see later mutation."""

    seq: int
    mono: float  # time.monotonic() at emit
    kind: str
    request_id: str = ""
    trace_id: str = ""
    tenant: str = ""
    priority: str = ""
    detail: dict = field(default_factory=dict)

    @property
    def wall(self) -> float:
        return self.mono + _EPOCH_OFFSET

    def to_wire(self) -> dict:
        out = {
            "seq": self.seq,
            "wall": round(self.wall, 6),
            "kind": self.kind,
        }
        for k in ("request_id", "trace_id", "tenant", "priority"):
            v = getattr(self, k)
            if v:
                out[k] = v
        if self.detail:
            out["detail"] = self.detail
        return out


class EventJournal:
    def __init__(
        self,
        capacity: int = CAPACITY,
        capture_capacity: int = CAPTURE_CAPACITY,
        clock=time.monotonic,
    ):
        self._clock = clock
        self._lock = threading.Lock()
        self._ring: deque[Event] = deque(maxlen=capacity)
        self._seq = 0
        self._counts: dict[str, int] = {}
        # request_id -> {"reason", "pinned_mono", "events": [Event, ...]}
        # (LRU-bounded: the newest interesting requests win)
        self._captures: OrderedDict[str, dict] = OrderedDict()
        self._capture_capacity = capture_capacity
        self.pinned_total = 0

    # ---------------- ingest ----------------

    def emit(
        self,
        kind: str,
        request_id: Optional[str] = None,
        trace_id: Optional[str] = None,
        tenant: str = "",
        priority: str = "",
        **detail,
    ) -> Event:
        """Append one event. ``kind`` must be declared; request/trace ids
        default to the ambient request context's (pass them explicitly on
        threads outside the context — the engine loop)."""
        if kind not in _KIND_SET:
            raise ValueError(
                f"undeclared event kind {kind!r} — add it to "
                "DECLARED_EVENT_KINDS (utils/events.py)"
            )
        if request_id is None and trace_id is None:
            request_id, trace_id = _ambient_ids()
        ev = Event(
            seq=0,  # replaced under the lock below
            mono=self._clock(),
            kind=kind,
            request_id=request_id or "",
            trace_id=trace_id or request_id or "",
            tenant=tenant,
            priority=priority,
            detail=detail,
        )
        with self._lock:
            object.__setattr__(ev, "seq", self._seq)
            self._seq += 1
            self._ring.append(ev)
            self._counts[kind] = self._counts.get(kind, 0) + 1
        # event<->span exemplar link: journal entries land on the trace
        # timeline as zero-duration spans keyed by the same trace_id
        if tracing.enabled():
            tracing.record_span(
                f"event.{kind}", ev.mono, duration=0.0,
                request_id=ev.request_id or None, trace_id=ev.trace_id or None,
                attrs={"event_seq": ev.seq, **{k: str(v) for k, v in detail.items()}},
            )
        return ev

    # ---------------- forensics ----------------

    def events_for(self, request_id: str) -> list[Event]:
        """Every journal event for one request: the pinned capture (if any)
        merged with whatever still lives in the ring, seq-ordered."""
        with self._lock:
            cap = self._captures.get(request_id)
            chain = {e.seq: e for e in (cap["events"] if cap else ())}
            for e in self._ring:
                if e.request_id == request_id:
                    chain.setdefault(e.seq, e)
        return [chain[s] for s in sorted(chain)]

    def pin(self, request_id: str, reason: str) -> bool:
        """Copy a request's current event chain into the capture map so it
        survives ring eviction. Called at finish time for any request that
        blew its TTFT/ITL budget or errored. Idempotent per request (the
        first reason wins); returns True when a new capture landed."""
        if not request_id:
            return False
        with self._lock:
            if request_id in self._captures:
                self._captures.move_to_end(request_id)
                return False
            events = [e for e in self._ring if e.request_id == request_id]
            self._captures[request_id] = {
                "reason": reason,
                "pinned_mono": self._clock(),
                "events": events[-CAPTURE_EVENTS:],
            }
            self.pinned_total += 1
            while len(self._captures) > self._capture_capacity:
                self._captures.popitem(last=False)
        return True

    def capture_reason(self, request_id: str) -> Optional[str]:
        with self._lock:
            cap = self._captures.get(request_id)
            return cap["reason"] if cap else None

    def captured_ids(self) -> list[str]:
        with self._lock:
            return list(self._captures)

    def timeline(self, request_id: str) -> dict:
        """The ``/debug/requests/{id}`` document: the request's events in
        causal order with inter-event durations, plus the pin verdict."""
        events = self.events_for(request_id)
        out_events = []
        prev: Optional[Event] = None
        for e in events:
            w = e.to_wire()
            w["dt_ms"] = round((e.mono - prev.mono) * 1e3, 3) if prev else 0.0
            out_events.append(w)
            prev = e
        return {
            "request_id": request_id,
            "found": bool(events),
            "events": out_events,
            "span_ms": (
                round((events[-1].mono - events[0].mono) * 1e3, 3)
                if len(events) > 1 else 0.0
            ),
            "pinned": self.capture_reason(request_id),
        }

    # ---------------- fleet / exposition ----------------

    def snapshot(self, limit: int = 32) -> dict:
        """Wire form for worker stats broadcasts: the newest ``limit``
        events + per-kind lifetime counts (fleet `/cluster/events` merges
        the recent lists; dynotop's EVT column reads the counts)."""
        with self._lock:
            recent = list(self._ring)[-limit:]
            counts = dict(self._counts)
            emitted = self._seq
            captures = len(self._captures)
        return {
            "emitted": emitted,
            "counts": counts,
            "captures": captures,
            "recent": [e.to_wire() for e in recent],
        }

    def render_metrics(self, prefix: str = "dynamo_event") -> str:
        from dynamo_tpu.utils.prometheus import render_family

        with self._lock:
            counts = sorted(self._counts.items())
            size = len(self._ring)
            pinned = self.pinned_total
        out = render_family(
            f"{prefix}_emitted_total", "counter",
            "lifecycle events appended to the flight-recorder journal, by kind",
            [({"kind": k}, n) for k, n in counts]
            or [({"kind": "request.enqueued"}, 0)],
        )
        out += render_family(
            f"{prefix}_journal_size", "gauge",
            "events currently resident in the bounded journal ring",
            [({}, size)],
        )
        out += render_family(
            f"{prefix}_captures_pinned_total", "counter",
            "slow/errored request event chains pinned to the capture ring "
            "(they survive journal eviction for /debug/requests forensics)",
            [({}, pinned)],
        )
        return out

    # ---------------- black box ----------------

    def dump_post_mortem(self, reason: str, path: Optional[str] = None) -> Optional[str]:
        """Write the ring (oldest first) as JSONL — the crash black box.
        Never raises: a failing dump must not mask the crash it documents.
        Returns the path written, or None."""
        import tempfile

        with self._lock:
            events = list(self._ring)
        if path is None:
            directory = os.environ.get(POSTMORTEM_DIR_ENV) or tempfile.gettempdir()
            path = os.path.join(
                directory,
                f"dyntpu-postmortem-{os.getpid()}-{int(time.time())}.jsonl",
            )
        try:
            with open(path, "w") as f:
                f.write(json.dumps({
                    "postmortem": reason,
                    "wall": time.time(),
                    "pid": os.getpid(),
                    "events": len(events),
                }) + "\n")
                for e in events:
                    f.write(json.dumps(e.to_wire(), default=str) + "\n")
        except OSError:
            return None
        return path


#: the per-process journal every plane emits into (tests construct their own)
JOURNAL = EventJournal()


def emit(kind: str, **kwargs) -> Event:
    return JOURNAL.emit(kind, **kwargs)


def merge_recent(worker_events: list[tuple[str, dict]], limit: int = 200) -> list[dict]:
    """Fleet timeline: merge per-worker ``snapshot()['recent']`` lists into
    one (wall, seq)-ordered view, each event labeled with its worker. Pure —
    `components/metrics` and tests call it off scraped stats."""
    merged: list[dict] = []
    for worker_id, snap in worker_events:
        for ev in (snap or {}).get("recent", ()):
            merged.append({**ev, "worker_id": worker_id})
    merged.sort(key=lambda e: (e.get("wall", 0.0), e.get("seq", 0)))
    return merged[-limit:]
