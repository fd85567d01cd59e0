"""End-to-end request tracing: a lightweight span recorder with
Chrome-trace/Perfetto export.

Spans ride the ambient :class:`~dynamo_tpu.runtime.context.RequestContext`
(the id + metadata bag that already crosses every network hop): the trace id
stamped at the edge lands in the context's metadata, every hop's handler
re-enters the context, and every span recorded anywhere in the stack carries
that trace id — so one request's spans from the HTTP frontend, the
processor/router, the prefill worker, and the decode worker stitch into a
single timeline keyed by ``trace_id``.

Two sinks, one timed block. The recorder is off by default: a ``span()``
then costs one ``time.monotonic()`` pair and appends nothing. Enable it with
``DYNTPU_TRACE=<path>`` (spans append to the file as JSONL, one Chrome trace
event per line) or programmatically via :func:`enable` (in-memory ring only
when no path is given). ``tools/trace_view.py`` summarizes a capture;
the HTTP service's ``/trace`` endpoint serves the in-memory ring as a
Perfetto-loadable ``{"traceEvents": [...]}`` document.

Whether or not the recorder is on, a ``span()`` in a process that has
imported JAX is also a ``jax.profiler.TraceAnnotation`` of the same name with
the span's scalar attributes: it costs under a microsecond while no profiler
session runs, and in any ``jax.profiler`` trace it lands on its thread's line
of the host plane, on the same clock as the device's operations. That is
what lets a reduction of the trace say what the host did while the device
waited (``benchmark/trace_steps.py``). A process that never imports JAX (a
frontend or a processor of a multi-process deployment) gets the recorder's
span and nothing else.

Event shape (Chrome trace event format, complete-event ``ph: "X"``)::

    {"name": "engine.prefill", "ph": "X", "cat": "dyntpu",
     "ts": <epoch µs>, "dur": <µs>, "pid": <os pid>, "tid": <thread id>,
     "args": {"trace_id": ..., "request_id": ..., "thread": ...,
              "parent": <name of the enclosing span, or None>, ...}}

``ts`` is epoch-anchored (one monotonic->epoch offset captured at import), so
events from different processes line up on a shared timeline.
"""

from __future__ import annotations

import contextvars
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Optional


def _ambient_context():
    # lazy: the runtime package imports utils during its own bootstrap
    from dynamo_tpu.runtime.context import current_context

    return current_context()


TRACE_ENV = "DYNTPU_TRACE"
MAX_EVENTS = 65536

# monotonic->epoch anchor: span timers use monotonic, exported ts is epoch µs
_EPOCH_OFFSET = time.time() - time.monotonic()

# the span open around the running code: per thread and per asyncio task, so
# that tasks interleaving on one loop never become each other's parent
_open_span: contextvars.ContextVar = contextvars.ContextVar("dyntpu_span", default=None)
# jax.profiler.TraceAnnotation, once this process has imported JAX
_annotation = None
_SCALARS = (bool, int, float, str)

_lock = threading.Lock()
_events: deque = deque(maxlen=MAX_EVENTS)
_file = None
_path: Optional[str] = None
_enabled = bool(os.environ.get(TRACE_ENV))
if _enabled:
    _path = os.environ[TRACE_ENV]


def enabled() -> bool:
    return _enabled


def enable(path: Optional[str] = None) -> None:
    """Turn the recorder on; ``path`` (or $DYNTPU_TRACE) gets JSONL appends."""
    global _enabled, _path
    with _lock:
        _enabled = True
        if path is not None:
            _path = path


def disable() -> None:
    global _enabled, _file, _path
    with _lock:
        _enabled = False
        if _file is not None:
            try:
                _file.close()
            except OSError:
                pass
            _file = None
        # a later bare enable() starts fresh (env-configured path or memory
        # only) instead of appending to whatever path the last enable() used
        _path = os.environ.get(TRACE_ENV) or None


def clear() -> None:
    with _lock:
        _events.clear()


def current_trace_id() -> Optional[str]:
    """Trace id of the ambient request context (metadata-stamped id, falling
    back to the request id), or None outside a request."""
    ctx = _ambient_context()
    if ctx is None:
        return None
    return ctx.metadata.get("trace_id") or ctx.request_id


def _write_line(ev: dict) -> None:
    global _file
    if _path is None:
        return
    try:
        if _file is None:
            _file = open(_path, "a", buffering=1)
        _file.write(json.dumps(ev, default=str) + "\n")
    except OSError:
        pass  # tracing must never take the serving path down


def record_span(
    name: str,
    start: float,
    end: Optional[float] = None,
    duration: Optional[float] = None,
    request_id: Optional[str] = None,
    trace_id: Optional[str] = None,
    attrs: Optional[dict] = None,
) -> None:
    """Record one complete span. ``start``/``end`` are time.monotonic() values;
    pass ``duration`` instead of ``end`` when more convenient. request/trace
    ids default to the ambient context's — pass them explicitly on threads
    that run outside the request context (the engine loop). ``parent`` is the
    ``span()`` block this call was made in, if any. An interval that is over
    cannot be put on the profiler's clock: only ``span()`` blocks reach a
    ``jax.profiler`` trace."""
    if not _enabled:
        return
    if duration is None:
        duration = (end if end is not None else time.monotonic()) - start
    if request_id is None or trace_id is None:
        ctx = _ambient_context()
        if ctx is not None:
            if request_id is None:
                request_id = ctx.request_id
            if trace_id is None:
                trace_id = ctx.metadata.get("trace_id") or ctx.request_id
    if trace_id is None:
        trace_id = request_id
    thread = threading.current_thread()
    args = {"trace_id": trace_id, "request_id": request_id, "thread": thread.name,
            "parent": _open_span.get()}
    if attrs:
        args.update(attrs)
    ev = {
        "name": name,
        "ph": "X",
        "cat": "dyntpu",
        "ts": int((start + _EPOCH_OFFSET) * 1e6),
        "dur": max(0, int(duration * 1e6)),
        "pid": os.getpid(),
        "tid": thread.ident or 0,
        "args": args,
    }
    with _lock:
        _events.append(ev)
        _write_line(ev)


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` once this process has imported JAX,
    None until then: a span never makes a process import JAX."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # JAX is still being imported on another thread
            return None
        _annotation = TraceAnnotation
    return _annotation


class span:
    """Time a block as one span: ONE ``time.monotonic()`` pair, read back as
    ``.t0`` / ``.t1`` / ``.dt`` by a caller that feeds the same interval to
    counters of its own. The block is a ``jax.profiler.TraceAnnotation`` named
    ``name`` with the scalar ``attrs`` (see the module docstring) and, when
    the recorder is on, one Chrome event named ``alias or name`` whose
    ``parent`` is the enclosing span. Works across awaits: it measures the
    wall time of the enclosed block."""

    __slots__ = ("name", "alias", "request_id", "trace_id", "attrs",
                 "t0", "t1", "_annotation", "_token")

    def __init__(self, name: str, request_id: Optional[str] = None,
                 trace_id: Optional[str] = None, alias: Optional[str] = None,
                 **attrs):
        self.name = name
        self.alias = alias or name  # the recorder's name for the event
        self.request_id = request_id
        self.trace_id = trace_id
        self.attrs = attrs
        self.t0 = self.t1 = 0.0

    @property
    def dt(self) -> float:
        return self.t1 - self.t0

    def __enter__(self) -> "span":
        cls = _trace_annotation()
        if cls is None:
            self._annotation = None
        else:
            self._annotation = cls(self.name, **{
                k: v for k, v in self.attrs.items() if isinstance(v, _SCALARS)
            })
            self._annotation.__enter__()
        self._token = _open_span.set(self.alias)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        _open_span.reset(self._token)
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        if _enabled:
            record_span(
                self.alias, self.t0, end=self.t1,
                request_id=self.request_id, trace_id=self.trace_id,
                attrs=self.attrs or None,
            )


def events(
    trace_id: Optional[str] = None, request_id: Optional[str] = None
) -> list[dict]:
    """Snapshot of the in-memory ring, optionally filtered."""
    with _lock:
        snap = list(_events)
    if trace_id is not None:
        snap = [e for e in snap if e["args"].get("trace_id") == trace_id]
    if request_id is not None:
        snap = [e for e in snap if e["args"].get("request_id") == request_id]
    return snap


def trace_ids() -> list[str]:
    """Distinct trace ids currently in the ring (insertion order)."""
    seen: dict[str, None] = {}
    with _lock:
        for e in _events:
            tid = e["args"].get("trace_id")
            if tid:
                seen.setdefault(tid, None)
    return list(seen)


def export(trace_id: Optional[str] = None) -> dict:
    """Perfetto/chrome://tracing-loadable document."""
    return {
        "displayTimeUnit": "ms",
        "traceEvents": events(trace_id=trace_id),
        "otherData": {"source": "dynamo_tpu", "enabled": _enabled},
    }
