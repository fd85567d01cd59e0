"""Monitored jit: count XLA compiles and cumulative compile seconds.

Recompile storms are the top TPU serving hazard: a stray dynamic shape (an
unbucketed prompt length, a new sampling-feature combination mid-traffic)
silently turns ms-scale steps into multi-second XLA compiles, and nothing in
the serving metrics distinguishes that from device slowness. ``monitored_jit``
wraps a ``jax.jit``-ed callable and charges any call that grew the function's
executable cache to a shared ``CompileMonitor`` — count, cumulative seconds,
and the last compile's label/age land in the engine's resource gauges, so a
storm shows up as a climbing ``dynamo_engine_xla_compiles_total`` instead of
an unexplained latency cliff.

Detection uses the jitted function's ``_cache_size()`` (present on every jax
version this repo supports): a call that returns with a bigger cache compiled.
The attributed seconds include trace time — exactly the stall a request
experienced. Wrappers are transparent for plain calls; attribute access
forwards to the wrapped function.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from dynamo_tpu.utils.logging import get_logger

log = get_logger("utils.compile_monitor")


class CompileMonitor:
    """Shared compile telemetry for one process's jitted functions."""

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.last_label: Optional[str] = None
        self.last_ts: Optional[float] = None
        self.per_label: dict[str, int] = {}

    def record(self, label: str, seconds: float, count: int = 1, what: str = "") -> None:
        with self._lock:
            self.compiles += count
            self.compile_s += seconds
            self.last_label = label
            self.last_ts = self._clock()
            self.per_label[label] = self.per_label.get(label, 0) + count
        if seconds > 1.0:
            # a slow compile mid-serving is worth a log line even without
            # Prometheus scraping: it is the stall the caller just felt
            # `what` names the program among the label's variants: without it
            # a log cannot say WHICH shape traffic met first
            log.info("xla compile: %s took %.2fs (%d total)%s", label, seconds,
                     self.compiles, f" [{what}]" if what else "")

    def snapshot(self) -> dict:
        with self._lock:
            age = (
                round(self._clock() - self.last_ts, 3)
                if self.last_ts is not None
                else None
            )
            return {
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 4),
                "last_label": self.last_label,
                "last_compile_age_s": age,
                "per_label": dict(self.per_label),
            }


def _describe(args, kwargs) -> str:
    """What tells one executable of a jitted function from another, as far as
    a call shows it: the shapes of its small integer arrays (the packed
    control arrays of the engine's step programs carry lanes, rows and table
    width in theirs) and its static keywords that are set. Built only when a
    call compiled."""
    shapes = ["x".join(map(str, a.shape)) for a in args
              if getattr(a, "ndim", 0) == 2 and getattr(a, "dtype", None) is not None
              and a.dtype.kind == "i"]
    flags = [k if v is True else f"{k}={v}" for k, v in kwargs.items()
             if isinstance(v, (bool, int)) and v]
    return " ".join(shapes + flags)


class _MonitoredJit:
    """Callable proxy over a jitted function; detects cache growth per call."""

    __slots__ = ("_fn", "_label", "_monitor", "_clock")

    def __init__(self, fn, label: str, monitor: CompileMonitor, clock=time.monotonic):
        self._fn = fn
        self._label = label
        self._monitor = monitor
        self._clock = clock

    def _cache_size(self) -> Optional[int]:
        probe = getattr(self._fn, "_cache_size", None)
        if probe is None:
            return None
        try:
            return probe()
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        t0 = self._clock()
        result = self._fn(*args, **kwargs)
        if before is not None:
            after = self._cache_size()
            if after is not None and after > before:
                self._monitor.record(self._label, self._clock() - t0, after - before,
                                     _describe(args, kwargs))
        return result

    def __getattr__(self, name):
        return getattr(self._fn, name)


def monitored_jit(fn, label: str, monitor: Optional[CompileMonitor]):
    """Wrap an already-jitted callable; ``monitor=None`` is a passthrough."""
    if monitor is None:
        return fn
    return _MonitoredJit(fn, label, monitor)
