"""Goodput accounting: per-request SLO outcomes -> windowed goodput.

The reference frames planner and disagg verdicts in DistServe-style *goodput*
terms: the fraction of requests that met their latency budgets, not raw
throughput. This module is the accounting half of the trace-replay harness
(``dynamo_tpu/loadgen/``): every finished request produces ONE
``RequestOutcome`` — TTFT, the per-token inter-arrival series, queue wait,
token counts, and the tenant/adapter/scenario tags the request carried — and
a ``GoodputTracker`` folds outcomes into a rolling window of met/missed/error
verdicts per scenario and per tenant.

A request MEETS its SLO when it finished without error, its TTFT is within
the TTFT budget, and the p99 of its OWN inter-token-latency series is within
the ITL budget (per-request p99, the DistServe criterion — a single stalled
window blows the request, averaging cannot hide it). Budgets resolve
per-outcome first (a replay scenario stamps its own), then the tracker's
defaults; an unset budget never fails a request.

Exposed as the ``dynamo_goodput_*`` Prometheus families on the engine and
HTTP-frontend /metrics surfaces (conformance-checked), in worker stats
broadcasts (dynotop's GOODPUT column), and — via ``summarize_outcomes`` — as
the per-scenario report of ``dynamo_tpu.loadgen``'s replay.

Thread-safe: the engine loop and the HTTP asyncio thread both observe.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Iterable, Optional

# per-request ITL series cap: enough for a 128K-output request at one gap per
# token; beyond that the p99 is already stable and memory growth is the risk
MAX_ITL_SAMPLES = 8192


def percentile(vals, p: float) -> Optional[float]:
    """Nearest-rank percentile; None on an empty series (never 0.0 — a fake
    zero p99 reads as a *great* latency, the worst possible failure mode)."""
    vals = sorted(vals)
    if not vals:
        return None
    k = max(0, min(len(vals) - 1, int(round(p / 100.0 * (len(vals) - 1)))))
    return vals[k]


@dataclass
class RequestOutcome:
    """One finished request's SLO-relevant facts (the unit of goodput)."""

    request_id: str
    scenario: str = ""  # replay scenario tag ("" = organic traffic)
    tenant: str = ""
    adapter: str = ""  # LoRA adapter name ("" = base model)
    queue_wait_s: Optional[float] = None  # engine submission -> admission
    ttft_s: Optional[float] = None  # submission -> first token (None = no token)
    # per-token inter-arrival gaps AFTER the first token, client-shaped: a
    # decode window's tokens land together, so the series is bursty by
    # design and its p99 is the honest stall signal
    itl_s: tuple = ()
    prompt_tokens: int = 0
    output_tokens: int = 0
    cached_tokens: int = 0
    duration_s: float = 0.0  # submission -> finish
    finish_reason: str = ""  # stop | length | error | ...
    error: bool = False
    # per-request budget overrides (seconds; None = use the tracker default)
    ttft_budget_s: Optional[float] = None
    itl_budget_s: Optional[float] = None

    def itl_p99_s(self) -> Optional[float]:
        return percentile(self.itl_s, 99)

    def itl_p50_s(self) -> Optional[float]:
        return percentile(self.itl_s, 50)

    def to_wire(self) -> dict:
        """Compact wire/JSONL form: the ITL series collapses to its
        percentiles (a 8K-entry float list per request would dwarf the
        record it annotates)."""
        p50, p99 = self.itl_p50_s(), self.itl_p99_s()
        return {
            "request_id": self.request_id,
            "scenario": self.scenario,
            "tenant": self.tenant,
            "adapter": self.adapter,
            "queue_wait_ms": _ms(self.queue_wait_s),
            "ttft_ms": _ms(self.ttft_s),
            "itl_p50_ms": _ms(p50),
            "itl_p99_ms": _ms(p99),
            "itl_n": len(self.itl_s),
            "prompt_tokens": self.prompt_tokens,
            "output_tokens": self.output_tokens,
            "cached_tokens": self.cached_tokens,
            "duration_ms": _ms(self.duration_s),
            "finish_reason": self.finish_reason,
            "error": self.error,
        }


def _ms(s: Optional[float]) -> Optional[float]:
    return round(s * 1e3, 3) if s is not None else None


def outcome_meets(
    outcome: RequestOutcome,
    ttft_budget_s: Optional[float] = None,
    itl_budget_s: Optional[float] = None,
) -> bool:
    """The DistServe criterion for one request: finished cleanly, TTFT within
    budget, and the request's own ITL p99 within budget. Per-outcome budgets
    win over the passed defaults; an unset budget never fails."""
    if outcome.error:
        return False
    ttft_b = outcome.ttft_budget_s if outcome.ttft_budget_s is not None else ttft_budget_s
    itl_b = outcome.itl_budget_s if outcome.itl_budget_s is not None else itl_budget_s
    if ttft_b is not None:
        if outcome.ttft_s is None or outcome.ttft_s > ttft_b:
            return False
    if itl_b is not None:
        p99 = outcome.itl_p99_s()
        if p99 is not None and p99 > itl_b:
            return False
    return True


@dataclass
class _Sample:
    ts: float
    scenario: str
    tenant: str
    adapter: str
    met: bool
    error: bool
    ttft_s: Optional[float]
    itl_p99_s: Optional[float]
    output_tokens: int


class GoodputTracker:
    """Rolling-window goodput per scenario and per tenant.

    goodput(window) = met / (met + missed + errors) over the window's
    finished requests. Lifetime met/missed/error counters survive window
    pruning (the ``dynamo_goodput_requests_total`` counter family)."""

    def __init__(
        self,
        ttft_budget_s: Optional[float] = None,
        itl_budget_s: Optional[float] = None,
        window_s: float = 300.0,
        max_samples: int = 8192,
        clock=time.monotonic,
    ):
        self.ttft_budget_s = ttft_budget_s
        self.itl_budget_s = itl_budget_s
        self.window_s = window_s
        self._clock = clock
        self._lock = threading.Lock()
        self._window: deque[_Sample] = deque(maxlen=max_samples)
        # lifetime (scenario) -> [met, missed, errors]; "" = untagged traffic
        self._totals: dict[str, list] = {}
        self._tenant_totals: dict[str, list] = {}

    # ---------------- ingest ----------------

    def observe(self, outcome: RequestOutcome) -> bool:
        """Fold one finished request in; returns whether it met its SLO."""
        met = outcome_meets(outcome, self.ttft_budget_s, self.itl_budget_s)
        now = self._clock()
        with self._lock:
            self._window.append(_Sample(
                now, outcome.scenario, outcome.tenant, outcome.adapter,
                met, outcome.error,
                outcome.ttft_s, outcome.itl_p99_s(), outcome.output_tokens,
            ))
            for totals, key in (
                (self._totals, outcome.scenario),
                (self._tenant_totals, outcome.tenant),
            ):
                t = totals.setdefault(key, [0, 0, 0])
                if outcome.error:
                    t[2] += 1
                elif met:
                    t[0] += 1
                else:
                    t[1] += 1
        return met

    def _prune(self, now: float) -> None:
        cutoff = now - self.window_s
        while self._window and self._window[0].ts < cutoff:
            self._window.popleft()

    # ---------------- evaluation ----------------

    def snapshot(self) -> dict:
        """Wire form: overall + per-scenario + per-tenant windowed goodput
        (None with an empty window — never a fake 1.0 or 0.0) and lifetime
        counters."""
        now = self._clock()
        with self._lock:
            self._prune(now)
            window = list(self._window)
            totals = {k: list(v) for k, v in self._totals.items()}
            tenant_totals = {k: list(v) for k, v in self._tenant_totals.items()}

        def fold(samples: list) -> dict:
            n = len(samples)
            met = sum(1 for s in samples if s.met)
            ttfts = [s.ttft_s for s in samples if s.ttft_s is not None]
            itls = [s.itl_p99_s for s in samples if s.itl_p99_s is not None]
            return {
                "requests": n,
                "met": met,
                "errors": sum(1 for s in samples if s.error),
                "goodput": round(met / n, 5) if n else None,
                "ttft_p99_ms": _ms(percentile(ttfts, 99)),
                "itl_p99_ms": _ms(percentile(itls, 99)),
            }

        scenarios = sorted({s.scenario for s in window} | set(totals))
        tenants = sorted(
            ({s.tenant for s in window} | set(tenant_totals)) - {""}
        )
        snap = {
            "window_s": self.window_s,
            "ttft_budget_ms": _ms(self.ttft_budget_s),
            "itl_budget_ms": _ms(self.itl_budget_s),
            **fold(window),
            "scenarios": {
                sc: {
                    **fold([s for s in window if s.scenario == sc]),
                    "lifetime": dict(zip(
                        ("met", "missed", "errors"), totals.get(sc, [0, 0, 0])
                    )),
                }
                for sc in scenarios
            },
            "tenants": {
                t: fold([s for s in window if s.tenant == t]) for t in tenants
            },
        }
        # (tenant, adapter)-keyed windows, join key "tenant|adapter" — the
        # SAME key MeterLedger.snapshot()["adapters"] uses, so /cluster/status
        # readers join cost (device-seconds) against goodput per adapter
        # without re-parsing labels. Fully-untagged traffic ("|") is omitted;
        # base-model requests of a tagged tenant keep their "tenant|" row.
        pairs = sorted(
            {(s.tenant, s.adapter) for s in window} - {("", "")}
        )
        snap["adapters"] = {
            f"{t}|{a}": fold(
                [s for s in window if s.tenant == t and s.adapter == a]
            )
            for t, a in pairs
        }
        return snap

    def goodput(self, scenario: Optional[str] = None) -> Optional[float]:
        snap = self.snapshot()
        if scenario is None:
            return snap["goodput"]
        sc = snap["scenarios"].get(scenario)
        return sc["goodput"] if sc else None

    # ---------------- exposition ----------------

    def render_metrics(self, prefix: str = "dynamo_goodput") -> str:
        from dynamo_tpu.utils.prometheus import render_family

        snap = self.snapshot()
        ratio_samples = []
        if snap["goodput"] is not None:
            ratio_samples.append(({"scenario": ""}, snap["goodput"]))
        ttft_samples, itl_samples = [], []
        for sc, s in sorted(snap["scenarios"].items()):
            if s["goodput"] is not None:
                ratio_samples.append(({"scenario": sc}, s["goodput"]))
            if s["ttft_p99_ms"] is not None:
                ttft_samples.append(({"scenario": sc}, s["ttft_p99_ms"] / 1e3))
            if s["itl_p99_ms"] is not None:
                itl_samples.append(({"scenario": sc}, s["itl_p99_ms"] / 1e3))
        out = render_family(
            f"{prefix}_ratio", "gauge",
            "windowed fraction of finished requests meeting their TTFT/ITL-p99 "
            "budgets, by scenario (scenario=\"\" = all traffic; absent = empty "
            "window)",
            ratio_samples or [({"scenario": ""}, 1.0)],
        )
        totals = []
        with self._lock:
            for sc, t in sorted(self._totals.items()):
                for i, result in enumerate(("met", "missed", "error")):
                    totals.append(({"scenario": sc, "result": result}, t[i]))
        out += render_family(
            f"{prefix}_requests_total", "counter",
            "lifetime finished requests by scenario and SLO verdict",
            totals or [({"scenario": "", "result": "met"}, 0)],
        )
        if ttft_samples:
            out += render_family(
                f"{prefix}_ttft_p99_seconds", "gauge",
                "windowed p99 of per-request TTFT by scenario", ttft_samples,
            )
        if itl_samples:
            out += render_family(
                f"{prefix}_itl_p99_seconds", "gauge",
                "windowed p99 of per-request ITL-p99 by scenario", itl_samples,
            )
        tenant_samples = [
            ({"tenant": t}, s["goodput"])
            for t, s in sorted(snap["tenants"].items())
            if s["goodput"] is not None
        ]
        if tenant_samples:
            out += render_family(
                f"{prefix}_tenant_ratio", "gauge",
                "windowed goodput by tenant (multi-tenant QoS view)",
                tenant_samples,
            )
        return out


def summarize_outcomes(
    outcomes: Iterable[RequestOutcome],
    wall_s: Optional[float] = None,
    ttft_budget_s: Optional[float] = None,
    itl_budget_s: Optional[float] = None,
) -> dict:
    """Replay report over a finished outcome set: goodput against the
    budgets, pooled TTFT/ITL percentiles (ms), and output tok/s over
    ``wall_s`` (the replay's wall clock)."""
    outcomes = list(outcomes)
    n = len(outcomes)
    met = sum(
        1 for o in outcomes if outcome_meets(o, ttft_budget_s, itl_budget_s)
    )
    ttfts = [o.ttft_s for o in outcomes if o.ttft_s is not None]
    gaps: list[float] = []
    for o in outcomes:
        gaps.extend(o.itl_s)
    queue_waits = [o.queue_wait_s for o in outcomes if o.queue_wait_s is not None]
    out_tokens = sum(o.output_tokens for o in outcomes)
    return {
        "requests": n,
        "errors": sum(1 for o in outcomes if o.error),
        "goodput": round(met / n, 4) if n else None,
        "ttft_p50_ms": _ms(percentile(ttfts, 50)),
        "ttft_p99_ms": _ms(percentile(ttfts, 99)),
        "itl_p50_ms": _ms(percentile(gaps, 50)),
        "itl_p99_ms": _ms(percentile(gaps, 99)),
        "queue_wait_p99_ms": _ms(percentile(queue_waits, 99)),
        "output_tokens": out_tokens,
        "cached_tokens": sum(o.cached_tokens for o in outcomes),
        "tok_s": (
            round(out_tokens / wall_s, 2) if wall_s and wall_s > 0 else None
        ),
        "ttft_budget_ms": _ms(ttft_budget_s),
        "itl_budget_ms": _ms(itl_budget_s),
    }
