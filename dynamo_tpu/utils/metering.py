"""Cost attribution plane: per-tenant device-time and KV-residency metering.

The repo measures what tenants *experience* (goodput/SLO windows, PR 11) and
what they're *allowed* (QoS token buckets, PR 15), but until this module
nothing measured what they actually *consume*: device-seconds and KV
byte-seconds were only accounted globally (step anatomy; the
``dynamo_engine_kv_pages`` gauge). The :class:`MeterLedger` closes that gap
with two attributed planes that are **conservation-checked** against the
global instruments they shadow — in the step-anatomy tradition, the planes
can never disagree:

**Device-time plane.** Every engine dispatch already lands its four phases
(host_prep/dispatch/device_wait/reconcile) on a ``StepRecord`` through
``StepAnatomy.add_phase``. Each record now carries a *bill*: the list of
``(request_id, tenant, adapter, priority, weight)`` rows participating in the
dispatch, weighted by the token rows each contributes (decode steps per lane,
prompt rows per packed-prefill chunk, draft+1 rows per spec-verify lane).
``add_phase`` forwards every clamped phase delta here and the ledger splits
it across the bill proportionally, so by construction

    sum over (tenant, adapter, priority, kind) of device_seconds
      == sum over (phase, kind) of StepAnatomy.phase_seconds

to float round-off. Dispatches no request caused (offload drains, LoRA slot
loads) bill the empty *system* key — attributed time is partitioned, never
invented or dropped.

**KV-residency plane.** Byte-seconds of residency per tier (hbm/host/disk),
integrated lazily on the exact allocate/free/demote/restore edges the
``PageAllocator`` / ``HostKvPool`` / ``DiskKvStore`` ladder already executes.
Ownership model: a resident block is owned by the ``(tenant, request_id)``
that first made its bytes resident. Prefix-cache hits (refcount bumps,
host/disk membership hits) never re-own; a freed-but-cached reusable page
keeps charging its creator — residency *is* the benefit the prefix cache
sells, so its cost stays attributed. Demotions (hbm -> host -> disk) carry
the owner down the ladder; promotions re-own to the restoring request (its
prompt is why the bytes came back up). A global per-tier occupancy integral
is maintained on the *same* edges with the *same* timestamps, so

    sum over tenants of kv_byte_seconds[tier] == occupancy integral[tier]

exactly (shared piecewise-constant integration grid; tests/test_metering.py
asserts both identities).

**Queue/token plane.** Queued-seconds per tenant at admission, plus
admitted-vs-consumed token counters against the QoS bucket charge
(``admitted`` = the prompt+budget tokens the bucket was debited;
``prompt``/``output`` = what the engine actually computed), so the
admission-estimate-vs-realized-cost gap (the VTC fairness critique) is a
standing measurement instead of a hope.

Surfaces: ``render_metrics`` emits the five ``dynamo_cost_*`` families on
the engine's conformance surface; ``snapshot()`` rides resource_snapshot ->
worker stats -> ``/cluster/costs`` and the dynotop COST column;
``request_cost()`` backs the cost footer on ``/debug/requests/{id}`` from a
bounded LRU of per-request footers. ``PlannerService`` consumes the merged
per-tenant burn as the ROADMAP-item-1 demand signal.

Zero-cost when off: ``EngineConfig.metering=False`` wires no ledger anywhere
and every hook site is a ``meter is not None`` check.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Optional

#: KV residency tiers, top to bottom of the offload ladder
TIERS = ("hbm", "host", "disk")

#: charge kinds on dynamo_cost_tokens_total{kind=}: admitted = the QoS bucket
#: debit at admission; prompt/output = tokens the engine actually computed
TOKEN_KINDS = ("admitted", "prompt", "output")

#: (tenant, adapter, priority) for engine work no request caused — offload
#: drains, LoRA slot loads, untracked reconciles. Empty labels render as
#: tenant="" and keep the device-time partition exhaustive.
SYSTEM_KEY = ("", "", "")

#: per-request cost footers retained for /debug/requests/{id} (LRU bound —
#: footers are forensics, not accounting; the ledger totals never evict)
DEFAULT_FOOTERS = 256


class MeterLedger:
    """Per-(tenant, adapter, priority-class) cost accumulators.

    Thread-safe: the engine thread writes on every dispatch phase and KV
    edge; snapshot/render/request_cost run on the asyncio and scrape
    threads. The write path is a handful of dict float-adds under one lock.
    The clock is injectable so conservation tests can drive a fake timeline.
    """

    def __init__(self, clock=None, footer_capacity: int = DEFAULT_FOOTERS):
        self._lock = threading.Lock()
        self._clock = clock or time.monotonic
        # ---- device-time plane: (tenant, adapter, priority, kind) -> s
        self.device_seconds: dict[tuple, float] = {}
        # ---- KV plane, per tier:
        #   _kv_blocks: key -> (nbytes, owner)   owner = (tenant, request_id)
        #   _kv_tenant: tenant -> [resident_bytes, last_ts, byte_seconds]
        #   _kv_global: [resident_bytes, last_ts, byte_seconds]
        self._kv_blocks: dict[str, dict] = {t: {} for t in TIERS}
        self._kv_tenant: dict[str, dict] = {t: {} for t in TIERS}
        self._kv_global: dict[str, list] = {t: [0, None, 0.0] for t in TIERS}
        # ---- queue/token plane
        self.queued_seconds: dict[str, float] = {}
        self.token_counts: dict[tuple, int] = {}  # (tenant, kind) -> tokens
        # ---- per-request footers (LRU): rid -> footer dict
        self._footers: OrderedDict[str, dict] = OrderedDict()
        self._footer_cap = footer_capacity

    # ---------------- device-time plane (engine thread) ----------------

    def on_phase(self, rec, phase: str, dt: float) -> None:
        """Attribute one phase delta across the record's bill. Called by
        ``StepAnatomy.add_phase`` with the same clamped ``dt`` it adds to its
        own (phase, kind) counters — the two planes share every sample, which
        is what makes the conservation identity exact."""
        if dt <= 0:
            return
        kind = rec.kind if rec is not None else "decode_window"
        bill = getattr(rec, "bill", None) if rec is not None else None
        device = self.device_seconds
        with self._lock:
            if not bill:
                key = SYSTEM_KEY + (kind,)
                device[key] = device.get(key, 0.0) + dt
                return
            total_w = 0.0
            for row in bill:
                total_w += row[4]
            if total_w <= 0:
                total_w = float(len(bill))
            scale = dt / total_w
            footers = self._footers
            for rid, tenant, adapter, priority, weight in bill:
                share = scale * (weight if weight > 0 else 1.0)
                key = (tenant or "", adapter or "", priority or "", kind)
                device[key] = device.get(key, 0.0) + share
                if rid:
                    # hot path: no LRU bump per phase — footer recency rides
                    # creation and the (rarer) KV edges
                    ent = footers.get(rid)
                    if ent is None:
                        ent = self._footer(rid, tenant, adapter, priority)
                    elif adapter and not ent["adapter"]:
                        ent["adapter"] = str(adapter)
                        if priority and not ent["priority"]:
                            ent["priority"] = str(priority)
                    d = ent["device_s"]
                    d[kind] = d.get(kind, 0.0) + share

    # ---------------- KV-residency plane (engine thread) ----------------

    def _settle(self, entry: list, now: float) -> None:
        """Lazy piecewise-constant integration step: fold the time since the
        last edge at the current resident level, then advance the mark."""
        if entry[1] is not None and now > entry[1]:
            entry[2] += entry[0] * (now - entry[1])
        entry[1] = now

    def kv_acquire(self, tier: str, key, nbytes: int, owner) -> None:
        """Bytes became resident in ``tier`` under ``owner`` = (tenant,
        request_id). Idempotent: re-acquiring a resident key is a no-op (the
        original owner keeps paying — cache hits never re-own)."""
        if nbytes <= 0:
            return
        if owner:
            tenant = str(owner[0] or "")
            rid = str(owner[1] or "")
        else:
            tenant = rid = ""
        with self._lock:
            blocks = self._kv_blocks[tier]
            if key in blocks:
                return
            now = self._clock()
            blocks[key] = (int(nbytes), (tenant, rid))
            g = self._kv_global[tier]
            self._settle(g, now)
            g[0] += nbytes
            t = self._kv_tenant[tier].get(tenant)
            if t is None:
                t = self._kv_tenant[tier][tenant] = [0, now, 0.0]
            self._settle(t, now)
            t[0] += nbytes
            if rid:
                # hot path: no LRU bump per page — footer recency rides
                # creation
                ent = self._footers.get(rid)
                if ent is None:
                    ent = self._footer(rid, tenant, None, None)
                res = ent["kv_resident"].get(tier, 0) + nbytes
                ent["kv_resident"][tier] = res
                if res > ent["kv_peak"].get(tier, 0):
                    ent["kv_peak"][tier] = res

    def kv_release(self, tier: str, key):
        """Bytes left ``tier``. Returns the owner tuple so demotion sites can
        carry it down the ladder; safe no-op (returns None) for keys this
        ledger never saw (metering attached mid-flight)."""
        with self._lock:
            rec = self._kv_blocks[tier].pop(key, None)
            if rec is None:
                return None
            nbytes, owner = rec
            now = self._clock()
            g = self._kv_global[tier]
            self._settle(g, now)
            g[0] -= nbytes
            t = self._kv_tenant[tier].get(owner[0])
            if t is not None:
                self._settle(t, now)
                t[0] = max(0, t[0] - nbytes)
            ent = self._footers.get(owner[1])
            if ent is not None:
                ent["kv_resident"][tier] = max(
                    0, ent["kv_resident"].get(tier, 0) - nbytes
                )
            return owner

    def kv_resident_bytes(self, tier: str) -> int:
        """Current global resident bytes the ledger believes ``tier`` holds —
        tests pin this against the pool's own occupancy truth."""
        with self._lock:
            return self._kv_global[tier][0]

    # ---------------- queue/token plane (engine thread) ----------------

    def queued(self, tenant, seconds: float) -> None:
        if seconds <= 0:
            return
        key = str(tenant or "")
        with self._lock:
            self.queued_seconds[key] = (
                self.queued_seconds.get(key, 0.0) + seconds
            )

    def charge_tokens(self, tenant, kind: str, n: int) -> None:
        if n <= 0:
            return
        key = (str(tenant or ""), kind)
        with self._lock:
            self.token_counts[key] = self.token_counts.get(key, 0) + int(n)

    # ---------------- per-request footers ----------------

    def _footer(self, rid: str, tenant, adapter, priority) -> dict:
        """Get-or-create the LRU footer for ``rid`` (lock held by caller)."""
        ent = self._footers.get(rid)
        if ent is None:
            ent = {
                "tenant": str(tenant or ""),
                "adapter": str(adapter or ""),
                "priority": str(priority or ""),
                "device_s": {},
                "kv_resident": {},
                "kv_peak": {},
            }
            self._footers[rid] = ent
            while len(self._footers) > self._footer_cap:
                self._footers.popitem(last=False)
        else:
            self._footers.move_to_end(rid)
            if adapter and not ent["adapter"]:
                ent["adapter"] = str(adapter)
            if priority and not ent["priority"]:
                ent["priority"] = str(priority)
        return ent

    def request_cost(self, rid: str) -> Optional[dict]:
        """JSON-safe cost footer for one request — the /debug/requests/{id}
        payload. None once the LRU evicted it (footers are forensics)."""
        with self._lock:
            ent = self._footers.get(rid)
            if ent is None:
                return None
            device_ms = {
                k: round(s * 1e3, 4) for k, s in sorted(ent["device_s"].items())
            }
            return {
                "request_id": rid,
                "tenant": ent["tenant"],
                "adapter": ent["adapter"],
                "priority": ent["priority"],
                "device_ms": device_ms,
                "device_ms_total": round(
                    sum(ent["device_s"].values()) * 1e3, 4
                ),
                "kv_peak_bytes": {
                    t: int(v) for t, v in sorted(ent["kv_peak"].items()) if v
                },
            }

    # ---------------- conservation (tests + bench) ----------------

    def device_seconds_total(self) -> float:
        with self._lock:
            return sum(self.device_seconds.values())

    def kv_byte_seconds(self, tier: str, now: Optional[float] = None) -> dict:
        """Settle ``tier`` to ``now`` and return both sides of the identity:
        per-tenant byte-seconds and the global occupancy integral."""
        with self._lock:
            if now is None:
                now = self._clock()
            g = self._kv_global[tier]
            self._settle(g, now)
            tenants = {}
            for tenant, t in self._kv_tenant[tier].items():
                self._settle(t, now)
                tenants[tenant] = t[2]
            return {
                "tenants": tenants,
                "global": g[2],
                "resident_bytes": g[0],
            }

    def conservation(self, anatomy=None, now: Optional[float] = None) -> dict:
        """Both identities in one report: attributed device-seconds vs the
        step-anatomy wall totals, and per-tier summed byte-seconds vs the
        occupancy integrals."""
        out: dict = {}
        if anatomy is not None:
            with anatomy._lock:
                wall = sum(anatomy.phase_seconds.values())
            mine = self.device_seconds_total()
            out["device"] = {
                "meter_s": mine,
                "anatomy_s": wall,
                "abs_err_s": abs(mine - wall),
                "rel_err": abs(mine - wall) / wall if wall > 0 else 0.0,
            }
        kv = {}
        for tier in TIERS:
            side = self.kv_byte_seconds(tier, now=now)
            total = sum(side["tenants"].values())
            glob = side["global"]
            kv[tier] = {
                "tenant_sum_byte_s": total,
                "global_byte_s": glob,
                "abs_err_byte_s": abs(total - glob),
                "rel_err": abs(total - glob) / glob if glob > 0 else 0.0,
            }
        out["kv"] = kv
        return out

    # ---------------- derived views (any thread) ----------------

    def snapshot(self) -> dict:
        """Wire-safe rollup for resource_snapshot -> worker stats ->
        /cluster/costs and the dynotop COST column: per-tenant device-seconds
        by kind, per-tier byte-seconds and residency, queue and token
        charges, plus a (tenant|adapter) join table for the goodput plane."""
        now = self._clock()
        with self._lock:
            device = dict(self.device_seconds)
            queued = dict(self.queued_seconds)
            tokens = dict(self.token_counts)
            kv_t: dict[str, dict] = {}
            kv_g: dict[str, dict] = {}
            for tier in TIERS:
                g = self._kv_global[tier]
                self._settle(g, now)
                kv_g[tier] = {
                    "resident_bytes": g[0],
                    "byte_s": round(g[2], 6),
                }
                for tenant, t in self._kv_tenant[tier].items():
                    self._settle(t, now)
                    row = kv_t.setdefault(
                        tenant, {"byte_s": {}, "resident_bytes": {}}
                    )
                    row["byte_s"][tier] = round(t[2], 6)
                    row["resident_bytes"][tier] = t[0]
        tenants: dict[str, dict] = {}

        def _trow(tenant: str) -> dict:
            return tenants.setdefault(tenant, {
                "device_s": 0.0, "by_kind": {}, "kv_byte_s": {},
                "kv_resident_bytes": {}, "queued_s": 0.0, "tokens": {},
            })

        adapters: dict[str, float] = {}
        for (tenant, adapter, _priority, kind), s in device.items():
            row = _trow(tenant)
            row["device_s"] = round(row["device_s"] + s, 6)
            row["by_kind"][kind] = round(row["by_kind"].get(kind, 0.0) + s, 6)
            jk = f"{tenant}|{adapter}"
            adapters[jk] = round(adapters.get(jk, 0.0) + s, 6)
        for tenant, kv_row in kv_t.items():
            row = _trow(tenant)
            row["kv_byte_s"] = kv_row["byte_s"]
            row["kv_resident_bytes"] = kv_row["resident_bytes"]
        for tenant, s in queued.items():
            _trow(tenant)["queued_s"] = round(s, 6)
        for (tenant, kind), n in tokens.items():
            _trow(tenant)["tokens"][kind] = n
        total = sum(v for v in (r["device_s"] for r in tenants.values()))
        top = ""
        top_s = -1.0
        for tenant, row in tenants.items():
            if tenant and row["device_s"] > top_s:
                top, top_s = tenant, row["device_s"]
        return {
            "tenants": tenants,
            "adapters": adapters,
            "tiers": kv_g,
            "device_s_total": round(total, 6),
            "top_tenant": top,
            "footers": len(self._footers),
        }

    def render_metrics(self) -> str:
        """The five dynamo_cost_* families for the engine's conformance
        exposition surface (the single emitting site graftlint pins)."""
        from dynamo_tpu.utils.prometheus import render_family

        now = self._clock()
        with self._lock:
            device = sorted(self.device_seconds.items())
            queued = sorted(self.queued_seconds.items())
            tokens = sorted(self.token_counts.items())
            byte_s: list = []
            resident: list = []
            for tier in TIERS:
                for tenant in sorted(self._kv_tenant[tier]):
                    t = self._kv_tenant[tier][tenant]
                    self._settle(t, now)
                    byte_s.append(
                        ({"tenant": tenant, "tier": tier}, round(t[2], 6))
                    )
                    resident.append(
                        ({"tenant": tenant, "tier": tier}, t[0])
                    )
        parts = [
            render_family(
                "dynamo_cost_device_seconds_total", "counter",
                "attributed engine device-time per tenant/adapter/priority "
                "and dispatch kind (sums to the step-anatomy wall totals by "
                "construction; empty tenant = unattributed system work)",
                [({"tenant": t, "adapter": a, "priority": p, "kind": k},
                  round(s, 6))
                 for (t, a, p, k), s in device]
                or [({"tenant": "", "adapter": "", "priority": "",
                      "kind": "decode_window"}, 0)],
            ),
            render_family(
                "dynamo_cost_kv_byte_seconds_total", "counter",
                "KV residency integral per tenant and tier (byte-seconds; "
                "sums to the tier occupancy integral by construction)",
                byte_s or [({"tenant": "", "tier": "hbm"}, 0)],
            ),
            render_family(
                "dynamo_cost_kv_resident_bytes", "gauge",
                "KV bytes currently resident per owning tenant and tier",
                resident or [({"tenant": "", "tier": "hbm"}, 0)],
            ),
            render_family(
                "dynamo_cost_queued_seconds_total", "counter",
                "seconds requests spent queued before admission, per tenant",
                [({"tenant": t}, round(s, 6)) for t, s in queued]
                or [({"tenant": ""}, 0)],
            ),
            render_family(
                "dynamo_cost_tokens_total", "counter",
                "token charges per tenant: admitted = the QoS bucket debit "
                "at admission; prompt/output = tokens the engine computed "
                "(the admitted-vs-consumed gap is the fairness residual)",
                [({"tenant": t, "kind": k}, n) for (t, k), n in tokens]
                or [({"tenant": "", "kind": "admitted"}, 0)],
            ),
        ]
        return "".join(parts)
