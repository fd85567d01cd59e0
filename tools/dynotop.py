#!/usr/bin/env python3
"""dynotop: live fleet dashboard over the metrics component's /cluster/status.

    python tools/dynotop.py --url http://127.0.0.1:9091
    python tools/dynotop.py --url http://127.0.0.1:9091 --once   # one snapshot

Renders one row per worker: health state, heartbeat/staleness, slot and KV
page occupancy, waiting queue, HBM, compile churn, and SLO state — the
operator view of the signals the router/planner consume machine-side.
No third-party deps (urllib + optional curses), so it runs on a bare TPU VM.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

STATE_GLYPH = {
    "ready": "●", "degraded": "◐", "starting": "○", "draining": "◌",
    "migrating": "◎", "dead": "✗", "unknown": "?",
}


def fetch_status(url: str, timeout: float = 2.0) -> dict:
    with urllib.request.urlopen(url.rstrip("/") + "/cluster/status", timeout=timeout) as r:
        return json.loads(r.read().decode())


def _fmt_bytes(n) -> str:
    n = float(n or 0)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if n < 1024 or unit == "TB":
            return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
        n /= 1024
    return "?"


def _slo_cell(slo: dict | None) -> str:
    if not slo or not slo.get("metrics"):
        return "-"
    worst = None
    for name, s in slo["metrics"].items():
        if s.get("target_ms") is None:
            continue
        b = s.get("error_budget", 1.0)
        if worst is None or b < worst[1]:
            worst = (name, b)
    if worst is None:
        return "untargeted"
    name, budget = worst
    flag = "OK" if budget > 0 else "BLOWN"
    return f"{name} budget {budget:+.2f} {flag}"


def _format_event(ev: dict) -> str:
    """One recent-events pane line: wall clock, worker, kind, ids, detail."""
    wall = ev.get("wall")
    clock = time.strftime("%H:%M:%S", time.localtime(wall)) if wall else "--:--:--"
    rid = ev.get("request_id", "")
    detail = ev.get("detail") or {}
    kv = " ".join(f"{k}={v}" for k, v in list(detail.items())[:4])
    tenant = ev.get("tenant", "")
    tag = f" [{tenant}]" if tenant else ""
    return (
        f"{clock} {str(ev.get('worker_id', '?')):<10} "
        f"{ev.get('kind', '?'):<26} {rid:<14}{tag} {kv}".rstrip()
    )


def render_status(doc: dict, events_rows: int = 8, events_offset: int = 0) -> str:
    """Pure renderer: /cluster/status JSON -> the dashboard text (testable
    without a cluster; curses and plain mode both draw this).
    ``events_rows``/``events_offset`` size and scroll the recent-events pane
    (offset counts lines back from the newest event)."""
    s = doc.get("summary", {})
    lines = [
        f"dynotop — {doc.get('namespace')}/{doc.get('component')}  "
        f"workers={s.get('workers', 0)} servable={s.get('servable', 0)} "
        f"stale={s.get('stale', 0)} unservable={s.get('unservable', 0)}  "
        f"scrape={doc.get('scrape_interval_s', '?')}s",
        "",
    ]
    header = (
        f"{'WORKER':<12} {'STATE':<10} {'HB':>6} {'SEEN':>6} {'MISS':>4} "
        f"{'SLOTS':>7} {'KV%':>6} {'KVMEM':>11} {'PREFIX':>9} {'RADIX':>7} "
        f"{'SPEC':>10} {'LORA':>11} {'TIER':>9} {'GOODPUT':>9} {'MIG':>7} "
        f"{'QOS':>9} {'EVT':>8} {'COST':>13} {'STEP':>11} {'ROOF':>5} {'PREFILL':>15} {'WAIT':>5} "
        f"{'HBM':>9} {'CMPL':>5}  SLO"
    )
    # router radix-index health (router broadcast via /cluster/status):
    # per-worker indexed-block counts feed the RADIX column; the fleet
    # totals (nodes vs cap, evictions, lookup hit rate) print as a footer
    radix = doc.get("router_radix") or {}
    radix_per_worker = radix.get("per_worker") or {}
    lines.append(header)
    lines.append("-" * len(header))
    for w in doc.get("workers", []):
        health = w.get("health") or {}
        state = health.get("state", "unknown")
        glyph = STATE_GLYPH.get(state, "?")
        kv = w.get("kv_metrics") or {}
        res = w.get("resources") or {}
        slots = f"{kv.get('request_active_slots', 0)}/{kv.get('request_total_slots', 0)}"
        kv_pct = 100.0 * kv.get("kv_active_blocks", 0) / max(1, kv.get("kv_total_blocks", 1))
        # KV pool bytes at the worker's ACTUAL cache dtype (resource gauges
        # carry kv_pool_bytes_*/kv_cache_dtype since the int8 KV cache —
        # the old render assumed bf16 and over-reported int8 workers 2x);
        # workers predating the gauges show "-"
        kv_used = res.get("kv_pool_bytes_used")
        if kv_used is None and res.get("kv_page_bytes"):
            kv_used = res.get("kv_pages_used", 0) * res["kv_page_bytes"]
        dt = str(res.get("kv_cache_dtype", "") or "")
        kv_mem = (
            f"{_fmt_bytes(kv_used)}:{dt[:4]}" if kv_used is not None and dt
            else (_fmt_bytes(kv_used) if kv_used is not None else "-")
        )
        # prefix-cache effectiveness, local vs remote: % of queried blocks
        # served by this worker's own cache vs pulled off fleet peers
        # (prefix_fetch_* counters ride resource_snapshot since the
        # fleet-wide prefix cache; older workers show "-")
        q = res.get("prefix_cache_query_blocks", 0)
        if q:
            lpct = 100.0 * res.get("prefix_cache_hit_blocks", 0) / q
            rpct = 100.0 * res.get("prefix_fetch_blocks", 0) / q
            prefix = f"{lpct:.0f}/{rpct:.0f}%"
        else:
            prefix = "-"
        # speculative decoding: proposer kind + acceptance rate (what the
        # verify passes actually keep), riding resource_snapshot's
        # spec_proposer / spec_acceptance_rate; non-spec workers show "-"
        kind = res.get("spec_proposer")
        if kind:
            spec = f"{str(kind)[:5]} {100.0 * res.get('spec_acceptance_rate', 0):.0f}%"
        else:
            spec = "-"
        # multi-LoRA: resident/capacity device slots + the hottest adapter
        # by admitted sequences (lora_* resource gauges; base-only workers
        # show "-")
        if res.get("lora_capacity"):
            hot = str(res.get("lora_hot", "") or "")[:6]
            lora = f"{res.get('lora_resident', 0)}/{res['lora_capacity']}"
            if hot:
                lora = f"{lora} {hot}"
        else:
            lora = "-"
        # KV tier ladder below HBM (engine/offload.py + engine/kv_store.py
        # via resource_snapshot): host-resident and disk-resident block
        # counts, with disk restore fallbacks flagged; workers without an
        # offload tier (or predating the plane) show "-"
        if res.get("offload_capacity_blocks"):
            tier = f"{res.get('offload_blocks_resident', 0)}h"
            if res.get("disk_budget_bytes") is not None:
                tier = f"{tier}/{res.get('disk_blocks_resident', 0)}d"
                if res.get("disk_io_errors"):
                    tier = f"{tier}!{res['disk_io_errors']}"
        else:
            tier = "-"
        # goodput: windowed fraction of finished requests meeting their
        # TTFT/ITL-p99 budgets (utils/goodput.py via worker stats); workers
        # with an empty window (or predating the plane) show "-"
        gp = w.get("goodput") or {}
        if gp.get("goodput") is not None:
            goodput = f"{100.0 * gp['goodput']:.0f}% ({gp.get('requests', 0)})"
        else:
            goodput = "-"
        # live migration (disagg/migrate.py via resource_snapshot): handoffs
        # OUT of this worker / adoptions IN, with failed handoffs flagged;
        # workers predating the plane (or with no migrations) show "-"
        m_out = res.get("migration_out")
        m_in = res.get("migration_in")
        if m_out or m_in or res.get("migration_out_failed"):
            mig = f"{m_out or 0}>{m_in or 0}"
            if res.get("migration_out_failed"):
                mig = f"{mig}!{res['migration_out_failed']}"
        else:
            mig = "-"
        # multi-tenant QoS (utils/qos.py via resource_snapshot): running
        # lanes per priority class (c/s/b) with cumulative shed count
        # flagged; workers predating the plane (or with QoS disabled and no
        # activity) show "-"
        qos_res = res.get("qos") or {}
        running = qos_res.get("running") or {}
        # per-class SLO state (utils/slo.py priority-keyed series): a class
        # letter gains "*" when any of its targeted metrics blew its error
        # budget — one glance says WHICH class is hurting, not just that
        # the aggregate is
        prio_slo = (w.get("slo") or {}).get("priorities") or {}

        def _blown(cls: str) -> str:
            states = prio_slo.get(cls) or {}
            return "*" if any(
                s.get("target_ms") is not None
                and s.get("error_budget", 1.0) <= 0
                for s in states.values()
            ) else ""

        if qos_res:
            qos = "/".join(
                f"{running.get(c, 0)}{c[0]}{_blown(c)}"
                for c in ("critical", "standard", "batch")
            )
            if qos_res.get("sheds"):
                qos = f"{qos}!{qos_res['sheds']}"
        else:
            qos = "-"
        # flight recorder (utils/events.py via worker stats): lifetime events
        # journaled, with pinned forensic captures flagged; workers predating
        # the plane show "-"
        ev = w.get("events") or {}
        if ev.get("emitted") is not None:
            evt = str(ev["emitted"])
            if ev.get("captures"):
                evt = f"{evt}!{ev['captures']}p"
        else:
            evt = "-"
        # cost attribution (utils/metering.py via worker stats): attributed
        # device-seconds total + the hottest tenant by device burn; workers
        # predating the metering plane (or with it off) show "-"
        costs = w.get("costs") or {}
        if costs.get("device_s_total") is not None:
            cost = f"{costs['device_s_total']:.1f}s"
            top = str(costs.get("top_tenant", "") or "")[:6]
            if top:
                cost = f"{cost} {top}"
        else:
            cost = "-"
        # step anatomy (utils/step_anatomy.py via resource_snapshot): STEP =
        # host-side fraction of attributed engine time + the decode-window
        # dispatch cadence p50; ROOF = HBM floor over measured decode seconds
        # (the r5 "69.8% of roofline" number, live). Pre-plane workers: "-"
        anat = res.get("step_anatomy") or {}
        step = "-"
        if anat.get("host_frac") is not None:
            step = f"h{100.0 * anat['host_frac']:.0f}%"
            gap = anat.get("dispatch_gap_ms_p50")
            if gap is not None:
                step = f"{step} {gap:.1f}ms"
        roof = (
            f"{100.0 * anat['roofline_frac']:.0f}%"
            if anat.get("roofline_frac") is not None else "-"
        )
        # PREFILL: host-side fraction of prefill dispatch time + the
        # rows-amortized per-call fixed cost + the prefill roofline fraction
        # (max(MXU-FLOP, bytes) floor over measured). Workers predating the
        # prefill plane (r19) show "-"
        prefill = "-"
        if anat.get("prefill_host_frac") is not None:
            prefill = f"h{100.0 * anat['prefill_host_frac']:.0f}%"
            fx = anat.get("prefill_fixed_ms")
            if fx is not None:
                prefill = f"{prefill} {fx:.1f}ms"
            pr = anat.get("prefill_roofline_frac")
            if pr is not None:
                prefill = f"{prefill} {100.0 * pr:.0f}%"
        # RADIX: blocks this worker has indexed in the router's radix tree
        # (its advertised prefix-cache footprint); "-" until the router has
        # broadcast index health
        radix_cell = radix_per_worker.get(str(w.get("worker_id", "")), None)
        radix_cell = str(radix_cell) if radix_cell is not None else "-"
        hb = health.get("heartbeat_age_s")
        stale_mark = " STALE" if w.get("stale") else ""
        lines.append(
            f"{w.get('worker_id', '?'):<12} {glyph} {state:<8} "
            f"{(f'{hb:.1f}s' if hb is not None else '-'):>6} "
            f"{w.get('last_seen_s', 0):>5.1f}s {w.get('missed_scrapes', 0):>4} "
            f"{slots:>7} {kv_pct:>5.1f}% {kv_mem:>11} {prefix:>9} "
            f"{radix_cell:>7} {spec:>10} "
            f"{lora:>11} {tier:>9} {goodput:>9} {mig:>7} {qos:>9} {evt:>8} "
            f"{cost:>13} {step:>11} "
            f"{roof:>5} {prefill:>15} {kv.get('num_requests_waiting', 0):>5} "
            f"{_fmt_bytes(res.get('hbm_bytes_in_use', 0)):>9} "
            f"{res.get('xla_compiles', 0):>5}  {_slo_cell(w.get('slo'))}"
            f"{stale_mark}"
        )
    if not doc.get("workers"):
        lines.append("(no workers reporting)")
    hit = doc.get("kv_hit_rate") or {}
    if hit.get("isl_blocks"):
        pct = 100.0 * hit.get("overlap_blocks", 0) / hit["isl_blocks"]
        lines.append("")
        lines.append(f"router prefix-cache hit rate: {pct:.1f}% "
                     f"({hit.get('overlap_blocks', 0)}/{hit['isl_blocks']} blocks)")
    if radix:
        cap = radix.get("max_nodes")
        cap_s = f"/{cap}" if cap else " (unbounded)"
        lookups = radix.get("lookups_total", 0)
        hitpct = (
            f", lookup hit {100.0 * radix.get('hits_total', 0) / lookups:.1f}%"
            if lookups else ""
        )
        lines.append(
            f"router radix index: {radix.get('nodes', 0)}{cap_s} nodes "
            f"({_fmt_bytes(radix.get('bytes', 0))}, "
            f"{radix.get('shards', 1)} shard(s)), "
            f"evictions {radix.get('evictions_total', 0)}{hitpct}"
        )
    # recent-events pane: the fleet timeline (merged per-worker flight
    # recorder tails riding /cluster/status), newest last; j/k scroll it in
    # curses mode
    recent = doc.get("recent_events") or []
    if recent and events_rows > 0:
        total = len(recent)
        offset = max(0, min(events_offset, total - events_rows))
        end = total - offset
        window = recent[max(0, end - events_rows):end]
        lines.append("")
        pos = "" if offset == 0 else f" (scrolled {offset} back)"
        lines.append(
            f"recent events — {total} merged, newest last{pos} (j/k scroll):"
        )
        for ev in window:
            lines.append("  " + _format_event(ev))
    return "\n".join(lines)


def _plain_loop(url: str, interval: float) -> None:
    while True:
        try:
            doc = fetch_status(url)
            out = render_status(doc)
        except Exception as e:
            out = f"dynotop: fetch failed: {e}"
        print("\x1b[2J\x1b[H" + out, flush=True)
        time.sleep(interval)


def _curses_loop(url: str, interval: float) -> None:
    import curses

    def body(stdscr):
        curses.curs_set(0)
        stdscr.timeout(int(interval * 1000))
        offset = 0
        while True:
            try:
                doc = fetch_status(url)
                maxy, _ = stdscr.getmaxyx()
                # the pane gets whatever vertical room the worker table
                # leaves (floor 4 rows so it never vanishes entirely)
                rows = max(4, maxy - len(doc.get("workers", ())) - 10)
                text = render_status(doc, events_rows=rows, events_offset=offset)
            except Exception as e:
                text = f"dynotop: fetch failed: {e}"
            stdscr.erase()
            maxy, maxx = stdscr.getmaxyx()
            for i, line in enumerate(text.splitlines()[: maxy - 1]):
                stdscr.addnstr(i, 0, line, maxx - 1)
            stdscr.refresh()
            ch = stdscr.getch()
            if ch in (ord("q"), 27):
                return
            if ch in (ord("j"), curses.KEY_DOWN):
                offset = max(0, offset - 1)
            elif ch in (ord("k"), curses.KEY_UP):
                offset += 1
            elif ch in (ord("g"),):
                offset = 0

    curses.wrapper(body)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--url", default="http://127.0.0.1:9091",
                   help="metrics component base URL (serves /cluster/status)")
    p.add_argument("--interval", type=float, default=2.0)
    p.add_argument("--once", action="store_true", help="print one snapshot and exit")
    p.add_argument("--plain", action="store_true",
                   help="plain-text refresh loop instead of curses")
    args = p.parse_args(argv)

    if args.once:
        try:
            print(render_status(fetch_status(args.url)))
            return 0
        except Exception as e:
            print(f"dynotop: fetch failed: {e}", file=sys.stderr)
            return 1
    if args.plain or not sys.stdout.isatty():
        _plain_loop(args.url, args.interval)
        return 0
    try:
        _curses_loop(args.url, args.interval)
    except ImportError:
        _plain_loop(args.url, args.interval)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
