#!/usr/bin/env python3
"""From a profiler trace (`*.xplane.pb`) to the device's time by STEP and to
what the engine thread did meanwhile.

`trace_reduce.py` reads the device's operations; this reads, on the same
clock (nanoseconds since the profiler started):

  - each device plane's `XLA Modules` line: one event per run of a compiled
    program. The program names its jitted steps `dynamo_<label>`
    (`engine/model_runner.py` `_mjit`), so a run is a decode window, a packed
    prefill, a prefill chunk ... by name;
  - the engine thread's line of the host plane: the line that holds
    `engine.step` spans. The program's `tracing.span` blocks are
    `jax.profiler.TraceAnnotation`s, so every `engine.*` span stands there
    with its stats (`seq`, `k`, `rows`, ...);
  - each device plane's `XLA Ops` line, for the intervals in which the device
    ran nothing.

What it writes (`reduce` is plain arithmetic on what `load` returns, and is
what `benchmark/tests` check against a recorded slice of a chip trace):

  modules     by step label: calls, seconds, mean_ms (averaged over chips)
  decode      the decode-window module: seconds, steps (calls x the `k` stat
              of the `engine.decode_window.dispatch` spans), step_ms
  prefill     the prefill modules together: calls, seconds, mean_ms
  spans       the engine thread's spans by name: calls, seconds
  thread      the engine thread from the start of its first whole loop
              iteration in the trace to the end of its last: seconds under
              `engine.step`, `engine.post`, `engine.wait_for_work`, under the
              phases inside the steps, and covered by no span at all
  pairs       (prefill dispatch span, the module run it caused), see `pair`
  gaps        device gaps over 50 us, each with the innermost engine-thread
              span covering its start; `gap_seconds_by_span` sums them
  idle_host_s device-idle seconds during which the engine thread was in
              neither a `device_wait` phase nor `engine.wait_for_work`
  busy_s, window_s   as `trace_reduce` defines them

A program without the spans or the step names (the parent of the PR that
added them) gives empty tables, no pairs and `idle_host_s` None: the readers
then report nothing.

    JAX_PLATFORMS=cpu python benchmark/trace_steps.py TRACE.xplane.pb OUT.json [EVENTS.json]
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: `jit_dynamo_decode_window(4152...)` -> `decode_window`
STEP = re.compile(r"dynamo_([A-Za-z_]+[A-Za-z])")
DECODE = "decode_window"
#: which dispatch span causes runs of which step
PREFILL = {"engine.prefill_packed.dispatch": "prefill_packed",
           "engine.prefill_chunk.dispatch": "prefill"}
#: the engine loop's own spans; every other `engine.*` span on its thread is a
#: step-anatomy phase inside a step
LOOP = ("engine.step", "engine.post", "engine.wait_for_work")
WAITING = re.compile(r"^engine\.(\w+\.device_wait|wait_for_work)$")
GAP_NS = 50_000
MIN_PAIRS = 5


def load(path: str) -> dict:
    """{"modules": {plane: [(label, start_ns, dur_ns)]},
        "ops": {plane: [(start_ns, dur_ns)]},
        "host": [(name, start_ns, dur_ns, {stat: value})] of the engine thread,
        "layout": {plane: [line names]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"modules": {}, "ops": {}, "host": [], "layout": {}, "module_names": []}
    names = set()
    for plane in data.planes:
        lines = list(plane.lines)
        out["layout"][plane.name] = [ln.name for ln in lines]
        if DEVICE_PLANE.match(plane.name):
            for ln in lines:
                if ln.name == MODULES_LINE:
                    runs = []
                    for ev in ln.events:
                        names.add(ev.name)
                        m = STEP.search(ev.name)
                        runs.append((m.group(1) if m else ev.name, int(ev.start_ns), int(ev.duration_ns)))
                    out["modules"][plane.name] = runs
                elif ln.name == OPS_LINE:
                    out["ops"][plane.name] = [(int(ev.start_ns), int(ev.duration_ns)) for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                evs = [ev for ev in ln.events if ev.name.startswith("engine.")]
                if any(ev.name == "engine.step" for ev in evs):
                    out["host"] += [(ev.name, int(ev.start_ns), int(ev.duration_ns),
                                     {k: v for k, v in ev.stats if isinstance(v, (int, float, str))})
                                    for ev in evs]
    out["host"].sort(key=lambda e: (e[1], -e[2]))
    out["module_names"] = sorted(names)[:40]
    return out


def merged(intervals: list) -> list:
    """Sorted, disjoint (start, end) covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def innermost(host: list, starts: list, t: int) -> str | None:
    """Name of the shortest engine-thread span that covers `t`. `host` is
    sorted by start and `starts` holds its starts: walk back from the last
    span that began by `t` to the loop's own span there (a phase lies inside
    one, so nothing earlier can cover `t`)."""
    best = None
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, d, _ = host[i]
        if t < s + d and (best is None or d < best[1]):
            best = (name, d)
        if name in LOOP:
            break
    return best[0] if best else None


def pair(host: list, runs: list) -> tuple:
    """[(dispatch span, module run)] and a word on how they were matched.

    The runs of one step execute in the order of their dispatches, so the
    i-th dispatch span pairs with the (i+k)-th run: k runs at the trace's
    start belong to dispatches made before it began, and the last spans'
    runs may start after its end. What fixes k: a run starts no earlier than
    its dispatch span, and it has ended when the reconcile phase of the same
    `seq` begins (the engine materializes a result only once it is there).
    Runs queue on the device for longer than the time between two
    dispatches, so several k can keep every run after its span's start; only
    the largest that also keeps every run ahead of its reconcile is the
    pairing, since a smaller one pairs each span with an earlier dispatch's
    run. No k: no pairs, and the reason. A pair's backlog is the run's start
    less the span's END (the host has enqueued the call by then), not below
    zero."""
    rec_start = {}
    for name, s, _, st in host:
        if name.endswith(".reconcile") and "seq" in st:
            rec_start.setdefault(st["seq"], s)
    pairs, notes = [], []
    for span_name, label in PREFILL.items():
        spans = [(s, s + d, st) for name, s, d, st in host if name == span_name]
        mods = sorted((s, s + d) for lab, s, d in runs if lab == label)
        if not spans or not mods:
            continue
        fit = None
        for k in range(len(mods) - 1, -1, -1):
            both = list(zip(spans, mods[k:]))
            if any(st.get("seq") in rec_start for _, _, st in spans[len(both):]):
                continue  # a result materialized inside the trace came from a run inside it
            if both and all(
                ms >= ss and me <= rec_start.get(st.get("seq"), me)
                for (ss, _, st), (ms, me) in both
            ):
                fit = (k, both)
                break
        if fit is None:
            notes.append(f"{label}: no alignment of {len(spans)} dispatch spans with {len(mods)} runs "
                         "keeps every run between its dispatch and its reconcile")
            continue
        k, both = fit
        notes.append(f"{label}: {len(both)} pairs, {k} runs before the first span, "
                     f"{len(spans) - len(both)} spans after the last run")
        for (ss, se, st), (ms, me) in both:
            pairs.append({"seq": st.get("seq"), "rows": st.get("rows"), "step": label,
                          "backlog_ms": max(0, ms - se) / 1e6, "device_ms": (me - ms) / 1e6,
                          "dispatch_ms": (se - ss) / 1e6})
    return pairs, "; ".join(notes)


def reduce(ev: dict) -> dict:
    planes = [p for p, runs in ev["modules"].items() if runs]
    n = max(1, len(planes))
    host = ev["host"]
    modules: dict = {}
    for p in planes:
        for label, _, d in ev["modules"][p]:
            m = modules.setdefault(label, {"calls": 0.0, "seconds": 0.0})
            m["calls"] += 1 / n
            m["seconds"] += d / 1e9 / n
    for m in modules.values():
        m["mean_ms"] = m["seconds"] / m["calls"] * 1e3

    ks = [st["k"] for name, _, _, st in host if name == "engine.decode_window.dispatch" and "k" in st]
    dec = modules.get(DECODE)
    decode = None
    if dec and ks:
        steps = dec["calls"] * sum(ks) / len(ks)
        decode = {"seconds": dec["seconds"], "steps": steps, "step_ms": dec["seconds"] / steps * 1e3,
                  "k": sorted(set(ks))}
    pre = [m for label, m in modules.items() if label in PREFILL.values()]
    prefill = None
    if pre:
        calls, secs = sum(m["calls"] for m in pre), sum(m["seconds"] for m in pre)
        prefill = {"calls": calls, "seconds": secs, "mean_ms": secs / calls * 1e3}

    spans: dict = {}
    for name, _, d, _ in host:
        sp = spans.setdefault(name, {"calls": 0, "seconds": 0.0})
        sp["calls"] += 1
        sp["seconds"] += d / 1e9
    thread = None
    loop = [(s, s + d) for name, s, d, _ in host if name in LOOP]
    if loop:
        # from the first whole iteration of the engine loop to the last: a
        # step that began before the trace did left only its phases in it
        t0, t1 = min(s for s, _ in loop), max(e for _, e in loop)
        inside = [(name, s, d) for name, s, d, _ in host if s >= t0 and s + d <= t1]
        top = {k: sum(d for name, _, d in inside if name == f"engine.{k}") / 1e9
               for k in ("step", "post", "wait_for_work")}
        phases = sum(d for name, _, d in inside if name not in LOOP) / 1e9
        covered = sum(e - s for s, e in merged([(s, s + d) for _, s, d in inside])) / 1e9
        thread = {"seconds": (t1 - t0) / 1e9, **{f"{k}_s": v for k, v in top.items()},
                  "phases_s": phases, "uncovered_s": (t1 - t0) / 1e9 - covered}

    # pairs are taken on one chip's line: the runs of one program start
    # together on every chip it spans
    pairs, how = pair(host, ev["modules"][planes[0]]) if planes and host else ([], "no spans or no modules")

    busy = window = idle_host = 0.0
    gaps, by_span = [], {}
    waiting = merged([(s, s + d) for name, s, d, _ in host if WAITING.match(name)])
    starts = [s for _, s, _, _ in host]
    op_planes = [p for p, ops in ev["ops"].items() if ops]
    for p in op_planes:
        runs = merged([(s, s + d) for s, d in ev["ops"][p]])
        busy += sum(e - s for s, e in runs) / 1e9
        window = max(window, (runs[-1][1] - runs[0][0]) / 1e9)
        idle = [[a[1], b[0]] for a, b in zip(runs, runs[1:])]
        idle_host += (sum(e - s for s, e in idle) - overlap_ns(idle, waiting)) / 1e9
        for s, e in idle:
            if e - s > GAP_NS:
                name = innermost(host, starts, s) or "no span"
                gaps.append({"start_ns": s, "ms": (e - s) / 1e6, "span": name})
                by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9 / len(op_planes)
    m = max(1, len(op_planes))
    return {
        "planes": len(planes), "modules": modules, "decode": decode, "prefill": prefill,
        "spans": spans, "thread": thread, "pairs": pairs, "pairing": how,
        "gaps": sorted(gaps, key=lambda g: -g["ms"])[:50], "gap_seconds_by_span": by_span,
        "idle_host_s": idle_host / m if host and op_planes else None,
        "busy_s": busy / m, "window_s": window,
    }


def main(argv: list) -> int:
    ev = load(argv[0])
    out = reduce(ev)
    out["layout"], out["module_names"] = ev["layout"], ev["module_names"]
    Path(argv[1]).write_text(json.dumps(out))
    if len(argv) > 2:  # the raw events, for reading by hand and for fixtures
        Path(argv[2]).write_text(json.dumps({k: ev[k] for k in ("modules", "ops", "host")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
