#!/usr/bin/env python3
"""From a profiler trace (`*.xplane.pb`) to device numbers.

Device planes only (`/device:TPU:n`). On each, the line that carries the
device's operations (`XLA Ops`): every event there is one operation running
on that chip, with a start and a duration in nanoseconds on the device's
clock.

  busy_s      the union of the operation intervals, averaged over the chips
  window_s    from the first operation's start to the last one's end (the
              traced window as the device saw it), the widest over the chips
  device_ops  seconds by operation name (trailing numbers dropped), summed over
              events and averaged over the chips, the ten largest;
              `ops_by_name` and `calls_by_name` keep every full name
  idle_gaps   the longest gaps between operations, named by the operations
              before and after (what the HOST did in a gap needs the
              program's spans on the profiler's clock: the `tracing` issue's)

Reading the file needs `jax.profiler.ProfileData`, and the benchmark's parent
never imports JAX, so this runs as a script in a child held to the CPU, after
the server has stopped:

    JAX_PLATFORMS=cpu python benchmark/trace_reduce.py TRACE.xplane.pb OUT.json [EVENTS.json]

`reduce_events` is plain arithmetic on `{plane: [(name, start_ns, dur_ns)]}`
and is what `benchmark/tests` check against a recorded slice of a chip trace.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def load_events(path: str) -> tuple:
    """({plane: [(name, start_ns, dur_ns)]} for device planes' operation
    lines, {plane: [line names]} for every plane: the second is what a reader
    looks at by hand)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    events, layout = {}, {}
    for plane in data.planes:
        lines = list(plane.lines)
        layout[plane.name] = [ln.name for ln in lines]
        if not DEVICE_PLANE.match(plane.name):
            continue
        for ln in lines:
            if ln.name != OPS_LINE:
                continue
            events[plane.name] = [
                (short_name(ev.name), int(ev.start_ns), int(ev.duration_ns)) for ev in ln.events
            ]
    return events, layout


def short_name(name: str) -> str:
    """An event's name on the operation line is the whole HLO instruction
    (`%fusion.206 = bf16[64,2048]{...} fusion(...)`): keep the instruction's
    own name, and for a custom call its first operand's shape too (the
    page-table width tells the variants of one kernel apart)."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    m = re.search(r"custom-call\((\w+\[[\d,]*\])", rest)
    return f"{head} {m.group(1)}" if m else head


def union_ns(intervals: list) -> int:
    """Total length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def base_name(name: str) -> str:
    """`fusion.123` and `fusion.124` are one kind of operation to a reader of
    the top ten; a kernel's own name is kept whole."""
    return re.sub(r"[.\d]+$", "", name.split(" ")[0]) or name


def reduce_events(events: dict) -> dict:
    planes = {p: evs for p, evs in events.items() if evs}
    if not planes:
        return {"planes": 0, "busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "ops_by_name": {}, "calls_by_name": {}, "idle_gaps": [], "longest_gap_s": 0.0}
    n = len(planes)
    busy, window, by_name, calls, gaps = 0.0, 0.0, {}, {}, []
    for evs in planes.values():
        # nested events (a while loop and the ops inside it) share time: busy
        # time is the union, and the by-name table counts leaves only
        spans = sorted((s, s + d, name) for name, s, d in evs)
        busy += union_ns([(s, e) for s, e, _ in spans]) / 1e9
        window = max(window, (max(e for _, e, _ in spans) - spans[0][0]) / 1e9)
        for i, (s, e, name) in enumerate(spans):
            parent = i + 1 < len(spans) and spans[i + 1][0] < e and spans[i + 1][1] <= e
            if not parent:  # a leaf: nothing starts inside it
                by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
                calls[name] = calls.get(name, 0) + 1
        cur_e, cur_name = None, None
        for s, e, name in spans:
            if cur_e is not None and s > cur_e:
                gaps.append(((s - cur_e) / 1e9, f"{base_name(cur_name)}->{base_name(name)}"))
            if cur_e is None or e > cur_e:
                cur_e, cur_name = e, name
    grouped = {}
    for name, s in by_name.items():
        grouped[base_name(name)] = grouped.get(base_name(name), 0.0) + s / n
    top = sorted(grouped.items(), key=lambda kv: -kv[1])[:TOP]
    gap_by_kind = {}
    for s, kind in gaps:
        gap_by_kind[kind] = gap_by_kind.get(kind, 0.0) + s / n
    return {
        "planes": n,
        "busy_s": busy / n,
        "window_s": window,
        "device_ops": [[k, v] for k, v in top],
        "ops_by_name": {k: v / n for k, v in by_name.items()},
        "calls_by_name": {k: v / n for k, v in calls.items()},
        "idle_gaps": [[k, v] for k, v in
                      sorted(gap_by_kind.items(), key=lambda kv: -kv[1])[:TOP]],
        "longest_gap_s": max((s for s, _ in gaps), default=0.0),
    }


def main(argv: list) -> int:
    events, layout = load_events(argv[0])
    out = reduce_events(events)
    out["layout"] = layout
    Path(argv[1]).write_text(json.dumps(out))
    if len(argv) > 2:  # the raw events, for reading by hand and for fixtures
        Path(argv[2]).write_text(json.dumps(events))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
