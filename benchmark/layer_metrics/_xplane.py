"""Shared by the readers of `trace_steps.py`'s reduction (not a metric: no
entry in `BENCHMARK.json` names it).

`steps(ctx)` finds the traced run's `xplane.pb`, reduces it once in a child
held to the CPU (reading the file takes JAX, which the benchmark's parent
never imports) and keeps the result in `ctx`. The file is the newest
`.cache/work/*/trace/plugins/profile/*/*.xplane.pb` not older than the
moment the server's side thread started the profiler: `run.Server` empties
the trace directory at start, so an older file is another run's. Anything
missing (an untraced run, no file, a reduction that fails) gives None with a
line on stderr, and the readers then report nothing."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
TIMEOUT_S = 120


def steps(ctx: dict) -> dict | None:
    if "trace_steps" not in ctx:
        ctx["trace_steps"] = _reduce(ctx)
    return ctx["trace_steps"]


def _reduce(ctx: dict) -> dict | None:
    started = (ctx.get("trace_report") or {}).get("start_unix")
    if started is None:
        return None
    found = [p for p in (BENCH / ".cache" / "work").glob("*/trace/plugins/profile/*/*.xplane.pb")
             if p.stat().st_mtime >= started]
    if not found:
        print("layer_metrics/_xplane: no xplane.pb of this run", file=sys.stderr)
        return None
    trace = max(found, key=lambda p: p.stat().st_mtime)
    out = trace.parents[3] / "trace_steps.json"  # .../work/<cell>/trace/
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, str(BENCH / "trace_steps.py"), str(trace), str(out),
             str(out.with_name("trace_steps_events.json"))],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired:
        print(f"layer_metrics/_xplane: trace_steps.py took over {TIMEOUT_S}s", file=sys.stderr)
        return None
    if proc.returncode != 0:
        print(f"layer_metrics/_xplane: trace_steps.py failed rc={proc.returncode}:\n{proc.stderr[-2000:]}",
              file=sys.stderr)
        return None
    reduced = json.loads(out.read_text())
    print(json.dumps({"phase": "trace_steps", "seconds": round(time.monotonic() - t0, 2),
                      "xplane_bytes": trace.stat().st_size, "pairing": reduced["pairing"],
                      "modules": reduced["modules"], "decode": reduced["decode"], "prefill": reduced["prefill"],
                      "thread": reduced["thread"], "gap_seconds_by_span": reduced["gap_seconds_by_span"],
                      "idle_host_s": reduced["idle_host_s"], "busy_s": reduced["busy_s"],
                      "window_s": reduced["window_s"]}), flush=True)
    return reduced
