"""Shared by the readers of `trace_parts.py`'s reduction (not a metric: no
entry in `BENCHMARK.json` names it).

`parts(ctx)` finds the traced run's `xplane.pb` as `_xplane.steps` finds it,
reduces it once in a child held to the CPU and keeps the result in `ctx`;
one `{"phase": "trace_parts", ...}` line on stdout carries the table of
seconds by (step, part), the decode and prefill numbers, what is left
unnamed and what the reduction took. A program without the vocabulary (no
`step` part anywhere: the parent of the PR that opened the scopes) gives
None, as does anything missing, with a line on stderr; the readers then
report nothing.

The shares are over `ctx["trace"]["busy_s"]`, the busy seconds every other
`*_share_of_busy` reader divides by."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import trace_steps
from layer_metrics._xplane import BENCH

TIMEOUT_S = 120
#: the operations that ARE the expert products (`moe_share_of_busy`'s names)
EXPERT_PRODUCT = re.compile(r"ragged-dot|moe_grouped_matmul", re.I)
PREFILL_STEPS = tuple(trace_steps.PREFILL.values())


def parts(ctx: dict) -> dict | None:
    if "trace_parts" not in ctx:
        ctx["trace_parts"] = _reduce(ctx)
    return ctx["trace_parts"]


def _reduce(ctx: dict) -> dict | None:
    started = (ctx.get("trace_report") or {}).get("start_unix")
    if started is None:
        return None
    found = [p for p in (BENCH / ".cache" / "work").glob("*/trace/plugins/profile/*/*.xplane.pb")
             if p.stat().st_mtime >= started]
    if not found:
        print("layer_metrics/_parts: no xplane.pb of this run", file=sys.stderr)
        return None
    trace = max(found, key=lambda p: p.stat().st_mtime)
    out = trace.parents[3] / "trace_parts.json"  # .../work/<cell>/trace/
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, str(BENCH / "trace_parts.py"), str(trace), str(out)],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired:
        print(f"layer_metrics/_parts: trace_parts.py took over {TIMEOUT_S}s", file=sys.stderr)
        return None
    if proc.returncode != 0:
        print(f"layer_metrics/_parts: trace_parts.py failed rc={proc.returncode}:\n{proc.stderr[-2000:]}",
              file=sys.stderr)
        return None
    reduced = json.loads(out.read_text())
    print(json.dumps({"phase": "trace_parts", "seconds": round(time.monotonic() - t0, 2),
                      **{k: reduced[k] for k in (
                          "modules_mapped", "by_step_part", "ops_by_part", "decode", "fill",
                          "no_module_s", "body_named_s", "leaf_s", "busy_s", "unnamed_top")},
                      "prefill_pairs": len((reduced["prefill"] or {}).get("pairs", []))}), flush=True)
    if not any("step" in by_part for by_part in reduced["by_step_part"].values()):
        print("layer_metrics/_parts: no operation of this trace carries the part `step`: "
              "the program has not the scopes", file=sys.stderr)
        return None
    return reduced


def seconds(t: dict, names: tuple, steps: tuple | None = None) -> float:
    """Leaf seconds of the parts `names`, over all steps or over `steps`."""
    return sum(s for step, by_part in t["by_step_part"].items() if steps is None or step in steps
               for part, s in by_part.items() if part in names)


def share(ctx: dict, secs: float | None) -> float | None:
    busy = (ctx.get("trace") or {}).get("busy_s") or 0.0
    return 100.0 * secs / busy if secs and busy > 0 else None


def share_of(ctx: dict, names: tuple, steps: tuple | None = None) -> float | None:
    t = parts(ctx)
    return share(ctx, seconds(t, names, steps)) if t else None
