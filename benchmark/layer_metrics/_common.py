"""Shared by the readers in this directory (not a metric: no entry in
`BENCHMARK.json` names it).

A reader is `read(ctx) -> float | None`. `ctx` holds what the harness read
over the measured window of a `--trace 1` run:

  m0, m1       `/metrics` at the window's open and close, parsed
               (`probe.parse_exposition`)
  samples      [(monotonic time, parsed `/metrics`)], once a second
  steps0/1     `/debug/steps` summary at open and close (cumulative)
  records      the `/debug/steps` records begun after the window opened
  outcomes     every client Outcome; t_open, t_close, window_s
  trace        `trace_reduce.reduce_events` output; peaks; device; config
A reader that finds nothing to read returns None and the metric is left out.
"""

from __future__ import annotations

import probe


def delta(ctx: dict, name: str, **labels) -> float | None:
    a, b = probe.sample(ctx["m0"], name, **labels), probe.sample(ctx["m1"], name, **labels)
    return None if a is None or b is None else b - a


def phase_seconds(ctx: dict, kinds: tuple | None = None) -> dict:
    """{phase: seconds charged in the window}, over dispatch kinds."""
    p0 = ctx["steps0"].get("phase_seconds") or {}
    p1 = ctx["steps1"].get("phase_seconds") or {}
    out: dict = {}
    for key, s in p1.items():
        phase, kind = key.split(".", 1)
        if kinds is None or kind in kinds:
            out[phase] = out.get(phase, 0.0) + s - p0.get(key, 0.0)
    return out


def in_window(ctx: dict) -> list:
    return [o for o in ctx["outcomes"] if ctx["t_open"] <= o.due < ctx["t_close"]]
