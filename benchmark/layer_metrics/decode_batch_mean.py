"""Scheduler: sequences per decode step: over the window's decode-window
records of `/debug/steps`, tokens / steps (`utils/step_anatomy.StepRecord`)."""


def read(ctx):
    recs = [r for r in ctx["records"] if r["kind"] == "decode_window" and r["steps"] > 0]
    steps = sum(r["steps"] for r in recs)
    return sum(r["tokens"] for r in recs) / steps if steps else None
