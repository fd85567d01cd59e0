"""Device: how long a prefill waits on the device's queue: mean over the
trace's (dispatch span, module run) pairs of the run's start on the device
less the END of its `engine.prefill_*.dispatch` span on the engine thread,
not below 0, both on the profiler's clock (`trace_steps.pair`). Fewer than
`MIN_PAIRS` pairs: nothing, and stderr says why."""
import sys

import trace_steps
from layer_metrics import _xplane


def read(ctx):
    t = _xplane.steps(ctx)
    if not t:
        return None
    if len(t["pairs"]) < trace_steps.MIN_PAIRS:
        print(f"prefill_backlog_ms: {len(t['pairs'])} pairs ({t['pairing']})", file=sys.stderr)
        return None
    return sum(p["backlog_ms"] for p in t["pairs"]) / len(t["pairs"])
