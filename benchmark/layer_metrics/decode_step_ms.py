"""Runner: engine-thread milliseconds charged to decode windows (host prep,
dispatch, device wait, reconcile: host clock) per decode step, over the
window (`/debug/steps` summary, cumulative counters)."""
from layer_metrics import _common


def read(ctx):
    secs = sum(_common.phase_seconds(ctx, ("decode_window",)).values())
    steps = (ctx["steps1"].get("steps") or {}).get("decode_window", 0) - \
        (ctx["steps0"].get("steps") or {}).get("decode_window", 0)
    return secs / steps * 1e3 if steps > 0 else None
