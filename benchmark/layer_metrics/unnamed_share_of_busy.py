"""Kernels: what the split by part does not reach: leaf seconds of
instructions with no vocabulary name in their `op_name` (or none at all: the
compiler's own copies), plus the seconds inside no run or inside a run whose
module the trace did not carry, over busy seconds (`trace_parts.py`). 0 is a
value: a trace in which everything has a part reports it."""
from layer_metrics import _parts


def read(ctx):
    t = _parts.parts(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s") or 0.0
    if not t or busy <= 0:
        return None
    return 100.0 * (_parts.seconds(t, ("unnamed",)) + t["no_module_s"]) / busy
