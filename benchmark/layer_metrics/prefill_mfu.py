"""Kernels: the packed prefill's share of the chip's bf16 peak: over the
trace's (prefill dispatch span, module run) pairs whose run lies whole inside
the trace, the USEFUL operations of the packs (`_dense_cost.py`: the real rows
through every layer's matrices, and the attention products over at least the
context the span's `rows`, `ctx` and `lanes` allow; padding and the head earn
nothing) over the runs' device seconds x the peak. Dense configurations only.
Fewer than `MIN_PAIRS` pairs: nothing, and stderr says so."""
import sys

import trace_steps
from layer_metrics import _dense_cost, _parts


def read(ctx):
    t = _parts.parts(ctx)
    if not t or not t["prefill"] or not ctx.get("peaks"):
        return None
    pairs = [p for p in t["prefill"]["pairs"] if p["step"] == "prefill_packed"]
    if len(pairs) < trace_steps.MIN_PAIRS:
        print(f"prefill_mfu: {len(pairs)} pairs", file=sys.stderr)
        return None
    flops = sum(_dense_cost.prefill_useful_flops(ctx["config"], p["rows"], p["ctx"], p["lanes"]) for p in pairs)
    secs = sum(p["device_s"] for p in pairs)
    return 100.0 * flops / (secs * ctx["peaks"]["bf16_flops_per_s"]) if secs > 0 else None
