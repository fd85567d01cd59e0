"""Kernels: the expert layers' grouped products in a decode step against the
stream of the experts' matrices, which HBM bandwidth bounds: the bytes a step
has to move (`_moe_grouped_cost.py`: the three matrices of every (expert
layer, held expert) pair that received a row, from the program's counter
`dynamo_engine_moe_experts_touched_total` over the window's decode steps, and
the assigned rows) over the peak bandwidth, divided by the device seconds ONE
decode step spends in the part `moe_experts` (the kernel `moe_grouped_matmul`
and the activation between its calls: the leaf seconds of that part in the
decode-window runs lying whole inside the trace, over the sum of the `k` of
their dispatch spans, `trace_parts.py` `decode`). A program that does not
count the touched experts (the counter absent or 0) gives nothing."""
from layer_metrics import _moe_grouped_cost, _parts
from layer_metrics._common import delta


def read(ctx):
    touched = delta(ctx, "dynamo_engine_moe_experts_touched_total")
    rows = delta(ctx, "dynamo_engine_moe_assignments_total")
    steps = sum(r["steps"] for r in ctx["records"] if r["kind"] == "decode_window")
    if not touched or not rows or not steps or not ctx.get("peaks") \
            or "moe_intermediate_size" not in ctx["config"]:
        return None
    t = _parts.parts(ctx)
    if not t or not t["decode"] or not t["decode"]["steps"]:
        return None
    secs = t["decode"]["seconds_by_part"].get("moe_experts", 0.0)
    if secs <= 0:
        return None
    need = _moe_grouped_cost.grouped_bytes(ctx["config"], touched / steps, rows / steps)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (secs / t["decode"]["steps"])
