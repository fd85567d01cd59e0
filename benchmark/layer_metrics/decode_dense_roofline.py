"""Kernels: the decode step's dense matrices against the weight stream, which
HBM bandwidth bounds: the bytes a step reads at the least (`costs.weight_bytes`:
every layer's matrices and the head once) over the peak bandwidth, divided by
the device seconds ONE decode step spends in the parts that read them
(`attn_proj`, `mlp`, `norm`, `lm_head`): the leaf seconds of those parts in
the decode-window runs lying whole inside the trace, over the sum of the `k`
of their dispatch spans (`trace_parts.py` `decode`). Dense configurations
only: what an expert-parallel share must read turns on how many held experts
received a row."""
import costs
from layer_metrics import _parts

READS_WEIGHTS = ("attn_proj", "mlp", "norm", "lm_head")


def read(ctx):
    t = _parts.parts(ctx)
    if not t or not t["decode"] or not ctx.get("peaks"):
        return None
    d = t["decode"]
    secs = sum(s for part, s in d["seconds_by_part"].items() if part in READS_WEIGHTS)
    if secs <= 0 or not d["steps"] or "attn_proj" not in d["seconds_by_part"]:
        return None
    floor_s = costs.weight_bytes(ctx["config"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s / (secs / d["steps"])
