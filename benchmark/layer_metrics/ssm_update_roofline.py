"""Kernels: the state-update kernel's share of its roofline, which HBM
bandwidth bounds: the bytes one call has to move (`_ssm_cost.py`: each live
sequence's float32 state once in and once out, and its vectors) over the peak
bandwidth, divided by the kernel's mean traced time per call. The live
sequences are the window's decode records' tokens over steps (a sequence
frozen at its limit inside a window is not counted for the steps it sat
out)."""
from layer_metrics import _ssm_cost
from layer_metrics.ssm_share_of_busy import KERNEL


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("peaks") or "mamba_num_heads" not in ctx["config"]:
        return None
    names = [n for n in t["ops_by_name"] if KERNEL.search(n)]
    calls = sum(t["calls_by_name"][n] for n in names)
    seconds = sum(t["ops_by_name"][n] for n in names)
    recs = [r for r in ctx["records"] if r["kind"] == "decode_window" and r["steps"] > 0]
    steps = sum(r["steps"] for r in recs)
    if not calls or seconds <= 0 or not steps:
        return None
    need = _ssm_cost.ssm_update_bytes(ctx["config"], sum(r["tokens"] for r in recs) / steps)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (seconds / calls)
