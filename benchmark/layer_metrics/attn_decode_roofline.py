"""Kernels: the paged decode attention kernel's share of its roofline, which
HBM bandwidth bounds: the bytes one call has to move (`costs.py`: the K and V
of every live context token of this layer once, a query and an output row per
sequence) over the peak bandwidth, divided by the kernel's mean traced time
per call. The live context is the page pool's `active` pages, sampled once a
second over the window, times the page size (whole pages: a few percent
high, so the share is a few percent kind to the kernel); the batch is the
mean number of sequences in the window's decode records."""
import costs
import probe
from layer_metrics.attn_share_of_busy import DECODE_KERNEL

PAGE_SIZE = 16


def read(ctx):
    t = ctx.get("trace")
    if not t or not ctx.get("peaks"):
        return None
    names = [n for n in t["ops_by_name"] if DECODE_KERNEL.search(n)]
    calls = sum(t["calls_by_name"][n] for n in names)
    seconds = sum(t["ops_by_name"][n] for n in names)
    pages = [probe.sample(table, "dynamo_engine_kv_pages", state="active") for _, table in ctx["samples"]]
    pages = [p for p in pages if p is not None]
    windows = [r["participants"] for r in ctx["records"] if r["kind"] == "decode_window"]
    if not calls or seconds <= 0 or not pages or not windows:
        return None
    need = costs.decode_attention_bytes(ctx["config"], PAGE_SIZE * sum(pages) / len(pages),
                                        sum(windows) / len(windows))
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (seconds / calls)
