"""Kernels: the FOLDED paged decode attention kernel's share of its roofline
(`paged_decode_attention_pallas_folded`, pools of head_dim under 128), which
HBM bandwidth bounds: the bytes one call has to move (`costs.py`
`decode_attention_bytes`: the K and V of every context token of this layer
once, a query and an output row per sequence) over the peak bandwidth, divided
by the kernel's mean traced time per call.

The context is that of the sequences that DECODE, and not the pool's active
pages as `attn_decode_roofline` takes it: where prompts are long, half the
sequences that hold pages are still in prefill, the decode kernel never reads
them, and that reader read twice the kernel's share in
`lfm2-8b-a1b-d16.rag-over` (17.7% and 24.8% against 10.5% for the kernel
alone: PERF.md section 3, PR 42). Every decode-window record of `/debug/steps`
carries `floor_bytes` = (parameter bytes + pages held by the window's
participants x bytes a page) x steps (`utils/step_anatomy.py`
`decode_floor_bytes`), and the summary carries both constants (`roofline`), so
the participants' pages follow from the record; times the page size they are
the context (whole pages: half a page a sequence high, under 1% at a thousand
tokens). Records are weighted by their steps. A program whose records or
summary lack those numbers, or a trace without the folded kernel, gives
nothing."""
import costs
from layer_metrics.attn_share_of_busy import DECODE_KERNEL


def read(ctx):
    t = ctx.get("trace")
    roof = (ctx.get("steps1") or {}).get("roofline") or {}
    if not t or not ctx.get("peaks") or not roof.get("page_bytes") or not roof.get("page_size"):
        return None
    names = [n for n in t["ops_by_name"] if DECODE_KERNEL.search(n) and "folded" in n]
    calls = sum(t["calls_by_name"][n] for n in names)
    seconds = sum(t["ops_by_name"][n] for n in names)
    steps = pages = seqs = 0.0
    for r in ctx["records"]:
        if r["kind"] != "decode_window" or not r.get("steps") or not r.get("floor_bytes"):
            continue
        steps += r["steps"]
        pages += r["floor_bytes"] - r["steps"] * roof.get("param_bytes", 0)
        seqs += r["participants"] * r["steps"]
    if not calls or seconds <= 0 or not steps or pages <= 0:
        return None
    tokens = roof["page_size"] * pages / roof["page_bytes"] / steps
    need = costs.decode_attention_bytes(ctx["config"], tokens, seqs / steps)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / (seconds / calls)
