"""Benchmark client: 95th percentile of the time from when a request was due
to its first streamed token, over the window's requests that succeeded. Not
an end-to-end metric: in a 45 s window of 206 requests ten lie beyond it, and
its quartile spread over two sets of three runs of one tree was 4.0-6.9% (my chip runs, PR
24), more than half of the widest bound the contract allows."""
import client
from layer_metrics import _common


def read(ctx):
    ttft = [o.ttft_s * 1e3 for o in _common.in_window(ctx) if o.ok]
    return client.percentile(ttft, 95) if ttft else None
