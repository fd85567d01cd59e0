"""Benchmark client: mean time from when a request was due to its first
streamed token, over the window's requests that succeeded: the total that the
stages of the waterfall (`ttft_frontend_mean_ms`, `queue_wait_mean_ms`,
`prefill_hold_mean_ms`, `first_token_wait_mean_ms`) must add up to; what they
leave over is the client, the socket and the client's own lag."""
from layer_metrics import _common


def read(ctx):
    ttft = [o.ttft_s * 1e3 for o in _common.in_window(ctx) if o.ok]
    return sum(ttft) / len(ttft) if ttft else None
