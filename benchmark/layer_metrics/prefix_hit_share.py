"""Scheduler: share of prompt blocks served from the prefix cache:
`dynamo_engine_prefix_cache_blocks_total` hit / (hit + miss) over the window."""
from layer_metrics import _common


def read(ctx):
    hit = _common.delta(ctx, "dynamo_engine_prefix_cache_blocks_total", result="hit")
    miss = _common.delta(ctx, "dynamo_engine_prefix_cache_blocks_total", result="miss")
    if hit is None or miss is None or hit + miss <= 0:
        return None
    return 100.0 * hit / (hit + miss)
