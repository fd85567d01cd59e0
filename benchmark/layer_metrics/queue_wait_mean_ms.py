"""Scheduler: mean wait between a request's arrival at the engine and its
admission: `dynamo_engine_queue_wait_seconds` sum / count over the window."""
from layer_metrics import _common


def read(ctx):
    s = _common.delta(ctx, "dynamo_engine_queue_wait_seconds_sum")
    n = _common.delta(ctx, "dynamo_engine_queue_wait_seconds_count")
    return None if not n or s is None else s / n * 1e3
