"""Scheduler: mean time from a request's admission to the dispatch of the
prefill chunk that ends its prompt (chunking, the prefill pipeline gate,
prefix fetch): `dynamo_engine_prefill_hold_seconds` sum / count over the
window."""
from layer_metrics import _chain


def read(ctx):
    return _chain.mean_ms(ctx, "dynamo_engine_prefill_hold_seconds")
