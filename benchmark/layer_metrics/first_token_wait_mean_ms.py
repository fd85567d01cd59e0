"""Runner: mean time from the dispatch of a request's last prefill chunk to
its first token materialized on the host (what already stood on the device's
queue, the prefill itself, the order of reconciles):
`dynamo_engine_first_token_wait_seconds` sum / count over the window."""
from layer_metrics import _chain


def read(ctx):
    return _chain.mean_ms(ctx, "dynamo_engine_first_token_wait_seconds")
