"""HTTP frontend: what the frontend adds to the engine's own time to first
token: mean `llm_http_service_time_to_first_token_seconds` (from the request's
arrival at the handler) less mean `dynamo_engine_ttft_seconds` (from the
engine's submission queue to the first materialized token), over the window:
parsing, preprocessing, admission, the hops to and from the engine thread."""
from layer_metrics import _chain


def read(ctx):
    http = _chain.mean_ms(ctx, "llm_http_service_time_to_first_token_seconds")
    engine = _chain.mean_ms(ctx, "dynamo_engine_ttft_seconds")
    return None if http is None or engine is None else http - engine
