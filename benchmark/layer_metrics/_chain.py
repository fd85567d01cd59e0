"""Shared by the readers of a request's chain to its first token (not a
metric: no entry in `BENCHMARK.json` names it): the mean of a histogram over
the window, sum / count, in ms. The chain's stages come from a program that
has `dynamo_engine_prefill_hold_seconds`; one that lacks it (the parent of
the PR that added the chain) reports none of them, so that no waterfall is
drawn from stages that do not add up."""
from layer_metrics import _common


def mean_ms(ctx: dict, family: str) -> float | None:
    if _common.delta(ctx, "dynamo_engine_prefill_hold_seconds_count") is None:
        return None
    s, n = _common.delta(ctx, f"{family}_sum"), _common.delta(ctx, f"{family}_count")
    return None if not n or s is None else s / n * 1e3
