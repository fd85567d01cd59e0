"""Cache manager: single-layer pages running sequences gave back in the window
because every token of them lay behind a sliding window
(`dynamo_engine_kv_window_pages_released_total`)."""
from layer_metrics import _common


def read(ctx):
    return _common.delta(ctx, "dynamo_engine_kv_window_pages_released_total")
