"""Runner: mean device duration of one run of a prefill module
(`dynamo_prefill_packed`, `dynamo_prefill`) on the device's `XLA Modules`
line, device clock."""
from layer_metrics import _xplane


def read(ctx):
    t = _xplane.steps(ctx)
    return t["prefill"]["mean_ms"] if t and t["prefill"] else None
