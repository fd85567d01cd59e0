"""Runner: what prompt processing takes from token generation: device seconds
of the prefill modules' runs over the device's busy seconds, same trace."""
from layer_metrics import _xplane


def read(ctx):
    t = _xplane.steps(ctx)
    if not t or not t["prefill"] or t["busy_s"] <= 0:
        return None
    return 100.0 * t["prefill"]["seconds"] / t["busy_s"]
