"""Runner: device milliseconds per decode step: seconds of the decode-window
module's runs on the device's `XLA Modules` line over the decode steps they
ran (runs x the `k` of the dispatch spans), device clock. `decode_step_ms`
beside it is the engine thread's host-clock time per step."""
from layer_metrics import _xplane


def read(ctx):
    t = _xplane.steps(ctx)
    return t["decode"]["step_ms"] if t and t["decode"] else None
