"""Device: idle time the host caused: the share of the traced window in
which the device ran nothing AND the engine thread was in neither a
`device_wait` phase nor `engine.wait_for_work` (`trace_steps.reduce`)."""
from layer_metrics import _xplane


def read(ctx):
    t = _xplane.steps(ctx)
    if not t or t["idle_host_s"] is None or t["window_s"] <= 0:
        return None
    return 100.0 * t["idle_host_s"] / t["window_s"]
