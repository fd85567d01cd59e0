"""Runner: share of the engine thread's step time that is host work and not
waiting for the device: (host_prep + dispatch + reconcile) / total, all
dispatch kinds, over the window."""
from layer_metrics import _common


def read(ctx):
    p = _common.phase_seconds(ctx)
    total = sum(p.values())
    return 100.0 * (total - p.get("device_wait", 0.0)) / total if total > 0 else None
