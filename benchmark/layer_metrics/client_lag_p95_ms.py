"""How late the benchmark's own client sent: sent - due, 95th percentile over
the window's requests. A starved generator must not read as a fast server."""
import client
from layer_metrics import _common


def read(ctx):
    lags = [o.lag_s * 1e3 for o in _common.in_window(ctx) if o.sent]
    return client.percentile(lags, 95) if lags else None
