"""Kernels: the state-update kernel's share of its roofline in a `falcon_h1`
configuration, which HBM bandwidth bounds: `ssm_update_roofline.py`'s reading
(the bytes one call has to move over the peak bandwidth, divided by the
kernel's mean traced time per call; one call a layer and decode step; the live
sequences are the window's decode records' tokens over steps) at the shapes
this configuration's own keys give (`_ssm_h1_cost.py`). That reader reads
NemotronH's keys and gives nothing here; this one gives nothing there, nor for
a program without the kernel or the model."""
from layer_metrics import _ssm_h1_cost, ssm_update_roofline


def read(ctx):
    if not all(key in ctx["config"] for key in _ssm_h1_cost.KEYS.values()):
        return None
    return ssm_update_roofline.read(dict(ctx, config=_ssm_h1_cost.shapes(ctx["config"])))
