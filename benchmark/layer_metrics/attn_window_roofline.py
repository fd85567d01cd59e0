"""Kernels: the sliding-window decode attention calls' share of their HBM
roofline (`_attn_groups_cost.py`): the window layers' live pages once, a query
and an output row per sequence, over the peak bandwidth, divided by the mean
traced time of a call named `...sliding...` on the device's operation line."""
from layer_metrics import _attn_groups_cost


def read(ctx):
    return _attn_groups_cost.group_roofline(ctx, "window", sliding=True)
