"""Kernels: the full-attention decode calls' share of their HBM roofline in a
model that also has window layers (`_attn_groups_cost.py`): the full layers'
live pages once, a query and an output row per sequence, over the peak
bandwidth, divided by the mean traced time of a decode attention call whose
name has no `sliding` in it. (`attn_decode_roofline` takes the whole pool's
active pages as one layer's context and would read false here.)"""
from layer_metrics import _attn_groups_cost


def read(ctx):
    return _attn_groups_cost.group_roofline(ctx, "full", sliding=False)
