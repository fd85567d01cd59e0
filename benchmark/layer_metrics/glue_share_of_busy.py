"""Kernels: what stands between the products and the kernels: leaf seconds
of the parts `embed`, `norm`, `attn_kv` (rope, the cache write, page
addressing) and `step` (what the step programs do themselves) over busy
seconds (`trace_parts.py`)."""
from layer_metrics import _parts


def read(ctx):
    return _parts.share_of(ctx, ("embed", "norm", "attn_kv", "step"))
