"""Kernels: the output head and the sampler: leaf seconds of the parts
`lm_head` and `sample` over busy seconds (`trace_parts.py`)."""
from layer_metrics import _parts


def read(ctx):
    return _parts.share_of(ctx, ("lm_head", "sample"))
