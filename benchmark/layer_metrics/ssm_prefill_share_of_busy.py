"""Kernels: the prefill's side of the state-space layers, which
`ssm_share_of_busy` cannot see (no operation of it has `ssm` in its name):
leaf seconds of the part `ssm` (convolution, chunked scan, state rows) inside
runs of the prefill steps, over busy seconds (`trace_parts.py`)."""
from layer_metrics import _parts


def read(ctx):
    return _parts.share_of(ctx, ("ssm",), _parts.PREFILL_STEPS)
