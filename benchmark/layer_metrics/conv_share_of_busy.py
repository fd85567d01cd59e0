"""Kernels: what the gated short convolutions take of the device: leaf seconds
of the parts `ssm` (the gates, the taps, the window rows) and `ssm_proj` (the
in and out projections with the residual add) over busy seconds, from
`trace_parts.py`'s reduction of the same trace. A model whose recurrent layers
are Mamba-2 puts its scan and projections under the same parts; a program
without the scopes gives nothing."""
from layer_metrics import _parts


def read(ctx):
    return _parts.share_of(ctx, ("ssm", "ssm_proj"))
