"""Kernels: what the two mixers of a block take of the device together: leaf
seconds of the parts `ssm`, `ssm_proj` (the Mamba-2 mixer: convolution, scan
or state update, gate and norm; its projections and the sum of the branches)
and `attn`, `attn_kv`, `attn_proj` (the attention mixer: kernels, rope and the
cache write, projections) over busy seconds, from `trace_parts.py`'s reduction
of the same trace. The number that says whether the mechanism a parallel-mixer
block adds does most of the work, beside the SwiGLU (`dense_share_of_busy`
holds the projections too) and the head. A program without the scopes gives
nothing."""
from layer_metrics import _parts


def read(ctx):
    return _parts.share_of(ctx, ("ssm", "ssm_proj", "attn", "attn_kv", "attn_proj"))
