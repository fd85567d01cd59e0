"""Kernels: what the dense matrices take of the device: leaf seconds of the
parts `attn_proj`, `mlp`, `shared_experts` and `ssm_proj` (the products every
row meets, with their biases, activations and residual adds) over busy
seconds, from `trace_parts.py`'s reduction of the same trace."""
from layer_metrics import _parts


def read(ctx):
    return _parts.share_of(ctx, ("attn_proj", "mlp", "shared_experts", "ssm_proj"))
