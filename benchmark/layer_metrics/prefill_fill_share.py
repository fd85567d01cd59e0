"""Scheduler: how full the prefill programs run: the real rows over the rows
the program computes (`rows` / `padded` of the `engine.prefill_packed.dispatch`
and `engine.prefill_chunk.dispatch` spans: lanes x chunk bucket, or the chunk
buckets summed), summed over the trace's prefill dispatch spans
(`trace_parts.py` `fill`)."""
from layer_metrics import _parts


def read(ctx):
    t = _parts.parts(ctx)
    if not t or not t["fill"]["padded"]:
        return None
    return 100.0 * t["fill"]["rows"] / t["fill"]["padded"]
