"""Scheduler: rows a held expert sees per decode step and EXPERT layer, in a
model whose first `num_dense_layers` layers have a dense FFN and no router
(`moe_rows_per_expert` divides by every layer of `layer_types` and would read
low by that share): `dynamo_engine_moe_assignments_total` over the window,
divided by the experts held (`num_experts`), the decode steps of the window's
records and the layers that route. With every expert held it is batch x
experts a token / experts."""
from layer_metrics._common import delta


def read(ctx):
    conf = ctx["config"]
    got = delta(ctx, "dynamo_engine_moe_assignments_total")
    layers = len(conf.get("layer_types") or []) - int(conf.get("num_dense_layers") or 0)
    steps = sum(r["steps"] for r in ctx["records"] if r["kind"] == "decode_window")
    if not got or layers <= 0 or not steps or not conf.get("num_experts"):
        return None
    return got / (conf["num_experts"] * steps * layers)
