"""Scheduler: rows a held expert sees per decode step and expert layer, in a
model every layer of which has an expert layer (`layer_types` in its config):
`dynamo_engine_moe_assignments_total` over the window, divided by the experts
held (`num_experts`), the decode steps of the window's records and the layers.
(`moe_tokens_per_expert` counts `E`s in a NemotronH pattern and finds none
here.) The deployment's number is batch x experts a token / experts routed
over, times the data-parallel chips that send their batches to this expert."""
from layer_metrics._common import delta


def read(ctx):
    conf = ctx["config"]
    got = delta(ctx, "dynamo_engine_moe_assignments_total")
    layers = len(conf.get("layer_types") or [])
    steps = sum(r["steps"] for r in ctx["records"] if r["kind"] == "decode_window")
    if not got or not layers or not steps or not conf.get("num_experts"):
        return None
    return got / (conf["num_experts"] * steps * layers)
