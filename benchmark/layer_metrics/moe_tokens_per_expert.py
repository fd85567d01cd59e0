"""Scheduler: tokens a held expert sees per decode step and expert block:
`dynamo_engine_moe_assignments_total` (assignments that landed on experts held
here, counted on the device and added by the scheduler at each window's
reconcile) over the window, divided by the experts held, the decode steps of
the window's records and the expert blocks. The deployment's number is
batch x experts a token / experts routed over, times the data-parallel chips
that send their batches to this expert."""
from layer_metrics._common import delta


def read(ctx):
    got = delta(ctx, "dynamo_engine_moe_assignments_total")
    conf = ctx["config"]
    blocks = str(conf.get("hybrid_override_pattern", "")).count("E")
    steps = sum(r["steps"] for r in ctx["records"] if r["kind"] == "decode_window")
    if not got or not blocks or not steps or not conf.get("n_routed_experts"):
        return None
    return got / (conf["n_routed_experts"] * steps * blocks)
