"""Cache manager: the share of the per-slot recurrent state cache that live
sequences hold, averaged over the window: `dynamo_engine_state_slots` active /
total, sampled once a second. A model with no recurrent layers reports a total
of 0 and the metric is left out."""
import probe


def read(ctx):
    shares = []
    for _, table in ctx["samples"]:
        active = probe.sample(table, "dynamo_engine_state_slots", state="active")
        total = probe.sample(table, "dynamo_engine_state_slots", state="total")
        if active is not None and total:
            shares.append(100.0 * active / total)
    return sum(shares) / len(shares) if shares else None
