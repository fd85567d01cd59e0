"""Cache manager: the highest share of the page pool held by running
sequences in the window: `dynamo_engine_kv_pages` active / total, sampled once
a second. (`used` also counts what the prefix cache keeps of finished
sequences until the room is needed, and reads 100% in any long run.)"""
import probe


def read(ctx):
    shares = []
    for _, table in ctx["samples"] + [(0, ctx["m0"]), (0, ctx["m1"])]:
        active = probe.sample(table, "dynamo_engine_kv_pages", state="active")
        total = probe.sample(table, "dynamo_engine_kv_pages", state="total")
        if active is not None and total:
            shares.append(100.0 * active / total)
    return max(shares) if shares else None
