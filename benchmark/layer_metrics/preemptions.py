"""Cache manager: sequences preempted in the window
(`dynamo_engine_preemptions_total`, all reasons)."""
import probe


def read(ctx):
    def total(table):
        vals = [v for (n, _), v in table.items() if n == "dynamo_engine_preemptions_total"]
        return sum(vals) if vals else None

    a, b = total(ctx["m0"]), total(ctx["m1"])
    return None if a is None or b is None else b - a
