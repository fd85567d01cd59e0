"""Kernels: device time of the expert layer's grouped matrix products over
device busy time, from the profiler trace: every operation on the device's
line named `ragged-dot*` (XLA's lowering of `jax.lax.ragged_dot`, what the
product was until PR 30) or `moe_grouped_matmul*` (the kernel it is since), so
that both sides of a comparison read. The dispatch around the products (sort,
gathers, the select) is XLA fusions with no such name and is not counted."""
import re

KERNEL = re.compile(r"ragged-dot|moe_grouped_matmul", re.I)


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    secs = sum(s for name, s in t["ops_by_name"].items() if KERNEL.search(name))
    return 100.0 * secs / t["busy_s"] if secs > 0 else None
