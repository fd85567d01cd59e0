"""Kernels: device time of the Pallas attention calls (decode and prefill) over
device busy time, from the profiler trace. The kernels appear on the device's
operation line under their own names (`KERNEL`)."""
import re

#: `paged_decode_attention_pallas_lookahead`, `..._folded`, the flash prefill
#: kernels: every Pallas attention kernel of `ops/pallas/` has one of these
KERNEL = re.compile(r"attention|flash|prefill_pallas", re.I)
DECODE_KERNEL = re.compile(r"paged_decode_attention", re.I)


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    secs = sum(s for name, s in t["ops_by_name"].items() if KERNEL.search(name))
    return 100.0 * secs / t["busy_s"] if secs > 0 else None
