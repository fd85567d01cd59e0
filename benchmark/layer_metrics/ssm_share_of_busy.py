"""Kernels: device time of the state-space operations over device busy time,
from the profiler trace: every operation on the device's line with `ssm` in
its name. Today that is the decode step's `ssm_state_update` kernel; the
prefill's chunked scan is XLA fusions, which carry no such name (`PERF.md`
section 7), so this reads the decode side only."""
import re

KERNEL = re.compile(r"ssm", re.I)


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    secs = sum(s for name, s in t["ops_by_name"].items() if KERNEL.search(name))
    return 100.0 * secs / t["busy_s"] if secs > 0 else None
