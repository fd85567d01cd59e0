#!/usr/bin/env python3
"""The benchmark's server entry: the program's own `dynamo_tpu.launch.run.main`,
unchanged, in this process's main thread.

Only the process that holds the chip can trace it, and the program calls
`jax.profiler` nowhere. So in a traced run (`--trace-dir`), a side thread
waits for the file `<trace-dir>/start` (the benchmark's parent writes it in
the middle of the measured window), traces for `--trace-seconds` with the
Python tracer off, stops, and writes `<trace-dir>/done`. Without
`--trace-dir` this entry never touches the profiler: a `--trace 0` run and a
`--trace 1` run differ by the profiler alone.

    python benchmark/serve_entry.py [--trace-dir D --trace-seconds S] -- run <ckpt> --in http ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _trace_on_trigger(trace_dir: Path, seconds: float) -> None:
    start, done = trace_dir / "start", trace_dir / "done"
    while not start.exists():
        time.sleep(0.05)
    report = {"ok": False}
    try:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t0 = time.time()
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        time.sleep(seconds)
        t1 = time.time()
        jax.profiler.stop_trace()
        report = {"ok": True, "start_unix": t0, "asked_s": seconds,
                  "stop_called_unix": t1, "stop_returned_unix": time.time()}
    except Exception as e:  # reported to the parent, which fails the run
        report["error"] = f"{type(e).__name__}: {e}"
    # whole or not at all: the parent polls for this file and reads it at once
    part = trace_dir / "done.part"
    part.write_text(json.dumps(report))
    os.replace(part, done)


def main(argv: list) -> int:
    split = argv.index("--") if "--" in argv else len(argv)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-dir", type=Path, default=None)
    ap.add_argument("--trace-seconds", type=float, default=3.0)
    args = ap.parse_args(argv[:split])
    sys.path.insert(0, str(ROOT))
    if args.trace_dir is not None:
        args.trace_dir.mkdir(parents=True, exist_ok=True)
        threading.Thread(target=_trace_on_trigger, name="bench-trace", daemon=True,
                         args=(args.trace_dir, args.trace_seconds)).start()
    from dynamo_tpu.launch.run import main as run_main

    return run_main(argv[split + 1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
