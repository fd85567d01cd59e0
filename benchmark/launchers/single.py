"""Launcher `single`: one colocated engine behind the HTTP frontend, the
program's normal entry point (`python -m dynamo_tpu.launch.run run <ckpt>
--in http --out jax ...`), reached through `benchmark/serve_entry.py` so that
a traced run can start the profiler inside the process that holds the chip."""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent


def preflight() -> str | None:
    """Why this launcher cannot start here, before anything expensive."""
    if not (HERE.parent / "dynamo_tpu" / "launch" / "run.py").is_file():
        return f"no program under {HERE.parent}: dynamo_tpu/launch/run.py is not there"
    return None


def command(ckpt: Path, port: int, server_args: list, trace_dir: Path | None,
            trace_seconds: float) -> list:
    argv = [sys.executable, str(HERE / "serve_entry.py")]
    if trace_dir is not None:
        argv += ["--trace-dir", str(trace_dir), "--trace-seconds", str(trace_seconds)]
    return argv + ["--", "run", str(ckpt), "--in", "http", "--out", "jax",
                   "--http-port", str(port), *[str(a) for a in server_args]]
