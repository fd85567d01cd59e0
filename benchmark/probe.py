"""Reading a server from outside: HTTP helpers, the Prometheus text parser,
the wait for `/ready` and its device report.

Copied from `chip_smoke.py` (`http`, `get_json`, `metric`, `wait_ready`,
`compile_counters`, `spawn`/`stop`), so that a later PR may change the smoke
and not the yardstick. Corrected: the exposition is parsed once into a table
(the smoke scans the text once per sample it asks for), and a failure raises
`BenchError` (the smoke's `AssertionError` is compiled away under -O).
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import time
import urllib.error
import urllib.request
from pathlib import Path


class BenchError(RuntimeError):
    """The run cannot give a result: no result line is printed."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body: dict | None = None, timeout: float = 60.0) -> tuple:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def get_json(url: str, timeout: float = 30.0) -> tuple:
    status, text = http("GET", url, timeout=timeout)
    return status, (json.loads(text) if text.startswith("{") else {})


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)")
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def parse_exposition(text: str) -> dict:
    """{(name, ((label, value), ...sorted)): float} of a Prometheus text."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln[0] == "#":
            continue
        m = _SAMPLE.match(ln)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        out[(m.group(1), tuple(sorted(_LABEL.findall(m.group(2) or ""))))] = value
    return out


def sample(table: dict, name: str, **labels) -> float | None:
    """One sample of a parsed exposition: the first whose labels include
    `labels` (None when absent)."""
    want = set(labels.items())
    for (n, lab), v in table.items():
        if n == name and want <= set(lab):
            return v
    return None


def scrape(base: str) -> dict:
    status, text = http("GET", f"{base}/metrics")
    if status != 200:
        raise BenchError(f"/metrics answered {status}")
    return parse_exposition(text)


def spawn(argv: list, log_path: Path, env: dict | None = None, cwd: str | None = None) -> subprocess.Popen:
    """Start a child in its own process group, output to `log_path`."""
    log_path.parent.mkdir(parents=True, exist_ok=True)
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, cwd=cwd, env={**os.environ, **(env or {})},
                                stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
    proc.log_path = log_path
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 20.0) -> int | None:
    """SIGTERM the child's whole process group, then SIGKILL what is left,
    and wait until the leader has ended."""
    for sig, wait in ((signal.SIGTERM, grace_s), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(wait)
        except subprocess.TimeoutExpired:
            continue
        try:  # the leader is gone; sweep stragglers of its group
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        break
    return proc.poll()


def log_tail(proc, n: int = 40) -> str:
    try:
        return "".join(open(proc.log_path, errors="replace").readlines()[-n:])
    except OSError:
        return ""


def wait_ready(base: str, proc, timeout_s: float) -> dict:
    """Poll `/ready` until the server lists a model; returns its body, which
    carries the device the serving process runs on."""
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise BenchError(f"server exited rc={proc.returncode} before /ready:\n{log_tail(proc)}")
        try:
            status, body = get_json(f"{base}/ready", timeout=5)
            if status == 200 and body.get("status") == "ready" and body.get("models"):
                return body
            last = (status, body.get("status"))
        except (OSError, ValueError) as e:
            last = e
        time.sleep(0.5)
    raise BenchError(f"/ready not reached in {timeout_s:.0f}s (last: {last}):\n{log_tail(proc)}")
