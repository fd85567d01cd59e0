#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that dynamo-tpu still starts on the chip.

Run with no arguments on a machine with one TPU chip:

    python chip_smoke.py [--seed N]

  generate  a TinyLlama-1.1B checkpoint at its published widths, all 22
            layers, random weights from --seed (tools/make_hf_checkpoint.py),
            into .chip_smoke/ inside the checkout. Nothing outside the
            checkout is read.
  serve     `python -m dynamo_tpu.launch.run run <ckpt> --in http --out jax`
            as a child process; this process, a plain HTTP client, sends
            /v1/chat/completions requests (unary, repeated, streaming, longer
            than the largest prefill bucket, several at once) and checks
            status, SSE framing, token counts, that greedy repeats agree, and
            from /metrics and /debug/steps that packed prefill and batched
            decode windows were dispatched through the Pallas kernels. XLA
            compile count and seconds are printed before the first request
            and after the last: a compile in mid-traffic is a finding.
  parity    after the server has exited, a second child runs every Pallas
            kernel the dispatch can reach, compiled for the chip, at one
            published shape each, against the gather reference.

`--chips 4` (the builder runs it; the driver never does) runs ONLY the two
paths that exist only across chips, and what they are compared with:

  tp        a Qwen2.5-7B-width model (depth cut) through the engine at tp=1
            and then tp=4, both in one child; logprobs compared.
  replicas  `python -m dynamo_tpu.sdk.serve examples.graphs.agg:Frontend`
            with four one-chip workers behind the KV router; the supervisor
            must stay off the chips.

This process never imports JAX: a chip belongs to one process at a time, and
a parent that touched JAX would hold it. The device in the last line is what
the serving (or tp) child reported it ran on. Where JAX finds no accelerator
the script exits non-zero and prints no result. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and any phase that fails makes it `"ok": false` with a non-zero exit.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import glob
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"  # git-ignored: checkpoints, logs, graph configs
sys.path.insert(0, str(ROOT))

#: kernel-parity tolerance, absolute, on attention outputs of magnitude ~1.
#: Inputs and outputs are bf16 (8 mantissa bits: 2^-8 = 0.4% per rounding);
#: the folded kernels also round the probabilities to bf16 before the PV
#: matmul, so an output can be off by ~0.4% of sum(p*|v|) <~ 0.015. Int8 pools
#: are compared against the same dequantised values, so quantisation error
#: cancels. The reference runs in f32 under
#: jax.default_matmul_precision("highest"). A wrong page, a wrong mask, or a
#: DMA/semaphore race across grid programs — what a sandbox compile cannot
#: show — puts errors of order |v| ~ 1 into the output, fifty times this.
PARITY_ATOL = 2e-2
#: plus one bf16 ulp of the reference value itself (2^-7 relative): decode
#: outputs of a one-token context are a v row, |v| up to ~4, and the kernel's
#: output and the rounded reference may land on neighbouring bf16 values
PARITY_RTOL = 2.0 ** -7

#: tp=4 vs tp=1 logprob tolerance, absolute, on logprobs of magnitude 5-12.
#: The two runs round differently: tp=4 rounds each shard's partial sums of
#: the wo and down projections to bf16 before the all-reduce, and runs the
#: folded attention kernels (bf16 probabilities) where tp=1 runs the unfolded
#: ones. Over the cut depth that is ~1e-2 on logits of spread ~1.2. A wrong
#: head split, a missing all-reduce or a mis-sharded pool moves them by O(1).
TP_LOGPROB_ATOL = 1e-1


@dataclasses.dataclass
class Sizes:
    """What the smoke runs at. The command line always uses FULL; the CPU
    rehearsal in tests/test_chip_smoke.py passes TINY (and asks for interpret
    mode and the CPU itself, through the environment its children inherit)."""

    name: str
    platform: str  # what the children must report
    serve_geometry: dict  # tools.make_hf_checkpoint geometry of the served model
    serve_args: list  # extra `dynamo_tpu.launch.run run` arguments
    long_prompt_tokens: int  # > the largest prefill bucket (512)
    expect_pallas: bool  # served path must log Pallas kernels, no reference
    tp_geometry: dict
    replica_geometry: dict
    ready_timeout_s: float = 900.0

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def full_sizes() -> Sizes:
    from tools.make_hf_checkpoint import QWEN25_7B_GEOMETRY, TINYLLAMA_GEOMETRY

    return Sizes(
        name="full",
        platform="tpu",
        # TinyLlama-1.1B, published widths and depth (hidden 2048,
        # intermediate 5632, 32 q / 4 kv heads, head_dim 64, vocab 32000, bf16)
        serve_geometry=dict(TINYLLAMA_GEOMETRY),
        serve_args=[],
        long_prompt_tokens=700,
        expect_pallas=True,
        # Qwen2.5-7B widths; depth cut 28 -> 4 so that it also fits one chip
        # next to the tp=4 engine and loads in under a minute
        tp_geometry=dict(QWEN25_7B_GEOMETRY, num_hidden_layers=4, max_position_embeddings=2048),
        # TinyLlama-1.1B widths; depth cut 22 -> 4: this phase is about
        # placement and routing, four workers load it at once
        replica_geometry=dict(TINYLLAMA_GEOMETRY, num_hidden_layers=4),
    )


# ---------------------------------------------------------------- reporting

RESULTS: list = []


def report(phase: str, ok: bool, **fields) -> bool:
    RESULTS.append((phase, ok))
    print(json.dumps({"phase": phase, "ok": ok, **fields}, default=str), flush=True)
    return ok


def note(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class CheckFailed(AssertionError):
    """What the smoke observed is not what it must be."""


def check(cond, why="") -> None:
    """Raise unless `cond` (not `assert`: that is compiled away under -O)."""
    if not cond:
        raise CheckFailed(str(why))


# ---------------------------------------------------------------- processes

_children: list = []


def spawn(name: str, argv: list, env: dict | None = None) -> subprocess.Popen:
    """Start a child in its own process group, output to .chip_smoke/logs."""
    logs = WORK / "logs"
    logs.mkdir(parents=True, exist_ok=True)
    log = open(logs / f"{name}.log", "w")
    proc = subprocess.Popen(
        argv, cwd=str(ROOT), env={**os.environ, **(env or {})},
        stdout=log, stderr=subprocess.STDOUT, start_new_session=True,
    )
    proc.log_path = logs / f"{name}.log"
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 30.0) -> int | None:
    """SIGTERM the child's whole process group, then SIGKILL what is left."""
    for sig, wait in ((signal.SIGTERM, grace_s), (signal.SIGKILL, 10.0)):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(wait)
        except subprocess.TimeoutExpired:
            continue
        # the leader is gone; sweep stragglers of its group
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        break
    return proc.poll()


def stop_all() -> None:
    for proc in _children:
        if proc.poll() is None:
            stop(proc, grace_s=5.0)
        else:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass


def log_tail(proc, n: int = 30) -> str:
    try:
        return "".join(open(proc.log_path, errors="replace").readlines()[-n:])
    except OSError:
        return ""


def log_lines(proc, pattern: str) -> list:
    rx = re.compile(pattern)
    try:
        return [ln.rstrip("\n") for ln in open(proc.log_path, errors="replace") if rx.search(ln)]
    except OSError:
        return []


def run_child_phase(phase: str, sizes: Sizes, seed: int, timeout_s: float, extra: list = ()) -> tuple:
    """Run `chip_smoke.py --phase <phase>` to its end; (rc, JSON lines it printed)."""
    proc = spawn(
        f"child-{phase}",
        [sys.executable, str(ROOT / "chip_smoke.py"), "--phase", phase,
         "--seed", str(seed), "--sizes", sizes.to_json(), *extra],
        env={"DYNTPU_LOG": "info"},
    )
    try:
        rc = proc.wait(timeout_s)
    except subprocess.TimeoutExpired:
        stop(proc, grace_s=5.0)
        rc = 124
    rows = []
    for ln in open(proc.log_path, errors="replace"):
        if ln.startswith("{"):
            try:
                rows.append(json.loads(ln))
            except ValueError:
                pass
    return rc, rows, proc


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------- http client


def http(method: str, url: str, body: dict | None = None, timeout: float = 300.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def get_json(url: str, timeout: float = 30.0):
    status, text = http("GET", url, timeout=timeout)
    return status, (json.loads(text) if text.startswith("{") else {})


def metric(text: str, name: str, **labels) -> float | None:
    """One sample of a Prometheus exposition (None when absent)."""
    for ln in text.splitlines():
        if not ln.startswith(name):
            continue
        m = re.match(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)", ln)
        if not m or m.group(1) != name:
            continue
        got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
        if all(got.get(k) == v for k, v in labels.items()):
            return float(m.group(3))
    return None


def chat_body(model: str, content: str, max_tokens: int, **extra) -> dict:
    return {
        "model": model,
        "messages": [{"role": "user", "content": content}],
        "max_tokens": max_tokens,
        "temperature": 0.0,
        # random weights emit </s> at random: keep the count deterministic
        "ext": {"ignore_eos": True},
        **extra,
    }


def chat(base: str, body: dict) -> dict:
    status, text = http("POST", f"{base}/v1/chat/completions", body)
    if status != 200:
        raise AssertionError(f"HTTP {status}: {text[:300]}")
    return json.loads(text)


def chat_stream(base: str, body: dict) -> dict:
    """POST with stream=true; checks the SSE framing by hand and returns
    {"text", "chunks", "usage"}."""
    req = urllib.request.Request(
        f"{base}/v1/chat/completions", data=json.dumps({**body, "stream": True}).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    text, chunks, usage, finish, done = "", 0, None, None, False
    with urllib.request.urlopen(req, timeout=300) as r:
        check(r.status == 200, r.status)
        ctype = r.headers.get("Content-Type", "")
        check(ctype.startswith("text/event-stream"), f"Content-Type {ctype!r}")
        for raw in r:
            line = raw.decode().rstrip("\r\n")
            if not line or line.startswith(":"):
                continue  # event separator / comment
            check(not done, f"data after [DONE]: {line[:80]!r}")
            check(line.startswith("data: "), f"not an SSE data line: {line[:80]!r}")
            payload = line[len("data: "):]
            if payload == "[DONE]":
                done = True
                continue
            ev = json.loads(payload)
            check(ev.get("object") == "chat.completion.chunk", ev.get("object"))
            chunks += 1
            for choice in ev.get("choices", []):
                text += (choice.get("delta") or {}).get("content") or ""
                finish = choice.get("finish_reason") or finish
            usage = ev.get("usage") or usage
    check(done, "stream ended without data: [DONE]")
    return {"text": text, "chunks": chunks, "usage": usage, "finish_reason": finish}


def words(seed: int, n: int) -> str:
    """n pseudo-random lowercase words (the synthetic tokenizer's alphabet)."""
    import random

    rng = random.Random(seed)
    return " ".join(
        "".join(rng.choice("abcdefghijklmnopqrstuvwxyz") for _ in range(rng.randint(2, 8)))
        for _ in range(n)
    )


def prompt_of_tokens(ckpt: Path, seed: int, tokens: int) -> str:
    """A prompt the checkpoint's own tokenizer encodes to about `tokens`."""
    from tokenizers import Tokenizer

    tok = Tokenizer.from_file(str(ckpt / "tokenizer.json"))
    text = words(seed, tokens)  # >= 1 token per word: too long, then trim
    ids = tok.encode(text).ids
    return tok.decode(ids[:tokens])


# ---------------------------------------------------------------- phases: parent side


def preflight(sizes: Sizes, seed: int) -> dict | None:
    """What JAX finds in a child, before anything expensive. None = no
    accelerator (or no program): the caller exits non-zero with no result."""
    rc, rows, proc = run_child_phase("device", sizes, seed, timeout_s=300)
    dev = next((r["device"] for r in rows if "device" in r), None)
    if rc != 0 or dev is None:
        print(f"chip_smoke: the device child failed (rc={rc}):\n{log_tail(proc)}", file=sys.stderr)
        return None
    if dev["platform"] != sizes.platform:
        print(f"chip_smoke: JAX found {dev['platform']!r} ({dev['kind']}), not "
              f"{sizes.platform!r}: nothing to smoke here", file=sys.stderr)
        return None
    return dev


def environment_notes() -> None:
    """Cache location, radix index and chip detection: earlier lines."""
    from dynamo_tpu.llm.kv_router.indexer import KvIndexer
    from dynamo_tpu.sdk.allocator import detect_tpu_chips
    from dynamo_tpu.utils.xla_cache import DEFAULT_CACHE_DIR

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_CACHE_DIR)
    entries = len(list(Path(cache).glob("*"))) if Path(cache).is_dir() else 0
    index = type(KvIndexer(kv_block_size=16).shards[0]).__name__
    report(
        "environment", True,
        xla_cache_dir=cache,
        xla_cache_from="JAX_COMPILATION_CACHE_DIR" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "default (in the checkout)",
        xla_cache_entries_at_start=entries,
        radix_index="native (built from native/src on first use)" if index == "NativeRadixTree" else "python (no native library)",
        detect_tpu_chips=detect_tpu_chips(),
        dev_nodes=sorted(glob.glob("/dev/accel*") + glob.glob("/dev/vfio/*")),
    )


def phase_generate(name: str, geometry: dict, seed: int) -> Path:
    from tools.make_hf_checkpoint import make_checkpoint

    t0 = time.monotonic()
    out = WORK / f"ckpt-{name}-seed{seed}"
    stamp = out / ".complete"
    want = json.dumps({"geometry": geometry, "seed": seed}, sort_keys=True)
    reused = stamp.exists() and stamp.read_text() == want
    if not reused:
        shutil.rmtree(out, ignore_errors=True)
        make_checkpoint(str(out), geometry, seed=seed)
        stamp.write_text(want)
    size = sum(f.stat().st_size for f in out.iterdir())
    report(f"generate:{name}", True, path=os.path.relpath(out, ROOT), seed=seed,
           reused=reused, bytes=size, seconds=round(time.monotonic() - t0, 1),
           geometry=geometry)
    return out


def wait_ready(base: str, proc, timeout_s: float) -> dict:
    deadline = time.monotonic() + timeout_s
    last = None
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise AssertionError(f"server exited rc={proc.returncode} before /ready:\n{log_tail(proc)}")
        try:
            status, body = get_json(f"{base}/ready", timeout=5)
            if status == 200 and body.get("status") == "ready" and body.get("models"):
                return body
            last = (status, body.get("status"))
        except (OSError, ValueError) as e:
            last = e
        time.sleep(1.0)
    raise AssertionError(f"/ready not reached in {timeout_s:.0f}s (last: {last}):\n{log_tail(proc)}")


def compile_counters(base: str) -> dict:
    _, text = http("GET", f"{base}/metrics")
    return {
        "compiles": metric(text, "dynamo_engine_xla_compiles_total"),
        "compile_s": metric(text, "dynamo_engine_xla_compile_seconds_total"),
    }


def phase_serve(sizes: Sizes, ckpt: Path, seed: int) -> dict | None:
    """Serve the checkpoint through the normal entry point and drive it as a
    plain HTTP client. Returns the device the serving child reported."""
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    t0 = time.monotonic()
    proc = spawn("serve", [
        sys.executable, "-m", "dynamo_tpu.launch.run", "run", str(ckpt),
        "--in", "http", "--out", "jax", "--http-port", str(port), *sizes.serve_args,
    ], env={"DYNTPU_LOG": "info"})
    device = None
    checks: dict = {}
    try:
        ready = wait_ready(base, proc, sizes.ready_timeout_s)
        device = ready.get("device")
        model = ready["models"][0]
        checks["ready_s"] = round(time.monotonic() - t0, 1)
        checks["device"] = device
        checks["xla_cache_at_ready"] = ready.get("xla_cache")
        before = compile_counters(base)
        checks["compiles_before_traffic"] = before

        # 1+2. unary, then the same again: greedy repeats agree and the
        # second one hits the prefix cache
        p_short = "tell me about " + words(seed, 40)
        r1 = chat(base, chat_body(model, p_short, 16))
        r2 = chat(base, chat_body(model, p_short, 16))
        check(r1["usage"]["completion_tokens"] == 16, r1["usage"])
        check(r1["choices"][0]["finish_reason"] == "length", r1["choices"][0])
        check(r1["choices"][0]["message"]["content"] == r2["choices"][0]["message"]["content"], "greedy repeat disagrees")
        _, mtext = http("GET", f"{base}/metrics")
        hit_blocks = metric(mtext, "dynamo_engine_prefix_cache_blocks_total", result="hit")
        check(hit_blocks and hit_blocks > 0, f"repeat did not hit the prefix cache ({hit_blocks})")
        checks["unary"] = {"prompt_tokens": r1["usage"]["prompt_tokens"],
                           "completion_tokens": 16, "prefix_cache_hit_blocks": hit_blocks}

        # 3. streaming: SSE framing, and the same greedy answer as unary
        s1 = chat_stream(base, chat_body(model, p_short, 16))
        check(s1["text"] == r1["choices"][0]["message"]["content"], "stream != unary")
        check(s1["finish_reason"] == "length" and s1["usage"]["completion_tokens"] == 16, s1)
        checks["stream"] = {"chunks": s1["chunks"], "completion_tokens": 16}

        # 4. a prompt longer than the largest prefill bucket: chunked prefill
        p_long = prompt_of_tokens(ckpt, seed + 1, sizes.long_prompt_tokens)
        rl = chat(base, chat_body(model, p_long, 8))
        check(rl["usage"]["prompt_tokens"] > 512, rl["usage"])
        check(rl["usage"]["completion_tokens"] == 8, rl["usage"])
        checks["long_prompt"] = {"prompt_tokens": rl["usage"]["prompt_tokens"]}

        # 5. several at once: packed prefill lanes and a decode batch above 1
        outs: list = [None] * 4
        errs: list = []

        def one(i):
            try:
                outs[i] = chat(base, chat_body(model, f"question {i}: " + words(seed + 10 + i, 60), 24))
            except Exception as e:  # surfaced below, on the main thread
                errs.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        check(not errs, errs)
        check(all(o and o["usage"]["completion_tokens"] == 24 for o in outs), outs)
        checks["concurrent"] = {"requests": 4, "completion_tokens_each": 24}

        after = compile_counters(base)
        checks["compiles_after_traffic"] = after
        mid = (after["compiles"] or 0) - (before["compiles"] or 0)
        checks["finding_compiles_in_mid_traffic"] = {
            "count": mid,
            "seconds": round((after["compile_s"] or 0) - (before["compile_s"] or 0), 2),
        }

        # what was dispatched: /debug/steps and /metrics
        _, steps = get_json(f"{base}/debug/steps?limit=512")
        dispatches = steps["summary"]["dispatches"]
        batch = max((r["participants"] for r in steps["records"] if r["kind"] == "decode_window"), default=0)
        lanes = max((r["participants"] for r in steps["records"] if r["kind"] == "prefill_packed"), default=0)
        check(dispatches.get("prefill_packed", 0) > 0, dispatches)
        check(dispatches.get("decode_window", 0) > 0, dispatches)
        check(batch > 1, f"decode batch never above 1 (max participants {batch})")
        _, mtext = http("GET", f"{base}/metrics")
        check((metric(mtext, "dynamo_step_dispatch_total", kind="prefill_packed") or 0) > 0)
        check((metric(mtext, "dynamo_step_dispatch_total", kind="decode_window") or 0) > 0)
        checks["dispatches"] = dispatches
        checks["max_decode_batch"] = batch
        checks["max_prefill_lanes"] = lanes
        checks["roofline_frac"] = steps["summary"].get("roofline_frac")
        checks["peak_device_bytes"] = metric(mtext, "dynamo_engine_hbm_bytes", kind="peak")
        _, ready2 = get_json(f"{base}/ready")
        checks["xla_cache_after_traffic"] = ready2.get("xla_cache")

        # which attention paths the served program traced
        paths = [ln.split("attention path: ", 1)[1] for ln in log_lines(proc, r"attention path: ")]
        checks["attention_paths"] = paths
        if sizes.expect_pallas:
            check(any(p.startswith("decode -> pallas:") for p in paths), paths)
            check(any(p.startswith("prefill -> pallas:") for p in paths), paths)
            bad = [p for p in paths if "reference" in p or "interpret" in p]
            check(not bad, f"served path left the compiled kernels: {bad}")
        ok, err = True, None
    except Exception as e:  # report the phase, with the server's last words
        ok, err = False, f"{type(e).__name__}: {e}"
        checks["server_log_tail"] = log_tail(proc)
    finally:
        rc = stop(proc)
        checks["server_exit"] = rc
    report("serve", ok, error=err, seconds=round(time.monotonic() - t0, 1), **checks)
    return device if ok else None


def phase_child_rows(phase: str, sizes: Sizes, seed: int, timeout_s: float, extra: list = ()) -> tuple:
    """Run a JAX child phase; print its JSON lines; (ok, rows)."""
    t0 = time.monotonic()
    rc, rows, proc = run_child_phase(phase, sizes, seed, timeout_s, extra)
    for r in rows:
        print(json.dumps(r), flush=True)
    for ln in log_lines(proc, r"attention path: "):
        note("attention path: " + ln.split("attention path: ", 1)[1])
    cases = [r for r in rows if "case" in r]
    failed = [r["case"] for r in cases if not r.get("ok")]
    ok = rc == 0 and bool(cases) and not failed
    fields = dict(cases=len(cases), failed=failed, child_exit=rc,
                  seconds=round(time.monotonic() - t0, 1))
    if not ok:
        fields["child_log_tail"] = log_tail(proc)
    report(phase, ok, **fields)
    return ok, rows


def phase_replicas(sizes: Sizes, ckpt: Path, seed: int, workers: int = 4) -> bool:
    """Four one-chip workers behind the KV router, started by the SDK
    supervisor, which must stay off JAX."""
    http_port, cplane_port = free_port(), free_port()
    base = f"http://127.0.0.1:{http_port}"
    conf = WORK / "replicas.yaml"
    conf.write_text(
        f"Frontend:\n  model: {ckpt}\n  served_model_name: smoke\n"
        f"  host: 127.0.0.1\n  port: {http_port}\n"
        "Processor:\n  routing: kv\n  kv_block_size: 16\n"
        f"TpuWorker:\n  model: {ckpt}\n  workers: {workers}\n"
        "  max_model_len: 1024\n  num_pages: 512\n  max_seqs: 8\n"
    )
    t0 = time.monotonic()
    proc = spawn("replicas", [
        sys.executable, "-m", "dynamo_tpu.sdk.serve", "examples.graphs.agg:Frontend",
        "-f", str(conf), "--cplane", f"127.0.0.1:{cplane_port}", "--no-restart",
    ], env={"DYNTPU_LOG": "debug"})
    checks: dict = {}
    try:
        # every worker must come up on its own chip before traffic
        deadline = time.monotonic() + sizes.ready_timeout_s
        up: list = []
        while time.monotonic() < deadline and len(up) < workers:
            if proc.poll() is not None:
                raise AssertionError(f"supervisor exited rc={proc.returncode}:\n{log_tail(proc, 60)}")
            up = log_lines(proc, r"worker [0-9a-f]+ engine on ")
            time.sleep(1.0)
        check(len(up) == workers, f"{len(up)}/{workers} workers up:\n{log_tail(proc, 60)}")
        devices = {}
        for ln in up:
            m = re.search(r"worker ([0-9a-f]+) engine on (\{.*\})", ln)
            devices[m.group(1)] = ast.literal_eval(m.group(2))  # the dict the worker logged
        checks["worker_devices"] = devices
        visible = [d["visible"] for d in devices.values()]
        check(len(set(visible)) == workers, f"workers share a chip: {visible}")
        check(all(d["platform"] == sizes.platform for d in devices.values()), devices)
        # on the chip each worker is confined to its one chip (virtual CPU
        # devices of a rehearsal are not confined by TPU_VISIBLE_DEVICES)
        check(sizes.platform != "tpu" or all(d["count"] == 1 for d in devices.values()), devices)

        # the supervisor is a process manager: it may import the graph's
        # modules, but it must never initialise a JAX backend, or it would
        # hold a chip its workers need — libtpu is mapped only by that
        maps = Path(f"/proc/{proc.pid}/maps")
        checks["supervisor_off_the_chip"] = maps.exists() and "libtpu" not in maps.read_text()
        check(checks["supervisor_off_the_chip"], "the supervisor process loaded libtpu")

        # wait for the frontend, then send prefix-sharing sessions
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            try:
                status, body = get_json(f"{base}/v1/models", timeout=5)
                if status == 200 and any(m["id"] == "smoke" for m in body.get("data", [])):
                    break
            except (OSError, ValueError):
                pass
            time.sleep(1.0)
        else:
            raise AssertionError(f"frontend never listed the model:\n{log_tail(proc, 60)}")

        # the frontend lists the model before the processor has discovered
        # the workers' endpoints (5xx), and the KV router places requests at
        # random until its first load scrape has come back: send warm-up
        # requests, as a client would retry, until one is placed by the router
        warm = chat_body("smoke", "are you there", 2)
        deadline = time.monotonic() + 240
        warmups = 0
        while not log_lines(proc, r"routed \d+ tokens to worker "):
            status, text = http("POST", f"{base}/v1/chat/completions", warm)
            check(status == 200 or status >= 500, f"HTTP {status}: {text[:300]}")
            check(time.monotonic() < deadline, f"no request was placed by the KV router in 240 s (last HTTP {status}):\n{log_tail(proc, 40)}")
            warmups += 1
            time.sleep(1.0)
        checks["warmup_requests_before_kv_routing"] = warmups
        routed_before = len(log_lines(proc, r"routed \d+ tokens to worker "))

        groups = 4
        system = [prompt_of_tokens(ckpt, seed + 100 + g, 160) for g in range(groups)]
        answers: dict = {}
        for turn in range(3):
            for g in range(groups):
                # same session prompt every turn: a shared prefix AND a
                # greedy repeat, whichever worker takes it
                body = chat_body("smoke", system[g] + f" question {g}", 8)
                out = chat(base, body)
                check(out["usage"]["completion_tokens"] == 8, out["usage"])
                text = out["choices"][0]["message"]["content"]
                check(answers.setdefault(g, text) == text, f"session {g} answer changed on turn {turn}")
        routed = [re.search(r"to worker ([0-9a-f]+) \((\d+) cached", ln).groups()
                  for ln in log_lines(proc, r"routed \d+ tokens to worker ")][routed_before:]
        served = sorted({w for w, _ in routed})
        checks["requests"] = groups * 3
        checks["requests_placed_by_kv_router"] = len(routed)
        checks["finding_requests_placed_at_random"] = len(log_lines(proc, r"falling back to random"))
        checks["workers_that_served"] = served
        checks["requests_routed_to_cached_prefix"] = sum(1 for _, c in routed if int(c) > 0)
        check(len(served) > 1, f"every request went to one worker: {served}")
        check(checks["requests_routed_to_cached_prefix"] > 0, "the KV router never used a cached prefix")
        checks["radix_index"] = (log_lines(proc, r"radix index: ") or ["not logged"])[0].split("radix index: ")[-1]
        ok, err = True, None
    except Exception as e:
        ok, err = False, f"{type(e).__name__}: {e}"
        checks["supervisor_log_tail"] = log_tail(proc, 60)
    finally:
        checks["supervisor_exit"] = stop(proc, grace_s=60.0)
    return report("replicas", ok, error=err, seconds=round(time.monotonic() - t0, 1), **checks)


def run(sizes: Sizes, chips: int, seed: int) -> int:
    """The parent. Returns the exit code; prints the contract's last line
    unless JAX finds no accelerator."""
    WORK.mkdir(exist_ok=True)
    try:
        pre = preflight(sizes, seed)
        if pre is None:
            return 2
        environment_notes()
        if chips == 1:
            ckpt = phase_generate("tinyllama-1.1b" if sizes.name == "full" else "tiny", sizes.serve_geometry, seed)
            device = phase_serve(sizes, ckpt, seed)
            phase_child_rows("parity", sizes, seed, timeout_s=900)
        else:
            tp_ckpt = phase_generate("qwen2.5-7b-width" if sizes.name == "full" else "tiny-qwen", sizes.tp_geometry, seed)
            ok, rows = phase_child_rows("tp", sizes, seed, timeout_s=1500, extra=["--ckpt", str(tp_ckpt)])
            device = next((r["device"] for r in rows if "device" in r), None)
            # only now, with the tp child gone and its chips released
            rep_ckpt = phase_generate("tinyllama-width" if sizes.name == "full" else "tiny", sizes.replica_geometry, seed)
            phase_replicas(sizes, rep_ckpt, seed)
        device = device or pre  # a failed phase: say what the preflight child saw
    finally:
        stop_all()
        out = ROOT / "chiprun_out" / "chip_smoke_logs"
        if (WORK / "logs").is_dir():
            shutil.copytree(WORK / "logs", out, dirs_exist_ok=True)
    ok = all(ok for _, ok in RESULTS)
    if chips != 1 and device.get("count") != chips:
        ok = report("device-count", False, error=f"{device.get('count')} devices, {chips} asked for") and ok
    print(json.dumps({"ok": ok, "device": {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"],
    }, "phases": {p: o for p, o in RESULTS}}), flush=True)
    return 0 if ok else 1


# ---------------------------------------------------------------- phases: JAX children


def _device() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def child_device(sizes: Sizes, args) -> int:
    print(json.dumps({"device": _device()}), flush=True)
    return 0


def _parity_case(name: str, thunk) -> bool:
    """Run one kernel-vs-reference case; print its JSON line."""
    import numpy as np

    t0 = time.monotonic()
    try:
        got, ref = thunk()
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        check(got.shape == ref.shape, (got.shape, ref.shape))
        diff = np.abs(got - ref)
        excess = float(np.max(diff - PARITY_RTOL * np.abs(ref)))
        ok = bool(np.isfinite(got).all()) and excess <= PARITY_ATOL
        row = {"case": name, "ok": ok, "max_abs_err": round(float(np.max(diff)), 5),
               "max_err_beyond_one_ulp": round(max(excess, 0.0), 5), "atol": PARITY_ATOL,
               "ref_abs_max": round(float(np.max(np.abs(ref))), 3), "shape": list(got.shape)}
    except Exception as e:  # a refused compile is a failed case, and the next one still runs
        row = {"case": name, "ok": False, "error": f"{type(e).__name__}: {' '.join(str(e).split())[:400]}"}
    row["seconds"] = round(time.monotonic() - t0, 1)
    print(json.dumps(row), flush=True)
    return row["ok"]


def child_parity(sizes: Sizes, args) -> int:
    """Every Pallas kernel the dispatch can reach, compiled for the device
    this child runs on, against the gather reference."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.models.deepseek import DeepseekConfig, DeepseekModel
    from dynamo_tpu.ops import attention as A
    from dynamo_tpu.ops.pallas.paged_attention import paged_decode_attention_pallas
    from dynamo_tpu.ops.pallas.prefill_attention import paged_prefill_attention_pallas
    from dynamo_tpu.quant.kv import QuantizedPages, quantize_kv_rows

    print(json.dumps({"device": _device()}), flush=True)
    full = sizes.name == "full"
    # the dispatchers choose interpret mode themselves, and only off the chip
    # under DYNTPU_PALLAS=1; direct kernel calls below follow the same rule
    interpret = not A._on_tpu()
    rng = np.random.default_rng(args.seed)
    bf16 = jnp.bfloat16

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32) * scale, bf16)

    def pools(P, ps, hkv, d, int8, folded):
        out = []
        for _ in range(2):
            x = normal(P, ps, hkv, d)
            if int8:
                q, s = quantize_kv_rows(x.reshape(P * ps, hkv, d))
                q = q.reshape(P, ps, hkv * d) if folded else q.reshape(P, ps, hkv, d)
                out.append(QuantizedPages(q, s.reshape(P, ps)))
            else:
                out.append(x.reshape(P, ps, hkv * d) if folded else x)
        return out

    def reference(fn, *a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)

    def ragged_batch(ps):
        """(B, max_pages, tables, positions): one token, a page boundary,
        a few pages, the whole table (24 pages: three tiles of the decode
        kernel at page size 16)."""
        B, maxp = (8, 24) if full else (4, 6)
        tables = jnp.asarray(1 + rng.permutation(B * maxp).reshape(B, maxp), jnp.int32)
        lengths = [1, ps, ps + 1, 4 * ps + 3, 5 * ps, maxp * ps - 1, 2 * ps - 1, maxp * ps]
        return B, maxp, tables, jnp.asarray([n - 1 for n in lengths[:B]], jnp.int32)

    def cell_batch(ps):
        """The benchmark cell's decode batch: 64 slots (6 in the rehearsal),
        a third of them empty (one token on the trash page's row), the rest
        ragged across the tiled kernel's boundaries: a tile is 128 tokens,
        the cross-program window two tiles, then the double-buffered tail."""
        B = 64 if full else 6
        edges = [127, 128, 129, 255, 256, 257, 383, 384 + ps + 1, 641, 897, 130, 2 * ps - 1]
        return batch_of([1 if b % 3 == 0 else edges[b % len(edges)] for b in range(B)], ps)

    def rag_batch(ps, deepest=4096):
        """`lfm2-8b-a1b-d16.rag-over`'s decode batch: 256 slots (8 in the
        rehearsal), 77 of them live (3), every third slot from the first and
        empty ones between, ragged across the tile, the window and the tail,
        one context of `deepest` tokens (of 400)."""
        B, live, deep = (256, 77, deepest) if full else (8, 3, 400)
        edges = [127, 128, 129, 255, 257, 383, 1531, 2 * ps - 1, 1024, 641, 2049, 1]
        lengths = [1] * B
        for i in range(live):
            lengths[3 * i] = deep if i == 2 else min(edges[i % len(edges)], deep)
        return batch_of(lengths, ps)

    def batch_of(lengths, ps):
        """(B, max_pages, tables, positions) for these context lengths: pages
        of their own in a shuffled order, a table wider than any context and
        padded with page 0."""
        needed = [-(-n // ps) for n in lengths]
        maxp = max(needed) + 3
        order = iter(1 + rng.permutation(sum(needed)))
        tables = np.zeros((len(lengths), maxp), np.int32)
        for b, n in enumerate(needed):
            tables[b, :n] = [next(order) for _ in range(n)]
        return len(lengths), maxp, jnp.asarray(tables), jnp.asarray([n - 1 for n in lengths], jnp.int32)

    def in_runs(batch):
        """`batch`'s contexts with every tile of 128 tokens one aligned slab of
        the pool, the last one whole (PR 47: the allocator reserves the rest of
        a run for its sequence, and the kernel fetches such a tile in one copy)."""
        def laid_out(ps):
            B, maxp, _, pos = batch(ps)
            tp = max(1, 128 // ps)
            tiles = -(-(np.asarray(pos) + 1) // (tp * ps))
            slabs = iter((1 + rng.permutation(int(tiles.sum()))) * tp)
            tables = np.zeros((B, -(-maxp // tp) * tp), np.int32)
            for b, n in enumerate(tiles):
                for t in range(n):
                    tables[b, t * tp:(t + 1) * tp] = next(slabs) + np.arange(tp)
            return B, tables.shape[1], jnp.asarray(tables), pos

        return laid_out

    def decode(hq, hkv, d, ps, int8, kernel=None, batch=ragged_batch):
        folded = d < 128 or kernel == "folded"
        B, maxp, tables, pos = batch(ps)
        k, v = pools(int(tables.max()) + 1, ps, hkv, d, int8, folded)
        q = normal(B, hq, d)
        if kernel == "perseq":
            got = paged_decode_attention_pallas(q, k, v, tables, pos, interpret=interpret)
        else:
            got = jax.jit(A.dispatch_paged_decode_attention)(q, k, v, tables, pos)
        # the gather reference holds every row's whole table in f32: 32 rows at
        # a time (256 rows of 4096 tokens by 512 lanes would be 8 GB)
        want = [reference(A.paged_decode_attention, q[i:i + 32], k, v, tables[i:i + 32], pos[i:i + 32])
                for i in range(0, B, 32)]
        return got, jnp.concatenate(want)

    def decode_repeats(hq, hkv, d, ps, repeats):
        """The folded decode kernel `repeats` times on the SAME inputs at
        `lfm2-8b-a1b-d16.rag-over`'s geometry, every output compared bit for
        bit with the first: a tile merged before its DMAs had landed would
        show as a run that differs (ISSUE 44, H2). The pools are as a busy
        engine leaves them: every row past a length and every page no
        sequence owns holds what another sequence wrote, a finite value in
        one half and NaN in the other. The second half of the runs alternates
        with a program that streams 1 GiB (256 KiB in the rehearsal) through
        HBM, all queued without a wait between. Returns (the number of runs
        that differ from the first, 0)."""
        B, maxp, tables, pos = rag_batch(ps, deepest=4864)  # 4096 + 768: the mix's longest
        lengths = np.asarray(pos) + 1
        P = int(tables.max()) + 1 + 64  # and 64 pages that no table names
        k, v = pools(P, ps, hkv, d, False, True)
        stale = np.ones((P, ps), bool)
        stale[0, 0] = False  # an empty slot's one token, on the trash page
        for b, n in enumerate(lengths):
            for i in range(-(-n // ps)):
                stale[int(tables[b, i]), : min(ps, n - i * ps)] = False
        nan = jnp.asarray(stale & (np.arange(P)[:, None] % 2 == 1))[..., None]
        k, v = (jnp.where(nan, jnp.asarray(jnp.nan, bf16), x) for x in (k, v))
        q = normal(B, hq, d)
        kernel = jax.jit(A.dispatch_paged_decode_attention)
        first = jax.block_until_ready(kernel(q, k, v, tables, pos))
        check(bool(jnp.isfinite(first.astype(jnp.float32)).all()), "a stale row reached the output")
        stream = jax.jit(lambda x: x + 1)
        big = jnp.zeros(((1 << 30) if full else (1 << 18)) // 4, jnp.float32)
        differs = jax.jit(lambda a, b: jnp.any(jax.lax.bitcast_convert_type(a, jnp.uint16)
                                               != jax.lax.bitcast_convert_type(b, jnp.uint16)))
        bad = []
        for i in range(repeats):
            if i >= repeats // 2:
                big = stream(big)
            bad.append(differs(kernel(q, k, v, tables, pos), first))
        return jnp.sum(jnp.stack(bad)).astype(jnp.float32)[None], jnp.zeros(1, jnp.float32)

    def prefill(hq, hkv, d, ps, T, prefix, int8, lookahead=None, folded=None):
        # a chunk of T rows behind `prefix` cached tokens (not page-aligned
        # tiles past the lookahead window: the tail double buffer runs too)
        folded = d < 128 if folded is None else folded
        maxp = -(-(prefix + T) // ps) + 3  # a table wider than the context
        P = maxp + 2
        k, v = pools(P, ps, hkv, d, int8, folded)
        q = normal(T, hq, d)
        table = jnp.asarray(1 + rng.permutation(maxp) % (P - 1), jnp.int32)
        pos = jnp.asarray(prefix + np.arange(T), jnp.int32)
        if lookahead is None:
            got = jax.jit(A.dispatch_paged_prefill_attention)(q, k, v, table, pos)
        else:
            got = paged_prefill_attention_pallas(q, k, v, table, pos, interpret=interpret, lookahead=lookahead)
        return got, reference(A.paged_prefill_attention, q, k, v, table, pos)

    def window_decode(hq, hkv, d, ps, window, B):
        """The sliding-window decode kernel at contexts around 3 windows,
        the table's entries behind each window pointing at the null page (the
        engine gives those pages back), against the XLA path with the mask."""
        lengths = [3 * window + 7, 3 * window, 2 * window + ps + 1, window - 3][:B]
        maxp = -(-max(lengths) // ps) + 3
        order = iter(1 + rng.permutation(sum(-(-n // ps) for n in lengths)))
        tables = np.zeros((B, maxp), np.int32)
        for b, n in enumerate(lengths):
            tables[b, : -(-n // ps)] = [next(order) for _ in range(-(-n // ps))]
        k, v = pools(int(tables.max()) + 1, ps, hkv, d, False, False)
        q, pos = normal(B, hq, d), jnp.asarray([n - 1 for n in lengths], jnp.int32)
        want = reference(A.paged_decode_attention, q, k, v, jnp.asarray(tables), pos, window)
        for b, n in enumerate(lengths):
            tables[b, : max(0, n - window) // ps] = 0
        got = jax.jit(functools.partial(A.dispatch_paged_decode_attention, window=window))(
            q, k, v, jnp.asarray(tables), pos)
        return got, want

    def window_prefill(hq, hkv, d, ps, T, prefix, window):
        maxp = -(-(prefix + T) // ps) + 3
        k, v = pools(maxp + 2, ps, hkv, d, False, False)
        q = normal(T, hq, d)
        table = 1 + rng.permutation(maxp) % (maxp + 1)
        pos = jnp.asarray(prefix + np.arange(T), jnp.int32)
        want = reference(A.paged_prefill_attention, q, k, v, jnp.asarray(table, jnp.int32), pos, window)
        table[: max(0, prefix - window + 1) // ps] = 0
        got = jax.jit(functools.partial(A.dispatch_paged_prefill_attention, window=window))(
            q, k, v, jnp.asarray(table, jnp.int32), pos)
        return got, want

    def mla(ps, T=None, prefix=0):
        """The model's own Pallas call against its own _absorbed_attention."""
        h, dc, dn, dr, dv = (16, 512, 128, 64, 128) if full else (4, 128, 16, 64, 16)
        cfg = DeepseekConfig.tiny_mla(num_heads=h, kv_lora_rank=dc, qk_nope_head_dim=dn,
                                      qk_rope_head_dim=dr, v_head_dim=dv, dtype="bf16")
        model = DeepseekModel(cfg)
        lp = {"w_kb": normal(dc, h, dn, scale=dn ** -0.5), "w_vb": normal(dc, h, dv, scale=dc ** -0.5)}
        lat = cfg.latent_dim_padded

        def pool(P):
            rows = np.zeros((P, ps, lat), np.float32)
            rows[..., : cfg.latent_dim] = rng.standard_normal((P, ps, cfg.latent_dim))
            return jnp.asarray(rows, bf16)

        if T is None:  # decode
            B, maxp, tables, pos = ragged_batch(ps)
            pages = pool(B * maxp + 1)
            qn, qr = normal(B, h, dn), normal(B, h, dr)
            got = jax.jit(model._mla_decode_pallas)(lp, qn, qr, pages, tables, pos)

            def one(qn_b, qr_b, pt_b, pos_b):
                ctx = pages[pt_b].reshape(pt_b.shape[0] * ps, lat)
                return model._absorbed_attention(lp, qn_b[None], qr_b[None], ctx, pos_b[None])[0]

            return got, reference(jax.vmap(one), qn, qr, tables, pos)
        maxp = -(-(prefix + T) // ps) + 3
        pages = pool(maxp + 2)
        qn, qr = normal(T, h, dn), normal(T, h, dr)
        table = jnp.asarray(1 + rng.permutation(maxp) % (maxp + 1), jnp.int32)
        pos = jnp.asarray(prefix + np.arange(T), jnp.int32)
        got = jax.jit(model._mla_prefill_pallas)(lp, qn, qr, pages, table, pos)
        ctx = pages[table].reshape(maxp * ps, lat)
        return got, reference(model._absorbed_attention, lp, qn, qr, ctx, pos)

    def scattered(slots, n_live):
        """`n_live` of `slots` rows live, where a busy scheduler leaves them."""
        alive = np.zeros(slots, bool)
        alive[rng.permutation(slots)[:n_live]] = True
        return alive

    def live_decode(hq, hkv, d, ps, n_live):
        """The cell batch with `n_live` of its rows live (`qwen2.5-3b.chat`
        decodes 11 of 64): the live rows against the reference, every other
        row against zero."""
        from dynamo_tpu.ops.live_rows import live_rows

        B, maxp, tables, pos = cell_batch(ps)
        alive = scattered(B, n_live)
        k, v = pools(int(tables.max()) + 1, ps, hkv, d, False, False)
        q = normal(B, hq, d)
        got = jax.jit(A.dispatch_paged_decode_attention)(q, k, v, tables, pos, live=live_rows(jnp.asarray(alive)))
        want = reference(A.paged_decode_attention, q, k, v, tables, pos)
        return got, jnp.where(jnp.asarray(alive)[:, None, None], want, 0)

    def ssm_update(slots, H, P, G, N, n_live=None):
        """The one-token Mamba-2 state update, in place over the state, at a
        batch where a third of the slots are not live (or all but `n_live`):
        output y and the whole state against `jax.numpy`, which leaves a dead
        row's state as it was and reads it zero."""
        from dynamo_tpu.ops.live_rows import live_rows
        from dynamo_tpu.ops.pallas.ssm_update import ssm_state_update_pallas
        from dynamo_tpu.ops.ssm import ssm_state_update_reference

        def f32(*shape, scale=1.0):
            return jnp.asarray(rng.standard_normal(shape, dtype=np.float32) * scale)

        state = f32(slots + 1, H, P, N)
        alive = np.arange(slots) % 3 != 1 if n_live is None else scattered(slots, n_live)
        live = live_rows(jnp.asarray(alive))
        rows = jnp.arange(slots, dtype=jnp.int32)
        decay, dtx = jnp.exp(-jnp.abs(f32(slots, H))), f32(slots, H, P)
        b, c = f32(slots, G, N), f32(slots, G, N, scale=0.1)
        want_y, want_s = reference(ssm_state_update_reference, state, decay, dtx, b, c, rows, live)
        got_y, got_s = ssm_state_update_pallas(state, decay, dtx, b, c, rows, live, interpret=interpret)
        return (np.concatenate([np.asarray(got_y).ravel(), np.asarray(got_s).ravel()]),
                np.concatenate([np.asarray(want_y).ravel(), np.asarray(want_s).ravel()]))

    def grouped_matmul(M, K, N, G, tokens, topk, routed):
        """The expert layer's grouped product at a decode step's shape: each
        of `tokens` tokens chooses `topk` of `routed` experts, the first G are
        held, the rest of the M static rows belong to nobody and hold NaN on
        the way in. Rows inside groups against one dense float32 product per
        expert (XLA's `ragged_dot` is itself a Mosaic kernel on the chip, and
        refuses bf16 operands at the reference's precision)."""
        from dynamo_tpu.ops.pallas.grouped_matmul import grouped_matmul_pallas

        chosen = np.concatenate([rng.choice(routed, topk, replace=False) for _ in range(tokens)])
        sizes = np.bincount(chosen, minlength=routed)[:G]
        real, ends = int(sizes.sum()), np.cumsum(sizes)
        rows, bank = normal(M, K), normal(G, K, N, scale=K ** -0.5)
        row = jnp.arange(real)[:, None]

        def dense(rows, bank):
            def one(out, group):
                matrix, start, end = group
                y = rows[:real].astype(jnp.float32) @ matrix.astype(jnp.float32)
                return jnp.where((row >= start) & (row < end), y, out), None

            groups = (bank, jnp.asarray(ends - sizes), jnp.asarray(ends))
            return jax.lax.scan(one, jnp.zeros((real, N), jnp.float32), groups)[0]

        got = grouped_matmul_pallas(
            rows.at[real:].set(jnp.nan), bank, jnp.asarray(sizes, jnp.int32), interpret=interpret
        )
        return got[:real], reference(jax.jit(dense), rows, bank)

    if full:
        tiny, qwen, mixtral, bench, shard = (32, 4, 64), (28, 4, 128), (32, 8, 128), (16, 8, 128), (7, 1, 128)
        qwen3b = (16, 2, 128)  # the benchmark's configuration
        lfm2 = (32, 8, 64)  # lfm2-8b-a1b-d16: folded pools of 512 lanes
        falcon = (20, 4, 128)  # falcon-h1-34b-d6: five query heads a kv head
        T, prefix = 512, 1000
        command_a, window = (128, 8, 128), 4096  # command-a-plus-ep8
        deep_window, deep_full = 2 * window, 3 * window - T  # chunk starts at depth
    else:  # the CPU rehearsal: same code paths, interpret-mode sizes
        tiny, qwen, mixtral, bench, shard = (8, 2, 64), (4, 2, 128), (4, 2, 128), (4, 2, 128), (2, 1, 128)
        qwen3b = (4, 2, 128)
        lfm2 = (8, 4, 64)
        falcon = (10, 2, 128)
        T, prefix = 128, 200
        command_a, window = (4, 2, 128), 128
        deep_window = deep_full = 2176  # a table past 2048 tokens: the long tile
    cases = [
        ("decode folded tinyllama ps16 bf16", lambda: decode(*tiny, 16, False)),
        ("decode folded tinyllama ps16 int8", lambda: decode(*tiny, 16, True)),
        ("decode lookahead qwen2.5-7b ps16 bf16", lambda: decode(*qwen, 16, False)),
        ("decode lookahead mixtral ps16 int8", lambda: decode(*mixtral, 16, True)),
        ("decode lookahead mixtral ps128 bf16", lambda: decode(*mixtral, 128, False)),
        ("decode lookahead qwen2.5-3b cell batch ps16 bf16", lambda: decode(*qwen3b, 16, False, batch=cell_batch)),
        ("decode lookahead qwen2.5-7b cell batch ps16 int8", lambda: decode(*qwen, 16, True, batch=cell_batch)),
        ("decode perseq qwen2.5-7b ps16 int8", lambda: decode(*qwen, 16, True, kernel="perseq")),
        # every tile a run of the pool, fetched in one copy a pool (PR 47)
        ("decode lookahead qwen2.5-3b cell batch in runs ps16 bf16",
         lambda: decode(*qwen3b, 16, False, batch=in_runs(cell_batch))),
        ("decode folded lfm2 cell batch in runs ps16 bf16",
         lambda: decode(*lfm2, 16, False, batch=in_runs(rag_batch))),
        # the grid over live rows (PR 46): `chat`'s 11 live of 64, the rest zero
        ("decode lookahead qwen2.5-3b 11 live of 64 ps16 bf16",
         lambda: live_decode(*qwen3b, 16, 11 if full else 2)),
        # a group of 5 query heads is padded to 8 sublanes: rows 5-7 must reach no result
        ("decode lookahead falcon-h1 cell batch ps16 bf16", lambda: decode(*falcon, 16, False, batch=cell_batch)),
        ("prefill falcon-h1 ps16 bf16", lambda: prefill(*falcon, 16, T, prefix, False)),
        ("decode folded qwen2.5-7b tp4-shard ps16 bf16", lambda: decode(*shard, 16, False, kernel="folded")),
        ("decode folded lfm2 cell batch ps16 bf16", lambda: decode(*lfm2, 16, False, batch=rag_batch)),
        ("decode folded lfm2 cell batch ps16 int8", lambda: decode(*lfm2, 16, True, batch=rag_batch)),
        # max_abs_err here is a COUNT: runs whose output differs from the first in any bit
        ("decode folded lfm2 cell batch ps16 bf16 runs of 200 that differ from the first",
         lambda: decode_repeats(*lfm2, 16, 200 if full else 4)),
        ("prefill folded tinyllama ps16 bf16", lambda: prefill(*tiny, 16, T, prefix, False)),
        ("prefill folded tinyllama ps16 int8", lambda: prefill(*tiny, 16, T, prefix, True)),
        ("prefill lookahead qwen2.5-7b ps16 bf16", lambda: prefill(*qwen, 16, T, prefix, False)),
        ("prefill lookahead mixtral ps16 int8", lambda: prefill(*mixtral, 16, T, prefix, True)),
        ("prefill lookahead bench-16q8kv ps128 bf16", lambda: prefill(*bench, 128, T, prefix, False)),
        ("prefill basic mixtral ps16 bf16", lambda: prefill(*mixtral, 16, T, prefix, False, lookahead=False)),
        ("prefill folded qwen2.5-7b tp4-shard ps16 bf16", lambda: prefill(*shard, 16, T, prefix, False, folded=True)),
        ("decode sliding window command-a-plus 3W ps16 bf16",
         lambda: window_decode(*command_a, 16, window, 4)),
        ("prefill sliding window command-a-plus 2.5W ps16 bf16",
         lambda: window_prefill(*command_a, 16, T, 2 * window + window // 2, window)),
        # at depth, where the page table's width gives the long context tile
        ("prefill sliding window command-a-plus start 8k ps16 bf16",
         lambda: window_prefill(*command_a, 16, T, deep_window, window)),
        ("prefill full command-a-plus 12k ps16 bf16",
         lambda: prefill(*command_a, 16, T, deep_full, False)),
        ("mla decode classic ps16", lambda: mla(16)),
        ("mla prefill ps16", lambda: mla(16, T=T, prefix=prefix)),
        # Mamba-2 at the published widths (128 heads x 64 x 128), 24 slots
        ("ssm state update nemotron-h f32", lambda: ssm_update(*((24, 128, 64, 8, 128) if full else (6, 8, 8, 2, 128)))),
        ("ssm state update nemotron-h 11 live of 64 f32",
         lambda: ssm_update(*((64, 128, 64, 8, 128, 11) if full else (6, 8, 8, 2, 128, 2)))),
        # Falcon-H1's (32 heads x 128 x 256 in 2 groups): a block of 16 heads from its bytes
        ("ssm state update falcon-h1 f32", lambda: ssm_update(*((24, 32, 128, 2, 256) if full else (6, 4, 8, 2, 128)))),
        # the cell's decode step: 2816 static rows, 108 tokens x 22 of 512, 128 held
        ("moe grouped matmul nemotron-h decode bf16", lambda: grouped_matmul(
            *((2816, 1024, 2688, 128, 108, 22, 512) if full else (64, 128, 256, 4, 6, 3, 8)))),
    ]
    ok = True
    for name, thunk in cases:
        ok = _parity_case(name, thunk) and ok
    return 0 if ok else 1


def child_tp(sizes: Sizes, args) -> int:
    """The same checkpoint through the engine at tp=1 on one device, then at
    tp=4 on four, in this one process; logprobs compared, memory per device
    printed."""
    import asyncio
    import gc

    import jax
    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest
    from dynamo_tpu.utils.xla_cache import cache_stats, enable_compilation_cache

    enable_compilation_cache()
    print(json.dumps({"device": _device()}), flush=True)
    tp_high = len(jax.devices())
    vocab = sizes.tp_geometry["vocab_size"]
    rng = np.random.default_rng(args.seed)
    # one prompt past the 512 bucket (chunked prefill), the rest short
    lengths = [48, 130, 300, 600] if sizes.name == "full" else [20, 40, 70, 90]
    prompts = [rng.integers(3, vocab, n).tolist() for n in lengths]
    steps = 8

    def memory():
        out = []
        for d in jax.devices():
            s = d.memory_stats() or {}
            out.append({"id": d.id, "bytes_in_use": s.get("bytes_in_use"),
                        "peak_bytes_in_use": s.get("peak_bytes_in_use")})
        return out

    async def run_engine(tp: int) -> list:
        cfg = EngineConfig.for_model(
            args.ckpt, tp=tp, page_size=16, num_pages=512, max_seqs=4,
            max_model_len=1024, prefill_buckets=(64, 128, 256, 512),
        )
        t0 = time.monotonic()
        engine = AsyncJaxEngine(cfg)
        await engine.start()
        info = engine.device_info()
        mem = memory()
        try:
            async def one(i):
                req = EngineRequest(
                    request_id=f"tp{tp}-{i}", token_ids=list(prompts[i]),
                    sampling=SamplingParams(temperature=0.0, max_tokens=steps, ignore_eos=True),
                    logprobs=20,
                )
                return [o async for o in engine.generate(req) if o.token is not None]

            outs = await asyncio.gather(*[one(i) for i in range(len(prompts))])
        finally:
            await engine.shutdown()
        print(json.dumps({"engine": f"tp={tp}", "mesh_device_ids": info["mesh_device_ids"],
                          "start_and_run_s": round(time.monotonic() - t0, 1),
                          "memory_per_device": mem, "xla_cache": cache_stats()}), flush=True)
        return outs, mem

    def compare(a, b) -> dict:
        """Per prompt: compare logprobs position by position while the greedy
        tokens agree; a divergence must be a near-tie."""
        worst, compared, agree = 0.0, 0, []
        for oa, ob in zip(a, b):
            n = 0
            for sa, sb in zip(oa, ob):
                ta, tb = dict(sa.top_logprobs), dict(sb.top_logprobs)
                common = set(ta) & set(tb)
                check(common, "top-20 sets do not intersect")
                worst = max(worst, max(abs(ta[t] - tb[t]) for t in common))
                compared += len(common)
                if sa.token != sb.token:
                    # both tokens must sit within the tolerance of each other
                    gap = abs(ta.get(sa.token, -1e9) - ta.get(sb.token, 1e9))
                    check(gap <= 2 * TP_LOGPROB_ATOL, f"tokens diverge with a {gap:.3f} logprob gap")
                    break
                worst = max(worst, abs(sa.logprob - sb.logprob))
                n += 1
            agree.append(n)
        return {"max_abs_logprob_diff": round(worst, 5), "logprobs_compared": compared,
                "greedy_tokens_agreeing_per_prompt": agree}

    async def main() -> int:
        low, _ = await run_engine(1)
        gc.collect()
        high, mem = await run_engine(tp_high)
        row = {"case": f"tp={tp_high} logprobs vs tp=1", "atol": TP_LOGPROB_ATOL,
               "prompt_lengths": lengths, "steps": steps}
        try:
            row.update(compare(low, high))
            row["ok"] = row["max_abs_logprob_diff"] <= TP_LOGPROB_ATOL
        except AssertionError as e:
            row.update(ok=False, error=str(e))
        print(json.dumps(row), flush=True)
        used = [m["bytes_in_use"] for m in mem]
        spread = {"case": f"tp={tp_high} memory spread over {tp_high} devices", "bytes_in_use": used}
        if all(u is None for u in used):
            # the CPU backend reports no memory: nothing to judge off the chip
            spread.update(ok=_device()["platform"] != "tpu", note="backend reports no memory_stats")
        else:
            spread["ok"] = len(used) == tp_high and min(used) > 0 and max(used) <= 1.5 * min(used)
        print(json.dumps(spread), flush=True)
        return 0 if row["ok"] and spread["ok"] else 1

    return asyncio.run(main())


CHILD_PHASES = {"device": child_device, "parity": child_parity, "tp": child_tp}


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp=4 and four-replica paths (needs four chips)")
    ap.add_argument("--seed", type=int, default=0, help="weights, prompts and kernel inputs")
    # parent -> child plumbing, not for users
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--sizes", help=argparse.SUPPRESS)
    ap.add_argument("--ckpt", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return CHILD_PHASES[args.phase](Sizes(**json.loads(args.sizes)), args)
    return run(full_sizes(), args.chips, args.seed)


if __name__ == "__main__":
    sys.exit(main())
