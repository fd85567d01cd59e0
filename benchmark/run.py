#!/usr/bin/env python3
"""The benchmark: one cell of `BENCHMARK.json`, served by the program's normal
entry point on the chip and driven from outside as a user would.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --sweep <cell> [--seed n]        (builder's use)

Two processes. This one never imports JAX: it makes the checkpoint from the
seed (the tensors its configuration's `checkpoints/<plan>.py` lists), starts
the server (`benchmark/serve_entry.py`, which runs
`dynamo_tpu.launch.run.main` unchanged), waits for `/ready` and for the
first of the program's background compiles to end, holds the scheduler busy
with keeper streams so that no further one starts (`Keepers`), sends warm
traffic of the cell's own shapes and the correctness probes, then drives the cell's traffic as one
asyncio client, reads `/metrics`, `/debug/steps` and `/ready`, stops the
server, and compares the probes with the plain reference. The device in the
result is what the serving process reported; where that is not the platform
the configuration asks for, the run fails and prints no result.

Everything that belongs to one cell, configuration, mix, launcher, generator
or per-layer metric is a file found by its name in `BENCHMARK.json`
(`benchmark/README.md`). The last line of stdout is the result object.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import importlib.util
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

T_PROCESS_START = time.monotonic()
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import checkpoint  # noqa: E402
import client  # noqa: E402
import probe  # noqa: E402
from probe import BenchError  # noqa: E402

#: the profiler traces this long in the middle of a `--trace 1` window
TRACE_SECONDS = 3.0
#: keeper streams (see `Keepers`) and the tokens each generation asks for
KEEPER_STREAMS = 2
KEEPER_TOKENS = 1024
PROBE_LENGTHS = (48, 100, 200, 300)
PROBE_TOKENS = 8


def note(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


# ---------------------------------------------------------------- files by name


class Files:
    """Where the benchmark's data and code are found: under `root` first (a
    later PR's or a test's additions), then beside this file."""

    def __init__(self, root: Path):
        self.root = root
        self.spec = json.loads((root / "BENCHMARK.json").read_text())
        self.dirs = [root / p for p in self.spec["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    def find(self, *parts: str) -> Path:
        for d in self.dirs:
            if (d.joinpath(*parts)).exists():
                return d.joinpath(*parts)
        raise BenchError(f"no file {'/'.join(parts)} under {[str(d) for d in self.dirs]}")

    def data(self, kind: str, name: str) -> dict:
        return json.loads(self.find(kind, f"{name}.json").read_text())

    def module(self, kind: str, name: str):
        path = self.find(kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")

    def config_file(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise BenchError(f"no config {name!r} in BENCHMARK.json")

    def metrics_for(self, group: str, cell: str) -> list:
        return [m for m in self.spec[group] if cell in m.get("workloads", [cell])]


def reader_of(metric: str) -> str:
    """`decode_step_ms.over` is read by `layer_metrics/decode_step_ms.py`: one
    quantity that moves another end-to-end metric in other cells is entered
    once per group of cells, as `<reader>.<group>`, and needs no new code."""
    return metric.split(".", 1)[0]


#: keys of a configuration file that are the benchmark's own; every other key
#: is the published config.json's and goes into the checkpoint unchanged
OWN_KEYS = ("name", "source", "reduced", "assumed", "deployment", "benchmark")


# ---------------------------------------------------------------- the server


class Server:
    def __init__(self, files: Files, conf: dict, ckpt: Path, work: Path, trace: bool):
        b = conf["benchmark"]
        self.port = probe.free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.trace_dir = work / "trace" if trace else None
        if self.trace_dir is not None:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        launcher = files.module("launchers", b["launcher"])
        argv = launcher.command(ckpt, self.port, b["server_args"], self.trace_dir, TRACE_SECONDS)
        env = {k: str(v).replace("{checkout}", str(HERE.parent)) for k, v in b.get("env", {}).items()}
        self.proc = probe.spawn(argv, work / "server.log", cwd=str(HERE.parent),
                                env={"DYNTPU_LOG": "info", **env})

    def compiles(self) -> tuple:
        """(jit-cache growths the program counted, persistent-cache lookups)."""
        n = probe.sample(probe.scrape(self.base), "dynamo_engine_xla_compiles_total") or 0.0
        _, ready = probe.get_json(f"{self.base}/ready")
        xc = ready.get("xla_cache") or {}
        return n, (xc.get("hits", 0), xc.get("misses", 0))

    def wait_compiles_quiet(self, quiet_s: float, timeout_s: float) -> dict:
        """Until the compile counters have not moved for `quiet_s`."""
        deadline = time.monotonic() + timeout_s
        last, since = None, time.monotonic()
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchError(f"server exited rc={self.proc.returncode}:\n{probe.log_tail(self.proc)}")
            try:
                now = self.compiles()
            except OSError:  # the server is busy compiling: ask again
                time.sleep(0.5)
                continue
            if now != last:
                last, since = now, time.monotonic()
            if time.monotonic() - since >= quiet_s:
                return {"compiles": last[0], "cache_hits": last[1][0], "cache_misses": last[1][1]}
            time.sleep(0.5)
        raise BenchError(f"compiles did not come to rest in {timeout_s:.0f}s (last {last})")

    def stop(self) -> int | None:
        return probe.stop(self.proc)


class Keepers:
    """Streams that keep the scheduler from ever being idle, from `/ready`
    to the end of the window.

    Under `warmup="background"` (the only mode `launch.run` gives a real
    checkpoint) the program compiles about fifty more trace variants on the
    engine thread, one whenever it finds the scheduler idle: 850 s of a cold
    run (my chip run, PR 24), 15 s stalls in mid-traffic, and with them a first
    run that cannot end inside the 1200 s it is allowed. No flag turns it off,
    and this PR may not change the program. So each keeper holds one decode
    slot with an endless chain of short generations; the variants the cell's
    traffic does use are compiled by the warm bursts. The cost to what is
    measured is `KEEPER_STREAMS` of the server's decode slots, in every cell
    alike: about 6% on the latencies of `qwen2.5-3b.chat`, where they are two
    of some ten sequences in a decode step (PERF.md section 6, which also
    says why they stay to the window's close)."""

    def __init__(self, base: str, model: str):
        self.base, self.model = base, model
        self.tokens = KEEPER_TOKENS
        self.stop_event = threading.Event()
        self.errors: list = []
        self.threads = [threading.Thread(target=self._loop, args=(i,), daemon=True, name=f"keeper-{i}")
                        for i in range(KEEPER_STREAMS)]
        for t in self.threads:
            t.start()

    def _loop(self, i: int) -> None:
        first = True
        while not self.stop_event.is_set():
            # the first of each stream has its own length, so that the streams
            # never end together
            n = self.tokens * (i + 1) // (len(self.threads) + 1) if first else self.tokens
            first = False
            body = {"model": self.model, "prompt": [3 + i] * 8, "stream": True, "max_tokens": n,
                    "temperature": 0.0, "ext": {"ignore_eos": True}}
            req = urllib.request.Request(f"{self.base}/v1/completions", data=json.dumps(body).encode(),
                                         method="POST", headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=600) as r:
                    for _ in r:
                        if self.stop_event.is_set():
                            break
            except OSError as e:
                if not self.stop_event.is_set():
                    self.errors.append(f"{type(e).__name__}: {e}")
                    time.sleep(0.2)

    def stop(self) -> None:
        self.stop_event.set()
        for t in self.threads:
            t.join(5.0)


# ---------------------------------------------------------------- warm traffic and probes


async def warm_bursts(session, srv: Server, model: str, warm: dict, seed: int, vocab: int) -> dict:
    """The shapes a mix can reach, sent on purpose before the window: for
    each context depth (a cached shared prefix of that many tokens puts the
    sequence on the wider page-table rung), each prompt tail length (one per
    prefill bucket) and each burst size (packed prefill lanes), `burst`
    requests at once. What they compile, the persistent cache keeps."""
    from generators import _draw

    sent = 0
    for d, depth in enumerate(warm.get("depths", [0])):
        prefix = _draw.token_ids(seed, 900_000 + d, depth, vocab) if depth else []
        if prefix:  # into the prefix cache
            await client.complete(session, srv.base, model, prefix + [7], 1, time.monotonic())
            sent += 1
        for tail in warm["tails"]:
            for burst in warm["bursts"]:
                for rep in range(int(warm.get("repeats", 1))):
                    outs = await asyncio.gather(*[
                        client.complete(
                            session, srv.base, model,
                            prefix + _draw.token_ids(seed, 910_000 + sent + i, tail, vocab),
                            int(warm.get("tokens", 9)), time.monotonic())
                        for i in range(burst)])
                    sent += burst
                    bad = [o.error for o in outs if not o.ok]
                    if bad:
                        raise BenchError(f"warm request failed: {bad[:3]}")
    return {"warm_requests": sent}


async def send_probes(session, srv: Server, model: str, seed: int, vocab: int) -> list:
    """The correctness probes: fixed prompts from the seed, 8 greedy tokens
    each with their logprobs, one at a time (prefill, then decode steps
    through the cache)."""
    from generators import _draw

    probes = []
    for i, n in enumerate(PROBE_LENGTHS):
        prompt = _draw.token_ids(seed, 800_000 + i, n, vocab)
        out = await client.complete(session, srv.base, model, prompt, PROBE_TOKENS,
                                    time.monotonic(), logprobs=True)
        if not out.ok or len(out.logprobs) != PROBE_TOKENS:
            raise BenchError(f"probe {i} failed: {out.error or out.logprobs}")
        chosen = [checkpoint.token_id_of(tok) for tok, _ in out.logprobs]
        probes.append({"tokens": prompt + chosen, "prompt_len": n,
                       "server_logprobs": [lp for _, lp in out.logprobs]})
    return probes


def reference_logprobs(script: Path, ckpt: Path, cache: Path, probes: list, work: Path) -> tuple:
    """Teacher-forced logprobs from the plain reference, in a CPU child; kept
    by checkpoint and token sequences, so a repeated seed pays nothing."""
    asked = [{"tokens": p["tokens"], "prompt_len": p["prompt_len"]} for p in probes]
    key = hashlib.sha256(((ckpt / ".complete").read_text() + json.dumps(asked)).encode()).hexdigest()[:32]
    kept = cache / "reference" / f"{key}.json"
    if kept.exists():
        return json.loads(kept.read_text()), True, 0.0
    t0 = time.monotonic()
    kept.parent.mkdir(parents=True, exist_ok=True)
    (work / "probes.json").write_text(json.dumps(asked))
    proc = subprocess.run(
        [sys.executable, str(script), str(ckpt), str(work / "probes.json"), str(work / "reference.json")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise BenchError(f"reference failed rc={proc.returncode}:\n{proc.stderr[-2000:]}")
    shutil.move(str(work / "reference.json"), kept)
    return json.loads(kept.read_text()), False, time.monotonic() - t0


# ---------------------------------------------------------------- the measured run


async def fetch_json(session, url: str) -> dict:
    async with session.get(url) as r:
        return await r.json()


async def fetch_metrics(session, base: str) -> dict:
    async with session.get(f"{base}/metrics") as r:
        return probe.parse_exposition(await r.text())


async def measure(files: Files, srv: Server, model: str, mix: dict, cell_file: dict,
                  args, vocab: int, steps: list | None = None) -> dict:
    """Lead-in, window, drain. Returns everything the metrics are made of."""
    import aiohttp

    gen = files.module("generators", mix["generator"])
    seconds = float(args.seconds)
    plan = gen.build(mix, cell_file, vocab, args.seed, seconds, **({"steps": steps} if steps else {}))
    lead = 0.0 if steps else float(mix.get("lead_in_s", 0.0))
    drain = float(mix.get("drain_s", 30.0))
    outcomes: list = []
    ctx: dict = {"samples": [], "records": {}}
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=30)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session, \
            aiohttp.ClientSession(timeout=timeout) as side:

        async def send(prompt, max_tokens, due, tag=""):
            return await client.complete(session, srv.base, model, prompt, max_tokens, due,
                                         tag=tag, sink=outcomes)

        if hasattr(gen, "prepare"):
            ctx["prepare"] = await gen.prepare(plan, send)
            outcomes.clear()
        t_open = time.monotonic() + lead + 0.2
        t_close = t_open + (plan["end"] if steps else seconds)
        tasks = gen.drive(plan, send, t_open, t_close)

        async def steps_into(records: dict) -> dict:
            doc = await fetch_json(side, f"{srv.base}/debug/steps?limit=512")
            for r in doc.get("records", []):
                records[r["seq"]] = r
            return doc.get("summary", {})

        async def sampler():
            # once a second: the page pool's occupancy, and the step ring
            # before it wraps (512 records)
            while True:
                ctx["samples"].append((time.monotonic(), await fetch_metrics(side, srv.base)))
                await steps_into(ctx["records"])
                await asyncio.sleep(1.0)

        await asyncio.sleep(max(0.0, t_open - time.monotonic()))
        ctx["m0"] = await fetch_metrics(side, srv.base)
        before: dict = {}  # the ring still holds warm traffic and lead-in
        ctx["steps0"] = await steps_into(before)
        seq_open = max(before, default=0)
        ctx["t_open_actual"] = time.monotonic()
        poll = asyncio.create_task(sampler())
        if srv.trace_dir is not None and not steps:
            await asyncio.sleep(max(0.0, t_open + seconds * 0.5 - time.monotonic()))
            (srv.trace_dir / "start").write_text("go")
            ctx["trace_started_s"] = time.monotonic() - t_open
        await asyncio.sleep(max(0.0, t_close - time.monotonic()))
        poll.cancel()
        ctx["m1"] = await fetch_metrics(side, srv.base)
        ctx["steps1"] = await steps_into(ctx["records"])
        ctx["ready1"] = await fetch_json(side, f"{srv.base}/ready")
        ctx["t_close_actual"] = time.monotonic()
        _, pending = await asyncio.wait(tasks, timeout=drain) if tasks else (set(), set())
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, poll, return_exceptions=True)
    for o in outcomes:
        if o.done is None and not o.error:
            o.error = f"cut: not finished {drain:g}s after the window"
    ctx.update(outcomes=outcomes, t_open=t_open, t_close=t_close, window_s=t_close - t_open,
               records=[r for seq, r in ctx["records"].items() if seq > seq_open])
    return ctx


def end_to_end(ctx: dict, t_setup: float) -> tuple:
    """(metrics by name, attempted, failed): all taken by this client."""
    t0, t1 = ctx["t_open"], ctx["t_close"]
    window = [o for o in ctx["outcomes"] if t0 <= o.due < t1]
    good = [o for o in window if o.ok]
    completed_inside = sum(o.output_tokens for o in ctx["outcomes"]
                           if o.ok and o.done is not None and t0 <= o.done < t1)
    values = {"setup_s": t_setup, "output_tokens_per_s": completed_inside / (t1 - t0)}
    ttft = [o.ttft_s * 1e3 for o in good]
    tpot = [o.tpot_s * 1e3 for o in good if o.tpot_s is not None]
    if ttft:
        values["ttft_p50_ms"] = client.percentile(ttft, 50)
        values["ttft_p95_ms"] = client.percentile(ttft, 95)
    if tpot:
        values["tpot_p95_ms"] = client.percentile(tpot, 95)
    return values, len(window), len(window) - len(good)


def wait_trace(srv: Server) -> dict:
    """Wait until the server's side thread has stopped the profiler."""
    done = srv.trace_dir / "done"
    deadline = time.monotonic() + 120
    while not done.exists() and time.monotonic() < deadline:
        if srv.proc.poll() is not None:
            raise BenchError(f"server exited rc={srv.proc.returncode} while tracing:\n{probe.log_tail(srv.proc)}")
        time.sleep(0.2)
    if not done.exists():
        raise BenchError("the traced window never ended")
    # the server renames the finished file into place, so it is whole when seen
    report = json.loads(done.read_text())
    if not report.get("ok"):
        raise BenchError(f"the profiler failed in the server: {report}")
    return report


def run_reduction(srv: Server, work: Path) -> dict:
    """Reduce the trace in a child: reading the file takes JAX, which this
    process never imports."""
    found = sorted(srv.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise BenchError(f"no xplane.pb under {srv.trace_dir}")
    out = work / "trace_reduced.json"
    proc = subprocess.run(
        [sys.executable, str(HERE / "trace_reduce.py"), str(found[-1]), str(out), str(work / "trace_events.json")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise BenchError(f"trace reduction failed rc={proc.returncode}:\n{proc.stderr[-2000:]}")
    return json.loads(out.read_text())


# ---------------------------------------------------------------- main


def check_device(ready: dict, b: dict, cell: dict) -> dict:
    dev = ready.get("device") or {}
    want = b.get("platform", "tpu")
    if dev.get("platform") != want:
        raise BenchError(f"the server runs on {dev.get('platform')!r} ({dev.get('kind')}), "
                         f"not {want!r}: no result")
    if want == "tpu" and int(dev.get("count", 0)) < int(cell["chips"]):
        raise BenchError(f"the server sees {dev.get('count')} chips, the cell asks for {cell['chips']}")
    return dev


def serve_and_measure(files: Files, args, cell: dict, trace: bool, steps: list | None = None) -> dict:
    """Checkpoint, server, keepers, warm bursts, probes, the measured traffic
    (the cell's own, or a sweep's `steps`), server stopped. Returns what the
    result is made of."""
    conf = files.config_file(cell["config"])
    b = conf["benchmark"]
    why_not = files.module("launchers", b["launcher"]).preflight()
    if why_not:
        raise BenchError(why_not)
    # which tensors the checkpoint holds: `checkpoints/<name>.py`, found by name
    plan = files.module("checkpoints", b.get("checkpoint", "dense"))
    cache = HERE / ".cache"
    work = cache / "work" / cell["name"]
    work.mkdir(parents=True, exist_ok=True)
    hf = {k: v for k, v in conf.items() if k not in OWN_KEYS}
    ckpt, made, secs, size = checkpoint.ensure_checkpoint(cache, cell["config"], hf, args.seed, plan)
    note(phase="checkpoint", path=os.path.relpath(ckpt, HERE.parent), made=made,
         seconds=round(secs, 2), bytes=size)
    mix = files.data("traffic", cell["traffic"])
    cell_file = {} if steps else files.data("cells", cell["name"])
    vocab = conf["vocab_size"]
    srv = Server(files, conf, ckpt, work, trace)
    try:
        t0 = time.monotonic()
        ready = probe.wait_ready(srv.base, srv.proc, float(b.get("ready_timeout_s", 1100)))
        dev = check_device(ready, b, cell)
        model = ready["models"][0]
        note(phase="ready", seconds=round(time.monotonic() - t0, 2), device=dev,
             xla_cache=ready.get("xla_cache"))
        keepers = Keepers(srv.base, model)
        t0 = time.monotonic()
        rest = srv.wait_compiles_quiet(2.0, 300)
        note(phase="first_background_compile", seconds=round(time.monotonic() - t0, 2), **rest)

        async def before_window():
            import aiohttp

            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=900)) as s:
                t0 = time.monotonic()
                warmed = await warm_bursts(s, srv, model, mix["warm"], args.seed, vocab)
                note(phase="warm", seconds=round(time.monotonic() - t0, 2), **warmed)
                return await send_probes(s, srv, model, args.seed, vocab)

        probes = asyncio.run(before_window())
        t0 = time.monotonic()
        rest = srv.wait_compiles_quiet(2.0, 600)
        note(phase="compiles_at_rest", seconds=round(time.monotonic() - t0, 2), **rest)
        ctx = asyncio.run(measure(files, srv, model, mix, cell_file, args, vocab, steps=steps))
        keepers.stop()
        if keepers.errors:
            raise BenchError(f"a keeper stream failed: {keepers.errors[:3]}")
        trace_report = wait_trace(srv) if trace else None
    except BenchError:
        note(phase="failed", server_log_tail=probe.log_tail(srv.proc, 60))
        raise
    finally:
        rc = srv.stop()
    note(phase="server_stopped", rc=rc)
    return dict(conf=conf, b=b, cache=cache, work=work, ckpt=ckpt, srv=srv, dev=dev, probes=probes,
                ctx=ctx, trace_report=trace_report)


def run_cell(files: Files, args) -> int:
    cell = files.cell(args.workload)
    trace = bool(args.trace)
    got = serve_and_measure(files, args, cell, trace)
    conf, b, cache, work, ckpt, srv, dev = (got[k] for k in ("conf", "b", "cache", "work", "ckpt", "srv", "dev"))
    probes, ctx, trace_report = (got[k] for k in ("probes", "ctx", "trace_report"))
    t_setup = ctx["t_open"] - T_PROCESS_START

    # correctness, outside the window and after the server has gone
    ref, kept, ref_s = reference_logprobs(files.find("reference", f"{b['reference']}.py"), ckpt, cache, probes, work)
    worst = max(abs(a - r) for p, rs in zip(probes, ref) for a, r in zip(p["server_logprobs"], rs))
    compiles = (probe.sample(ctx["m1"], "dynamo_engine_xla_compiles_total") or 0) - \
               (probe.sample(ctx["m0"], "dynamo_engine_xla_compiles_total") or 0)
    values, attempted, failed = end_to_end(ctx, t_setup)
    counts_ok = all(o.output_tokens == o.asked_tokens for o in ctx["outcomes"] if o.ok)
    # the tolerance is the configuration's own (its file says why that much)
    atol = float(b["logprob_atol"])
    correct = worst <= atol and compiles == 0 and counts_ok and attempted > 0
    lags = [o.lag_s * 1e3 for o in ctx["outcomes"] if ctx["t_open"] <= o.due < ctx["t_close"]]
    compared = sum(len(r) for r in ref)
    note(phase="correctness", logprob_worst_abs_diff=worst, tolerance=atol,
         logprobs_compared=compared, reference_kept=kept,
         reference_seconds=round(ref_s, 2), compiles_in_window=compiles,
         token_counts_exact=counts_ok)
    note(phase="window", attempted=attempted, failed=failed,
         errors=sorted({o.error for o in ctx["outcomes"] if o.error})[:5],
         client_lag_p95_ms=client.percentile(lags, 95) if lags else None,
         lead_in_requests=sum(1 for o in ctx["outcomes"] if o.due < ctx["t_open"]),
         late_over_100ms=[round(o.due - ctx["t_open"], 2) for o in ctx["outcomes"] if o.lag_s > 0.1][:40],
         trace=trace_report and {k: round(v - trace_report["start_unix"], 3)
                                 for k, v in trace_report.items() if k.endswith("_unix")},
         trace_started_s=ctx.get("trace_started_s"),
         end_to_end=values, xla_cache_after=ctx["ready1"].get("xla_cache"))

    device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
              "memory_peak_bytes": int(probe.sample(ctx["m1"], "dynamo_engine_hbm_bytes", kind="peak") or 0)}
    result = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    if not trace:
        wanted = files.metrics_for("end_to_end", cell["name"])
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            raise BenchError(f"no value for {missing}: no request completed in the window")
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]} for m in wanted}
    else:
        reduced = run_reduction(srv, work)
        peaks = json.loads((HERE / "peaks.json").read_text())
        if dev["kind"] not in peaks and dev["platform"] == "tpu":
            raise BenchError(f"device kind {dev['kind']!r} is not in peaks.json")
        ctx.update(trace=reduced, peaks=peaks.get(dev["kind"]), device=dev, config=conf,
                   end_to_end=values, trace_report=trace_report)
        result["metrics"] = {}
        for m in files.metrics_for("per_layer", cell["name"]):
            value = files.module("layer_metrics", reader_of(m["name"])).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        if reduced["busy_s"] <= 0 and dev["platform"] == "tpu":
            raise BenchError("no operation ran on the device in the traced window")
    result["device"] = device
    # each number compared beside its limit, as the last lines of stderr too
    print(f"compared: logprob_worst_abs_diff {worst:.6g} (limit {atol:g}) over {compared} logprobs\n"
          f"compared: compiles_in_window {compiles:g} (limit 0)\n"
          f"compared: token_counts_exact {counts_ok} (must be True), attempted {attempted} (must be over 0)\n"
          f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def run_sweep(files: Files, args) -> int:
    """One set-up, rates rising by a quarter each step: the table for
    PERF.md and the knee, the highest step of the unbroken run of sustained
    steps from the first (see `sweep_table`)."""
    cell = files.cell(args.sweep)
    steps = [(args.sweep_start * 1.25 ** i, args.sweep_step_s) for i in range(args.sweep_steps)]
    args.seconds = sum(d for _, d in steps)
    ctx = serve_and_measure(files, args, cell, False, steps=steps)["ctx"]
    table = sweep_table(ctx, steps)
    for row in table:
        note(**row)
    knee = None
    for row in table:
        if not row["sustained"]:
            break
        knee = row["rate_rps"]
    note(phase="sweep", knee_rps=knee, cell_rate_rps=None if knee is None else round(0.8 * knee, 3),
         setup_s=ctx["t_open"] - T_PROCESS_START)
    return 0


def delivered_tokens(outs: list, lo: float, hi: float) -> float:
    """Output tokens streamed in [lo, hi): a request's first token at `first`,
    the rest evenly from `first` to `last`."""
    total = 0.0
    for o in outs:
        if not o.ok or o.first is None:
            continue
        total += 1.0 if lo <= o.first < hi else 0.0
        if o.output_tokens > 1 and o.last > o.first:
            overlap = max(0.0, min(hi, o.last) - max(lo, o.first))
            total += (o.output_tokens - 1) * overlap / (o.last - o.first)
    return total


def sweep_table(ctx: dict, steps: list) -> list:
    """Per step: output tokens/s offered (asked by the requests due in it)
    and delivered in its second half (when the step before has drained),
    requests in flight at its middle and end, latencies. A step is sustained
    when every request succeeded, the delivered rate is within 5% of the
    offered one, and the requests in flight at its end are no more than 1.2
    times those at its middle (4 more are not counted as growth). Tokens of
    requests COMPLETED in the step, as ISSUE 24 worded it, lag the offer by a
    request's length (5 s of a 20 s step) and fail every rising step."""
    rows, at = [], ctx["t_open"]
    outs = ctx["outcomes"]
    for rate, dur in steps:
        lo, hi, mid = at, at + dur, at + dur / 2
        mine = [o for o in outs if lo <= o.due < hi]
        offered = sum(o.asked_tokens for o in mine) / dur
        delivered = delivered_tokens(outs, mid, hi) / (hi - mid)

        def in_flight(t):
            return sum(1 for o in outs if o.sent and o.sent <= t and (o.done is None or o.done > t))

        fm, fe = in_flight(mid), in_flight(hi)
        good = [o for o in mine if o.ok]
        ttft = [o.ttft_s * 1e3 for o in good]
        tpot = [o.tpot_s * 1e3 for o in good if o.tpot_s is not None]
        rows.append({
            "rate_rps": round(rate, 3), "requests": len(mine), "failed": len(mine) - len(good),
            "offered_tokens_per_s": round(offered, 1), "delivered_tokens_per_s": round(delivered, 1),
            "in_flight_mid": fm, "in_flight_end": fe,
            "ttft_p50_ms": round(client.percentile(ttft, 50), 1) if ttft else None,
            "ttft_p95_ms": round(client.percentile(ttft, 95), 1) if ttft else None,
            "tpot_p95_ms": round(client.percentile(tpot, 95), 2) if tpot else None,
            "lag_p95_ms": round(client.percentile([o.lag_s * 1e3 for o in mine], 95), 2) if mine else None,
            "sustained": bool(good) and len(good) == len(mine)
            and delivered >= 0.95 * offered and fe <= max(1.2 * fm, fm + 4),
        })
        at = hi
    return rows


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", help="a cell's name: step its rate up and print the table")
    ap.add_argument("--sweep-start", type=float, default=2.0)
    ap.add_argument("--sweep-steps", type=int, default=8)
    ap.add_argument("--sweep-step-s", type=float, default=30.0)
    ap.add_argument("--root", type=Path, default=HERE.parent,
                    help="where BENCHMARK.json is (a test's or a later PR's own tree)")
    args = ap.parse_args(argv)
    # ended from outside (a time limit): unwind, so that the server is stopped
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        files = Files(args.root.resolve())
        if args.sweep:
            return run_sweep(files, args)
        if not args.workload:
            ap.error("--workload or --sweep")
        if args.seconds is None:
            args.seconds = float(files.spec["run_seconds"])
        return run_cell(files, args)
    except BenchError as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
