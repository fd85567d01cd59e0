#!/usr/bin/env python3
"""Past the window, through the served path: what the harness's probes (48-300
tokens, `run.py` `PROBE_LENGTHS`) never reach. By hand, through `chiprun`, with
a configuration's name; not a tier-1 test.

    chiprun --timeout 1500 -- python3 benchmark/tests/chip_long_probe.py command-a-plus-ep8 --serve
    python3 benchmark/tests/chip_long_probe.py command-a-plus-ep8 --compare     (any machine)

`--serve` makes the configuration's checkpoint from `--seed`, starts the
server as `run.py` does (same launcher, same arguments), and sends prompts of
`--lengths` tokens (6144 and 12288: 1.5 and 3 windows of 4096) asking for 8
greedy tokens with their logprobs: each COLD (chunked prefill through the
window, pages behind it given back on the way), then AGAIN with a fresh tail
of 40 tokens appended to prompt and answer (a prefix hit at depth: the window
group has to hold the blocks behind the match, else the match is refused and
`dynamo_engine_prefix_cache_refused_total` says so). It writes
`chiprun_out/long_probe/<config>.json`: the token sequences, the server's
logprobs, the counters.

`--compare` (no chip: the plain reference in blocks, minutes of CPU) makes the
same checkpoint if it is not there, computes the reference's logprobs of the
same tokens, and prints the worst |server - reference| a probe beside the
configuration's `logprob_atol`; and the CONTROL: the 12k probe by the
reference with the window's mask taken out, against the reference itself,
which has to differ by more than the tolerance (else nothing served can see
the mask at these weights, and the CPU tests at a window of 32 carry it).
Exit code 0 where every probe is inside the tolerance and the control outside.
Without either flag it does both.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import checkpoint  # noqa: E402
import client  # noqa: E402
import probe  # noqa: E402
import run  # noqa: E402
from generators import _draw  # noqa: E402

TAIL = 40


def serve(files, name: str, seed: int, lengths: list, out: Path) -> dict:
    conf = files.config_file(name)
    b = conf["benchmark"]
    hf = {k: v for k, v in conf.items() if k not in run.OWN_KEYS}
    cache = BENCH / ".cache"
    work = cache / "work" / f"{name}.long-probe"
    work.mkdir(parents=True, exist_ok=True)
    ckpt, *_ = checkpoint.ensure_checkpoint(cache, name, hf, seed, files.module("checkpoints", b["checkpoint"]))
    srv = run.Server(files, conf, ckpt, work, trace=False)
    vocab = conf["vocab_size"]
    try:
        ready = probe.wait_ready(srv.base, srv.proc, float(b.get("ready_timeout_s", 1100)))
        model = ready["models"][0]

        async def ask(session, prompt):
            o = await client.complete(session, srv.base, model, prompt, run.PROBE_TOKENS,
                                      time.monotonic(), logprobs=True)
            if not o.ok or len(o.logprobs) != run.PROBE_TOKENS:
                raise run.BenchError(f"long probe failed: {o.error or o.logprobs}")
            chosen = [checkpoint.token_id_of(tok) for tok, _ in o.logprobs]
            return {"tokens": prompt + chosen, "prompt_len": len(prompt),
                    "server_logprobs": [lp for _, lp in o.logprobs], "ttft_s": o.ttft_s}

        async def body():
            import aiohttp

            probes = []
            async with aiohttp.ClientSession(timeout=aiohttp.ClientTimeout(total=1200)) as s:
                for i, n in enumerate(lengths):
                    cold = await ask(s, _draw.token_ids(seed, 810_000 + i, n, vocab))
                    m0 = probe.scrape(srv.base)
                    hit = await ask(s, cold["tokens"] + _draw.token_ids(seed, 820_000 + i, TAIL, vocab))
                    m1 = probe.scrape(srv.base)
                    for key in ("hit", "miss"):
                        hit[f"blocks_{key}"] = \
                            (probe.sample(m1, "dynamo_engine_prefix_cache_blocks_total", result=key) or 0) - \
                            (probe.sample(m0, "dynamo_engine_prefix_cache_blocks_total", result=key) or 0)
                    probes += [dict(cold, kind=f"cold-{n}"), dict(hit, kind=f"hit-{n}")]
            return probes, probe.scrape(srv.base)

        probes, m = asyncio.run(body())
        counters = {k: probe.sample(m, k) for k in (
            "dynamo_engine_prefix_cache_refused_total", "dynamo_engine_kv_window_pages_released_total",
            "dynamo_engine_preemptions_total")}
    finally:
        srv.stop()
    doc = {"config": name, "seed": seed, "device": ready.get("device"), "probes": probes, "counters": counters}
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(doc))
    for p in probes:
        print(json.dumps({k: p[k] for k in p if k not in ("tokens", "server_logprobs")}), flush=True)
    print(json.dumps({"counters": counters, "device": ready.get("device")}), flush=True)
    return doc


def compare(files, name: str, doc: dict, control: bool = True) -> int:
    conf = files.config_file(name)
    b = conf["benchmark"]
    atol = float(b["logprob_atol"])
    hf = {k: v for k, v in conf.items() if k not in run.OWN_KEYS}
    ckpt, *_ = checkpoint.ensure_checkpoint(BENCH / ".cache", name, hf, doc["seed"],
                                            files.module("checkpoints", b["checkpoint"]))
    ref = files.module("reference", b["reference"])
    asked = [{"tokens": p["tokens"], "prompt_len": p["prompt_len"]} for p in doc["probes"]]
    t0 = time.monotonic()
    want = ref.teacher_forced_logprobs(ckpt, asked)
    ok = True
    for p, w in zip(doc["probes"], want):
        worst = max(abs(a - r) for a, r in zip(p["server_logprobs"], w))
        ok = ok and worst <= atol
        print(json.dumps({"probe": p["kind"], "context": len(p["tokens"]), "blocks_hit": p.get("blocks_hit"),
                          "worst_abs_diff": worst, "tolerance": atol, "inside": worst <= atol}), flush=True)
    if not control:
        return 0 if ok else 1
    longest = max(range(len(asked)), key=lambda i: len(asked[i]["tokens"]))
    unmasked = ref.teacher_forced_logprobs(ckpt, [asked[longest]], {"window": False})[0]
    moved = max(abs(a - r) for a, r in zip(unmasked, want[longest]))
    print(json.dumps({"control": "reference without the window's mask", "context": len(asked[longest]["tokens"]),
                      "worst_abs_diff": moved, "tolerance": atol, "outside": moved > atol,
                      "reference_seconds": round(time.monotonic() - t0, 1)}), flush=True)
    return 0 if ok and moved > atol else 1


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("config")
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--lengths", default="6144,12288")
    ap.add_argument("--serve", action="store_true")
    ap.add_argument("--compare", action="store_true")
    ap.add_argument("--skip-control", action="store_true",
                    help="--compare without the pass that takes the window's mask out (nine minutes at 12k)")
    ap.add_argument("--root", type=Path, default=BENCH.parent)
    args = ap.parse_args(argv)
    files = run.Files(args.root.resolve())
    out = BENCH.parent / "chiprun_out" / "long_probe" / f"{args.config}.json"
    try:
        if args.serve or not args.compare:
            doc = serve(files, args.config, args.seed, [int(n) for n in args.lengths.split(",")], out)
        else:
            doc = json.loads(out.read_text())
        if args.compare or not args.serve:
            return compare(files, args.config, doc, control=not args.skip_control)
        return 0
    except run.BenchError as e:
        print(f"chip_long_probe: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
