#!/usr/bin/env python3
"""From a profiler trace (`*.xplane.pb`) to the device's time by PART of the
model: the third reduction, beside `trace_reduce.py` (operations by name) and
`trace_steps.py` (runs by step, the engine thread).

The program names the parts of a step with `jax.named_scope`, from a closed
vocabulary (`PARTS`). A scope is debug information: it reaches the compiled
module as each instruction's `metadata.op_name`
(`jit(dynamo_decode_window)/step/while/body/attn_proj/dot_general`) and is in
neither the name nor the stats of an `XLA Ops` event. But the trace carries
the compiled modules themselves: the plane `/host:metadata` (which
`jax.profiler.ProfileData` shows with no lines) holds one `event_metadata`
entry per module that ran, named as the `XLA Modules` events are
(`jit_dynamo_prefill_packed(10348406563518328954)`: module name and program
fingerprint), with a stat `Hlo Proto` whose bytes are an `xla.HloProto`. So:

  the map   module -> {instruction -> part}: the INNERMOST vocabulary name in
            the instruction's `op_name` path; none, or no `op_name`: `unnamed`.
            A fusion's part is the fusion instruction's own `op_name`. Only
            a fusion that has none (the chip's compiler gives a multi-output
            fusion a tuple for its root, and no metadata) takes the name that
            most instructions of its body carry; `body_named_s` says how
            many seconds were named that way.
  the join  each leaf event of a device plane's `XLA Ops` line (the leaf rule
            of `trace_reduce.reduce_events`) lies inside one run on the
            `XLA Modules` line; the run's full name is the key into the map,
            so two variants of one step that both own a `fusion.204` do not
            mix. The first and the last run of a line may be clipped by the
            trace's edges: they count in every share and in nothing that is
            divided by a number of runs or steps.

The `Hlo Proto` is read from the wire format, with no protobuf class (the
classes that could read it ship only with tensorflow, whose import alone
takes 7 s of a reduction that has 20). A message is a sequence of
(field number << 3 | wire type) keys; type 0 is a varint, 2 a length and that
many bytes, 1 and 5 fixed 8 and 4 bytes. The fields walked, numbers from the
`.proto` files of tensorflow 2.x / xla as installed here:

  XSpace.planes = 1;  XPlane.name = 2, .event_metadata = 4 (map entries:
  key = 1, value = 2), .stat_metadata = 5 (the same);  XEventMetadata.name = 2,
  .stats = 5;  XStatMetadata.name = 2;  XStat.metadata_id = 1,
  .bytes_value = 6;  HloProto.hlo_module = 1;  HloModuleProto.name = 1,
  .computations = 3;  HloComputationProto.name = 1, .instructions = 2;
  HloComputationProto.id = 5;  HloInstructionProto.name = 1, .opcode = 2,
  .metadata = 7, .called_computation_ids = 38;  OpMetadata.op_name = 2.

What `reduce` writes (plain arithmetic on what `load` returns, and what
`benchmark/tests` hold to a recorded slice of a chip trace):

  by_step_part   {step label: {part: seconds}} over every leaf event, averaged
                 over the chips; the label is `trace_steps.STEP`'s, the whole
                 module name where a run is no `dynamo_` step
  ops_by_part    {part: {operation: seconds}}, operations by
                 `trace_reduce.base_name` (a kernel keeps its own name beside
                 its part, so `attn` can be told from `paged_decode_attention_*`)
  decode         the decode-window runs lying whole inside the trace: runs,
                 steps (the sum of their `k`, from the dispatch spans),
                 seconds, seconds_by_part
  prefill        the (dispatch span, module run) pairs, by `trace_steps.pair`'s
                 rule (`align`), whose run lies whole inside the trace, each with the span's
                 `rows`, `lanes`, `padded`, `ctx` and the run's device
                 seconds; and over ALL prefill dispatch spans of the trace the
                 sums of `rows` and `padded`
  no_module_s    leaf seconds inside no run, or inside a run whose module has
                 no entry in the map
  body_named_s   leaf seconds of fusions named by their body (see above)
  unnamed_top    the twenty largest `unnamed` instructions: module,
                 instruction, opcode, op_name, seconds
  leaf_s, busy_s, window_s, planes, modules_mapped

A trace without the plane, or a program without the scopes or the span stats
(the parent of the PR that added them), gives `unnamed` seconds, empty
tables and None where a number is missing; the readers then report nothing.

    JAX_PLATFORMS=cpu python benchmark/trace_parts.py TRACE.xplane.pb OUT.json [EVENTS.json]
"""

from __future__ import annotations

import bisect
import json
import re
import sys
import time
from pathlib import Path

import trace_reduce
import trace_steps
from trace_reduce import base_name, short_name
from trace_steps import DECODE, DEVICE_PLANE, MODULES_LINE, OPS_LINE, PREFILL, STEP

#: the closed vocabulary of parts; the program opens each as a
#: `jax.named_scope` of the same name (PERF.md section 3 says where)
PARTS = ("embed", "norm", "attn_proj", "attn_kv", "attn", "mlp", "ssm_proj", "ssm",
         "moe_router", "moe_dispatch", "moe_experts", "shared_experts", "lm_head",
         "sample", "step")
UNNAMED = "unnamed"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
TOP_UNNAMED = 20
_WRAPPED = re.compile(r"(\w+)\((.*)\)")


def part_of(op_name: str) -> str:
    """The innermost vocabulary name in an `op_name` path. A component may
    be wrapped by a transform (`vmap(mlp)`); `jit(norm)` is a function's
    name and no scope."""
    for comp in reversed(op_name.split("/")):
        while (m := _WRAPPED.fullmatch(comp)) is not None:
            if m.group(1) in ("jit", "pjit"):
                comp = ""
                break
            comp = m.group(2)
        if comp in PARTS:
            return comp
    return UNNAMED


# ---------------------------------------------------------------- the wire format


def _varint(buf, i: int) -> tuple:
    """(the varint that starts at byte `i`, the index after it)."""
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for length-delimited bytes; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield key >> 3, 0, v
        elif wt == 2:
            ln, i = _varint(buf, i)
            yield key >> 3, 2, buf[i:i + ln]
            i += ln
        elif wt == 1:
            i += 8
        elif wt == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _varints(value) -> list:
    """A repeated integer field's values: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


def hlo_instructions(hlo_proto) -> dict:
    """{instruction name: (opcode, op_name, body part)} of an `xla.HloProto`.
    The body part is "" but for a fusion that has no `op_name` of its own (a
    multi-output fusion's root is a tuple the compiler made): then it is the
    vocabulary name that most instructions of the fused computation carry."""
    out, calls, bodies = {}, {}, {}
    for f, wt, module in fields(hlo_proto):
        if f != 1 or wt != 2:
            continue
        for f, wt, comp in fields(module):
            if f != 3 or wt != 2:
                continue
            comp_id, inside = None, []
            for f, wt, inst in fields(comp):
                if f == 5 and wt == 0:
                    comp_id = inst
                if f != 2 or wt != 2:
                    continue
                name = opcode = op_name = ""
                called = []
                for f, wt, v in fields(inst):
                    if f == 38:
                        called += _varints(v)
                    elif wt != 2:
                        continue
                    elif f == 1:
                        name = _text(v)
                    elif f == 2:
                        opcode = _text(v)
                    elif f == 7:
                        for f2, wt2, v2 in fields(v):
                            if f2 == 2 and wt2 == 2:
                                op_name = _text(v2)
                out[name] = (opcode, op_name, "")
                inside.append(op_name)
                if opcode == "fusion" and not op_name and called:
                    calls[name] = called[0]
            bodies[comp_id] = inside
    for name, comp_id in calls.items():
        votes: dict = {}
        for op_name in bodies.get(comp_id, ()):
            part = part_of(op_name)
            if part != UNNAMED:
                votes[part] = votes.get(part, 0) + 1
        if votes:
            out[name] = (out[name][0], "", max(votes, key=votes.get))
    return out


def part_in(meta: tuple) -> str:
    """The part of one entry of `hlo_instructions`."""
    return part_of(meta[1]) if meta[1] else meta[2] or UNNAMED


def _map_entries(plane, field: int):
    """The values of a `map<int64, Message>` field: (key, message bytes)."""
    for f, wt, entry in fields(plane):
        if f != field or wt != 2:
            continue
        key, value = 0, None
        for f2, wt2, v in fields(entry):
            if f2 == 1 and wt2 == 0:
                key = v
            elif f2 == 2 and wt2 == 2:
                value = v
        if value is not None:
            yield key, value


def load_modules(path: str) -> dict:
    """{module name as on the `XLA Modules` line: {instruction: (opcode,
    op_name, body part)}} from the trace's `/host:metadata` plane; empty
    where the trace has none."""
    space = memoryview(Path(path).read_bytes())
    out = {}
    for f, wt, plane in fields(space):
        if f != 1 or wt != 2:
            continue
        if not any(f2 == 2 and wt2 == 2 and _text(v) == METADATA_PLANE for f2, wt2, v in fields(plane)):
            continue
        stat_ids = {key for key, meta in _map_entries(plane, 5)
                    if any(f2 == 2 and wt2 == 2 and _text(v) == HLO_STAT for f2, wt2, v in fields(meta))}
        for _, meta in _map_entries(plane, 4):
            name, proto = "", None
            for f2, wt2, v in fields(meta):
                if f2 == 2 and wt2 == 2:
                    name = _text(v)
                elif f2 == 5 and wt2 == 2:
                    stat = {f3: v3 for f3, _, v3 in fields(v)}
                    if stat.get(1) in stat_ids and 6 in stat:
                        proto = stat[6]
            if name and proto is not None:
                out[name] = hlo_instructions(proto)
    return out


# ---------------------------------------------------------------- the events


def load(path: str) -> dict:
    """{"modules": {plane: [(full module name, start_ns, dur_ns)]},
        "ops": {plane: [(short name, start_ns, dur_ns)]},
        "host": the engine thread's spans, as `trace_steps.load` gives them,
        "map": {module name: {instruction: (opcode, op_name, body part)}}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {"modules": {}, "ops": {}, "host": [], "map": load_modules(path)}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for ln in plane.lines:
                if ln.name == MODULES_LINE:
                    out["modules"][plane.name] = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                                                  for ev in ln.events]
                elif ln.name == OPS_LINE:
                    out["ops"][plane.name] = [(short_name(ev.name), int(ev.start_ns), int(ev.duration_ns))
                                              for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [ev for ev in ln.events if ev.name.startswith("engine.")]
                if any(ev.name == "engine.step" for ev in evs):
                    out["host"] += [(ev.name, int(ev.start_ns), int(ev.duration_ns),
                                     {k: v for k, v in ev.stats if isinstance(v, (int, float, str))})
                                    for ev in evs]
    out["host"].sort(key=lambda e: (e[1], -e[2]))
    return out


def step_label(module: str) -> str:
    m = STEP.search(module)
    return m.group(1) if m else module


def leaves(evs: list) -> list:
    """The events in which nothing starts: `trace_reduce.reduce_events`'s
    rule (a while loop and the operations inside it share time)."""
    spans = sorted((s, s + d, name) for name, s, d in evs)
    return [(s, e, name) for i, (s, e, name) in enumerate(spans)
            if not (i + 1 < len(spans) and spans[i + 1][0] < e and spans[i + 1][1] <= e)]


def align(spans: list, runs: list, rec_start: dict) -> list:
    """[(span stats, run)] for one step's dispatch spans [(start, end, stats)]
    and runs [(start, end)], both in time order: `trace_steps.pair`'s rule
    (the largest offset that keeps every run after its span's start and
    before the reconcile of its `seq`); no such offset: no pairs."""
    for k in range(len(runs) - 1, -1, -1):
        both = list(zip(spans, runs[k:]))
        if any(st.get("seq") in rec_start for _, _, st in spans[len(both):]):
            continue
        if both and all(ms >= ss and me <= rec_start.get(st.get("seq"), me)
                        for (ss, _, st), (ms, me) in both):
            return [(st, run) for (_, _, st), run in both]
    return []


def reduce(ev: dict) -> dict:
    planes = [p for p, evs in ev["ops"].items() if evs]
    n = max(1, len(planes))
    # module -> instruction -> (part, whether a fusion's body named it)
    parts_of = {mod: {inst: (part_in(meta), bool(not meta[1] and meta[2])) for inst, meta in insts.items()}
                for mod, insts in ev["map"].items()}
    by_step_part: dict = {}
    ops_by_part: dict = {}
    unnamed: dict = {}
    run_parts: dict = {}  # (plane, run start) -> {part: seconds}, for whole-run numbers
    runs_of: dict = {}
    leaf_s = no_module_s = body_named_s = busy = window = 0.0
    for p in planes:
        runs = runs_of[p] = sorted((s, s + d, name) for name, s, d in ev["modules"].get(p, []))
        starts = [r[0] for r in runs]
        lv = leaves(ev["ops"][p])
        every = [(s, s + d) for _, s, d in ev["ops"][p]]  # as `trace_reduce`: the loops too
        busy += trace_reduce.union_ns(every) / 1e9
        window = max(window, (max(e for _, e in every) - min(s for s, _ in every)) / 1e9)
        for s, e, name in lv:
            secs = (e - s) / 1e9
            leaf_s += secs
            i = bisect.bisect_right(starts, s) - 1
            run = runs[i] if i >= 0 and e <= runs[i][1] else None
            parts = parts_of.get(run[2]) if run else None
            if parts is None:
                no_module_s += secs
                continue
            inst = name.split(" ")[0]
            part, by_body = parts.get(inst, (UNNAMED, False))
            if by_body:
                body_named_s += secs
            label = step_label(run[2])
            by_step_part.setdefault(label, {})
            by_step_part[label][part] = by_step_part[label].get(part, 0.0) + secs / n
            ops = ops_by_part.setdefault(part, {})
            ops[base_name(name)] = ops.get(base_name(name), 0.0) + secs / n
            acc = run_parts.setdefault((p, run[0]), {})
            acc[part] = acc.get(part, 0.0) + secs
            if part == UNNAMED:
                key = (run[2], inst)
                unnamed[key] = unnamed.get(key, 0.0) + secs / n

    host = ev["host"]
    rec_start: dict = {}
    for name, s, _, st in host:
        if name.endswith(".reconcile") and "seq" in st:
            rec_start.setdefault(st["seq"], s)
    decode = prefill = None
    fill = {"rows": 0, "padded": 0, "spans": 0}
    for name, _, _, st in host:
        if name in PREFILL and "rows" in st and "padded" in st:
            fill["rows"] += st["rows"]
            fill["padded"] += st["padded"]
            fill["spans"] += 1
    if planes and host:
        # pairs are taken on one chip's line, as `trace_steps.reduce` takes them
        p = planes[0]
        runs = runs_of[p]
        whole = {r[0] for r in runs[1:-1]}  # the first and the last may be clipped
        dspans = [(s, s + d, st) for name, s, d, st in host if name == "engine.decode_window.dispatch"]
        druns = [(s, e) for s, e, name in runs if step_label(name) == DECODE]
        got = [(st, run) for st, run in align(dspans, druns, rec_start)
               if run[0] in whole and "k" in st and (p, run[0]) in run_parts]
        if got:
            by_part: dict = {}
            for _, run in got:
                for part, secs in run_parts[(p, run[0])].items():
                    by_part[part] = by_part.get(part, 0.0) + secs
            decode = {"runs": len(got), "steps": sum(st["k"] for st, _ in got),
                      "seconds": sum(e - s for _, (s, e) in got) / 1e9, "seconds_by_part": by_part}
        pairs = []
        for span_name, label in PREFILL.items():
            spans = [(s, s + d, st) for name, s, d, st in host if name == span_name]
            pruns = [(s, e) for s, e, name in runs if step_label(name) == label]
            for st, (s, e) in align(spans, pruns, rec_start):
                if s in whole and all(k in st for k in ("rows", "padded", "ctx")):
                    pairs.append({"seq": st.get("seq"), "step": label, "rows": st["rows"],
                                  "lanes": st.get("lanes", 1), "padded": st["padded"],
                                  "ctx": st["ctx"], "device_s": (e - s) / 1e9})
        prefill = {"pairs": pairs}
    top = [(mod, inst, secs, ev["map"].get(mod, {}).get(inst, ("", "", "")))
           for (mod, inst), secs in sorted(unnamed.items(), key=lambda kv: -kv[1])[:TOP_UNNAMED]]
    return {
        "planes": len(planes), "modules_mapped": len(parts_of),
        "by_step_part": by_step_part, "ops_by_part": ops_by_part,
        "decode": decode, "prefill": prefill, "fill": fill,
        "no_module_s": no_module_s / n, "body_named_s": body_named_s / n, "leaf_s": leaf_s / n, "busy_s": busy / n, "window_s": window,
        "unnamed_top": [{"module": mod, "instruction": inst, "seconds": secs, "opcode": meta[0], "op_name": meta[1]}
                        for mod, inst, secs, meta in top],
    }


def main(argv: list) -> int:
    t0 = time.monotonic()
    ev = load(argv[0])
    out = reduce(ev)
    out["seconds"] = time.monotonic() - t0
    Path(argv[1]).write_text(json.dumps(out))
    if len(argv) > 2:  # the raw events and the map, for reading by hand and for fixtures
        Path(argv[2]).write_text(json.dumps(ev))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
