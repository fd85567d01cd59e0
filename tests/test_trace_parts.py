"""The split of a trace by part of the model (ISSUE 39): the program's
`jax.named_scope`s reach every instruction of the step programs, a profiler
trace carries the compiled modules with them, `benchmark/trace_parts.py`
joins operations to parts, and the prefill dispatch spans say what a pack
holds. CPU, tiny sizes: the CPU's fusions differ from the chip's, the
metadata on them does not."""

import importlib.util
import re
import sys
import zlib
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "benchmark"


def _bench_module(name):
    if str(BENCH) not in sys.path:  # trace_parts imports its two siblings by name
        sys.path.insert(0, str(BENCH))
    spec = importlib.util.spec_from_file_location(name, BENCH / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_parts = _bench_module("trace_parts")

# ---------------- (a) every instruction of a step program has its part ----------------

#: what the compiler makes itself and gives no `op_name`
COMPILERS_OWN = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast", "copy"}
#: a step's own code moves positions, tables and tokens: a product or a
#: kernel whose innermost name is `step` is a block somebody left unscoped
NOT_A_STEPS_OWN = re.compile(r"dot_general|conv_general|ragged_dot|pallas_call|custom_call|cumsum|cumlogsumexp|sort")
SHARED = {"embed", "norm", "attn_proj", "attn_kv", "attn", "lm_head", "sample", "step"}
MODELS = {
    "tiny": SHARED | {"mlp"},
    "tiny-hybrid": SHARED | {"ssm_proj", "ssm", "moe_router", "moe_dispatch", "moe_experts", "shared_experts"},
    "tiny-window": SHARED | {"moe_router", "moe_dispatch", "moe_experts", "shared_experts"},
    "tiny-conv": SHARED | {"mlp", "ssm_proj", "ssm", "moe_router", "moe_dispatch", "moe_experts"},
    "tiny-parallel": SHARED | {"mlp", "ssm_proj", "ssm"},
}
STEPS = {"decode_window": "_decode_window", "prefill_packed": "_prefill_packed"}

_NAME = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def top_level_instructions(hlo_text):
    """[(name, opcode, op_name, the op_names inside a fusion's body)] of the
    entry computation and of what it runs as a program (loop bodies and
    conditions, called computations): every computation no fusion or
    reducer refers to."""
    comps, inner, cur, calls = {}, set(), None, {}
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            cur = m.group(1)
            comps[cur] = []
            continue
        m = _NAME.match(line)
        if m and cur is not None:
            opcode = _OPCODE.search(m.group(2))
            op_name = _OP_NAME.search(line)
            comps[cur].append((m.group(1), opcode.group(1) if opcode else "",
                               op_name.group(1) if op_name else ""))
            if " fusion(" in line or "to_apply=" in line or " custom-call(" in line:
                # a fusion's body, a reducer, a custom call's comparator
                refs = re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line) \
                    + re.findall(r"%?([\w.\-]+)", "".join(re.findall(r"called_computations=\{([^}]*)\}", line)))
                inner.update(refs)
                calls[m.group(1)] = refs
    return [inst + ([op for ref in calls.get(inst[0], ()) for _, _, op in comps.get(ref, ()) if op],)
            for name, insts in comps.items() if name not in inner for inst in insts]


def _request(rid, n_prompt, max_tokens=12):
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    rng = np.random.default_rng(zlib.crc32(rid.encode()))
    return EngineRequest(
        request_id=rid, token_ids=rng.integers(1, 200, n_prompt).tolist(),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens, ignore_eos=True))


def _hand_engine(**over):
    """A tiny engine whose scheduler the test steps itself."""
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    base = dict(model_id="tiny", page_size=4, num_pages=256, max_seqs=4, max_model_len=96,
                prefill_buckets=(8, 16, 32), prefill_lanes=2, decode_steps=4)
    eng = AsyncJaxEngine(EngineConfig(**{**base, **over}))
    eng._initialize()
    return eng


@pytest.fixture(scope="module", params=sorted(MODELS))
def step_programs(request):
    """{step label: the top-level instructions of the program the engine
    ran, each with its part}, for one tiny model: the calls are caught on their way to the jitted step
    and lowered again from their shapes."""
    import jax

    eng = _hand_engine(model_id=request.param)
    runner, calls = eng.runner, {}

    def catch(label, attr):
        real = getattr(runner, attr)

        def spy(*args, **kwargs):
            shapes = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x,
                (args, kwargs))
            calls.setdefault(label, (real._fn, shapes))
            return real(*args, **kwargs)

        setattr(runner, attr, spy)

    for label, attr in STEPS.items():
        catch(label, attr)
    sched = eng.scheduler
    for rid in ("a", "b"):
        sched.add_request(_request(rid, 12))
    for _ in range(6):
        sched.step()
    assert set(calls) == set(STEPS)
    return request.param, {label: _compiled(fn.lower(*shapes[0], **shapes[1]).compile())
                           for label, (fn, shapes) in calls.items()}


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _compiled(compiled):
    """[(name, opcode, op_name, part, op_names in its body)] of a compiled program's top-level
    instructions: which they are from its text, their parts from its module
    as the trace carries it (an `HloProto` around the serialized module),
    through the reduction's own reader."""
    module = compiled.runtime_executable().hlo_modules()[0].as_serialized_hlo_module_proto()
    insts = trace_parts.hlo_instructions(memoryview(b"\x0a" + _varint(len(module)) + module))
    return [(name, opcode, op, trace_parts.part_in(insts[name]), inside)
            for name, opcode, op, inside in top_level_instructions(compiled.as_text())]


@pytest.mark.parametrize("label", sorted(STEPS))
def test_every_instruction_of_a_step_program_has_its_part(label, step_programs):
    model, programs = step_programs
    insts = programs[label]
    under = [i[:4] for i in insts if f"jit(dynamo_{label})" in i[2]]
    assert len(under) > 20, "the step program's instructions were not found"
    unnamed = [(name, op) for name, _, op, part in under if part == trace_parts.UNNAMED]
    assert not unnamed, f"no part of the vocabulary in: {unnamed[:5]}"
    # no op_name at all: the compiler's own opcodes, a fusion that took its
    # body's part, or a fusion of an expansion the compiler made (a cumsum's
    # reduce-windows), whose body carries no op_name either
    bare = [(name, opcode) for name, opcode, op, part, inside in insts
            if not op and part == trace_parts.UNNAMED and opcode not in COMPILERS_OWN
            and not (opcode == "fusion" and not inside)]
    assert not bare, f"instructions with no op_name that are not the compiler's own: {bare[:5]}"
    seen = {part for _, _, _, part in under}
    assert MODELS[model] <= seen, f"{model} {label}: no instruction of {sorted(MODELS[model] - seen)}"
    assert seen <= set(trace_parts.PARTS)
    unscoped = [(name, op) for name, _, op, part in under
                if part == "step" and NOT_A_STEPS_OWN.search(op.rsplit("/", 1)[-1])]
    assert not unscoped, f"a product or kernel under `step` alone (a block without a scope?): {unscoped[:5]}"


@pytest.mark.parametrize("op_name, part", [
    ("jit(dynamo_decode_window)/step/while/body/attn_proj/dot_general", "attn_proj"),
    ("jit(dynamo_prefill_packed)/step/attn_window/attn_kv/scatter", "attn_kv"),  # the outer scope is no part
    ("jit(f)/step/moe_dispatch/moe_experts/moe_experts/jit(grouped_matmul_pallas)/pallas_call", "moe_experts"),
    ("jit(f)/step/lm_head/norm/mul", "norm"),  # the innermost name counts
    ("jit(f)/step/vmap(mlp)/tanh", "mlp"),  # a transform wraps the scope
    ("jit(norm)/reduce_sum", "unnamed"),  # a jitted function's name is no scope
    ("jit(f)/attn_window/add", "unnamed"),
    ("", "unnamed"),
])
def test_part_of_an_op_name(op_name, part):
    assert trace_parts.part_of(op_name) == part


# ---------------- (b) a real trace carries the modules with the scopes ----------------


def test_a_profiler_trace_carries_the_modules_and_their_scopes(tmp_path):
    """The map read from the `/host:metadata` plane of a real CPU trace names
    both scopes of a tiny jitted function, under the name its runs have."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dynamo_parts_test(x, w):
        with jax.named_scope("step"):
            with jax.named_scope("mlp"):
                h = jnp.tanh(x @ w)
            with jax.named_scope("lm_head"):
                return h @ w.T

    x = jnp.ones((16, 16))
    dynamo_parts_test(x, x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        dynamo_parts_test(x, x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (trace,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    modules = trace_parts.load_modules(str(trace))
    (name,) = [m for m in modules if "dynamo_parts_test" in m]
    assert re.fullmatch(r"jit_dynamo_parts_test\(\d+\)", name)
    insts = modules[name]
    parts = {trace_parts.part_in(meta) for meta in insts.values()}
    assert {"mlp", "lm_head"} <= parts
    dots = [meta for meta in insts.values() if meta[0] == "dot"]
    assert sorted(trace_parts.part_in(m) for m in dots) == ["lm_head", "mlp"]
    assert all(meta[1].startswith("jit(dynamo_parts_test)/step/") for meta in dots)


# ---------------- (c) the reduction's arithmetic, by hand ----------------

DEV = "/device:TPU:0"
WINDOW_A, WINDOW_B = "jit_dynamo_decode_window(11)", "jit_dynamo_decode_window(22)"
PACKED = "jit_dynamo_prefill_packed(33)"


def _meta(op_name, opcode="fusion", body=""):
    return (opcode, op_name, body)


def _hand_events():
    """Five runs on one chip. The two variants of the decode window both own
    a `fusion.204`: a projection in one, the sampler in the other. The first
    and the last run stand at the trace's edges."""
    win = "jit(dynamo_decode_window)/step/while/body/"
    the_map = {
        WINDOW_A: {"fusion.204": _meta(win + "attn_proj/dot_general"), "while": _meta(win[:-6], "while"),
                   "copy.1": _meta("", "copy"), "fusion.9": _meta("jit(dynamo_decode_window)/step/add")},
        WINDOW_B: {"fusion.204": _meta(win + "sample/sort"), "while": _meta(win[:-6], "while"),
                   "multi.1": _meta("", "fusion", "attn_kv")},
        PACKED: {"fusion.7": _meta("jit(dynamo_prefill_packed)/step/ssm/while/body/dot_general"),
                 "fusion.8": _meta("jit(dynamo_prefill_packed)/plain/add")},
    }
    modules = [(WINDOW_A, 0, 1000), (WINDOW_B, 1000, 1000), (PACKED, 2000, 500), (WINDOW_A, 2500, 1000),
               ("jit_other(44)", 3600, 100), (WINDOW_B, 3700, 300)]
    ops = [
        # run 0 (clipped: the first): a loop and two leaves inside it
        ("while", 0, 1000), ("fusion.204", 100, 400), ("copy.1", 600, 100),
        # run 1: the other variant's fusion.204 is the sampler
        ("while", 1000, 1000), ("fusion.204", 1100, 300), ("multi.1", 1500, 200),
        # run 2: the packed prefill
        ("fusion.7", 2000, 300), ("fusion.8", 2300, 100),
        # run 3: whole
        ("while", 2500, 1000), ("fusion.204", 2600, 500), ("fusion.9", 3200, 50),
        # between runs, inside no run; then a module the map has not
        ("stray", 3520, 30), ("fusion.1", 3600, 100),
        # run 5 (clipped: the last)
        ("fusion.204", 3700, 300),
    ]
    host = [("engine.step", 0, 4000, {}),
            ("engine.decode_window.dispatch", 900, 20, {"seq": 2, "k": 4}),
            ("engine.prefill_packed.dispatch", 1900, 20,
             {"seq": 3, "rows": 200, "lanes": 2, "padded": 256, "ctx": 64}),
            ("engine.decode_window.dispatch", 2400, 20, {"seq": 4, "k": 2}),
            ("engine.decode_window.dispatch", 3650, 20, {"seq": 5, "k": 4}),
            ("engine.prefill_packed.dispatch", 3950, 20,
             {"seq": 6, "rows": 100, "lanes": 1, "padded": 128, "ctx": 0}),  # its run: after the trace
            ("engine.decode_window.reconcile", 2100, 5, {"seq": 2}),
            ("engine.prefill_packed.reconcile", 2600, 5, {"seq": 3}),
            ("engine.decode_window.reconcile", 3550, 5, {"seq": 4})]
    host.sort(key=lambda e: (e[1], -e[2]))
    return {"modules": {DEV: modules}, "ops": {DEV: ops}, "host": host, "map": the_map}


def test_reduce_by_hand():
    r = trace_parts.reduce(_hand_events())
    ns = 1e-9
    win, pre = r["by_step_part"]["decode_window"], r["by_step_part"]["prefill_packed"]
    # leaves only (the loops share their children's time); the two variants'
    # `fusion.204` do not mix: 400 + 500 of projections, 300 + 300 of sampler
    assert win["attn_proj"] == pytest.approx(900 * ns) and win["sample"] == pytest.approx(600 * ns)
    assert win["step"] == pytest.approx(50 * ns)
    assert win["unnamed"] == pytest.approx(100 * ns)  # the compiler's copy has no op_name
    assert win["attn_kv"] == pytest.approx(200 * ns)  # a fusion with none takes its body's
    assert r["body_named_s"] == pytest.approx(200 * ns)
    assert pre == {"ssm": pytest.approx(300 * ns), "unnamed": pytest.approx(100 * ns)}
    assert "while" not in r["ops_by_part"].get("step", {})
    assert r["ops_by_part"]["attn_proj"] == {"fusion": pytest.approx(900 * ns)}
    # inside no run (30) and inside a run whose module the trace did not carry (100)
    assert r["no_module_s"] == pytest.approx(130 * ns)
    assert r["leaf_s"] == pytest.approx(2380 * ns) == pytest.approx(
        sum(s for by in r["by_step_part"].values() for s in by.values()) + r["no_module_s"])
    assert r["modules_mapped"] == 3 and r["planes"] == 1
    # per-step numbers: the runs at the edges are left out; the whole windows
    # are runs 1 (k=4) and 3 (k=2), paired with their dispatch spans in order
    d = r["decode"]
    assert d["runs"] == 2 and d["steps"] == 6 and d["seconds"] == pytest.approx(2000 * ns)
    assert d["seconds_by_part"] == {"sample": pytest.approx(300 * ns), "attn_kv": pytest.approx(200 * ns),
                                    "attn_proj": pytest.approx(500 * ns), "step": pytest.approx(50 * ns)}
    assert r["prefill"]["pairs"] == [{"seq": 3, "step": "prefill_packed", "rows": 200, "lanes": 2,
                                      "padded": 256, "ctx": 64, "device_s": pytest.approx(500 * ns)}]
    # fill: over every prefill dispatch span of the trace, paired or not
    assert r["fill"] == {"rows": 300, "padded": 384, "spans": 2}
    top = r["unnamed_top"]
    assert [(u["module"], u["instruction"]) for u in top] == [(WINDOW_A, "copy.1"), (PACKED, "fusion.8")]
    assert top[1]["op_name"].endswith("plain/add") and top[0]["opcode"] == "copy"


def test_reduce_without_the_map_or_the_spans():
    """A trace without the metadata plane, of a program without the span
    stats: everything is `no_module_s`, nothing is paired, nothing raises."""
    ev = _hand_events()
    ev["map"] = {}
    ev["host"] = [(n, s, d, {k: v for k, v in st.items() if k in ("seq", "rows")}) for n, s, d, st in ev["host"]]
    r = trace_parts.reduce(ev)
    assert r["by_step_part"] == {} and r["no_module_s"] == pytest.approx(r["leaf_s"])
    assert r["decode"] is None and r["prefill"] == {"pairs": []} and r["fill"]["padded"] == 0
    assert trace_parts.reduce({"modules": {}, "ops": {}, "host": [], "map": {}})["leaf_s"] == 0.0


def test_the_wire_reader_on_a_hand_made_message():
    def field(number, payload):
        if isinstance(payload, int):
            return _varint(number << 3) + _varint(payload)
        return _varint(number << 3 | 2) + _varint(len(payload)) + payload

    def instruction(name, opcode, op_name=None, calls=()):
        meta = field(7, field(1, b"type") + field(2, op_name.encode())) if op_name is not None else b""
        return field(2, field(1, name.encode()) + field(2, opcode.encode()) + meta
                     + field(35, 7) + b"".join(field(38, c) for c in calls))

    body = field(1, b"fused") + instruction("p", "parameter") \
        + instruction("a", "add", "jit(f)/step/attn_kv/add") + instruction("m", "multiply", "jit(f)/step/attn_kv/mul") \
        + instruction("c", "convert", "jit(f)/step/embed/convert") + field(5, 300)
    entry = field(1, b"main") + instruction("fusion.1", "fusion", "jit(f)/step/mlp/dot_general", calls=(300,)) \
        + instruction("multi", "fusion", calls=(300,)) + instruction("copy.2", "copy") + field(5, 301)
    proto = field(1, field(1, b"jit_f") + field(3, body) + field(3, entry))
    insts = trace_parts.hlo_instructions(memoryview(proto))
    assert insts["fusion.1"] == ("fusion", "jit(f)/step/mlp/dot_general", "")
    assert insts["multi"] == ("fusion", "", "attn_kv")  # most of its body says so
    assert insts["copy.2"] == ("copy", "", "") and trace_parts.part_in(insts["copy.2"]) == "unnamed"
    assert trace_parts.part_in(insts["multi"]) == "attn_kv" and trace_parts.part_in(insts["fusion.1"]) == "mlp"


# ---------------- (d) the prefill dispatch spans say what a pack holds ----------------


def _prefill_spans(eng, requests, steps=10):
    from dynamo_tpu.utils import tracing

    tracing.clear()
    tracing.enable()
    try:
        for r in requests:
            eng.scheduler.add_request(r)
        for _ in range(steps):
            eng.scheduler.step()
        return [e for e in tracing.events() if e["name"] == "engine.prefill"], \
            [e for e in tracing.events() if e["name"].endswith(".host_prep")]
    finally:
        tracing.disable()
        tracing.clear()


@pytest.mark.parametrize("path", ["packed", "chunked"])
def test_prefill_dispatch_spans_say_what_they_hold(path):
    """`padded`: the rows the program computes (a pack's blocks of 8 rows, or
    the chunk buckets summed); `ctx`: the tokens already in the cache when
    each chunk starts, summed over the chunks (one per sequence, however many
    blocks it rides as). 70 tokens take chunks of 32, 32 and 6 (buckets 32,
    32, 8; 4, 4 and 1 blocks); 12 tokens take one chunk of 12 (bucket 16; 2
    blocks)."""
    eng = _hand_engine(prefill_lanes=2 if path == "packed" else 1)
    spans, preps = _prefill_spans(eng, [_request("long", 70), _request("short", 12)])
    args = [{k: e["args"].get(k) for k in ("rows", "lanes", "padded", "ctx")} for e in spans]
    assert all(a["padded"] >= a["rows"] > 0 and a["ctx"] >= 0 for a in args)
    assert sum(a["rows"] for a in args) == 82
    if path == "packed":
        # the first pack holds both prompts' first chunks in 4 + 2 blocks of 8
        assert args[0] == {"rows": 44, "lanes": 6, "padded": 48, "ctx": 0}
        # then the long prompt alone: 32 rows on 32 cached, 6 rows on 64
        assert args[1:] == [{"rows": 32, "lanes": 4, "padded": 32, "ctx": 32},
                            {"rows": 6, "lanes": 1, "padded": 8, "ctx": 64}]
        assert {e["name"] for e in preps} >= {"engine.prefill_packed.host_prep", "engine.decode_window.host_prep"}
    else:
        # one span per prompt, every chunk of it inside
        by_rows = {a["rows"]: a for a in args}
        assert by_rows[70]["padded"] == 32 + 32 + 8 and by_rows[70]["ctx"] == 0 + 32 + 64
        assert by_rows[12]["padded"] == 16 and by_rows[12]["ctx"] == 0
        assert "engine.prefill_chunk.host_prep" in {e["name"] for e in preps}
    # host_prep is a span of its own now, and still the record's phase
    recs = eng.scheduler.anatomy.records(64)
    assert any(r["host_prep_ms"] > 0 for r in recs)


def test_prefix_hit_counts_as_context():
    """A prompt whose first blocks are already in the cache starts its chunk
    behind them: `ctx` is the cached tokens."""
    eng = _hand_engine()
    first = _request("first", 40, max_tokens=2)
    _prefill_spans(eng, [first], steps=12)
    again = _request("again", 8, max_tokens=2)
    again.token_ids = first.token_ids[:32] + again.token_ids
    spans, _ = _prefill_spans(eng, [again], steps=4)
    (a,) = [e["args"] for e in spans]
    assert a["ctx"] > 0 and a["ctx"] % 4 == 0 and a["ctx"] + a["rows"] == 40
