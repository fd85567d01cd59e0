"""Prefill dispatch-ahead (EngineConfig.prefill_pipeline_depth): config
validation, backlog-aware chunk-bucket promotion, the prefill roofline floor
arithmetic, the StepAnatomy prefill plane, and token-identical parity of the
pipelined scheduler vs the strict reconcile-per-call baseline (greedy,
seeded, and int8-KV arms) plus cancel-mid-pipeline safety."""

import asyncio
import functools
import zlib

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.utils.step_anatomy import (
    DEVICE_PEAKS,
    RooflineModel,
    StepAnatomy,
)


# ---------------- config ----------------


def test_pipeline_depth_validation():
    assert EngineConfig(model_id="tiny").prefill_pipeline_depth == 2
    assert EngineConfig(model_id="tiny", prefill_pipeline_depth=1) is not None
    with pytest.raises(ValueError):
        EngineConfig(model_id="tiny", prefill_pipeline_depth=0)


def test_chunk_len_for_backlog_promotion():
    cfg = EngineConfig(
        model_id="tiny", page_size=4, num_pages=256, max_model_len=1024,
        prefill_buckets=(16, 32, 64), prefill_flat_depth=128,
    )
    # flat-depth budget = 64*128 = 8192: at context depth 256 only the
    # 16-row bucket fits (16*272 <= 8192 < 32*288)
    assert cfg.chunk_len_for(256) == 16
    # a deep backlog (>= 2*top rows pending) doubles the budget: 32*288
    # now fits, 64*320 still doesn't — fewer, larger dispatches
    assert cfg.chunk_len_for(256, backlog_rows=128) == 32
    assert cfg.chunk_len_for(256, backlog_rows=127) == 16
    # no promotion past what the doubled budget allows
    assert cfg.chunk_len_for(256, backlog_rows=10_000) == 32


# ---------------- prefill floor arithmetic ----------------


V5E = "TPU v5 lite"
DEFAULT_MXU_TFLOPS = DEVICE_PEAKS[V5E]["mxu_tflops"]


def test_prefill_floor_hand_computed(monkeypatch):
    monkeypatch.delenv("DYNTPU_MXU_TFLOPS", raising=False)
    roof = RooflineModel(
        param_bytes=1_000_000, page_bytes=2048, page_size=16,
        hbm_bw=1e9, param_count=500_000, device_kind=V5E,
    )
    # bytes bound: params + ceil(48/16)=3 pages; FLOP bound: 2*N*rows/MXU
    rows = 48
    bytes_floor = (1_000_000 + 3 * 2048) / 1e9
    flop_floor = 2.0 * 500_000 * rows / (DEFAULT_MXU_TFLOPS * 1e12)
    assert roof.prefill_floor_bytes(rows) == 1_000_000 + 3 * 2048
    assert roof.prefill_floor_seconds(rows) == pytest.approx(
        max(bytes_floor, flop_floor)
    )
    # a big enough model goes FLOP-bound; the env knob moves the bound
    big = RooflineModel(
        param_bytes=10, page_bytes=1, page_size=16,
        hbm_bw=1e15, param_count=10**12, device_kind=V5E,
    )
    assert big.prefill_floor_seconds(512) == pytest.approx(
        2.0 * 10**12 * 512 / (DEFAULT_MXU_TFLOPS * 1e12)
    )
    monkeypatch.setenv("DYNTPU_MXU_TFLOPS", "100")
    big2 = RooflineModel(
        param_bytes=10, page_bytes=1, page_size=16,
        hbm_bw=1e15, param_count=10**12,
    )
    assert big2.prefill_floor_seconds(512) == pytest.approx(
        2.0 * 10**12 * 512 / 100e12
    )


def test_prefill_plane_accumulation_and_gauge():
    roof = RooflineModel(param_bytes=1000, page_bytes=10, page_size=4,
                         hbm_bw=1000.0, param_count=100, device_kind=V5E)
    a = StepAnatomy(roofline=roof)
    assert a.prefill_roofline_fraction() is None  # no priced prefill yet
    assert a.prefill_fixed_ms() is None
    assert "dynamo_engine_prefill_roofline_fraction" not in a.render_metrics()
    rec = a.begin("prefill_packed")
    a.add_phase(rec, "host_prep", 0.001)
    a.add_phase(rec, "dispatch", 0.009)
    a.note_steps(rec, tokens=8, participants=2)
    a.note_prefill_floor(rec, 8)
    # floor = (1000 + 2*10) / 1000 B/s = 1.02 s over 0.010 s measured
    assert rec.floor_s == pytest.approx(1.02)
    assert a.prefill_roofline_fraction() == pytest.approx(1.02 / 0.010)
    assert a.prefill_fixed_ms() == pytest.approx(10.0)
    snap = a.snapshot()
    assert snap["prefill_roofline_frac"] == pytest.approx(102.0)
    assert snap["prefill_fixed_ms"] == pytest.approx(10.0)
    assert snap["prefill_host_frac"] == 1.0
    # the prefill floor must NOT pollute the decode roofline fraction
    assert a.roofline_fraction() is None
    text = a.render_metrics()
    assert "dynamo_engine_prefill_roofline_fraction" in text
    # /debug/steps record carries the per-dispatch floor
    assert rec.to_dict()["floor_ms"] == pytest.approx(1020.0)


# ---------------- scheduler parity: pipelined vs reconcile-per-call ----------


def _cfg(depth, **over):
    base = dict(
        model_id="tiny", page_size=4, num_pages=256, max_seqs=8,
        max_model_len=96, prefill_buckets=(8, 16, 32), prefill_lanes=2,
        decode_steps=4, pipeline_depth=2, prefill_pipeline_depth=depth,
    )
    base.update(over)
    return EngineConfig(**base)


async def _serve_tokens(cfg, prompts, sampling_kw):
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    eng = AsyncJaxEngine(cfg)
    await eng.start()
    try:
        toks = {i: [] for i in range(len(prompts))}

        async def one(i):
            req = EngineRequest(
                request_id=f"p-{i}", token_ids=list(prompts[i]),
                sampling=SamplingParams(max_tokens=8, ignore_eos=True,
                                        **sampling_kw),
            )
            async for out in eng.generate(req):
                if out.token is not None:
                    toks[i].append(out.token)

        await asyncio.gather(*[one(i) for i in range(len(prompts))])
        stalls = eng.scheduler.stage.prefill_stalls
        calls = eng.scheduler.stage.prefill_calls
        return toks, stalls, calls
    finally:
        await eng.shutdown()


@pytest.mark.parametrize(
    "sampling_kw,over",
    [
        ({"temperature": 0.0}, {}),  # greedy
        ({"temperature": 0.8, "seed": 7}, {}),  # seeded stochastic
        ({"temperature": 0.0}, {"kv_cache_dtype": "int8"}),  # int8 KV
    ],
    ids=["greedy", "seeded", "int8_kv"],
)
def test_pipelined_token_parity(sampling_kw, over):
    """Dispatch-ahead is a scheduling change only: depth=2 must produce the
    exact token streams of the strict depth=1 baseline — greedy, seeded
    (per-request deterministic stream), and quantized-KV arms alike."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 200, 24).tolist() for _ in range(6)]

    async def both():
        t1, s1, c1 = await _serve_tokens(_cfg(1, **over), prompts, sampling_kw)
        t2, s2, c2 = await _serve_tokens(_cfg(2, **over), prompts, sampling_kw)
        return t1, s1, c1, t2, s2, c2

    t1, s1, c1, t2, s2, c2 = asyncio.run(both())
    for i in range(len(prompts)):
        assert t1[i], f"request {i} produced no tokens"
        assert t1[i] == t2[i], f"request {i}: {t1[i]} != {t2[i]}"
    # the burst packs multiple calls (2 lanes over 6 prompts), so the strict
    # arm must have paid forced stalls the pipelined arm avoids
    assert c1 >= 2 and c2 >= 2
    assert s1 > s2, f"depth=1 stalls {s1} not above depth=2 stalls {s2}"


def test_cancel_mid_pipeline():
    """Cancelling requests while packed prefills ride unreconciled must not
    wedge the gate or corrupt survivors: stale in-flight entries skip
    finished sequences, remaining requests complete, and the engine serves
    fresh traffic afterwards."""
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 200, 24).tolist() for _ in range(6)]

    async def run():
        eng = AsyncJaxEngine(_cfg(2))
        await eng.start()
        try:
            done = {}

            async def one(i):
                req = EngineRequest(
                    request_id=f"c-{i}", token_ids=list(prompts[i % len(prompts)]),
                    sampling=SamplingParams(temperature=0.0, max_tokens=8,
                                            ignore_eos=True),
                )
                toks = []
                async for out in eng.generate(req):
                    if out.token is not None:
                        toks.append(out.token)
                done[i] = toks

            tasks = [asyncio.create_task(one(i)) for i in range(6)]
            # let the burst enter the scheduler, then kill half the clients
            # while their prefills are (or were just) in flight
            await asyncio.sleep(0)
            for t in tasks[::2]:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            # survivors completed with output
            for i in (1, 3, 5):
                assert done.get(i), f"survivor {i} produced no tokens"
            # the engine still serves fresh traffic (slots/pages released)
            await one(99)
            assert done[99]
        finally:
            await eng.shutdown()

    asyncio.run(run())


# ---------------- the order of the device's queue (PR 32) ----------------
#
# The device's queue is FIFO: what the scheduler commits to it stands ahead of
# every prompt that has not arrived yet. These tests drive Scheduler.step() by
# hand, with no engine thread, and make the device "slow" or "fast" by fixing
# what the readiness poll answers, so the order is the same on every machine.


def _hand_driven(monkeypatch, ready, **over):
    """A tiny engine whose scheduler the test steps itself; ``ready`` is a
    one-element list the test flips: what every non-blocking poll of an
    in-flight result answers (False: a device still busy with it)."""
    from dynamo_tpu.engine import scheduler as sched_mod
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    over.setdefault("max_seqs", 4)
    eng = AsyncJaxEngine(_cfg(2, **over))
    eng._initialize()
    monkeypatch.setattr(sched_mod, "_is_ready", lambda arr: ready[0])
    return eng


def _hand_request(rid, n_prompt=12, max_tokens=64):
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    rng = np.random.default_rng(zlib.crc32(rid.encode()))
    return EngineRequest(
        request_id=rid, token_ids=rng.integers(1, 200, n_prompt).tolist(),
        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens,
                                ignore_eos=True),
    )


def _spy_prefill_dispatches(sched, look=None):
    """[(request ids of the pack, kinds in flight ahead of it)] of every
    packed prefill dispatched from now on, taken at the dispatch; ``look``
    reads something else than the kinds off the scheduler."""
    look = look or _kinds
    seen = []
    real = sched.runner.prefill_chunk_batch

    def spy(lanes, **kw):
        slots = {lane[3] for lane in lanes}
        rids = [s.req.request_id for s in sched.slots
                if s is not None and s.slot in slots]
        seen.append((rids, look(sched)))
        return real(lanes, **kw)

    sched.runner.prefill_chunk_batch = spy
    return seen


def _kinds(sched):
    return ["window" if e.kind == "window" else "prefill" for e in sched.in_flight]


def test_default_depth_is_double_buffering():
    assert EngineConfig(model_id="tiny").pipeline_depth == 2


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_new_prompt_stands_behind_depth_minus_one_windows(depth, monkeypatch):
    """With decode windows in flight and a device that finishes nothing
    until the host blocks on it, a newly added request's prefill is
    dispatched behind ``pipeline_depth - 1`` unreconciled windows: one at the
    default of 2 (the window that is running), two at the old default of 3."""
    ready = [False]
    eng = _hand_driven(monkeypatch, ready, pipeline_depth=depth)
    sched = eng.scheduler
    for rid in ("a", "b"):
        sched.add_request(_hand_request(rid))
    for _ in range(4):
        sched.step()
    assert _kinds(sched) == ["window"] * (depth - 1)
    calls0, ahead0 = sched.stage.prefill_calls, sched.stage.prefill_windows_ahead
    seen = _spy_prefill_dispatches(sched)
    sched.add_request(_hand_request("late"))
    sched.step()
    assert sched.stage.prefill_calls == calls0 + 1
    assert sched.stage.prefill_windows_ahead - ahead0 == depth - 1
    # the same, read off the queue at the moment of the dispatch: the
    # windows dispatched before the prefill that nobody has reconciled
    assert seen == [(["late"], ["window"] * (depth - 1))]


@pytest.mark.parametrize("depth", [2, 3])
def test_a_running_prefill_counts_as_the_running_entry(depth, monkeypatch):
    """Where the oldest in-flight entry is a prefill, it is what the device
    runs, and every window in flight waits behind it: at most depth - 1 of
    them are committed, so the next prompt finds no second waiting window."""
    ready = [False]
    eng = _hand_driven(monkeypatch, ready, pipeline_depth=depth)
    sched = eng.scheduler
    sched.add_request(_hand_request("a"))
    # one step: admit, prefill, windows behind it; the device "finishes
    # nothing", so the closing block materializes the prefill alone
    outs = sched.step()
    assert [o.request_id for o in outs if o.token is not None] == ["a"]
    assert _kinds(sched) == ["window"] * (depth - 1)
    # the next prompts, one a step: none finds more than depth - 1 windows
    # waiting, whether a window or a prefill heads the queue
    seen = _spy_prefill_dispatches(sched)
    for rid in ("b", "c", "d"):
        sched.add_request(_hand_request(rid))
        sched.step()
        kinds = _kinds(sched)
        assert kinds.count("window") <= depth
        assert kinds[1:].count("window") <= depth - 1
    assert [rids for rids, _ in seen] == [["b"], ["c"], ["d"]]
    # ahead[0] is what the device runs; behind it at most depth - 1 windows
    assert all(ahead[1:].count("window") <= depth - 1 for _, ahead in seen), seen


def test_step_returns_tokens_without_a_device_wait(monkeypatch):
    """A step whose opening non-blocking reconcile materialized tokens
    refills the device's queue and returns them; it enters no blocking
    device wait (no ``device_wait`` phase is recorded for it), and the wait
    happens on the next call."""
    ready = [False]
    eng = _hand_driven(monkeypatch, ready)
    sched = eng.scheduler
    sched.add_request(_hand_request("a"))
    sched.add_request(_hand_request("b"))
    for _ in range(3):
        sched.step()
    assert _kinds(sched) == ["window"]
    def waited_ms():
        return sum(r["device_wait_ms"] for r in sched.anatomy.records(512))

    waits0, waited0 = sched.stage.reconcile_waits, waited_ms()
    ready[0] = True  # the window in flight has landed by the next call
    outs = sched.step()
    ready[0] = False
    assert {o.request_id for o in outs if o.token is not None} == {"a", "b"}
    assert sched.stage.reconcile_waits == waits0
    assert waited_ms() == waited0
    # ... and the queue was refilled before it returned
    assert _kinds(sched) == ["window", "window"]
    outs = sched.step()  # nothing in hand now: this call blocks
    assert sched.stage.reconcile_waits == waits0 + 1
    assert waited_ms() > waited0 and outs


@functools.lru_cache(maxsize=None)
def _staggered_tokens(depth):
    """Greedy streams of a mixed run: prompts of several lengths arriving
    while earlier ones decode, so prefills interleave with windows."""
    from dynamo_tpu.engine.engine import AsyncJaxEngine
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 200, n).tolist() for n in (24, 9, 40, 17, 30, 12)]

    async def run():
        eng = AsyncJaxEngine(_cfg(2, pipeline_depth=depth))
        await eng.start()
        try:
            toks = {i: [] for i in range(len(prompts))}

            async def one(i):
                await asyncio.sleep(0.03 * i)
                req = EngineRequest(
                    request_id=f"s-{i}", token_ids=list(prompts[i]),
                    sampling=SamplingParams(temperature=0.0, max_tokens=20,
                                            ignore_eos=True),
                )
                async for out in eng.generate(req):
                    if out.token is not None:
                        toks[i].append(out.token)

            await asyncio.gather(*[one(i) for i in range(len(prompts))])
            return toks
        finally:
            await eng.shutdown()

    return asyncio.run(run())


@pytest.mark.parametrize("depth", [2, 3])
def test_greedy_streams_do_not_depend_on_depth(depth):
    """The order on the device's queue is all that depth changes: greedy
    streams of a mixed prefill and decode run are token for token those of
    the synchronous engine (depth 1)."""
    base = _staggered_tokens(1)
    got = _staggered_tokens(depth)
    for i, want in base.items():
        assert len(want) == 20
        assert got[i] == want, f"request {i} at depth {depth}: {got[i]} != {want}"


def test_windows_ahead_on_the_span_and_in_metrics(monkeypatch):
    """The prefill dispatch span says how many windows stood ahead of it, and
    /metrics carries the two counters whose quotient is that number's mean:
    at most 1 at the default depth."""
    from dynamo_tpu.utils import tracing
    from dynamo_tpu.utils.prometheus import check_exposition

    ready = [False]
    eng = _hand_driven(monkeypatch, ready)
    sched = eng.scheduler
    tracing.clear()
    tracing.enable()
    try:
        sched.add_request(_hand_request("a"))
        for i in range(6):
            sched.step()
            sched.add_request(_hand_request(f"late-{i}", max_tokens=8))
        for _ in range(4):
            sched.step()
        spans = [e for e in tracing.events() if e["name"] == "engine.prefill"]
    finally:
        tracing.disable()
        tracing.clear()
    assert len(spans) >= 6
    assert all("windows_ahead" in e["args"] and "rows" in e["args"] for e in spans)
    # the flash kernel's context tile, a label: 0 where the gather reference runs
    assert all(e["args"]["tile"] == 0 for e in spans)
    assert [e["args"]["windows_ahead"] for e in spans][0] == 0  # an empty queue
    st = sched.stage
    assert st.prefill_calls == len(spans)
    assert st.prefill_windows_ahead == sum(e["args"]["windows_ahead"] for e in spans)
    assert 0 < st.prefill_windows_ahead <= st.prefill_calls
    text = eng.render_stage_metrics()
    assert check_exposition(text) == []
    assert f"dynamo_engine_prefill_dispatches_total {st.prefill_calls}" in text
    assert f"dynamo_engine_prefill_windows_ahead_total {st.prefill_windows_ahead}" in text


# ---------------- the decode window as the unit of admission (ISSUE 36) -------
#
# A window is ``decode_steps`` fused steps, and a prompt that arrives while
# one runs waits for it: the default went from 8 to 4 by a rule fixed before
# the chip runs (PERF.md, PR 36). The order on the device's queue must not
# depend on K, and neither may a token.


def test_default_window_is_the_one_issue_36_chose():
    """ISSUE 36: ``decode_steps`` and ``pipeline_depth`` together set how long a
    new prompt waits (one window of K steps ahead of its prefill, half of
    one in the inbox). Whoever edits either default re-runs that issue's
    rule on the chip (PERF.md section 6, PR 36) and changes this test."""
    cfg = EngineConfig(model_id="tiny")
    assert cfg.decode_steps == 4
    assert cfg.pipeline_depth == 2


@functools.lru_cache(maxsize=None)
def _late_prompt_run(k):
    """A hand-stepped engine with windows of ``k`` steps: two requests decode,
    a third arrives while a window runs on a device that finishes nothing
    until the host blocks on it, and all three are stepped to their end.
    Returns what the late prompt's prefill found ahead of it and every
    request's streamed tokens."""
    from dynamo_tpu.utils import tracing

    with pytest.MonkeyPatch.context() as mp:
        ready = [False]
        eng = _hand_driven(mp, ready, decode_steps=k)
        sched = eng.scheduler
        toks = {}

        def step():
            for o in sched.step():
                if o.token is not None:
                    toks.setdefault(o.request_id, []).append(o.token)

        tracing.clear()
        tracing.enable()
        try:
            for rid in ("a", "b"):
                sched.add_request(_hand_request(rid, max_tokens=40))
            for _ in range(3):
                step()
            before = _kinds(sched)
            calls0, ahead0 = sched.stage.prefill_calls, sched.stage.prefill_windows_ahead
            seen = _spy_prefill_dispatches(
                sched, look=lambda s: [(e.kind, e.rec.steps) for e in s.in_flight])
            sched.add_request(_hand_request("late", max_tokens=40))
            step()
            found = dict(
                before=before, seen=seen,
                calls=sched.stage.prefill_calls - calls0,
                windows_ahead=sched.stage.prefill_windows_ahead - ahead0,
            )
            for _ in range(200):
                if not sched.has_work():
                    break
                step()
            spans = tracing.events()
        finally:
            tracing.disable()
            tracing.clear()
        found["span_ahead"] = [e["args"]["windows_ahead"] for e in spans
                               if e["name"] == "engine.prefill"][-1]
        found["span_k"] = {e["args"]["k"] for e in spans if e["name"] == "engine.decode.window"}
        found["record_steps"] = {r["steps"] for r in sched.anatomy.records(512, kind="decode_window")}
        found["tokens"] = toks
        return found


@pytest.mark.parametrize("k", [2, 4, 8])
def test_late_prompt_waits_behind_one_window_of_k_steps(k):
    """Whatever the window's length, a prompt that arrives while a window runs
    has its prefill dispatched behind exactly that one window (``windows_ahead``
    1 on the span and in the counter), the window is K steps long by its own
    dispatch record and span, and the three requests' greedy streams are token
    for token those of the 8-step window this default replaced."""
    got = _late_prompt_run(k)
    assert got["before"] == ["window"]
    assert got["calls"] == 1 and got["windows_ahead"] == 1 and got["span_ahead"] == 1
    assert got["seen"] == [(["late"], [("window", k)])]
    assert got["span_k"] == {k} and got["record_steps"] == {k}
    want = _late_prompt_run(8)["tokens"]
    assert sorted(got["tokens"]) == ["a", "b", "late"]
    assert all(len(t) == 40 for t in got["tokens"].values())
    assert got["tokens"] == want
