"""Layer groups (engine/page_table.py `GroupedPageAllocator`, the scheduler
and the runner over it): attention layers that keep different tokens share one
pool of single-layer pages, a window group gives back what lies behind its
window, and a prefix match is given only where every group can serve it.

Served through the engine (scheduler, runner, allocator, prefix cache,
sampler) with `tests/test_cohere2_moe.py`'s small checkpoint, window 32, and
compared with the plain reference in float32; then the allocator alone; then
that a model with one group allocates exactly as before.
"""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.engine.page_table import GroupedPageAllocator, PageAllocator
from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import EngineRequest
from dynamo_tpu.models.cohere2_moe import LayerGroup

from test_cohere2_moe import HF_TINY, reference, tokens, write_checkpoint

#: logprobs of the tokens the engine chose, float32 on both sides
LOGPROB_ATOL = 2e-4
PS = 8  # 4 pages a window of 32


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    return write_checkpoint(tmp_path_factory.mktemp("layer_groups") / "ckpt", HF_TINY, 37)


def _engine(ckpt, **kw):
    base = dict(model_id=str(ckpt), num_pages=400, max_seqs=2, max_model_len=256, page_size=PS,
                prefill_buckets=(16, 32), decode_steps=4)
    return AsyncJaxEngine(EngineConfig(**{**base, **kw}))


async def _generate(eng, rid, prompt, max_tokens):
    toks, lps = [], []
    req = EngineRequest(request_id=rid, token_ids=list(prompt), logprobs=1,
                        sampling=SamplingParams(temperature=0.0, max_tokens=max_tokens))
    async for out in eng.generate(req):
        if out.token is not None:
            toks.append(out.token)
            lps.append(out.logprob)
    return toks, lps


def _check(ckpt, prompts, results):
    probes = [{"tokens": list(p) + toks, "prompt_len": len(p)} for p, (toks, _) in zip(prompts, results)]
    for (toks, lps), want in zip(results, reference.teacher_forced_logprobs(ckpt, probes)):
        assert len(toks) == len(want)
        np.testing.assert_allclose(lps, want, atol=LOGPROB_ATOL)


def _serve(ckpt, body, **engine_kw):
    async def run():
        eng = _engine(ckpt, **engine_kw)
        await eng.start()
        try:
            return await body(eng)
        finally:
            await eng.shutdown()

    return asyncio.run(run())


ENGINE_CASES = {
    # a prompt inside the window, two decode windows
    "one_chunk": dict(prompts=[tokens(10, 20)], max_tokens=8, engine={}),
    # a prompt of four chunks that passes the window three times over
    "chunked_past_the_window": dict(prompts=[tokens(11, 110)], max_tokens=6, engine={}),
    # two at once: chunks share packed calls, decode shares windows
    "packed": dict(prompts=[tokens(12, 70), tokens(13, 9)], max_tokens=9, engine={}),
    # decode crosses the window: the context starts inside it and ends 2 W on
    "window_crossed_in_decode": dict(prompts=[tokens(14, 25)], max_tokens=70, engine={}),
    # four sequences through two slots
    "slot_reused": dict(prompts=[tokens(15 + i, 12 + 19 * i) for i in range(4)], max_tokens=7,
                        engine={}),
    # a pool two sequences cannot both grow in: the younger is preempted and
    # resumes by recomputing, through the window
    "preempted": dict(prompts=[tokens(20, 60), tokens(21, 60)], max_tokens=50,
                      engine=dict(num_pages=58, watermark=0.0)),
}


@pytest.mark.parametrize("case", sorted(ENGINE_CASES))
def test_engine_matches_the_reference(ckpt, case):
    spec = ENGINE_CASES[case]

    async def body(eng):
        results = await asyncio.gather(*[
            _generate(eng, f"{case}-{i}", p, spec["max_tokens"])
            for i, p in enumerate(spec["prompts"])])
        return results, eng.scheduler.preempt_count, eng.resource_snapshot()

    results, preempted, snap = _serve(ckpt, body, **spec["engine"])
    _check(ckpt, spec["prompts"], results)
    if case == "preempted":
        assert preempted >= 1
    assert snap["kv_pages_active"] == 0  # every page came back
    assert snap["kv_group_pages"]["window"]["active"] == 0
    assert snap["moe_routed"] > snap["moe_assignments"] > 0  # half the experts are held
    # a (layer, held expert) pair is touched by at least one assignment
    assert 0 < snap["moe_experts_touched"] <= snap["moe_assignments"]


def test_pages_behind_the_window_come_back_while_the_sequence_runs(ckpt):
    """A context of 120 + 60 under a window of 32: the window group never
    holds more than the window and a chunk, the full group holds every block,
    the free count rises as the window moves on, and the counter counts."""

    async def body(eng):
        seen = []

        async def watch():
            while True:
                alloc = eng.allocator
                if alloc is not None and alloc._seqs:
                    seen.append((alloc.group_pages(), alloc.free_pages,
                                 next(iter(alloc._seqs.values())).num_pages))
                await asyncio.sleep(0.005)

        task = asyncio.create_task(watch())
        prompt = tokens(30, 120)
        result = await _generate(eng, "long", prompt, 60)
        task.cancel()
        return prompt, result, seen, eng.resource_snapshot(), eng.render_stage_metrics()

    prompt, result, seen, snap, text = _serve(ckpt, body)
    _check(ckpt, [prompt], [result])
    assert seen, "the sequence was never observed"
    window_layers, chunk = 3, 32
    most = max(g["window"]["active"] for g, _, _ in seen)
    assert most <= window_layers * ((32 + chunk) // PS + 2)
    groups, _, blocks = seen[-1]
    assert groups["full"]["active"] == blocks  # one layer, every block
    assert groups["window"]["whole"] == window_layers * blocks
    assert groups["window"]["active"] < groups["window"]["whole"] / 2
    assert snap["kv_window_pages_released"] >= window_layers * (170 - 32 - 2 * PS) // PS
    for family in ('dynamo_engine_kv_group_pages{group="window",state="active"}',
                   'dynamo_engine_kv_group_pages{group="full",state="cached"}',
                   "dynamo_engine_kv_window_pages_released_total",
                   "dynamo_engine_moe_assignments_total"):
        assert family in text, family


def test_a_prefix_hit_with_the_window_pages_present_equals_a_cold_run(ckpt):
    """Turn two extends turn one's prompt and answer: the full group's chain
    and the window group's last blocks are in the cache, the match is given,
    and the logprobs are the reference's, as a cold engine's are."""
    first, tail = tokens(40, 90), tokens(41, 21)

    async def warm(eng):
        answer, _ = await _generate(eng, "t1", first, 12)
        second = first + answer + tail
        out = await _generate(eng, "t2", second, 8)
        return second, out, eng.resource_snapshot()

    second, hit, snap = _serve(ckpt, warm)
    assert snap["prefix_cache_hit_blocks"] == (90 + 12 - 1) // PS and snap["prefix_cache_refused"] == 0

    async def cold(eng):
        return await _generate(eng, "cold", second, 8)

    fresh = _serve(ckpt, cold)
    assert hit[0] == fresh[0]
    np.testing.assert_allclose(hit[1], fresh[1], atol=LOGPROB_ATOL)
    _check(ckpt, [second], [hit])


def test_the_next_turn_hits_though_decode_moved_the_window_past_the_prompt(ckpt):
    """A conversation's next prompt extends the LAST PROMPT, not the answer: an
    answer of 40 tokens under a window of 32 made decode give back every window
    block the match at the prompt's end needs. They joined the LRU at its
    young end (nothing dropped behind a window is reclaimed ahead of its age),
    so the match is given, under allocation pressure from another sequence
    too."""
    first, tail = tokens(44, 90), tokens(45, 30)

    async def body(eng):
        await _generate(eng, "t1", first, 40)
        await _generate(eng, "other", tokens(46, 100), 4)  # takes and frees pages in between
        second = first + tail
        out = await _generate(eng, "t2", second, 8)
        return second, out, eng.resource_snapshot()

    second, out, snap = _serve(ckpt, body, num_pages=120, watermark=0.0)
    assert snap["prefix_cache_refused"] == 0 and snap["prefix_cache_hit_blocks"] == 90 // PS
    _check(ckpt, [second], [out])


def test_a_match_whose_window_pages_were_evicted_is_refused_whole(ckpt):
    """The same two turns, but between them the window group's cached blocks
    are reclaimed (the full group's stay): the match is refused whole and
    counted, and the answer is still the reference's."""
    first, tail = tokens(42, 90), tokens(43, 21)

    async def body(eng):
        answer, _ = await _generate(eng, "t1", first, 12)
        alloc = eng.allocator
        pool = alloc._reusable  # evict the window group's entries
        for key in [k for k in pool if alloc.groups[k[0]].window]:
            entry = pool.pop(key)
            del alloc._entries[key]
            alloc._evictable_pages -= len(entry)
            alloc._cached[key[0]] -= len(entry)
            alloc._free.extend(entry)
        assert alloc.lookup_prefix(first + answer + tail) == 0
        second = first + answer + tail
        out = await _generate(eng, "t2", second, 8)
        return second, out, eng.resource_snapshot()

    second, out, snap = _serve(ckpt, body)
    assert snap["prefix_cache_refused"] == 1 and snap["prefix_cache_hit_blocks"] == 0
    _check(ckpt, [second], [out])


REFUSED = {
    "speculation": (dict(speculative="ngram:2"), "speculative decoding is refused"),
    "offload": (dict(host_cache_blocks=4), "host and disk KV tiers are refused"),
    "tensor_parallel": (dict(tp=2), "tp/pp/sp > 1 are refused"),
    "int8_kv": (dict(kv_cache_dtype="int8"), "int8"),
    "one_prefill_lane": (dict(prefill_lanes=1), "prefill_lanes <= 1 is refused"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_at_start_up_with_the_reason(what):
    kw, reason = REFUSED[what]

    async def body():
        eng = AsyncJaxEngine(EngineConfig(model_id="tiny-window", num_pages=64, max_seqs=2, **kw))
        await eng.start()

    with pytest.raises(Exception, match=reason):
        asyncio.run(body())


def test_transfer_paths_are_refused_for_a_model_with_layer_groups():
    async def body():
        eng = AsyncJaxEngine(EngineConfig(model_id="tiny-window", num_pages=64, max_seqs=2))
        await eng.start()
        try:
            assert "page table each" in eng.transfer_refusal()
            assert not eng.config.migration and not eng.config.prefix_fetch
            with pytest.raises(ValueError, match="page table each"):
                eng.sync_allocate_remote("r", [1, 2, 3])
        finally:
            await eng.shutdown()

    asyncio.run(body())


# ---------------------------------------------------------------- the allocator alone

GROUPS = [LayerGroup("window", (0, 1, 2), 32), LayerGroup("full", (3,), 0)]


def _alloc(pages=200):
    return GroupedPageAllocator(pages, PS, GROUPS)


def test_allocator_takes_window_pages_chunk_by_chunk_and_gives_them_back():
    a = _alloc()
    prompt = list(range(3, 163))  # 160 tokens = 20 blocks
    assert a.pages_for_prompt(160) == 20 + 3 * 5
    cached, state = a.allocate_sequence("s", prompt)
    assert cached == 0 and state.num_pages == 20
    assert a.active_pages == 20  # the full group whole, the window group nothing yet
    free0 = a.free_pages
    assert a.ensure_capacity("s", 32) and a.active_pages == 20 + 3 * 4
    assert a.release_behind("s", 32) == 0  # position 32 still sees key 1
    assert a.ensure_capacity("s", 64)
    assert a.release_behind("s", 64) == 3 * 4  # blocks 0-3 lie behind (64 - 32, 64]
    assert a.free_pages == free0 - 3 * 4
    assert [state.tables[0][b] for b in range(4)] == [0] * 4 and state.tables[3][0] != 0
    assert a.window_pages_released == 12
    a.free_sequence("s")
    assert a.active_pages == 0 and a.free_pages == 199


def test_what_is_dropped_behind_a_window_joins_the_one_lru():
    """Whether a prefill chunk or a decode window dropped it: the oldest entry
    goes first, and nothing is reclaimed ahead of its age."""
    a = _alloc(pages=1 + 20 + 3 * 20)
    prompt = list(range(3, 163))
    _, state = a.allocate_sequence("s", prompt)
    assert a.ensure_capacity("s", 160)
    a.commit_prefilled("s", 160)  # every block registered in both groups
    oldest = {state.tables[t][0] for t in range(3)}  # block 0 of the window group
    a.release_behind("s", 64)
    a.release_behind("s", 80)
    assert len(a._reusable) == 6 and all(k[0] == 0 for k in a._reusable)
    a.free_sequence("s")  # its other blocks are evictable now, behind those six
    assert a.active_pages == 0 and len(a._free) == 0
    got = a._pop_free_pages(3)
    assert set(got) == oldest
    assert len([k for k in a._reusable if k[0] == 0]) == 19  # one entry of three pages went


def test_allocator_gives_a_match_only_with_the_window_blocks_behind_it():
    a = _alloc()
    prompt = list(range(3, 3 + 96))  # 12 blocks
    a.allocate_sequence("s", prompt)
    assert a.ensure_capacity("s", 96)
    a.commit_prefilled("s", 96)
    a.free_sequence("s")
    longer = prompt + [7] * 20
    assert a.lookup_prefix(longer) == 96
    cached, state = a.allocate_sequence("t", longer)
    assert cached == 96 and a.cache_hit_blocks == 12
    # the window group took blocks 8-11 only: keys in (96 - 32, 96]
    assert [bool(state.tables[0][b]) for b in range(12)] == [False] * 8 + [True] * 4
    assert all(state.tables[3][b] for b in range(12))
    a.free_sequence("t")
    # evict one window block the match needs: refused whole, and counted
    key = (0, state.token_seq.blocks[9].sequence_hash)
    entry = a._reusable.pop(key)
    del a._entries[key]
    a._evictable_pages -= len(entry)
    a._cached[0] -= len(entry)
    a._free.extend(entry)
    assert a.lookup_prefix(longer) == 0
    cached, _ = a.allocate_sequence("u", longer)
    assert cached == 0 and a.prefix_refused == 1
    # a prompt that is the cached one entire leaves its last block to prefill
    a.free_sequence("u")
    assert a.lookup_prefix(prompt[:40]) == 32


def test_allocator_out_of_pages_takes_nothing():
    a = _alloc(pages=30)
    with pytest.raises(MemoryError):
        a.allocate_sequence("big", list(range(3, 3 + 8 * 40)))
    assert a.free_pages == 29 and not a._seqs
    _, state = a.allocate_sequence("s", list(range(3, 3 + 8 * 20)))
    assert a.free_pages == 9
    assert a.ensure_capacity("s", 24) and a.free_pages == 0
    assert not a.ensure_capacity("s", 32)  # three more pages are not there
    assert a.free_pages == 0 and state.tables[0][3] == 0


# ---------------------------------------------------------------- one group: as before

#: page ids a fixed script of calls gave at the parent commit (PR 36), pages 16,
#: page size 4: allocate, grow, free, a prefix hit, a second sequence
ONE_GROUP_SCRIPT = [
    ("a", [1, 2, 3]), ("a+", [1, 2, 3, 4]), ("b", [5, 6]),
    ("a2", [1, 2, 4]), ("c", [3, 7, 8, 9]),
]


def test_a_model_with_one_group_allocates_exactly_as_before():
    a = PageAllocator(16, 4)
    got = []
    _, s = a.allocate_sequence("a", list(range(10, 20)))
    got.append(("a", list(s.pages)))
    assert a.ensure_capacity("a", 14)
    got.append(("a+", list(s.pages)))
    a.commit_prefilled("a", 10)
    _, s = a.allocate_sequence("b", list(range(30, 36)))
    got.append(("b", list(s.pages)))
    a.free_sequence("a")
    cached, s = a.allocate_sequence("a2", list(range(10, 20)))
    assert cached == 8
    got.append(("a2", list(s.pages)))
    _, s = a.allocate_sequence("c", list(range(40, 54)))
    got.append(("c", list(s.pages)))
    assert got == ONE_GROUP_SCRIPT
    assert a.pages_for_prompt(10) == 3
    assert (a.free_pages, a.active_pages, a.used_pages) == (6, 9, 9)


@pytest.mark.parametrize("model_id", ["tiny", "tiny-hybrid"])
def test_one_group_models_take_the_plain_allocator(model_id):
    async def body():
        eng = AsyncJaxEngine(EngineConfig(model_id=model_id, num_pages=32, max_seqs=2))
        await eng.start()
        try:
            out = await _generate(eng, "r", [5, 6, 7, 8, 9], 6)
            return type(eng.allocator), eng.scheduler.grouped, eng.runner.kv_tables, out, \
                eng.resource_snapshot()
        finally:
            await eng.shutdown()

    kind, grouped, tables, out, snap = asyncio.run(body())
    assert kind is PageAllocator and not grouped and tables == 1 and len(out[0]) == 6
    assert snap["kv_group_pages"] == {} and snap["kv_window_pages_released"] == 0
