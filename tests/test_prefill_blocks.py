"""The packed prefill's unit is the block (PR 40): a pack is N blocks of
`EngineConfig.prefill_block` rows, each chunk padded to its own next block,
where it was a rectangle of whole chunks padded to the widest one, N a power
of two. A model with recurrent layers keeps the rectangle.

(a) the packer as a pure function of a pending set; (b) a chunk as blocks of
one call against the same chunk as one lane and as one call a block; (c) the
recurrent model's packs are the parent's, letter for letter; (d) every
program the packer can emit is in warm-up's list.
"""

import zlib

import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.scheduler import plan_block_pack

# ---------------- (a) the packer, a pure function ----------------

#: name -> (block, budget, pending [(prefill_pos, chunk end, prompt_len)] in
#: admission order, the blocks expected [(index, start, end)] or None)
PENDING = {
    # ISSUE 40's pair: 2 x 512 = 1024 rows as a rectangle, 512 + 128 as blocks
    "pair_400_100": (128, 8, [(0, 400, 400), (0, 100, 100)],
                     [(0, 0, 128), (0, 128, 256), (0, 256, 384), (0, 384, 400), (1, 0, 100)]),
    "lone_300": (128, 8, [(0, 300, 300)], [(0, 0, 128), (0, 128, 256), (0, 256, 300)]),
    # two whole chunks fill the budget: the third sequence waits
    "budget_full": (128, 8, [(0, 512, 2048), (0, 512, 600), (0, 300, 300)], None),
    # the third chunk has one block left: cut at 128, its rest rides the next call
    "cut_to_fit": (128, 8, [(0, 300, 300), (0, 512, 900), (0, 512, 512)],
                   [(0, 0, 128), (0, 128, 256), (0, 256, 300), (1, 0, 128), (1, 128, 256),
                    (1, 256, 384), (1, 384, 512), (2, 0, 128)]),
    # a prompt resumed in mid-block (a prefix hit of 37 tokens) and one at depth
    "resumed": (128, 8, [(1024, 1536, 1700), (37, 100, 100)], None),
    # ten short prompts: eight blocks, one each, the last two wait
    "many_short": (128, 8, [(0, 40 + i, 40 + i) for i in range(10)], None),
    # every block holds 64 rows or fewer: the bucket is still the block
    "all_under_64": (128, 8, [(0, 50, 50), (0, 60, 60)], [(0, 0, 50), (1, 0, 60)]),
    # the tiny models' block of 16
    "tiny_block": (16, 8, [(0, 32, 75), (5, 25, 25), (0, 32, 40), (0, 9, 9)], None),
}


def _ceil(n, d):
    return -(-n // d)


@pytest.mark.parametrize("name", sorted(PENDING))
def test_the_packer_is_a_function_of_the_pending_set(name):
    block, budget, pending, want = PENDING[name]
    blocks = plan_block_pack([(pos, end) for pos, end, _ in pending], block, budget)
    if want is not None:
        assert blocks == want
    # N is the number of blocks, exactly, and never passes the budget
    assert 1 <= len(blocks) <= budget
    # admission order is kept, and a sequence's blocks stand together
    order = [i for i, _, _ in blocks]
    assert order == sorted(order) and set(order) == set(range(max(order) + 1))
    taken = {}
    for i, start, end in blocks:
        pos, chunk_end, _ = pending[i]
        assert 0 < end - start <= block
        # a chunk starts where the sequence stands and its blocks follow on
        assert start == taken.get(i, pos)
        taken[i] = end
        assert end <= chunk_end
    for i, end in taken.items():
        pos, chunk_end, _ = pending[i]
        if end < chunk_end:  # cut to fit: at a block boundary, and nothing follows it
            assert (end - pos) % block == 0 and i == max(taken)
    # only a chunk's last block may be short
    for (i, start, end), (j, _, _) in zip(blocks, blocks[1:]):
        assert end - start == block or i != j
    # the rows the program computes: every chunk padded to its OWN next block
    assert block * len(blocks) == block * sum(_ceil(taken[i] - pending[i][0], block) for i in taken)
    # what is left of the budget is under the next pending chunk's first block
    assert len(blocks) == budget or len(taken) == len(pending)
    # `is_final` (the block that ends the prompt) sits on one block of a sequence at most
    for i in taken:
        finals = [b for b in blocks if b[0] == i and b[2] == pending[i][2]]
        assert len(finals) == (1 if taken[i] == pending[i][2] else 0)


def test_the_block_is_derived_from_the_buckets():
    assert EngineConfig(model_id="tiny").prefill_block == 128  # (64, 128, 256, 512)
    assert EngineConfig(model_id="tiny", prefill_buckets=(16, 32)).prefill_block == 16
    assert EngineConfig(model_id="tiny", prefill_buckets=(64, 256, 512)).prefill_block == 256
    assert EngineConfig(model_id="tiny").pack_blocks == 8
    assert EngineConfig(model_id="tiny", prefill_buckets=(64, 256, 512)).pack_blocks == 4


# ---------------- through the scheduler ----------------


def _hand_engine(model_id, **over):
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    base = dict(model_id=model_id, page_size=4, num_pages=256, max_seqs=4, max_model_len=96,
                prefill_buckets=(8, 16, 32), prefill_lanes=4, decode_steps=4)
    eng = AsyncJaxEngine(EngineConfig(**{**base, **over}))
    eng._initialize()
    return eng


def _packs(eng, lengths, steps=12):
    """[[N, bucket, [[rows, start, slot, is_final] per lane]] per packed call]
    of the prompts `lengths`, added at once and stepped by hand."""
    from dynamo_tpu.engine.sampling import SamplingParams
    from dynamo_tpu.engine.scheduler import EngineRequest

    seen = []
    real = eng.runner.prefill_chunk_batch

    def spy(lanes, N, **kw):
        seen.append([N, kw["bucket"], [[len(l[0]), int(l[1]), int(l[3]), bool(l[6])] for l in lanes]])
        return real(lanes, N=N, **kw)

    eng.runner.prefill_chunk_batch = spy
    for i, n in enumerate(lengths):
        rng = np.random.default_rng(zlib.crc32(f"r{i}".encode()))
        eng.scheduler.add_request(EngineRequest(
            request_id=f"r{i}", token_ids=rng.integers(1, 200, n).tolist(),
            sampling=SamplingParams(temperature=0.0, max_tokens=4, ignore_eos=True)))
    for _ in range(steps):
        eng.scheduler.step()
    return seen


#: what the PARENT's packer (commit a2de145, before PR 40) handed to
#: `prefill_chunk_batch` for prompts of 70, 12, 40, 9, 33 and 20 tokens on
#: `tiny-hybrid` with `_hand_engine`'s settings: taken by running `_packs`
#: there (its bucket the one `pack_prefill_lanes` derived from the longest lane)
PARENT_RECTANGLES = [
    [4, 32, [[32, 0, 0, False], [12, 0, 1, True], [32, 0, 2, False], [9, 0, 3, True]]],
    [2, 32, [[32, 32, 0, False], [8, 32, 2, True]]],
    [1, 8, [[6, 64, 0, True]]],
    [2, 32, [[32, 0, 0, False], [20, 0, 1, True]]],
    [1, 8, [[1, 32, 0, True]]],
]


def test_a_recurrent_model_keeps_the_parents_rectangles():
    """(c) `runner.recurrent` chooses the packer: not one lane, N or bucket of
    `tiny-hybrid`'s packs differs from what the parent handed the runner."""
    eng = _hand_engine("tiny-hybrid")
    assert eng.runner.recurrent
    assert _packs(eng, [70, 12, 40, 9, 33, 20]) == PARENT_RECTANGLES


def test_the_scheduler_hands_the_runner_blocks():
    """The same prompts on `tiny`: blocks of 8 rows, 8 a pack at most, N their
    number, a chunk cut to fit resumed at its block boundary by the next call,
    the counters in step."""
    eng = _hand_engine("tiny")
    assert not eng.runner.recurrent
    packs = _packs(eng, [70, 12, 40, 9, 33, 20])
    assert all(N == len(lanes) <= 8 and bucket == 8 for N, bucket, lanes in packs)
    assert packs[0] == [8, 8, [[8, 0, 0, False], [8, 8, 0, False], [8, 16, 0, False], [8, 24, 0, False],
                               [8, 0, 1, False], [4, 8, 1, True], [8, 0, 2, False], [8, 8, 2, False]]]
    # slot 2's chunk of 32 was cut at 16: it goes on from there
    assert [8, 16, 2, False] in packs[1][2]
    # every prompt row was computed once, and one block of each prompt is final
    assert sum(rows for _, _, lanes in packs for rows, *_ in lanes) == 70 + 12 + 40 + 9 + 33 + 20
    assert sum(final for _, _, lanes in packs for *_, final in lanes) == 6
    st = eng.scheduler.stage
    assert st.prefill_rows == 184 and st.prefill_padded_rows == 8 * sum(N for N, _, _ in packs)
    text = eng.render_stage_metrics()
    assert f"dynamo_engine_prefill_rows_total {st.prefill_rows}" in text
    assert f"dynamo_engine_prefill_padded_rows_total {st.prefill_padded_rows}" in text


def test_a_pack_of_short_blocks_keeps_the_blocks_program():
    """Two prompts of 50 and 60 tokens under the default buckets: the bucket
    is the block of 128, not the 64 their longest lane would choose (a program
    warm-up does not compile)."""
    from dynamo_tpu.engine.sampling import MAX_EOS_IDS, SamplingParams

    eng = _hand_engine("tiny", prefill_buckets=(64, 128, 256, 512), max_model_len=512, page_size=16)
    packs = _packs(eng, [50, 60], steps=4)
    assert packs == [[2, 128, [[50, 0, 0, True], [60, 0, 1, True]]]]
    # the runner pads to the bucket it is handed, not to its longest lane's (64)
    lane = (np.zeros(50, np.int32), 0, np.zeros(32, np.int32), 0, SamplingParams(), (), True)
    assert eng.runner.pack_prefill_lanes([lane], N=1, bucket=128)[0].shape == (1, 128 + 32 + 6 + MAX_EOS_IDS)


# ---------------- (b) a chunk as blocks of one call ----------------

#: the tolerance tests/test_llama_model.py holds a packed prefill to against
#: the per-request one (logits and page contents)
ATOL = 1e-4


@pytest.mark.parametrize("model_id", ["tiny", "tiny-window"])
def test_a_chunk_as_blocks_of_one_call(model_id):
    """One prompt of 300 tokens prefilled (A) as the parent's single lane in
    the 512 bucket, (B) as ONE call of three blocks of 128 rows with the same
    page table and start positions 0, 128, 256, (C) as three calls of one
    block each: the same first token, the same logprobs, the same pages.
    `tiny-window`'s window layers see 32 tokens, a quarter of a block."""
    from dynamo_tpu.engine.sampling import SamplingParams

    eng = _hand_engine(model_id, page_size=16, num_pages=512, max_model_len=512,
                       prefill_buckets=(64, 128, 256, 512))
    runner, alloc = eng.runner, eng.scheduler.allocator
    grouped = eng.scheduler.grouped
    if model_id == "tiny-window":
        assert grouped and runner.model.config.sliding_window < 128
    tokens = np.random.default_rng(40).integers(1, 200, 300).astype(np.int32)
    sampling = SamplingParams(temperature=0.0)

    def prefill(rid, calls):
        """`calls`: [(N, bucket, [(start, end)])]; the last lane of the last call is final."""
        _, state = alloc.allocate_sequence(rid, tokens.tolist())
        if grouped:
            assert alloc.ensure_capacity(rid, 300)
        table = eng.scheduler._new_table(state)
        for N, bucket, spans in calls:
            lanes = [(tokens[a:b], a, table, 0, sampling, (), b == 300) for a, b in spans]
            toks, (chosen, tids, tvals) = runner.prefill_chunk_batch(
                lanes, N=N, want_logprobs=True, bucket=bucket)
        j = len(spans) - 1
        kv = runner.kv_cache
        if grouped:  # one pool of single-layer pages, a table row per attention layer
            ids = np.asarray(table)[:, : -(-300 // 16)].reshape(-1)
        else:  # a flat pool, layer l's pages at l * num_pages
            n_layers, pages = runner.model.config.num_layers, np.asarray(table)[: -(-300 // 16)]
            ids = (np.arange(n_layers)[:, None] * (kv["k"].shape[0] // n_layers) + pages[None, :]).reshape(-1)
        return (int(np.asarray(toks)[j]), float(np.asarray(chosen)[j]), np.asarray(tvals)[j],
                np.asarray(kv["k"][ids], np.float32), np.asarray(kv["v"][ids], np.float32))

    one_lane = prefill("A", [(1, 512, [(0, 300)])])
    one_call = prefill("B", [(3, 128, [(0, 128), (128, 256), (256, 300)])])
    three_calls = prefill("C", [(1, 128, [(0, 128)]), (1, 128, [(128, 256)]), (1, 128, [(256, 300)])])
    for other in (one_lane, three_calls):
        assert one_call[0] == other[0]
        np.testing.assert_allclose(one_call[1], other[1], atol=ATOL)
        np.testing.assert_allclose(one_call[2], other[2], atol=ATOL)
        # 300 tokens fill 18 pages and 12 rows of the 19th: compare what was written
        for got, want in zip(one_call[3:], other[3:]):
            got = got.reshape(-1, 19, *got.shape[1:])[:, :18]
            want = want.reshape(-1, 19, *want.shape[1:])[:, :18]
            assert np.abs(got).max() > 0
            np.testing.assert_allclose(got, want, atol=ATOL)


# ---------------- (d) warm-up compiles what the packer can emit ----------------


@pytest.mark.parametrize("model_id", ["tiny", "tiny-window", "tiny-hybrid", "tiny-conv"])
def test_every_program_the_packer_can_emit_is_in_warm_ups_list(model_id):
    """The sets compared, nothing compiled: every (N, T) of the packer on
    every rung of the page-table ladder; for the blocks, all of it on the first
    and the last rung before readiness; for the rectangles on a ladder of four
    rungs, the parent's lists."""
    recurrent = model_id in ("tiny-hybrid", "tiny-conv")  # the recurrent contract's two signers
    over = dict(prefill_buckets=(64, 128, 256, 512), page_size=16, num_pages=64,
                max_model_len=16384 if recurrent else 8192)
    if not recurrent:
        over["prefill_lanes"] = 2  # the blocks take no count from it
    eng = _hand_engine(model_id, **over)
    runner, c = eng.runner, eng.config
    assert c.table_buckets == ((128, 256, 512, 1024) if recurrent else (128, 256, 512))
    core, later = runner.packed_warmup_shapes()
    assert len(set(core + later)) == len(core + later)
    if runner.recurrent:
        # a ladder past three rungs: 11 (bucket, power-of-two N) shapes on the
        # first rung, 8 of them before readiness, and one chunk at N = 1 a
        # wider rung. What a long prompt's packs meet beyond that (N over 1 on
        # the wider rungs, the shorter buckets there) compiles in traffic: 33
        # more programs before readiness would hold a start-up for every one
        assert not runner.warms_every_rung
        assert [c.lanes_for(b) for b in c.prefill_buckets] == [4, 4, 4, 2]
        assert [c.lanes_for(b, wide=True) for b in c.prefill_buckets] == [2, 2, 2, 2]
        assert sorted(core) == sorted((n, b, 128) for b in c.prefill_buckets for n in {1, c.lanes_for(b)})
        assert len([s for s in core + later if s[2] == 128]) == 11
        assert [s for s in later if s[2] != 128] == [(1, 512, 256), (1, 512, 512), (1, 256, 1024)]
        return
    # what the packer emits: driven over many pending sets, not read off the runner
    rng = np.random.default_rng(7)
    emitted = set()
    for _ in range(400):
        pending = [(0, int(rng.integers(1, 513))) for _ in range(int(rng.integers(1, 12)))]
        emitted.add((len(plan_block_pack(pending, c.prefill_block, c.pack_blocks)), c.prefill_block))
    assert emitted == {(n, 128) for n in range(1, 9)} == set(runner.packed_prefill_shapes())
    assert {(n, t, w) for n, t in emitted for w in c.table_buckets} == set(core + later)
    assert set(core) == {(n, 128, w) for n in range(1, 9) for w in (128, 512)}
    # 8 shapes a rung and feature variant where the rectangles took 11
    assert len(runner.packed_prefill_shapes()) == 8
    thunks = runner.warmup_extra_thunks()
    # decode: 3 variants + 2 wider rungs; per-request: 3 variants + 4 buckets;
    # packed: 8 on the middle rung + 3 feature variants x 8 on the first
    assert len(thunks) == 3 + 2 + 3 + 4 + 8 + 3 * 8


@pytest.mark.parametrize("model_id,max_model_len,ladder", [
    ("tiny-conv", 5120, (128, 256, 320)),  # lfm2-8b-a1b-d16's ladder
    ("tiny-hybrid", 4096, (128, 256)),  # nemotron3-super-ep4's
    ("tiny-hybrid", 8192, (128, 256, 512)),
])
def test_a_recurrent_model_on_a_short_ladder_meets_no_new_step_program_in_traffic(
        monkeypatch, model_id, max_model_len, ladder):
    """Nothing compiled: the scheduler's own rectangle packer driven over
    pending sets of every kind (prompts of every length at every chunk the
    planner cuts, one to eight at once), and warm-up's core with the two
    runner calls that compile replaced by a record. Every (lanes, bucket,
    table width) the packer emits and a decode window on every rung are
    compiled BEFORE readiness, and nothing is left behind it.

    (PR 44: with N = 1 and N = lanes_for on the first rung alone before
    readiness, `lfm2-8b-a1b-d16.rag-over` opened every window with five of
    the then 33 rectangles never compiled, all of them four lanes on the 256
    or the 320 rung; the driver read one run of the same change (PR 43) as not
    `correct`, and a compile in the window is one of the three things it checks.
    A pack that holds a lane beyond the first rung now takes two lanes at
    most, so those five and (4, 128, 256) are no programs any more: 11
    rectangles on the first rung and 8 on each wider one.)"""
    from types import SimpleNamespace

    from dynamo_tpu.engine.scheduler import Scheduler

    eng = _hand_engine(model_id, prefill_buckets=(64, 128, 256, 512), page_size=16,
                       num_pages=64, max_model_len=max_model_len, max_seqs=8)
    runner, c = eng.runner, eng.config
    assert c.table_buckets == ladder and runner.warms_every_rung

    rng = np.random.default_rng(44)
    emitted = set()
    for _ in range(6000):
        pending = []
        for slot in range(int(rng.integers(1, 9))):
            # some whole chunks and a tail of any bucket, as often at its tail
            # as anywhere: four tails of 64 beside a long prompt are rare otherwise
            tail = int(rng.integers(1, rng.choice(c.prefill_buckets) + 1))
            prompt = min(int(rng.integers(0, 11)) * c.max_prefill_chunk + tail, max_model_len - 1)
            cuts = [0]
            while cuts[-1] + c.chunk_len_for(cuts[-1]) < prompt:
                cuts.append(cuts[-1] + c.chunk_len_for(cuts[-1]))
            pages = -(-prompt // c.page_size)
            pending.append(SimpleNamespace(
                prefill_pos=int(rng.choice([cuts[-1], rng.choice(cuts)])), prompt_len=prompt,
                slot=slot, finished=False,
                page_table=np.zeros(c.table_bucket_for(pages), np.int32)))
        me = SimpleNamespace(config=c, grouped=False, slots=pending)
        backlog = sum(s.prompt_len - s.prefill_pos for s in pending)
        chunks, bucket, N = Scheduler._pack_rectangle(me, pending, backlog, [])
        # the width as `_dispatch_prefill_batches` and `pack_prefill_lanes` take it
        width = c.table_bucket_for(max(s.page_table.shape[-1] for s, _, _ in chunks))
        emitted.add((N, bucket, width))
    core, later = runner.packed_warmup_shapes()
    assert later == [] and len(core) == len(set(core)) == 11 + 8 * (len(ladder) - 1)
    assert emitted == set(core)
    assert {n for n, _, w in emitted if w > ladder[0]} == {1, 2}

    compiled = {"packed": [], "windows": []}
    monkeypatch.setattr(runner, "_warm_packed", lambda n, t, w: compiled["packed"].append((n, t, w)))
    monkeypatch.setattr(runner, "dispatch_decode_window",
                        lambda pos, tables, *a, **kw: compiled["windows"].append((tables.shape, a[-1], kw)))
    runner.warmup_core()
    assert compiled["packed"] == core
    # `_batch_tables`: a window is as wide as its widest sequence's rung
    assert compiled["windows"] == [((8, w), c.decode_steps, {}) for w in ladder]
    # behind readiness: the feature variants and the per-request trace alone
    # (decode 3, per-request 3 + 4, packed 3 x 11 on the first rung)
    assert len(runner.warmup_extra_thunks()) == 3 + 3 + 4 + 3 * 11


#: name -> (pending [(prompt_len, prefill_pos)], the pack [(N, bucket, width), lanes taken])
WIDE_PACKS = {
    # four short chunks on the first rung: one pack of four, as ever
    "four_short": ([(200, 0), (100, 0), (60, 0), (50, 0)], ((4, 256, 128), 4)),
    # the first is the tail of a prompt of 2248 tokens (141 pages: the 256 rung):
    # the parent packed (4, 256, 256), a program no warm-up met
    "deep_tail_first": ([(2248, 2048), (100, 0), (60, 0), (50, 0)], ((2, 256, 256), 2)),
    # the deep one comes third: the pack closes before it would be the third lane
    "deep_tail_third": ([(100, 0), (60, 0), (2248, 2048), (50, 0)], ((2, 128, 128), 2)),
    # and second: two lanes, the pack is on the wider rung
    "deep_tail_second": ([(100, 0), (4200, 4096), (60, 0)], ((2, 128, 320), 2)),
    # chunks of 512 were two a pack on every rung already
    "deep_heads": ([(4000, 512), (3000, 1024), (100, 0)], ((2, 512, 256), 2)),
    "deep_alone": ([(4000, 3584)], ((1, 512, 256), 1)),
}


@pytest.mark.parametrize("name", WIDE_PACKS)
def test_a_rectangle_beyond_the_first_rung_takes_two_lanes(name):
    from types import SimpleNamespace

    from dynamo_tpu.engine.scheduler import Scheduler

    c = EngineConfig(model_id="tiny-conv", page_size=16, max_model_len=5120, num_pages=64,
                     prefill_buckets=(64, 128, 256, 512), prefill_lanes=4)
    asked, (shape, taken) = WIDE_PACKS[name]
    pending = [SimpleNamespace(prefill_pos=pos, prompt_len=n, slot=i, finished=False,
                               page_table=np.zeros(c.table_bucket_for(-(-n // c.page_size)), np.int32))
               for i, (n, pos) in enumerate(asked)]
    me = SimpleNamespace(config=c, grouped=False, slots=pending)
    chunks, bucket, N = Scheduler._pack_rectangle(me, pending, sum(n - p for n, p in asked), [])
    width = c.table_bucket_for(max(s.page_table.shape[-1] for s, _, _ in chunks))
    assert ((N, bucket, width), len(chunks)) == (shape, taken)
    assert [s.slot for s, _, _ in chunks] == list(range(taken))  # admission order, no one skipped
