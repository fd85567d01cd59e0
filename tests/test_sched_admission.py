"""Admission-order unit tests (no JAX): the max_model_len rejection is pure
host work and must run BEFORE the per-step fairness-cap break, so an oversized
prompt at the queue head fails in the same scheduler step instead of stalling
behind the cap (ADVICE r5)."""

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.page_table import PageAllocator
from dynamo_tpu.engine.scheduler import EngineRequest, RunningSeq, Scheduler


class _StubRunner:
    """Just enough runner surface for Scheduler._admit's control flow."""

    packed_prefill_mode = False

    lora_store = None

    def write_token_slots(self, slots, tokens):  # pragma: no cover
        pass

    def set_slot_lora(self, slot, lora_slot):  # pragma: no cover
        pass


def _scheduler(max_model_len=64, cap=1):
    cfg = EngineConfig(
        model_id="tiny", page_size=4, num_pages=64, max_seqs=4,
        max_model_len=max_model_len, prefill_batches_per_step=cap,
    )
    alloc = PageAllocator(cfg.num_pages, cfg.page_size)
    return Scheduler(cfg, _StubRunner(), alloc)


def _occupy_decode_slot(sched):
    """A running decode sequence (prefill done) makes the fairness cap bind."""
    seq = RunningSeq(
        req=EngineRequest("running", [1, 2, 3]), slot=0, prompt_len=3,
        cached_len=0, prefill_pos=None,
    )
    sched.slots[0] = seq
    return seq


def test_oversized_prompt_rejected_before_fairness_cap(monkeypatch):
    sched = _scheduler(max_model_len=8, cap=1)
    _occupy_decode_slot(sched)

    # admission itself stubbed out: this test is about _admit's ORDERING, not
    # the prefill dispatch it triggers
    started = []

    def fake_start(req, slot, lora_slot=0):
        sched.slots[slot] = RunningSeq(
            req=req, slot=slot, prompt_len=len(req.token_ids), cached_len=0,
            prefill_pos=None,
        )
        started.append(req.request_id)

    monkeypatch.setattr(sched, "_start_sequence", fake_start)

    sched.add_request(EngineRequest("ok-1", [1] * 4))
    sched.add_request(EngineRequest("too-long", [1] * 99))  # > max_model_len
    sched.add_request(EngineRequest("ok-2", [1] * 4))

    outputs = sched._admit()

    # ok-1 consumed the per-step cap; the oversized request must STILL fail in
    # this same step (pure rejection, no chip work), leaving ok-2 to wait
    assert started == ["ok-1"]
    errors = [o for o in outputs if o.finish_reason == "error"]
    assert [o.request_id for o in errors] == ["too-long"]
    assert [r.request_id for r in sched.waiting] == ["ok-2"]


def test_oversized_rejection_does_not_consume_the_cap(monkeypatch):
    sched = _scheduler(max_model_len=8, cap=1)
    _occupy_decode_slot(sched)
    started = []

    def fake_start(req, slot, lora_slot=0):
        sched.slots[slot] = RunningSeq(
            req=req, slot=slot, prompt_len=len(req.token_ids), cached_len=0,
            prefill_pos=None,
        )
        started.append(req.request_id)

    monkeypatch.setattr(sched, "_start_sequence", fake_start)

    # oversized at the HEAD: rejected immediately, and the request behind it
    # still gets this step's one capped start
    sched.add_request(EngineRequest("too-long", [1] * 99))
    sched.add_request(EngineRequest("ok-1", [1] * 4))

    outputs = sched._admit()
    assert [o.request_id for o in outputs if o.finish_reason == "error"] == ["too-long"]
    assert started == ["ok-1"]
    assert not sched.waiting


def test_reserved_pages_do_not_hold_a_request_back(monkeypatch):
    """A sequence's unwritten run (PR 47: pages reserved for it to grow into)
    counts as free at admission: a prompt that fits only with those pages is
    started, and the allocator takes them back for it."""
    cfg = EngineConfig(model_id="tiny", page_size=4, num_pages=4 * 8, max_seqs=4,
                       max_model_len=256, prefill_batches_per_step=4)
    alloc = PageAllocator(cfg.num_pages, cfg.page_size, tile_pages=8)
    sched = Scheduler(cfg, _StubRunner(), alloc)
    for i in range(3):  # each takes a whole tile for its one page: 3 held, 21 reserved
        alloc.allocate_sequence(f"r{i}", [i + 1] * 4)
    assert (alloc.active_pages, alloc.reserved_pages, alloc._free.free) == (3, 21, 7)
    started = []

    def fake_start(req, slot, lora_slot=0):
        alloc.allocate_sequence(req.request_id, req.token_ids)
        sched.slots[slot] = RunningSeq(req=req, slot=slot, prompt_len=len(req.token_ids),
                                       cached_len=0, prefill_pos=None)
        started.append(req.request_id)

    monkeypatch.setattr(sched, "_start_sequence", fake_start)
    sched.add_request(EngineRequest("big", list(range(100, 100 + 20 * 4))))  # 20 pages of 7 free
    sched._admit()
    assert started == ["big"] and not sched.waiting
    assert alloc.active_pages == 23 and alloc.reserved_pages == 8
