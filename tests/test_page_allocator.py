"""PageAllocator: prefix cache, refcounting, LRU reuse, KV events."""

import pytest

from dynamo_tpu.engine.page_table import PageAllocator
from dynamo_tpu.llm.tokens import TokenSequence

PS = 4


def make(num_pages=8, events=None):
    sink = events.append if events is not None else None
    return PageAllocator(num_pages, PS, event_sink=sink)


def test_basic_allocation_and_free():
    a = make()
    cached, st = a.allocate_sequence("s1", list(range(10)))  # 3 pages
    assert cached == 0
    assert len(st.pages) == 3
    assert 0 not in st.pages  # null page never allocated
    assert a.active_pages == 3
    a.commit_prefilled("s1", 10)
    a.free_sequence("s1")
    assert a.active_pages == 0
    # 2 full blocks stay cached (reusable), 1 partial page freed
    assert a.free_pages == 7


def test_prefix_cache_hit_and_sharing():
    events = []
    a = make(events=events)
    prompt = list(range(8))  # 2 full blocks
    a.allocate_sequence("s1", prompt + [99, 98])
    a.commit_prefilled("s1", 10)
    stored = [e for e in events if e.kind == "stored"]
    assert len(stored) == 2  # two full blocks registered

    # second sequence with the same 8-token prefix
    cached, st2 = a.allocate_sequence("s2", prompt + [55, 44, 33, 22, 11])
    assert cached == 8
    st1 = a._seqs["s1"]
    assert st2.pages[:2] == st1.pages[:2]  # physical sharing
    assert a._refcount[st1.pages[0]] == 2

    a.free_sequence("s1")
    # shared pages still referenced by s2
    assert a._refcount[st2.pages[0]] == 1
    a.free_sequence("s2")


def test_full_prompt_cache_hit_leaves_one_block_to_prefill():
    a = make()
    prompt = list(range(8))
    a.allocate_sequence("s1", prompt)
    a.commit_prefilled("s1", 8)
    a.free_sequence("s1")
    cached, st = a.allocate_sequence("s2", prompt)
    assert cached == 4  # not 8: last block must be prefilled for logits


def test_lru_eviction_emits_removed():
    events = []
    a = make(num_pages=6, events=events)  # 5 usable pages
    a.allocate_sequence("s1", list(range(8)))  # 2 pages, both full blocks
    a.commit_prefilled("s1", 8)
    a.free_sequence("s1")  # both pages now reusable
    assert a.free_pages == 5

    # allocating 5 pages forces reclaim of the cached blocks (LRU order);
    # the batched reclaim may coalesce them into one removed event, so the
    # contract is the set of advertised hashes, not the event count
    a.allocate_sequence("s2", list(range(100, 120)))  # 5 pages
    removed_hashes = [
        h for e in events if e.kind == "removed" for h in e.block_hashes
    ]
    assert len(removed_hashes) == 2
    assert a.free_pages == 0

    with pytest.raises(MemoryError):
        a.allocate_sequence("s3", [1, 2, 3, 4])


def test_decode_block_completion_registers_one_token_late():
    events = []
    a = make(events=events)
    a.allocate_sequence("s1", [1, 2, 3])  # partial block
    a.commit_prefilled("s1", 3)
    assert not [e for e in events if e.kind == "stored"]
    # completing block 0 must NOT register it yet: the block's last row's
    # KV is only written once token 4 is FED, which the appearance of token
    # 5 proves — registering at fill time advertised a block whose final
    # position read garbage to any sequence extending past it
    a.append_token("s1", 4)
    assert not [e for e in events if e.kind == "stored"]
    a.append_token("s1", 5)
    stored = [e for e in events if e.kind == "stored"]
    assert len(stored) == 1
    ts = TokenSequence([1, 2, 3, 4], PS)
    assert stored[0].blocks[0].block_hash == ts.blocks[0].sequence_hash


def test_ensure_capacity_grows_and_fails():
    a = make(num_pages=4)  # 3 usable
    a.allocate_sequence("s1", [1, 2, 3, 4])
    assert a.ensure_capacity("s1", 12)  # 3 pages
    assert not a.ensure_capacity("s1", 13)  # would need a 4th


def test_oom_rollback_restores_state():
    a = make(num_pages=4)
    with pytest.raises(MemoryError):
        a.allocate_sequence("big", list(range(100)))
    assert a.free_pages == 3
    assert "big" not in a._seqs


# ---------------------------------------------------------------- runs of a tile
# (PR 47): a sequence takes its fresh pages a whole aligned tile of the pool at
# a time, so that the decode attention kernel fetches a tile in one copy; the
# page stays the unit of hashing, sharing, refcounts and events

TP = 8  # pages per tile, as `decode_tile_pages` gives every benchmark cell


def make_tiled(tiles=20, events=None, **kw):
    """A pool of `tiles` whole tiles beside the null page's (7 loose pages)."""
    sink = events.append if events is not None else None
    return PageAllocator((tiles + 1) * TP, PS, event_sink=sink, tile_pages=TP, **kw)


def runs_of(st):
    """Per tile of the sequence's table: its entries are first, first + 1, ...
    (what the kernel's flag says)."""
    e = st.entries
    return [e[i:i + TP] == list(range(e[i], e[i] + TP)) for i in range(0, len(st.pages), TP)]


def check(a):
    """The counters agree with a walk over the running sequences."""
    seqs = list(a._seqs.values())
    assert a.reserved_pages == sum(len(s.reserved) for s in seqs)
    assert a.tiles == sum(-(-len(s.pages) // TP) for s in seqs)
    assert a.run_tiles == sum(sum(runs_of(s)) for s in seqs)
    held = {p for s in seqs for p in s.pages}
    assert a.active_pages == len(held)
    assert a.free_pages == (a.num_pages - 1) - len(held)
    assert set(a._reserving) == {s.seq_id for s in seqs if s.reserved}


def test_a_sequence_takes_its_pages_by_runs_of_a_tile():
    a = make_tiled()
    _, st = a.allocate_sequence("s1", list(range(19 * PS)))  # 19 pages: 2 tiles and 3 pages
    assert len(st.pages) == 19 and len(st.reserved) == 5
    assert runs_of(st) == [True, True, True]
    for t in range(3):  # aligned in the pool and in the sequence's logical pages
        assert st.entries[t * TP] % TP == 0
    # the reserved tail holds no token: not active, not used, still free to others
    assert (a.active_pages, a.reserved_pages, a.used_pages) == (19, 5, 19)
    assert a.free_pages == 20 * TP + 7 - 19
    assert (a.tiles, a.run_tiles) == (3, 3)
    # growing through the reserved pages takes nothing from the pool
    free = a._free.free
    assert a.ensure_capacity("s1", 24 * PS)
    assert a._free.free == free and st.reserved == [] and a.reserved_pages == 0
    assert a.ensure_capacity("s1", 24 * PS + 1)  # the next tile, one page of it needed
    assert len(st.pages) == 25 and len(st.reserved) == 7 and runs_of(st) == [True] * 4
    check(a)
    a.free_sequence("s1")
    assert (a.tiles, a.run_tiles, a.reserved_pages, a.active_pages) == (0, 0, 0, 0)
    assert a.free_pages == 20 * TP + 7


def test_tiles_are_runs_after_a_churn_of_admissions_and_releases():
    """A few hundred sequences come, decode and go with the prefix cache on
    (every full block registered, so the free list runs dry and every page
    comes out of the cache's LRU): a new sequence still finds its runs."""
    import random

    rng = random.Random(47)
    events = []
    a = make_tiled(tiles=64, events=events)
    running, shares = [], []
    for n in range(400):
        while len(running) >= 6 or (running and rng.random() < 0.3):
            a.free_sequence(running.pop(rng.randrange(len(running))))
        rid = f"r{n}"
        prompt = [rng.randrange(1 << 20) for _ in range(rng.randrange(20, 300))]
        _, st = a.allocate_sequence(rid, prompt)
        a.commit_prefilled(rid, len(prompt))
        running.append(rid)
        for other in running:  # everyone decodes a few tokens, a page at a time
            so = a._seqs[other]
            for _ in range(rng.randrange(0, 12)):
                assert a.ensure_capacity(other, len(so.token_seq) + 1)
                a.append_token(other, rng.randrange(1 << 20))
        check(a)
        if n >= 100:
            shares.append(a.run_tiles / a.tiles)
            assert all(runs_of(st)), f"admission {n} got scattered tiles"
    assert a._free.free < 64 * TP // 4  # the pool did fill with cached blocks
    assert min(shares) == 1.0
    # every evicted block told the router, once
    stored = [b.block_hash for e in events if e.kind == "stored" for b in e.blocks]
    removed = [h for e in events if e.kind == "removed" for h in e.block_hashes]
    assert removed and len(removed) == len(set(removed)) and set(removed) <= set(stored)
    assert len(stored) - len(removed) == len(a._cache)


def test_reserved_pages_are_given_back_before_anyone_is_refused():
    a = make_tiled(tiles=3)  # 24 pages in tiles + 7 loose
    _, s1 = a.allocate_sequence("s1", list(range(PS)))  # a tile: 1 page + 7 reserved
    _, s2 = a.allocate_sequence("s2", list(range(100, 100 + PS)))
    _, s3 = a.allocate_sequence("s3", list(range(200, 200 + PS)))
    assert a.reserved_pages == 21 and a.active_pages == 3
    # admission's reckoning (scheduler: free_pages against the prompt's need)
    # counts the reserved pages: nobody is held back for them
    assert a.free_pages == 31 - 3
    _, big = a.allocate_sequence("big", list(range(1000, 1000 + 20 * PS)))  # 7 loose + 13 taken back
    assert len(big.pages) == 20 and a.reserved_pages == 8 and a.active_pages == 23
    check(a)
    # what a sequence keeps of its run is the run's start: it grows into it
    kept = list(s3.reserved)
    assert kept == list(range(s3.pages[0] + 1, s3.pages[0] + 1 + len(kept)))
    # ensure_capacity succeeds on pages taken back, down to the last one
    assert a.ensure_capacity("big", 28 * PS)
    assert a.reserved_pages == 0 and a.free_pages == 0 and a.active_pages == 31
    assert not a.ensure_capacity("s1", 2 * PS)  # now the pool is full, and says so
    with pytest.raises(MemoryError):
        a.allocate_sequence("late", [1])
    check(a)
    # a sequence whose reserved pages went grows by single pages, and its
    # last tile is no run until a whole one is its own again
    a.free_sequence("big")
    assert a.ensure_capacity("s1", 3 * PS)
    assert runs_of(s1) == [False] and (a.tiles, a.run_tiles) == (3, 0)
    check(a)


def test_a_shared_prefix_keeps_its_runs_for_the_sharer():
    events = []
    a = make_tiled(events=events)
    prompt = list(range(20 * PS))  # 20 pages: 2 tiles and a half
    _, w = a.allocate_sequence("writer", prompt + [7])
    a.commit_prefilled("writer", len(prompt) + 1)
    cached, s = a.allocate_sequence("sharer", prompt + [9, 9])
    assert cached == 20 * PS and s.pages[:20] == w.pages[:20] and s.shared_prefix_pages == 20
    # the whole shared tiles are runs for the sharer; the tile where shared
    # and own pages meet is none, and the pages past it start a tile of its own
    assert runs_of(s) == [True, True, False]
    assert (a.tiles, a.run_tiles) == (6, 5)
    check(a)
    a.free_sequence("writer")
    assert (a.tiles, a.run_tiles) == (3, 2)
    check(a)
    # a third one after both left: the cached prefix is still where it was
    a.free_sequence("sharer")
    cached, t = a.allocate_sequence("third", prompt + [5])
    assert cached == 20 * PS and runs_of(t)[:2] == [True, True]
    check(a)


def test_the_host_tier_restore_still_gets_its_pages():
    import numpy as np

    from dynamo_tpu.engine.offload import HostKvPool
    from dynamo_tpu.models.paged import PagedModel

    class _Runner:  # host-pool transfers without a device
        model = PagedModel(None)  # the contract's defaults: `wire_n_axis`

        def extract_pages(self, ids):
            return np.zeros((1, 2, len(ids), PS, 1, 2), np.float32)

        def inject_pages_bucketed(self, ids, data, axis=None):
            pass

    events = []
    pool = HostKvPool(_Runner(), capacity_blocks=64)
    a = make_tiled(tiles=4, events=events, offload=pool)
    prompt = list(range(1, 1 + 16 * PS))  # two tiles of full blocks
    a.allocate_sequence("a", prompt + [3])
    a.commit_prefilled("a", len(prompt) + 1)
    a.free_sequence("a")
    # fillers push the cached blocks out to the host tier, a run at a time
    for i in range(4):
        a.allocate_sequence(f"f{i}", list(range(1000 * (i + 1), 1000 * (i + 1) + 8 * PS)))
    assert len(pool) == 16 and not [e for e in events if e.kind == "removed"]
    for i in range(4):
        a.free_sequence(f"f{i}")
    cached, st = a.allocate_sequence("again", prompt + [3])
    assert cached == 16 * PS and len(st.pages) == 17
    assert runs_of(st) == [True, True, True]  # restored into runs, and the rest
    assert len(pool) == 0 and all(h in a._cache for h in st.registered_hashes)
    check(a)


def test_a_tile_of_one_page_is_the_allocator_it_was():
    a = make(num_pages=8)  # tile_pages 1: nothing is reserved, nothing is a run
    _, st = a.allocate_sequence("s1", list(range(10)))
    assert st.reserved == [] and a.reserved_pages == 0 and (a.tiles, a.run_tiles) == (3, 0)
    assert st.entries is st.pages
