"""Fleet-wide prefix cache: cross-worker KV pull over the dataplane.

Correctness bar: a worker that pulls a peer's cached prefix instead of
recomputing it must produce TOKEN-IDENTICAL output (the injected KV equals
the locally-computed KV), and every failure mode — dead peer, black-holed
connection, holder death mid-stream, evicted blocks ("gone") — must degrade
to recompute, never to an error or a wedged admission queue.
"""

import asyncio
import time

import pytest

from dynamo_tpu.engine.sampling import SamplingParams
from dynamo_tpu.engine.scheduler import EngineRequest

# 24 tokens -> 6 full blocks at page_size 4; the fetchable prefix caps at
# (24 - 1) // 4 = 5 blocks (the last token must prefill for logits)
PROMPT = [5, 9, 2, 77, 31, 8, 100, 42, 17, 3, 60, 61,
          7, 13, 19, 23, 29, 37, 41, 43, 47, 53, 59, 67]


def _req(rid, prompt, n=6, holder="", blocks=0):
    return EngineRequest(
        request_id=rid,
        token_ids=list(prompt),
        sampling=SamplingParams(temperature=0.0, max_tokens=n),
        kv_holder_addr=holder,
        kv_holder_blocks=blocks,
    )


async def _collect(engine, req):
    toks, finish, cached = [], None, 0
    async for out in engine.generate(req):
        if out.token is not None:
            toks.append(out.token)
        cached = max(cached, out.cached_tokens)
        if out.finished:
            finish = out.finish_reason
    return toks, finish, cached


def _engine(**over):
    from dynamo_tpu.engine.engine import AsyncJaxEngine

    from tests.test_engine import tiny_engine_config

    return AsyncJaxEngine(tiny_engine_config(**over))


# ---------------- two-engine loopback: pull + token parity ----------------


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_cross_worker_pull_token_parity(kv_dtype):
    """Worker B pulls worker A's prefix over the wire and must emit exactly
    the tokens A emits (greedy, same weights) while skipping the prefix
    recompute — with both the bf16 and the int8 KV cache (int8 pages ride
    the wire with their scale planes in the part headers)."""
    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient

    async def body():
        holder = _engine(kv_cache_dtype=kv_dtype)
        await holder.start()
        puller = _engine(kv_cache_dtype=kv_dtype)
        await puller.start()
        srv = None
        try:
            expected, finish, _ = await _collect(holder, _req("seed", PROMPT))
            assert finish == "length" and len(expected) == 6
            srv = await KvPullServer(holder, host="127.0.0.1").start()
            puller.attach_prefix_fetch(
                PrefixFetchClient(asyncio.get_running_loop(), timeout_s=30.0)
            )
            got, finish, cached = await _collect(
                puller, _req("pull", PROMPT, holder=srv.address, blocks=6)
            )
            assert got == expected, f"pulled {got} != recompute {expected}"
            assert finish == "length"
            sched = puller.scheduler
            assert sched.prefix_fetch_hits == 1
            assert sched.prefix_fetch_fallbacks == 0
            assert sched.prefix_fetch_blocks == 5  # capped at (24-1)//4
            assert sched.prefix_fetch_tokens == 20
            assert cached >= 20  # pulled prefix reported like a local hit
            assert srv.served == 1
            assert srv.served_blocks["hbm"] == 5
            assert srv.bytes_sent > 0
            res = puller.resource_snapshot()
            assert res["prefix_fetch_blocks"] == 5
            assert res["prefix_fetch_bytes"] == srv.bytes_sent
            # the pulled blocks registered locally: a repeat request is now a
            # plain local hit, no second fetch
            got2, _, cached2 = await _collect(
                puller, _req("pull2", PROMPT, holder=srv.address, blocks=6)
            )
            assert got2 == expected
            assert sched.prefix_fetch_hits == 1  # no new fetch
            assert cached2 >= 20
        finally:
            if srv is not None:
                await srv.stop()
            await holder.shutdown()
            await puller.shutdown()

    asyncio.run(body())


def test_cross_worker_pull_mixed_dtype_peers():
    """An int8 holder serving a bf16 puller still works end to end: the
    {"q","s"} wire block dequantizes into the bf16 cache at scatter time
    (scatter_pages_wire) — functional interop, no exact-parity claim across
    the dtype boundary."""
    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient

    async def body():
        holder = _engine(kv_cache_dtype="int8")
        await holder.start()
        puller = _engine()  # bf16 cache
        await puller.start()
        srv = None
        try:
            await _collect(holder, _req("seed", PROMPT))
            srv = await KvPullServer(holder, host="127.0.0.1").start()
            puller.attach_prefix_fetch(
                PrefixFetchClient(asyncio.get_running_loop(), timeout_s=30.0)
            )
            got, finish, cached = await _collect(
                puller, _req("pull", PROMPT, holder=srv.address, blocks=6)
            )
            assert finish == "length" and len(got) == 6
            assert puller.scheduler.prefix_fetch_hits == 1
            assert cached >= 20
        finally:
            if srv is not None:
                await srv.stop()
            await holder.shutdown()
            await puller.shutdown()

    asyncio.run(body())


# ---------------- failure ladder: everything degrades to recompute ----------------


def test_fetch_failures_degrade_to_recompute():
    """Dead peer, black-holed connection (timeout), holder death mid-fetch,
    and evicted blocks ("gone") all fall back to recompute — the request
    completes normally and admission never wedges."""
    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient

    async def body():
        puller = _engine(prefix_fetch_timeout_s=0.4)
        await puller.start()
        fetcher = PrefixFetchClient(asyncio.get_running_loop(), timeout_s=0.4)
        puller.attach_prefix_fetch(fetcher)
        sched = puller.scheduler

        def prompt(seed):
            return [(seed * 97 + i * 13) % 400 + 1 for i in range(24)]

        blackhole_conns = []

        async def _blackhole(reader, writer):
            blackhole_conns.append(writer)  # accept, never answer

        async def _die_mid_fetch(reader, writer):
            await reader.readexactly(4)  # start reading the request frame...
            writer.close()  # ...and die

        blackhole = await asyncio.start_server(_blackhole, "127.0.0.1", 0)
        killer = await asyncio.start_server(_die_mid_fetch, "127.0.0.1", 0)
        bh_port = blackhole.sockets[0].getsockname()[1]
        k_port = killer.sockets[0].getsockname()[1]
        try:
            # (a) connection refused: resolves as an error, fast
            toks, finish, _ = await _collect(
                puller, _req("dead", prompt(1), holder="127.0.0.1:9", blocks=6)
            )
            assert finish == "length" and len(toks) == 6
            assert sched.prefix_fetch_fallbacks == 1

            # (b) black hole: the fetch timeout bounds the stall
            t0 = time.monotonic()
            toks, finish, _ = await _collect(
                puller,
                _req("blackhole", prompt(2), holder=f"127.0.0.1:{bh_port}", blocks=6),
            )
            assert finish == "length" and len(toks) == 6
            assert sched.prefix_fetch_fallbacks == 2
            assert fetcher.results.get("timeout", 0) == 1
            assert time.monotonic() - t0 < 30.0

            # (c) holder dies mid-fetch: clean error, immediate fallback
            toks, finish, _ = await _collect(
                puller,
                _req("killer", prompt(3), holder=f"127.0.0.1:{k_port}", blocks=6),
            )
            assert finish == "length" and len(toks) == 6
            assert sched.prefix_fetch_fallbacks == 3

            # (d) holder alive but blocks not there: a clean "gone" response,
            # not a timeout (self-pull: our own pull server, blocks of a
            # prompt we never cached)
            srv = await KvPullServer(puller, host="127.0.0.1").start()
            try:
                toks, finish, _ = await _collect(
                    puller, _req("gone", prompt(4), holder=srv.address, blocks=6)
                )
                assert finish == "length" and len(toks) == 6
                assert sched.prefix_fetch_fallbacks == 4
                assert srv.gone == 1
                assert fetcher.results.get("gone", 0) == 1
            finally:
                await srv.stop()
            assert sched.prefix_fetch_hits == 0
        finally:
            blackhole.close()
            killer.close()
            for w in blackhole_conns:
                w.close()
            await puller.shutdown()

    asyncio.run(body())


# ---------------- eviction truthfulness ----------------


def test_eviction_publishes_removed_events():
    """Every block the allocator reclaims from the prefix cache (no host
    tier) must emit a `removed` event carrying the same block identity its
    `stored` event advertised — so no router ever points a fetch at a block
    the holder no longer has."""
    from dynamo_tpu.engine.page_table import PageAllocator

    events = []
    alloc = PageAllocator(num_pages=6, page_size=4, event_sink=events.append)
    alloc.allocate_sequence("a", list(range(1, 17)))  # 4 blocks
    alloc.commit_prefilled("a", 16)
    alloc.free_sequence("a")
    stored = [b.block_hash for e in events if e.kind == "stored" for b in e.blocks]
    assert len(stored) == 4
    # a second sequence forces reclaim of 3 reusable blocks (1 page was free)
    alloc.allocate_sequence("b", list(range(101, 117)))
    removed = [h for e in events if e.kind == "removed" for h in e.block_hashes]
    assert len(removed) == 3
    assert set(removed) <= set(stored)
    # advertised-minus-removed is exactly what the pull server can still find
    live = set(stored) - set(removed)
    assert live and all(alloc.cached_page(h) is not None for h in live)
    assert all(alloc.cached_page(h) is None for h in removed)


def test_offload_drop_publishes_removed_once_gone_from_all_tiers():
    """With a host tier, reclaiming a device block is NOT a removal (the
    block is still pullable from the host pool); only the host-LRU drop —
    the block leaving its last tier — emits `removed`."""
    from dynamo_tpu.engine.page_table import PageAllocator
    from dynamo_tpu.models.paged import PagedModel

    class _Runner:  # host-pool transfers without a device
        model = PagedModel(None)  # the contract's defaults: `wire_n_axis`

        def extract_pages(self, ids):
            import numpy as np

            return np.zeros((1, 2, len(ids), 4, 1, 2), np.float32)

        def inject_pages_bucketed(self, ids, data, axis=None):
            pass

    from dynamo_tpu.engine.offload import HostKvPool

    events = []
    pool = HostKvPool(_Runner(), capacity_blocks=2)
    alloc = PageAllocator(num_pages=6, page_size=4,
                          event_sink=events.append, offload=pool)
    alloc.allocate_sequence("a", list(range(1, 17)))
    alloc.commit_prefilled("a", 16)
    alloc.free_sequence("a")
    alloc.allocate_sequence("b", list(range(101, 117)))
    removed = [h for e in events if e.kind == "removed" for h in e.block_hashes]
    # 3 device blocks were reclaimed; the first spilled to host and was then
    # LRU-dropped when the next two arrived (capacity 2) -> exactly 1 removal
    assert len(removed) == 1
    assert len(pool) == 2
    stored = [b.block_hash for e in events if e.kind == "stored" for b in e.blocks]
    assert set(removed) <= set(stored)


# ---------------- radix tree under churn ----------------


def test_radix_tree_remove_worker_and_expiration_under_churn():
    from dynamo_tpu.llm.kv_events import KvCacheEvent, StoredBlock
    from dynamo_tpu.llm.kv_router.indexer import RadixTree, RouterEvent

    def stored(worker, chain):
        blocks, parent = [], None
        for h in chain:
            blocks.append(StoredBlock(block_hash=h * 1000 + worker,
                                      tokens_hash=h, parent_hash=parent))
            parent = h * 1000 + worker
        return RouterEvent(worker_id=worker,
                           event=KvCacheEvent.stored(parent_hash=None, blocks=blocks))

    tree = RadixTree(expiration_duration=0.05)
    seq = [11, 22, 33]
    for w in (1, 3):
        tree.apply_event(stored(w, seq))
    tree.apply_event(stored(2, [11, 22, 99]))  # worker 2 diverges at depth 2

    scores = tree.find_matches(seq).scores
    assert scores == {1: 3, 2: 2, 3: 3}

    # churn: remove a worker entirely, then partially remove another's blocks
    tree.remove_worker(2)
    scores = tree.find_matches(seq).scores
    assert 2 not in scores and scores[1] == 3
    tree.apply_event(RouterEvent(
        worker_id=1, event=KvCacheEvent.removed([33 * 1000 + 1])
    ))
    scores = tree.find_matches(seq).scores
    assert scores == {1: 2, 3: 3}
    # re-advertise after re-store: worker 2 comes back
    tree.apply_event(stored(2, seq))
    assert tree.find_matches(seq).scores[2] == 3

    # frequency expiration: uses recorded now, decayed after the window
    freqs1 = tree.find_matches(seq).frequencies
    assert freqs1 and freqs1[0] >= 1
    time.sleep(0.06)
    freqs2 = tree.find_matches(seq).frequencies
    assert freqs2[0] <= freqs1[0]


# ---------------- router: one radix walk + remote-holder selection ----------------


def test_router_overlap_memo_and_remote_holder():
    """schedule/prefix_hit_tokens share ONE radix walk per prompt, and the
    remote-holder pick comes from the same OverlapScores."""
    import time as _time

    from dynamo_tpu.llm.kv_events import KvCacheEvent, StoredBlock
    from dynamo_tpu.llm.kv_router.indexer import RouterEvent
    from dynamo_tpu.llm.kv_router.metrics_aggregator import WorkerView
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.llm.tokens import compute_block_hash_for_seq

    class _Drt:
        cplane = None

    router = KvRouter(_Drt(), "ns", "backend", kv_block_size=4)
    prompt = list(range(1, 13))  # 3 blocks
    hashes = compute_block_hash_for_seq(prompt, 4)

    def stored(worker, n):
        blocks, parent = [], None
        for i, th in enumerate(hashes[:n]):
            bh = th ^ worker
            blocks.append(StoredBlock(block_hash=bh, tokens_hash=th, parent_hash=parent))
            parent = bh
        return {"payload": RouterEvent(
            worker_id=worker,
            event=KvCacheEvent.stored(parent_hash=None, blocks=blocks),
        ).to_wire()}

    router._on_kv_event(stored(0xA, 3))
    router._on_kv_event(stored(0xB, 1))

    calls = [0]
    orig = router.indexer.find_matches_for_request

    def counting(token_ids, early_exit=False, salt=0):
        calls[0] += 1
        return orig(token_ids, early_exit, salt=salt)

    router.indexer.find_matches_for_request = counting

    overlap = router._find_overlap(prompt)
    assert calls[0] == 1
    assert router._find_overlap(prompt) is overlap  # memo hit
    assert calls[0] == 1
    assert router.prefix_hit_tokens(prompt, 0xA) == 12
    assert calls[0] == 1  # satellite: no second radix walk

    holder = router.best_remote_holder(overlap, 0xB)
    assert holder == (0xA, 3)
    assert router.best_remote_holder(overlap, 0xA) is None  # B's 1 < A's 3 + margin

    # a new KV event invalidates the memo (the tree changed)
    router._on_kv_event(stored(0xB, 2))
    router._find_overlap(prompt)
    assert calls[0] == 2

    # pull_address comes from the stats broadcast of a servable worker
    router.aggregator._workers[0xA] = WorkerView(
        0xA,
        data={"kv_pull": {"address": "10.0.0.7:4040"},
              "health": {"state": "ready", "heartbeat_age_s": 0.01}},
        last_seen=_time.monotonic(),
    )
    assert router.pull_address(0xA) == "10.0.0.7:4040"
    assert router.pull_address(0xB) == ""  # unknown worker -> no address
    router.aggregator._workers[0xA].data["health"]["state"] = "draining"
    assert router.pull_address(0xA) == ""  # never fetch from a draining peer


# ---------------- dynotop prefix column ----------------


def test_dynotop_prefix_column_local_vs_remote():
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "dynotop", Path(__file__).resolve().parent.parent / "tools" / "dynotop.py"
    )
    dynotop = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dynotop)

    doc = {
        "namespace": "ns", "component": "backend", "summary": {"workers": 1},
        "workers": [{
            "worker_id": "ab", "last_seen_s": 0.1, "missed_scrapes": 0,
            "health": {"state": "ready", "heartbeat_age_s": 0.01},
            "kv_metrics": {"request_active_slots": 1, "request_total_slots": 4,
                           "kv_active_blocks": 1, "kv_total_blocks": 10},
            "resources": {"prefix_cache_query_blocks": 10,
                          "prefix_cache_hit_blocks": 8,
                          "prefix_fetch_blocks": 2},
        }],
    }
    text = dynotop.render_status(doc)
    assert "PREFIX" in text
    assert "80/20%" in text  # local 8/10, remote 2/10
    # workers predating the counters render a dash, not a crash
    doc["workers"][0]["resources"] = {}
    assert "80/20%" not in dynotop.render_status(doc)


# ---------------- exposition ----------------


def test_prefix_fetch_exposition_families():
    from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient
    from dynamo_tpu.utils.prometheus import check_exposition

    srv = KvPullServer(None)
    srv.served, srv.gone = 3, 1
    srv.served_blocks["host"] = 2
    text = srv.render_metrics()
    assert check_exposition(text) == []
    assert 'dynamo_prefix_fetch_served_total{result="hit"} 3' in text
    assert 'dynamo_prefix_fetch_served_blocks_total{tier="host"} 2' in text

    cl = PrefixFetchClient(None)
    cl.results["timeout"] = 2
    cl.fetch_seconds.observe(0.1)
    text = cl.render_metrics()
    assert check_exposition(text) == []
    assert 'dynamo_prefix_fetch_client_requests_total{result="timeout"} 2' in text
    assert "dynamo_prefix_fetch_client_seconds_bucket" in text
