"""KV router: radix indexer, cost scheduler, and the full routed path over the
broker (engine allocator events -> indexer -> schedule), plus the bounded/
sharded index plane (LRU eviction, leak pruning, shard determinism, and the
eviction-truthful overlap memo)."""

import asyncio
import os
import subprocess
import sys

import pytest

from dynamo_tpu.engine.page_table import PageAllocator
from dynamo_tpu.llm.kv_events import KvCacheEvent, StoredBlock
from dynamo_tpu.llm.kv_router.indexer import KvIndexer, OverlapScores, RouterEvent
from dynamo_tpu.llm.kv_router.scheduler import (
    AllWorkersBusyError,
    KvScheduler,
    ProcessedEndpoints,
    WorkerLoad,
    select_worker,
)

BS = 4  # kv block size


@pytest.fixture(params=["python", "native"])
def make_indexer(request):
    if request.param == "native":
        from dynamo_tpu.llm.kv_router.native_indexer import native_available

        if not native_available():
            pytest.skip("native library not buildable")

    def make():
        return KvIndexer(BS, use_native=request.param == "native")

    return make


def stored(worker, indexer, parent, blocks):
    """blocks: list of (block_hash, tokens_hash)."""
    indexer.apply_event(
        RouterEvent(
            worker_id=worker,
            event=KvCacheEvent.stored(
                parent_hash=parent,
                blocks=[StoredBlock(block_hash=b, tokens_hash=t) for b, t in blocks],
            ),
        )
    )


def test_indexer_basic_match_and_removal(make_indexer):
    idx = make_indexer()
    # worker 1 caches blocks A->B; worker 2 caches A only
    stored(1, idx, None, [(100, 10), (101, 11)])
    stored(2, idx, None, [(200, 10)])

    scores = idx.find_matches([10, 11])
    assert scores.scores == {1: 2, 2: 1}
    scores = idx.find_matches([10, 99])
    assert scores.scores == {1: 1, 2: 1}
    scores = idx.find_matches([99])
    assert scores.scores == {}

    # removed event drops only that worker's claim
    idx.apply_event(RouterEvent(worker_id=1, event=KvCacheEvent.removed([100])))
    scores = idx.find_matches([10, 11])
    assert scores.scores == {2: 1, 1: 1}  # worker 1 still owns depth-2 block

    idx.remove_worker(2)
    assert idx.find_matches([10]).scores == {}


def test_indexer_parent_chaining_mid_tree(make_indexer):
    idx = make_indexer()
    stored(1, idx, None, [(100, 10)])
    # attach at depth 1 via parent block_hash
    stored(1, idx, 100, [(101, 11)])
    assert idx.find_matches([10, 11]).scores == {1: 2}
    # a different worker with same content hashes shares nodes
    stored(2, idx, None, [(300, 10)])
    stored(2, idx, 300, [(301, 11)])
    assert idx.find_matches([10, 11]).scores == {1: 2, 2: 2}


def test_indexer_from_allocator_events(make_indexer):
    """Engine-side PageAllocator events drive the router index end-to-end."""
    events = []
    alloc = PageAllocator(32, BS, event_sink=events.append)
    prompt = list(range(12))  # 3 full blocks
    alloc.allocate_sequence("s1", prompt)
    alloc.commit_prefilled("s1", 12)

    idx = make_indexer()
    for ev in events:
        idx.apply_event(RouterEvent(worker_id=7, event=ev))

    scores = idx.find_matches_for_request(prompt)
    assert scores.scores == {7: 3}
    # a longer prompt sharing 2 blocks
    scores = idx.find_matches_for_request(prompt[:8] + [99, 98, 97, 96])
    assert scores.scores == {7: 2}


def load(worker_id, active=0, total=10, kv_active=0, kv_total=100):
    return WorkerLoad(
        worker_id=worker_id,
        request_active_slots=active,
        request_total_slots=total,
        kv_active_blocks=kv_active,
        kv_total_blocks=kv_total,
    )


def test_scheduler_prefers_overlap():
    eps = ProcessedEndpoints.new([load(1), load(2)])
    overlap = OverlapScores(scores={2: 8})  # 8 blocks cached on worker 2
    picked = select_worker(eps, isl_tokens=64, overlap=overlap, kv_block_size=BS)
    assert picked == 2


def test_scheduler_balance_mode_avoids_loaded_worker():
    # worker 1 has the overlap but is heavily loaded; balance mode weighs load
    eps = ProcessedEndpoints.new(
        [load(1, kv_active=90), load(2, kv_active=5)]
    )
    overlap = OverlapScores(scores={1: 2})  # small overlap on the loaded one
    picked = select_worker(eps, isl_tokens=64, overlap=overlap, kv_block_size=BS)
    assert picked == 2


def test_scheduler_excludes_full_workers():
    eps = ProcessedEndpoints.new([load(1, active=10), load(2)])
    picked = select_worker(eps, 16, OverlapScores(scores={1: 4}), BS)
    assert picked == 2
    eps = ProcessedEndpoints.new([load(1, active=10), load(2, kv_active=100)])
    with pytest.raises(AllWorkersBusyError):
        select_worker(eps, 16, OverlapScores(), BS)


def test_scheduler_optimistic_bump():
    sched = KvScheduler(BS)
    sched.update_endpoints([load(1, total=2), load(2, total=2)])
    first = sched.schedule(16, OverlapScores(scores={1: 4}))
    assert first == 1
    # after two more schedules worker 1 fills up (bumped to 2 slots), so 2 wins
    sched.schedule(16, OverlapScores(scores={1: 4}))
    third = sched.schedule(16, OverlapScores(scores={1: 4}))
    assert third == 2


def test_kv_router_over_broker():
    from dynamo_tpu.cplane.broker import Broker
    from dynamo_tpu.llm.kv_router.publisher import KvEventPublisher, KvMetricsPublisher
    from dynamo_tpu.llm.kv_router.router import KvRouter
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    async def body():
        broker = Broker()
        port = await broker.start()
        worker = DistributedRuntime(cplane_address=f"127.0.0.1:{port}")
        await worker.connect()
        router_rt = DistributedRuntime(cplane_address=f"127.0.0.1:{port}")
        await router_rt.connect()
        try:
            wid = worker.primary_lease.lease_id

            # worker serves an endpoint exposing kv metrics via stats handler
            async def handler(req):
                yield {"ok": True}

            metrics = KvMetricsPublisher(
                lambda: {
                    "request_active_slots": 0,
                    "request_total_slots": 4,
                    "kv_active_blocks": 0,
                    "kv_total_blocks": 100,
                }
            )
            ep = worker.namespace("ns").component("backend").endpoint("generate")
            await ep.serve_endpoint(handler, metrics=metrics.stats_handler)

            router = KvRouter(router_rt, "ns", "backend", kv_block_size=BS)
            await router.start()

            # engine-side: allocator events flow through the publisher
            pub = KvEventPublisher(
                worker.cplane, "ns|backend.kv_events", wid, loop=asyncio.get_running_loop()
            )
            alloc = PageAllocator(32, BS, event_sink=lambda e: asyncio.ensure_future(
                pub.publish_async(e)
            ))
            prompt = list(range(16))
            alloc.allocate_sequence("s1", prompt)
            alloc.commit_prefilled("s1", 16)
            await asyncio.sleep(0.2)  # let events propagate

            assert router.indexer.find_matches_for_request(prompt).scores == {wid: 4}
            picked = await router.schedule(prompt)
            assert picked == wid
            assert router.prefix_hit_tokens(prompt, wid) == 16

            # worker death prunes the index
            await worker._shutdown_hook()
            for _ in range(100):
                if not router.indexer.find_matches_for_request(prompt).scores:
                    break
                await asyncio.sleep(0.02)
            assert router.indexer.find_matches_for_request(prompt).scores == {}
            await router.stop()
        finally:
            await router_rt._shutdown_hook()
            await broker.stop()

    asyncio.run(body())

# ---------------- bounded / sharded index plane ----------------


def py_indexer(**kw):
    return KvIndexer(BS, use_native=False, **kw)


def test_radix_removed_event_prunes_leaked_nodes():
    """Regression for the node leak: a full store -> remove cycle must leave
    the node count at baseline (the unbounded ancestor only discarded worker
    ids, so childless worker-less chains accumulated forever)."""
    idx = py_indexer()
    assert idx.radix_stats()["nodes"] == 0
    stored(1, idx, None, [(100, 10), (101, 11), (102, 12)])
    assert idx.radix_stats()["nodes"] == 3
    idx.apply_event(RouterEvent(worker_id=1, event=KvCacheEvent.removed([102, 101, 100])))
    s = idx.radix_stats()
    assert s["nodes"] == 0 and s["entries"] == 0
    # interior removal must NOT prune: a deeper block another claim still
    # owns has to stay reachable from the root
    stored(1, idx, None, [(100, 10), (101, 11)])
    idx.apply_event(RouterEvent(worker_id=1, event=KvCacheEvent.removed([100])))
    assert idx.find_matches([10, 11]).scores == {1: 1}
    assert idx.radix_stats()["nodes"] == 2
    # removing the deep block drains the whole chain
    idx.apply_event(RouterEvent(worker_id=1, event=KvCacheEvent.removed([101])))
    assert idx.radix_stats()["nodes"] == 0


def test_radix_remove_worker_prunes_unshared_chains():
    idx = py_indexer()
    stored(1, idx, None, [(100, 10), (101, 11)])
    stored(2, idx, None, [(200, 10)])  # shares the depth-1 node
    idx.remove_worker(1)
    s = idx.radix_stats()
    # the shared depth-1 node survives (worker 2 claims it); worker 1's
    # private depth-2 node is gone
    assert s["nodes"] == 1 and s["workers"] == 1
    assert idx.find_matches([10, 11]).scores == {2: 1}
    idx.remove_worker(2)
    s = idx.radix_stats()
    assert s["nodes"] == 0 and s["workers"] == 0 and s["entries"] == 0


def test_radix_bounded_lru_eviction_keeps_hot_prefix():
    idx = py_indexer(max_nodes=8)
    stored(1, idx, None, [(1000, 500), (1001, 501)])  # the hot chain
    for i in range(50):
        stored(1, idx, None, [(2000 + i, 9000 + i)])
        idx.find_matches([500, 501])  # keep the hot chain recently-hit
        assert idx.radix_stats()["nodes"] <= 8
    s = idx.radix_stats()
    assert s["evictions_total"] >= 40
    assert s["bytes"] > 0
    # the hot chain survived arbitrary churn; cold churn nodes were evicted
    assert idx.find_matches([500, 501]).scores == {1: 2}
    assert s["generation"] > 0


def test_radix_byte_cap_bounds_resident_bytes():
    idx = py_indexer(max_bytes=8 * 1024)
    for i in range(200):
        stored(1, idx, None, [(3000 + i, 7000 + i)])
    s = idx.radix_stats()
    assert s["bytes"] <= 8 * 1024
    assert s["evictions_total"] > 0


def test_sharded_bounded_index_under_distinct_prefix_churn():
    """The sharded, bounded index against the unbounded one under churn of
    distinct single-block prefixes with a hot working set of depth-4 chains
    that traffic keeps walking: resident nodes never pass the cap, evictions
    happened, the unbounded arm only grows, and the hot set's hit ratio stays
    within 0.05 of the unbounded arm's. Counts only, no latency."""
    CAP, HOT, DEPTH, CHURN, EVERY = 2_000, 50, 4, 40_000, 2_000

    def hot_seq(j):
        return [(1 << 40) + j * DEPTH + d for d in range(DEPTH)]

    def arm(**kw):
        idx = py_indexer(**kw)
        for j in range(HOT):
            stored(1, idx, None, [((1 << 50) + h, h) for h in hot_seq(j)])
        checkpoints = []
        for i in range(CHURN):
            stored(1, idx, None, [((1 << 51) + i, i)])
            if i % 8 == 0:  # LRU only protects what gets walked
                idx.find_matches(hot_seq((i // 8) % HOT))
            if i % EVERY == 0:
                checkpoints.append(idx.radix_stats()["nodes"])
        matched = sum(idx.find_matches(hot_seq(j)).scores.get(1, 0) for j in range(HOT))
        return idx.radix_stats(), checkpoints, matched / (HOT * DEPTH)

    _, grown, hot_unbounded = arm()
    stats, held, hot_bounded = arm(max_nodes=CAP, num_shards=4)
    assert all(b > a for a, b in zip(grown, grown[1:])), grown
    assert grown[-1] >= HOT * DEPTH + CHURN - EVERY
    assert max(held) <= CAP and stats["nodes"] <= CAP, (held, stats)
    assert stats["shards"] == 4 and stats["max_nodes"] == CAP
    assert stats["evictions_total"] >= CHURN - CAP, stats
    assert hot_unbounded == 1.0
    assert hot_bounded >= hot_unbounded - 0.05, hot_bounded


def test_stats_incremental_counters_match_recount():
    """stats() is O(1) off incremental counters; they must agree with a full
    recount of the lookup tables after a mixed store/remove/evict workload."""
    idx = py_indexer(max_nodes=64)
    for i in range(100):
        stored(1 + i % 3, idx, None, [(i * 10, 5000 + i), (i * 10 + 1, 6000 + i)])
        if i % 7 == 0:
            idx.apply_event(RouterEvent(
                worker_id=1 + i % 3, event=KvCacheEvent.removed([i * 10])))
    idx.remove_worker(2)
    entries, workers = idx.stats()
    recount_entries = sum(
        len(d) for t in idx.shards for d in t.lookup.values()
    )
    recount_workers = len({w for t in idx.shards for w in t.lookup})
    assert entries == recount_entries
    assert workers == recount_workers
    # node counter agrees with an actual tree walk too
    def count(node):
        return 1 + sum(count(c) for c in node.children.values())
    assert idx.radix_stats()["nodes"] == sum(count(t.root) - 1 for t in idx.shards)


@pytest.mark.parametrize("shards", [1, 3])
def test_sharded_indexer_matches_single_shard_semantics(shards):
    """The sharded facade must answer exactly like one tree: parent chaining
    lands in the owning shard, removed events fan out by owning shard, and
    remove_worker drops the worker everywhere."""
    idx = py_indexer(num_shards=shards)
    assert idx.radix_stats()["shards"] == shards
    stored(1, idx, None, [(100, 10)])
    stored(1, idx, 100, [(101, 11)])  # chained via parent block_hash
    stored(2, idx, None, [(300, 10)])
    stored(2, idx, 300, [(301, 11)])
    stored(3, idx, None, [(400, 77), (401, 78)])
    assert idx.find_matches([10, 11]).scores == {1: 2, 2: 2}
    assert idx.find_matches([77, 78]).scores == {3: 2}
    assert idx.stats() == (6, 3)
    idx.apply_event(RouterEvent(worker_id=3, event=KvCacheEvent.removed([401, 400])))
    assert idx.find_matches([77, 78]).scores == {}
    idx.remove_worker(1)
    assert idx.find_matches([10, 11]).scores == {2: 2}
    assert idx.stats() == (2, 1)


def test_shard_routing_is_deterministic_across_processes():
    """Same request -> same shard, in every process: the first-block hash is
    a seeded xxh3 of the token bytes, so shard routing needs no coordination
    between frontends (and must not depend on PYTHONHASHSEED)."""
    from dynamo_tpu.llm.kv_router.indexer import shard_index
    from dynamo_tpu.llm.tokens import compute_block_hash_for_seq

    prompt = list(range(32))
    local = shard_index(compute_block_hash_for_seq(prompt, BS)[0], 8)
    code = (
        "from dynamo_tpu.llm.tokens import compute_block_hash_for_seq\n"
        "from dynamo_tpu.llm.kv_router.indexer import shard_index\n"
        f"print(shard_index(compute_block_hash_for_seq(list(range(32)), {BS})[0], 8))\n"
    )
    for seed in ("0", "1"):
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu"},
        )
        assert out.returncode == 0, out.stderr
        assert int(out.stdout.strip()) == local


def _bare_router(**indexer_kw):
    """A KvRouter with no control plane: only the indexer/memo paths run."""
    from dynamo_tpu.llm.kv_router.router import KvRouter

    class _Drt:
        cplane = None

    router = KvRouter(_Drt(), "ns", "backend", kv_block_size=BS)
    router.indexer = KvIndexer(BS, use_native=False, **indexer_kw)
    return router


def test_overlap_memo_invalidated_by_eviction():
    """The one-entry overlap memo must never return a score for an evicted
    subtree — even when the eviction happened OUTSIDE _on_kv_event (direct
    indexer traffic bypasses the explicit invalidation sites; the generation
    key in _overlap_key is what catches it)."""
    from dynamo_tpu.llm.tokens import compute_block_hash_for_seq

    router = _bare_router(max_nodes=4)
    prompt = list(range(BS * 2))  # 2 blocks
    hashes = compute_block_hash_for_seq(prompt, BS)
    stored(1, router.indexer, None, [(900 + i, h) for i, h in enumerate(hashes)])
    ov1 = router._find_overlap(prompt)
    assert ov1.scores == {1: 2}
    assert router._find_overlap(prompt) is ov1  # memo reuse while unchanged
    # churn unrelated prefixes straight into the indexer until the prompt's
    # nodes evict (no KV event reaches the router, so only generation works)
    for i in range(10):
        stored(1, router.indexer, None, [(5000 + i, 8000 + i)])
    ov2 = router._find_overlap(prompt)
    assert ov2 is not ov1
    assert ov2.scores == {}


def test_overlap_memo_invalidated_by_direct_remove_worker():
    from dynamo_tpu.llm.tokens import compute_block_hash_for_seq

    router = _bare_router()
    prompt = list(range(BS * 2))
    hashes = compute_block_hash_for_seq(prompt, BS)
    stored(7, router.indexer, None, [(900 + i, h) for i, h in enumerate(hashes)])
    ov1 = router._find_overlap(prompt)
    assert ov1.scores == {7: 2}
    router.indexer.remove_worker(7)  # bypasses _watch_instances
    ov2 = router._find_overlap(prompt)
    assert ov2 is not ov1
    assert ov2.scores == {}
