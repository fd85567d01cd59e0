"""Distributed runtime: two-plane RPC end-to-end over a real broker + TCP.

Mirrors the reference's pipeline/network tests (reference: lib/runtime/tests/
pipeline.rs + lib/bindings/python/tests fixture pattern)."""

import asyncio

import pytest

from dynamo_tpu.cplane.broker import Broker
from dynamo_tpu.runtime.codec import TwoPartMessage, decode, encode, CodecError
from dynamo_tpu.runtime.client import NoInstancesError
from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.service import collect_service_stats
from dynamo_tpu.runtime.tcp import ResponseStreamError


def run(coro):
    return asyncio.run(coro)


# ---------------- codec ----------------


def test_two_part_codec_roundtrip():
    msg = TwoPartMessage(header=b"hdr", body=b"payload" * 100)
    data = encode(msg)
    out, rest = decode(data + b"extra")
    assert out == msg and rest == b"extra"


def test_two_part_codec_checksum():
    data = bytearray(encode(TwoPartMessage(header=b"h", body=b"b")))
    data[-1] ^= 0xFF
    with pytest.raises(CodecError):
        decode(bytes(data))


# ---------------- RPC harness ----------------


async def with_cluster(fn):
    broker = Broker()
    port = await broker.start()
    drts = []

    async def drt():
        d = DistributedRuntime(cplane_address=f"127.0.0.1:{port}")
        await d.connect()
        drts.append(d)
        return d

    try:
        return await fn(drt)
    finally:
        for d in drts:
            await d._shutdown_hook()
        await broker.stop()


async def serve_doubler(worker: DistributedRuntime, ns="test", comp="worker", ep="generate"):
    async def handler(request):
        for x in request["values"]:
            yield {"doubled": x * 2, "worker": worker.primary_lease.lease_id}

    endpoint = worker.namespace(ns).component(comp).endpoint(ep)
    return await endpoint.serve_endpoint(handler, metrics=lambda: {"load": 0.5})


def test_rpc_stream_end_to_end():
    async def body(drt):
        worker, caller = await drt(), await drt()
        await serve_doubler(worker)
        client = await caller.client("test", "worker", "generate")
        await client.wait_for_instances(timeout=5)
        stream = await client.random({"values": [1, 2, 3]})
        results = [item async for item in stream]
        assert [r["doubled"] for r in results] == [2, 4, 6]

    run(with_cluster(body))


def test_rpc_handler_error_propagates():
    async def body(drt):
        worker, caller = await drt(), await drt()

        async def bad_handler(request):
            yield {"ok": 1}
            raise ValueError("boom")

        ep = worker.namespace("test").component("w2").endpoint("gen")
        await ep.serve_endpoint(bad_handler)
        client = await caller.client("test", "w2", "gen")
        await client.wait_for_instances(timeout=5)
        stream = await client.random({})
        with pytest.raises(ResponseStreamError, match="boom"):
            async for _ in stream:
                pass

    run(with_cluster(body))


def test_rpc_error_before_stream():
    async def body(drt):
        worker, caller = await drt(), await drt()

        async def fail_fast(request):
            raise RuntimeError("rejected")
            yield  # pragma: no cover

        ep = worker.namespace("test").component("w3").endpoint("gen")
        await ep.serve_endpoint(fail_fast)
        client = await caller.client("test", "w3", "gen")
        await client.wait_for_instances(timeout=5)
        with pytest.raises(ResponseStreamError, match="rejected"):
            await client.random({})

    run(with_cluster(body))


def test_direct_and_round_robin_routing():
    async def body(drt):
        w1, w2, caller = await drt(), await drt(), await drt()
        await serve_doubler(w1)
        await serve_doubler(w2)
        client = await caller.client("test", "worker", "generate")
        ids = await client.wait_for_instances(timeout=5)
        while len(client.instance_ids()) < 2:
            await asyncio.sleep(0.02)
        ids = client.instance_ids()
        assert len(ids) == 2

        # direct: always the chosen worker
        for target in ids:
            stream = await client.direct({"values": [5]}, target)
            results = [r async for r in stream]
            assert results[0]["worker"] == target

        # round robin alternates
        seen = []
        for _ in range(4):
            stream = await client.round_robin({"values": [1]})
            results = [r async for r in stream]
            seen.append(results[0]["worker"])
        assert seen == [ids[0], ids[1], ids[0], ids[1]]

    run(with_cluster(body))


def test_instance_vanishes_on_worker_death():
    async def body(drt):
        worker, caller = await drt(), await drt()
        await serve_doubler(worker)
        client = await caller.client("test", "worker", "generate")
        await client.wait_for_instances(timeout=5)
        assert len(client.instance_ids()) == 1

        await worker._shutdown_hook()  # lease revoked => instance key deleted
        for _ in range(100):
            if not client.instance_ids():
                break
            await asyncio.sleep(0.02)
        assert client.instance_ids() == []
        with pytest.raises(NoInstancesError):
            await client.random({"values": [1]})

    run(with_cluster(body))


def test_stats_scrape():
    async def body(drt):
        w1, w2, caller = await drt(), await drt(), await drt()
        await serve_doubler(w1)
        await serve_doubler(w2)
        stats = await collect_service_stats(caller.cplane, "test", "worker", timeout=0.3)
        assert len(stats.endpoints) == 2
        assert all(e.data == {"load": 0.5} for e in stats.endpoints)
        ids = {e.instance_id for e in stats.endpoints}
        assert ids == {w1.primary_lease.lease_id, w2.primary_lease.lease_id}

    run(with_cluster(body))


def test_stats_scrape_survives_a_sibling_endpoint_without_stats():
    """One process serving two endpoints of a component (the worker's
    `generate` + `migrate`): the sibling that reports nothing must not take
    the component's scrape subject from the one that does — it used to, the
    KV router saw no loads and placed every request at random."""
    async def body(drt):
        worker, caller = await drt(), await drt()
        await serve_doubler(worker)

        async def quiet(request):
            yield {}

        await worker.namespace("test").component("worker").endpoint("migrate").serve_endpoint(quiet)
        stats = await collect_service_stats(caller.cplane, "test", "worker", timeout=0.3)
        assert [(e.endpoint, e.data) for e in stats.endpoints] == [("generate", {"load": 0.5})]

    run(with_cluster(body))


def test_dyn_endpoint_address():
    async def body(drt):
        worker, caller = await drt(), await drt()
        await serve_doubler(worker)
        client = await caller.endpoint_client("dyn://test.worker.generate")
        await client.wait_for_instances(timeout=5)
        stream = await client.random({"values": [7]})
        results = [r async for r in stream]
        assert results[0]["doubled"] == 14

    run(with_cluster(body))


def test_request_context_propagates_across_hops():
    """The metadata bag injected at the edge reaches the first-hop handler via
    the envelope, and flows AMBIENTLY into a second hop the handler makes
    without any explicit plumbing (reference: pipeline/context.rs — Context
    rides every network hop)."""
    from dynamo_tpu.runtime.context import current_context, new_context, use_context

    async def body(drt):
        backend, middle, caller = await drt(), await drt(), await drt()

        async def backend_handler(request):
            ctx = current_context()
            yield {
                "trace": ctx.metadata.get("trace") if ctx else None,
                "rid": ctx.request_id if ctx else None,
            }

        ep = backend.namespace("ctx").component("backend").endpoint("gen")
        await ep.serve_endpoint(backend_handler)

        async def middle_handler(request):
            # no explicit context arg: the ambient context must carry over
            client = await middle.client("ctx", "backend", "gen")
            await client.wait_for_instances(timeout=5)
            stream = await client.random({"hop": 2})
            async for item in stream:
                ctx = current_context()
                item["middle_saw"] = ctx.metadata.get("trace") if ctx else None
                yield item

        ep2 = middle.namespace("ctx").component("middle").endpoint("gen")
        await ep2.serve_endpoint(middle_handler)

        client = await caller.client("ctx", "middle", "gen")
        await client.wait_for_instances(timeout=5)
        ctx = new_context(request_id="req-42", metadata={"trace": "abc123"})
        with use_context(ctx):
            stream = await client.random({"hop": 1})
        results = [item async for item in stream]
        assert results == [
            {"trace": "abc123", "rid": "req-42", "middle_saw": "abc123"}
        ]

        # no ambient context -> handler sees None
        stream = await client.random({"hop": 1})
        results = [item async for item in stream]
        assert results[0]["trace"] is None

    run(with_cluster(body))
