"""Planner: scaling policy (pure) + service publishing desired replicas
(reference claims a Planner as capability #2 but ships none; ours is real)."""

import asyncio
import json

from dynamo_tpu.components.planner import Planner, PlannerService, PoolPolicy
from dynamo_tpu.llm.kv_router.scheduler import WorkerLoad


def load(active=0, total=8, kv=0, kv_total=100, waiting=0, wid=1):
    return WorkerLoad(
        worker_id=wid,
        request_active_slots=active,
        request_total_slots=total,
        kv_active_blocks=kv,
        kv_total_blocks=kv_total,
        num_requests_waiting=waiting,
    )


def mk_planner(sustain=2, cooldown=100.0):
    pol = PoolPolicy(min_replicas=1, max_replicas=4, sustain=sustain, cooldown_s=cooldown)
    return Planner(decode_policy=pol, prefill_policy=pol)


def test_scale_up_requires_sustained_pressure():
    p = mk_planner(sustain=3)
    hot = [load(active=8, wid=1)]  # slot pressure 1.0
    for t in range(2):
        d = p.observe(hot, 0, 1, 1, now=float(t))[0]
        assert not d.is_change  # not sustained yet
    d = p.observe(hot, 0, 1, 1, now=2.0)[0]
    assert d.is_change and d.desired == 2


def test_pressure_blip_resets_sustain():
    p = mk_planner(sustain=2)
    hot, idle = [load(active=8)], [load(active=4)]  # 1.0 vs 0.5 (dead zone)
    p.observe(hot, 0, 1, 1, now=0.0)
    p.observe(idle, 0, 1, 1, now=1.0)  # resets the streak
    d = p.observe(hot, 0, 1, 1, now=2.0)[0]
    assert not d.is_change


def test_cooldown_blocks_consecutive_changes():
    p = mk_planner(sustain=1, cooldown=60.0)
    hot = [load(active=8)]
    d = p.observe(hot, 0, 1, 1, now=0.0)[0]
    assert d.desired == 2
    d = p.observe(hot, 0, 2, 1, now=10.0)[0]  # inside cooldown
    assert not d.is_change
    d = p.observe(hot, 0, 2, 1, now=61.0)[0]  # cooldown expired
    assert d.desired == 3


def test_scale_down_and_min_bound():
    p = mk_planner(sustain=2, cooldown=0.0)
    idle = [load(active=0)]
    p.observe(idle, 0, 2, 1, now=0.0)
    d = p.observe(idle, 0, 2, 1, now=1.0)[0]
    assert d.desired == 1
    # at min: never below
    p2 = mk_planner(sustain=1, cooldown=0.0)
    d = p2.observe(idle, 0, 1, 1, now=0.0)[0]
    assert not d.is_change and d.desired == 1


def test_max_bound():
    p = mk_planner(sustain=1, cooldown=0.0)
    hot = [load(active=8)]
    d = p.observe(hot, 0, 4, 1, now=0.0)[0]
    assert not d.is_change and d.desired == 4


def test_kv_pressure_alone_triggers():
    p = mk_planner(sustain=1, cooldown=0.0)
    kv_hot = [load(active=1, kv=95)]  # kv 0.95, slots 0.125
    d = p.observe(kv_hot, 0, 1, 1, now=0.0)[0]
    assert d.desired == 2


def test_prefill_queue_scales_prefill_pool():
    p = mk_planner(sustain=2, cooldown=0.0)
    # queue 8 vs 1 replica * 4/worker -> pressure 1.0
    p.observe([], 8, 1, 1, now=0.0)
    d = p.observe([], 8, 1, 1, now=1.0)[1]
    assert d.component == "prefill-worker" and d.desired == 2
    # decode pool untouched (no loads -> pressure 0, but scale-down respects min)
    assert p.observe([], 8, 1, 2, now=2.0)[0].desired == 1


def test_planner_service_publishes_desired_replicas():
    from dynamo_tpu.cplane.broker import Broker
    from dynamo_tpu.llm.kv_router.publisher import KvMetricsPublisher
    from dynamo_tpu.runtime.distributed import DistributedRuntime

    async def body():
        broker = Broker()
        port = await broker.start()
        addr = f"127.0.0.1:{port}"

        rt = DistributedRuntime(cplane_address=addr)
        await rt.connect()
        pub = KvMetricsPublisher(
            lambda: {
                "request_active_slots": 8,
                "request_total_slots": 8,
                "kv_active_blocks": 90,
                "kv_total_blocks": 100,
                "num_requests_waiting": 5,
            }
        )

        async def handler(req):
            yield {"ok": True}

        ep = rt.namespace("pl").component("worker").endpoint("generate")
        await ep.serve_endpoint(handler, metrics=pub.stats_handler)

        prt = DistributedRuntime(cplane_address=addr)
        await prt.connect()
        svc = PlannerService(
            prt, "pl",
            planner=Planner(
                decode_policy=PoolPolicy(sustain=2, cooldown_s=0.0, max_replicas=4),
                prefill_policy=PoolPolicy(sustain=2, cooldown_s=0.0, max_replicas=4),
            ),
        )
        try:
            await svc.step()
            decisions = await svc.step()  # sustained on 2nd observation
            decode = decisions[0]
            assert decode.desired == 2 and decode.current == 1

            kvs = await prt.cplane.kv_get_prefix("planner/pl/desired/")
            by_key = {item.key.rsplit("/", 1)[1]: json.loads(item.value) for item in kvs}
            assert by_key["worker"]["replicas"] == 2
            assert by_key["prefill-worker"]["replicas"] == 1
        finally:
            await rt._shutdown_hook()
            await prt._shutdown_hook()
            await broker.stop()

    asyncio.new_event_loop().run_until_complete(body())


def test_supervisor_applies_planner_scaling(monkeypatch):
    """The serve supervisor consumes the planner's desired-replica keys:
    scale-up spawns new replicas, each on its own chip assignment and never
    more than there are chips (a chip belongs to one process), scale-down
    terminates the highest indices and the restart loop leaves them dead."""
    from dynamo_tpu.sdk.serve import Supervisor

    sup = Supervisor("m:X", {}, "127.0.0.1:1", planner_scaling=True, planner_poll_s=0.0)

    class Meta:
        namespace = "pl"
        component = "worker"

    cls = type("Worker", (), {})
    envs = [{"TPU_VISIBLE_DEVICES": str(i)} for i in range(4)]
    sup._class_info["Worker"] = (cls, Meta, envs)
    sup.desired["Worker"] = 2

    spawned = []
    monkeypatch.setattr(sup, "spawn", lambda c, i, env=None: spawned.append((i, env)))
    monkeypatch.setattr(
        sup, "_read_planner_desired", lambda: {"planner/pl/desired/worker": 6}
    )
    sup._apply_planner_scaling()
    # six wanted, four chips: held at four, replicas 2,3 on chips 2,3
    assert sup.desired["Worker"] == 4
    assert spawned == [(2, envs[2]), (3, envs[3])]

    class FakeProc:
        def __init__(self):
            self.terminated = False

        def poll(self):
            return None

        def terminate(self):
            self.terminated = True

    sup.children = {f"Worker-{i}": FakeProc() for i in range(4)}
    monkeypatch.setattr(
        sup, "_read_planner_desired", lambda: {"planner/pl/desired/worker": 1}
    )
    sup._last_planner_poll = 0.0
    sup._apply_planner_scaling()
    assert [sup.children[f"Worker-{i}"].terminated for i in range(4)] == [
        False, True, True, True,
    ]
