"""Worker service: the JAX engine behind a runtime endpoint.

Tokens-in/tokens-out over the wire: PreprocessedRequest dict -> stream of
BackendOutput dicts (detokenization happens here, next to the engine, so text
deltas stream back ready to serve — reference: examples/llm/components/
worker.py VllmWorker, lib/llm/src/backend.rs).

Publishes KV events (kv_events subject) and ForwardPassMetrics (stats handler)
so KV routers can target it. Optionally wraps the engine in the disagg decode
path.
"""

from __future__ import annotations

import asyncio
from typing import Optional

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.llm.backend import Backend
from dynamo_tpu.llm.kv_router.publisher import KvEventPublisher
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.model_registry import ModelEntry, ModelRegistration
from dynamo_tpu.llm.protocols.common import PreprocessedRequest
from dynamo_tpu.llm.tokenizer import get_tokenizer
from dynamo_tpu.utils import get_logger

log = get_logger("components.worker")

GENERATE_ENDPOINT = "generate"
MIGRATE_ENDPOINT = "migrate"


class WorkerService:
    def __init__(
        self,
        drt,
        namespace: str,
        component: str,
        card: ModelDeploymentCard,
        engine_config: EngineConfig,
        enable_disagg_decode: bool = False,
        register: bool = True,
        engine_factory=None,
        admin_port: int | None = None,
    ):
        self.drt = drt
        self.namespace = namespace
        self.component = component
        self.card = card
        self.engine_config = engine_config
        self.enable_disagg_decode = enable_disagg_decode
        self.register = register
        # optional (kv_event_sink) -> engine: hosts an external engine (e.g.
        # llm.external.ExternalTokenEngine) behind this worker instead of the
        # native JAX engine — the reference's engine-agnostic worker slot
        self.engine_factory = engine_factory
        self.engine = None  # AsyncJaxEngine or DisaggDecodeEngine
        self.backend: Optional[Backend] = None
        self._served = None
        self._kv_publisher: Optional[KvEventPublisher] = None
        # fleet-wide prefix cache: peers pull OUR cached prefixes from this
        # export server; its address rides the stats broadcast so the KV
        # router can attach us as a holder (disagg/prefix_fetch.py)
        self.kv_pull_server = None
        # live migration (disagg/migrate.py): the peer-facing `migrate`
        # runtime endpoint adopts manifests; /admin/drain on the admin HTTP
        # port triggers the migrate-then-die drain of THIS worker
        self.admin_port = admin_port
        self._admin_runner = None
        self._migrate_served = None
        self._migrate_client = None

    async def start(self) -> "WorkerService":
        loop = asyncio.get_running_loop()
        worker_id = self.drt.primary_lease.lease_id
        subject = f"{self.namespace}|{self.component}.kv_events"
        self._kv_publisher = KvEventPublisher(self.drt.cplane, subject, worker_id, loop=loop)

        if self.engine_factory is not None:
            inner = self.engine_factory(self._kv_publisher.publish)
            starter = getattr(inner, "start", None)
            if starter is not None:
                result = starter()
                if asyncio.iscoroutine(result):
                    await result
        else:
            inner = AsyncJaxEngine(self.engine_config, kv_event_sink=self._kv_publisher.publish)
            await inner.start()
            log.info("worker %x engine on %s", worker_id, inner.device_info())
        if self.engine_config.prefix_fetch and isinstance(inner, AsyncJaxEngine):
            from dynamo_tpu.disagg.prefix_fetch import KvPullServer, PrefixFetchClient

            # both directions of the fleet prefix cache: serve our prefixes
            # to pulling peers, and pull theirs when the router attaches a
            # holder to an incoming request
            self.kv_pull_server = await KvPullServer(inner).start()
            inner.kv_pull_server = self.kv_pull_server
            inner.attach_prefix_fetch(PrefixFetchClient(
                loop, timeout_s=self.engine_config.prefix_fetch_timeout_s
            ))
        engine = inner
        if self.enable_disagg_decode:
            from dynamo_tpu.disagg.decode_worker import DisaggDecodeEngine

            engine = DisaggDecodeEngine(
                inner, self.drt, self.namespace, self.component, self.card.display_name
            )
            await engine.start()
        self.engine = engine
        self._inner_engine = inner

        tokenizer = get_tokenizer(self.card.tokenizer)
        self.backend = Backend(engine, tokenizer)

        ep = self.drt.namespace(self.namespace).component(self.component).endpoint(GENERATE_ENDPOINT)
        self._served = await ep.serve_endpoint(self._handle, metrics=self._stats)

        # live migration: adopt peers' manifests on `migrate`, and keep a
        # client to the same endpoint so OUR drain can hand sequences out
        if self.engine_config.migration and isinstance(inner, AsyncJaxEngine):
            mep = (
                self.drt.namespace(self.namespace)
                .component(self.component)
                .endpoint(MIGRATE_ENDPOINT)
            )
            self._migrate_served = await mep.serve_endpoint(self._handle_migrate)
            self._migrate_client = await self.drt.client(
                self.namespace, self.component, MIGRATE_ENDPOINT
            )
            # QoS shed hook (engine-thread callable): when a waiting
            # critical request must evict a lower-class lane, hand the
            # victim to a servable peer via live migration instead of
            # preempt+recompute — the batch request survives elsewhere and
            # this worker's slot frees when the relay takes over
            me = self.drt.primary_lease.lease_id
            eng_loop = loop

            def _shed_via_migration(request_id: str) -> bool:
                try:
                    peers = [
                        i for i in self._migrate_client.instance_ids() if i != me
                    ]
                except Exception:
                    return False
                if not peers:
                    return False
                adopter = self._peer_adopter(peers[0])
                asyncio.run_coroutine_threadsafe(
                    inner.migrate_out(request_id, adopter), eng_loop
                )
                return True

            if inner.scheduler is not None:
                inner.scheduler.migrate_shed = _shed_via_migration
        if self.admin_port is not None:
            await self._start_admin(self.admin_port)

        if self.register:
            entry = ModelEntry(
                name=self.card.display_name,
                endpoint=f"dyn://{self.namespace}.{self.component}.{GENERATE_ENDPOINT}",
                model_type="chat",
                card=self.card,
            )
            # lease-tied + refreshed: the card dies with this worker's lease
            # and any surviving co-worker's refresh restores it (MDC TTL
            # semantics, reference: model_card/model.rs)
            self._registration = await ModelRegistration(
                self.drt.cplane, entry, lease_id=self.drt.primary_lease.lease_id
            ).start()
            # multi-LoRA: every configured adapter registers as its own
            # servable model name <base>:<adapter> (same endpoint + card;
            # frontends list and route them like any model; the worker
            # resolves the suffix back to lora_name in _handle)
            self._lora_registrations = []
            if getattr(self.engine_config, "lora_adapters", ()):
                from dynamo_tpu.lora.adapter import parse_adapter_specs

                for name in parse_adapter_specs(self.engine_config.lora_adapters):
                    a_entry = ModelEntry(
                        name=f"{self.card.display_name}:{name}",
                        endpoint=entry.endpoint,
                        model_type="chat",
                        card=self.card,
                    )
                    self._lora_registrations.append(await ModelRegistration(
                        self.drt.cplane, a_entry,
                        lease_id=self.drt.primary_lease.lease_id,
                    ).start())
        return self

    async def stop(self) -> None:
        if self._admin_runner is not None:
            await self._admin_runner.cleanup()
        if self._migrate_served is not None:
            await self._migrate_served.stop()
        if self._migrate_client is not None:
            await self._migrate_client.stop()
        for reg in getattr(self, "_lora_registrations", ()):
            await reg.stop(unregister=False)
        if getattr(self, "_registration", None) is not None:
            # unregister=False: the card key is lease-tied, so OUR lease revoke
            # (DRT shutdown) removes it if we were the owner — while a clean
            # scale-down of one worker of a multi-worker model must NOT blip
            # the shared card for the survivors
            await self._registration.stop(unregister=False)
        if self._served is not None:
            await self._served.stop()
        if self.kv_pull_server is not None:
            await self.kv_pull_server.stop()
        if self.engine is not None:
            await self.engine.shutdown()

    def _stats(self) -> dict:
        stats = {"kv_metrics": self._inner_engine.metrics().to_wire()}
        # per-stage latency attribution (scheduler StageStats): scraped by the
        # standalone metrics component into llm_engine_stage_seconds_total
        stage = getattr(self._inner_engine, "stage_snapshot", None)
        if stage is not None:
            snap = stage()
            if snap:
                stats["stage_seconds"] = snap
        # fleet health plane: lifecycle state + heartbeat age (routers and the
        # planner skip draining/dead workers), resource gauges (page pool,
        # HBM, compile churn), and the rolling SLO state — all ride the same
        # stats broadcast the aggregator already scrapes
        health = getattr(self._inner_engine, "health", None)
        if health is not None:
            stats["health"] = health.snapshot()
        resources = getattr(self._inner_engine, "resource_snapshot", None)
        if resources is not None:
            snap = resources()
            if snap:
                stats["resources"] = snap
        slo = getattr(self._inner_engine, "slo_snapshot", None)
        if slo is not None:
            stats["slo"] = slo()
        ev = getattr(self._inner_engine, "events_snapshot", None)
        if ev is not None:
            # flight-recorder summary: newest events + per-kind counts (the
            # metrics component's /cluster/events merges the recent lists;
            # dynotop's EVT column reads the counts)
            stats["events"] = ev()
        goodput = getattr(self._inner_engine, "goodput_snapshot", None)
        if goodput is not None:
            # windowed per-scenario/tenant SLO-met fraction (dynotop GOODPUT
            # column; item-5 QoS scheduling reads the per-tenant view)
            stats["goodput"] = goodput()
        costs = getattr(self._inner_engine, "cost_snapshot", None)
        if costs is not None:
            # cost-attribution rollup (utils/metering.py): per-tenant device-
            # seconds and KV byte-seconds — the metrics component's
            # /cluster/costs merge, dynotop's COST column, and the planner's
            # per-tenant demand signal all read this broadcast
            snap = costs()
            if snap:
                stats["costs"] = snap
        # live migration: whether this worker adopts peers' sequences (the
        # planner's rebalance decisions only target migration-enabled pairs)
        stats["migration"] = {
            "enabled": bool(getattr(self.engine_config, "migration", False))
            and self._migrate_client is not None,
        }
        if self.admin_port is not None and self._admin_runner is not None:
            # the planner's rebalance EXECUTOR reads this out of the stats
            # broadcast to POST /admin/drain on the decided source worker
            stats["admin"] = {"address": f"127.0.0.1:{self.admin_port}"}
        if self.kv_pull_server is not None:
            # the fleet prefix cache's discovery channel: routers read the
            # pull address out of this broadcast to attach us as a holder
            srv = self.kv_pull_server
            stats["kv_pull"] = {
                "address": srv.address,
                "served": srv.served,
                "gone": srv.gone,
                "served_blocks": dict(srv.served_blocks),
                "bytes_sent": srv.bytes_sent,
            }
        if self.enable_disagg_decode and self.engine is not None:
            stats["disagg"] = {
                "remote_prefills": self.engine.remote_prefills,
                "local_prefills": self.engine.local_prefills,
            }
            if self.engine.kv_server is not None:
                kv = self.engine.kv_server
                stats["disagg"]["kv_dataplane"] = {
                    "received": kv.received,
                    "parts_received": kv.parts_received,
                    "bytes_received": kv.bytes_received,
                    "dropped": kv.dropped,
                    "rejected": kv.rejected,
                    "checksum_failures": kv.checksum_failures,
                    "parts_scattered": self.engine.parts_scattered,
                    "address": kv.address,
                }
        return stats

    # ---------------- live migration (disagg/migrate.py) ----------------

    async def _handle_migrate(self, request: dict):
        """Peer-facing adoption endpoint: a draining/hot peer ships one
        sequence's manifest here; we adopt it (seq_handoff KV pull with
        recompute fallback) and stream the continuation tokens back — the
        peer relays them into its still-open client stream."""
        from dynamo_tpu.disagg.migrate import SequenceManifest

        manifest = SequenceManifest.from_wire(request)
        async for out in self._inner_engine.adopt_migrated(manifest):
            yield {
                "request_id": out.request_id,
                "token": out.token,
                "finished": out.finished,
                "finish_reason": out.finish_reason,
                "cached_tokens": out.cached_tokens,
            }

    def _peer_adopter(self, instance_id: int):
        """Adapter from the peer's `migrate` stream to the StepOutput shape
        AsyncJaxEngine.migrate_out relays."""
        from dynamo_tpu.engine.scheduler import StepOutput

        async def adopter(manifest):
            stream = await self._migrate_client.direct(
                manifest.to_wire(), instance_id
            )
            async for item in stream:
                yield StepOutput(
                    request_id=item.get("request_id", manifest.request_id),
                    token=item.get("token"),
                    finished=bool(item.get("finished")),
                    finish_reason=item.get("finish_reason"),
                    cached_tokens=int(item.get("cached_tokens", 0) or 0),
                )

        return adopter

    async def drain(self, target_instance: int | None = None) -> dict:
        """Operator drain, migrate-then-die instead of drain-by-attrition:
        mark this worker draining (routers/planner stop sending work), hand
        every in-flight sequence to a peer worker of the same component, and
        report what moved. Sequences whose handoff fails keep decoding here
        (never worse than attrition). The caller shuts the worker down once
        this returns."""
        eng = self._inner_engine
        health = getattr(eng, "health", None)
        if health is not None:
            health.set_state("draining", "operator drain requested")
        results = {"migrated": 0, "resumed": 0, "failed": 0, "skipped": 0}
        if not getattr(self.engine_config, "migration", True) or self._migrate_client is None:
            return {**results, "migration": "disabled"}
        if target_instance is None:
            me = self.drt.primary_lease.lease_id
            peers = [i for i in self._migrate_client.instance_ids() if i != me]
            target_instance = peers[0] if peers else None
        if target_instance is None:
            log.warning("drain: no migration peer available; draining by attrition")
            return {**results, "migration": "no-peer"}
        if health is not None:
            health.set_state("migrating", "drain: handing sequences to peer")
        adopter = self._peer_adopter(target_instance)
        sched = eng.scheduler
        rids = [
            s.req.request_id for s in sched.slots
            if s is not None and not s.finished
        ]
        for rid in rids:
            try:
                res = await eng.migrate_out(rid, adopter)
            except Exception:
                log.exception("drain: migration of %s crashed", rid)
                results["failed"] += 1
                continue
            status = res.get("status", "failed")
            results["migrated" if status == "ok" else
                    status if status in results else "failed"] += 1
        if health is not None:
            health.set_state("draining", "drain: migration pass complete")
        results["migration"] = "done"
        results["target_instance"] = f"{target_instance:x}"
        log.info("drain complete: %s", results)
        return results

    async def _start_admin(self, port: int) -> None:
        """Tiny operator-facing HTTP plane: POST /admin/drain {target?:
        "<instance hex>"} triggers the migrate-then-die drain."""
        from aiohttp import web

        app = web.Application()

        async def _drain(request: web.Request) -> web.Response:
            target = None
            try:
                body = await request.json()
            except Exception:
                body = {}
            if isinstance(body, dict) and body.get("target"):
                target = int(str(body["target"]), 16)
            result = await self.drain(target_instance=target)
            return web.json_response(result)

        app.router.add_post("/admin/drain", _drain)
        self._admin_runner = web.AppRunner(app, access_log=None)
        await self._admin_runner.setup()
        site = web.TCPSite(self._admin_runner, "127.0.0.1", port)
        await site.start()
        self.admin_port = site._server.sockets[0].getsockname()[1]
        log.info("worker admin endpoint on 127.0.0.1:%d", self.admin_port)

    async def _handle(self, request: dict):
        pre = PreprocessedRequest.from_wire(request)
        # distributed-path base:adapter resolution: the frontend routes by
        # registered model NAME; the worker maps the suffix back to the
        # adapter it configured (exact display-name prefix match, so a tiny
        # override JSON containing ':' can't misparse)
        if not pre.lora_name and pre.model:
            base_prefix = self.card.display_name + ":"
            if str(pre.model).startswith(base_prefix):
                suffix = str(pre.model)[len(base_prefix):]
                from dynamo_tpu.lora.adapter import parse_adapter_specs

                if suffix in parse_adapter_specs(
                    getattr(self.engine_config, "lora_adapters", ())
                ):
                    pre.lora_name = suffix
        async for out in self.backend.generate(pre):
            yield {
                "request_id": out.request_id,
                "text": out.text,
                "token_ids": out.token_ids,
                "finish_reason": out.finish_reason,
                "cumulative_tokens": out.cumulative_tokens,
                "cached_tokens": out.cached_tokens,
                "logprobs": out.logprobs,
            }


async def _main(args) -> None:
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    from dynamo_tpu.utils.xla_cache import enable_compilation_cache

    from dynamo_tpu.parallel.mesh import init_multihost

    enable_compilation_cache()  # engine restarts reload executables from disk
    init_multihost()  # no-op unless DYNTPU_COORDINATOR is set

    drt = DistributedRuntime(cplane_address=args.cplane)
    await drt.connect()
    from dynamo_tpu.models.registry import is_tiny_family

    if is_tiny_family(args.model):
        card = ModelDeploymentCard.for_tiny(args.model)
    else:
        card = ModelDeploymentCard.from_local_path(args.model)
    svc = WorkerService(
        drt,
        args.namespace,
        args.component,
        card,
        EngineConfig.for_model(
            args.model,
            tp=args.tp,
            page_size=args.page_size,
            num_pages=args.num_pages,
            max_seqs=args.max_seqs,
            max_model_len=args.max_model_len,
            quantize=getattr(args, "quantize", None),
            kv_cache_dtype=getattr(args, "kv_cache_dtype", None),
            speculative=getattr(args, "speculative", None),
            lora_adapters=tuple(
                s.strip() for s in (getattr(args, "lora_adapters", "") or "").split(",")
                if s.strip()
            ),
            max_loras=getattr(args, "max_loras", None) or 4,
            lora_rank=getattr(args, "lora_rank", None) or 8,
            kv_stream=not getattr(args, "no_kv_stream", False),
            kv_stream_lanes=getattr(args, "kv_stream_lanes", None) or 2,
            prefix_fetch=not getattr(args, "no_prefix_fetch", False),
            prefix_fetch_timeout_s=getattr(args, "prefix_fetch_timeout_s", None) or 5.0,
            prefix_fetch_min_blocks=getattr(args, "prefix_fetch_min_blocks", None) or 1,
            migration=not getattr(args, "no_migration", False),
            migration_timeout_s=getattr(args, "migration_timeout_s", None) or 10.0,
            qos=not getattr(args, "no_qos", False),
            qos_preempt_wait_ms=getattr(args, "qos_preempt_wait_ms", None) or 250.0,
            metering=not getattr(args, "no_metering", False),
            slo_ttft_ms=getattr(args, "slo_ttft_ms", None),
            slo_itl_ms=getattr(args, "slo_itl_ms", None),
            prefill_buckets=tuple(
                int(b) for b in getattr(args, "prefill_buckets", "").split(",") if b
            ) or EngineConfig.prefill_buckets,
            prefill_flat_depth=getattr(args, "prefill_flat_depth", None) or 8192,
            prefill_pipeline_depth=getattr(
                args, "prefill_pipeline_depth", None
            ) or EngineConfig.prefill_pipeline_depth,
            host_cache_blocks=getattr(args, "host_cache_blocks", None) or 0,
            host_cache_bytes=getattr(args, "host_cache_bytes", None) or 0,
            disk_cache_bytes=getattr(args, "disk_cache_bytes", None) or 0,
            disk_cache_dir=getattr(args, "disk_cache_dir", None) or "",
            offload_watermark=getattr(args, "offload_watermark", None) or 0.90,
        ),
        enable_disagg_decode=args.disagg,
        admin_port=getattr(args, "admin_port", None),
    )
    await svc.start()
    log.info(
        "worker up: model=%s endpoint=dyn://%s.%s.%s disagg=%s",
        card.display_name, args.namespace, args.component, GENERATE_ENDPOINT, args.disagg,
    )
    try:
        while True:
            await asyncio.sleep(3600)
    finally:
        await svc.stop()


def main(argv=None) -> None:
    """Plain-process decode/aggregated worker (helm: worker.yaml; the SDK
    graph variants live in examples/graphs/)."""
    import argparse
    import os

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("model", help="model path or tiny:{...} spec")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--component", default="backend")
    p.add_argument("--cplane", default=os.environ.get("DYNTPU_CPLANE", "127.0.0.1:4222"))
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--page-size", type=int, default=16)
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--max-seqs", type=int, default=8)
    p.add_argument("--max-model-len", type=int, default=2048)
    p.add_argument("--quantize", choices=["int8_wo"], default=None,
                   help="weight-only quantization applied at load time")
    p.add_argument("--kv-cache-dtype", choices=["bf16", "int8"], default=None,
                   help="KV cache storage dtype: int8 halves attention HBM "
                        "traffic and ~doubles page capacity (per-page "
                        "scales; composes with --quantize)")
    p.add_argument("--speculative", default=None, metavar="KIND:...",
                   help="speculative decoding: ngram:<k> (prompt-lookup "
                        "proposals) or draft:<model>:<k> (a second, smaller "
                        "registry model with its own paged KV drafts k "
                        "tokens per round; composes with --quantize / "
                        "--kv-cache-dtype)")
    p.add_argument("--lora-adapters", default="",
                   help="comma-separated LoRA adapter specs served as "
                        "<model>:<name> (name | name=<dir> | "
                        "name=random:<seed>); a mixed-adapter batch decodes "
                        "in one gathered dispatch (dynamo_tpu/lora/)")
    p.add_argument("--max-loras", type=int, default=4,
                   help="device adapter slots; more adapters than slots "
                        "multiplex via LRU eviction/hot-swap")
    p.add_argument("--lora-rank", type=int, default=8,
                   help="adapter pool rank (smaller adapters zero-pad; "
                        "larger are rejected at load)")
    p.add_argument("--disagg", action="store_true", help="wrap in the disagg decode path")
    p.add_argument("--slo-ttft-ms", type=float, default=None,
                   help="TTFT SLO target in ms (rolling percentiles + error "
                        "budget ride stats and /metrics; env "
                        "DYNTPU_SLO_TTFT_MS)")
    p.add_argument("--slo-itl-ms", type=float, default=None,
                   help="inter-token-latency SLO target in ms (env "
                        "DYNTPU_SLO_ITL_MS)")
    p.add_argument("--kv-stream-lanes", type=int, default=2,
                   help="parallel KV data-plane connections per destination "
                        "(disagg; parts stripe across lanes)")
    p.add_argument("--no-kv-stream", action="store_true",
                   help="disable chunk-streamed KV transfer (fall back to one "
                        "monolithic post-prefill send)")
    p.add_argument("--no-prefix-fetch", action="store_true",
                   help="disable the fleet-wide prefix cache (don't serve KV "
                        "pulls or fetch remote prefixes from peers)")
    p.add_argument("--prefix-fetch-timeout-s", type=float, default=5.0,
                   help="remote prefix pull deadline; on expiry the request "
                        "degrades to recompute (never an error)")
    p.add_argument("--prefix-fetch-min-blocks", type=int, default=1,
                   help="minimum holder advantage (blocks) over the local "
                        "prefix cache before a pull is worth issuing")
    p.add_argument("--no-migration", action="store_true",
                   help="disable live sequence migration (drain degrades to "
                        "attrition and the frontend answers retriable 503s "
                        "while draining)")
    p.add_argument("--migration-timeout-s", type=float, default=10.0,
                   help="deadline belt on one sequence handoff (KV pull + "
                        "first continuation token); on expiry the sequence "
                        "resumes decoding locally")
    p.add_argument("--no-qos", action="store_true",
                   help="disable multi-tenant QoS scheduling (priority "
                        "classes ignored: FIFO admission, recency-only "
                        "preemption victims)")
    p.add_argument("--no-metering", action="store_true",
                   help="disable per-tenant cost attribution (no ledger: "
                        "dynamo_cost_* families, /cluster/costs shares and "
                        "per-request cost footers all go dark)")
    p.add_argument("--qos-preempt-wait-ms", type=float, default=250.0,
                   help="how long a critical request waits with no free "
                        "slot before the scheduler evicts a lower-class "
                        "lane for it (anti-thrash gate)")
    p.add_argument("--admin-port", type=int, default=None,
                   help="operator admin HTTP port on 127.0.0.1 (0 = "
                        "ephemeral): POST /admin/drain migrates in-flight "
                        "sequences to a peer and marks this worker draining")
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated padded prefill chunk lengths (e.g. "
                        "512,1024,2048 for long-context configs); empty = "
                        "the engine default")
    p.add_argument("--prefill-flat-depth", type=int, default=8192,
                   help="context depth past which the scheduler shrinks "
                        "prefill chunks to keep per-chunk latency flat "
                        "(0 disables)")
    p.add_argument("--prefill-pipeline-depth", type=int, default=None,
                   help="packed prefill calls dispatched ahead of result "
                        "materialization (1 = strict reconcile per call; "
                        "default 2 overlaps call N+1's host prep with call "
                        "N's device time)")
    p.add_argument("--host-cache-blocks", type=int, default=0,
                   help="host-DRAM KV offload tier capacity in blocks "
                        "(0 disables; long-context cold KV drains here "
                        "under page pressure)")
    p.add_argument("--host-cache-bytes", type=int, default=0,
                   help="host-DRAM KV tier budget in bytes, resolved to "
                        "blocks at the model's ACTUAL per-page wire cost "
                        "(an int8 KV cache fits ~2x the blocks of bf16 in "
                        "the same budget; the larger of the two knobs wins)")
    p.add_argument("--disk-cache-bytes", type=int, default=0,
                   help="disk KV tier budget in bytes (0 disables; requires "
                        "a host tier — host-pool LRU victims demote to disk "
                        "int8-compressed instead of dropping, and a cold "
                        "session resume restores disk->host->HBM without a "
                        "prefill recompute)")
    p.add_argument("--disk-cache-dir", default="",
                   help="directory for disk-tier block files (default: the "
                        "DYNTPU_KV_DISK_DIR env var, else a fresh tempdir "
                        "the store owns and cleans up)")
    p.add_argument("--offload-watermark", type=float, default=0.90,
                   help="page-pool occupancy fraction that triggers the "
                        "batched cold-block drain to the host tier "
                        "(>= 1.0 disables the proactive drain)")
    args = p.parse_args(argv)
    asyncio.run(_main(args))


if __name__ == "__main__":
    main()
