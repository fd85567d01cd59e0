"""Namespace / Component / Endpoint model + endpoint serving.

Mirrors the reference component model (reference: lib/runtime/src/component.rs:73-321,
component/endpoint.rs:20-143): hierarchical naming, discoverable instance keys
held under the process's primary lease, a per-endpoint request subject, and a
push-endpoint loop that drives the handler and streams responses over the TCP
call-home plane.

Key layout (control-plane KV):
  instances/{ns}/components/{comp}/{endpoint}:{lease_hex}  -> msgpack instance info
Request subject:
  {ns}|{comp}.{endpoint}-{lease_hex}
"""

from __future__ import annotations

import asyncio
import inspect
from dataclasses import dataclass
from typing import Any, AsyncIterator, Callable, Optional

import msgpack

from dynamo_tpu.runtime.context import RequestContext, use_context
from dynamo_tpu.runtime.tcp import ConnectionInfo, call_home
from dynamo_tpu.utils import get_logger, tracing

log = get_logger("runtime.component")

INSTANCE_PREFIX = "instances"


def instance_key(ns: str, comp: str, endpoint: str, lease_id: int) -> str:
    return f"{INSTANCE_PREFIX}/{ns}/components/{comp}/{endpoint}:{lease_id:x}"


def endpoint_subject(ns: str, comp: str, endpoint: str, lease_id: int) -> str:
    return f"{ns}|{comp}.{endpoint}-{lease_id:x}"


@dataclass(frozen=True)
class EndpointInfo:
    namespace: str
    component: str
    endpoint: str
    instance_id: int  # lease id
    subject: str
    transport: str = "cplane-tcp"

    def to_wire(self) -> dict:
        return {
            "namespace": self.namespace,
            "component": self.component,
            "endpoint": self.endpoint,
            "instance_id": self.instance_id,
            "subject": self.subject,
            "transport": self.transport,
        }

    @classmethod
    def from_wire(cls, d: dict) -> "EndpointInfo":
        return cls(
            namespace=d["namespace"],
            component=d["component"],
            endpoint=d["endpoint"],
            instance_id=d["instance_id"],
            subject=d["subject"],
            transport=d.get("transport", "cplane-tcp"),
        )


class Namespace:
    def __init__(self, drt, name: str):
        self._drt = drt
        self.name = name

    def component(self, name: str) -> "Component":
        return Component(self._drt, self.name, name)


class Component:
    def __init__(self, drt, namespace: str, name: str):
        self._drt = drt
        self.namespace = namespace
        self.name = name

    def endpoint(self, name: str) -> "Endpoint":
        return Endpoint(self._drt, self.namespace, self.name, name)

    @property
    def event_subject_prefix(self) -> str:
        return f"{self.namespace}|{self.name}"

    def kv_events_subject(self) -> str:
        """Engine KV events channel (reference: kv_router/publisher.rs:33-74)."""
        return f"{self.event_subject_prefix}.kv_events"

    def stats_subject(self) -> str:
        """Service-stats scrape subject (reference: nats.rs scrape_service)."""
        return f"$SRV.STATS.{self.namespace}|{self.name}"


class Endpoint:
    def __init__(self, drt, namespace: str, component: str, name: str):
        self._drt = drt
        self.namespace = namespace
        self.component = component
        self.name = name
        self._stats_handler: Optional[Callable[[], dict]] = None

    @property
    def path(self) -> str:
        return f"{self.namespace}/{self.component}/{self.name}"

    # ---------------- serving ----------------

    def stats_handler(self, fn: Callable[[], dict]) -> None:
        self._stats_handler = fn

    async def serve_endpoint(
        self,
        handler: Callable[[Any], AsyncIterator[Any]],
        metrics: Optional[Callable[[], dict]] = None,
    ) -> "ServedEndpoint":
        """Register this endpoint for discovery and start its push loop.

        handler: async function or async-generator function taking the
        deserialized request; values it yields stream back to the caller.
        """
        drt = self._drt
        lease_id = drt.primary_lease.lease_id
        subject = endpoint_subject(self.namespace, self.component, self.name, lease_id)
        info = EndpointInfo(
            namespace=self.namespace,
            component=self.component,
            endpoint=self.name,
            instance_id=lease_id,
            subject=subject,
        )
        served = ServedEndpoint(drt, info, handler, metrics or self._stats_handler)
        await served.start()
        return served


class ServedEndpoint:
    """The push-endpoint loop (reference: pipeline/network/ingress/push_endpoint.rs)."""

    def __init__(self, drt, info: EndpointInfo, handler, stats_fn=None):
        self._drt = drt
        self.info = info
        self.handler = handler
        self.stats_fn = stats_fn
        self._tasks: set[asyncio.Task] = set()
        self._stats_subject = f"$SRV.STATS.{info.namespace}|{info.component}"

    async def start(self) -> None:
        client = self._drt.cplane
        await client.subscribe(self.info.subject, self._on_request)
        if self.stats_fn is not None:
            # the scrape subject is per COMPONENT and a client holds one
            # handler per subject: an endpoint with nothing to report must
            # not take it from the sibling endpoint that has (the worker's
            # `migrate` endpoint used to silence `generate`'s stats, and the
            # KV router, seeing no loads, placed every request at random)
            await client.subscribe(self._stats_subject, self._on_stats)
        await self._register()
        # broker outage or lease expiry: re-register once the connection (and
        # the lease, under its original id) is healed — subscriptions are
        # replayed by the client itself
        client.reconnect_hooks.append(self._register)
        log.info("serving %s (instance %x)", self.info.subject, self.info.instance_id)

    async def _register(self) -> None:
        key = instance_key(
            self.info.namespace, self.info.component, self.info.endpoint, self.info.instance_id
        )
        # put (not create-if-absent): re-registration after a heal must win
        await self._drt.cplane.kv_put(
            key, msgpack.packb(self.info.to_wire()), lease_id=self._drt.primary_lease.lease_id
        )

    async def stop(self) -> None:
        client = self._drt.cplane
        try:
            client.reconnect_hooks.remove(self._register)
        except ValueError:
            pass
        await client.unsubscribe(self.info.subject)
        key = instance_key(
            self.info.namespace, self.info.component, self.info.endpoint, self.info.instance_id
        )
        await client.kv_delete(key)
        for t in list(self._tasks):
            t.cancel()

    # ---------------- request handling ----------------

    def _on_request(self, msg: dict) -> None:
        task = asyncio.ensure_future(self._handle_request(msg["payload"]))
        self._tasks.add(task)
        task.add_done_callback(self._tasks.discard)

    def _on_stats(self, msg: dict) -> None:
        if msg.get("reply"):
            stats = {}
            if self.stats_fn is not None:
                try:
                    stats = self.stats_fn()
                except Exception:
                    log.exception("stats handler failed")
            payload = {
                "instance_id": self.info.instance_id,
                "endpoint": self.info.endpoint,
                "subject": self.info.subject,
                "data": stats,
            }
            asyncio.ensure_future(self._drt.cplane.publish(msg["reply"], payload))

    async def _handle_request(self, payload: dict) -> None:
        conn_info = ConnectionInfo.from_wire(payload["conn_info"])
        request = msgpack.unpackb(payload["request"], raw=False)
        ctx = RequestContext.from_wire(payload["context"]) if payload.get("context") else None
        with use_context(ctx):
            # server-side hop span: the whole handler stream, on the timeline
            # of whatever trace id the caller shipped in the context
            with tracing.span(
                f"rpc.handle.{self.info.endpoint}",
                component=self.info.component,
            ):
                await self._run_handler(conn_info, request)

    async def _run_handler(self, conn_info, request) -> None:

        # Drive the handler to its first item BEFORE calling home: setup-time
        # failures ride the prologue (reference: network.rs:64-73 — first frame
        # is ResponseStreamPrologue ok-or-error), later failures are stream
        # error frames.
        first: Optional[Any] = None
        has_first = False
        stream = None
        try:
            result = self.handler(request)
            if inspect.isasyncgen(result):
                stream = result
                try:
                    first = await stream.__anext__()
                    has_first = True
                except StopAsyncIteration:
                    has_first = False
            elif inspect.iscoroutine(result):
                first = await result
                has_first = True
            else:
                raise TypeError("handler must be async or an async generator")
        except Exception as e:
            log.exception("handler for %s failed at setup", self.info.subject)
            try:
                await call_home(conn_info, error=f"{type(e).__name__}: {e}")
            except Exception:
                log.warning("failed to report error to caller")
            return

        sender = await call_home(conn_info)
        try:
            if has_first:
                await sender.send(msgpack.packb(first, use_bin_type=True))
            if stream is not None:
                async for item in stream:
                    await sender.send(msgpack.packb(item, use_bin_type=True))
            await sender.close()
        except Exception as e:
            log.exception("handler for %s failed mid-stream", self.info.subject)
            try:
                await sender.close(error=f"{type(e).__name__}: {e}")
            except Exception:
                log.warning("failed to report stream error to caller")
