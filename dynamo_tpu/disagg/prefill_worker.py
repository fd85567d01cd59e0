"""Prefill worker: consumes the remote-prefill work queue, runs prefill on its
engine, and pushes KV + first token to the decode worker.

Mirrors the reference prefill worker loop (reference: examples/llm/components/
prefill_worker.py:84-137 prefill_queue_handler). Cross-process KV rides the
dedicated data plane; with streaming enabled (EngineConfig.kv_stream, the
default) each prefill chunk's finalized pages are staged to host and put on
the wire while the next chunk computes — so by the time the completion
notification lands on the decode worker most KV bytes are already there.
"""

from __future__ import annotations

import asyncio
import time
from typing import Optional

from dynamo_tpu.engine.engine import AsyncJaxEngine
from dynamo_tpu.llm.remote_prefill import RemotePrefillRequest, prefill_queue_name
from dynamo_tpu.runtime.context import RequestContext, use_context
from dynamo_tpu.utils import get_logger, tracing
from dynamo_tpu.utils.prometheus import render_family

log = get_logger("disagg.prefill")


class PrefillWorker:
    def __init__(
        self,
        engine: AsyncJaxEngine,
        drt,
        namespace: str,
        model: str,
        kv_stream: Optional[bool] = None,
        kv_stream_lanes: Optional[int] = None,
    ):
        from dynamo_tpu.disagg import refuse_recurrent

        refuse_recurrent(engine, "a disaggregated prefill worker")
        self.engine = engine
        self.drt = drt
        self.namespace = namespace
        self.model = model
        self.queue_name = prefill_queue_name(namespace, model)
        self._task: Optional[asyncio.Task] = None
        self._clients: dict[str, object] = {}
        self.completed = 0
        cfg = getattr(engine, "config", None)
        if kv_stream is None:
            kv_stream = getattr(cfg, "kv_stream", True)
        if kv_stream_lanes is None:
            kv_stream_lanes = getattr(cfg, "kv_stream_lanes", 2)
        self.kv_stream = bool(kv_stream)
        from dynamo_tpu.disagg.dataplane import KvDataPlaneClient

        self.kv_client = KvDataPlaneClient(lanes=max(1, int(kv_stream_lanes or 1)))
        # streamed-transfer observability: wall seconds a part spent on the
        # wire (D2H complete -> drain), and the portion of that which
        # overlapped the request's remaining prefill compute — the pipelining
        # win the streamed protocol exists for
        self.stream_requests = 0
        self.stream_parts = 0
        self.stream_bytes = 0
        self.stream_send_s = 0.0
        self.stream_overlap_s = 0.0

    async def start(self) -> "PrefillWorker":
        refusal = getattr(self.engine, "transfer_refusal", None)
        why = refusal() if refusal else None
        if why:
            raise ValueError(why)
        self._task = asyncio.create_task(self._loop())
        return self

    async def stop(self) -> None:
        if self._task:
            self._task.cancel()
        await self.kv_client.close()

    async def _client_for(self, endpoint: str):
        client = self._clients.get(endpoint)
        if client is None:
            client = await self.drt.endpoint_client(endpoint)
            await client.wait_for_instances(timeout=10)
            self._clients[endpoint] = client
        return client

    async def _loop(self) -> None:
        log.info("prefill worker consuming %s", self.queue_name)
        try:
            while True:
                try:
                    msg = await self.drt.cplane.queue_pull(self.queue_name)
                except ConnectionError:
                    if getattr(self.drt.cplane, "_dead", False):
                        # reconnect window exhausted: the broker is gone for
                        # good — die loudly, don't impersonate a live consumer
                        log.error(
                            "control plane is dead; prefill consumer for %s exiting",
                            self.queue_name,
                        )
                        return
                    # broker blip: the parked pull died with the connection;
                    # the cplane client heals in the background — keep
                    # re-arming the pull instead of letting the consumer die
                    # (the queue is durable, work survives the restart)
                    log.warning("queue pull lost connection; re-arming %s", self.queue_name)
                    await asyncio.sleep(0.5)
                    continue
                try:
                    await self._handle(RemotePrefillRequest.from_wire(msg.payload))
                    await self.drt.cplane.queue_ack(self.queue_name, msg.msg_id)
                    self.completed += 1
                except Exception:
                    log.exception("remote prefill failed; nacking")
                    try:
                        await self.drt.cplane.queue_nack(self.queue_name, msg.msg_id)
                    except Exception:
                        pass
        except asyncio.CancelledError:
            pass

    async def _handle(self, rp: RemotePrefillRequest) -> None:
        # the work queue bypasses the RPC envelope's context propagation, so
        # re-enter the request context from the message itself: logs stamp the
        # originating request id and spans land on the edge-stamped trace
        ctx = RequestContext(
            request_id=rp.request_id,
            metadata={"trace_id": rp.trace_id} if rp.trace_id else {},
        )
        with use_context(ctx):
            with tracing.span(
                "disagg.prefill", prompt_len=len(rp.token_ids),
                decode_worker=f"{rp.decode_worker_id:x}",
            ):
                await self._handle_traced(rp)

    async def _handle_traced(self, rp: RemotePrefillRequest) -> None:
        from dynamo_tpu.disagg import ici

        # same-process decode worker? hand the KV off as a device array (ICI
        # path: blocks reshard onto the decode mesh without touching host
        # memory). Cross-process with a kv_addr: bulk bytes ride the dedicated
        # data-plane socket and the control message is the completion
        # notification. Neither: legacy inline bytes in the result.
        device = ici.is_local(rp.decode_worker_id)
        mode = "ici" if device else ("socket" if rp.kv_addr else "inline")
        stream = mode == "socket" and self.kv_stream
        tkey = ici.transfer_key(rp.decode_worker_id, rp.request_id) if device else ""
        if tkey:
            # a redelivered message must not be swallowed by a tombstone a
            # cancelled earlier attempt (possibly a colocated sibling worker)
            # left behind
            ici.clear_tombstone(tkey)
        result = None
        delivered = False
        send_tasks: list[asyncio.Task] = []
        loop = asyncio.get_running_loop()
        cat_axis = self.engine.runner.model.wire_n_axis

        async def _ship(seq: int, total: int, pf: int, pt: int, d2h_fut):
            from dynamo_tpu.quant.kv import wire_nbytes

            arr = await asyncio.wrap_future(d2h_fut)  # D2H staged off-thread
            t0 = time.monotonic()
            # int8 caches stage the {"q","s"} wire dict: the int8 payload is
            # half the bf16 bytes and the scale plane rides the part header
            await self.kv_client.send_part(
                rp.kv_addr, rp.request_id, arr, token=rp.kv_token,
                part_seq=seq, part_total=total,
                page_from=pf, page_to=pt, cat_axis=cat_axis,
            )
            return t0, time.monotonic(), wire_nbytes(arr)

        def on_part(seq, total, pf, pt, d2h_fut):
            # engine thread -> event loop; tasks created in emission order so
            # the send_tasks list is complete before run_on_engine resolves
            # (both ride call_soon_threadsafe on the same loop, FIFO)
            loop.call_soon_threadsafe(
                lambda: send_tasks.append(
                    asyncio.create_task(_ship(seq, total, pf, pt, d2h_fut))
                )
            )

        try:
            result, host_data = await self.engine.run_on_engine(
                lambda: self.engine.sync_remote_prefill(
                    rp, mode=mode, on_part=on_part if stream else None
                )
            )
            t_compute_end = time.monotonic()
            client = await self._client_for(rp.decode_endpoint)

            async def deliver():
                # deliver directly to the requesting decode worker (the
                # RDMA-WRITE + notify analogue)
                stream_out = await client.direct(result.to_wire(), rp.decode_worker_id)
                async for ack in stream_out:
                    if not ack.get("ok"):
                        # permanent rejection (request cancelled/unknown on
                        # the decode side): drop the work — nacking would
                        # redeliver a poisoned message forever
                        log.warning(
                            "decode worker rejected prefill result for %s: %s",
                            rp.request_id, ack,
                        )
                        return False
                return True

            # every payload part BEFORE the notification: a delivered result
            # then implies the payload is on the wire, so a socket failure
            # surfaces here (-> nack + redelivery) instead of stranding the
            # decode side in a full receive() timeout after a notification
            # whose payload will never arrive
            if send_tasks:
                with tracing.span(
                    "disagg.kv_stream", parts=len(send_tasks), mode="socket"
                ):
                    spans = await asyncio.gather(*send_tasks)
                send_s = sum(t1 - t0 for t0, t1, _ in spans)
                overlap = sum(
                    max(0.0, min(t1, t_compute_end) - t0) for t0, t1, _ in spans
                )
                self.stream_requests += 1
                self.stream_parts += len(spans)
                self.stream_bytes += sum(b for _, _, b in spans)
                self.stream_send_s += send_s
                self.stream_overlap_s += overlap
            if host_data is not None:
                from dynamo_tpu.quant.kv import wire_nbytes

                ps = self.engine.config.page_size
                with tracing.span(
                    "disagg.kv_send", bytes=wire_nbytes(host_data), mode="socket"
                ):
                    await self.kv_client.send(
                        rp.kv_addr, rp.request_id, host_data, token=rp.kv_token,
                        page_from=result.skip_leading_tokens // ps,
                        page_to=-(-result.prompt_len // ps),
                        cat_axis=cat_axis,
                    )
            ok = await deliver()
            if not ok:
                return
            delivered = True
        except BaseException:
            if tkey and result is None:
                # cancelled (or failed) while the engine thread may still be
                # producing: the park could land after us, so tombstone it.
                # An ordinary exception from sync_remote_prefill means nothing
                # was parked and the tombstone is TTL-pruned harmlessly.
                ici.discard_transfer(tkey)
            raise
        finally:
            if send_tasks and not delivered:
                # a failed/cancelled request must not leave part sends (or
                # their D2H waits) dangling into the next queue item
                for t in send_tasks:
                    t.cancel()
                await asyncio.gather(*send_tasks, return_exceptions=True)
            if not delivered and result is not None and result.kv_transfer_id:
                # park happened but delivery/ack failed: drop the real array
                ici.pop_transfer(result.kv_transfer_id)

    def render_metrics(self) -> str:
        """Prometheus exposition for the send side of the KV stream: the
        client frame/byte/lane counters plus the measured compute/transfer
        overlap the chunk pipelining buys."""
        return self.kv_client.render_metrics() + "".join([
            render_family(
                "dynamo_kv_stream_requests_total", "counter",
                "remote prefills whose KV was chunk-streamed",
                [({}, self.stream_requests)],
            ),
            render_family(
                "dynamo_kv_stream_send_seconds_total", "counter",
                "wall seconds KV parts spent on the wire (D2H done -> drained)",
                [({}, round(self.stream_send_s, 6))],
            ),
            render_family(
                "dynamo_kv_stream_overlap_seconds_total", "counter",
                "portion of part send seconds overlapped with prefill compute",
                [({}, round(self.stream_overlap_s, 6))],
            ),
        ])
