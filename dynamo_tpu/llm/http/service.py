"""OpenAI-compatible HTTP service (aiohttp).

Mirrors the reference HTTP service (reference: lib/llm/src/http/service/
service_v2.rs:24-90, openai.rs:132,214, service.rs:58 ModelManager): models
attach/detach dynamically; requests always stream internally and are
aggregated for ``stream=false``; SSE framing with a final ``data: [DONE]``.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import AsyncIterator, Callable, Optional

from aiohttp import web

from dynamo_tpu.runtime.context import new_context, use_context
from dynamo_tpu.llm.protocols.aggregator import (
    aggregate_chat_stream,
    aggregate_completion_stream,
)
from dynamo_tpu.llm.protocols.openai import (
    ChatCompletionRequest,
    ChatDeltaGenerator,
    CompletionDeltaGenerator,
    CompletionRequest,
    ProtocolError,
    Usage,
)
from dynamo_tpu.llm.http.metrics import Metrics
from dynamo_tpu.utils.goodput import MAX_ITL_SAMPLES
from dynamo_tpu.llm.protocols import sse
from dynamo_tpu.llm.tools import ToolCallError, ToolCallingMatcher
from dynamo_tpu.utils import events, get_logger, tracing

log = get_logger("http")


class ModelPipeline:
    """Everything needed to serve one model: preprocessor + backend."""

    def __init__(self, name: str, preprocessor, backend, model_type: str = "chat"):
        self.name = name
        self.preprocessor = preprocessor
        self.backend = backend
        self.model_type = model_type  # chat | completion | both

    @property
    def serves_chat(self) -> bool:
        return self.model_type in ("chat", "both")

    @property
    def serves_completion(self) -> bool:
        return self.model_type in ("completion", "both")


class ModelManager:
    def __init__(self):
        self._models: dict[str, ModelPipeline] = {}

    def add(self, pipeline: ModelPipeline) -> None:
        self._models[pipeline.name] = pipeline

    def remove(self, name: str) -> Optional[ModelPipeline]:
        return self._models.pop(name, None)

    def get(self, name: Optional[str]) -> Optional[ModelPipeline]:
        if name in self._models:
            return self._models[name]
        if name is None and len(self._models) == 1:
            return next(iter(self._models.values()))
        return None

    def list_models(self) -> list[str]:
        return sorted(self._models)


class HttpService:
    def __init__(
        self,
        manager: Optional[ModelManager] = None,
        host: str = "0.0.0.0",
        port: int = 8080,
        extra_metrics: Optional[Callable[[], str]] = None,
        slo=None,  # Optional[SloTracker]: rolling TTFT/ITL SLO state
        readiness: Optional[Callable[[], tuple]] = None,
        step_source: Optional[Callable[..., dict]] = None,
        qos=None,  # Optional[AdmissionController]: multi-tenant QoS plane
        cost_source: Optional[Callable[[str], Optional[dict]]] = None,
    ):
        self.manager = manager or ModelManager()
        self.host = host
        self.port = port
        self.metrics = Metrics()
        # SLO tracker (utils/slo.py): fed TTFT/ITL alongside the histograms,
        # rendered into /metrics, and surfaced on /ready. Default tracker has
        # targets from the DYNTPU_SLO_*_MS env knobs (untargeted metrics
        # still report percentiles).
        if slo is None:
            from dynamo_tpu.utils.slo import SloTracker, targets_from_env

            slo = SloTracker(targets_from_env())
        self.slo = slo
        # goodput plane (utils/goodput.py): one RequestOutcome per served
        # request — TTFT + the per-chunk ITL series + tenant/adapter tags —
        # rendered as dynamo_goodput_* on /metrics. Budgets default to the
        # SLO targets; untargeted frontends still count errors.
        from dynamo_tpu.utils.goodput import GoodputTracker

        self.goodput = GoodputTracker(
            ttft_budget_s=self.slo.targets.get("ttft"),
            itl_budget_s=self.slo.targets.get("itl"),
        )
        # multi-tenant QoS plane (utils/qos.py): priority classes from the
        # x-priority header or per-tenant/adapter policy, per-tenant token
        # budgets answering retriable 429 + Retry-After BEFORE any SSE
        # bytes, and an engine-backpressure check that sheds batch-class
        # load first. Default controller comes from the DYNTPU_QOS_BUDGETS /
        # DYNTPU_QOS_PRIORITIES env specs; with neither set it carries no
        # budgets (nothing throttles) but still classifies and counts.
        if qos is None:
            from dynamo_tpu.utils.qos import AdmissionController, QosPolicy

            qos = AdmissionController(QosPolicy.from_env())
        self.qos = qos
        # readiness provider: () -> (ok: bool, detail: dict). None = always
        # ready (a bare service with no downstream dependency to gate on).
        # FrontendService wires downstream-worker liveness through this; the
        # colocated engine frontend wires the engine's HealthMonitor.
        self._readiness = readiness
        self._extra_metrics = extra_metrics
        # step-anatomy source for a colocated engine: (limit=, kind=) ->
        # {"records": [...], "summary": {...}} (AsyncJaxEngine.debug_steps)
        self._step_source = step_source
        # cost-footer source for a colocated engine: (request_id) -> the
        # MeterLedger footer (device-ms by dispatch kind + peak KV bytes per
        # tier) or None (AsyncJaxEngine.request_cost). Merged into
        # /debug/requests/{id} under a "cost" key.
        self._cost_source = cost_source
        self._runner: Optional[web.AppRunner] = None
        self.app = web.Application()
        self.app.router.add_post("/v1/chat/completions", self._chat)
        self.app.router.add_post("/v1/completions", self._completions)
        self.app.router.add_get("/v1/models", self._models)
        self.app.router.add_get("/metrics", self._metrics)
        self.app.router.add_get("/trace", self._trace)
        self.app.router.add_get("/debug/steps", self._debug_steps)
        self.app.router.add_get("/debug/requests/{rid}", self._debug_request)
        self.app.router.add_get("/health", self._health)
        # probe split: /live answers "is this process running" and must never
        # block on (or 503 because of) the model manager or any downstream;
        # /ready answers "should a load balancer send traffic here"
        self.app.router.add_get("/live", self._live)
        self.app.router.add_get("/ready", self._ready)

    # ---------------- lifecycle ----------------

    async def start(self) -> int:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, self.host, self.port)
        await site.start()
        self.port = site._server.sockets[0].getsockname()[1]
        log.info("http service listening on %s:%d", self.host, self.port)
        return self.port

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()

    async def run_forever(self) -> None:
        await self.start()
        while True:
            await asyncio.sleep(3600)

    # ---------------- handlers ----------------

    async def _health(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "ok", "models": self.manager.list_models()})

    async def _live(self, request: web.Request) -> web.Response:
        # static by design: liveness must stay 200 while readiness flaps
        return web.json_response({"status": "live"})

    def set_readiness(self, provider: Callable[[], tuple]) -> None:
        self._readiness = provider

    async def _ready(self, request: web.Request) -> web.Response:
        ok, detail = True, {}
        if self._readiness is not None:
            try:
                result = self._readiness()
                if asyncio.iscoroutine(result):
                    result = await result
                ok, detail = result
            except Exception as e:
                ok, detail = False, {"error": str(e)}
        slo = self.slo.snapshot()
        body = {
            "status": "ready" if ok else "unready",
            "models": self.manager.list_models(),
            # informational: an exhausted error budget degrades, it does not
            # pull the pod out of rotation (that would shed the very traffic
            # the SLO exists for)
            "slo_ok": slo["ok"],
            # how many frontend replicas this door's admission buckets are
            # split across (1 = it holds the whole fleet budget itself)
            "qos_fleet_replicas": max(1, int(self.qos.policy.fleet_replicas)),
            **detail,
        }
        return web.json_response(body, status=200 if ok else 503)

    async def _models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": name, "object": "model", "owned_by": "dynamo-tpu"}
                    for name in self.manager.list_models()
                ],
            }
        )

    async def _metrics(self, request: web.Request) -> web.Response:
        extra = (self.slo.render_metrics() + self.slo.render_burn_metrics()
                 + self.goodput.render_metrics() + self.qos.render_metrics()
                 + events.JOURNAL.render_metrics())
        if self._extra_metrics:
            extra += self._extra_metrics()
        return web.Response(text=self.metrics.render(extra), content_type="text/plain")

    async def _trace(self, request: web.Request) -> web.Response:
        """Debug endpoint: the in-memory span ring as a Perfetto-loadable
        Chrome-trace document. ``?trace_id=`` / ``?request_id=`` filter to one
        request's stitched timeline; empty unless tracing is enabled
        (DYNTPU_TRACE=<path> or tracing.enable())."""
        doc = tracing.export()
        tid = request.query.get("trace_id")
        rid = request.query.get("request_id")
        if tid or rid:
            doc["traceEvents"] = tracing.events(trace_id=tid, request_id=rid)
        return web.json_response(doc)

    async def _debug_steps(self, request: web.Request) -> web.Response:
        """Debug endpoint: the colocated engine's recent step-anatomy records
        (utils/step_anatomy.py) — per-dispatch host-prep/dispatch/device-wait/
        reconcile milliseconds plus the host/roofline summary fractions.
        ``?limit=`` caps the record count, ``?kind=`` filters to one dispatch
        kind (decode_window, prefill_packed, ...). Frontends with no engine
        attached answer with an empty record list."""
        if self._step_source is None:
            return web.json_response({"records": [], "summary": {}})
        try:
            limit = int(request.query.get("limit", 128))
        except ValueError:
            limit = 128
        kind = request.query.get("kind") or None
        return web.json_response(self._step_source(limit=limit, kind=kind))

    async def _debug_request(self, request: web.Request) -> web.Response:
        """Per-request forensics: the flight recorder's causally ordered
        event chain for one request id, with inter-event durations
        (``dt_ms``) and the pin verdict. Served from the live journal merged
        with the capture ring, so over-budget/erroring requests stay
        reconstructable after ring eviction (utils/events.py). A colocated
        engine with metering on appends the request's cost footer
        (utils/metering.py): device-ms by dispatch kind + peak resident KV
        bytes per tier — what this request COST, alongside what happened."""
        rid = request.match_info["rid"]
        doc = events.JOURNAL.timeline(rid)
        if self._cost_source is not None:
            try:
                cost = self._cost_source(rid)
            except Exception:
                cost = None
            if cost is not None:
                doc["cost"] = cost
        return web.json_response(doc)

    def _error(
        self, status: int, message: str, code: str | None = None,
        headers: dict | None = None,
    ) -> web.Response:
        err = {"message": message, "type": "invalid_request_error"}
        if code:
            err["code"] = code  # e.g. context_length_exceeded
        return web.json_response({"error": err}, status=status, headers=headers)

    async def _chat(self, request: web.Request) -> web.StreamResponse:
        return await self._handle(request, kind="chat")

    async def _completions(self, request: web.Request) -> web.StreamResponse:
        return await self._handle(request, kind="completion")

    async def _handle(self, request: web.Request, kind: str) -> web.StreamResponse:
        endpoint = "chat_completions" if kind == "chat" else "completions"
        t0 = time.monotonic()
        try:
            body = await request.json()
        except Exception:
            self.metrics.inc_request("unknown", endpoint, "unary", "400")
            return self._error(400, "invalid JSON body")
        try:
            req = (
                ChatCompletionRequest.from_dict(body)
                if kind == "chat"
                else CompletionRequest.from_dict(body)
            )
        except ProtocolError as e:
            self.metrics.inc_request(str(body.get("model")), endpoint, "unary", "400")
            return self._error(400, str(e), code=e.code)

        pipeline = self.manager.get(req.model)
        if pipeline is None:
            # structured OpenAI 404 (error.code model_not_found) on BOTH
            # unary and stream paths: the model/adapter check runs before any
            # SSE response starts, so a stream=true request naming an unknown
            # LoRA adapter gets a plain JSON error, never SSE bytes
            self.metrics.inc_request(str(req.model), endpoint, "unary", "404")
            return self._error(
                404, f"model {req.model!r} not found", code="model_not_found"
            )
        if kind == "chat" and not pipeline.serves_chat:
            return self._error(400, f"model {req.model!r} does not serve chat")
        if kind == "completion" and not pipeline.serves_completion:
            return self._error(400, f"model {req.model!r} does not serve completions")

        model = pipeline.name
        rtype = "stream" if req.stream else "unary"

        # pre-admission availability: a draining backend that cannot migrate
        # its load answers a RETRIABLE 503 with Retry-After — on both the
        # unary and stream paths, and always BEFORE any SSE bytes (the check
        # runs ahead of preprocessing and the stream response), so clients
        # and load balancers can re-dispatch instead of surfacing an error
        avail_fn = getattr(pipeline.backend, "availability", None)
        if avail_fn is not None:
            try:
                avail = avail_fn()
                if asyncio.iscoroutine(avail):
                    avail = await avail
            except Exception:
                avail = None
            if avail and not avail.get("servable", True) and avail.get("retriable"):
                self.metrics.inc_request(model, endpoint, rtype, "503")
                retry_after = int(avail.get("retry_after_s", 10))
                return self._error(
                    503,
                    avail.get("reason", f"model {model!r} is draining; retry"),
                    code="model_draining",
                    headers={"Retry-After": str(retry_after)},
                )

        # ---------- multi-tenant QoS admission (utils/qos.py) ----------
        # priority class: explicit x-priority header wins (strict parse — an
        # unknown class is a 400, not a silent downgrade), else the policy's
        # per-tenant/adapter default
        tenant = request.headers.get("x-tenant", "")
        adapter = model.split(":", 1)[1] if ":" in model and "{" not in model else ""
        from dynamo_tpu.utils.qos import parse_priority

        try:
            priority = parse_priority(request.headers.get("x-priority"))
        except ValueError as e:
            self.metrics.inc_request(model, endpoint, rtype, "400")
            return self._error(400, str(e), code="invalid_priority")
        if not request.headers.get("x-priority"):
            priority = self.qos.policy.priority_for(tenant, adapter)

        # seeded admission chaos knob (DYNTPU_FAULT_ADMISSION): deterministic
        # retriable 429s / injected delays so client retry/backoff and the
        # shed path are testable without real overload
        from dynamo_tpu.disagg.faults import admission_plan

        fault = admission_plan()
        if fault is not None:
            delay = fault.delay_s()
            if delay > 0:
                await asyncio.sleep(delay)
            if fault.should_reject():
                # shed happens before the preprocessor stamps a request id:
                # a client-supplied x-request-id keeps the shed chain
                # reconstructable via /debug/requests/{id}
                rid = request.headers.get("x-request-id", "")
                self.qos.record_shed(tenant, priority, request_id=rid)
                if rid:
                    events.JOURNAL.pin(rid, "shed")
                self.metrics.inc_request(model, endpoint, rtype, "429")
                return self._error(
                    429, "admission fault injected (DYNTPU_FAULT_ADMISSION)",
                    code="rate_limited", headers={"Retry-After": "1"},
                )

        # engine backpressure: estimated queue wait (depth x measured drain
        # rate) against the TTFT budget — batch-class load sheds FIRST with
        # a retriable 429, always before any SSE bytes, so interactive
        # classes keep their budgets through an overload
        if priority == "batch":
            bp_fn = getattr(pipeline.backend, "backpressure", None)
            bp = None
            if bp_fn is not None:
                try:
                    bp = bp_fn()
                    if asyncio.iscoroutine(bp):
                        bp = await bp
                except Exception:
                    bp = None
            if bp and bp.get("est_wait_s") is not None:
                budget = self.slo.targets.get("ttft") or self.qos.policy.shed_wait_s
                if bp["est_wait_s"] > budget:
                    rid = request.headers.get("x-request-id", "")
                    self.qos.record_shed(tenant, priority, request_id=rid)
                    if rid:
                        events.JOURNAL.pin(rid, "shed")
                    self.metrics.inc_request(model, endpoint, rtype, "429")
                    return self._error(
                        429,
                        f"engine overloaded (estimated wait "
                        f"{bp['est_wait_s']:.1f}s exceeds the "
                        f"{budget:.1f}s budget); batch-class load shed",
                        code="overloaded",
                        headers={"Retry-After": str(bp.get("retry_after_s", 10))},
                    )
        try:
            # off the event loop: chat-template render + BPE encode are
            # CPU-bound (the tokenizer's Rust encode releases the GIL), and a
            # request burst otherwise serializes its preprocessing ahead of
            # every stream's first token (r5: ~160 ms of the burst TTFT gap
            # between the HTTP and engine-loop legs at bs32). The dedicated
            # small pool (not the default executor) bounds thread-local
            # tokenizer loads to its worker count — see
            # llm/tokenizer.py:preprocessing_executor.
            from dynamo_tpu.llm.tokenizer import preprocessing_executor

            loop = asyncio.get_running_loop()
            t_pre = time.monotonic()
            if kind == "chat":
                pre, annotations = await loop.run_in_executor(
                    preprocessing_executor(), pipeline.preprocessor.preprocess_chat, req
                )
            else:
                pre, annotations = await loop.run_in_executor(
                    preprocessing_executor(), pipeline.preprocessor.preprocess_completion, req
                )
            t_pre_end = time.monotonic()
        except ProtocolError as e:
            # includes the preprocessor's context-length rejection: the
            # client gets a structured 400 with error.code
            # "context_length_exceeded", not a 500 or an SSE abort (the
            # check runs before any stream response starts)
            self.metrics.inc_request(model, endpoint, rtype, "400")
            return self._error(400, str(e), code=e.code)

        # per-tenant token-rate budget: charge prompt tokens + the output
        # budget against the tenant's bucket; an exhausted budget answers a
        # structured retriable 429 whose Retry-After says when the bucket
        # will hold this request's cost — before any SSE bytes
        cost = len(pre.token_ids) + max(0, pre.sampling.max_tokens)
        decision = self.qos.admit(
            tenant, priority, cost,
            request_id=getattr(pre, "request_id", "") or "",
        )
        if not decision.admitted:
            self.metrics.inc_request(model, endpoint, rtype, "429")
            return self._error(
                429, decision.reason + "; retry later", code="rate_limited",
                headers={"Retry-After": str(decision.retry_after_s)},
            )

        tool_matcher = None
        if kind == "chat" and req.tool_choice not in (None, "none") and not req.tools:
            self.metrics.inc_request(model, endpoint, rtype, "400")
            return self._error(400, "tool_choice requires a non-empty tools list")
        if kind == "chat" and req.tools and req.tool_choice != "none":
            try:
                tool_matcher = ToolCallingMatcher(req.tool_choice)
            except ValueError as e:
                self.metrics.inc_request(model, endpoint, rtype, "400")
                return self._error(400, str(e))
            if tool_matcher.forced_name is not None:
                known = {
                    (t.get("function") or {}).get("name")
                    for t in req.tools
                    if isinstance(t, dict)
                }
                if tool_matcher.forced_name not in known:
                    self.metrics.inc_request(model, endpoint, rtype, "400")
                    return self._error(
                        400,
                        f"tool_choice function {tool_matcher.forced_name!r} "
                        "is not in tools",
                    )

        # ambient request context: the trace/request ids stamped here ride
        # every downstream hop this request makes (workers, routers — see
        # dynamo_tpu/runtime/context.py); use_context resets on exit so
        # keep-alive connections (same task across requests) can't leak it
        meta = {"endpoint": endpoint, "model": model}
        if request.headers.get("x-request-id"):
            meta["x-request-id"] = request.headers["x-request-id"]
        ctx = new_context(request_id=getattr(pre, "request_id", None), metadata=meta)
        # the edge stamps the trace id: every downstream hop (processor,
        # workers) inherits it through the context's metadata bag, so one
        # request's spans stitch into a single multi-hop timeline
        ctx.ensure_trace_id()
        if tracing.enabled():
            tracing.record_span(
                "http.preprocess", t_pre, end=t_pre_end,
                request_id=ctx.request_id, trace_id=ctx.trace_id,
                attrs={"tokens": len(pre.token_ids)},
            )

        self.metrics.inflight(model, 1)
        try:
            with use_context(ctx):
                # completions echo: the prompt text leads the output stream
                # (token-id prompts echo their detokenization)
                echo_text = None
                if kind == "completion" and getattr(req, "echo", False):
                    if pre.logprobs is not None:
                        # OpenAI returns logprobs for echoed prompt tokens;
                        # prompt logprobs aren't computed here, so reject the
                        # combination explicitly rather than return a response
                        # that silently omits them
                        self.metrics.inc_request(model, endpoint, rtype, "400")
                        return self._error(
                            400, "echo with logprobs is not supported"
                        )
                    if isinstance(req.prompt, str):
                        echo_text = req.prompt
                    else:
                        echo_text = pipeline.preprocessor.tokenizer.decode(
                            pre.token_ids,
                            skip_special_tokens=pre.skip_special_tokens,
                        )
                # goodput/QoS tags: tenant/scenario/priority ride the
                # PreprocessedRequest to the engine so BOTH trackers (this
                # frontend's and the engine's) attribute the request and the
                # scheduler serves it at the admitted class
                pre.tenant = tenant
                pre.scenario = request.headers.get("x-scenario", "")
                pre.priority = priority
                chunks = self._generate_chunks(
                    pipeline, pre, kind, model, annotations, tool_matcher,
                    echo_text=echo_text,
                    tenant=pre.tenant,
                    priority=priority,
                    t_arrival=t0,
                )
                if req.stream:
                    return await self._stream_response(request, chunks, model, endpoint, t0)
                if kind == "chat":
                    result = await aggregate_chat_stream(chunks)
                else:
                    result = await aggregate_completion_stream(chunks)
            self.metrics.inc_request(model, endpoint, rtype, "200")
            return web.json_response(result)
        except ToolCallError as e:
            # model output did not satisfy a required/forced tool choice
            self.metrics.inc_request(model, endpoint, rtype, "422")
            return self._error(422, str(e))
        except Exception:
            log.exception("request failed")
            self.metrics.inc_request(model, endpoint, rtype, "500")
            return self._error(500, "internal error")
        finally:
            self.metrics.inflight(model, -1)
            self.metrics.observe_duration(model, endpoint, time.monotonic() - t0)
            tracing.record_span(
                "http.request", t0, end=time.monotonic(),
                request_id=ctx.request_id, trace_id=ctx.trace_id,
                attrs={"endpoint": endpoint, "model": model},
            )

    async def _generate_chunks(
        self,
        pipeline: ModelPipeline,
        pre,
        kind: str,
        model: str,
        annotations: dict,
        tool_matcher: Optional[ToolCallingMatcher] = None,
        echo_text: Optional[str] = None,
        tenant: str = "",
        priority: str = "",
        *,
        t_arrival: float,
    ) -> AsyncIterator[dict]:
        gen = (
            ChatDeltaGenerator(model) if kind == "chat" else CompletionDeltaGenerator(model)
        )
        usage = Usage(prompt_tokens=len(pre.token_ids))
        # requested annotations ride the SSE stream as named events, ahead of
        # the first delta (reference: protocols/annotated.rs envelope)
        for name, value in annotations.items():
            yield {"__event__": name, "data": value}
        if echo_text:
            yield gen.text_chunk(echo_text)
        want_timing = "timing" in pre.annotations
        t_start = time.monotonic()
        t_first = None
        t_prev = None  # last output-chunk arrival, for inter-token latency
        # goodput outcome accounting: the per-token gap series (amortized
        # over each chunk's tokens, same as the ITL histogram) + the
        # adapter suffix of a base:adapter LoRA model name
        itl_gaps: list = []
        adapter = model.split(":", 1)[1] if ":" in model and "{" not in model else ""
        # With tools active the full text must be buffered so a tool-call JSON
        # response never leaks as content deltas (tool calls are matched on
        # complete messages, llm/tools.py).
        buffered: list[str] = []
        buffered_lp: list = []
        async for out in pipeline.backend.generate(pre):
            usage.completion_tokens = out.cumulative_tokens
            if t_first is None and out.token_ids:
                t_first = t_prev = time.monotonic()
                # the histogram's help says "from request arrival": parsing,
                # preprocessing and admission are part of it (t_arrival is the
                # handler's first line; t_start is after them)
                self.metrics.observe_ttft(model, t_first - t_arrival)
                self.slo.observe(
                    "ttft", t_first - t_start, tenant=tenant, priority=priority
                )
                # OpenAI semantics: the role delta leads the stream at first-
                # token time. Also the client's only honest TTFT signal — the
                # first CONTENT delta can lag several tokens behind while the
                # detokenizer waits for a stable byte sequence.
                role = getattr(gen, "role_chunk", None)
                if role is not None and not gen._sent_role:
                    yield role()
            elif t_prev is not None and out.token_ids:
                # engine windows arrive as multi-token chunks: the honest
                # per-token number is the chunk gap amortized over its tokens
                now = time.monotonic()
                gap = (now - t_prev) / len(out.token_ids)
                self.metrics.observe_itl(model, gap)
                self.slo.observe("itl", gap, tenant=tenant, priority=priority)
                if len(itl_gaps) < MAX_ITL_SAMPLES:
                    itl_gaps.extend([gap] * min(
                        len(out.token_ids), MAX_ITL_SAMPLES - len(itl_gaps)
                    ))
                t_prev = now
            if tool_matcher is not None:
                if out.text:
                    buffered.append(out.text)
                if out.logprobs:
                    buffered_lp.extend(out.logprobs)
            elif out.text or out.logprobs:
                yield gen.text_chunk(out.text, logprobs=out.logprobs)
            if out.finished:
                finish = out.finish_reason or "stop"
                self._record_outcome(
                    pre, model, tenant, adapter, finish, t_start, t_first,
                    itl_gaps, usage, out.cached_tokens,
                )
                if tool_matcher is not None:
                    text = "".join(buffered)
                    calls = tool_matcher.get_calls(text)
                    if calls:
                        yield gen.tool_calls_chunk(calls)
                        finish = "tool_calls"
                    elif text:
                        yield gen.text_chunk(text, logprobs=buffered_lp or None)
                if want_timing:
                    total = time.monotonic() - t_start
                    ttft = (t_first - t_start) if t_first is not None else None
                    decode_s = (time.monotonic() - t_first) if t_first is not None else 0.0
                    yield {
                        "__event__": "timing",
                        "data": {
                            "ttft_ms": round(ttft * 1e3, 1) if ttft is not None else None,
                            "total_ms": round(total * 1e3, 1),
                            "output_tokens": usage.completion_tokens,
                            "cached_tokens": out.cached_tokens,
                            "decode_tok_per_s": (
                                round((usage.completion_tokens - 1) / decode_s, 1)
                                if usage.completion_tokens > 1 and decode_s > 0
                                else None
                            ),
                        },
                    }
                yield gen.finish_chunk(finish, usage)
                return

    def _record_outcome(
        self, pre, model: str, tenant: str, adapter: str, finish: str,
        t_start: float, t_first, itl_gaps: list, usage, cached_tokens: int,
    ) -> None:
        """One RequestOutcome per served request into the frontend goodput
        plane (error finishes count as SLO misses)."""
        from dynamo_tpu.utils.goodput import RequestOutcome

        try:
            self.goodput.observe(RequestOutcome(
                request_id=getattr(pre, "request_id", "") or "",
                scenario=getattr(pre, "scenario", "") or "",
                tenant=tenant,
                adapter=adapter,
                ttft_s=(t_first - t_start) if t_first is not None else None,
                itl_s=tuple(itl_gaps),
                prompt_tokens=usage.prompt_tokens,
                output_tokens=usage.completion_tokens,
                cached_tokens=cached_tokens,
                duration_s=time.monotonic() - t_start,
                finish_reason=finish,
                error=finish == "error",
            ))
        except Exception:
            log.exception("goodput outcome failed")

    async def _stream_response(
        self, request: web.Request, chunks: AsyncIterator[dict], model: str, endpoint: str, t0: float
    ) -> web.StreamResponse:
        resp = web.StreamResponse(
            status=200,
            headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "Connection": "keep-alive",
            },
        )
        await resp.prepare(request)
        status = "200"
        try:
            async for chunk in chunks:
                if "__event__" in chunk:
                    await resp.write(sse.encode_event(chunk["__event__"], chunk.get("data")))
                    continue
                await resp.write(sse.encode_data(chunk))
            await resp.write(sse.encode_done())
        except (asyncio.CancelledError, ConnectionResetError):
            status = "499"
            raise
        except ToolCallError as e:
            status = "422"
            err = json.dumps({"error": {"message": str(e), "type": "tool_call_error"}})
            await resp.write(f"data: {err}\n\ndata: [DONE]\n\n".encode())
        except Exception:
            log.exception("stream failed")
            status = "500"
            await resp.write(
                b'data: {"error": {"message": "internal error"}}\n\ndata: [DONE]\n\n'
            )
        finally:
            self.metrics.inc_request(model, endpoint, "stream", status)
        await resp.write_eof()
        return resp
