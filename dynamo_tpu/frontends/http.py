"""in=http: serve the local pipeline over the OpenAI HTTP frontend."""

from __future__ import annotations


from dynamo_tpu.frontends.pipeline import build_pipeline, card_for_model
from dynamo_tpu.llm.http.service import HttpService
from dynamo_tpu.utils import get_logger
from dynamo_tpu.utils.prometheus import render_family

log = get_logger("frontends.http")


def engine_metrics_text(engine) -> str:
    """Prometheus exposition for a colocated engine: ForwardPassMetrics
    gauges (one conformant family per field) + the per-stage latency
    histograms (queue wait, TTFT, prefill, decode window, reconcile)."""
    parts = []
    m = getattr(engine, "metrics", None)
    if m is not None:
        fm = m()
        for k, v in fm.to_wire().items():
            parts.append(render_family(
                f"llm_worker_{k}", "gauge", f"worker {k}", [({}, v)]
            ))
    stage = getattr(engine, "render_stage_metrics", None)
    if stage is not None:
        parts.append(stage())
    return "".join(parts)


def engine_readiness(engine):
    """/ready provider for a colocated engine: reflects the engine's
    HealthMonitor state (serving requires ready/degraded, not
    starting/draining/dead). Engines without the health plane (external
    token engines) stay ready."""

    def provider() -> tuple:
        health = getattr(engine, "health", None)
        if health is None:
            return True, {}
        snap = health.snapshot()
        ok = snap["state"] in ("ready", "degraded")
        detail = {"engine": snap}
        if ok and hasattr(engine, "device_info"):
            # what it runs on and where its compiles are cached: a probe (or
            # chip_smoke.py) reads the device from here, it does not assume it
            from dynamo_tpu.utils.xla_cache import cache_stats

            detail["device"] = engine.device_info()
            detail["xla_cache"] = cache_stats()
        return ok, detail

    return provider


async def run_http(engine, args) -> None:
    from dynamo_tpu.utils.slo import SloTracker, targets_from_env

    card = card_for_model(args.model, getattr(args, "max_model_len", None))
    pipeline = build_pipeline(engine, card)

    slo = SloTracker(targets_from_env({
        "ttft": getattr(args, "slo_ttft_ms", None),
        "itl": getattr(args, "slo_itl_ms", None),
    }))
    service = HttpService(
        port=args.http_port,
        extra_metrics=lambda: engine_metrics_text(engine),
        slo=slo,
        readiness=engine_readiness(engine),
        # step-anatomy debug plane (/debug/steps): recent per-dispatch
        # host/device phase records off the colocated engine's ring
        step_source=getattr(engine, "debug_steps", None),
        # cost footer on /debug/requests/{id}: the colocated engine's
        # MeterLedger per-request footer (utils/metering.py)
        cost_source=getattr(engine, "request_cost", None),
    )
    service.manager.add(pipeline)
    # multi-LoRA: each configured adapter serves as its own OpenAI model name
    # (<base>:<adapter>) through a lora_name-stamping preprocessor wrapper;
    # everything downstream (backend, engine) is shared
    adapters = getattr(getattr(engine, "config", None), "lora_adapters", ())
    if adapters:
        from dynamo_tpu.frontends.pipeline import lora_pipelines

        for lp in lora_pipelines(pipeline, adapters):
            service.manager.add(lp)
    await service.run_forever()
