"""Per-service worker entrypoint: runs ONE @service class in this process.

reference: deploy/dynamo/sdk/src/dynamo/sdk/cli/serve_dynamo.py:37-75 —
creates the DistributedRuntime, instantiates the class, serves its @endpoint
methods, runs @async_on_start hooks, then parks until shutdown.
"""

from __future__ import annotations

import argparse
import importlib
import inspect

from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.runtime.runtime import Runtime, Worker
from dynamo_tpu.sdk.config import ServiceConfig
from dynamo_tpu.utils import get_logger

log = get_logger("sdk.serve_worker")


def load_class(spec: str):
    module_name, _, cls_name = spec.partition(":")
    module = importlib.import_module(module_name)
    return getattr(module, cls_name)


async def run_service(runtime: Runtime, cls) -> None:
    meta = cls.__dynamo_service__
    drt = DistributedRuntime(runtime=runtime)
    await drt.connect()

    instance = cls()
    instance.runtime = drt
    config = ServiceConfig.load().for_service(meta.config_key)
    instance.config = config

    # bind dependency clients
    for attr, target in getattr(cls, "__dynamo_depends__", {}).items():
        getattr(instance, attr).bind_runtime(drt)

    for hook_name in cls.__dynamo_on_start__:
        hook = getattr(instance, hook_name)
        result = hook()
        if inspect.iscoroutine(result):
            await result

    served = []
    for method_name, ep_meta in cls.__dynamo_endpoints__.items():
        handler = getattr(instance, method_name)
        ep = drt.namespace(meta.namespace).component(meta.component).endpoint(ep_meta["name"])
        metrics = getattr(instance, "stats_handler", None)
        served.append(await ep.serve_endpoint(handler, metrics=metrics))
        log.info("serving %s/%s/%s", meta.namespace, meta.component, ep_meta["name"])

    await runtime.cancellation.cancelled()
    for s in served:
        await s.stop()
    stop = getattr(instance, "on_shutdown", None)
    if stop is not None:
        result = stop()
        if inspect.iscoroutine(result):
            await result


def main(argv=None) -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("service", help="module.path:ClassName")
    args = parser.parse_args(argv)
    cls = load_class(args.service)
    if (cls.__dynamo_service__.resources or {}).get("tpu"):
        # this process will start an engine: restarts reload executables
        from dynamo_tpu.utils.xla_cache import enable_compilation_cache

        enable_compilation_cache()
    Worker.execute(lambda runtime: run_service(runtime, cls))


if __name__ == "__main__":
    main()
