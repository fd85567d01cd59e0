"""`dynamo-tpu serve` supervisor: launch a whole service graph from one entry.

reference: deploy/dynamo/sdk/src/dynamo/sdk/cli/{serve.py,serving.py} — the
circus-based process-per-service supervisor. Ours: discover the dependency
graph from the entry @service class, optionally start an embedded broker,
spawn one subprocess per service (x workers), restart on failure, tear down
on SIGINT.

    python -m dynamo_tpu.sdk.serve examples.graphs.agg:Frontend -f agg.yaml
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import time

from dynamo_tpu.sdk.allocator import ResourceAllocator
from dynamo_tpu.sdk.config import ENV_KEY, ServiceConfig
from dynamo_tpu.sdk.serve_worker import load_class
from dynamo_tpu.utils import get_logger

log = get_logger("sdk.serve")


def discover_graph(entry_cls) -> list[type]:
    """Entry class + transitive depends() targets, dependency-first order."""
    seen: dict[type, None] = {}

    def visit(cls):
        if cls in seen:
            return
        for target in getattr(cls, "__dynamo_depends__", {}).values():
            visit(target)
        seen[cls] = None

    visit(entry_cls)
    return list(seen)


def class_spec(cls) -> str:
    return f"{cls.__module__}:{cls.__name__}"


def _port_open(address: str) -> bool:
    host, _, port = address.rpartition(":")
    try:
        with socket.create_connection((host or "127.0.0.1", int(port)), timeout=0.5):
            return True
    except OSError:
        return False


class Supervisor:
    def __init__(
        self,
        entry_spec: str,
        config: dict,
        cplane: str,
        restart: bool = True,
        planner_scaling: bool = False,
        planner_poll_s: float = 5.0,
    ):
        self.entry_spec = entry_spec
        self.config = config
        self.cplane = cplane
        self.restart = restart
        self.children: dict[str, subprocess.Popen] = {}
        self.broker_proc = None
        self._stopping = False
        self.allocator = ResourceAllocator()
        self._worker_envs: dict[str, dict[str, str]] = {}
        # planner-driven scaling (components/planner.py publishes desired
        # replica counts; the supervisor is the single-host consumer — the
        # deploy reconciler is the K8s one)
        self.planner_scaling = planner_scaling
        self.planner_poll_s = planner_poll_s
        self.desired: dict[str, int] = {}  # class name -> replica count
        self._class_info: dict[str, tuple] = {}  # name -> (cls, meta, envs)
        self._last_planner_poll = 0.0

    def _env(self) -> dict:
        env = dict(os.environ)
        env[ENV_KEY] = json.dumps(self.config)
        env["DYNTPU_CPLANE"] = self.cplane
        return env

    def ensure_broker(self) -> None:
        if _port_open(self.cplane):
            log.info("control plane already running at %s", self.cplane)
            return
        host, _, port = self.cplane.rpartition(":")
        self.broker_proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.cplane.broker", "--host", host or "127.0.0.1",
             "--port", port],
            env=self._env(),
        )
        for _ in range(50):
            if _port_open(self.cplane):
                return
            time.sleep(0.1)
        raise RuntimeError(f"broker failed to start on {self.cplane}")

    def spawn(self, cls, replica: int, extra_env: dict[str, str] | None = None) -> None:
        spec = class_spec(cls)
        name = f"{cls.__name__}-{replica}"
        if extra_env is not None:
            self._worker_envs[name] = extra_env
        env = self._env()
        env.update(self._worker_envs.get(name, {}))
        proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.sdk.serve_worker", spec],
            env=env,
        )
        self.children[name] = proc
        log.info("spawned %s (pid %d)", name, proc.pid)

    def run(self) -> int:
        entry_cls = load_class(self.entry_spec)
        graph = discover_graph(entry_cls)
        log.info("service graph: %s", " -> ".join(c.__name__ for c in graph))
        self.ensure_broker()
        for cls in graph:
            meta = cls.__dynamo_service__
            num_workers, worker_envs = self.allocator.get_worker_env(
                meta, self.config.get(cls.__name__, {})
            )
            self.desired[cls.__name__] = num_workers
            self._class_info[cls.__name__] = (cls, meta, worker_envs)
            for i in range(num_workers):
                self.spawn(cls, i, worker_envs[i])

        def on_signal(signum, frame):
            self._stopping = True

        signal.signal(signal.SIGINT, on_signal)
        signal.signal(signal.SIGTERM, on_signal)

        exit_code = 0
        try:
            while not self._stopping:
                time.sleep(0.5)
                if self.planner_scaling:
                    self._apply_planner_scaling()
                for name, proc in list(self.children.items()):
                    rc = proc.poll()
                    if rc is None:
                        continue
                    cls_name, replica = name.rsplit("-", 1)
                    if int(replica) >= self.desired.get(cls_name, 0):
                        # scaled-down replica exiting after terminate()
                        self.children.pop(name, None)
                        continue
                    if self.restart and not self._stopping:
                        log.warning("%s exited rc=%s; restarting", name, rc)
                        cls = next(c for c in discover_graph(load_class(self.entry_spec))
                                   if c.__name__ == cls_name)
                        self.spawn(cls, int(replica))
                    else:
                        log.error("%s exited rc=%s", name, rc)
                        exit_code = rc or 1
                        self._stopping = True
                        break
        finally:
            self.shutdown()
        return exit_code

    # ---------------- planner-driven scaling ----------------

    def _read_planner_desired(self) -> dict[str, int]:
        """Fetch planner/{ns}/desired/{component} keys from the control plane.
        Returns {key: replicas}. One short-lived connection per poll."""
        import asyncio

        async def fetch():
            from dynamo_tpu.cplane.client import CplaneClient

            client = CplaneClient(self.cplane)
            await client.connect()
            try:
                items = await client.kv_get_prefix("planner/")
                out = {}
                for i in items:
                    if "/desired/" not in i.key:
                        continue
                    try:
                        out[i.key] = int(json.loads(i.value)["replicas"])
                    except Exception:
                        log.warning("malformed planner key %s", i.key)
                return out
            finally:
                await client.close()

        async def bounded():
            # the monitor loop also does crash-restarts: a hung control plane
            # must not stall it
            return await asyncio.wait_for(fetch(), timeout=3.0)

        return asyncio.run(bounded())

    def _apply_planner_scaling(self) -> None:
        now = time.time()
        if now - self._last_planner_poll < self.planner_poll_s:
            return
        self._last_planner_poll = now
        try:
            desired_by_key = self._read_planner_desired()
        except Exception as e:
            log.debug("planner poll failed: %s", e)
            return
        for cls_name, (cls, meta, envs) in self._class_info.items():
            key = f"planner/{meta.namespace}/desired/{meta.component}"
            want = desired_by_key.get(key)
            if want is not None and envs and "TPU_VISIBLE_DEVICES" in envs[0]:
                # a chip belongs to one process: never more replicas than
                # chip assignments (see allocator)
                if want > len(envs):
                    log.warning(
                        "planner wants %d x %s but only %d chip assignment(s) "
                        "exist; holding at %d", want, cls_name, len(envs), len(envs),
                    )
                    want = len(envs)
            if want is None or want == self.desired.get(cls_name):
                continue
            have = self.desired[cls_name]
            log.info("planner: scaling %s %d -> %d", cls_name, have, want)
            self.desired[cls_name] = want
            for i in range(have, want):  # scale up
                # a replica of this index terminated by an earlier scale-down
                # may still be exiting: reap it before reusing the name (two
                # live processes must not share chip assignments)
                old = self.children.pop(f"{cls_name}-{i}", None)
                if old is not None and old.poll() is None:
                    try:
                        old.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        old.kill()
                        old.wait()
                # chipless services reuse the (cpu-pinning) envs round-robin
                env = envs[i % len(envs)] if envs else None
                self.spawn(cls, i, env)
            for i in range(want, have):  # scale down, highest index first
                name = f"{cls_name}-{i}"
                proc = self.children.get(name)
                if proc is not None and proc.poll() is None:
                    proc.terminate()

    def shutdown(self) -> None:
        self._stopping = True
        for name, proc in self.children.items():
            if proc.poll() is None:
                proc.terminate()
        deadline = time.time() + 10
        for proc in self.children.values():
            try:
                proc.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                proc.kill()
        if self.broker_proc is not None and self.broker_proc.poll() is None:
            self.broker_proc.terminate()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="dynamo-tpu serve", description=__doc__)
    parser.add_argument("entry", help="module.path:ServiceClass")
    parser.add_argument("-f", "--file", default=None, help="YAML service config")
    parser.add_argument("--cplane", default=os.environ.get("DYNTPU_CPLANE", "127.0.0.1:4222"))
    parser.add_argument("--no-restart", action="store_true")
    parser.add_argument(
        "--planner-scaling", action="store_true",
        help="scale service replicas from the planner's desired-replica keys",
    )
    parser.add_argument("overrides", nargs="*", help="--Service.key=value overrides")
    args = parser.parse_args(argv)
    config = ServiceConfig.from_yaml_and_overrides(args.file, args.overrides)
    sup = Supervisor(
        args.entry, config, args.cplane, restart=not args.no_restart,
        planner_scaling=args.planner_scaling,
    )
    return sup.run()


if __name__ == "__main__":
    raise SystemExit(main())
