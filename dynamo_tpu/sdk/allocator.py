"""TPU chip assignment for the serve supervisor.

reference: deploy/dynamo/sdk/src/dynamo/sdk/cli/allocator.py:33-134
(ResourceAllocator.assign_gpus / get_worker_env). Ours allocates TPU chips
instead of CUDA devices: each worker process gets a disjoint chip set via
`TPU_VISIBLE_DEVICES` (libtpu honours it the way CUDA honours
CUDA_VISIBLE_DEVICES); services that request no TPU are pinned to
`JAX_PLATFORMS=cpu` so importing jax in them never grabs the chips.

A chip belongs to one process at a time: a second process that opens it fails
or hangs. So a request is for whole chips, each chip is assigned once, and a
service that asks for chips where none (or too few) are detected fails at
start-up with a message instead of leaving its workers to contend for
whatever is visible. To run a chip-requesting graph on the CPU, give the
service ``resources: {tpu: 0}`` in its YAML section.

Set DYNTPU_DISABLE_TPU_ALLOCATION=1 to manage visibility manually, and
DYNTPU_DEPLOYMENT_ENV for K8s replica mode (every replica gets the same
assignment; the pod boundary provides isolation) — mirrors
DYNAMO_DISABLE_GPU_ALLOCATION / DYNAMO_DEPLOYMENT_ENV.
"""

from __future__ import annotations

import glob
import os

DISABLE_TPU_ALLOCATION_ENV = "DYNTPU_DISABLE_TPU_ALLOCATION"
DEPLOYMENT_ENV = "DYNTPU_DEPLOYMENT_ENV"
NUM_CHIPS_ENV = "DYNTPU_TPU_CHIPS"  # override detection, e.g. =4


def detect_tpu_chips() -> int:
    """Count local TPU chips without importing jax (cheap, fork-safe)."""
    if NUM_CHIPS_ENV in os.environ:
        return int(os.environ[NUM_CHIPS_ENV])
    # TPU VM runtimes expose one /dev/accel<N> (or vfio group) per chip.
    accel = glob.glob("/dev/accel[0-9]*")
    if accel:
        return len(accel)
    vfio = [p for p in glob.glob("/dev/vfio/[0-9]*")]
    return len(vfio)


def chip_env(chips: list[int]) -> dict[str, str]:
    """Environment that confines one process to ``chips``. libtpu opens every
    chip of the host unless told otherwise; a one-chip worker also needs the
    1x1x1 process bounds, or it waits for the host's other chips."""
    env = {"TPU_VISIBLE_DEVICES": ",".join(map(str, chips))}
    if len(chips) == 1:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = "1,1,1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


class ResourceAllocator:
    """Splits the host's TPU chips across service workers."""

    def __init__(self, total_chips: int | None = None) -> None:
        self.total_chips = detect_tpu_chips() if total_chips is None else total_chips
        self._next_chip = 0

    @property
    def remaining_chips(self) -> int:
        return self.total_chips - self._next_chip

    def assign_chips(self, count) -> list[int]:
        """Assign `count` whole chips, each to one worker only. Returns ids."""
        if count < 1 or int(count) != count:
            raise ValueError(
                f"TPU request {count!r}: a chip cannot be shared (one process "
                "opens it, the next fails or hangs); ask for whole chips"
            )
        count = int(count)
        if count > self.remaining_chips:
            raise RuntimeError(
                f"{count} TPU chip(s) requested but {self.remaining_chips} of "
                f"{self.total_chips} detected remain unassigned "
                f"(/dev/accel*, /dev/vfio/*; override with {NUM_CHIPS_ENV}). "
                "Give the service `resources: {tpu: 0}` to run it on the CPU, "
                f"or set {DISABLE_TPU_ALLOCATION_ENV}=1 to manage chip "
                "visibility yourself."
            )
        chips = list(range(self._next_chip, self._next_chip + count))
        self._next_chip += count
        return chips

    def get_worker_env(self, meta, config: dict) -> tuple[int, list[dict[str, str]]]:
        """(num_workers, per-worker env) for a service.

        `meta` is the ServiceMeta from @service; `config` the service's YAML
        section (may override workers/resources).
        """
        resources = config["resources"] if "resources" in config else meta.resources
        resources = resources or {}
        num_chips = resources.get("tpu", 0)
        workers = config.get("workers", meta.workers)
        if workers == "cpu_count":
            workers = os.cpu_count() or 1
            num_chips = 0
        num_workers = int(workers)

        if not num_chips or os.environ.get(DISABLE_TPU_ALLOCATION_ENV):
            # No chips for this service: keep jax off the TPU entirely.
            env = {"JAX_PLATFORMS": "cpu"} if not num_chips else {}
            return num_workers, [dict(env) for _ in range(num_workers)]

        if os.environ.get(DEPLOYMENT_ENV):
            # K8s replicas: every replica pod gets the same visible set.
            env = chip_env(self.assign_chips(num_chips))
            return num_workers, [dict(env) for _ in range(num_workers)]

        return num_workers, [
            chip_env(self.assign_chips(num_chips)) for _ in range(num_workers)
        ]
