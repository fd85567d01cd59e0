"""Same-pod (ICI) KV transfer for disaggregated prefill/decode.

When the prefill and decode engines share a process (one TPU pod host serving
both roles on different mesh slices, or colocated workers), KV blocks never
need to touch host memory or the network data plane: the prefill side gathers
the blocks into a device array (ModelRunner.extract_pages_device) and the
decode side reshards it onto its own mesh with jax.device_put — on multi-chip
hardware that transfer rides the inter-chip interconnect (ICI), the analogue
of the reference's NIXL RDMA WRITE between GPUs (reference: patch
vllm/distributed/device_communicators/nixl.py). The control message
(PrefillResult) still travels the normal response plane; only the bulk KV
payload is handed off in-process.

The hub is a process-local registry: decode engines register under their
worker id; a prefill worker that finds its target here uses the device path
and parks the gathered array under the request id until the decode side
adopts it.
"""

from __future__ import annotations

import threading

import time

_lock = threading.Lock()
_local_workers: set[int] = set()  # decode worker ids served in this process
_transfers: dict[str, object] = {}  # transfer key -> device array
# abandoned keys whose park may still be in flight -> tombstone timestamp.
# TTL'd: a park that hasn't landed within the TTL never will (it's queued on
# an engine thread in this process), so stale entries are pruned instead of
# ever clearing the whole set (which could drop live tombstones and leak).
_tombstones: dict[str, float] = {}
_TOMBSTONE_TTL_S = 600.0
_total = 0  # device transfers ever started (observability/tests)


def _prune_tombstones_locked(now: float) -> None:
    if len(_tombstones) > 1024:
        dead = [k for k, t in _tombstones.items() if now - t > _TOMBSTONE_TTL_S]
        for k in dead:
            del _tombstones[k]


def register_worker(worker_id: int) -> None:
    with _lock:
        _local_workers.add(worker_id)


def unregister_worker(worker_id: int) -> None:
    with _lock:
        _local_workers.discard(worker_id)


def is_local(worker_id: int) -> bool:
    with _lock:
        return worker_id in _local_workers


def transfer_key(decode_worker_id: int, request_id: str) -> str:
    """Request ids are only unique per decode worker; the key namespaces them
    so colocated decode workers can never collide."""
    return f"{decode_worker_id}/{request_id}"


def put_transfer(transfer_id: str, data) -> bool:
    """Park a gathered device array. Returns False (and drops the data) when
    the consumer already abandoned the request — its discard_transfer left a
    tombstone because cancellation can land while the prefill engine thread is
    still producing, i.e. before there is anything to pop."""
    global _total
    with _lock:
        if transfer_id in _tombstones:
            del _tombstones[transfer_id]
            return False
        _transfers[transfer_id] = data
        _total += 1
        return True


def pop_transfer(transfer_id: str):
    with _lock:
        return _transfers.pop(transfer_id, None)


def discard_transfer(transfer_id: str) -> None:
    """Consumer-side abandon: drop the parked array now, or leave a tombstone
    so a park that is still in flight on the producer side gets dropped on
    arrival instead of leaking device memory."""
    now = time.monotonic()
    with _lock:
        if _transfers.pop(transfer_id, None) is None:
            _prune_tombstones_locked(now)
            _tombstones[transfer_id] = now


def clear_tombstone(transfer_id: str) -> None:
    """Called when a request id is (re)used for a fresh remote prefill so a
    stale tombstone from an earlier cancelled attempt can't swallow its KV."""
    with _lock:
        _tombstones.pop(transfer_id, None)


def transfer_count() -> int:
    """Parked (not yet adopted) transfers."""
    with _lock:
        return len(_transfers)


def total_transfers() -> int:
    """Device transfers ever started."""
    with _lock:
        return _total
