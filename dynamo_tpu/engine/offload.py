"""Host-DRAM KV offload tier.

The TPU analogue of the reference's multi-tier KV block manager (reference:
lib/llm/src/kv/{manager,storage,layer}.rs — CUDA pinned-host staging +
copy streams; docs/architecture.md:91-96 claims +40% TTFT from system-memory
offload). On TPU-VM the host tier is plain numpy arrays in process memory;
device<->host movement goes through the runner's jitted block gather/scatter
(dynamo_tpu/engine/model_runner.py extract_pages/inject_pages).

Flow:
  - when the device prefix cache must reclaim a refcount-0 cached block, the
    block's KV is saved to the host pool instead of being dropped
  - allocate_sequence() consults the host pool after device-cache misses:
    hits are injected back into freshly-allocated device pages and count as
    cached prefix (no recompute)
  - the host pool is LRU-bounded; a victim DEMOTES to the disk tier
    (engine/kv_store.py) when one is attached, else it is dropped. Either
    way, `save`/`save_many` return only the hashes that left their LAST
    tier — the only blocks allowed to emit the `removed` KV event, so the
    prefix cache, router, and fleet state stay truthful across all three
    rungs of the ladder.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Optional

import numpy as np

from dynamo_tpu.utils import events, get_logger, tracing

log = get_logger("engine.offload")


def resolve_host_capacity_blocks(
    blocks: int, budget_bytes: int, page_bytes: int
) -> int:
    """Host-tier capacity in blocks from the two config knobs.

    ``budget_bytes`` divides by the model's ACTUAL per-page wire cost
    (model.kv_page_bytes — int8 caches store int8 pages + scale planes on
    the host too, ~half the bf16 bytes), so the same DRAM budget holds ~2x
    blocks under an int8 KV cache instead of silently assuming bf16. When
    both knobs are set the larger capacity wins. Pure arithmetic — the
    PR-8-follow-up unit tests pin it down."""
    from_bytes = budget_bytes // max(1, page_bytes) if budget_bytes > 0 else 0
    return max(blocks, from_bytes)


class HostKvPool:
    """LRU pool of KV blocks in host DRAM, keyed by chained sequence hash."""

    def __init__(self, runner, capacity_blocks: int = 0, block_bytes: int = 0):
        self.runner = runner
        self.capacity_blocks = capacity_blocks
        # per-block wire bytes at the ACTUAL cache dtype (telemetry: the
        # resident-bytes gauge; 0 = unknown, gauges render zero)
        self.block_bytes = block_bytes
        self._blocks: OrderedDict[int, np.ndarray] = OrderedDict()  # seq_hash -> [L,2,1,ps,H,D]
        #: optional engine/kv_store.DiskKvStore — the tier below this one;
        #: LRU victims demote into it instead of dropping
        self.disk = None
        self.saves = 0
        self.loads = 0
        self.drops = 0
        self.transfer_s = 0.0  # device<->host block movement (both directions)
        #: optional utils/metering.MeterLedger — byte-residency edges: blocks
        #: acquire under the owner the allocator hands down on demote, LRU
        #: victims release (carrying the owner further down to the disk tier)
        self.meter = None

    @property
    def bytes_resident(self) -> int:
        return len(self._blocks) * self.block_bytes

    def __len__(self) -> int:
        return len(self._blocks)

    def __contains__(self, seq_hash: int) -> bool:
        return seq_hash in self._blocks

    def in_any_tier(self, seq_hash: int) -> bool:
        """Membership across host DRAM AND the disk tier below it — the
        question ``lookup_prefix`` asks (any tier can still answer)."""
        return seq_hash in self._blocks or (
            self.disk is not None and seq_hash in self.disk
        )

    def _demote(self, victim: int, block, owner=None) -> list[int]:
        """One LRU victim leaves host DRAM: spill to disk when a disk tier
        is attached (returns only the hashes that left their LAST tier —
        disk-budget evictions), else the victim is simply gone. ``owner`` is
        the metering owner carried down the ladder."""
        if self.disk is None:
            return [victim]
        return self.disk.spill(victim, block, owner=owner)

    def _emit_spills(self, spills_before: int) -> None:
        """Journal the host->disk demotions a save batch caused (one batched
        event: demotion runs inside the eviction loop, per-victim events
        would swamp the ring under pressure)."""
        if self.disk is None:
            return
        n = self.disk.spills - spills_before
        if n > 0:
            events.emit("offload.disk_spill", request_id="", blocks=n)

    def save(self, seq_hash: int, page_id: int, owner=None) -> list[int]:
        """Copy a device page to host. Returns seq hashes that left their
        last tier (for removed-event emission)."""
        if self.capacity_blocks <= 0:
            return [seq_hash]  # offload disabled: block is simply gone
        t0 = time.monotonic()
        data = self.runner.extract_pages(np.asarray([page_id], np.int32))
        self.transfer_s += time.monotonic() - t0
        self._blocks[seq_hash] = data
        self._blocks.move_to_end(seq_hash)
        if self.meter is not None:
            self.meter.kv_acquire("host", seq_hash, self.block_bytes, owner)
        self.saves += 1
        dropped = []
        spills0 = self.disk.spills if self.disk is not None else 0
        while len(self._blocks) > self.capacity_blocks:
            victim, block = self._blocks.popitem(last=False)
            victim_owner = (
                self.meter.kv_release("host", victim)
                if self.meter is not None else None
            )
            dropped.extend(self._demote(victim, block, owner=victim_owner))
            self.drops += 1
        self._emit_spills(spills0)
        return dropped

    def save_many(self, pairs: list[tuple[int, int]],
                  owners: Optional[dict] = None) -> list[int]:
        """Copy a batch of device pages to host with ONE device gather (the
        pressure-eviction path: per-block save() pays a dispatch + D2H round
        trip per page, serialized into whatever allocation needed the pages).
        ``owners`` maps seq_hash -> metering owner handed down by the
        allocator. Returns seq hashes that left their last tier
        (removed-event emission)."""
        if self.capacity_blocks <= 0:
            return [h for h, _ in pairs]
        if not pairs:
            return []
        from dynamo_tpu.quant.kv import wire_split

        axis = self.runner.model.wire_n_axis
        t0 = time.monotonic()
        data = self.runner.extract_pages(
            np.asarray([p for _, p in pairs], np.int32)
        )
        blocks = wire_split(data, axis, len(pairs))
        dt = time.monotonic() - t0
        self.transfer_s += dt
        tracing.record_span("engine.kv_offload.save", t0, duration=dt,
                            attrs={"blocks": len(pairs)})
        for (seq_hash, _), block in zip(pairs, blocks):
            self._blocks[seq_hash] = block
            self._blocks.move_to_end(seq_hash)
            if self.meter is not None:
                self.meter.kv_acquire(
                    "host", seq_hash, self.block_bytes,
                    (owners or {}).get(seq_hash),
                )
        self.saves += len(pairs)
        dropped = []
        spills0 = self.disk.spills if self.disk is not None else 0
        while len(self._blocks) > self.capacity_blocks:
            victim, block = self._blocks.popitem(last=False)
            victim_owner = (
                self.meter.kv_release("host", victim)
                if self.meter is not None else None
            )
            dropped.extend(self._demote(victim, block, owner=victim_owner))
            self.drops += 1
        self._emit_spills(spills0)
        return dropped

    def load(self, seq_hash: int, page_id: int) -> bool:
        """Inject a host block into a device page. True on hit."""
        data = self._blocks.get(seq_hash)
        if data is None:
            return False
        self._blocks.move_to_end(seq_hash)
        t0 = time.monotonic()
        self.runner.inject_pages(np.asarray([page_id], np.int32), data)
        self.transfer_s += time.monotonic() - t0
        self.loads += 1
        return True

    def load_many(self, pairs: list[tuple[int, int]]) -> set[int]:
        """Inject host blocks into device pages with ONE device call.

        The per-block path pays a full dispatch + host->device transfer round
        trip per block — on a prefix-restore of N blocks that serializes N
        round trips directly into TTFT. Only the CONTIGUOUS leading run of
        hits is injected (a block may have been LRU-dropped between the
        caller's membership check and this call — e.g. by a save() triggered
        while allocating the destination pages — and blocks past the first
        miss can't count as cached prefix anyway). Returns the hit hashes."""
        hits: list[tuple[int, int]] = []
        for h, p in pairs:
            if h not in self._blocks:
                break
            hits.append((h, p))
        if not hits:
            return set()
        from dynamo_tpu.quant.kv import wire_concat

        axis = self.runner.model.wire_n_axis
        # the batch is padded to a power of two inside inject_pages_bucketed
        # (shared with the streamed-disagg part scatter) so the donated
        # scatter compiles a handful of shapes, not one per prefix length
        n = len(hits)
        t0 = time.monotonic()
        # int8 caches store {"q","s"} wire dicts (page data + scale plane,
        # half the host bytes per block); wire_concat maps over both leaves
        data = wire_concat([self._blocks[h] for h, _ in hits], axis=axis)
        ids = np.asarray([p for _, p in hits], np.int32)
        self.runner.inject_pages_bucketed(ids, data, axis=axis)
        dt = time.monotonic() - t0
        self.transfer_s += dt
        tracing.record_span("engine.kv_offload.restore", t0, duration=dt,
                            attrs={"blocks": n})
        for h, _ in hits:
            self._blocks.move_to_end(h)
        self.loads += n
        return {h for h, _ in hits}

    def peek(self, seq_hash: int):
        """Read a host block without device movement (the fleet prefix-cache
        pull server's host-tier leg). Bumps LRU recency — a block peers keep
        pulling is a block worth keeping. The returned array is stored-once /
        never mutated, so handing out the reference is safe even if the pool
        later LRU-drops the entry mid-serialization."""
        data = self._blocks.get(seq_hash)
        if data is not None:
            self._blocks.move_to_end(seq_hash)
        return data

    def discard(self, seq_hash: int) -> None:
        if self._blocks.pop(seq_hash, None) is not None:
            if self.meter is not None:
                self.meter.kv_release("host", seq_hash)
