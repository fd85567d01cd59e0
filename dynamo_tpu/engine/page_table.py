"""Paged KV block allocator with prefix caching and KV event emission.

The worker-side analogue of the reference's KV block manager
(reference: lib/llm/src/kv/{manager,reuse,reserved}.rs semantics) fused with
vLLM-style prefix caching, re-designed for the JAX engine:

  - physical page 0 is reserved as the null/trash page (masked writes and
    page-table padding target it — see dynamo_tpu/ops/attention.py)
  - full blocks are identified by their chained sequence hash
    (dynamo_tpu/llm/tokens.py); a completed block's page is registered in the
    prefix cache and can be shared (refcounted) by later sequences
  - refcount-0 cached pages form an LRU "reuse pool": they still serve prefix
    hits but are reclaimed when fresh pages run out
    (reference: lib/llm/src/kv/reuse.rs:50 AvailableBlocks priority reuse)
  - block store / evict emit KvCacheEvents for the KV router's global index
    (reference: lib/llm/src/kv_router/protocols.rs:35-100, publisher.rs:33-74)

Pure Python bookkeeping — device arrays never flow through here; the scheduler
translates page ids into jnp page tables.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Optional

from dynamo_tpu.llm.tokens import TokenBlock, TokenSequence
from dynamo_tpu.llm.kv_events import KvCacheEvent, StoredBlock
from dynamo_tpu.utils import get_logger

log = get_logger("engine.pages")


@dataclass
class SequencePages:
    """Page state for one live sequence."""

    seq_id: str
    pages: list[int] = field(default_factory=list)  # logical block i -> physical page
    shared_prefix_pages: int = 0  # leading pages refcounted from the prefix cache
    token_seq: Optional[TokenSequence] = None  # hashing state (block_size = page_size)
    registered_hashes: list[int] = field(default_factory=list)  # sequence hashes we cached

    @property
    def num_pages(self) -> int:
        return len(self.pages)


class PageAllocator:
    """Physical page allocator + prefix cache for one engine's KV cache."""

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        event_sink: Optional[Callable[[KvCacheEvent], None]] = None,
        offload=None,  # Optional[HostKvPool]: host-DRAM tier (engine/offload.py)
        match_prefix: bool = True,
    ):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self.page_size = page_size
        #: False for a model with recurrent layers: pages hold the attention
        #: layers' KV only, and a hit on them without the recurrent state at
        #: that position would be another model, silently. Every match is
        #: withheld (and counted) until state snapshots exist.
        self.match_prefix = match_prefix
        self.prefix_refused = 0  # sequences whose cached prefix was withheld
        self.event_sink = event_sink
        self.offload = offload
        # off-device blocks (host DRAM *or* disk tier): meta survives until
        # the block leaves its LAST tier, when the one removed event fires
        self._offloaded_meta: dict[int, StoredBlock] = {}
        self._free: list[int] = list(range(num_pages - 1, 0, -1))  # stack; page 0 reserved
        # sequence_hash -> physical page holding that full block
        self._cache: dict[int, int] = {}
        self._cache_meta: dict[int, StoredBlock] = {}  # seq_hash -> event payload
        self._refcount: dict[int, int] = {}  # physical page -> live users
        # refcount-0 cached blocks, LRU order (oldest first): seq_hash -> page
        self._reusable: OrderedDict[int, int] = OrderedDict()
        self._seqs: dict[str, SequencePages] = {}
        # stats
        self.cache_hit_blocks = 0
        self.cache_query_blocks = 0
        self.peak_used_pages = 0  # page-pool occupancy high-watermark
        #: optional utils/metering.MeterLedger + HBM bytes one page costs —
        #: set by the engine when metering is on. Ownership model: a page is
        #: owned by the (tenant, request_id) that first allocated it; prefix
        #: hits and reusable-pool parking never re-own (residency is the
        #: benefit the cache sells, so its cost stays attributed); demotions
        #: to the host tier carry the owner down the ladder.
        self.meter = None
        self.meter_page_bytes = 0
        self._seq_owner: dict[str, tuple] = {}  # seq_id -> (tenant, rid)

    # ------------- capacity -------------

    @property
    def free_pages(self) -> int:
        """Immediately + reclaimably free pages."""
        return len(self._free) + len(self._reusable)

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def active_pages(self) -> int:
        """Pages referenced by live sequences."""
        return (self.num_pages - 1) - len(self._free) - len(self._reusable)

    def _pop_free_page(self) -> int:
        return self._pop_free_pages(1)[0]

    def _pop_free_pages(self, n: int) -> list[int]:
        """Take ``n`` pages: the free list first, then LRU reclaim from the
        refcount-0 reusable pool — with the whole reclaim batch offloaded to
        the host tier in ONE device gather (the per-block save path pays a
        dispatch + D2H round trip per page, which serializes directly into
        TTFT when a deep prompt allocates thousands of pages). Raises
        MemoryError (nothing taken) when both sources run dry."""
        if n <= len(self._free):
            out = [self._free.pop() for _ in range(n)]
        else:
            if n > len(self._free) + len(self._reusable):
                raise MemoryError("out of KV pages")
            out = [self._free.pop() for _ in range(len(self._free))]
            out.extend(self._reclaim_reusable(n - len(out)))
        if self.used_pages > self.peak_used_pages:
            self.peak_used_pages = self.used_pages
        return out

    def _meter_acquire(self, pages: list[int], owner) -> None:
        """Metering edge: ``pages`` became HBM-resident under ``owner``."""
        if self.meter is not None and self.meter_page_bytes > 0:
            for page in pages:
                self.meter.kv_acquire(
                    "hbm", page, self.meter_page_bytes, owner
                )

    def _meter_release(self, page: int):
        """Metering edge: ``page`` left HBM. Returns the owner (carried down
        the ladder by demotion sites)."""
        if self.meter is not None:
            return self.meter.kv_release("hbm", page)
        return None

    def _reclaim_reusable(self, n: int) -> list[int]:
        """Evict up to ``n`` LRU refcount-0 cached blocks; with a host tier
        configured their KV is offloaded (one batched gather) instead of
        dropped. Returns the freed pages."""
        victims: list[tuple[int, object, int]] = []  # (seq_hash, meta, page)
        while self._reusable and len(victims) < n:
            seq_hash, page = self._reusable.popitem(last=False)
            del self._cache[seq_hash]
            victims.append((seq_hash, self._cache_meta.pop(seq_hash), page))
        if not victims:
            return []
        # metering: every victim page leaves HBM here; the owners ride into
        # the host pool so demoted residency keeps charging its creator
        owners = {h: self._meter_release(p) for h, _, p in victims}
        removed = []
        if self.offload is not None:
            dropped = set(
                self.offload.save_many(
                    [(h, p) for h, _, p in victims], owners=owners
                )
            )
            meta_by_hash = {h: m for h, m, _ in victims}
            for h, m, _ in victims:
                if h not in dropped:
                    self._offloaded_meta[h] = m
            for victim in dropped:
                vm = meta_by_hash.get(victim) or self._offloaded_meta.pop(victim, None)
                if vm is not None:
                    removed.append(vm.block_hash)
        else:
            removed = [m.block_hash for _, m, _ in victims]
        if removed:
            self._emit(KvCacheEvent.removed(removed))
        return [p for _, _, p in victims]

    def drain_to_host(self, n: int) -> int:
        """Pressure-driven offload: move up to ``n`` of the coldest
        refcount-0 cached blocks to the host tier (one batched gather) and
        return their pages to the free list — so allocation bursts find
        fresh pages instead of paying the reclaim transfer at the moment of
        exhaustion. Returns the number of pages freed."""
        if self.offload is None or not self._reusable:
            return 0
        pages = self._reclaim_reusable(n)
        self._free.extend(pages)
        return len(pages)

    # ------------- events -------------

    def _emit(self, event: KvCacheEvent) -> None:
        if self.event_sink is not None:
            self.event_sink(event)

    # ------------- sequence lifecycle -------------

    def lookup_prefix(self, prompt_tokens: list[int], salt: int = 0) -> int:
        """Number of leading tokens already cached in ANY tier (block
        granularity), without allocating. Disagg routing's prefix-hit estimate.
        ``salt`` = the request's LoRA adapter uid (0 = base): adapter-specific
        prefixes live under salted chained hashes and never cross-hit."""
        if not self.match_prefix:
            return 0
        ts = TokenSequence(prompt_tokens, self.page_size, salt=salt)
        hits = 0
        for block in ts.blocks:
            h = block.sequence_hash
            if h in self._cache or (
                self.offload is not None and self.offload.in_any_tier(h)
            ):
                hits += 1
            else:
                break
        return hits * self.page_size

    def cached_page(self, seq_hash: int) -> Optional[int]:
        """Physical page holding a cached block, or None. Blocks parked in the
        refcount-0 reusable pool still serve reads (the fleet prefix-cache
        pull server looks blocks up here; callers run on the engine thread,
        so lookup and the subsequent gather dispatch are atomic)."""
        return self._cache.get(seq_hash)

    def allocate_sequence(
        self, seq_id: str, prompt_tokens: list[int], salt: int = 0,
        owner: Optional[tuple] = None,
    ) -> tuple[int, SequencePages]:
        """Allocate pages for a prompt, reusing cached prefix blocks.

        Returns (cached_len, seq_state): the first cached_len tokens already
        have KV in shared pages and must NOT be recomputed (except the last
        token if the full prompt hits, so there is always something to prefill).
        ``salt`` folds a LoRA adapter uid into the chained block identity, so
        an adapter's KV (its k/v projections carry the adapter delta) never
        serves — or is served by — another adapter's identical token prefix.
        """
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id} already allocated")
        ts = TokenSequence(prompt_tokens, self.page_size, salt=salt)
        state = SequencePages(seq_id=seq_id, token_seq=ts)
        # metering owner for every page this sequence newly acquires (device
        # prefix hits keep their original owner; restored pages re-own to
        # the restoring request — its prompt is why the bytes came back up)
        self._seq_owner[seq_id] = owner

        # 1. device-tier prefix hits: chain of full blocks present in cache
        device_hits: list[int] = []
        for block in ts.blocks:
            page = self._cache.get(block.sequence_hash)
            if page is None:
                break
            device_hits.append(page)
        if device_hits and not self.match_prefix:
            self.prefix_refused += 1
            device_hits = []

        # 2. host-tier hits continuing the chain: each costs a fresh device
        # page + a host->device block copy, but no recompute
        host_hit_hashes: list[int] = []
        if self.offload is not None:
            for block in ts.blocks[len(device_hits) :]:
                if block.sequence_hash in self.offload:
                    host_hit_hashes.append(block.sequence_hash)
                else:
                    break

        self.cache_query_blocks += len(ts.blocks)
        self.cache_hit_blocks += len(device_hits) + len(host_hit_hashes)

        # Never consume the *entire* prompt from cache: leave at least the last
        # token to prefill so the model produces next-token logits.
        total_hit = len(device_hits) + len(host_hit_hashes)
        if total_hit and total_hit * self.page_size >= len(prompt_tokens):
            if host_hit_hashes:
                host_hit_hashes.pop()
            else:
                device_hits.pop()

        for page in device_hits:
            self._ref_page(page)
        state.pages.extend(device_hits)
        state.shared_prefix_pages = len(device_hits)

        try:
            # host-tier blocks: fresh pages first, then ONE batched inject for
            # the whole prefix restore (the per-block path pays a dispatch +
            # transfer round trip per block, serialized into TTFT);
            # re-registered on-device so later sequences share them again
            host_pairs: list[tuple[int, int]] = []
            if host_hit_hashes:
                fresh = self._pop_free_pages(len(host_hit_hashes))
                self._meter_acquire(fresh, owner)
                for seq_hash, page in zip(host_hit_hashes, fresh):
                    self._refcount[page] = 1
                    state.pages.append(page)
                    host_pairs.append((seq_hash, page))
            hit_hashes = self.offload.load_many(host_pairs) if host_pairs else set()
            # only the contiguous restored prefix counts as cached: a block may
            # have been LRU-dropped from the host pool while its destination
            # page was being allocated (a save() can evict — load_many injects
            # the leading run only); pages past the first miss just get
            # overwritten by the prefill recompute
            restored = 0
            for seq_hash, page in host_pairs:
                if seq_hash not in hit_hashes:
                    break
                restored += 1
                self.offload.discard(seq_hash)
                meta = self._offloaded_meta.pop(seq_hash, None)
                if meta is not None:
                    self._cache[seq_hash] = page
                    self._cache_meta[seq_hash] = meta
                    state.registered_hashes.append(seq_hash)
                else:
                    # a host block with no tracked meta just left its LAST
                    # tier via discard() without re-registering on device:
                    # advertise the removal so no router ever points a fetch
                    # at a block this worker no longer holds (the block's
                    # engine identity IS its chained sequence hash)
                    self._emit(KvCacheEvent.removed([seq_hash]))

            if restored:
                from dynamo_tpu.utils import events

                events.emit(
                    "offload.restore", request_id=seq_id,
                    blocks=restored, host_hits=len(host_pairs),
                )

            cached_len = (len(device_hits) + restored) * self.page_size

            # 3. fresh pages for the rest of the prompt — one batched take
            # (the reclaim leg offloads its whole victim batch in one gather)
            total_pages_needed = -(-len(prompt_tokens) // self.page_size)
            need = total_pages_needed - len(state.pages)
            if need > 0:
                fresh = self._pop_free_pages(need)
                self._meter_acquire(fresh, owner)
                for page in fresh:
                    self._refcount[page] = 1
                    state.pages.append(page)
        except MemoryError:
            self._rollback(state)
            self._seq_owner.pop(seq_id, None)
            raise

        # Blocks completed by the prompt itself (all but what the prefix cache
        # already holds) get registered once their KV is actually computed —
        # the scheduler calls commit_prefilled().
        self._seqs[seq_id] = state
        return cached_len, state

    def promote_restored(self, seq_id: str, base_block: int, blocks: int) -> None:
        """A disk restore scattered ``blocks`` wire blocks into this
        sequence's pages starting at logical block ``base_block`` — promote
        them disk->device: drop the disk copies and re-register each block
        in the device prefix cache under its preserved meta, so later
        sequences share them again. No ``stored`` event fires (the block
        never emitted ``removed`` — its advertised identity stayed valid
        across the whole HBM->host->disk->HBM round trip)."""
        state = self._seqs.get(seq_id)
        disk = self.offload.disk if self.offload is not None else None
        if state is None or disk is None:
            return
        for i in range(base_block, base_block + blocks):
            if i >= len(state.pages) or i >= len(state.token_seq.blocks):
                break
            h = state.token_seq.blocks[i].sequence_hash
            disk.discard(h)
            if h in self._cache:
                continue  # another writer registered it while we restored
            meta = self._offloaded_meta.pop(h, None)
            if meta is not None:
                self._cache[h] = state.pages[i]
                self._cache_meta[h] = meta
                state.registered_hashes.append(h)
            else:
                # restored with no tracked meta: it just left its last tier
                # without re-registering — advertise the removal (same
                # contract as the host-restore leg above)
                self._emit(KvCacheEvent.removed([h]))

    def drop_disk_blocks(self, hashes: list) -> None:
        """Blocks whose disk files failed verification (corrupt/truncated)
        just left their last tier: discard the index entries and emit the
        one truthful ``removed`` per block."""
        disk = self.offload.disk if self.offload is not None else None
        if disk is None:
            return
        removed = []
        for h in hashes:
            disk.discard(h)
            meta = self._offloaded_meta.pop(h, None)
            if meta is not None and h not in self._cache:
                removed.append(meta.block_hash)
        if removed:
            self._emit(KvCacheEvent.removed(removed))

    def _rollback(self, state: SequencePages) -> None:
        """Undo a failed allocation. Cache-registered pages (shared prefix hits
        and host-tier reloads) return to the reusable pool — their on-device
        data is still valid; only uncached fresh pages go back to the free list."""
        pages = set(state.pages)
        page_to_hash = {p: h for h, p in self._cache.items() if p in pages}
        for page in state.pages:
            self._unref_page(page, evictable_hash=page_to_hash.get(page))
        state.pages.clear()

    def commit_prefilled(self, seq_id: str, prompt_len: int) -> None:
        """Register all full blocks covered by the (now computed) prompt KV."""
        state = self._seqs[seq_id]
        full_blocks = prompt_len // self.page_size
        for i in range(state.shared_prefix_pages, full_blocks):
            block = state.token_seq.blocks[i]
            self._register_block(state, block, state.pages[i])

    def ensure_capacity(self, seq_id: str, length: int) -> bool:
        """Make sure pages exist to hold `length` tokens. False if OOM."""
        state = self._seqs[seq_id]
        needed = -(-length // self.page_size)
        if state.num_pages >= needed:
            return True
        try:
            fresh = self._pop_free_pages(needed - state.num_pages)
        except MemoryError:
            return False
        self._meter_acquire(fresh, self._seq_owner.get(seq_id))
        for page in fresh:
            self._refcount[page] = 1
            state.pages.append(page)
        return True

    def append_token(self, seq_id: str, token: int) -> None:
        """Track a decoded token; registers blocks ONE TOKEN AFTER they fill.

        A decode-written block's last row's KV only exists once the
        block-following token has been fed (token ``p`` is sampled from fed
        position ``p-1``, so appending ``p`` proves KV through ``p-1``).
        Registering at fill time used to advertise — locally and through KV
        events to the radix/fleet caches — a block whose final position
        reads garbage to any sequence extending past it: forever if the
        writer finished exactly at the block boundary (a multi-turn
        conversation extending a cached response, a migrated history being
        re-admitted), or transiently if a reader raced the writer's next
        window. Deferring by one token makes every advertised block's KV
        actually complete; a sequence that ends at a block boundary simply
        never registers its final block (its KV is incomplete by
        construction and the prefill recompute is one block)."""
        state = self._seqs[seq_id]
        state.token_seq.push_token(token)
        n = len(state.token_seq)
        # the newest token (index n-1) proves KV through n-2: the last block
        # fully below that bound is safe to register
        if (n - 1) % self.page_size == 0 and n > self.page_size:
            idx = (n - 1) // self.page_size - 1
            if idx < len(state.pages):
                self._register_block(
                    state, state.token_seq.blocks[idx], state.pages[idx]
                )

    def free_sequence(self, seq_id: str) -> None:
        """Release a sequence. Full cached blocks become reusable (LRU);
        uncached pages return to the free list immediately."""
        state = self._seqs.pop(seq_id)
        self._seq_owner.pop(seq_id, None)
        page_to_hash = {}
        for i, block in enumerate(state.token_seq.blocks):
            if i < len(state.pages) and block.sequence_hash in self._cache and self._cache[block.sequence_hash] == state.pages[i]:
                page_to_hash[state.pages[i]] = block.sequence_hash
        for page in state.pages:
            self._unref_page(page, evictable_hash=page_to_hash.get(page))

    # ------------- internals -------------

    def _ref_page(self, page: int) -> None:
        self._refcount[page] = self._refcount.get(page, 0) + 1
        # a cached page in the reusable pool that regains a user leaves the pool
        for seq_hash, p in list(self._reusable.items()):
            if p == page:
                del self._reusable[seq_hash]
                break

    def _unref_page(self, page: int, evictable_hash: Optional[int]) -> None:
        rc = self._refcount.get(page, 0) - 1
        if rc > 0:
            self._refcount[page] = rc
            return
        self._refcount.pop(page, None)
        if evictable_hash is not None and self._cache.get(evictable_hash) == page:
            self._reusable[evictable_hash] = page  # cached, reclaimable, LRU tail
            self._reusable.move_to_end(evictable_hash)
            # metering: a reusable-pool page stays resident and keeps
            # charging its owner — no edge until reclaim
        else:
            self._meter_release(page)
            self._free.append(page)

    def _register_block(self, state: SequencePages, block: TokenBlock, page: int) -> None:
        if block.sequence_hash in self._cache:
            return  # dedupe: first writer wins, our copy stays private
        self._cache[block.sequence_hash] = page
        meta = StoredBlock(
            block_hash=block.sequence_hash,
            tokens_hash=block.block_hash,
            parent_hash=block.parent_sequence_hash,
        )
        self._cache_meta[block.sequence_hash] = meta
        state.registered_hashes.append(block.sequence_hash)
        self._emit(KvCacheEvent.stored(parent_hash=block.parent_sequence_hash, blocks=[meta]))
