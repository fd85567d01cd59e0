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
  - a sequence GROWS by runs of a TILE (PR 47): the decode attention kernel
    walks a context a tile of pages at a time and fetches a tile whose pages
    are consecutive in the pool as one copy, so fresh pages are taken a whole
    aligned tile of the pool at a time wherever one is idle (`_FreeTiles`).
    The page stays the unit of everything that names it: hashes, sharing,
    refcounts, events, tiers, transfer

Pure Python bookkeeping — device arrays never flow through here; the scheduler
translates page ids into jnp page tables.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field
from itertools import islice
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

    #: the rest of the run the newest pages came from, ascending: this
    #: sequence's to grow into, holding no token yet, and taken back (from the
    #: end) when the pool has nothing else to give
    reserved: list[int] = field(default_factory=list)
    tile_is_run: list[bool] = field(default_factory=list)  # per tile of `pages`

    @property
    def num_pages(self) -> int:
        return len(self.pages)

    @property
    def entries(self) -> list[int]:
        """What the sequence's page table shows: its pages, then the rest of
        their run, so that the kernel finds the last tile whole."""
        return self.pages + self.reserved if self.reserved else self.pages


_FREE, _CACHED, _HELD = 0, 1, 2


class _FreeTiles:
    """Which pages no running sequence holds, kept by TILE of the pool
    (``tile`` consecutive pages, aligned), so that a run of a tile can be
    found after any churn. A page is FREE, CACHED (a registered block with no
    user: evictable) or HELD. A tile with no held page is WHOLE and can be
    given out as one run; the free pages of every other tile are LOOSE and
    serve the requests for single pages first, so whole tiles stay whole.
    The allocator owns the blocks; this only tracks states and counts."""

    def __init__(self, num_pages: int, tile: int):
        self.tile = tile
        self.state = bytearray(num_pages)  # all free
        self.state[0] = _HELD  # the null page is nobody's
        self.idle = [0] * -(-num_pages // tile)  # per tile: pages free or cached
        for page in range(1, num_pages):
            self.idle[page // tile] += 1
        self.free = num_pages - 1  # pages in state FREE
        #: whole tiles, the next to give first: those with no cached block in
        #: front (nothing is lost by taking them), then by the time they became whole
        self.whole: OrderedDict[int, None] = OrderedDict(
            (k, None) for k, n in enumerate(self.idle) if n == tile)
        self.loose: dict[int, None] = {
            page: None for page in range(1, num_pages) if self.idle[page // tile] < tile}

    def _pages(self, k: int) -> range:
        return range(k * self.tile, min((k + 1) * self.tile, len(self.state)))

    def hold(self, page: int) -> None:
        """FREE or CACHED -> HELD (a cached block regained a user)."""
        k = page // self.tile
        if self.idle[k] == self.tile:  # no longer whole: its free pages are loose
            del self.whole[k]
            self.loose.update((p, None) for p in self._pages(k) if self.state[p] == _FREE)
        if self.state[page] == _FREE:
            self.free -= 1
            del self.loose[page]
        self.state[page] = _HELD
        self.idle[k] -= 1

    def release(self, page: int, cached: bool) -> None:
        """HELD -> CACHED (its block stays registered) or FREE."""
        k = page // self.tile
        self.state[page] = _CACHED if cached else _FREE
        self.free += not cached
        self.idle[k] += 1
        if self.idle[k] < self.tile:
            if not cached:
                self.loose[page] = None
            return
        pages = self._pages(k)
        for p in pages:
            self.loose.pop(p, None)
        self.whole[k] = None
        if all(self.state[p] == _FREE for p in pages):
            self.whole.move_to_end(k, last=False)

    def drop(self, page: int) -> None:
        """CACHED -> FREE (its block was evicted where it lay)."""
        self.state[page] = _FREE
        self.free += 1
        if self.idle[page // self.tile] < self.tile:
            self.loose[page] = None

    def take_run(self) -> Optional[tuple[int, list[int]]]:
        """Hold the next whole tile: (its first page, its pages that hold a
        cached block, which the caller evicts), or None where no tile is whole."""
        if not self.whole:
            return None
        k, _ = self.whole.popitem(last=False)
        pages = self._pages(k)
        cached = [p for p in pages if self.state[p] == _CACHED]
        self.free -= len(pages) - len(cached)
        for p in pages:
            self.state[p] = _HELD
        self.idle[k] = 0
        return pages[0], cached

    def take_single(self) -> Optional[int]:
        """Hold one free page: a loose one, else one of the next whole tile
        (which breaks it). None where that tile holds cached blocks only: the
        caller evicts the oldest block instead."""
        if self.loose:
            page = next(reversed(self.loose))
        else:
            k = next(iter(self.whole), None)
            page = None if k is None else next(
                (p for p in self._pages(k) if self.state[p] == _FREE), None)
            if page is None:
                return None
        self.hold(page)
        return page


class PageAllocator:
    """Physical page allocator + prefix cache for one engine's KV cache."""

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        event_sink: Optional[Callable[[KvCacheEvent], None]] = None,
        offload=None,  # Optional[HostKvPool]: host-DRAM tier (engine/offload.py)
        match_prefix: bool = True,
        tile_pages: int = 1,
    ):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self.page_size = page_size
        #: pages the decode attention kernel walks at a time
        #: (`ops.pallas.paged_attention.decode_tile_pages`, handed over by the
        #: engine): a sequence takes its fresh pages by aligned runs of this many
        self.tile_pages = tile_pages
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
        self._free = _FreeTiles(num_pages, tile_pages)  # page 0 reserved
        # sequence_hash -> physical page holding that full block
        self._cache: dict[int, int] = {}
        self._cache_meta: dict[int, StoredBlock] = {}  # seq_hash -> event payload
        self._refcount: dict[int, int] = {}  # physical page -> live users
        # refcount-0 cached blocks, LRU order (oldest first): seq_hash -> page
        self._reusable: OrderedDict[int, int] = OrderedDict()
        self._reusable_hash: dict[int, int] = {}  # the same, page -> seq_hash
        self._seqs: dict[str, SequencePages] = {}
        # the sequences that hold reserved pages (the unwritten rest of a
        # run), oldest first, and how many those are in all
        self._reserving: dict[str, SequencePages] = {}
        self.reserved_pages = 0
        # tiles of the running sequences' pages, and those of them that are
        # one run: counters kept as the sequences change, because `/metrics`
        # reads them from another thread, where `_seqs` cannot be walked
        self.tiles = 0
        self.run_tiles = 0
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
        """Pages a sequence can still be given: free, reclaimable from the
        prefix cache, or reserved by a sequence that has written nothing
        there yet (taken back when the rest runs dry)."""
        return self._free.free + len(self._reusable) + self.reserved_pages

    @property
    def used_pages(self) -> int:
        """Pages that hold tokens: of running sequences and of cached blocks."""
        return (self.num_pages - 1) - self._free.free - self.reserved_pages

    @property
    def active_pages(self) -> int:
        """Pages referenced by live sequences (their reserved ones hold
        nothing yet and are not counted)."""
        return self.used_pages - len(self._reusable)

    def pages_for_prompt(self, n_tokens: int) -> int:
        """Pages a prompt of `n_tokens` needs (admission's reckoning)."""
        return -(-n_tokens // self.page_size)

    def _grow(self, state: SequencePages, n: int, owner) -> list[int]:
        """Append ``n`` pages to the sequence and return them. At a tile
        boundary of its logical pages the sequence takes a whole idle tile of
        the pool where there is one: the pages it needs now, and the rest
        RESERVED for it to grow into (the kernel fetches such a tile in one
        copy). Elsewhere, and where no tile is whole, single pages: a free one,
        else the LRU's oldest cached block, else a page another sequence has
        reserved and not written. Every cached block evicted on the way goes
        to the host tier in ONE batch (the per-block save path pays a dispatch
        + D2H round trip per page, which serializes into TTFT when a deep
        prompt allocates thousands of pages). Raises MemoryError, with
        nothing taken, where the pool cannot give ``n``."""
        if n > self.free_pages:
            raise MemoryError("out of KV pages")
        first_tile = len(state.pages) // self.tile_pages
        victims: list[tuple[int, int]] = []  # (seq_hash, page) evicted to make room
        fresh: list[int] = []
        for _ in range(n):
            if state.reserved:
                page = state.reserved.pop(0)
                self._reserve(state, -1)
            elif len(state.pages) % self.tile_pages == 0 and (
                    run := self._free.take_run()) is not None:
                page, cached = run
                victims += [(self._unpark(p), p) for p in cached]
                state.reserved = list(range(page + 1, page + self.tile_pages))
                self._reserve(state, len(state.reserved))
            else:
                page = self._free.take_single()
                if page is None and self._reusable:
                    page = next(iter(self._reusable.values()))  # the LRU's oldest
                    victims.append((self._unpark(page), page))
                    self._free.hold(page)
                elif page is None:
                    page = self._take_back()
            self._refcount[page] = 1
            state.pages.append(page)
            fresh.append(page)
        self._evict(victims)
        self._meter_acquire(fresh, owner)
        self._retile(state, first_tile)
        if self.used_pages > self.peak_used_pages:
            self.peak_used_pages = self.used_pages
        return fresh

    def _reserve(self, state: SequencePages, n: int) -> None:
        """The sequence's reserved pages changed by ``n``."""
        self.reserved_pages += n
        if state.reserved:
            self._reserving[state.seq_id] = state
        else:
            self._reserving.pop(state.seq_id, None)

    def _take_back(self) -> int:
        """The last reserved page of the sequence that has reserved longest:
        what it keeps of its run is still the run's start, and its last tile
        is no run until it has grown through it."""
        other = next(iter(self._reserving.values()))
        page = other.reserved.pop()
        self._reserve(other, -1)
        self._retile(other, len(other.tile_is_run) - 1)
        return page

    def _unpark(self, page: int) -> int:
        """A refcount-0 cached page leaves the reusable pool: its seq_hash."""
        seq_hash = self._reusable_hash.pop(page)
        del self._reusable[seq_hash]
        return seq_hash

    def _is_run(self, state: SequencePages, t: int) -> bool:
        """Tile t of the sequence's page table is one run of the pool (what
        the kernel's flag will say of it)."""
        n = self.tile_pages
        tile = state.pages[t * n:(t + 1) * n]
        if len(tile) < n:
            tile = tile + state.reserved[:n - len(tile)]
        return n > 1 and len(tile) == n and tile == list(range(tile[0], tile[0] + n))

    def _retile(self, state: SequencePages, first_tile: int) -> None:
        """Count the sequence's tiles from ``first_tile`` on again (it grew,
        shared a prefix, or lost a reserved page); with no pages, forget them."""
        first_tile = max(0, first_tile)
        was = state.tile_is_run[first_tile:]
        now = [self._is_run(state, t)
               for t in range(first_tile, -(-len(state.pages) // self.tile_pages))]
        state.tile_is_run[first_tile:] = now
        self.tiles += len(now) - len(was)
        self.run_tiles += sum(now) - sum(was)

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

    def _evict(self, victims: list[tuple[int, int]]) -> None:
        """Cached blocks ``(seq_hash, page)``, already out of the reusable
        pool, leave the device: with a host tier configured their KV is
        offloaded (one batched gather) instead of dropped. Each block that
        leaves its last tier is named in the one ``removed`` event."""
        if not victims:
            return
        metas = {h: self._cache_meta.pop(h) for h, _ in victims}
        for h, _ in victims:
            del self._cache[h]
        # metering: every victim page leaves HBM here; the owners ride into
        # the host pool so demoted residency keeps charging its creator
        owners = {h: self._meter_release(p) for h, p in victims}
        removed = []
        if self.offload is not None:
            dropped = set(self.offload.save_many(victims, owners=owners))
            for h, m in metas.items():
                if h not in dropped:
                    self._offloaded_meta[h] = m
            for victim in dropped:
                vm = metas.get(victim) or self._offloaded_meta.pop(victim, None)
                if vm is not None:
                    removed.append(vm.block_hash)
        else:
            removed = [m.block_hash for m in metas.values()]
        if removed:
            self._emit(KvCacheEvent.removed(removed))

    def drain_to_host(self, n: int) -> int:
        """Pressure-driven offload: move up to ``n`` of the coldest
        refcount-0 cached blocks to the host tier (one batched gather) and
        return their pages to the free list — so allocation bursts find
        fresh pages instead of paying the reclaim transfer at the moment of
        exhaustion. Returns the number of pages freed."""
        if self.offload is None or not self._reusable:
            return 0
        pages = list(islice(self._reusable.values(), n))
        self._evict([(self._unpark(page), page) for page in pages])
        for page in pages:
            self._free.drop(page)
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
        # a shared prefix is a run for the sharer wherever it was for its
        # first writer (the same logical index); the tile where shared and
        # own pages meet is none
        self._retile(state, 0)

        try:
            # host-tier blocks: fresh pages first, then ONE batched inject for
            # the whole prefix restore (the per-block path pays a dispatch +
            # transfer round trip per block, serialized into TTFT);
            # re-registered on-device so later sequences share them again
            host_pairs: list[tuple[int, int]] = []
            if host_hit_hashes:
                fresh = self._grow(state, len(host_hit_hashes), owner)
                host_pairs = list(zip(host_hit_hashes, fresh))
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
                self._grow(state, need, owner)
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
        self._release(state, page_to_hash)

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
            self._grow(state, needed - state.num_pages, self._seq_owner.get(seq_id))
        except MemoryError:
            return False
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
        self._release(state, page_to_hash)

    # ------------- internals -------------

    def _release(self, state: SequencePages, page_to_hash: dict) -> None:
        """The sequence lets go of everything: its pages lose a user (a
        registered block stays, evictable), its reserved pages are free. A
        tile it alone held is whole again, and comes out as the run it was."""
        for page in state.pages:
            self._unref_page(page, evictable_hash=page_to_hash.get(page))
        state.pages.clear()
        for page in state.reserved:
            self._free.release(page, cached=False)
        n = len(state.reserved)
        state.reserved.clear()
        self._reserve(state, -n)
        self._retile(state, 0)

    def _ref_page(self, page: int) -> None:
        self._refcount[page] = self._refcount.get(page, 0) + 1
        # a cached page in the reusable pool that regains a user leaves the pool
        if page in self._reusable_hash:
            self._unpark(page)
            self._free.hold(page)

    def _unref_page(self, page: int, evictable_hash: Optional[int]) -> None:
        rc = self._refcount.get(page, 0) - 1
        if rc > 0:
            self._refcount[page] = rc
            return
        self._refcount.pop(page, None)
        if evictable_hash is not None and self._cache.get(evictable_hash) == page:
            self._reusable[evictable_hash] = page  # cached, reclaimable, LRU tail
            self._reusable.move_to_end(evictable_hash)
            self._reusable_hash[page] = evictable_hash
            self._free.release(page, cached=True)
            # metering: a reusable-pool page stays resident and keeps
            # charging its owner — no edge until reclaim
        else:
            self._meter_release(page)
            self._free.release(page, cached=False)

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


# ---------------------------------------------------------------- layer groups


@dataclass
class GroupedSequencePages:
    """Page state for one live sequence of a model with layer groups: a page
    table per attention layer, all of one logical length; an entry is 0 where
    the sequence holds no page for that block (given back behind a window, or
    never taken because a prefix hit began past it)."""

    seq_id: str
    tables: list  # [table][logical block] -> physical page or 0
    token_seq: Optional[TokenSequence] = None
    shared_prefix_pages: int = 0  # leading blocks taken from the prefix cache
    released: list = field(default_factory=list)  # per group: blocks below are gone
    held: list = field(default_factory=list)  # per group: blocks from here on are not taken yet

    @property
    def num_pages(self) -> int:
        """Logical blocks the sequence has room for (every table's length)."""
        return len(self.tables[0])


class GroupedPageAllocator(PageAllocator):
    """ONE pool of single-layer pages and one byte budget under attention
    layers that come in GROUPS (`model.layer_groups`: name, page tables,
    window). A logical block of a group is an ENTRY: one page per layer of the
    group, taken and given back together. A group with a window W keeps, per
    running sequence, the blocks that hold a token within W of its newest
    position and gives the rest back (`release_behind`), during a long chunked
    prefill as during decode; a group without one keeps every block.

    Prefix cache, per group: a registered entry that loses its last user stays
    as evictable, in ONE LRU, whether its sequence finished or dropped it
    behind a window. What decode drops lies within a window of the prompt's
    end, which is where a conversation's next turn matches (the answer is not
    part of the next prompt), so it must not be reclaimed ahead of older
    entries. A match of P tokens is
    given only if every group can serve the first new token: the groups
    without a window hold every block below P, and each group with one holds
    every block with a token in (P - W, P]. Else the match is refused WHOLE
    and counted (`prefix_refused`): a hit without those rows would be another
    model, silently.

    Not made for this allocator, and refused at start-up for such a model
    (model_runner.layer_group_refusal): the host and disk tiers, transfer of
    pages between engines, int8 pages, tp/pp/sp.
    """

    def __init__(self, num_pages: int, page_size: int, groups,
                 event_sink: Optional[Callable[[KvCacheEvent], None]] = None):
        super().__init__(num_pages, page_size, event_sink=event_sink)
        # a stack of single pages: an entry's pages are one of each layer of a
        # group, and no kernel walks them as a run
        self._free: list[int] = list(range(num_pages - 1, 0, -1))
        self.groups = list(groups)
        self.num_tables = sum(len(g.tables) for g in self.groups)
        #: the groups that keep every block: their chain decides a match's length
        self._whole = [i for i, g in enumerate(self.groups) if not g.window]
        # without one no chain of blocks is ever whole: every match is refused
        self.match_prefix = bool(self._whole)
        # (group, seq_hash) -> entry (tuple of pages, one per table of the group)
        self._entries: dict[tuple, tuple] = {}
        # an entry's users, by its first page (pages belong to one entry)
        self._users: dict[int, int] = {}
        # refcount-0 registered entries, oldest first: (group, seq_hash) -> entry
        self._reusable: OrderedDict[tuple, tuple] = OrderedDict()
        # counters, kept as the pools change: `/metrics` reads them from
        # another thread, where the pools themselves cannot be walked
        self._evictable_pages = 0
        self._cached = [0] * len(self.groups)  # evictable pages, by group
        self._active = [0] * len(self.groups)  # pages held by running sequences
        self._live_blocks = 0  # logical blocks of all running sequences
        self.window_pages_released = 0

    # ------------- capacity -------------

    @property
    def free_pages(self) -> int:
        return len(self._free) + self._evictable_pages

    @property
    def used_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    @property
    def active_pages(self) -> int:
        return self.used_pages - self._evictable_pages

    def pages_for_prompt(self, n_tokens: int) -> int:
        """Pages a prompt of `n_tokens` needs at most at one time."""
        blocks = -(-n_tokens // self.page_size)
        need = 0
        for g in self.groups:
            kept = min(blocks, -(-g.window // self.page_size) + 1) if g.window else blocks
            need += kept * len(g.tables)
        return need

    def group_pages(self) -> dict:
        """{group: {state: pages}}: `active` held by running sequences,
        `cached` evictable, `free_equivalent` what the free and evictable
        pages give this group in whole entries, `whole` what the running
        sequences would hold of this group with no window. Counters only:
        safe to read from a thread that is not the engine's."""
        out = {}
        for gi, g in enumerate(self.groups):
            n = len(g.tables)
            out[g.name] = {
                "active": self._active[gi], "cached": self._cached[gi],
                "free_equivalent": self.free_pages // n * n, "whole": self._live_blocks * n,
            }
        return out

    def _pop_free_pages(self, n: int) -> list[int]:
        if n > self.free_pages:
            raise MemoryError("out of KV pages")
        removed = []
        while len(self._free) < n:
            (gi, seq_hash), entry = self._reusable.popitem(last=False)
            del self._entries[(gi, seq_hash)]
            self._evictable_pages -= len(entry)
            self._cached[gi] -= len(entry)
            for page in entry:
                self._meter_release(page)
            self._free.extend(entry)
            if self._whole and gi == self._whole[0]:
                removed.append(seq_hash)
        if removed:
            self._emit(KvCacheEvent.removed(removed))
        out = [self._free.pop() for _ in range(n)]
        if self.used_pages > self.peak_used_pages:
            self.peak_used_pages = self.used_pages
        return out

    # ------------- entries -------------

    def _take(self, gi: int, entry: tuple) -> None:
        """One more running sequence uses `entry`."""
        users = self._users.get(entry[0], 0)
        if users == 0:
            self._active[gi] += len(entry)
        self._users[entry[0]] = users + 1

    def _take_cached(self, gi: int, seq_hash: int) -> tuple:
        key = (gi, seq_hash)
        entry = self._entries[key]
        if self._reusable.pop(key, None) is not None:
            self._evictable_pages -= len(entry)
            self._cached[gi] -= len(entry)
        self._take(gi, entry)
        return entry

    def _drop(self, gi: int, entry: tuple, seq_hash: Optional[int]) -> None:
        """A running sequence lets go of `entry`; with its last user gone it
        stays as evictable where it is the registered entry of `seq_hash`,
        else its pages are free."""
        users = self._users[entry[0]] - 1
        if users > 0:
            self._users[entry[0]] = users
            return
        del self._users[entry[0]]
        self._active[gi] -= len(entry)
        key = (gi, seq_hash)
        if seq_hash is not None and self._entries.get(key) == entry:
            self._reusable[key] = entry
            self._evictable_pages += len(entry)
            self._cached[gi] += len(entry)
        else:
            for page in entry:
                self._meter_release(page)
            self._free.extend(entry)

    def _fresh(self, gi: int, owner) -> tuple:
        entry = tuple(self._pop_free_pages(len(self.groups[gi].tables)))
        self._meter_acquire(list(entry), owner)
        self._take(gi, entry)
        return entry

    def _entry_of(self, state: GroupedSequencePages, gi: int, block: int):
        entry = tuple(state.tables[t][block] for t in self.groups[gi].tables)
        return entry if entry[0] else None

    def _set(self, state: GroupedSequencePages, gi: int, block: int, entry) -> None:
        for t, page in zip(self.groups[gi].tables, entry or [0] * len(self.groups[gi].tables)):
            state.tables[t][block] = page

    def _hash_of(self, state: GroupedSequencePages, block: int) -> Optional[int]:
        blocks = state.token_seq.blocks
        return blocks[block].sequence_hash if block < len(blocks) else None

    def _first_needed(self, gi: int, position: int) -> int:
        """First block that holds a key a query at `position` may see."""
        window = self.groups[gi].window
        return max(0, position - window + 1) // self.page_size if window else 0

    # ------------- the prefix rule -------------

    def _match(self, ts: TokenSequence, prompt_len: int) -> tuple:
        """(blocks matched, refused): the longest chain every whole group
        holds, never the entire prompt, and only if every window group still
        holds the blocks behind it that the first new token will read."""
        if not self._whole:
            return 0, False
        n = 0
        for block in ts.blocks:
            if any((gi, block.sequence_hash) not in self._entries for gi in self._whole):
                break
            n += 1
        if n and n * self.page_size >= prompt_len:
            n -= 1
        if not n:
            return 0, False
        for gi, g in enumerate(self.groups):
            if not g.window:
                continue
            for b in range(self._first_needed(gi, n * self.page_size), n):
                if (gi, ts.blocks[b].sequence_hash) not in self._entries:
                    return 0, True
        return n, False

    def lookup_prefix(self, prompt_tokens: list[int], salt: int = 0) -> int:
        ts = TokenSequence(prompt_tokens, self.page_size, salt=salt)
        return self._match(ts, len(prompt_tokens))[0] * self.page_size

    def cached_page(self, seq_hash: int) -> Optional[int]:
        return None  # no single page holds a block: pulls are refused at start-up

    # ------------- sequence lifecycle -------------

    def allocate_sequence(self, seq_id: str, prompt_tokens: list[int], salt: int = 0,
                          owner: Optional[tuple] = None) -> tuple[int, GroupedSequencePages]:
        """Pages for a prompt: the matched prefix from the cache, then every
        block of the groups without a window. A window group's blocks past the
        match are taken chunk by chunk (`ensure_capacity`) and given back as
        the prefill moves on (`release_behind`), so a long prompt never holds
        more of a window layer than the window and a chunk."""
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id} already allocated")
        ts = TokenSequence(prompt_tokens, self.page_size, salt=salt)
        n, refused = self._match(ts, len(prompt_tokens))
        self.prefix_refused += int(refused)
        self.cache_query_blocks += len(ts.blocks)
        self.cache_hit_blocks += n
        total = -(-len(prompt_tokens) // self.page_size)
        state = GroupedSequencePages(
            seq_id=seq_id, tables=[[0] * total for _ in range(self.num_tables)], token_seq=ts,
            shared_prefix_pages=n,
            released=[self._first_needed(gi, n * self.page_size) for gi in range(len(self.groups))],
            held=[n if g.window else total for g in self.groups],
        )
        self._seq_owner[seq_id] = owner
        try:
            for gi, g in enumerate(self.groups):
                for b in range(state.released[gi], n):
                    self._set(state, gi, b, self._take_cached(gi, ts.blocks[b].sequence_hash))
                if not g.window:
                    for b in range(n, total):
                        self._set(state, gi, b, self._fresh(gi, owner))
        except MemoryError:
            self._release_all(state)
            self._seq_owner.pop(seq_id, None)
            raise
        self._seqs[seq_id] = state
        self._live_blocks += total
        return n * self.page_size, state

    def ensure_capacity(self, seq_id: str, length: int) -> bool:
        """Make sure pages exist to hold tokens up to `length` in every group
        (a window group: from the first block it has not given back). False,
        with nothing taken, if the pool cannot give them."""
        state = self._seqs[seq_id]
        needed = -(-length // self.page_size)
        missing = [
            (gi, b) for gi in range(len(self.groups))
            for b in range(max(state.held[gi], state.released[gi]), needed)
        ]
        if sum(len(self.groups[gi].tables) for gi, _ in missing) > self.free_pages:
            return False
        self._live_blocks += max(0, needed - state.num_pages)
        for table in state.tables:
            table.extend([0] * (needed - len(table)))
        for gi, b in missing:
            self._set(state, gi, b, self._fresh(gi, self._seq_owner.get(seq_id)))
        state.held = [max(h, needed) for h in state.held]
        return True

    def release_behind(self, seq_id: str, position: int) -> int:
        """Every query still to be dispatched for this sequence sits at
        `position` or later: give back each window group's blocks that lie
        wholly behind its window there. Steps already dispatched read their own
        copy of the tables and run before any later write to the pages. A
        registered entry that is dropped joins the LRU (see the class). Returns
        the pages given back."""
        state = self._seqs[seq_id]
        given = 0
        for gi, g in enumerate(self.groups):
            upto = min(self._first_needed(gi, position), state.num_pages)
            for b in range(state.released[gi], upto):
                entry = self._entry_of(state, gi, b)
                if entry is not None:
                    self._drop(gi, entry, self._hash_of(state, b))
                    self._set(state, gi, b, None)
                    given += len(entry)
            state.released[gi] = max(state.released[gi], upto)
        self.window_pages_released += given
        return given

    def commit_prefilled(self, seq_id: str, prompt_len: int) -> None:
        state = self._seqs[seq_id]
        for b in range(state.shared_prefix_pages, prompt_len // self.page_size):
            self._register_block(state, b)

    def append_token(self, seq_id: str, token: int) -> None:
        """As `PageAllocator.append_token`: a decode-written block is
        registered one token after it fills."""
        state = self._seqs[seq_id]
        state.token_seq.push_token(token)
        n = len(state.token_seq)
        if (n - 1) % self.page_size == 0 and n > self.page_size:
            b = (n - 1) // self.page_size - 1
            if b < state.num_pages:
                self._register_block(state, b)

    def _register_block(self, state: GroupedSequencePages, b: int) -> None:
        block = state.token_seq.blocks[b]
        for gi in range(len(self.groups)):
            entry = self._entry_of(state, gi, b)
            key = (gi, block.sequence_hash)
            if entry is None or key in self._entries:
                continue  # given back already, or first writer wins
            self._entries[key] = entry
            if self._whole and gi == self._whole[0]:
                meta = StoredBlock(block_hash=block.sequence_hash, tokens_hash=block.block_hash,
                                   parent_hash=block.parent_sequence_hash)
                self._emit(KvCacheEvent.stored(parent_hash=block.parent_sequence_hash, blocks=[meta]))

    def free_sequence(self, seq_id: str) -> None:
        state = self._seqs.pop(seq_id)
        self._live_blocks -= state.num_pages
        self._release_all(state)
        self._seq_owner.pop(seq_id, None)

    def _release_all(self, state: GroupedSequencePages) -> None:
        for gi in range(len(self.groups)):
            for b in range(state.num_pages):
                entry = self._entry_of(state, gi, b)
                if entry is not None:
                    self._drop(gi, entry, self._hash_of(state, b))
        for table in state.tables:
            table.clear()
