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

    def pages_for_prompt(self, n_tokens: int) -> int:
        """Pages a prompt of `n_tokens` needs (admission's reckoning)."""
        return -(-n_tokens // self.page_size)

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
    def active_pages(self) -> int:
        return (self.num_pages - 1) - len(self._free) - self._evictable_pages

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
