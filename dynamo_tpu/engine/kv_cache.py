"""Host-side paged KV cache bookkeeping: page allocator + per-sequence state.

This is the engine-internal analogue of the reference's KV block pools
(reference: lib/llm/src/kv/reuse.rs:50-214 AvailableBlocks,
kv/reserved.rs:66-140 ReservedBlocks): free pages are reclaimable by content
hash (prefix cache), in-flight pages are ref-counted and shared between
sequences with identical prefixes. The device arrays themselves live in the
engine (models/*.init_cache); only integer bookkeeping happens here, so the
scheduler never touches HBM.

Prefix reuse hashing follows the reference's chained sequence hash
(reference: lib/llm/src/tokens.rs:30-210): each full page is identified by
hash(parent_seq_hash, page_token_ids).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import xxhash


def page_hash(parent: int, tokens: Sequence[int]) -> int:
    """Chained content hash of one full page of tokens.

    xxh3_64 seed 1337 over token bytes, chained with the parent hash —
    matching the reference's block-hash recipe (reference:
    lib/llm/src/kv_router/indexer.rs:87-104, seed at :64).
    """
    h = xxhash.xxh3_64(seed=1337)
    h.update(parent.to_bytes(8, "little", signed=False))
    for t in tokens:
        h.update(int(t).to_bytes(4, "little", signed=True))
    return h.intdigest()


def tokens_hash(tokens: Sequence[int]) -> int:
    """Content-only (unchained) page hash — the router-side LocalBlockHash
    (reference: lib/llm/src/kv_router/indexer.rs:87-104): computable from
    query tokens alone, keys the routing radix tree."""
    h = xxhash.xxh3_64(seed=1337)
    for t in tokens:
        h.update(int(t).to_bytes(4, "little", signed=True))
    return h.intdigest()


def content_salt(data: bytes) -> int:
    """xxh3_64(seed 1337) over raw content bytes — the salt used to rewrite
    multimodal placeholder token ids (engine._resolve_mm salts from pixels,
    scheduler._admit from embeds as a fallback). ONE definition: both sides
    of a disaggregated pair must derive identical salts or their page
    hashes disagree (code-review r3)."""
    return xxhash.xxh3_64(data, seed=1337).intdigest()


@dataclasses.dataclass
class PageInfo:
    ref_count: int = 0
    seq_hash: Optional[int] = None   # set once the page is full + hashed


class PageAllocator:
    """Free-list page allocator with content-hash reuse (prefix caching).

    Freed pages keep their contents and sit in a reuse map keyed by chained
    sequence hash until evicted (LRU order), like the reference's
    AvailableBlocks match-by-sequence-hash reclaim.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.num_pages = num_pages
        self.page_size = page_size
        # host-tier hook: called with (pid, seq_hash) just before a reusable
        # page's content is recycled, while its KV is still intact in HBM —
        # the engine offloads it to the HostKvPool here (engine/offload.py)
        self.on_evict = None
        self.pages: List[PageInfo] = [PageInfo() for _ in range(num_pages)]
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        # seq_hash -> page id, for pages whose ref_count dropped to 0
        self._reusable: Dict[int, int] = {}
        self._reusable_order: List[int] = []  # LRU eviction order (page ids)
        # live (ref_count>0) full pages by hash, for inflight sharing
        self._live: Dict[int, int] = {}
        # (kind, page, seq_hash, parent_seq_hash, tokens_hash); tokens_hash=0
        # for "removed" (removal is keyed by the chained hash)
        self.events: List[Tuple[str, int, int, int, int]] = []

    # -- stats ---------------------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free) + len(self._reusable)

    @property
    def usage(self) -> float:
        return 1.0 - self.num_free / self.num_pages

    def can_allocate(self, n: int) -> bool:
        return self.num_free >= n

    # -- allocation ----------------------------------------------------------
    def allocate(self) -> int:
        """Take one blank page (evicting from the reuse pool if needed)."""
        if self._free:
            pid = self._free.pop()
        else:
            pid = self._evict_one()
        info = self.pages[pid]
        info.ref_count = 1
        info.seq_hash = None
        return pid

    def _evict_one(self) -> int:
        while self._reusable_order:
            pid = self._reusable_order.pop(0)
            info = self.pages[pid]
            if info.ref_count == 0 and info.seq_hash is not None \
                    and self._reusable.get(info.seq_hash) == pid:
                if self.on_evict is not None:
                    self.on_evict(pid, info.seq_hash)
                del self._reusable[info.seq_hash]
                self.events.append(("removed", pid, info.seq_hash, 0, 0))
                info.seq_hash = None
                return pid
        raise MemoryError("KV cache exhausted: no free or reusable pages")

    def lookup(self, seq_hash: int) -> Optional[int]:
        """Find a page holding this hashed prefix page (live or reusable)."""
        pid = self._live.get(seq_hash)
        if pid is not None:
            return pid
        return self._reusable.get(seq_hash)

    def share(self, pid: int) -> int:
        """Add a reference to an existing page (prefix-cache hit)."""
        info = self.pages[pid]
        if info.ref_count == 0:
            # revive from the reuse pool
            if info.seq_hash is not None and self._reusable.get(info.seq_hash) == pid:
                del self._reusable[info.seq_hash]
                self._live[info.seq_hash] = pid
        info.ref_count += 1
        return pid

    def seal(self, pid: int, parent_hash: int, tokens: Sequence[int]) -> int:
        """Mark a page full and content-hashed; returns the chained hash."""
        sh = page_hash(parent_hash, tokens)
        info = self.pages[pid]
        info.seq_hash = sh
        self._live[sh] = pid
        self.events.append(("stored", pid, sh, parent_hash, tokens_hash(tokens)))
        return sh

    def free(self, pid: int) -> None:
        info = self.pages[pid]
        info.ref_count -= 1
        if info.ref_count > 0:
            return
        if info.seq_hash is not None:
            if self._live.get(info.seq_hash) == pid:
                del self._live[info.seq_hash]
            if info.seq_hash in self._reusable:
                # duplicate content (two requests computed the same page):
                # only one copy is worth keeping — recycle this one as blank
                info.seq_hash = None
                self._free.append(pid)
            else:
                self._reusable[info.seq_hash] = pid
                self._reusable_order.append(pid)
        else:
            self._free.append(pid)

    def drain_events(self) -> List[Tuple[str, int, int, int, int]]:
        ev, self.events = self.events, []
        return ev


class StateSlots:
    """The second kind of per-sequence device state, beside the pages: a
    pool of fixed-size recurrent-state slots (a model with
    linear-attention layers, ModelConfig.state_leaves). A sequence takes
    ONE slot at its first prefill chunk, before it has a decode slot,
    and holds it until it finishes, is aborted or is preempted
    (preemption is by recompute: the state of a prefix exists nowhere
    once the sequence has moved on). So the pool is max_slots +
    max_prefill_batch wide: every decode slot's sequence, and the rows
    of one prefill batch that have pages but no decode slot yet. Nothing
    is cleared on the host or the device at reuse: a step whose row
    starts at position 0 starts from zeros (models/llama.kda_mix)."""

    def __init__(self, n: int):
        self.n = n
        self._free = list(range(n - 1, -1, -1))

    @property
    def used(self) -> int:
        return self.n - len(self._free)

    def take(self) -> int:
        """A free slot, or -1."""
        return self._free.pop() if self._free else -1

    def give(self, slot: int) -> None:
        if slot >= 0:
            assert slot not in self._free, f"state slot {slot} freed twice"
            self._free.append(slot)


@dataclasses.dataclass
class SequenceState:
    """Per-request device-cache bookkeeping owned by the scheduler."""

    request_id: str
    prompt: List[int]
    pages: List[int] = dataclasses.field(default_factory=list)
    page_hashes: List[int] = dataclasses.field(default_factory=list)
    # a model with a window pool (ModelConfig.window_pool): the pages the
    # sequence holds in the SECOND pool, for its sliding layers, logical
    # pages wfirst, wfirst + 1, ... of its context. Only the pages its
    # next step can see: the scheduler hands back, at every commit, those
    # wholly behind the window of the next position (never hashed, never
    # shared)
    wpages: List[int] = dataclasses.field(default_factory=list)
    wfirst: int = 0
    num_cached: int = 0       # tokens whose KV is already valid in the cache
    num_computed: int = 0     # tokens whose KV was computed by US this request
    output: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1            # decode slot id, -1 while prefilling
    # recurrent-state slot (StateSlots), -1 = none: taken at the first
    # prefill chunk on an engine whose model has linear-attention layers
    state_slot: int = -1
    prefill_only: bool = False  # park after prefill instead of decoding
    # bumped on every preempt-and-readmit: lets the engine's device-resident
    # decode-state signature distinguish a re-prefilled request from an
    # uninterrupted one (same request_id, same slot, possibly the same page
    # COUNT — but stale device token/position/page-table otherwise)
    epoch: int = 0
    # multimodal: [(prompt_offset, embeds [n, D])] — kept on the sequence so
    # chunked prefill and preempt-and-re-prefill can rebuild embed rows
    mm_spans: list = dataclasses.field(default_factory=list)
    # multi-tenant QoS (runtime/qos.py): class name + resolved priority,
    # set at admission from EngineRequest.qos. qos_bypassed counts how
    # many times a higher class jumped this sequence in the waiting
    # queue — bounded by QosPolicy.aging_limit (the no-starvation
    # guarantee); preempted_by records the preemptor's class so the
    # debt is repaid when this victim resumes decoding.
    qos: str = ""
    qos_prio: int = 0
    qos_bypassed: int = 0
    preempted_by: Optional[str] = None
    # tiered-KV streaming decode (engine/streaming.py): set at admission
    # when the full page footprint exceeds stream_resident_pages. A
    # streamed sequence never holds seq.pages — its residency plan
    # (resident set, window-pool staging, spill victims) lives on the
    # StreamingDecoder's StreamSeq record.
    streamed: bool = False

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output)

    @property
    def all_tokens(self) -> List[int]:
        """prompt + generated tokens; the KV-resident token sequence.

        Prefill iterates over this (not just prompt) so a preempted request
        re-prefills its generated tokens too without folding them into the
        prompt (which would corrupt max_tokens accounting)."""
        return self.prompt + self.output

    def flat_index(self, pos: int, page_size: int) -> int:
        return self.pages[pos // page_size] * page_size + pos % page_size

    def wflat_index(self, pos: int, page_size: int) -> int:
        """`flat_index` in the window pool: `pos` lies in a held page."""
        return (self.wpages[pos // page_size - self.wfirst] * page_size
                + pos % page_size)
