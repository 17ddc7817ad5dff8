"""Host-side page allocator for the paged device KV cache.

The device side is arks_tpu.ops.paged_attention (pool + block tables);
this is the authority over which pool page holds what:

- **Free list + refcounts**: a page is free (refcount 0), private (held by
  one slot), or shared (held by several slots and/or the prefix index).
- **Prefix index**: chained content digests (same scheme as
  engine.prefix_cache) -> page id, LRU-ordered.  Registering a prompt's
  full pages costs NOTHING on the device — the pages are already there;
  a later prompt with the same prefix just points its table at them.
  This replaces the host-resident PrefixKVCache's device->host harvest
  copies and PCIe re-upload on hits, and because pages/tables are plain
  dispatch arguments, it works on multi-host gangs (the old design's
  single-host restriction — VERDICT round 2 item 2).
- **Eviction**: allocation prefers the free list; under pressure it evicts
  LRU index-retained pages (refcount held only by the index).  The pool is
  sized so active slots can always allocate: slots*pages_per_slot worst
  case is reserved, retention rides the surplus + an explicit extra.

Thread-safety: engine thread only (like the rest of the scheduler state);
the disaggregated prefill path never touches the allocator.
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

# THE one hash-chaining implementation lives in arks_tpu.prefix_sketch
# (jax-free, so the router can share it for tokenize-free scoring); the
# allocator's prefix index and the host PrefixKVCache keep keying the
# same bytes through these re-exports.
from arks_tpu.prefix_sketch import chain_digests, iter_chain_digests

__all__ = ["OutOfPagesError", "iter_chain_digests", "chain_digests",
           "pages_needed", "mixed_grid_steps", "mixed_kv_bytes",
           "PageAllocator", "WindowPages", "window_pages_per_slot"]


class OutOfPagesError(RuntimeError):
    pass


_SMEM_BYTES = 2**20  # a v5e core's scalar memory


def mixed_step_row_limit(max_pages: int) -> int:
    """The most flat rows (slots + chunk budget) a mixed step may carry on
    a TPU: until PR 45 the KV row-write kernels (``ops/paged_attention.py::
    paged_kv_update*``) prefetched ONE block-table row per flat token into
    SMEM, each padded to whole 128-lane tiles of int32, and the chip
    refuses a dispatch whose operands pass ``_SMEM_BYTES`` (1 MiB on a
    v5e: "would exceed memory ... space=smem").  The write positions (4
    bytes a row) and the layer index share that memory.  32 pages a slot
    (8192 tokens of 256) gives 2030 rows.  The row write now prefetches
    three vectors of a row's length and no table; the limit stands until
    a step of more rows has been compiled and run (ROADMAP M4)."""
    return (_SMEM_BYTES - 1024) // (-(-max_pages // 128) * 128 * 4 + 4)


def pages_needed(length: int, rows: int, page: int, max_pages: int) -> int:
    """Block-table entries a slot needs before a dispatch burst writing
    ``rows`` rows from position ``length`` (rows = K per fused dispatch x
    the in-flight pipeline depth: with ARKS_PIPELINE_DEPTH dispatches
    issued ahead of host resolution, the host must pre-own pages for
    EVERY in-flight dispatch's write window, not just the next one).

    Clamped to ``max_pages``: near the cache cap the host's lagged view
    can overshoot the window, but the device's dead_len mask retires the
    slot before any write lands past max_cache_len — growing the table
    beyond its row width would corrupt the neighbouring slot's row."""
    return min((length + rows - 1) // page + 1, max_pages)


def mixed_grid_steps(pos_start, q_len, *, page: int, block_q: int,
                     num_qb: int, max_pages: int, window: int = 0) -> int:
    """Page-compute steps of one mixed dispatch — the host-side numpy
    mirror of ops.paged_attention.build_mixed_work_list: each active
    (seq, q_block) item visits exactly its own causal page count (with
    ``window``, a window layer: the pages from the one that holds the
    lowest key its first query attends), q_len=0 lanes and padding items
    visit zero.  The counter it feeds
    (mixed_grid_steps_total) describes the grid PLAN, so it reads the same
    under either attention impl.

    Inputs must already be host numpy arrays (the engine's issue path
    holds them that way) — no device fetches happen here; the hot-path
    guard covers this function."""
    pos = pos_start.astype(np.int64, copy=False)
    ql = q_len.astype(np.int64, copy=False)
    q_lo = (np.arange(num_qb, dtype=np.int64) * block_q)[None, :]
    active = q_lo < ql[:, None]
    kv_end = np.where(active, pos[:, None] + np.minimum(q_lo + block_q,
                                                        ql[:, None]), 0)
    pages = np.minimum(-(-kv_end // page), max_pages)
    if window:
        first = np.maximum(pos[:, None] + q_lo - (window - 1), 0) // page
        pages = pages - np.minimum(np.where(active, first, 0), pages)
    return int(pages.sum())


def mixed_kv_bytes(pos_start, q_len, *, page: int, block_q: int,
                   num_qb: int, max_pages: int, hkv: int,
                   page_head_bytes: int, window: int = 0) -> tuple[int, int]:
    """(actual, ideal) KV bytes one mixed dispatch streams from HBM — the
    host-side mirror of the ragged kernel's DMA schedule, feeding the
    mixed_kv_bytes_total / _ideal_total counter pair.

    ``actual``: every active (seq, q_block) item re-streams its own
    causal page prefix, and the head-group split is a pure partition of
    the head axis (n_groups x head_group == hkv), so the grouped and
    ungrouped schedules move the same bytes AT EQUAL block_q — the
    grouped win arrives entirely through the larger tuned block_q
    (fewer q-blocks, fewer prefix re-streams), which is why this mirror
    takes the PLAN's block_q/num_qb rather than a head-group count.

    ``ideal``: each distinct causal page crosses the wire exactly once
    per dispatch (what a perfect cross-q-block-sharing schedule would
    move).  actual/ideal is the waste ratio docs/monitoring.md alerts
    on.

    ``page_head_bytes``: bytes one (page, head) KV block moves — K + V
    (+ scale rows when quantized); the engine derives it from the pool
    dtypes so int4 packing halves it automatically.

    ``window`` (a window layer): both counts leave out the pages wholly
    below the window of the item's (the lane's) first query."""
    actual = mixed_grid_steps(
        pos_start, q_len, page=page, block_q=block_q, num_qb=num_qb,
        max_pages=max_pages, window=window) * hkv * page_head_bytes
    pos = pos_start.astype(np.int64, copy=False)
    ql = q_len.astype(np.int64, copy=False)
    seq_end = np.where(ql > 0, pos + ql, 0)
    seq_pages = np.minimum(-(-seq_end // page), max_pages)
    if window:
        first = np.maximum(pos - (window - 1), 0) // page
        seq_pages = seq_pages - np.minimum(np.where(ql > 0, first, 0),
                                           seq_pages)
    ideal = int(seq_pages.sum()) * hkv * page_head_bytes
    return actual, ideal


class PageAllocator:
    def __init__(self, num_pages: int, page: int, on_evict=None) -> None:
        self.page = page
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))
        self._ref = [0] * num_pages
        # digest -> page id; LRU order (oldest first).  The index holds ONE
        # reference on each registered page.
        self._index: "OrderedDict[bytes, int]" = OrderedDict()
        self._page_digest: dict[int, bytes] = {}
        # Spill hook: called as on_evict(digest, page) the moment an
        # index-retained page is evicted, BEFORE the page can reach the
        # free list — the engine uses it to queue the page for an async
        # D2H spill into the host-RAM prefix tier while its content is
        # still guaranteed un-overwritten on device.  Must not raise and
        # must not call back into the allocator (it runs mid-alloc).
        self.on_evict = on_evict
        # Membership mirror for the routing sketch: server threads need a
        # consistent view of WHICH digests are indexed, while _index stays
        # engine-thread-only.  The mirror tracks membership changes
        # (register/evict), not recency touches — so the hot decode path
        # (match's move_to_end) never takes the lock, and the version only
        # moves when an exported sketch would actually change.
        self._mirror_lock = threading.Lock()
        self._mirror: "OrderedDict[bytes, None]" = OrderedDict()
        self.index_version = 0
        # Stats (mirrored into EngineMetrics by the engine).
        self.hit_tokens = 0
        self.query_tokens = 0

    # -- allocation ----------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def retained_pages(self) -> int:
        return len(self._index)

    def alloc(self, n: int) -> list[int]:
        """n fresh pages (refcount 1 each).  Evicts LRU retained pages as
        needed; raises OutOfPagesError when even eviction cannot satisfy
        (pool mis-sized)."""
        while len(self._free) < n and self._index:
            self._evict_lru()
        if len(self._free) < n:
            raise OutOfPagesError(
                f"need {n} pages, {len(self._free)} free and nothing "
                "evictable — pool too small for the active slots")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def _evict_lru(self) -> None:
        digest, pg = self._index.popitem(last=False)
        del self._page_digest[pg]
        with self._mirror_lock:
            self._mirror.pop(digest, None)
            self.index_version += 1
        if self.on_evict is not None:
            self.on_evict(digest, pg)
        self._ref[pg] -= 1
        if self._ref[pg] == 0:
            self._free.append(pg)

    def incref(self, pages) -> None:
        for p in pages:
            self._ref[p] += 1

    def decref(self, pages) -> None:
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._free.append(p)
            elif self._ref[p] < 0:
                raise AssertionError(f"page {p} refcount underflow")

    # -- prefix index --------------------------------------------------

    def match(self, digests: list[bytes]) -> list[int]:
        """Pages for the longest indexed digest-chain prefix; each matched
        page gets a caller reference (incref) and an LRU touch."""
        pages = []
        for d in digests:
            pg = self._index.get(d)
            if pg is None:
                break
            self._index.move_to_end(d)
            self._ref[pg] += 1
            pages.append(pg)
        return pages

    def register(self, digests: list[bytes], pages: list[int]) -> None:
        """Put (digest, page) pairs into the index.  The index takes ONE
        reference per newly-registered page; already-indexed digests keep
        their existing page (the caller's duplicate page stays owned by the
        caller alone and is freed on its decref).  A page already indexed
        under a DIFFERENT digest is skipped: _page_digest is a one-to-one
        reverse map, and overwriting it would leave the old digest's index
        entry stale — evicting either digest would then delete the other's
        reverse entry and a later eviction would KeyError mid-alloc (and
        the refcount held for the old entry would leak)."""
        for d, pg in zip(digests, pages):
            if d in self._index:
                self._index.move_to_end(d)
                continue
            if self._page_digest.get(pg, d) != d:
                continue
            self._index[d] = pg
            self._page_digest[pg] = d
            self._ref[pg] += 1
            with self._mirror_lock:
                self._mirror[d] = None
                self._mirror.move_to_end(d)
                self.index_version += 1

    def index_snapshot(self) -> tuple[list[bytes], int]:
        """Indexed digests (registration order, oldest first) plus the
        membership version — the tier-0 input to the routing sketch.
        Safe from any thread; the engine thread only pays the mirror lock
        on membership changes, never per match."""
        with self._mirror_lock:
            return list(self._mirror), self.index_version

    # -- stats ---------------------------------------------------------

    def record_query(self, num_tokens: int, hit: int) -> None:
        self.query_tokens += num_tokens
        self.hit_tokens += hit

    @property
    def hit_rate(self) -> float:
        return self.hit_tokens / self.query_tokens if self.query_tokens else 0.0


def window_pages_per_slot(window: int, rows: int, page: int,
                          max_pages: int) -> int:
    """The most window-layer pages one slot holds: the pages that meet the
    ``window - 1`` keys behind the first of ``rows`` rows written in one
    dispatch burst (a prefill chunk's budget, or a decode row times the
    pipeline's depth) and the rows themselves, wherever the first falls in
    its page.  Window 512, a 1024-row chunk, pages of 256: 7."""
    return min(-(-(window - 1 + rows) // page) + 1, max_pages)


class WindowPages:
    """Host authority over the WINDOW layers' pages of a model with window
    and full attention layers: an allocator, block tables and lifetimes of
    their own.  A full-attention page lives as long as its sequence; a
    window page is released once it lies wholly behind the window of the
    next row its slot writes, so a slot holds at most
    :func:`window_pages_per_slot` of them whatever its context, and the
    pool (slots x that many) can never run out.

    ``tables[slot, j]`` is the page that holds positions ``[j x page,
    (j + 1) x page)`` of the slot; entries behind the window go STALE when
    their page is released (they may name a page another slot now owns)
    and are never read: the window launch's work list starts at the page
    that holds the lowest key a query attends
    (``ops.paged_attention.build_mixed_work_list``).

    When a page may go: :meth:`cover` is called with the slot's RESOLVED
    length, before the dispatch that writes from it (under pipelined
    decode the host's lengths lag the device's by the dispatches in
    flight).  Every dispatch still in flight reads at positions at or past
    that length, so its window starts at or past the bound the release is
    made on; and a page handed on to another slot is written by a later
    dispatch of the same stream.  Engine thread only."""

    def __init__(self, num_slots: int, max_pages: int, page: int,
                 window: int, per_slot: int) -> None:
        self.page, self.window, self.per_slot = page, window, per_slot
        self.max_pages = max_pages
        self.alloc = PageAllocator(num_slots * per_slot, page)
        self.tables = np.zeros((num_slots, max_pages), np.int32)
        # slot -> [index of the first page held, the pages from it on,
        # every page the slot was given so far].
        self._held: dict[int, list] = {}
        # Pages the held slots would hold had none been released: the sum
        # of the pages each was given.
        self.unreleased_pages = 0

    @property
    def pages_in_use(self) -> int:
        return self.alloc.num_pages - self.alloc.free_pages

    def held(self, slot: int) -> tuple[int, list[int]]:
        first, pages, _ = self._held.get(slot, (0, [], 0))
        return first, list(pages)

    def cover(self, slot: int, start: int, rows: int) -> int:
        """Before a dispatch burst that writes ``rows`` rows of ``slot``
        from position ``start``: release the pages wholly behind ``start -
        window + 1`` (the lowest key the first of them attends) and own
        every page from the one that holds it up to the last row's.
        Returns the pages released."""
        lo = max(start - self.window + 1, 0) // self.page
        hi = min((start + max(rows, 1) - 1) // self.page, self.max_pages - 1)
        rec = self._held.setdefault(slot, [lo, [], 0])
        first, pages, _ = rec
        gone = min(max(lo - first, 0), len(pages))
        if gone:
            self.alloc.decref(pages[:gone])
            del pages[:gone]
        rec[0] = first = first + gone if pages else lo
        need = hi + 1 - (first + len(pages))
        if need > 0:
            new = self.alloc.alloc(need)
            at = first + len(pages)
            self.tables[slot, at: at + need] = new
            pages.extend(new)
            rec[2] += need
            self.unreleased_pages += need
        return gone

    def release(self, slot: int) -> None:
        """The slot is done: every page back (not counted as released
        behind a window)."""
        _, pages, given = self._held.pop(slot, (0, [], 0))
        if pages:
            self.alloc.decref(pages)
        self.unreleased_pages -= given
