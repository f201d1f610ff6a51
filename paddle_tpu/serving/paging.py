"""Host-side KV page accounting: the allocator and the prefix index.

The device holds one page pool ``[L, n_pages, page_size, Hkv*dh]`` per
K/V (generation.py owns those tensors); THIS module owns the metadata —
which physical pages are free, how many holders reference each page, and
which pages cache which prompt prefixes. Everything here is plain Python
over numpy ints: no device traffic, no locks (the engine is single-
threaded per tick). A model whose layers differ in kind (full attention /
a sliding window) has one such pool, ``PagePool`` and ``PrefixIndex`` PER
KIND, behind one :class:`PageCache` each: both invariants below hold for
each, and the engine, which walks a list of them, keeps the indexes in
lockstep (the same content keys, page by page).

Two invariants the engine relies on:

- **Reservation-before-admission.** A request reserves every page it can
  ever need (prompt + max_new_tokens, plus one copy-on-write spare when
  it shares a page it will later write) at admission, so decode never
  allocates — pool pressure surfaces as a typed admission signal
  (:class:`~paddle_tpu.serving.errors.CacheExhaustedError` /
  deferral), never as a mid-decode failure.
- **Write-implies-exclusive.** A page with refcount > 1 is never written;
  the engine copies it first (``kv_cache_page_copy``) and redirects the
  writer's block table to the copy. The prefix index counts as a holder,
  so cached prefixes are immutable by construction.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

SCRAP_PAGE = 0  # padding rows / vacant decode slots write here

PAGED_CACHE_K = "serving.paged_cache_k"
PAGED_CACHE_V = "serving.paged_cache_v"
# the window layers' pools of a spec whose layers differ in kind
PAGED_CACHE_KW = "serving.paged_cache_kw"
PAGED_CACHE_VW = "serving.paged_cache_vw"
# a sparse latent layer's pooled indexer keys, under the latent pool's pages
PAGED_CACHE_INDEX = "serving.paged_cache_index"
#: kind -> the scope names and op slots of its pools, the plane column and
#: op slot of its table, the suffix of its statistics' keys (and of the
#: engine keyword that sizes it), how an error names its pages
_KINDS = {
    "global": ((PAGED_CACHE_K, PAGED_CACHE_V), ("CacheK", "CacheV"),
               "serving.block_table", "BlockTable", "", "pages"),
    "window": ((PAGED_CACHE_KW, PAGED_CACHE_VW), ("CacheKW", "CacheVW"),
               "serving.block_table_w", "BlockTableW", "_window",
               "window-layer pages"),
}


class PagePool:
    """Free-list + refcount allocator over ``n_pages`` physical pages.

    Page 0 is the scrap page — permanently pinned, never handed out.
    ``reserve``/``release_reservation`` implement admission-time holds:
    reserved pages are not yet assigned, but they are subtracted from
    :meth:`available` so concurrent admissions cannot oversubscribe, and
    ``alloc(reserved=True)`` draws a physical page out of the hold.
    """

    def __init__(self, n_pages: int, page_size: int):
        if n_pages < 2:
            raise ValueError("need at least 2 pages (one is scrap)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        # LIFO free list: recently-freed pages are re-used first
        self._free: List[int] = list(range(self.n_pages - 1, 0, -1))
        self._ref = np.zeros(self.n_pages, np.int32)
        self._ref[SCRAP_PAGE] = 1  # pinned
        self._reserved = 0
        #: alloc + incref + decref calls so far: whoever holds a page got
        #: it, or let it go, through one of them, so a count of pages by
        #: holder stays valid for as long as this stands still
        self.changes = 0

    # -- accounting --------------------------------------------------------
    @property
    def capacity(self) -> int:
        """Allocatable pages (everything but scrap)."""
        return self.n_pages - 1

    def available(self) -> int:
        """Pages allocatable right now (free minus admission holds)."""
        return len(self._free) - self._reserved

    def pages_in_use(self) -> int:
        return self.capacity - len(self._free)

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    # -- reservation holds -------------------------------------------------
    def reserve(self, n: int) -> None:
        """Hold ``n`` free pages for a future ``alloc(reserved=True)``."""
        if n < 0:
            raise ValueError("negative reservation")
        if self.available() < n:
            raise RuntimeError(
                f"reserve({n}) with only {self.available()} available")
        self._reserved += n

    def release_reservation(self, n: int) -> None:
        if n > self._reserved:
            raise RuntimeError("releasing more pages than reserved")
        self._reserved -= n

    # -- alloc/ref ---------------------------------------------------------
    def alloc(self, reserved: bool = False) -> int:
        """Pop a free page (refcount 1). ``reserved=True`` consumes one
        unit of a prior :meth:`reserve` hold."""
        if reserved:
            if self._reserved < 1:
                raise RuntimeError("alloc(reserved=True) without a hold")
            self._reserved -= 1
        elif self.available() < 1:
            raise RuntimeError("page pool exhausted")
        page = self._free.pop()
        self._ref[page] = 1
        self.changes += 1
        return page

    def incref(self, page: int) -> None:
        if page == SCRAP_PAGE or self._ref[page] < 1:
            raise RuntimeError(f"incref of unallocated page {page}")
        self._ref[page] += 1
        self.changes += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; returns True when the page was freed."""
        if page == SCRAP_PAGE:
            raise RuntimeError("decref of the scrap page")
        if self._ref[page] < 1:
            raise RuntimeError(f"decref of free page {page}")
        self._ref[page] -= 1
        self.changes += 1
        if self._ref[page] == 0:
            self._free.append(page)
            return True
        return False

    def stats(self) -> dict:
        return {"n_pages": self.n_pages, "page_size": self.page_size,
                "in_use": self.pages_in_use(), "free": len(self._free),
                "reserved": self._reserved,
                "shared": int(np.sum(self._ref[1:] > 1))}


def chain_key(parent: Optional[bytes], tokens: Sequence[int],
              media: Optional[bytes] = None) -> bytes:
    """Content-derived prefix key: digest of (parent key, page tokens).
    Two prompts share page i iff their first i pages carry identical
    tokens — the digest chain makes the whole-prefix comparison O(1)
    per page regardless of depth. ``media``: the digest of the pixels
    whose rows lie on the page (every vision placeholder has ONE id: two
    clips of equal length differ only here); a page of text alone has
    none, and its key is what it always was."""
    h = hashlib.blake2b(digest_size=16)
    h.update(parent or b"\x00")
    h.update(np.asarray(tokens, np.int64).tobytes())
    if media:
        h.update(b"\x01media")
        h.update(media)
    return h.digest()


class PrefixIndex:
    """LRU map from prompt-prefix keys to cached pages.

    Each entry holds ONE pool reference on its page, so cached prefixes
    survive the requests that produced them — the next request with the
    same system prompt skips that prefill. Entries are content-keyed by
    :func:`chain_key`, walked page-by-page from the prompt's first page;
    the final PARTIAL page may be cached too (keyed by its shorter token
    tuple), which is what makes a full-prompt hit — and therefore a real
    copy-on-write divergence — possible.

    ``evict_until`` frees least-recently-used entries until the pool can
    satisfy an allocation; entries whose page is still held by a live
    request drop only the index's reference (the page stays resident
    under the request and is freed when it finishes).

    **State snapshots** (``n_snapshots`` > 0: an engine whose slots carry a
    recurrent state beside their pages): the index also owns
    ``n_snapshots`` rows of the engine's snapshot arrays. A row is a copy
    of a slot's state at a page boundary ``snapshot_stride`` pages apart,
    held under the key of the page that ENDS there, so a prefix is a hit
    only as deep as the deepest such boundary that has both its pages and a
    row (``lookup_snapshot``). Rows are least-recently-used among
    themselves (``alloc_snapshot`` evicts the oldest unpinned one when none
    is free); a row goes with its page when the page's entry is evicted;
    and a row some slot is about to start from is PINNED: evicted or
    orphaned, it is not handed out again before ``unpin_snapshot``.
    """

    def __init__(self, pool: PagePool, n_snapshots: int = 0,
                 snapshot_stride: int = 0):
        self._pool = pool
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        if n_snapshots and snapshot_stride < 1:
            raise ValueError("snapshot rows need snapshot_stride >= 1 pages")
        self.snapshot_stride = int(snapshot_stride)
        self.n_snapshots = int(n_snapshots)
        self._snap_free: List[int] = list(range(self.n_snapshots - 1, -1, -1))
        self._snaps: "OrderedDict[bytes, int]" = OrderedDict()
        self._snap_pins: Dict[int, int] = {}
        self._snap_orphans: set = set()     # dropped while pinned
        self.snapshot_evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def _walk(self, prompt: np.ndarray, n_full: int, media=None):
        """The chain walk every lookup makes: (key, page) of each cached
        full page of ``prompt`` among its first ``n_full``, in order, up to
        the first that is not cached (hits, one miss and recency counted).
        ``media``: the prompt's media digest a page (``chain_key``)."""
        ps = self._pool.page_size
        key: Optional[bytes] = None
        for i in range(n_full):
            key = chain_key(key, prompt[i * ps:(i + 1) * ps],
                            media[i] if media else None)
            page = self._entries.get(key)
            if page is None:
                self.misses += 1
                return
            self._entries.move_to_end(key)
            self.hits += 1
            yield key, page

    def lookup(self, prompt: np.ndarray, media=None
               ) -> Tuple[int, List[int], bytes]:
        """Longest cached prefix of ``prompt``: returns
        ``(shared_tokens, page_ids, last_matched_key)``. Walks full
        pages, then tries the exact partial tail; ``shared_tokens`` is a
        page multiple except on a full-prompt hit. Does NOT take
        references — the caller increfs the pages it decides to use."""
        ps = self._pool.page_size
        n_full = len(prompt) // ps
        key: Optional[bytes] = None
        pages: List[int] = []
        for key, page in self._walk(prompt, n_full, media):
            pages.append(page)
        shared = len(pages) * ps
        tail = prompt[n_full * ps:]
        if len(pages) == n_full and len(tail):
            k = chain_key(key, tail, media[n_full] if media else None)
            page = self._entries.get(k)
            if page is not None:
                self._entries.move_to_end(k)
                self.hits += 1
                key = k
                pages.append(page)
                shared += len(tail)
            else:
                self.misses += 1
        return shared, pages, key or b""

    def page_after(self, parent_key: bytes, tokens: Sequence[int],
                   media: Optional[bytes] = None
                   ) -> Optional[Tuple[bytes, int]]:
        """The cached page that continues ``parent_key`` (b"" for the
        first page) by the full page ``tokens``, with its key; None when
        it is not cached. No statistics, no reference taken."""
        k = chain_key(parent_key or None, tokens, media)
        page = self._entries.get(k)
        return None if page is None else (k, page)

    def insert(self, parent_key: bytes, tokens: Sequence[int],
               page: int, media: Optional[bytes] = None) -> bytes:
        """Cache ``page`` as the prefix continuation ``tokens`` of
        ``parent_key`` (b"" for the first page). Takes one pool
        reference; a no-op (key returned) when already cached."""
        k = chain_key(parent_key or None, tokens, media)
        if k not in self._entries:
            self._pool.incref(page)
            self._entries[k] = page
        self._entries.move_to_end(k)
        return k

    def evict_until(self, pages_needed: int) -> int:
        """Drop LRU entries until ``pool.available() >= pages_needed``
        (or the index is empty). Returns entries evicted. A snapshot goes
        with the page that ends at its boundary."""
        n = 0
        while (self._pool.available() < pages_needed and self._entries):
            key, page = self._entries.popitem(last=False)
            self._pool.decref(page)
            self.evictions += 1
            if key in self._snaps:
                self._drop_snapshot(key)
            n += 1
        return n

    # -- state snapshots ---------------------------------------------------
    def lookup_snapshot(self, prompt: np.ndarray, limit: int
                        ) -> Tuple[int, int, List[int], bytes, Optional[int]]:
        """The prefix of ``prompt`` a slot WITH STATE can enter at: ->
        ``(matched, shared, page_ids, key, row)``. ``matched``: tokens of
        the full pages the index holds for the prompt's first ``limit``
        tokens; ``shared`` <= matched: the deepest stride boundary among
        them that also has a snapshot (0: none), ``page_ids`` / ``key`` the
        pages up to it and the chain key there, ``row`` its snapshot row
        (None when ``shared`` is 0). No reference is taken and nothing is
        pinned: the caller increfs the pages and pins the row."""
        ps, stride = self._pool.page_size, self.snapshot_stride
        pages: List[int] = []
        best = (0, b"", None)
        for k, page in self._walk(prompt, min(limit, len(prompt)) // ps):
            pages.append(page)
            if len(pages) % stride == 0 and k in self._snaps:
                self._snaps.move_to_end(k)
                best = (len(pages), k, self._snaps[k])
        n, key_at, row = best
        return len(pages) * ps, n * ps, pages[:n], key_at, row

    def snapshot_row(self, key: bytes) -> Optional[int]:
        return self._snaps.get(key)

    def alloc_snapshot(self) -> Optional[int]:
        """A snapshot row to write: a free one, else the least recently
        used row no slot is starting from (its key loses it); None when
        every row is pinned."""
        if self._snap_free:
            return self._snap_free.pop()
        for key, row in self._snaps.items():
            if not self._snap_pins.get(row):
                self._drop_snapshot(key)    # the row is free again
                return self._snap_free.pop()
        return None

    def attach_snapshot(self, key: bytes, row: int) -> bool:
        """Hold ``row`` under ``key`` (the chain key of the page that ends
        at the snapshot's boundary). The page must be cached and have no
        snapshot yet: else the row goes back to the free list (False)."""
        if key not in self._entries or key in self._snaps:
            self._snap_free.append(row)
            return False
        self._snaps[key] = row
        return True

    def pin_snapshot(self, row: int) -> None:
        self._snap_pins[row] = self._snap_pins.get(row, 0) + 1

    def unpin_snapshot(self, row: int) -> None:
        left = self._snap_pins.get(row, 0) - 1
        if left > 0:
            self._snap_pins[row] = left
            return
        self._snap_pins.pop(row, None)
        if row in self._snap_orphans:
            self._snap_orphans.discard(row)
            self._snap_free.append(row)

    def _drop_snapshot(self, key: bytes) -> None:
        row = self._snaps.pop(key)
        self.snapshot_evictions += 1
        if self._snap_pins.get(row):
            self._snap_orphans.add(row)     # freed by its last unpin
        else:
            self._snap_free.append(row)

    def snapshots_in_use(self) -> int:
        return self.n_snapshots - len(self._snap_free)

    def clear(self) -> int:
        return self.evict_until(self._pool.n_pages + 1)

    def stats(self) -> dict:
        out = {"entries": len(self._entries), "hits": self.hits,
               "misses": self.misses, "evictions": self.evictions}
        if self.n_snapshots:
            out.update(snapshots=len(self._snaps),
                       snapshot_evictions=self.snapshot_evictions)
        return out


class Held:
    """What ONE slot holds of one :class:`PageCache` (``_Slot.held``: a
    list parallel to the engine's list of caches)."""

    __slots__ = ("pages", "first", "entries", "reserve", "cow")

    def __init__(self):
        self.pages: List[int] = []  # physical page per table entry so
                                    # far; 0 once it lies behind the window
        self.first = 0      # first entry that may hold a page
        self.entries = 0    # entries the slot will ever touch
        self.reserve = 0    # pool pages held for the entries to come
        self.cow = 0        # ... and for one copy-on-write


class PageCache:
    """ONE kind of page cache: the pools of the layers of that kind
    (``shape``, under ``scope_names`` in the scope and ``op_slots`` of the
    paged ops), their :class:`PagePool` and :class:`PrefixIndex` (None:
    no prefix sharing), the column of a call's plane that carries a
    slot's table, and the rule by which a slot's :class:`Held` grows.

    The rule is ``window``. 0, the full-attention kind (a latent pool
    too): a slot holds every page of its sequence, all of them allocated
    at admission (``take``), so decode never allocates. A window of
    ``window`` tokens: a query at ``pos`` reaches keys ``pos - window < j
    <= pos``, so admission only HOLDS pages, ``live`` of them (the
    window, the chunk in flight, one page of slack each way) plus the
    prompt pages the slot will leave to the index (``need``), and
    ``advance`` allocates and lets go. ``count(name, n)`` is the
    engine's ``metrics.inc``."""

    def __init__(self, name: str, pool: PagePool,
                 index: Optional[PrefixIndex], *, layers: int,
                 row_width: int, n_pools: int = 2, window: int = 0,
                 live: int = 0, index_row: Optional[Tuple[int, int]] = None,
                 count: Callable[..., None]):
        names, slots, self.table, self.table_slot, self.suffix, \
            self.noun = _KINDS[name]
        self.name = name
        #: gauges ``mem/<stem>_in_use`` / ``_free``, ``cache_stats()`` keys
        self.stem = f"kv{self.suffix}_pages"
        self.pool, self.index = pool, index
        self.page_size = pool.page_size
        self.scope_names, self.op_slots = names[:n_pools], slots[:n_pools]
        #: [L, n_pages, page_size, row]: a token's row of one layer is
        #: contiguous, so a page is contiguous and lane-dense on the device
        #: (ops/pipeline_ops.py says why the head-major form was not)
        self.shape = (layers, pool.n_pages, pool.page_size, row_width)
        #: scope name -> shape of every pool. ``index_row`` = (tokens a
        #: group, width): one more, narrow pool under the SAME page ids,
        #: a sparse latent layer's pooled indexer keys [L, n_pages,
        #: page_size / group, width]; it moves, is copied and is let go
        #: with its page
        self.shapes = {name: self.shape for name in self.scope_names}
        if index_row is not None:
            group, width = index_row
            if name != "global" or pool.page_size % group:
                raise ValueError(
                    f"index_row: pooled keys of {group} tokens lie inside a "
                    f"page of the full-attention kind (page_size "
                    f"{pool.page_size} is not whole groups)")
            self.scope_names += (PAGED_CACHE_INDEX,)
            self.op_slots += ("CacheIndex",)
            self.shapes[PAGED_CACHE_INDEX] = (
                layers, pool.n_pages, pool.page_size // group, width)
        self.window, self.live = int(window), int(live)
        self._count = count
        self._held_at, self._held_n = -1, 0     # ``held_pages``' memo

    # -- the growth rule ---------------------------------------------------
    def first_entry(self, pos: int) -> int:
        """The first table entry a query at ``pos`` can still reach."""
        if not self.window:
            return 0
        return max(0, pos - self.window + 1) // self.page_size

    def never_fits(self, entries: int) -> bool:
        """Whether the kind that holds every page of a sequence can never
        hold ``entries`` of them, whatever the index shares (a shared
        prefix trades >=1 page for <=1 copy-on-write spare)."""
        return not self.window and entries > self.pool.capacity

    def need(self, entries: int, n_hit: int, unshared: int, cow: int) -> int:
        """Pages an admission must find available: its own (``entries``
        less the ``n_hit`` it shares; a window kind at most its live
        window plus the full pages of its ``unshared`` prompt tokens: it
        leaves them to the index, where they stay resident, so their
        successors need pages too) and the ``cow`` spare of a slot whose
        generation writes a shared page."""
        own = entries - n_hit
        if self.window:
            donate = (unshared // self.page_size
                      if self.index is not None else 0)
            own = min(own, self.live + donate)
        return own + cow

    # -- admission ---------------------------------------------------------
    def hold(self, pages: Sequence[int]) -> None:
        """Take a reference on a hit's pages before any eviction runs."""
        for pid in pages:
            self.pool.incref(pid)

    def unhold(self, pages: Sequence[int]) -> None:
        for pid in pages:
            self.pool.decref(pid)

    def make_room(self, need: int) -> bool:
        """Evict the index until ``need`` pages are available; False:
        they are not (the admission defers, by this kind)."""
        if self.pool.available() < need and self.index is not None:
            self.index.evict_until(need)
        return self.pool.available() >= need

    def take(self, held: Held, hit: List[int], keep: int, entries: int,
             need: int, cow: int) -> None:
        """Give an admitted slot what it holds from here on: of the hit
        the pages from entry ``keep`` (held since ``hold``), and its own,
        allocated now or held for ``advance``, by the rule."""
        held.pages = [0] * min(keep, len(hit)) + hit[keep:]
        held.first, held.entries, held.cow = keep, entries, cow
        if self.window:
            self.pool.reserve(need)
            held.reserve = need - cow
        else:
            held.pages += [self.pool.alloc() for _ in range(need - cow)]
            if cow:
                self.pool.reserve(cow)

    # -- a slot's table as it advances -------------------------------------
    def _alloc(self, held: Held) -> int:
        if held.reserve > 0:
            held.reserve -= 1
            return self.pool.alloc(reserved=True)
        # defensive: the admission hold covers every allocation
        self._count("kv_window_unreserved_allocs")
        self.make_room(1)
        return self.pool.alloc()

    def advance(self, held: Held, q_first: int, q_last: int) -> None:
        """Before a call whose queries for this slot sit at positions
        ``q_first..q_last`` (nothing to do for the kind that never lets
        go): release the pages no query of the call (or any later one)
        can reach, and allocate, out of the slot's hold, the entries the
        call writes. A page that came back to the pool is held again at
        once while the slot still has entries to come, so the hold never
        shrinks under it."""
        if not self.window:
            return
        keep_from = self.first_entry(q_first)
        pages = held.pages
        for e in range(held.first, min(keep_from, len(pages))):
            pid = pages[e]
            if not pid:
                continue
            pages[e] = 0
            self._count("kv_window_pages_released")
            if self.pool.decref(pid) \
                    and held.reserve < held.entries - len(pages):
                self.pool.reserve(1)
                held.reserve += 1
        held.first = max(held.first, keep_from)
        while len(pages) <= q_last // self.page_size:
            pages.append(self._alloc(held)
                         if len(pages) >= held.first else 0)

    def before_write(self, held: Held, pos: int, copy_fn,
                     last: Optional[int] = None) -> None:
        """What a decode tick that writes position ``pos`` (a verify tick:
        ``pos..last``, its queries' positions too) needs of the slot's
        table first: the page under it, and that its own. One still shared
        (refcount > 1) is copied (``copy_fn(cache, src, dst)``) to a fresh
        page from the slot's admission-time spare and the table
        redirected: copy-on-write. A window kind lets go only of what the
        query at ``pos``, the COMMITTED position, can no longer reach.
        (Every live slot passes here every tick: the common case is one
        refcount read.)"""
        last = pos if last is None else last
        if self.window:
            self.advance(held, pos, last)
        for entry in range(pos // self.page_size,
                           last // self.page_size + 1):
            self._own(held, entry, copy_fn)

    def _own(self, held: Held, entry: int, copy_fn) -> None:
        pid = held.pages[entry]
        if self.pool._ref[pid] <= 1:
            return
        if held.cow > 0:
            held.cow -= 1
            new = self.pool.alloc(reserved=True)
        else:  # defensive: never expected, but never corrupt a share
            self.make_room(1)
            new = self.pool.alloc()
        copy_fn(self, pid, new)
        self.pool.decref(pid)
        held.pages[entry] = new
        self._count("kv_cow_copies")

    def trade(self, held: Held, entry: int, page: int) -> None:
        """Hold ``page`` (another slot's, of the same content) for
        ``entry`` in place of the slot's own."""
        self.pool.incref(page)
        self.pool.decref(held.pages[entry])
        held.pages[entry] = page

    def table_of(self, held: Held) -> List[int]:
        """The slot's page ids in table order (a copy)."""
        return list(held.pages)

    def release(self, held: Held) -> None:
        """Everything the slot holds goes back: a reference a page, and
        what is left of its hold."""
        for pid in held.pages:
            if pid:
                self.pool.decref(pid)
        held.pages = []
        if held.reserve + held.cow:
            self.pool.release_reservation(held.reserve + held.cow)
            held.reserve = held.cow = 0

    # -- accounting --------------------------------------------------------
    def pages_read(self, pos: np.ndarray, last=None) -> int:
        """The pages a decode tick's attention walks in a layer of this
        kind for queries at ``pos`` (one a row at least: a vacant slot
        reads the scrap page); with ``last``, the pages queries at
        ``pos..last`` reach between them, each counted once."""
        first = (np.maximum(pos + 1 - self.window, 0) // self.page_size
                 if self.window else 0)
        last = pos if last is None else last
        return int((last // self.page_size + 1 - first).sum())

    def chunk_pages_read(self, start: np.ndarray, lengths: np.ndarray,
                         width: int) -> int:
        """The pages a prefill chunk's attention walks in a layer of this
        kind (``kernels/paged_attention.chunk_pages_in_reach``, the
        kernel's own rule): rows at ``start`` with ``lengths`` real tokens
        (0: a padding row, which reads none) under tables ``width`` wide."""
        from ..kernels.paged_attention import chunk_pages_in_reach

        first, end = chunk_pages_in_reach(
            start.astype(np.int64), lengths.astype(np.int64),
            self.page_size, self.window or None, xp=np)
        return int((np.minimum(end, width) - np.minimum(first, width)).sum())

    def held_pages(self, helds: Iterable[Held]) -> int:
        """Distinct pages the slots hold (``helds``: theirs of this
        cache); what only the index still caches is evictable and not
        counted. Recounted only after the pool changed hands
        (``PagePool.changes``)."""
        if self._held_at != self.pool.changes:
            self._held_n = np.unique(np.fromiter(
                (p for held in helds for p in held.pages if p),
                np.int64)).size
            self._held_at = self.pool.changes
        return self._held_n
