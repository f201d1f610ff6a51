"""``serving.paging.PageCache`` without an engine: one kind of page cache
(pool, index, table column, growth rule) and what a slot holds of it
(``Held``), driven by hand through the steps ``GenerationEngine`` takes:
look the prompt up, size the admission, hold the hit, make room or defer,
take the pages, advance chunk by chunk and tick by tick, copy a shared
page before a write, register, release. Pure host, no device."""
from collections import Counter

import numpy as np
import pytest

from paddle_tpu.serving.paging import (Held, PageCache, PagePool,
                                       PrefixIndex, chain_key)

PS, WINDOW, CHUNK = 4, 8, 8
#: what a window slot can hold at once: the window, the chunk in flight and
#: a page of slack each way (the engine's own expression)
LIVE = -(-WINDOW // PS) + 1 + -(-CHUNK // PS)


def _cache(kind, n_pages=40, sharing=True):
    pool = PagePool(n_pages, PS)
    counts = Counter()
    cache = PageCache(
        kind, pool, PrefixIndex(pool) if sharing else None, layers=2,
        row_width=16, count=lambda name, n=1: counts.update({name: n}),
        **(dict(window=WINDOW, live=LIVE) if kind == "window" else {}))
    return cache, counts


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 500, size=n)


def _entries(n_tokens):
    return -(-n_tokens // PS)


class _Seq:
    """One sequence through one cache, as the engine walks it: ``done``
    prompt tokens are cached, ``at`` is the position the next tick
    writes, ``left`` the ticks to come."""

    def __init__(self, cache, prompt, max_new):
        self.cache, self.prompt, self.max_new = cache, prompt, max_new
        self.held, self.copies = Held(), []
        self.done = self.at = self.left = 0

    def plan(self):
        """-> (shared tokens, the hit's pages, first entry kept, need,
        copy-on-write spare) of an admission."""
        cache, plen = self.cache, len(self.prompt)
        shared, hit = 0, []
        if cache.index is not None:
            shared, hit, _ = cache.index.lookup(self.prompt)
        cow = int(shared == plen)
        keep = cache.first_entry(shared if shared < plen else plen - 1)
        need = cache.need(_entries(plen + self.max_new), len(hit),
                          plen - shared, cow)
        return shared, hit, keep, need, cow

    def admit(self):
        """True: admitted; False: deferred, with every hold undone."""
        cache, plen = self.cache, len(self.prompt)
        shared, hit, keep, need, cow = self.plan()
        cache.hold(hit[keep:])
        if not cache.make_room(need):
            cache.unhold(hit[keep:])
            return False
        cache.take(self.held, hit, keep, _entries(plen + self.max_new),
                   need, cow)
        # a hit on the whole prompt skips the prefill: the first tick
        # re-writes the prompt's last token, into a page it shares
        self.done, self.at = shared, plen - cow
        self.left = self.max_new - 1 + cow
        return True

    def _copy(self, cache, src, dst):
        assert cache is self.cache
        self.copies.append((src, dst))

    def chunk(self):
        """One prefill chunk, then the full pages it completed go into
        the index."""
        end = min(self.done + CHUNK, len(self.prompt))
        self.cache.advance(self.held, self.done, end - 1)
        self.done = end
        self.register()

    def prefill(self):
        while self.done < len(self.prompt):
            self.chunk()

    def register(self, tail=False):
        if self.cache.index is None:
            return
        n_full, key = self.done // PS, b""
        pages = self.held.pages
        for i in range(n_full):
            toks = self.prompt[i * PS:(i + 1) * PS]
            key = (self.cache.index.insert(key, toks, pages[i])
                   if i < len(pages) and pages[i]
                   else chain_key(key or None, toks))   # (a page let go)
        rest = self.prompt[n_full * PS:]
        if tail and self.done == len(self.prompt) and len(rest) \
                and pages[n_full]:
            self.cache.index.insert(key, rest, pages[n_full])

    def tick(self):
        self.cache.before_write(self.held, self.at, self._copy)
        page = self.held.pages[self.at // PS]
        assert page and self.cache.pool.refcount(page) == 1, \
            "a tick writes a page of the slot's own"
        _no_page_twice(self.held)
        self.at += 1
        self.left -= 1

    def decode(self):
        while self.left:
            self.tick()

    def finish(self):
        self.register(tail=True)
        self.cache.release(self.held)


def _no_page_twice(held):
    own = [p for p in held.pages if p]
    assert len(own) == len(set(own)), held.pages


def _settled(cache):
    """Nothing in flight: no hold is left, and the index alone holds
    pages, one reference each."""
    assert cache.pool.stats()["reserved"] == 0
    n_index = len(cache.index) if cache.index is not None else 0
    assert cache.pool.pages_in_use() == n_index
    if cache.index is not None:
        assert all(cache.pool.refcount(p) == 1
                   for p in cache.index._entries.values())


# ---------------------------------------------------------------------------
# identity: what the engine reads off a cache to build programs and stats
# ---------------------------------------------------------------------------
def test_a_kind_names_its_pools_its_table_and_its_statistics():
    g, _ = _cache("global")
    w, _ = _cache("window", n_pages=12)
    assert g.scope_names == ("serving.paged_cache_k",
                             "serving.paged_cache_v")
    assert w.scope_names == ("serving.paged_cache_kw",
                             "serving.paged_cache_vw")
    assert (g.op_slots, w.op_slots) == (("CacheK", "CacheV"),
                                        ("CacheKW", "CacheVW"))
    assert (g.table, g.table_slot) == ("serving.block_table", "BlockTable")
    assert (w.table, w.table_slot) == ("serving.block_table_w",
                                       "BlockTableW")
    assert (g.stem, g.suffix, w.stem, w.suffix) == (
        "kv_pages", "", "kv_window_pages", "_window")
    assert g.shape == (2, 40, PS, 16) and w.shape == (2, 12, PS, 16)
    # a latent block's cache is ONE pool
    latent = PageCache("global", PagePool(8, PS), None, layers=3,
                       row_width=40, n_pools=1, count=lambda *a: None)
    assert latent.scope_names == ("serving.paged_cache_k",)
    assert latent.op_slots == ("CacheK",)


@pytest.mark.parametrize("pos,first", [(0, 0), (7, 0), (8, 0), (11, 1),
                                       (12, 1), (15, 2), (40, 8)])
def test_first_entry_a_query_reaches(pos, first):
    """Keys ``pos - window < j <= pos``: the window kind's first entry
    is that of key ``pos - window + 1``; the other kind reaches all."""
    assert _cache("window")[0].first_entry(pos) == first
    assert _cache("global")[0].first_entry(pos) == 0


# ---------------------------------------------------------------------------
# what an admission needs, against a hand count
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("case,kind,want", [
    # 10 + 6 tokens = 4 pages, nothing cached: all of them
    ("unshared", "global", (0, 0, 4, 0)),
    # the first two pages are cached: two of its own
    ("shared_prefix", "global", (8, 2, 2, 0)),
    # the whole prompt is cached (2 full pages + the partial third): one
    # page to come and one spare for the write into the shared third
    ("full_hit", "global", (10, 3, 1 + 1, 1)),
    # a window slot holds at most LIVE pages however long it runs ...
    ("unshared_long", "window", (0, 0, LIVE + 60 // PS, 0)),
    # ... plus the full prompt pages it will leave to the index (those of
    # its unshared tokens), and never more than it has entries
    ("unshared", "window", (0, 0, 4, 0)),
    ("shared_prefix_long", "window", (8, 2, LIVE + (60 - 8) // PS, 0)),
    ("full_hit", "window", (10, 3, 1 + 1, 1)),
])
def test_need_of_an_admission(case, kind, want):
    cache, _ = _cache(kind, n_pages=80)
    long = case.endswith("_long")
    prompt = _prompt(60 if long else 10)
    max_new = 200 if long else 6
    if not case.startswith("unshared"):
        first = _Seq(cache, prompt if case == "full_hit" else
                     np.concatenate([prompt[:8], _prompt(5, seed=9)]), 3)
        assert first.admit()
        first.prefill()
        first.finish()
    shared, hit, keep, need, cow = _Seq(cache, prompt, max_new).plan()
    assert (shared, len(hit), need, cow) == want
    if kind == "global":
        assert keep == 0
    else:   # of the hit, what the next query still reaches
        assert keep == cache.first_entry(min(shared, len(prompt) - 1))


def test_a_window_kind_without_an_index_donates_nothing():
    cache, _ = _cache("window", sharing=False)
    assert cache.need(70, 0, 60, 0) == LIVE
    assert _cache("window")[0].need(70, 0, 60, 0) == LIVE + 15


def test_the_kind_that_holds_every_page_refuses_before_the_lookup():
    g, _ = _cache("global", n_pages=5)
    w, _ = _cache("window", n_pages=5)
    assert g.never_fits(5) and not g.never_fits(4)
    assert not w.never_fits(500)    # its need is known after the lookup


# ---------------------------------------------------------------------------
# the growth rule, step by step
# ---------------------------------------------------------------------------
def test_the_full_attention_kind_allocates_at_admission_and_never_again():
    cache, _ = _cache("global")
    seq = _Seq(cache, _prompt(10), 6)
    assert seq.admit()
    # LIFO free list: the pool's first pages, in table order
    assert seq.held.pages == [1, 2, 3, 4]
    before = cache.pool.changes
    seq.prefill()
    seq.decode()
    assert seq.at == 10 + 6 - 1     # the last token is never written
    assert seq.held.pages == [1, 2, 3, 4] and seq.copies == []
    assert cache.pool.changes == before + 2     # the two full prompt pages
    seq.finish()
    _settled(cache)


def test_the_window_kind_reserves_then_allocates_as_it_writes():
    cache, counts = _cache("window")
    seq = _Seq(cache, _prompt(20), 3 * WINDOW)
    assert seq.admit()
    assert seq.held.pages == [] and cache.pool.pages_in_use() == 0
    entries = _entries(20 + 3 * WINDOW)
    assert seq.held.reserve == min(entries, LIVE + 20 // PS)
    assert cache.pool.stats()["reserved"] == seq.held.reserve
    seq.prefill()
    # the last chunk's queries start at 16: entries before 9 // 4 are gone
    assert seq.held.pages == [0, 0, 3, 4, 5]
    held = []
    while seq.left:
        seq.tick()
        held.append(sum(1 for p in seq.held.pages if p))
    assert max(held) <= WINDOW // PS + 1
    assert counts["kv_window_pages_released"] >= entries - 3
    assert counts["kv_window_unreserved_allocs"] == 0
    seq.finish()
    _settled(cache)
    assert len(cache.index) == 5    # the prompt's pages outlive the slot


def test_a_write_into_a_shared_page_copies_it_first_by_kind():
    for kind in ("global", "window"):
        cache, counts = _cache(kind)
        prompt = _prompt(10)
        first = _Seq(cache, prompt, 2)
        assert first.admit()
        first.prefill()
        first.decode()
        first.finish()      # leaves two full pages and the partial third
        again = _Seq(cache, prompt, 4)
        assert again.admit() and again.done == 10 and again.held.cow == 1
        shared = again.held.pages[2]
        assert cache.pool.refcount(shared) == 2
        again.decode()
        (src, dst), = again.copies
        assert src == shared and again.held.pages[2] == dst != shared
        assert again.held.cow == 0 and counts["kv_cow_copies"] == 1
        assert cache.pool.refcount(shared) == 1     # the index's alone
        again.finish()
        _settled(cache)


def test_a_deferred_admission_leaves_the_refcounts_as_it_found_them():
    for kind in ("global", "window"):
        cache, _ = _cache(kind, n_pages=14)
        prompt = _prompt(12)
        first = _Seq(cache, prompt, 20)
        assert first.admit()
        first.prefill()     # its three prompt pages are in the index
        blocked = _Seq(cache, np.concatenate([prompt, _prompt(16, 3)]), 20)
        ref = cache.pool._ref.copy()
        reserved = cache.pool.stats()["reserved"]
        indexed = list(cache.index._entries.values())
        assert not blocked.admit()
        # the index was evicted for room before the admission gave up (a
        # page the first slot still holds stays resident under it): that
        # reference apart, every count is what it was, the hit's included
        for page in set(indexed) - set(cache.index._entries.values()):
            ref[page] -= 1
        np.testing.assert_array_equal(cache.pool._ref, ref)
        assert cache.pool.stats()["reserved"] == reserved
        assert blocked.held.pages == [] and blocked.held.reserve == 0
        first.decode()
        first.finish()
        assert blocked.admit()
        blocked.prefill()
        blocked.decode()
        blocked.finish()
        _settled(cache)


def test_trade_and_a_table_that_arrives_whole():
    cache, _ = _cache("global", n_pages=8)
    a, b = _Seq(cache, _prompt(8), 1), _Seq(cache, _prompt(8), 1)
    assert a.admit() and b.admit()
    own = b.held.pages[0]
    cache.trade(b.held, 0, a.held.pages[0])     # b holds a's first page
    assert b.held.pages[0] == a.held.pages[0]
    assert cache.pool.refcount(a.held.pages[0]) == 2
    assert cache.pool.refcount(own) == 0
    whole = Held()     # a migrated-in handoff: room first, then all at once
    assert not cache.make_room(3)
    assert cache.make_room(2)
    cache.take(whole, [], 0, 2, 2, 0)
    assert len(cache.table_of(whole)) == 2 and whole.cow == 0
    for held in (a.held, b.held, whole):
        cache.release(held)
    assert cache.pool.pages_in_use() == 0


@pytest.mark.parametrize("kind", ["global", "window"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_random_run_leaks_nothing(kind, seed):
    """Admissions (half of them over one of three shared prefixes, some
    of whole cached prompts), chunks, ticks, copy-on-write and releases in
    a random interleaving against a pool that defers some of them."""
    rng = np.random.RandomState(seed)
    cache, counts = _cache(kind, n_pages=32)
    prefixes = [_prompt(n, seed=10 + n) for n in (8, 12, 16)]
    prompts, live, done, deferred = [], [], 0, 0
    for step in range(1500):
        if live and rng.rand() < 0.8:
            seq = live[rng.randint(len(live))]
            if seq.done < len(seq.prompt):
                seq.chunk()
            elif seq.left:
                seq.tick()
            else:
                seq.finish()
                live.remove(seq)
                done += 1
            _no_page_twice(seq.held)
            continue
        if prompts and rng.rand() < 0.2:
            prompt = prompts[rng.randint(len(prompts))]    # a whole hit
        else:
            tail = _prompt(rng.randint(1, 14), seed=1000 + step)
            prompt = (np.concatenate([prefixes[rng.randint(3)], tail])
                      if rng.rand() < 0.5 else tail)
            prompts.append(prompt)
        seq = _Seq(cache, prompt, int(rng.randint(1, 3 * WINDOW)))
        if not seq.admit():
            deferred += 1
            continue
        live.append(seq)
        assert cache.held_pages(s.held for s in live) == len(
            {p for s in live for p in s.held.pages if p})
    for seq in live:
        seq.finish()
    assert done > 20 and deferred > 0, (done, deferred)
    assert counts["kv_window_unreserved_allocs"] == 0
    if kind == "window":
        assert counts["kv_window_pages_released"] > 0
    _settled(cache)
