"""Paged KV cache pins: token-exactness vs the one-shot decode op on mixed
greedy batches, prefix sharing (stored-once pages, copy-on-write on
divergence, refcounted release), Sarathi-style chunked-prefill fairness,
typed pool backpressure, and the zero-recompile steady state over the
chunked/shared/COW paths."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.serving import (CacheExhaustedError, DynamicBatcher,
                                GenerationEngine, LMSpec, Request)
from paddle_tpu.serving.generation import PAGED_CACHE_K, PAGED_CACHE_V

VOCAB, D, L, H, MAXLEN = 32, 16, 2, 2, 64

# weight cache: the LM startup compiles once per (seed, variant); scopes
# share the immutable weight arrays (decode never writes them — only the
# engines' own cache tensors are donated), which keeps this file's many
# fresh-engine tests off the startup-compile hot path
_WEIGHTS = {}


def _init_lm_scope(seed=7, **lm_kwargs):
    key = (seed, tuple(sorted(lm_kwargs.items())))
    exe = pt.Executor(pt.TPUPlace())
    if key not in _WEIGHTS:
        scope = pt.Scope()
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            prompt = layers.data("p_init", shape=[8], dtype="int64")
            models.transformer_lm_generate(
                prompt, vocab_size=VOCAB, d_model=D, n_layers=L,
                num_heads=H, max_len=MAXLEN, max_new_tokens=1, **lm_kwargs)
        startup.random_seed = seed
        exe.run(startup, scope=scope)
        _WEIGHTS[key] = {n: scope.get(n) for n in scope.keys()}
    scope = pt.Scope()
    for n, v in _WEIGHTS[key].items():
        scope.set(n, v)
    return scope, exe


def _reference_decode(scope, exe, prompts, max_new, **lm_kwargs):
    tp = prompts.shape[1]
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        prompt = layers.data(f"p_ref{tp}_{max_new}", shape=[tp],
                             dtype="int64")
        out_ids = models.transformer_lm_generate(
            prompt, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, max_new_tokens=max_new, **lm_kwargs)
    got, = exe.run(prog, feed={f"p_ref{tp}_{max_new}": prompts},
                   fetch_list=[out_ids], scope=scope)
    return np.asarray(got)


def _spec(**kw):
    return LMSpec(vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                  max_len=MAXLEN, **kw)


def _reference_each(scope, exe, prompts, max_new, **lm_kwargs):
    """The one-shot op's greedy decode of each prompt at its own length
    (the op has no lengths plane: one program per prompt length)."""
    return [_reference_decode(scope, exe, p[None], max_new, **lm_kwargs)[0]
            for p in prompts]


# ---------------------------------------------------------------------------
# token-exactness vs the one-shot decode op
# ---------------------------------------------------------------------------
class TestPagedParity:
    _LENS = [3, 5, 8, 11, 6, 14, 2, 16]  # mixed lengths, bs=8

    @pytest.fixture(scope="class")
    def mixed(self):
        scope, exe = _init_lm_scope(7)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, VOCAB, (n,)).astype("int64")
                   for n in self._LENS]
        return prompts, _reference_each(scope, exe, prompts, 5)

    @pytest.mark.parametrize("page_size", [4, 8, 16])
    def test_mixed_length_greedy_batch_matches_one_shot_decode(
            self, mixed, page_size):
        """THE acceptance pin: a bs>=8 mixed-length greedy workload
        through the engine emits exactly the one-shot
        ``transformer_stack_generate`` tokens (same weights, same
        prompts, same horizons), wherever the page boundaries fall:
        inside most prompts (4), inside the generation (8: 3+5, 6+5),
        hardly anywhere (16)."""
        prompts, want = mixed
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=8,
                               page_size=page_size,
                               prompt_buckets=(4, 8, 16))
        got = eng.generate_all(prompts, max_new_tokens=5)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        assert eng.metrics.counter("completed") == len(prompts)
        # every page released on finish (sharing retains prefix pages)
        assert eng.pool.pages_in_use() == len(eng.prefix_index)

    @pytest.mark.slow
    def test_gqa_rope_paged_parity(self):
        """Per-row rotary offsets in the paged chunk prefill (each batch
        row resumes at its own absolute position) vs the one-shot op."""
        kw = dict(use_rope=True, num_kv_heads=1)
        scope_r, exe = _init_lm_scope(5, **kw)
        scope_p, _ = _init_lm_scope(5, **kw)
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, VOCAB, (n,)).astype("int64")
                   for n in (5, 12)]
        eng = GenerationEngine(_spec(**kw), scope_p, slots=2, page_size=4,
                               prompt_buckets=(16,),
                               prefill_batch_buckets=(2,))
        got = eng.generate_all(prompts, max_new_tokens=4)
        for a, b in zip(_reference_each(scope_r, exe, prompts, 4, **kw),
                        got):
            np.testing.assert_array_equal(a, b)


def test_default_pool_holds_every_slot_at_full_context_plus_beam_spares():
    """n_pages when none is given: a full table a slot plus the scrap
    page; a beam engine adds a copy-on-write spare a slot and two a
    hypothesis."""
    s, k = 3, 4
    pmax = -(-MAXLEN // 16)
    eng = GenerationEngine(_spec(), slots=s, page_size=16)
    assert eng.pmax == pmax and eng.n_pages == s * pmax + 1
    beam = GenerationEngine(_spec(), slots=s, page_size=16, beam_width=k)
    assert beam.n_pages == s * pmax + 1 + s + 2 * k
    assert GenerationEngine(_spec(), slots=s, page_size=16, beam_width=k,
                            n_pages=7).n_pages == 7


def test_there_is_one_cache_layout_and_no_switch_for_it():
    with pytest.raises(TypeError, match="kv_cache"):
        GenerationEngine(_spec(), slots=2, **{"kv_cache": "paged"})


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------
class TestPrefixSharing:
    def test_shared_system_prompt_stored_once_token_exact(self):
        """Three requests share a 2-page system prompt: tokens match the
        sharing-disabled engine exactly, prefix_hit_tokens counts the
        skipped prefill, live sharers hold the SAME physical pages
        (sub-linear pool growth), and finish releases refcounts down to
        the index-retained prefix."""
        scope_a, _ = _init_lm_scope(7)
        scope_b, _ = _init_lm_scope(7)
        rng = np.random.RandomState(4)
        ps = 8
        sys_prompt = rng.randint(0, VOCAB, (2 * ps,)).astype("int64")
        tails = [rng.randint(0, VOCAB, (n,)).astype("int64")
                 for n in (3, 5, 7)]
        prompts = [np.concatenate([sys_prompt, t]) for t in tails]
        plain = GenerationEngine(_spec(), scope_a, slots=4, page_size=ps,
                                 prefix_sharing=False,
                                 prompt_buckets=(8, 16, 32))
        shared = GenerationEngine(_spec(), scope_b, slots=4, page_size=ps,
                                  prompt_buckets=(8, 16, 32))
        ref = plain.generate_all(prompts, max_new_tokens=4)
        assert plain.metrics.counter("prefix_hit_tokens") == 0

        # first request populates the index...
        got0 = shared.generate_all([prompts[0]], max_new_tokens=4)
        np.testing.assert_array_equal(got0[0], ref[0])
        assert shared.metrics.counter("prefix_hit_tokens") == 0
        base_pages = shared.pool.pages_in_use()
        # ...the next two (admitted TOGETHER) share its system pages
        got12 = shared.generate_all(prompts[1:], max_new_tokens=4)
        np.testing.assert_array_equal(got12[0], ref[1])
        np.testing.assert_array_equal(got12[1], ref[2])
        assert shared.metrics.counter("prefix_hit_tokens") == 2 * 2 * ps
        assert shared.metrics.counter("prefix_hits") == 2
        # stored once: two extra sequences of 3 pages each grew the pool
        # by their UNSHARED pages only
        peak = shared.metrics.snapshot()["gauges"]["mem/kv_pages_in_use"]
        assert peak <= base_pages + 2 * 2  # tail page + one gen page each
        # refcounted release: only index-held prefix pages stay resident
        assert shared.pool.pages_in_use() == len(shared.prefix_index)
        assert shared.pool.stats()["shared"] == 0  # no live sharers left

    @pytest.mark.parametrize("first_new", [1, 9],
                             ids=["holder leaves first", "holder stays"])
    def test_a_cold_shared_prompt_is_held_and_prefilled_once(self, first_new):
        """Three requests over one COLD 5-page system prompt admitted
        together (chunk = 2 pages, so it streams through ``prefill_tick``):
        the later two take the first one's pages into their tables, the
        chunks are run by whichever slot's turn comes (pages register as
        they fill and the others move past them), and every answer is the
        sharing-disabled engine's, also when the first holder finishes
        and lets go while the others still prefill."""
        scope_a, _ = _init_lm_scope(7)
        scope_b, _ = _init_lm_scope(7)
        rng = np.random.RandomState(5)
        ps = 4
        sys_prompt = rng.randint(0, VOCAB, (5 * ps,)).astype("int64")
        prompts = [np.concatenate(
            [sys_prompt, rng.randint(0, VOCAB, (n,)).astype("int64")])
            for n in (2, 9, 13)]
        new = [first_new, 6, 6]
        kw = dict(slots=4, page_size=ps, prefill_chunk=2 * ps,
                  prompt_buckets=(4, 8))
        plain = GenerationEngine(_spec(), scope_a, prefix_sharing=False,
                                 **kw)
        ref = [plain.generate_all([p], max_new_tokens=n)[0]
               for p, n in zip(prompts, new)]
        eng = GenerationEngine(_spec(), scope_b, **kw)
        reqs = [Request({"prompt": p}, {"max_new_tokens": n}, None)
                for p, n in zip(prompts, new)]
        eng.admit(reqs)
        assert eng.active == 3
        tables = [eng._slots[i].held[0].pages for i in range(3)]
        assert tables[0][:5] == tables[1][:5] == tables[2][:5]
        eng._drive([])
        for req, want in zip(reqs, ref):
            np.testing.assert_array_equal(req.future.result(timeout=1), want)
        chunks = eng.metrics.counter("prefill_chunks")
        assert chunks <= plain.metrics.counter("prefill_chunks") - 4
        assert eng.metrics.counter("prefix_hit_tokens") >= 2 * 5 * ps - 2 * ps
        assert eng.pool.pages_in_use() == len(eng.prefix_index)

    def test_full_prompt_hit_takes_copy_on_write(self):
        """A repeated IDENTICAL prompt full-hits the prefix cache: zero
        prefill tokens, identical output, and the first generated token
        triggers exactly the copy-on-write path (the shared tail page is
        about to be written) — pinned via kv_cow_copies and the cached
        page's survival for a THIRD identical request."""
        scope, _ = _init_lm_scope(7)
        rng = np.random.RandomState(6)
        prompt = rng.randint(0, VOCAB, (11,)).astype("int64")  # 1.375 pages
        eng = GenerationEngine(_spec(), scope, slots=2, page_size=8,
                               prompt_buckets=(8, 16))
        first = eng.generate_all([prompt], max_new_tokens=4)[0]
        assert eng.metrics.counter("kv_cow_copies") == 0
        prefills0 = eng.metrics.counter("prefills")
        second = eng.generate_all([prompt], max_new_tokens=4)[0]
        np.testing.assert_array_equal(second, first)
        # full hit: the whole prompt was served from cached pages
        assert eng.metrics.counter("prefix_hit_tokens") == prompt.size
        assert eng.metrics.counter("prefills") == prefills0  # none ran
        assert eng.metrics.counter("kv_cow_copies") >= 1
        third = eng.generate_all([prompt], max_new_tokens=4)[0]
        np.testing.assert_array_equal(third, first)
        assert eng.metrics.counter("prefix_hit_tokens") == 2 * prompt.size

    @pytest.mark.slow
    def test_swap_params_invalidates_prefix_cache(self):
        """Rolling weight updates drop cached prefixes — K/V computed
        with the old weights must never serve the new ones."""
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=2, page_size=8)
        prompt = np.arange(10, dtype=np.int64) % VOCAB
        eng.generate_all([prompt], max_new_tokens=3)
        assert len(eng.prefix_index) > 0
        eng.swap_params(_init_lm_scope(8)[0])
        assert len(eng.prefix_index) == 0
        assert eng.pool.pages_in_use() == 0


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------
class TestChunkedPrefill:
    def test_decode_ticks_interleave_with_long_prefill(self):
        """THE fairness pin: while a near-Tmax prompt prefills, the
        in-flight stream keeps emitting a token per tick — per-tick
        prefill work is bounded by prefill_chunk, so decode latency
        cannot spike by a whole-prompt prefill."""
        scope, exe = _init_lm_scope(7)
        rng = np.random.RandomState(9)
        short = rng.randint(0, VOCAB, (6,)).astype("int64")
        long_p = rng.randint(0, VOCAB, (48,)).astype("int64")  # 6 chunks
        ref_short = _reference_decode(scope, exe, short[None], 10)[0]
        ref_long = _reference_decode(scope, exe, long_p[None], 4)[0]
        eng = GenerationEngine(_spec(), scope, slots=2, page_size=8,
                               prefill_chunk=8, prompt_buckets=(8, 16))
        r_short = Request({"prompt": short}, {"max_new_tokens": 10}, None)
        r_long = Request({"prompt": long_p}, {"max_new_tokens": 4}, None)
        eng.admit([r_short])
        eng.decode_tick()
        eng.admit([r_long])  # enters the chunked-prefill state
        short_progress = []
        while eng.prefilling:  # the long prompt is streaming in
            eng.prefill_tick()
            eng.decode_tick()
            st = eng._slots[[i for i in range(eng.slots)
                             if eng._slots[i] is not None
                             and eng._slots[i].state == "decode"][0]]
            short_progress.append(len(st.generated))
        # every interleaved tick advanced the short stream by one token
        assert short_progress == sorted(short_progress)
        assert len(short_progress) >= 5  # 48/8 = 6 chunks ran
        assert short_progress[-1] > short_progress[0]
        while eng.active:
            eng.prefill_tick()
            eng.decode_tick()
        np.testing.assert_array_equal(r_short.future.result(1), ref_short)
        np.testing.assert_array_equal(r_long.future.result(1), ref_long)
        # per-chunk latency is the bounded unit of prefill work
        snap = eng.metrics.snapshot()
        assert snap["counters"]["prefill_chunks"] == 6
        assert "prefill_chunk_ms" in snap["latency"]


# ---------------------------------------------------------------------------
# pool backpressure
# ---------------------------------------------------------------------------
class TestBackpressure:
    def test_request_that_can_never_fit_fails_typed(self):
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=2, page_size=8,
                               n_pages=3, prompt_buckets=(8, 16, 32))
        big = Request({"prompt": np.arange(30, dtype=np.int64) % VOCAB},
                      {"max_new_tokens": 4}, None)  # needs 5 of 2 pages
        assert eng.admit([big]) == 0
        with pytest.raises(CacheExhaustedError) as ei:
            big.future.result(timeout=1)
        assert ei.value.pages_needed == 5 and ei.value.pages_free == 2
        assert eng.free_slots == 2  # no slot leaked
        # a fitting request still serves
        small = eng.generate_all([np.arange(6, dtype=np.int64)],
                                 max_new_tokens=2)
        assert small[0].size == 8

    @pytest.mark.slow
    def test_transient_pressure_defers_not_fails(self):
        """Two requests that EACH fit but not TOGETHER: the second is
        deferred until the first finishes — backpressure, not a
        mid-decode failure."""
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=2, page_size=8,
                               n_pages=3, prefix_sharing=False,
                               prompt_buckets=(8, 16))
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, VOCAB, (10,)).astype("int64")
                   for _ in range(2)]  # 2 pages each, pool holds 2
        got = eng.generate_all(prompts, max_new_tokens=4)
        assert all(g.size == 14 for g in got)
        assert eng.metrics.counter("admission_deferred") >= 1
        assert eng.metrics.counter("cache_exhausted") == 0
        assert eng.pool.pages_in_use() == 0

    def test_deferred_surfaces_through_serve_step(self):
        """The server path: pool-blocked requests wait in the engine's
        deferred line while serve_step keeps decode moving; everyone
        completes once pages free up."""
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=3, page_size=8,
                               n_pages=3, prefix_sharing=False,
                               prompt_buckets=(8, 16))
        batcher = DynamicBatcher(buckets=(1, 2, 4), max_wait_ms=1)
        rng = np.random.RandomState(5)
        futs = [batcher.submit(
            {"prompt": rng.randint(0, VOCAB, (9,)).astype("int64")},
            max_new_tokens=3) for _ in range(3)]
        for _ in range(200):
            eng.serve_step(batcher, idle_wait_s=0)
            if all(f.done() for f in futs):
                break
        for f in futs:
            assert f.result(timeout=1).size == 12
        assert eng.metrics.counter("admission_deferred") >= 1


# ---------------------------------------------------------------------------
# compile-cache steady state
# ---------------------------------------------------------------------------
class TestZeroRecompile:
    @pytest.mark.slow
    def test_paged_zero_recompiles_incl_chunked_shared_cow(self):
        """Warmup covers every paged shape — chunk widths x batch
        buckets, decode, AND the copy-on-write page copy — so a workload
        exercising chunked prefill, prefix hits, and COW adds zero
        compile-cache misses."""
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=4, page_size=8,
                               prefill_chunk=16, prompt_buckets=(8, 16),
                               prefill_batch_buckets=(1, 2, 4))
        eng.warmup()
        misses0 = eng.cache_stats()["misses"]
        rng = np.random.RandomState(31)
        prompts = [rng.randint(0, VOCAB, (rng.randint(2, 15),))
                   .astype("int64") for _ in range(8)]
        prompts.append(rng.randint(0, VOCAB, (40,)).astype("int64"))
        got = eng.generate_all(prompts, max_new_tokens=5)
        # the chunked long prompt decodes token-exact (vs the one-shot
        # reference) straight off the streaming-prefill pages
        ref = _reference_decode(scope, _init_lm_scope(7)[1],
                                prompts[-1][None], 5)[0]
        np.testing.assert_array_equal(got[-1], ref)
        eng.generate_all([prompts[0]], max_new_tokens=5)  # full hit + COW
        stats = eng.cache_stats()
        assert stats["misses"] == misses0, stats
        assert stats["hits"] > 0
        assert eng.metrics.counter("prefill_chunks") >= 3
        assert eng.metrics.counter("kv_cow_copies") >= 1
        assert eng.metrics.counter("prefix_hit_tokens") > 0


# ---------------------------------------------------------------------------
# the pools as the layer loop's in-place carry
# ---------------------------------------------------------------------------
def _pool_sized_ops(hlo, pool_shape):
    """``copy`` / ``dynamic-slice`` instructions of the optimized HLO whose
    result has the pool's or one layer-pool's element count."""
    import re

    sizes = {int(np.prod(pool_shape)), int(np.prod(pool_shape[1:]))}
    found = []
    for m in re.finditer(r"= \w+\[([\d,]+)\]\S* (copy|dynamic-slice)\(",
                         hlo):
        if int(np.prod([int(d) for d in m.group(1).split(",")])) in sizes:
            found.append(m.group(0))
    return found


class TestInPlaceStep:
    @pytest.mark.parametrize("which", ["decode", "prefill"])
    def test_step_temporaries_do_not_scale_with_the_pool(self, which):
        """THE structural pin: with n_pages far above the pages in use,
        the compiled step's temporaries stay under half of ONE pool (the
        scanned-over form took more than two: a layer slice, its
        transposed twin, the restacked output and a whole-pool copy),
        and no copy / dynamic-slice of pool or layer-pool size is left."""
        scope, _ = _init_lm_scope(7)
        eng = GenerationEngine(_spec(), scope, slots=2, page_size=8,
                               n_pages=512, prefill_chunk=16,
                               prompt_buckets=(16,),
                               prefill_batch_buckets=(1,))
        eng.warmup()
        prog = (eng._decode_prog if which == "decode"
                else eng._prefill_prog(16))[0]
        aots = [c.aot for key, c in eng.executor._cache.items()
                if key[0] == id(prog)]
        assert len(aots) == 1
        pool = eng.scope.get(PAGED_CACHE_K)
        assert pool.shape[1] == 512
        stats = aots[0].memory_analysis()
        if stats is not None and hasattr(stats, "temp_size_in_bytes"):
            assert stats.temp_size_in_bytes < pool.nbytes / 2, (
                stats.temp_size_in_bytes, pool.nbytes)
        assert not _pool_sized_ops(aots[0].as_text(), pool.shape)

    @pytest.mark.parametrize("prefill_chunk", [16, 8])
    def test_ticks_and_chunk_touch_only_the_written_cells(
            self, prefill_chunk):
        """In-place safety: the prefill of an 11-token prompt (one chunk
        of 16, or two of 8) and two consecutive decode ticks on pools
        pre-filled with a seeded pattern change ONLY the (layer, page,
        row) cells of the tokens they cached — every other element is
        bitwise the pattern (scrap page 0 apart: vacant slots and pad
        tokens write there) — the written rows are the K/V the one-shot
        family's prefill captures for every layer, scattered by the
        block table, and the tokens are the one-shot op's although every
        page still holds the pattern beyond the rows written."""
        import jax.numpy as jnp

        from paddle_tpu.ops import pipeline_ops

        scope_r, exe = _init_lm_scope(7)
        scope_p, _ = _init_lm_scope(7)
        paged = GenerationEngine(_spec(), scope_p, slots=2, page_size=8,
                                 n_pages=40, prefill_chunk=prefill_chunk,
                                 prompt_buckets=(16,), prefix_sharing=False)
        rng = np.random.RandomState(25)
        shape = paged.scope.get(PAGED_CACHE_K).shape
        assert shape == (L, 40, 8, D)  # [L, N, ps, Hkv*dh]
        pattern = {n: rng.standard_normal(shape).astype("float32")
                   for n in (PAGED_CACHE_K, PAGED_CACHE_V)}
        with paged.executor.device_ctx():
            for n, v in pattern.items():
                paged.scope.set(n, jnp.asarray(v))
        prompt = rng.randint(0, VOCAB, (11,)).astype("int64")
        req = Request({"prompt": prompt}, {"max_new_tokens": 8}, None)
        paged.admit([req])  # 11 <= 16: ONE prefill chunk; > 8: it streams
        chunks = 0
        while paged.prefilling:
            chunks += paged.prefill_tick()
        assert chunks == (0 if prefill_chunk == 16 else 2)
        paged.decode_tick()
        paged.decode_tick()
        st_p = next(st for st in paged._slots if st is not None)
        want_ids = _reference_decode(scope_r, exe, prompt[None], 3)[0]
        assert st_p.generated == want_ids[len(prompt):].tolist()
        written = len(prompt) + 2  # the prompt + one row per tick
        pages = list(st_p.held[0].pages)
        assert 0 not in pages and len(pages) >= -(-written // 8)
        # every layer's K/V over prompt + the two decoded tokens, from
        # the one-shot family's prefill: [L, 1, Hkv, T, dh]
        blk = _spec().block
        params = {key: jnp.asarray(scope_r.get(f"lm_stack.stack_{key}"))
                  for key in blk.stack_slots().values()}
        x = (jnp.asarray(scope_r.get("tok_emb"))[want_ids[:written]]
             + jnp.asarray(scope_r.get("pos_emb"))[:written])[None]
        _, (ks, vs) = pipeline_ops._prefill(blk, params, x, 1, written)
        for pname, kv in ((PAGED_CACHE_K, ks), (PAGED_CACHE_V, vs)):
            got = np.asarray(paged.scope.get(pname))
            # [L, Hkv, T, dh] -> one [Hkv*dh] row per position
            rows = np.asarray(kv)[:, 0].transpose(0, 2, 1, 3).reshape(
                L, -1, D)
            want = pattern[pname].copy()
            mask = np.zeros(shape[:3], bool)
            for pos in range(written):
                want[:, pages[pos // 8], pos % 8] = rows[:, pos]
                mask[:, pages[pos // 8], pos % 8] = True
            np.testing.assert_allclose(got[mask], want[mask], rtol=1e-5,
                                       atol=1e-6)
            untouched = ~mask
            untouched[:, 0] = False  # the scrap page
            np.testing.assert_array_equal(got[untouched],
                                          pattern[pname][untouched])
