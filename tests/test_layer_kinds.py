"""Layer kinds in one stack (``LMSpec(layer_pattern=, window=)``: full
attention without positions / a sliding window with RoPE), grouped queries
at an explicit ``head_dim``, ReGLU experts routed from the attention's
input, and the KV cache held BY KIND — at a tiny size on the CPU against
the plain float32 reference in ``benchmark/families/window_moe_lm.py``:
d 64, 4 query / 2 KV heads of 32 (so H*dh != d), two periods of [global +
NoPE, window + RoPE x 3], window 8, pages of 4, 8 experts top-2, through
the normal path (``transformer_lm(spec=)`` / ``GenerationEngine(spec, ..)``).

Tolerance: program and reference run the same float32 arithmetic in a
different order; observed <= 2e-6 on log-probs, the bound is 2e-5. Every
wrong model of the reference's ``VARIANTS`` lies >= 2.5e-3 away."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import window_moe_lm as fam
from paddle_tpu import layers, models
from paddle_tpu.kernels.flash_attention import reference_attention
from paddle_tpu.kernels.paged_attention import paged_attention_decode
from paddle_tpu.lm_spec import Block, BlockNotSupportedError, LMSpec
from paddle_tpu.ops.pipeline_ops import _gather_pages
from paddle_tpu.serving import GenerationEngine

F32_TOL = 2e-5
WINDOW, PS = 8, 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config():
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "smallthinker-tiny.json")) as f:
        return json.load(f)


ENGINE = {"slots": 4, "page_size": PS, "n_pages": 120, "n_pages_window": 40,
          "max_len": 64, "prompt_buckets": [4, 8],
          "prefill_batch_buckets": [1, 2], "prefill_chunk": 8}


@pytest.fixture
def no_amp():
    pt.set_amp(False)


def _engine(seed=3, **engine):
    eng, _ = fam.build_engine(tiny_config(), {"engine": {**ENGINE, **engine}},
                              seed, beam_width=4)
    return eng


def _window_kind(eng):
    """The window kind's pool and prefix index: the second of the
    engine's caches (``eng.pool`` / ``eng.prefix_index`` are the first's)."""
    full, window = eng._caches
    assert (full.name, window.name) == ("global", "window")
    return window.pool, window.index


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 500, size=n)


def _served_error(eng, prompt, new, variant=""):
    """Largest |served top-4 log-prob - reference's| over every chunk end
    and decode step of one request, and the emitted sequence."""
    config = tiny_config()
    calls, out = fam.served_logprobs(eng, prompt, new)
    ref = np.asarray(jax.nn.log_softmax(fam.reference_logits(
        config, fam.weights_of(None, eng.scope), out, variant=variant), -1))
    return max(float(np.abs(ref[p][i] - v).max()) for p, v, i in calls), out


def _counters(eng):
    return eng.metrics.snapshot()["counters"]


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------
def test_spec_carries_kinds_head_dim_and_expert_options_in_the_attrs():
    spec = fam.spec_of(tiny_config())
    blk = spec.block
    assert blk.kinds == ((False, False), (True, True), (True, True),
                         (True, True))
    assert (spec.layers_of(False), spec.layers_of(True)) == (2, 6)
    attrs = json.loads(json.dumps(blk.attrs()))     # as a saved program's
    assert attrs["head_dim"] == 32 and attrs["window"] == WINDOW
    assert attrs["expert_act"] == "relu"
    assert attrs["router_input"] == "attn_input"
    assert Block.from_attrs(attrs) == blk
    planes = {k: shape for _, k, shape, _ in spec.stack_planes()}
    assert planes["qkv_w"] == [64, 4 * 32 + 2 * 2 * 32]
    assert planes["out_w"] == [4 * 32, 64]


def test_one_kind_specs_keep_the_attrs_they_had():
    gpt2 = LMSpec(vocab_size=32, d_model=16, n_layers=2, num_heads=2)
    assert gpt2.block.attrs() == {"num_heads": 2, "num_kv_heads": None,
                                  "use_rope": False}
    assert gpt2.head_dim == 8 and gpt2.block.head_dim is None
    assert gpt2.block.kinds is None and not gpt2.block.has_window


@pytest.mark.parametrize("why,kw", [
    ("an unknown kind", dict(layer_pattern=("full", "window"))),
    ("a pattern without rope positions", dict(
        layer_pattern=("full+nope",), use_rope=False)),
    ("a window layer without a window", dict(
        layer_pattern=("full+nope", "window+rope"))),
    ("no full-attention layer", dict(layer_pattern=("window+rope",),
                                     window=8)),
    ("layers that are not whole periods", dict(
        layer_pattern=("full+nope", "window+rope", "window+rope"), window=8)),
    ("heads that do not divide the width", dict(num_heads=3)),
])
def test_spec_refuses(why, kw):
    base = dict(vocab_size=32, d_model=16, n_layers=4, num_heads=2,
                use_rope=True)
    with pytest.raises(ValueError):
        LMSpec(**{**base, **kw})


# ---------------------------------------------------------------------------
# the engine against the reference's full forward
# ---------------------------------------------------------------------------
def test_chunked_prefill_and_decode_to_three_windows_match_the_reference(
        no_amp):
    eng = _engine()
    err, out = _served_error(eng, _prompt(27), 3 * WINDOW)
    assert out.size == 27 + 3 * WINDOW and err < F32_TOL
    c = _counters(eng)
    assert c["kv_window_pages_released"] > 0
    assert c.get("kv_window_unreserved_allocs", 0) == 0
    assert c["moe_dropped_tokens"] == 0


@pytest.mark.parametrize("variant", sorted(fam.VARIANTS))
def test_each_wrong_model_is_told_from_the_right_one(no_amp, variant):
    """The check is tight enough: no window, RoPE on the global layers,
    silu for relu, the router after attention, KV head n % Hkv, bfloat16
    where float32 is stated — each lies far outside the tolerance."""
    err, _ = _served_error(_engine(), _prompt(27), WINDOW, variant=variant)
    assert err > 100 * F32_TOL


def test_one_shot_and_chunked_prefill_agree(no_amp):
    prompt = _prompt(8, seed=4)         # fits one chunk; or two of four
    outs = [_engine(prefill_chunk=c, prompt_buckets=[4, 8]).generate_all(
        [prompt], max_new_tokens=12)[0] for c in (8, 4)]
    np.testing.assert_array_equal(*outs)


def test_prefix_hit_longer_than_the_window_serves_both_kinds(no_amp):
    """The second request shares 20 tokens (5 pages, 2.5 windows) with the
    first: the full-attention kind takes all five cached pages, the window
    kind only those its next query can reach."""
    eng = _engine()
    shared = _prompt(20, seed=1)
    eng.generate_all([np.concatenate([shared, _prompt(5, seed=2)])],
                     max_new_tokens=4)
    before = _counters(eng)
    err, _ = _served_error(eng, np.concatenate([shared, _prompt(7, seed=3)]),
                           2 * WINDOW)
    after = _counters(eng)
    assert (after["prefix_hit_tokens"]
            - before.get("prefix_hit_tokens", 0)) == 20
    assert err < F32_TOL


def test_full_prompt_hit_copies_the_shared_page_of_both_kinds(no_amp):
    """The same 10-token prompt again: every page is cached, the tail page
    (2 tokens) is shared and about to be written: one copy a kind, and the
    cached pages stay what they were."""
    eng = _engine()
    prompt = _prompt(10, seed=5)
    first = eng.generate_all([prompt], max_new_tokens=6)[0]
    before = _counters(eng)
    err, out = _served_error(eng, prompt, 6)
    after = _counters(eng)
    assert after["kv_cow_copies"] - before.get("kv_cow_copies", 0) == 2
    assert (after["prefix_hit_tokens"]
            - before.get("prefix_hit_tokens", 0)) == 10
    assert err < F32_TOL
    np.testing.assert_array_equal(out, first)
    # and a third time: the index's pages were not written by the second
    np.testing.assert_array_equal(
        eng.generate_all([prompt], max_new_tokens=6)[0], first)


# ---------------------------------------------------------------------------
# the cache's invariants
# ---------------------------------------------------------------------------
def _drive(eng, reqs, watch):
    """The engine loop of ``generate_all`` with ``watch(eng)`` after every
    pass."""
    from paddle_tpu.serving.batcher import Request

    reqs = [Request({"prompt": p}, {"max_new_tokens": n, "eos_id": None},
                    None) for p, n in reqs]
    pending = list(reqs)
    while pending or eng.active or eng._deferred:
        if pending and eng.free_slots and not eng._deferred:
            k = min(len(pending), eng.free_slots)
            eng.admit(pending[:k])
            pending = pending[k:]
        eng._admit_deferred()
        eng.prefill_tick()
        eng.decode_tick()
        watch(eng)
    return [r.future.result(timeout=0.1) for r in reqs]


def test_the_held_page_counters_equal_a_recount_at_every_tick(no_amp):
    """``kv_pages_held_*`` are recounted only after a pool changed hands
    (``PagePool.changes``): every tick's increment is still the distinct
    pages the slots hold at that tick, through admissions, shared
    prefixes, window releases and finishes."""
    eng = _engine()
    run, seen = eng._run_decode, []

    def counted():
        want = {cache.name: len({p for st in eng._slots if st is not None
                                 for p in st.held[i].pages if p})
                for i, cache in enumerate(eng._caches)}
        before = _counters(eng)
        out = run()
        after = _counters(eng)
        seen.append(all(
            after[f"kv_pages_held_{name}"]
            - before.get(f"kv_pages_held_{name}", 0) == n
            for name, n in want.items()))
        return out

    eng._run_decode = counted
    shared = _prompt(3 * PS, seed=3)
    _drive(eng, [(np.concatenate([shared, _prompt(5, seed=4)]), 2 * WINDOW),
                 (np.concatenate([shared, _prompt(9, seed=5)]), WINDOW),
                 (_prompt(7, seed=6), 3)], lambda eng: None)
    assert len(seen) > 2 * WINDOW and all(seen)


def test_a_long_slot_holds_a_window_of_pages_and_never_a_shared_write(
        no_amp):
    eng = _engine()
    wpool, windex = _window_kind(eng)
    held, writes_shared = [], []

    def watch(eng):
        for slot, st in enumerate(eng._slots):
            if st is None or st.state != "decode":
                continue
            w = st.held[1]
            held.append(sum(1 for p in w.pages if p))
            entry = int(eng._pos[slot]) // PS
            # the page the NEXT tick writes is copied first if shared
            if entry < len(w.pages) and w.pages[entry]:
                writes_shared.append(
                    wpool.refcount(w.pages[entry]) > 1
                    and int(eng._pos[slot]) % PS != 0 and w.cow == 0
                    and st.shared_tokens != st.prompt.size)
    _drive(eng, [(_prompt(6, seed=7), 3 * WINDOW + 6)], watch)
    assert max(held) <= WINDOW // PS + 2
    assert not any(writes_shared)
    c = _counters(eng)
    assert c.get("kv_window_unreserved_allocs", 0) == 0
    # what the slot held, tick by tick: a window of the one kind, every
    # page of the other — which is what one table for all layers holds
    assert c["kv_pages_held_window"] < c["kv_pages_held_global"]
    assert c["kv_pages_held_global"] == c["kv_pages_uniform_equiv"]
    # everything came back: only the prefix indexes hold pages now
    assert wpool.stats()["reserved"] == 0
    assert eng.pool.stats()["reserved"] == 0
    assert wpool.pages_in_use() == len(windex)
    assert eng.pool.pages_in_use() == len(eng.prefix_index)


@pytest.mark.parametrize("walks", [True, False], ids=["walk", "gather"])
def test_prefill_units_count_the_pages_their_chunk_walk_reads(no_amp, walks):
    """``prefill_attn_pages_read_<kind>`` / ``prefill_attn_table_pages_<kind>``,
    a prefill unit: the pages in reach of the unit's real rows by the chunk
    walk's own rule (a window layer from its window's page) against rows x
    table width, where the engine's prefill programs took the kernel (the
    ops' predicate, here answered for it: no chip); an engine whose
    programs gather counts neither."""
    eng = _engine()
    assert eng._chunk_walks(8) is False     # the CPU mesh: the gathered form
    eng._chunk_walk = {tc: walks for tc in (4, 8)}
    before = _counters(eng)
    # 19 tokens: chunks at 0 and 8 (8 tokens each), then 3 tokens at 16
    eng.generate_all([_prompt(19, seed=5)], max_new_tokens=2)
    c = {k: v - before.get(k, 0) for k, v in _counters(eng).items()
         if k.startswith("prefill_attn")}
    if not walks:
        assert not c
        return
    units = _counters(eng)["prefill_feed_host_arrays"] \
        - before.get("prefill_feed_host_arrays", 0)
    assert units == 3
    width = ENGINE["max_len"] // PS
    assert c["prefill_attn_table_pages_global"] == units * width
    assert c["prefill_attn_table_pages_window"] == units * width
    # full layers: pages 0 .. the chunk's last: 2 + 4 + 5
    assert c["prefill_attn_pages_read_global"] == 11
    # window 8, pages of 4: from (start - 7) // 4: 2, 4 - 0, 5 - 2
    assert c["prefill_attn_pages_read_window"] == 9


def test_releasing_behind_the_window_never_frees_an_indexed_page(no_amp):
    """A 24-token prompt leaves six window pages to the prefix index as
    its chunks complete; the slot moves past them while it decodes, and
    every one of them is still allocated — and still right: a second
    request that hits the prefix reads them."""
    eng = _engine()
    prompt = _prompt(24, seed=8)
    eng.generate_all([prompt], max_new_tokens=2 * WINDOW)
    wpool, windex = _window_kind(eng)
    indexed = list(windex._entries.values())
    assert len(indexed) >= 6
    assert all(wpool.refcount(p) == 1 for p in indexed)
    assert _counters(eng)["kv_window_pages_released"] >= 6
    err, _ = _served_error(eng, np.concatenate([prompt, _prompt(3, seed=9)]),
                           4)
    assert err < F32_TOL


@pytest.mark.parametrize("short,counter", [
    (dict(n_pages_window=14), "admit_deferred_window"),
    (dict(n_pages=14), "admit_deferred_global"),
])
def test_either_pool_defers_an_admission_and_leaks_nothing(no_amp, short,
                                                           counter):
    """Three requests of 12 + 20 tokens (8 pages each of the global kind,
    a hold of 8 of the window kind) against a pool of 13 usable pages of
    one kind: the second waits for the first, deferred by THAT kind."""
    eng = _engine(**short)
    reqs = [(_prompt(12, seed=s), 20) for s in (10, 11, 12)]
    outs = _drive(eng, reqs, lambda eng: None)
    c = _counters(eng)
    assert c[counter] > 0
    other = ({"admit_deferred_window", "admit_deferred_global"}
             - {counter}).pop()
    assert c.get(other, 0) == 0
    assert c.get("kv_window_unreserved_allocs", 0) == 0
    wpool, windex = _window_kind(eng)
    assert wpool.stats()["reserved"] == eng.pool.stats()["reserved"] == 0
    assert wpool.pages_in_use() == len(windex)
    assert eng.pool.pages_in_use() == len(eng.prefix_index)
    # deferral changes when a request runs, not what it says
    alone = _engine()
    for (p, n), got in zip(reqs, outs):
        np.testing.assert_array_equal(
            got, alone.generate_all([p], max_new_tokens=n)[0])


def test_a_request_no_window_pool_can_hold_fails_typed(no_amp):
    from paddle_tpu.serving.errors import CacheExhaustedError

    eng = _engine(n_pages_window=5)
    with pytest.raises(CacheExhaustedError, match="window-layer pages"):
        eng.generate_all([_prompt(30, seed=13)], max_new_tokens=20)


# ---------------------------------------------------------------------------
# the decode kernel: grouped queries x window x page boundaries
# ---------------------------------------------------------------------------
K_L, K_N, K_PS, K_P = 2, 24, 16, 6
K_ROWS = [[3, 5, 9, 2, 11, 0], [0, 0, 7, 8, 4, 0], [6], []]
K_LENGTHS = [4 * K_PS + 3, 4 * K_PS + 16, 5, 1]


@pytest.mark.parametrize("window", [None, 2 * K_PS, K_PS + 5, K_PS, 1])
@pytest.mark.parametrize("dtype,heads,kv_heads,d_head", [
    pytest.param(jnp.float32, 4, 2, 64, id="f32-4/2x64"),
    pytest.param(jnp.bfloat16, 28, 4, 128, id="bf16-28/4x128"),
    pytest.param(jnp.float32, 16, 16, 64, id="f32-16/16x64"),
])
def test_kernel_is_reference_attention_for_groups_and_windows(
        dtype, heads, kv_heads, d_head, window):
    """Interpret mode against ``reference_attention`` over the gathered
    pages: rows whose window starts inside a page, on a page boundary,
    beyond pages the table no longer holds (entry 0), a row shorter than
    the window and a vacant slot."""
    rng = np.random.default_rng(0)
    width = kv_heads * d_head
    ck, cv = (jnp.asarray(rng.standard_normal((K_L, K_N, K_PS, width)), dtype)
              for _ in range(2))
    table = np.zeros((len(K_ROWS), K_P), np.int32)
    for s, r in enumerate(K_ROWS):
        table[s, :len(r)] = r
    table, lengths = jnp.asarray(table), jnp.asarray(K_LENGTHS, jnp.int32)
    q = jnp.asarray(2 * rng.standard_normal((len(K_ROWS), heads, d_head)),
                    dtype)
    got = paged_attention_decode(q, ck, cv, jnp.int32(1), table, lengths,
                                 interpret=True, window=window)
    kw = {} if window is None else dict(window=window)
    want = reference_attention(
        q[:, :, None, :], _gather_pages(ck, 1, table, kv_heads),
        _gather_pages(cv, 1, table, kv_heads), lengths=lengths, **kw)
    want = want.transpose(0, 2, 1, 3).reshape(len(K_ROWS), -1)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=0, atol=tol)


def test_window_mask_of_the_reference_counts_positions_not_rows():
    """``k_pos0``: the keys are a slice of the context starting at that
    position; a window of 3 under a block-causal mask."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 2, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 10, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 10, 8)), jnp.float32)
    q0 = jnp.asarray([7])
    whole = reference_attention(q, k, v, causal=True, q_pos0=q0, window=3,
                                k_pos0=jnp.asarray([0]))
    part = reference_attention(q, k[:, :, 4:], v[:, :, 4:], causal=True,
                               q_pos0=q0, window=3, k_pos0=jnp.asarray([4]))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(part),
                               atol=1e-6)
    # query 7 sees keys 5, 6, 7 only
    s = jnp.einsum("d,kd->k", q[0, 0, 0], k[0, 0, 5:8]) / np.sqrt(8)
    want = jax.nn.softmax(s) @ v[0, 0, 5:8]
    np.testing.assert_allclose(np.asarray(whole[0, 0, 0]), np.asarray(want),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# the train op: the same block function for T <= window
# ---------------------------------------------------------------------------
T, B = 8, 3


@pytest.fixture(scope="module")
def trained():
    from paddle_tpu.core.backward import append_backward
    from paddle_tpu.ops import common

    before = common._AMP
    pt.set_amp(False)
    config = tiny_config()
    spec = fam.spec_of(config)
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = 3
    scope = pt.Scope()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        logits, aux = models.transformer_lm(ids, spec=spec,
                                            pipeline_stack=True, remat=True)
        ce = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, spec.vocab_size]),
            layers.reshape(tgt, shape=[-1, 1])))
        loss = layers.elementwise_add(
            ce, layers.scale(aux, scale=spec.router_aux_loss_coef))
        grads = {p.name: g for p, g in append_backward(loss)}
    exe = pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    scope.set("tok_emb", scope.get("tok_emb") * 64)
    w = {k: np.asarray(v) for k, v in fam.weights_of(None, scope).items()}
    seq = np.random.RandomState(0).randint(0, 512, (B, T + 1))
    feed = {"ids": seq[:, :-1].astype("int64"),
            "tgt": seq[:, 1:].astype("int64")}
    names = sorted(grads)
    out = exe.run(main, feed=feed,
                  fetch_list=[loss] + [grads[n] for n in names], scope=scope)
    ref_loss, ref_grads = fam.reference_grads(config, w, feed)
    common._AMP = before
    return dict(loss=float(np.asarray(out[0]).reshape(())),
                grads=dict(zip(names, map(np.asarray, out[1:]))),
                ref_loss=ref_loss, ref_grads=ref_grads)


def test_train_loss_matches_the_reference(trained):
    assert abs(trained["loss"] - trained["ref_loss"]) < F32_TOL
    assert trained["loss"] > 1.0


@pytest.mark.parametrize("name", ["tok_emb", "final_ln.scale", "lm_head.w"]
                         + [f"lm_stack.stack_{k}" for k in fam._STACK])
def test_every_train_gradient_matches_the_reference(trained, name):
    got, ref = trained["grads"][name], np.asarray(trained["ref_grads"][name])
    assert got.shape == ref.shape
    assert np.abs(ref).max() > 0, "a gradient that is zero tests nothing"
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=F32_TOL * max(1.0, np.abs(ref).max()))


# ---------------------------------------------------------------------------
# save -> load keeps the spec
# ---------------------------------------------------------------------------
def test_save_load_serve_keeps_the_spec(no_amp, tmp_path):
    config = tiny_config()
    spec = fam.spec_of(config)
    scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace(0))
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p_init", shape=[8], dtype="int64")
        out = models.transformer_lm_generate(p, spec=spec, max_new_tokens=1)
    startup.random_seed = 5
    exe.run(startup, scope=scope)
    kw = dict(max_seq_len=64, slots=2, page_size=PS, prompt_buckets=(4, 8),
              prefill_batch_buckets=(1,), prefill_chunk=8, eos_id=None)
    eng = GenerationEngine(spec, scope, **kw)
    pt.io.save_inference_model(str(tmp_path), ["p_init"], [out], exe,
                               main_program=prog, scope=scope)
    loaded = GenerationEngine.from_saved(str(tmp_path), **kw)
    want = fam.spec_of(config)
    want.max_len = 64
    want.router_aux_loss_coef = 0.0     # training only, not in the program
    assert loaded.spec == want
    assert loaded.n_pages_window == eng.n_pages_window > 0
    prompt = _prompt(13, seed=14)
    np.testing.assert_array_equal(
        loaded.generate_all([prompt], max_new_tokens=2 * WINDOW)[0],
        eng.generate_all([prompt], max_new_tokens=2 * WINDOW)[0])


# ---------------------------------------------------------------------------
# what knows one kind of layer says so
# ---------------------------------------------------------------------------
def _kind_attrs():
    return fam.spec_of(tiny_config()).block.attrs()


def test_training_beyond_the_window_is_refused_by_name(no_amp):
    from paddle_tpu.core.enforce import EnforceError

    spec = fam.spec_of(tiny_config())
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[WINDOW + 1], dtype="int64")
        with pytest.raises((BlockNotSupportedError, EnforceError),
                           match="window layers of 8"):
            models.transformer_lm(ids, spec=spec, pipeline_stack=True)


@pytest.mark.parametrize("op", ["transformer_stack_beam_search"])
def test_one_kind_ops_refuse_layer_kinds_by_name(op):
    from paddle_tpu.core.registry import get_op

    with pytest.raises(BlockNotSupportedError, match=op):
        get_op(op).fn(dict(_kind_attrs(), max_new_tokens=1), {})


def test_seq2seq_family_refuses_layer_kinds():
    with pytest.raises(BlockNotSupportedError):
        fam.spec_of(tiny_config()).block.require_gpt2("the seq2seq family")


@pytest.mark.parametrize("surface", ["export_slot", "adopt_slot",
                                     "share_cache_with", "beam request",
                                     "serialized handoff", "disagg"])
def test_slot_handoff_and_beams_refuse_layer_kinds(no_amp, surface):
    from paddle_tpu.serving.batcher import Request

    eng = _engine()
    if surface == "export_slot":
        with pytest.raises(BlockNotSupportedError, match="export_slot"):
            eng.export_slot(0)
    elif surface == "adopt_slot":
        with pytest.raises(BlockNotSupportedError, match="adopt_slot"):
            eng.adopt_slot({"pool": eng.pool})
    elif surface == "share_cache_with":
        with pytest.raises(BlockNotSupportedError, match="share_cache_with"):
            GenerationEngine(eng.spec, eng.scope, share_cache_with=eng)
    elif surface == "beam request":
        req = Request({"prompt": _prompt(5)},
                      {"max_new_tokens": 4, "beam_size": 2}, None)
        eng.admit([req])
        with pytest.raises(BlockNotSupportedError, match="beam search"):
            req.future.result(timeout=0.1)
    elif surface == "serialized handoff":
        req = Request({"prompt": _prompt(5), "handoff": {}},
                      {"max_new_tokens": 4}, None)
        with pytest.raises(BlockNotSupportedError, match="handoff"):
            eng.admit([req])
    else:
        from paddle_tpu.serving.disagg import DisaggEngine

        with pytest.raises(BlockNotSupportedError):
            DisaggEngine.build(eng.spec, scope=eng.scope, slots=2,
                               max_seq_len=64, page_size=PS)


def test_engine_reports_each_kinds_pool(no_amp):
    eng = _engine()
    eng.generate_all([_prompt(9)], max_new_tokens=4)
    stats, state = eng.cache_stats(), eng.flight_state()
    assert stats["kv_pages_n_pages"] == 120
    assert stats["kv_window_pages_n_pages"] == 40
    assert state["pool"]["n_pages"] == 120
    assert state["pool_window"]["n_pages"] == 40
    k = eng.scope.get("serving.paged_cache_k")
    kw = eng.scope.get("serving.paged_cache_kw")
    assert k.shape == (2, 120, PS, 64) and kw.shape == (6, 40, PS, 64)
