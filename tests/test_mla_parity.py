"""Latent attention (``LMSpec(attn="mla")``: a latent page pool, expanded
prefill mathematics and an absorbed decode), YaRN RoPE, a shared expert and
an expert layer told which of the router's experts it holds
(``experts_held``) — at a tiny size on the CPU against the plain float32
reference in ``benchmark/families/mla_moe_lm.py``: d 64, 4 heads, ranks 32 /
16, nope 8 | rope 8 | v 16, experts 2..5 held of a router over 8, top-2, a
shared expert of 32, YaRN ``original_max`` 16 with positions past it (so
that ``a(i)`` != 1), through the normal path (``GenerationEngine(spec, ..)``).

Tolerances. float32 everywhere: program (absorbed, through the cache) and
reference (expanded, no cache) run the same arithmetic in another order;
observed <= 1e-6 on log-probs, the bound is 2e-5; every wrong model of the
reference's ``VARIANTS`` lies >= 0.05 away, the one that rounds norms,
router logits and softmax to bfloat16 among them. bfloat16 PAGES (the
latent rows; all else float32): ``BF16_PAGE_TOL`` on the MEDIAN error over
the positions, 2 x what the right model reads and 0.6 x what the bf16-router
/ bf16-scores variant of the reference reads. bfloat16 everything (AMP):
``BF16_TOL``, which a fault of the mathematics fails."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import mla_moe_lm as fam
from paddle_tpu import layers, models
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.paged_attention import (MLA_KERNEL,
                                                paged_attention_decode)
from paddle_tpu.lm_spec import (Block, BlockNotSupportedError, LMSpec,
                                RopeScaling)
from paddle_tpu.ops import pipeline_ops
from paddle_tpu.ops.moe_ops import moe_topk
from paddle_tpu.serving import GenerationEngine

F32_TOL = 2e-5
BF16_PAGE_TOL = 0.0012
BF16_TOL = 0.006
PS = 4
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config(**assumed):
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "mistral4-tiny.json")) as f:
        config = json.load(f)
    config["assumed"].update(assumed)
    return config


def real_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-small-4-119b.json")) as f:
        return json.load(f)


ENGINE = {"slots": 4, "page_size": PS, "n_pages": 120, "max_len": 64,
          "prompt_buckets": [4, 8], "prefill_batch_buckets": [1, 2],
          "prefill_chunk": 8}


@pytest.fixture
def no_amp():
    pt.set_amp(False)


def _engine(seed=3, config=None, **engine):
    eng, _ = fam.build_engine(config or tiny_config(),
                              {"engine": {**ENGINE, **engine}}, seed,
                              beam_width=4)
    return eng


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 500, size=n)


def _served_errors(eng, prompt, new, variant="", config=None):
    """|served top-4 log-prob - reference's| (the largest of the four) at
    every chunk end and decode step of one request, and the sequence."""
    config = config or tiny_config()
    calls, out = fam.served_logprobs(eng, prompt, new)
    ref = np.asarray(jax.nn.log_softmax(fam.reference_logits(
        config, fam.weights_of(None, eng.scope), out, variant=variant), -1))
    return np.array([float(np.abs(ref[p][i] - v).max())
                     for p, v, i in calls]), out


def _counters(eng):
    return eng.metrics.snapshot()["counters"]


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------
def test_spec_carries_the_latent_widths_scaling_and_share_in_the_attrs():
    spec = fam.spec_of(tiny_config())
    attrs = spec.block.attrs()
    assert attrs["attn"] == "mla" and attrs["experts_held"] == [2, 4]
    assert attrs["shared_expert"] is True and "head_dim" not in attrs
    assert attrs["rope_scaling"]["original_max"] == 16
    json.dumps(attrs)                    # a program's attrs are JSON
    assert Block.from_attrs(attrs) == spec.block
    assert spec.block.stack_slots()["KvbW"] == "kv_b_w"
    assert "QkvW" not in spec.block.stack_slots()
    planes = {key: shape for _, key, shape, _ in spec.stack_planes()}
    assert planes["router_w"] == [64, 8]
    assert planes["moe_gate_w"] == [4, 64, 32]          # the held experts
    assert planes["kv_a_w"] == [64, 16 + 8]
    assert planes["kv_b_w"] == [16, 4 * (8 + 16)]
    assert planes["out_w"] == [4 * 16, 64]
    assert planes["shared_down_w"] == [32, 64]


def test_the_cache_row_is_a_property_of_the_spec():
    """One latent row a token a layer against K and V rows of Hkv * dh."""
    tiny = fam.spec_of(tiny_config())
    assert (tiny.cache_pools, tiny.cache_row_width) == (1, 16 + 8)
    real = fam.spec_of(real_config())
    # 320 values, held at three whole lane rows
    assert (real.cache_pools, real.cache_row_width) == (1, 384)
    mha = LMSpec(vocab_size=8, d_model=4096, n_layers=6, num_heads=32)
    assert (mha.cache_pools, mha.cache_row_width) == (2, 4096)
    assert mha.cache_bytes_per_token == 6 * 2 * 4096 * 4


def test_the_cut_configuration_counts_its_published_parameters():
    assert fam.spec_of(real_config()).n_params() == 5_422_771_712


def test_other_specs_keep_the_attrs_they_had():
    assert LMSpec(vocab_size=8, d_model=64, n_layers=2,
                  num_heads=4).block.attrs() == {
        "num_heads": 4, "num_kv_heads": None, "use_rope": False}
    olmoe = LMSpec(vocab_size=8, d_model=64, n_layers=2, num_heads=4,
                   use_rope=True, norm="rms_norm", qk_norm=True,
                   rope_pairing="half", ffn="swiglu_moe", num_experts=8,
                   experts_per_tok=2, d_expert=16, bias=False)
    assert set(olmoe.block.attrs()) == {
        "num_heads", "num_kv_heads", "use_rope", "norm", "qk_norm",
        "rope_pairing", "ffn", "experts_per_tok", "bias"}
    assert "QkvW" in olmoe.block.stack_slots()


@pytest.mark.parametrize("why,kw", [
    ("widths", dict(kv_lora_rank=0)),
    ("odd rotary dims", dict(qk_rope_head_dim=7)),
    ("learned positions", dict(use_rope=False)),
    ("kv groups", dict(num_kv_heads=2)),
])
def test_spec_refuses(why, kw):
    base = dict(num_heads=4, use_rope=True, attn="mla", q_lora_rank=8,
                kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
                v_head_dim=8)
    with pytest.raises(ValueError):
        Block(**{**base, **kw})


def test_experts_held_must_lie_inside_the_router():
    with pytest.raises(ValueError, match="experts_held"):
        LMSpec(vocab_size=8, d_model=64, n_layers=2, num_heads=4,
               ffn="swiglu_moe", num_experts=8, experts_per_tok=2,
               d_expert=16, experts_held=(6, 4))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("config", [tiny_config(), real_config()],
                         ids=["tiny", "published"])
def test_yarn_frequencies_match_a_direct_evaluation(config):
    sc = fam.rope_scaling_of(config)
    rp = config["rope_parameters"]
    dim = config["qk_rope_head_dim"]
    got = np.asarray(fa.yarn_inv_freq(dim, float(rp["rope_theta"]), sc))
    want = fam.yarn_inv_freq(config)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    theta = float(rp["rope_theta"]) ** (-2.0 * np.arange(dim // 2) / dim)
    turns = rp["original_max_position_embeddings"] * theta / (2 * np.pi)
    # kept where a pair turns more than beta_fast times, interpolated
    # where it turns less than once, strictly between in between
    fast, slow = turns > rp["beta_fast"], turns < rp["beta_slow"]
    assert fast.any() and slow.any()
    np.testing.assert_allclose(got[fast], theta[fast], rtol=1e-6)
    np.testing.assert_allclose(got[slow], theta[slow] / rp["factor"],
                               rtol=1e-6)
    mid = ~fast & ~slow
    assert np.all(got[mid] <= theta[mid] * (1 + 1e-6))
    assert np.all(got[mid] >= theta[mid] / rp["factor"] * (1 - 1e-6))


def test_rotary_under_scaling_is_the_references_rotation():
    config = tiny_config()
    sc = fam.rope_scaling_of(config)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 8))
    got = fa.rotary(x, jnp.asarray([7, 30]), 100.0, "interleaved",
                    scaling=sc)
    for b, p0 in enumerate((7, 30)):
        want = fam._rope(x[b].transpose(1, 0, 2), jnp.arange(p0, p0 + 5),
                         fam.yarn_inv_freq(config))
        np.testing.assert_allclose(np.asarray(got[b].transpose(1, 0, 2)),
                                   np.asarray(want), atol=1e-5)


def test_softmax_scale_and_temperature_of_the_published_keys():
    sc = fam.rope_scaling_of(real_config())
    assert sc.cos_sin_scale == 1.0
    assert abs(sc.softmax_mscale - 1.4852 ** 2) < 2e-4
    assert abs(pipeline_ops._sm_scale(fam.spec_of(real_config()).block)
               - 128 ** -0.5 * sc.softmax_mscale) < 1e-9
    assert RopeScaling().softmax_mscale == 1.0


# ---------------------------------------------------------------------------
# serving through the latent pages against the reference's full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prompt_len,new", [(7, 4), (21, 24)],
                         ids=["one chunk", "three chunks past original_max"])
def test_prefill_and_absorbed_decode_match_the_reference(no_amp, prompt_len,
                                                         new):
    eng = _engine()
    errs, out = _served_errors(eng, _prompt(prompt_len), new)
    assert out.size == prompt_len + new
    assert errs.max() < F32_TOL, errs
    c = _counters(eng)
    # the page walk's counters are the K/V engines' (one reader for all)
    assert c["paged_attn_pages_read"] > 0 and c["paged_attn_table_pages"] > 0


@pytest.mark.parametrize("variant", sorted(fam.VARIANTS))
def test_each_wrong_model_is_told_from_the_right_one(no_amp, variant):
    errs, _ = _served_errors(_engine(), _prompt(21), 20, variant=variant)
    assert errs.max() > 1000 * F32_TOL, (variant, errs.max())


def test_one_shot_generate_runs_the_same_block(no_amp):
    """Absorbed = expanded: the one-shot generate op (every head's keys
    and values expanded into a dense cache, ``_mla_expand``) emits what
    the engine (latent pages, absorbed prefill and decode) emits; both
    are held to the reference's expanded logits above."""
    eng = _engine()
    prompt = _prompt(13, seed=4)
    served = eng.generate_all([prompt], max_new_tokens=12)[0]
    spec = fam.spec_of(tiny_config())
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p", shape=[13], dtype="int64")
        out = models.transformer_lm_generate(p, spec=spec, max_new_tokens=12)
    got = pt.Executor(pt.TPUPlace(0)).run(
        prog, feed={"p": prompt[None]}, fetch_list=[out],
        scope=eng.scope)[0]
    np.testing.assert_array_equal(np.asarray(got)[0], served)


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bf16_pages_hold_a_tolerance_a_bf16_router_or_scores_fail(no_amp,
                                                                   seed):
    """The latent rows stored and attended in bfloat16, all else float32:
    the MEDIAN error over the positions (three chunks and 24 decode steps)
    reads 0.0006 and holds ``BF16_PAGE_TOL``; against the reference with
    norms, router logits and softmax rounded to bfloat16 (one precision
    below what the configuration states) it reads 0.002 and fails it. The
    median and not the largest: a rounded latent can flip a near-tie of a
    router's top-2, and that position then runs another expert."""
    config = tiny_config(page_dtype="bfloat16")
    eng = _engine(seed=seed, config=config)
    assert str(eng.scope.get("serving.paged_cache_k").dtype) == "bfloat16"
    calls, out = fam.served_logprobs(eng, _prompt(21, seed), 24)
    w = fam.weights_of(None, eng.scope)

    def median_error(variant):
        ref = np.asarray(jax.nn.log_softmax(fam.reference_logits(
            config, w, out, variant=variant), -1))
        return float(np.median([np.abs(ref[p][i] - v).max()
                                for p, v, i in calls]))

    right, lossy = median_error(""), median_error("bf16_stated_f32")
    assert 5 * F32_TOL < right < BF16_PAGE_TOL < lossy, (right, lossy)


def test_bf16_weights_pages_and_operands_serve_near_the_reference():
    """bfloat16 parameters, pages and matmul operands (AMP), as the
    benchmark's configuration runs: the median error reads 0.0022-0.0024
    (bf16 products at 2^-9 relative through two layers); a fault of the
    mathematics (no query temperature: 0.013) is outside ``BF16_TOL``.
    At these toy widths the engine's own rounding hides a bf16 router
    (0.0022-0.0029): the test above is the one that tells that apart."""
    config = tiny_config(param_dtype="bfloat16", page_dtype="bfloat16")
    pt.set_amp(True)
    try:    # (conftest's autouse fixture puts the policy back)
        eng = _engine(config=config)
        right, out = _served_errors(eng, _prompt(21), 24, config=config)
        wrong, _ = _served_errors(_engine(config=config), _prompt(21), 24,
                                  config=config,
                                  variant="no_query_temperature")
    finally:
        pt.set_amp(False)
    assert str(eng.scope.get("tok_emb").dtype) == "bfloat16"
    assert 5 * F32_TOL < np.median(right) < BF16_TOL < np.median(wrong)


@pytest.mark.parametrize("fault", ["none", "another_model",
                                   "anothers_tokens"])
def test_the_cells_check_holds_served_logprobs_and_emitted_tokens(
        no_amp, monkeypatch, fault):
    """``reference_logit_gaps`` (what the serve driver compares with the
    mix's ``logit_gap_tol``) replays the checked requests through a twin
    of the built engine and returns two readings in the terms of
    ``CHECK_LOGPROB_TOL``: the 95th percentile of the served log-prob error, and
    the timed tokens' largest gap below the reference's best, scaled.
    A sound run reads far under the limit; a program that computes
    another model than the reference fails the first; an answer that holds
    tokens the model did not choose fails the second."""
    config = tiny_config()
    eng = _engine(config=config)
    prompts = [_prompt(21), _prompt(6, 4)]
    outs = [np.array(o) for o in eng.generate_all(prompts, max_new_tokens=9)]
    if fault == "another_model":
        real = fam._jit_hidden
        monkeypatch.setattr(fam, "_jit_hidden",
                            lambda c, variant="": real(c, "no_mscale"))
    if fault == "anothers_tokens":
        outs[1][-3] = (outs[1][-3] + 17) % 500
    got = fam.reference_logit_gaps(
        config, fam.weights_of(None, eng.scope),
        [(p.size, o) for p, o in zip(prompts, outs)])
    assert got.shape == (2,)
    served, emitted = got
    if fault == "another_model":
        assert served > fam.CHECK_LOGPROB_TOL
    else:
        assert served < F32_TOL
    if fault == "anothers_tokens":
        assert emitted > fam.CHECK_LOGPROB_TOL
    elif fault == "none":
        assert emitted < F32_TOL
    assert fam.reference_logit_gaps(config, {}, []).size == 0


def test_a_prefix_hit_on_latent_pages_gives_the_logits_of_a_cold_run(no_amp):
    shared, tail_a, tail_b = _prompt(16, 1), _prompt(5, 2), _prompt(6, 3)
    warm = _engine()
    warm.generate_all([np.concatenate([shared, tail_a])], max_new_tokens=3)
    before = _counters(warm).get("prefix_hit_tokens", 0)
    prompt = np.concatenate([shared, tail_b])
    hit_calls, hit_out = fam.served_logprobs(warm, prompt, 8)
    assert _counters(warm)["prefix_hit_tokens"] - before == 16
    cold_calls, cold_out = fam.served_logprobs(_engine(), prompt, 8)
    np.testing.assert_array_equal(hit_out, cold_out)
    cold = {p: v for p, v, _ in cold_calls}
    for p, v, _ in hit_calls:
        np.testing.assert_allclose(v, cold[p], atol=F32_TOL)


def test_requests_over_one_cold_document_prefill_it_once_between_them(
        no_amp):
    """Two requests over one 40-token document and a short unrelated one
    admitted together (a cold prefix cache): all three are admitted at
    once, nothing waits; the document's pages are registered chunk by
    chunk and each of the two takes over what the other has written, so
    the document is prefilled ONCE between them. All answer as they do
    alone."""
    from paddle_tpu.serving.batcher import Request

    doc = _prompt(40, 9)
    prompts = [np.concatenate([doc, _prompt(5, 10)]),
               np.concatenate([doc, _prompt(7, 11)]), _prompt(6, 12)]
    alone = [_engine().generate_all([p], max_new_tokens=5)[0]
             for p in prompts]
    chunks_alone = []
    for p in prompts:
        one = _engine()
        one.generate_all([p], max_new_tokens=5)
        c = _counters(one)
        chunks_alone.append(c.get("prefill_chunks", 0) + c["prefills"])
    eng = _engine()
    reqs = [Request({"prompt": p}, {"max_new_tokens": 5}, None)
            for p in prompts]
    eng.admit(reqs)
    assert eng.active == 3 and not eng._deferred
    # the document's ten pages are held ONCE: the second request took the
    # first one's pages into its table (written or not), not a set of its own
    held = [eng._slots[i].held[0].pages for i in range(2)]
    assert held[0][:10] == held[1][:10]
    assert eng.pool.stats()["in_use"] == sum(
        eng._entries_for(p.size + 5) for p in prompts) - 10
    eng._drive([])
    for req, want in zip(reqs, alone):
        np.testing.assert_array_equal(req.future.result(timeout=1), want)
    c = _counters(eng)
    assert c.get("admission_deferred", 0) == 0
    # every full page of the document but those each slot wrote itself
    assert c["prefix_hit_tokens"] == 40
    # the document's five chunks ran once, not twice
    assert c["prefill_chunks"] < sum(chunks_alone) - 3
    assert eng.pool.stats()["in_use"] == len(eng.prefix_index)


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
    assert loaded.spec == spec
    prompt = _prompt(13, seed=14)
    np.testing.assert_array_equal(
        loaded.generate_all([prompt], max_new_tokens=9)[0],
        eng.generate_all([prompt], max_new_tokens=9)[0])


def test_engine_holds_one_latent_pool_and_counts_the_held_share(no_amp):
    eng = _engine()
    eng.generate_all([_prompt(20), _prompt(5, 7)], max_new_tokens=6)
    assert eng.scope.get("serving.paged_cache_k").shape == (2, 120, PS, 24)
    assert not eng.scope.has("serving.paged_cache_v")
    gauges = eng.metrics.snapshot()["gauges"]
    assert gauges["mem/kv_cache_bytes"] == 2 * 120 * PS * 24 * 4
    assert gauges["mem/kv_bytes_per_token"] == 2 * 24 * 4
    c = _counters(eng)
    assert c["moe_held_assignments"] + c["moe_absent_assignments"] \
        == c["moe_assignments"] > 0
    assert c["moe_absent_assignments"] > 0       # 4 of 8 experts are absent
    assert c["moe_dropped_tokens"] == 0          # an absent expert is no drop
    assert 0 < c["moe_touched_experts"] <= 4 * c["moe_layer_calls"]


@pytest.mark.parametrize("walks", [True, False], ids=["walk", "gather"])
def test_prefill_units_count_the_pages_the_latent_chunk_walk_reads(no_amp,
                                                                   walks):
    """A latent engine (ONE pool, ``cache_pools`` 1) counts
    ``prefill_attn_pages_read`` / ``prefill_attn_table_pages`` (one kind: no
    suffix) where its prefill programs took the chunk walk (the ops'
    predicate, here answered for it: no chip), by the walk's own rule
    (``PageCache.chunk_pages_read``: page 0 to the chunk's last real key);
    an engine whose programs gather counts neither."""
    eng = _engine()
    assert eng.spec.cache_pools == 1
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
    # a unit's row brings its whole table: 64 / 4 entries
    assert c == {"prefill_attn_table_pages": units * ENGINE["max_len"] // PS,
                 # pages 0 .. the chunk's last, of 4 tokens: 8 / 4, 16 / 4
                 # and ceil(19 / 4)
                 "prefill_attn_pages_read": 2 + 4 + 5}
    (cache,) = eng._caches
    assert cache.chunk_pages_read(np.array([0, 8, 16, 40]),
                                  np.array([8, 8, 3, 0]), 16) == 11


@pytest.mark.parametrize("backend,row,latent,ok", [
    ("tpu", 384, 256, True),        # mistral4: 256 + 64 held at 384
    ("tpu", 640, 512, True),        # ling3: 512 + 64 held at 640
    ("tpu", 384, 320, False),       # a latent of two and a half lane rows
    ("cpu", 384, 256, False),
])
def test_the_engine_asks_the_ops_own_rule_about_its_one_pool(
        monkeypatch, backend, row, latent, ok):
    """``GenerationEngine._chunk_walks`` for a latent spec: the ops'
    predicate over queries as wide as the pool's row and the latent as the
    value, at the cells' chunk widths, memoised a width."""
    from types import SimpleNamespace

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    spec = SimpleNamespace(
        cache_pools=1, cache_row_width=row, num_heads=32,
        page_dtype="bfloat16", block=SimpleNamespace(kv_lora_rank=latent))
    eng = SimpleNamespace(
        spec=spec, _chunk_walk={},
        _caches=[SimpleNamespace(shape=(6, 1536, 256, row))])
    for tc in (64, 256):
        assert GenerationEngine._chunk_walks(eng, tc) is ok
    assert eng._chunk_walk == {64: ok, 256: ok}


# ---------------------------------------------------------------------------
# the expert layer's share
# ---------------------------------------------------------------------------
def _expert_weights(E=8, d=64, f=32, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    mk = lambda key, shape: jax.random.normal(key, shape) * shape[-2] ** -0.5
    return {"router_w": mk(k[0], (d, E)) * 4, "moe_gate_w": mk(k[1], (E, d, f)),
            "moe_up_w": mk(k[2], (E, d, f)), "moe_down_w": mk(k[3], (E, f, d)),
            "shared_gate_w": mk(k[4], (d, f)), "shared_up_w": mk(k[5], (d, f)),
            "shared_down_w": mk(k[6], (f, d)),
            "x": jax.random.normal(k[7], (24, d))}


def _program_share(w, held, shared=True):
    first, n = held if held else (0, 8)
    y, counts, _ = moe_topk(
        w["x"], w["router_w"], w["moe_gate_w"][first:first + n],
        w["moe_up_w"][first:first + n], w["moe_down_w"][first:first + n], 2,
        True, held=held,
        shared=(w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
        if shared else None)
    return y, counts


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(
        no_amp):
    """Expert parallelism's contract: the routed parts the four shares
    compute, plus what every chip computes alike (the shared expert)
    counted once, add up to the whole layer — in the program (sorted
    grouped matmuls over the held rows) and in the reference (dense over
    the held experts), and the two agree share by share."""
    w = _expert_weights()
    config = tiny_config()
    with jax.default_matmul_precision("highest"):
        whole, counts = _program_share(w, None)
        routed = [_program_share(w, (first, 2), shared=False)[0]
                  for first in (0, 2, 4, 6)]
        only_shared = _program_share(w, (0, 2))[0] - routed[0]
        np.testing.assert_allclose(sum(routed) + only_shared, whole,
                                   atol=F32_TOL)
        ref_whole = fam.expert_layer(config, w, w["x"], held=(0, 8))
        np.testing.assert_allclose(whole, ref_whole, atol=F32_TOL)
        for first, got in zip((0, 2, 4, 6), routed):
            p = {**w, **{k: w[k][first:first + 2] for k in
                         ("moe_gate_w", "moe_up_w", "moe_down_w")}}
            want, _ = fam.expert_layer(config, p, w["x"], held=(first, 2),
                                       parts=True)
            np.testing.assert_allclose(got, want, atol=F32_TOL)
    # every share counted the router's assignments over ALL experts
    assert int(counts.sum()) == 24 * 2 and counts.shape == (8,)


def test_an_absent_expert_adds_exactly_zero_and_all_held_is_the_old_call(
        no_amp):
    w = _expert_weights(seed=1)
    none, counts = _program_share(w, None, shared=False)
    all_held, _ = _program_share(w, (0, 8), shared=False)
    np.testing.assert_array_equal(np.asarray(none), np.asarray(all_held))
    # rows whose two experts are both absent get a zero, not a small number
    part, _ = _program_share(w, (6, 2), shared=False)
    probs = jax.nn.softmax(w["x"] @ w["router_w"], axis=-1)
    top = np.asarray(jax.lax.top_k(probs, 2)[1])
    absent = (top < 6).all(axis=1)
    assert absent.any() and (~absent).any()
    assert np.all(np.asarray(part)[absent] == 0.0)
    assert np.all(np.abs(np.asarray(part)[~absent]).sum(axis=1) > 0)


def test_held_experts_inside_a_whole_stack_take_their_layers_groups(no_amp):
    """``layer=``: the held experts of layer l are groups l*n .. of the
    flattened [L * n, ..] stacks (the serving ops' form)."""
    w = _expert_weights(seed=2)
    alone, _ = _program_share(w, (2, 4), shared=False)
    stack = {k: jnp.stack([jnp.zeros_like(w[k][2:6]), w[k][2:6]])
             for k in ("moe_gate_w", "moe_up_w", "moe_down_w")}
    got, _, _ = moe_topk(w["x"], w["router_w"], stack["moe_gate_w"],
                         stack["moe_up_w"], stack["moe_down_w"], 2, True,
                         layer=jnp.int32(1), held=(2, 4))
    np.testing.assert_allclose(got, alone, atol=1e-6)


# ---------------------------------------------------------------------------
# the kernel: latent decode on the page walk (interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_kernel_is_reference_attention_over_the_gathered_rows(dtype,
                                                                     tol):
    L, N, ps, W, r, H, b = 2, 12, 16, 128, 96, 4, 3
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    pool = jax.random.normal(k[0], (L, N, ps, W)).astype(dtype)
    q = (jax.random.normal(k[1], (b, H, W)) * 0.3).astype(dtype)
    table = jnp.asarray([[3, 5, 7, 0], [9, 0, 0, 0], [2, 4, 6, 8]], jnp.int32)
    lengths = jnp.asarray([2 * ps + 5, 1, 4 * ps], jnp.int32)
    got = paged_attention_decode(q, pool, None, jnp.int32(1), table, lengths,
                                 sm_scale=1.0, name=MLA_KERNEL,
                                 interpret=True).reshape(b, H, W)
    lat = pool[1, table].reshape(b, 1, 4 * ps, W)
    want = fa.reference_attention(q[:, :, None], lat, lat, lengths=lengths,
                                  sm_scale=1.0)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    # the caller keeps the latent's columns; a scale handed in is used
    half = paged_attention_decode(q, pool, None, jnp.int32(1), table,
                                  lengths, sm_scale=0.5, name=MLA_KERNEL,
                                  interpret=True)
    assert not np.allclose(np.asarray(half, np.float32).reshape(b, H, W),
                           np.asarray(got, np.float32), atol=tol)
    del r


def test_a_decode_step_on_a_chip_takes_the_latent_kernel(no_amp,
                                                         monkeypatch):
    """On a TPU backend with a lane-aligned row the decode tick calls the
    kernel under its own name, one pool operand; everything else (CPU, an
    unaligned row, t > 1) gathers."""
    from paddle_tpu.kernels import paged_attention

    seen = []

    def fake(q, ck, cv, layer, table, lengths, **kw):
        seen.append((q.shape, ck.shape, cv, kw))
        return jnp.zeros((q.shape[0], q.shape[1] * q.shape[2]), ck.dtype)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(paged_attention, "paged_attention_decode", fake)
    blk = Block(num_heads=4, use_rope=True, norm="rms_norm", bias=False,
                attn="mla", q_lora_rank=8, kv_lora_rank=96,
                qk_nope_head_dim=8, qk_rope_head_dim=32, v_head_dim=16)
    assert blk.cache_row(64) == (1, 128)
    b, W = 2, 128
    ck = jnp.zeros((1, 6, 16, W))
    p = {"kv_b_w": jnp.zeros((96, 4 * 24))}
    proj = (jnp.zeros((b, 4, 1, 8)), jnp.zeros((b, 4, 1, 32)),
            jnp.zeros((b, 1, 96)), jnp.zeros((b, 1, 32)))
    attend = pipeline_ops._mla_paged_step(
        blk, b, 1, lambda layer_p, h: proj, {"lengths": jnp.ones((b,),
                                                                  jnp.int32)},
        lambda layer_p, h, ctx, x_l: (ctx, None))
    ctx, *_ = attend(jnp.zeros((b, 1, 64)), ck, None, 0, p, None,
                     jnp.zeros((b, 3), jnp.int32), jnp.zeros((b, 1), jnp.int32),
                     jnp.zeros((b, 1), jnp.int32))
    assert ctx.shape == (b, 1, 4 * 16)
    (q_shape, pool_shape, cv, kw), = seen
    assert q_shape == (b, 4, W) and pool_shape == ck.shape and cv is None
    assert kw == {"sm_scale": 1.0, "name": "paged_mla_decode"}


# ---------------------------------------------------------------------------
# what does not run this spec says so
# ---------------------------------------------------------------------------
def _mla_attrs():
    return fam.spec_of(tiny_config()).block.attrs()


def test_the_train_op_refuses_by_name(no_amp):
    from paddle_tpu.core.enforce import EnforceError

    spec = fam.spec_of(tiny_config())
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[8], dtype="int64")
        with pytest.raises((BlockNotSupportedError, EnforceError),
                           match="latent attention"):
            models.transformer_lm(ids, spec=spec, pipeline_stack=True)


def test_beam_search_op_refuses_by_name():
    from paddle_tpu.core.registry import get_op

    with pytest.raises(BlockNotSupportedError,
                       match="transformer_stack_beam_search"):
        get_op("transformer_stack_beam_search").fn(
            dict(_mla_attrs(), max_new_tokens=1), {})


def test_seq2seq_family_refuses():
    with pytest.raises(BlockNotSupportedError):
        fam.spec_of(tiny_config()).block.require_gpt2("the seq2seq family")


@pytest.mark.parametrize("surface", ["export_slot", "adopt_slot",
                                     "share_cache_with", "beam request",
                                     "serialized handoff", "disagg"])
def test_slot_handoff_and_beams_refuse_latent_pages(no_amp, surface):
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
