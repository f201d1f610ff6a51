"""A drafting (multi-token-prediction) block on the paged plane, at toy
widths on the CPU mesh: ``LMSpec(draft_block=True)`` over window and full
layers behind a dense first layer (the K-EXAONE shape, family
``window_mtp_moe_lm``).

(a) the engine against the plain float32 reference: prefill, then verify
    ticks through the cache, log-probs at every committed position and the
    draft against the reference block's argmax, with every wrong model of
    ``VARIANTS`` told apart;
(b) the same engine with and without the block emits identical tokens;
(c) a rejected draft's K/V never reaches a later position's logits;
(d) the sixteen shares of an expert layer add up to the uncut layer;
(e) what refuses a drafting block, by name;
(f) the verify walk of ``kernels/paged_attention`` in interpret mode.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import window_mtp_moe_lm as fam
from paddle_tpu import models
from paddle_tpu.decoding.params import SamplingParams
from paddle_tpu.kernels import paged_attention
from paddle_tpu.kernels.flash_attention import reference_attention
from paddle_tpu.lm_spec import BlockNotSupportedError, LMSpec
from paddle_tpu.ops.pipeline_ops import _gather_pages
from paddle_tpu.serving import GenerationEngine

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests", "data")
PS, WINDOW = 4, 8
#: float32 on the CPU against the float32 reference: the largest served
#: top-8 log-prob error read 2e-5 (sums in another order); a reference one
#: precision lower (norms, router scores, softmax in bfloat16) reads
#: 1e-2 and more, every fault of the mathematics more still
TOL = 2e-4


@pytest.fixture
def no_amp():
    from paddle_tpu.ops import common

    before = common._AMP
    pt.set_amp(False)
    yield
    common._AMP = before


def tiny_config():
    with open(os.path.join(DATA, "configs", "kexaone-tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    return build_model()


def build_model():
    """(config, weights by name): seeded, the embedding scaled, the
    drafting block started as the configuration says, and the norm scales
    moved off 1 so that a norm too many or too few shows."""
    config = tiny_config()
    spec = fam.spec_of(config)
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        models.lm_parameters(spec)
    startup.random_seed = 11
    exe.run(startup, scope=scope)
    a = config["assumed"]
    scope.set("tok_emb", scope.get("tok_emb") * a["embedding_scale"])
    fam.mtp_start_up(scope, a["mtp_init"])
    rng = np.random.default_rng(3)
    for name in ("final_ln.scale", "mtp.norm_h_s", "mtp.norm_e_s",
                 "mtp.head_norm_s"):
        v = scope.get(name)
        scope.set(name, v * jnp.asarray(
            rng.uniform(0.5, 1.5, v.shape), v.dtype))
    return config, {n: scope.get(n) for n in spec.param_names()}


def _engine(model, draft=True, **kw):
    config, w = model
    spec = fam.spec_of(config)
    if not draft:
        spec = dataclasses.replace(spec, draft_block=False)
    scope = pt.Scope()
    for name in spec.param_names():
        scope.set(name, w[name])
    kw = {**dict(slots=3, page_size=PS, max_seq_len=64, prompt_buckets=(8,),
                 prefill_batch_buckets=(1, 2), prefill_chunk=8, n_pages=80,
                 n_pages_window=40), **kw}
    return GenerationEngine(spec, scope, **kw)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, size=n)


# ---------------------------------------------------------------------------
# (a) engine against reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def replay(model):
    from paddle_tpu.ops import common

    before = common._AMP
    pt.set_amp(False)
    eng = _engine(model, beam_width=fam.CHECK_TOPK)
    # a prompt of three chunks (the last partial), decode across page
    # edges and past the 8-key window
    calls, drafts, out = fam.served(eng, _prompt(21), 24)
    counted = eng.metrics.snapshot()["counters"]
    common._AMP = before
    return calls, drafts, out, counted


def _read(model, replay, variant=""):
    """-> the largest served log-prob error of the stack, the share of
    drafts the reference block ranks first, the largest served log-prob
    error of the drafting block."""
    config, w = model
    r = fam.read_replay(config, w, *replay[:3], variant)
    assert len(r["draft_errs"]) == r["draft_n"] > 0
    return (max(r["errs"]), r["draft_equal"] / r["draft_n"],
            max(r["draft_errs"]))


def test_prefill_then_verify_ticks_serve_the_reference(model, replay):
    calls, drafts, out, counted = replay
    # every position from the last prompt token on was served exactly once
    # (both rows of an accepted pair), chunk ends before it
    served = [p for p, _, _ in calls]
    assert served[:3] == [7, 15, 20] and served[2:] == list(range(20, 44))
    assert counted["mtp_accepted"] >= 3         # ... and some ticks took two
    assert counted["mtp_drafted"] > counted["mtp_accepted"]  # some did not
    assert counted["decode_tokens"] == 23       # the first came of prefill
    worst, equal, worst_draft = _read(model, replay)
    assert worst < TOL
    assert equal == 1.0     # the draft IS the reference block's argmax
    assert worst_draft < TOL    # ... and its log-probs the block's


@pytest.mark.parametrize("variant", [v for v in fam.VARIANTS
                                     if v not in fam.DRAFT_VARIANTS])
def test_a_wrong_stack_is_told_by_the_logits(model, replay, variant):
    worst, _, _ = _read(model, replay, variant)
    assert worst > 10 * TOL, (variant, worst)


@pytest.mark.parametrize("variant,at_most", [
    # (the hidden half enters M scaled by 1/16 here: a norm too many moves
    # few drafts, but moves some; the right block moves none. A fault of
    # the block's attention or experts may move NO argmax — the embedding
    # half decides it — which is why its log-probs are read too)
    ("mtp_normed_h", 0.99), ("mtp_halves_swapped", 0.5),
    ("mtp_windowed", 1.0), ("mtp_no_shared_expert", 1.0)])
def test_a_wrong_drafting_block_is_told_by_the_draft_alone(model, replay,
                                                           variant, at_most):
    assert {v for v, _ in test_a_wrong_drafting_block_is_told_by_the_draft_alone
            .pytestmark[0].args[1]} == set(fam.DRAFT_VARIANTS)
    worst, equal, worst_draft = _read(model, replay, variant)
    assert worst < TOL          # invisible in the stack's logits
    assert equal <= at_most, (variant, equal)
    assert worst_draft > 10 * TOL, (variant, worst_draft)


# ---------------------------------------------------------------------------
# (b) identical tokens with and without the block
# ---------------------------------------------------------------------------
def _sampling():
    return [None, SamplingParams(temperature=0.8, top_p=0.9, seed=5), None,
            SamplingParams(temperature=1.0, top_k=8, seed=9), None]


def test_tokens_are_those_of_the_engine_without_the_block(model, no_amp):
    with_, without = _engine(model), _engine(model, draft=False)
    prompts = [_prompt(n, seed=n) for n in (5, 13, 9, 20, 3)]
    a = with_.generate_all(prompts, max_new_tokens=22, sampling=_sampling())
    b = without.generate_all(prompts, max_new_tokens=22,
                             sampling=_sampling())
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    c = with_.metrics.snapshot()["counters"]
    assert c["mtp_accepted"] > 0 and c["verify_rows_rejected"] > 0
    assert c["decode_tokens"] == 5 * 21
    assert c["decode_live_rows"] < c["decode_tokens"]
    assert c["decode_feed_host_arrays"] == c["decode_steps"]    # one plane
    assert "decode_live_rows" not in without.metrics.snapshot()["counters"]


def test_a_request_may_end_on_the_first_of_two_tokens(model, no_amp):
    """Every answer length from 1 up: where an accepted pair straddles the
    end, the second token is not emitted."""
    with_, without = _engine(model), _engine(model, draft=False)
    p = _prompt(6, seed=2)
    ended_inside_a_pair = 0
    for n in range(1, 14):
        before = with_.metrics.snapshot()["counters"]
        a = with_.generate_all([p], max_new_tokens=n)[0]
        b = without.generate_all([p], max_new_tokens=n)[0]
        np.testing.assert_array_equal(a, b)
        assert a.size == p.size + n
        after = with_.metrics.snapshot()["counters"]
        took = {k: after.get(k, 0) - before.get(k, 0)
                for k in ("decode_tokens", "decode_live_rows",
                          "mtp_accepted")}
        assert took["decode_tokens"] == n - 1
        ended_inside_a_pair += (took["decode_live_rows"]
                                + took["mtp_accepted"] > n - 1)
    assert ended_inside_a_pair >= 1


def test_a_prefix_hit_brings_the_blocks_pages_cut_to_a_page_inside(
        model, no_amp):
    with_, without = _engine(model), _engine(model, draft=False)
    shared = _prompt(16, seed=7)
    tails = [_prompt(5, seed=8), _prompt(7, seed=9)]
    for tail in tails:
        p = np.concatenate([shared, tail])
        a = with_.generate_all([p], max_new_tokens=10)[0]
        b = without.generate_all([p], max_new_tokens=10)[0]
        np.testing.assert_array_equal(a, b)
    c = with_.metrics.snapshot()["counters"]
    # 16 tokens matched (four pages); the block's row at position 15 was
    # made with the FIRST request's token 16: three pages are taken
    assert c["prefix_hit_tokens"] == 12
    assert without.metrics.snapshot()["counters"]["prefix_hit_tokens"] == 16
    # ... and the same prompt again is no full hit either
    p = np.concatenate([shared, tails[0]])
    a = with_.generate_all([p], max_new_tokens=10)[0]
    np.testing.assert_array_equal(
        a, without.generate_all([p], max_new_tokens=10)[0])


def test_a_logits_processor_row_is_fed_no_draft(model, no_amp):
    class Even:
        def mask(self, step, generated):
            m = np.zeros(512, np.float32)
            m[::2] = 1.0
            return m

    sp = SamplingParams(logits_processor=Even())
    with_, without = _engine(model), _engine(model, draft=False)
    p = _prompt(9, seed=4)
    a = with_.generate_all([p], max_new_tokens=8, sampling=sp)[0]
    b = without.generate_all([p], max_new_tokens=8, sampling=sp)[0]
    np.testing.assert_array_equal(a, b)
    assert (a[p.size:] % 2 == 0).all()
    c = with_.metrics.snapshot()["counters"]
    assert c["mtp_drafted"] == 0 and c["mtp_first_ticks"] == 7


# ---------------------------------------------------------------------------
# (c) a rejected draft leaves nothing behind
# ---------------------------------------------------------------------------
def test_a_rejected_drafts_rows_never_reach_a_later_position(model, no_amp):
    """Every tick is fed a WRONG draft (its K/V rows, the stack's and the
    block's, are written all the same): tokens and served log-probs are
    those of the reference."""
    config, w = model
    eng = _engine(model, beam_width=fam.CHECK_TOPK)
    feed = eng._decode_feed

    def wrong_draft():
        live = eng._draft_tok >= 0
        eng._draft_tok[live] = (eng._draft_tok[live] + 1) % 512
        return feed()

    eng._decode_feed = wrong_draft
    p = _prompt(10, seed=5)
    calls, _, out = fam.served(eng, p, 16)
    c = eng.metrics.snapshot()["counters"]
    assert c["verify_rows_rejected"] >= 10
    ref = _engine(model, draft=False).generate_all([p], max_new_tokens=16)[0]
    np.testing.assert_array_equal(out, ref)
    assert max(fam.read_replay(config, w, calls, [], out)["errs"]) < TOL


# ---------------------------------------------------------------------------
# (d) the shares add up
# ---------------------------------------------------------------------------
def test_sixteen_shares_of_an_expert_layer_add_up_to_the_whole(model,
                                                               no_amp):
    """Program (``moe_topk(held=)``) and reference (``expert_layer(held=)``)
    alike: the routed parts of all sixteen one-expert shares, with the
    shared expert counted ONCE, are the uncut layer."""
    from paddle_tpu.ops.moe_ops import moe_topk

    config, _ = model
    E, d, f = config["router_outputs"], 64, 16
    rng = np.random.default_rng(0)
    # (router logits of unit size: a sigmoid that saturates ties at 1.0)
    p = {"router_w": rng.standard_normal((d, E)).astype(np.float32) / 8,
         "moe_gate_w": rng.standard_normal((E, d, f)).astype(np.float32) / 8,
         "moe_up_w": rng.standard_normal((E, d, f)).astype(np.float32) / 8,
         "moe_down_w": rng.standard_normal((E, f, d)).astype(np.float32) / 4,
         "shared_gate_w": rng.standard_normal((d, f)).astype(np.float32) / 8,
         "shared_up_w": rng.standard_normal((d, f)).astype(np.float32) / 8,
         "shared_down_w": rng.standard_normal((f, d)).astype(np.float32) / 4}
    p = {k: jnp.asarray(v) for k, v in p.items()}
    h2 = jnp.asarray(rng.standard_normal((128, d)), jnp.float32)
    shared = (p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"])
    kw = dict(score="sigmoid", routed_scale=2.5)
    with jax.default_matmul_precision("highest"):
        whole_ref = fam.expert_layer(config, p, h2, held=(0, E))
        whole = moe_topk(h2, p["router_w"], p["moe_gate_w"], p["moe_up_w"],
                         p["moe_down_w"], 2, True, shared=shared, **kw)[0]
        parts_ref, parts = [], []
        for e in range(E):
            one = {**p, **{k: p[k][e:e + 1] for k in (
                "moe_gate_w", "moe_up_w", "moe_down_w")}}
            routed, shared_ref = fam.expert_layer(config, one, h2,
                                                  held=(e, 1), parts=True)
            parts_ref.append(routed)
            with_shared = moe_topk(
                h2, p["router_w"], one["moe_gate_w"], one["moe_up_w"],
                one["moe_down_w"], 2, True, held=(e, 1), shared=shared,
                **kw)[0]
            parts.append(with_shared - shared_ref)
    np.testing.assert_allclose(sum(parts_ref) + shared_ref, whole_ref,
                               atol=1e-4)
    np.testing.assert_allclose(sum(parts) + shared_ref, whole, atol=1e-4)
    np.testing.assert_allclose(whole, whole_ref, atol=1e-4)


# ---------------------------------------------------------------------------
# (e) what refuses a drafting block
# ---------------------------------------------------------------------------
def _plain_draft_spec():
    """One kind of layer, K and V pages, no state: only the drafting block
    stands between this spec and the handoff."""
    return LMSpec(vocab_size=64, d_model=32, n_layers=2, num_heads=4,
                  use_rope=True, norm="rms_norm", bias=False,
                  ffn="swiglu_moe", num_experts=4, experts_per_tok=2,
                  d_expert=16, draft_block=True, max_len=64)


@pytest.mark.parametrize("surface", ["export_slot", "adopt_slot",
                                     "share_cache_with", "beam request",
                                     "serialized handoff", "disagg", "pp"])
def test_what_moves_one_token_a_step_refuses_a_drafting_block(no_amp,
                                                              surface):
    from paddle_tpu.serving.batcher import Request

    spec = _plain_draft_spec()
    match = "drafting block"
    if surface == "pp":
        from paddle_tpu.core.registry import get_op

        with pytest.raises(BlockNotSupportedError, match=match):
            spec.block.require_no_draft("a pp pipeline")
        assert "require_no_draft" in open(
            get_op("pipelined_transformer_stack").fn.__code__.co_filename
        ).read()
        return
    scope = pt.Scope()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        models.lm_parameters(spec)
    pt.Executor(pt.TPUPlace(0)).run(startup, scope=scope)
    eng = GenerationEngine(spec, scope, slots=2, page_size=PS,
                           max_seq_len=32, beam_width=2)
    if surface == "export_slot":
        with pytest.raises(BlockNotSupportedError, match=match):
            eng.export_slot(0)
    elif surface == "adopt_slot":
        with pytest.raises(BlockNotSupportedError, match=match):
            eng.adopt_slot({"pool": eng.pool})
    elif surface == "share_cache_with":
        with pytest.raises(BlockNotSupportedError, match=match):
            GenerationEngine(spec, eng.scope, share_cache_with=eng)
    elif surface == "beam request":
        req = Request({"prompt": _prompt(5) % 64},
                      {"max_new_tokens": 4, "beam_size": 2}, None)
        eng.admit([req])
        with pytest.raises(BlockNotSupportedError, match=match):
            req.future.result(timeout=0.1)
    elif surface == "serialized handoff":
        req = Request({"prompt": _prompt(5) % 64, "handoff": {}},
                      {"max_new_tokens": 4}, None)
        with pytest.raises(BlockNotSupportedError, match=match):
            eng.admit([req])
    else:
        from paddle_tpu.serving.disagg import DisaggEngine

        with pytest.raises(BlockNotSupportedError, match=match):
            DisaggEngine.build(spec, scope=eng.scope, slots=2,
                               max_seq_len=32, page_size=PS)


def test_the_one_scan_ops_refuse_a_dense_head_under_layer_kinds(model):
    from paddle_tpu.core.registry import get_op

    attrs = fam.spec_of(model[0]).block.attrs()
    with pytest.raises(BlockNotSupportedError, match="dense FFN"):
        get_op("transformer_stack_generate").fn(
            dict(attrs, max_new_tokens=1), {
                "Prompt": [jnp.zeros((1, 4), jnp.int32)], **{
                    k: [jnp.zeros((1,))] for k in (
                        "TokEmb", "FinalLnS", "HeadW", "Ln1S", "QkvW",
                        "OutW", "Ln2S", "DenseGateW", "DenseUpW",
                        "DenseDownW", "RouterW", "MoeGateW", "MoeUpW",
                        "MoeDownW", "SharedGateW", "SharedUpW",
                        "SharedDownW")}}, None)


def test_spec_names_the_blocks_planes_and_counts_its_layer(model):
    spec = fam.spec_of(model[0])
    assert spec.layers_of(False) == 2 and spec.pool_layers(False) == 3
    assert spec.layers_of(True) == spec.pool_layers(True) == 6
    assert spec.plane_layers("dense_up_w") == 1
    assert spec.plane_layers("router_w") == 7
    assert spec.plane_layers("qkv_w") == 8
    names = spec.param_names()
    assert "mtp.proj_w" in names and "mtp_stack.stack_qkv_w" in names
    assert spec.draft_param_count() == sum(
        int(np.prod(model[1][n].shape)) for n in names
        if n.startswith("mtp"))
    assert spec.draft_planes()[0][1:3] == ("proj_w", [128, 64])
    again = LMSpec(**{**dataclasses.asdict(spec)})
    assert again.block == spec.block and again.block.draft_block
    assert not spec.block.draft().draft_block


# ---------------------------------------------------------------------------
# (f) the verify walk in interpret mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("dtype,width,heads", [
    (jnp.float32, 64, 8), (jnp.bfloat16, 128, 8),   # 8 query / 2 cached heads
    (jnp.float32, 64, 2)])                          # one query a cached head
def test_the_verify_walk_is_reference_attention_at_two_positions(
        window, dtype, width, heads):
    L, N, ps, P = 2, 24, 16, 4
    d_head = width // 2
    rng = np.random.default_rng(1)
    ck, cv = (jnp.asarray(rng.standard_normal((L, N, ps, width)), dtype)
              for _ in range(2))
    table = np.zeros((3, P), np.int32)
    for s, pages in enumerate([[3], [5, 9, 2], [7, 2, 11, 4]]):
        table[s, :len(pages)] = pages
    # the second position crosses a page edge in row 1
    lengths = jnp.asarray([5, 2 * ps, 3 * ps + 6], jnp.int32)
    q = jnp.asarray(2 * rng.standard_normal((3, heads, 2, d_head)), dtype)
    got = paged_attention.paged_attention_verify(
        q, ck, cv, jnp.int32(1), jnp.asarray(table), lengths,
        interpret=True, window=window)
    assert got.shape == (3, 2, heads * d_head)
    kv = width // d_head
    more = {} if window is None else dict(
        window=window, k_pos0=jnp.zeros((3,), jnp.int32))
    want = reference_attention(
        q, _gather_pages(ck, 1, jnp.asarray(table), kv),
        _gather_pages(cv, 1, jnp.asarray(table), kv), causal=True,
        q_pos0=lengths - 1, **more)
    want = want.transpose(0, 2, 1, 3).reshape(3, 2, -1)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
