"""A vision tower inside the prefill unit, three-axis rotary (``rope="mrope"``)
and learned sparse attention over K and V pages (``LMSpec(index_topk=,
index_pool=1, vision=VisionSpec(..))``) — at a tiny size on the CPU against
the plain float32 reference in ``benchmark/families/dsa_gqa_moe_vl.py`` (the
tower over whole frames, the indexer scored against every position and picked
by a full sort, attention a masked softmax over ALL positions, every expert
dense): d 32, 4 / 2 heads of 16, two layers, an indexer of 2 heads of 8 picking
16 tokens, 8 experts top-2, a two-block tower over 16 x 16 frames (4 merged
rows a frame), through the normal path (``GenerationEngine(spec, ..,
media_resolver=)`` and ``Server.submit({"prompt", "media"})``: pages of 8,
chunks of 16).

Tolerances. float32 everywhere: the program (chunked prefill with the tower
in the unit, indexer keys through the cache, the exact top-k and the gather of
what it picked) and the reference run the same arithmetic in another order:
observed 2e-6 on log-probs, the bound is 2e-5; every wrong model of the
reference's ``VARIANTS`` that the traffic reaches lies >= 5e-3 away."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import dsa_gqa_moe_vl as fam
from paddle_tpu.kernels.paged_attention import (paged_attention_decode,
                                                paged_attention_prefill)
from paddle_tpu.lm_spec import Block, LMSpec, VisionSpec
from paddle_tpu.ops import pipeline_ops
from paddle_tpu.serving import Server
from paddle_tpu.serving.errors import BadRequestError

F32_TOL = 2e-5
WRONG_TOL = 5e-3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = {"slots": 3, "page_size": 8, "n_pages": 40, "max_len": 96,
          "prompt_buckets": [8, 16], "prefill_batch_buckets": [1, 2],
          "prefill_chunk": 16}
NEW = 10
#: (text ids before the clip, frames, ids after it): a clip inside index_topk
#: 16; one whose frames straddle chunks of 16 and pages of 8; the same past
#: three times index_topk; a prompt of text alone; two clips
PROMPTS = {"short-clip": [(5, 1, 3)], "three-chunks": [(5, 5, 6)],
           "past-topk": [(3, 9, 7)], "text-only": [(37, 0, 0)],
           "two-clips": [(4, 2, 3), (2, 3, 5)]}
WRONG_AT = ("three-chunks", "past-topk")


def tiny_config(**top):
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "keye2-tiny.json")) as f:
        config = json.load(f)
    config.update(top)
    return config


def _prompt(config, parts, seed=0):
    rng = np.random.default_rng(seed)
    v = fam.vision_of(config)
    ids = []
    for before, frames, after in parts:
        ids += list(rng.integers(0, 90, before))
        if frames:
            ids += [v.vision_start_id] + [v.video_pad_id] * (
                frames * v.tokens_per_frame) + [v.vision_end_id]
        ids += list(rng.integers(0, 90, after))
    return np.asarray(ids, np.int64)


def _engine(config, seed=7, **engine_kw):
    eng, _ = fam.build_engine(config, {"engine": ENGINE}, seed, **engine_kw)
    return eng


@pytest.fixture(scope="module")
def served():
    """One float32 engine, the prompts through its own ticks (pixels by its
    resolver): -> {name: (errors by variant, emitted, served positions,
    pick misses by variant)}."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config, beam_width=8)
    w = fam.weights_of(None, eng.scope)
    bank = fam._ENGINES[id(config)][1]
    out = {"config": config, "eng": eng, "w": w, "bank": bank}
    for name, parts in PROMPTS.items():
        eng.prefix_index.clear()        # every prompt cold: every unit runs
        out[name] = fam.served_errors(
            config, w, eng, _prompt(config, parts), NEW,
            variants=("", "no_selection") + (
                tuple(fam.VARIANTS) if name in WRONG_AT else ()), bank=bank)
    out["counters"] = eng.metrics.snapshot()["counters"]
    out["gauges"] = eng.metrics.snapshot()["gauges"]
    return out


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_prefill_in_chunks_then_decode_agree_with_the_full_forward(served,
                                                                   name):
    errs, again, at, _ = served[name]
    n = _prompt(served["config"], PROMPTS[name]).size
    assert again.size == n + NEW
    assert at.size == -(-n // 16) + NEW - 1     # chunk ends + decode steps
    assert max(errs[""]) < F32_TOL


@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_the_pick_is_the_reference_s(served, name):
    """Scoring the indexer keys the ENGINE cached (written across chunk and
    page boundaries, then a token a tick) picks exactly the positions the
    reference's full sort picks, ties included."""
    assert max(served[name][3][""]) == 0.0


@pytest.mark.parametrize("variant", [
    "recent_pick", "no_selection", "no_mrope", "no_vision",
    "no_qk_norm"])
def test_every_wrong_model_lies_far_from_the_engine(served, variant):
    for name in WRONG_AT:
        assert max(served[name][0][variant]) > WRONG_TOL, (variant, name)


@pytest.fixture(scope="module")
def check_lines(served):
    """The benchmark's own check (``fam.check_readings``: what
    ``reference_logit_gaps`` hands the serve driver) on two requests the
    engine answered, against the reference and every wrong model."""
    config, eng = served["config"], served["eng"]
    results = []
    for name in WRONG_AT:
        prompt = _prompt(config, PROMPTS[name])
        eng.prefix_index.clear()
        results.append((prompt.size, np.asarray(eng.generate_all(
            [{"prompt": prompt}], max_new_tokens=NEW)[0])))
    return fam.check_readings(config, served["w"], results,
                              variants=("",) + tuple(fam.VARIANTS))


@pytest.mark.parametrize("variant", [""] + sorted(fam.VARIANTS))
def test_the_check_passes_the_right_model_and_no_wrong_one(check_lines,
                                                           variant):
    """The four scaled readings against the one limit, as the serve driver
    compares them: a float32 engine reads zeros against the reference and
    over the limit against every variant, the lower precisions included."""
    line = check_lines[variant]
    assert len(line["scaled"]) == 4
    assert line["replays_equal_to_timed"] == len(WRONG_AT)
    if variant:
        assert max(line["scaled"]) > fam.CHECK_LOGPROB_TOL
    else:
        assert max(line["scaled"]) < F32_TOL


def test_a_context_under_index_topk_selects_nothing(served):
    errs, _, at, _ = served["short-clip"]
    inside = at < 16
    assert inside.any()
    assert np.asarray(errs["no_selection"])[inside].max() < F32_TOL
    assert np.asarray(served["past-topk"][0]["no_selection"]).max() \
        > WRONG_TOL


def test_selection_media_and_memory_are_counted(served):
    c, g = served["counters"], served["gauges"]
    spec = fam.spec_of(served["config"])
    assert c["dsa_layer_calls"] == 2 * c["dsa_calls"]   # every layer selects
    assert c["dsa_groups_scored"] > c["dsa_rows_attended"] / 2 > 0
    frames = sum(f for parts in PROMPTS.values() for _, f, _ in parts)
    assert c["media_spans_admitted"] == 5       # one a clip, resolved
    assert c["vision_tokens_prefilled"] == 4 * frames
    # a frame two chunks share is encoded with each
    assert frames <= c["vision_frames_encoded"] <= 2 * frames
    assert c["media_bytes_fed"] == c["vision_frames_encoded"] * 16 * 16 * 3
    assert g["mem/index_bytes_per_token"] == 2 * 8 * 4
    assert g["mem/vision_param_bytes"] == 4 * spec.vision_param_count()
    assert spec.n_params() > spec.vision_param_count() > 0


# -- positions -------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PROMPTS))
def test_mrope_ids_of_text_clip_text(served, name):
    config = served["config"]
    v = fam.vision_of(config)
    prompt = _prompt(config, PROMPTS[name])
    spans, ids, row = v.media_layout(prompt)
    assert [f for _, f in spans] == [f for _, f, _ in PROMPTS[name] if f]
    text = row < 0
    assert (ids[text, 0] == ids[text, 1]).all() \
        and (ids[text, 1] == ids[text, 2]).all()
    assert ids[0].tolist() == [0, 0, 0]
    nxt = 0
    for first, frames in spans:
        b = int(ids[first - 1, 0]) + 1          # the start id's + 1
        k = np.arange(frames * 4)
        want = b + np.stack([k // 4, k % 4 // 2, k % 2], axis=1)
        assert (ids[first:first + frames * 4] == want).all()
        assert (row[first:first + frames * 4] == nxt + k).all()
        nxt += frames * 4
        # the text after the clip resumes at b + max(F, grid / merge)
        assert ids[first + frames * 4, 0] == b + max(frames, 2)
    if not spans:
        assert (ids[:, 0] == np.arange(prompt.size)).all()


def test_a_decoding_slot_carries_its_rotary_offset(served):
    """After a clip the next text id is NOT the sequence index: the tick is
    fed last id + 1 - sequence length for the slot, and its position stays
    the cache index."""
    config, eng = served["config"], served["eng"]
    prompt = _prompt(config, PROMPTS["past-topk"], seed=5)
    _, ids, _ = fam.vision_of(config).media_layout(prompt)
    want = int(ids[-1, 0]) + 1 - prompt.size
    assert want < 0             # 9 frames of 4 rows take 9 ids, not 36
    seen = []
    run = eng.executor.run

    def spy(prog, feed=None, **kw):
        if feed is not None and "serving.rope_off" in feed:
            live = np.flatnonzero(feed["serving.pos"] >= prompt.size)
            seen.extend((int(feed["serving.rope_off"][s]),
                         int(feed["serving.pos"][s])) for s in live)
        return run(prog, feed=feed, **kw)

    eng.executor.run = spy
    try:
        eng.generate_all([prompt], max_new_tokens=4)
    finally:
        eng.executor.run = run
    assert [o for o, _ in seen] == [want] * 3
    assert [p for _, p in seen] == [prompt.size + i for i in range(3)]


# -- media by payload and by resolver -----------------------------------------
def test_media_through_the_payload_and_through_the_resolver_are_the_same_bits(
        served):
    config, eng, bank = served["config"], served["eng"], served["bank"]
    prompt = _prompt(config, PROMPTS["two-clips"], seed=3)
    spans, _, _ = fam.vision_of(config).media_layout(prompt)
    media = [fam.clip_frames(bank, prompt, s) for s in spans]
    eng.prefix_index.clear()
    a_calls, a_out, _ = fam.served(eng, prompt, 6)
    eng.prefix_index.clear()
    b_calls, b_out, _ = fam.served(eng, prompt, 6, media=media)
    assert np.array_equal(a_out, b_out) and a_out[:prompt.size].tolist() \
        == prompt.tolist()              # the result echoes the submitted ids
    for (pa, va, ia), (pb, vb, ib) in zip(a_calls, b_calls):
        assert pa == pb and np.array_equal(va, vb) and np.array_equal(ia, ib)
    # other pixels: another answer
    eng.prefix_index.clear()
    other = [255 - m for m in media]
    c_calls, _, _ = fam.served(eng, prompt, 6, media=other)
    assert max(float(np.abs(va - vc).max())
               for (_, va, _), (_, vc, _) in zip(a_calls, c_calls)) > WRONG_TOL


def _frames(n, shape=(16, 16, 3), dtype=np.uint8):
    return np.zeros((n,) + shape, dtype)


@pytest.mark.parametrize("why,make", [
    ("a span that is not whole frames",
     lambda p: ({"prompt": np.delete(p, 8), "media": [_frames(5)]})),
    ("a span that is never closed",
     lambda p: ({"prompt": p[:20], "media": [_frames(5)]})),
    ("a placeholder outside a span",
     lambda p: ({"prompt": np.r_[p, 91], "media": [_frames(5)]})),
    ("a missing entry", lambda p: ({"prompt": p, "media": []})),
    ("one entry too many",
     lambda p: ({"prompt": p, "media": [_frames(5), _frames(1)]})),
    ("fewer frames than the span's ids",
     lambda p: ({"prompt": p, "media": [_frames(4)]})),
    ("a wrong frame shape",
     lambda p: ({"prompt": p, "media": [_frames(5, (8, 8, 3))]})),
    ("a wrong dtype",
     lambda p: ({"prompt": p, "media": [_frames(5, dtype=np.float32)]})),
])
def test_a_malformed_request_is_refused_at_submit(served, why, make):
    config, eng = served["config"], served["eng"]
    prompt = _prompt(config, PROMPTS["three-chunks"])
    srv = Server(eng)
    before = eng.metrics.counter("media_requests_refused")
    with pytest.raises(BadRequestError):
        srv.submit(make(prompt), max_new_tokens=2)
    assert eng.metrics.counter("media_requests_refused") == before + 1
    assert eng.active == 0


def test_a_span_without_media_needs_a_resolver_and_text_engines_refuse_media():
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config)
    eng.media_resolver = None
    prompt = _prompt(config, PROMPTS["short-clip"])
    with pytest.raises(BadRequestError, match="media_resolver"):
        Server(eng).submit({"prompt": prompt}, max_new_tokens=2)
    Server(eng)     # (a text prompt passes the check)
    eng.check_payload({"prompt": prompt[:4]})
    spec = LMSpec(vocab_size=32, d_model=16, n_layers=1, num_heads=2)
    from paddle_tpu.serving import GenerationEngine
    with pytest.raises(ValueError, match="no vision tower"):
        GenerationEngine(spec, media_resolver=lambda p, s: None)


# -- the spec's refusals say what they refuse -----------------------------------
def _kv(**kw):
    base = dict(num_heads=4, num_kv_heads=2, use_rope=True, norm="rms_norm",
                bias=False, rope_pairing="half", ffn="swiglu_moe",
                experts_per_tok=2, index_heads=2, index_dim=8, index_topk=16,
                index_pool=1)
    return Block(**{**base, **kw})


@pytest.mark.parametrize("kw,match", [
    (dict(index_pool=2), "index_pool 1"),
    (dict(layer_pattern=("full+rope", "window+rope"), window=8),
     "no layer_pattern"),
    (dict(index_heads=0), "index_heads"),
    (dict(rope="mrope"), "mrope_section"),
    (dict(rope="mrope", mrope_section=(2, 3), head_dim=10), "mrope_section"),
    (dict(rope="mrope", mrope_section=(2, 3, 3), rope_pairing="interleaved"),
     "half-split"),
    (dict(qk_norm_heads=True), "qk_norm"),
])
def test_the_spec_refuses_what_it_cannot_run(kw, match):
    with pytest.raises(ValueError, match=match):
        _kv(**kw)


def test_the_spec_counts_the_tower_and_names_its_planes():
    _kv()       # selection on a stack of full-attention K/V layers: in
    spec = fam.spec_of(tiny_config())
    names = spec.param_names()
    assert "vision.patch_w" in names and "vision.stack_qkv_w" in names \
        and "lm_stack.stack_idx_k_w" in names
    assert spec.block.attrs()["mrope_section"] == [2, 3, 3]
    assert Block.from_attrs(spec.block.attrs()) == spec.block
    with pytest.raises(ValueError, match="pairs of a head"):
        LMSpec(vocab_size=32, d_model=32, n_layers=1, num_heads=2,
               use_rope=True, rope_pairing="half", rope="mrope",
               mrope_section=(2, 2, 2))
    with pytest.raises(ValueError, match="whole patches"):
        VisionSpec(image_size=15, patch_size=4)


# -- the pick as a mask on the K/V walks (interpret mode) ------------------------
PS, DH, HKV, H, DI, HI = 16, 128, 1, 2, 8, 2
N, P, TOPK = 20, 8, 24


def _walk_case(rows, t, seed=0, ties=False):
    rng = np.random.default_rng(seed)
    b = len(rows)
    ck = jnp.asarray(rng.standard_normal((1, N, PS, HKV * DH)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((1, N, PS, HKV * DH)), jnp.float32)
    ci = rng.standard_normal((1, N, PS, DI))
    if ties:
        ci = rng.standard_normal((3, DI))[rng.integers(0, 3, ci.shape[:3])]
    table = jnp.asarray(np.stack([rng.permutation(np.arange(1, N))[:P]
                                  for _ in range(b)]).astype(np.int32))
    q = jnp.asarray(0.3 * rng.standard_normal((b, H, t, DH)), jnp.float32)
    q_i = jnp.asarray(rng.standard_normal((b, t, HI, DI)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((b, t, HI)), jnp.float32)
    start = jnp.asarray([r[0] for r in rows], jnp.int32)
    real = jnp.asarray([r[1] for r in rows], jnp.int32)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    return ck, cv, jnp.asarray(ci, jnp.float32), table, q, q_i, w_i, start, \
        real, pos


WALK_BLK = dict(num_heads=H, num_kv_heads=HKV, head_dim=DH, index_dim=DI,
                index_heads=HI, index_topk=TOPK)


@pytest.mark.parametrize("ties", [False, True], ids=["random", "forced-ties"])
@pytest.mark.parametrize("rows", [[(0, 16), (16, 16)], [(64, 16), (100, 16)],
                                  [(26, 13), (50, 0), (63, 1)]],
                         ids=["under-topk", "over-topk", "crossing-padding"])
def test_masked_kv_chunk_walk_is_the_gather_of_the_picked_rows(rows, ties):
    blk, t = _kv(**WALK_BLK), 16
    ck, cv, ci, table, q, q_i, w_i, start, real, pos = _walk_case(
        rows, t, ties=ties)
    want = pipeline_ops._dsa_attend_kv(blk, q, q_i, w_i, ck, cv, ci, 0,
                                       table, pos)          # [b, t, H dh]
    picked = pipeline_ops._dsa_pick(blk, q_i, w_i, ci, 0, table, pos)
    assert picked.shape == (len(rows), t, P * PS)
    got = paged_attention_prefill(q, ck, cv, 0, table, start, real,
                                  interpret=True, group_mask=picked,
                                  group_rows=1)
    for s, (_, n) in enumerate(rows):
        assert not np.asarray(got[s, n:]).any()
        if n:
            np.testing.assert_allclose(got[s, :n], want[s, :n], atol=1e-5,
                                       rtol=0)


@pytest.mark.parametrize("ties", [False, True], ids=["random", "forced-ties"])
def test_masked_kv_decode_walk_is_the_gather_of_the_picked_rows(ties):
    lengths = jnp.asarray([101, 0, 17, P * PS, 30], jnp.int32)
    rows, blk = [(int(n) - 1, 1) for n in lengths], _kv(**WALK_BLK)
    ck, cv, ci, table, q, q_i, w_i, _, _, pos = _walk_case(rows, 1, seed=1,
                                                           ties=ties)
    want = pipeline_ops._dsa_attend_kv(blk, q, q_i, w_i, ck, cv, ci, 0,
                                       table, pos)[:, 0]
    picked = pipeline_ops._dsa_pick(blk, q_i, w_i, ci, 0, table, pos)
    got = paged_attention_decode(q[:, :, 0], ck, cv, 0, table, lengths,
                                 interpret=True, group_mask=picked[:, 0],
                                 group_rows=1)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5, rtol=0)
