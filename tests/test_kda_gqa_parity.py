"""K/V pages beside a recurrent state, with prefix hits through state
snapshots (``LMSpec(layer_pattern=("gqa", "kda", "kda", "kda"))``: one
softmax grouped-query layer without positions and with a channel gate, then
three Kimi Delta Attention layers in Kimi Linear's own form — softplus decay,
beta in (0, 2), low-rank decay and gate projections — every layer a sigmoid
router with a selection bias over a held share of the experts) — at a tiny
size on the CPU against the plain float32 reference in
``benchmark/families/kda_gqa_moe_lm.py``: d 32, 4 query / 2 KV heads of 8, two
periods, experts 0..1 held of a router over 16 (one chip of eight), top-2,
through the normal path (``GenerationEngine(spec, .., snapshot_stride=,
n_snapshots=)``).

Tolerances. float32 everywhere: program (chunked prefill from the slot's
state or from a snapshot row, the recurrent step, paged grouped-query decode)
and reference (one scan over the sequence, full scores, no cache) run the
same arithmetic in another order: observed <= 3e-6 on log-probs, the bound is
2e-5; every wrong model of the reference's ``VARIANTS`` (and a snapshot
restored from the wrong boundary) lies >= 5e-3 away. A request that enters at
a snapshot is held to the SAME request served cold in the same engine BIT FOR
BIT: the stride's tokens are whole prefill chunks, so both are chunked at the
same positions, a snapshot row is a copy, and each row of a call is computed
alone (one prefill row a call)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import kda_gqa_moe_lm as fam
from paddle_tpu.kernels import kda
from paddle_tpu.lm_spec import Block, BlockNotSupportedError, LMSpec
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.serving.batcher import Request
from paddle_tpu.serving.paging import PagePool, PrefixIndex

F32_TOL = 2e-5
WRONG_TOL = 5e-3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MATH_VARIANTS = [v for v in fam.VARIANTS
                 if v not in ("bf16_stated_f32", "bf16_state")]
#: page 8, chunk 16, a snapshot every 2 pages = every chunk end
ENGINE = {"slots": 3, "page_size": 8, "n_pages": 80, "max_len": 128,
          "prompt_buckets": [8, 16], "prefill_batch_buckets": [1],
          "prefill_chunk": 16, "snapshot_stride": 2, "n_snapshots": 8,
          "mask_plane": 0}


def tiny_config(**assumed):
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "solar2-tiny.json")) as f:
        config = json.load(f)
    config["assumed"].update(assumed)
    return config


def bench_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "solar-open2-250b.json")) as f:
        return json.load(f)


def _engine(seed=7, config=None, beam=True, **engine):
    eng, _ = fam.build_engine(config or tiny_config(),
                              {"engine": {**ENGINE, **engine}}, seed,
                              **({"beam_width": 8} if beam else {}))
    return eng


def _counters(eng):
    return dict(eng.metrics.snapshot()["counters"])


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 96, size=n)


@pytest.fixture(scope="module")
def twice():
    """One float32 engine; prompts of 5, 21 and 53 tokens (12 new each)
    served cold and again from the snapshot the cold run left, each
    compared with the right model and the one whose state is bfloat16."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config=config)
    w = fam.weights_of(None, eng.scope)
    out = {"config": config, "eng": eng, "w": w}
    for n in (5, 21, 53):
        out[n] = fam.replay_twice(
            config, w, eng, _prompt(n, n), 12,
            variants=("", "bf16_state") if n == 53 else ("",))
    return out


@pytest.fixture(scope="module")
def wrong():
    """One period of the tiny model: a 37-token prompt cold and from its
    snapshot against every model of ``VARIANTS`` that leaves a piece of
    the mathematics out."""
    pt.set_amp(False)
    config = tiny_config()
    config.update(num_hidden_layers=4, gqa_layers=[0])
    eng = _engine(config=config)
    return fam.replay_twice(config, fam.weights_of(None, eng.scope), eng,
                            _prompt(37, 37), 12,
                            variants=("",) + tuple(MATH_VARIANTS))


@pytest.fixture(scope="module")
def plain():
    """One float32 engine without the beam plane, shared by the tests that
    only read tokens and counters (each empties the index first)."""
    pt.set_amp(False)
    return _engine(beam=False)


def _alone(eng, prompt, new):
    """``prompt`` served cold, with nothing beside it."""
    eng.prefix_index.clear()
    out = eng.generate_all([prompt], max_new_tokens=new)[0]
    eng.prefix_index.clear()
    return out


# ---------------------------------------------------------------------------
# the served path against the reference's full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [5, 21, 53])
@pytest.mark.parametrize("how", ["cold", "hit"])
def test_engine_equals_the_reference_in_float32(twice, n, how):
    r = twice[n][how]
    assert max(r["errs"][""]) < F32_TOL, max(r["errs"][""])
    assert max(r["state"][""]["rel_err"]) < 1e-5
    assert max(r["state"][""]["bits"]) == 0
    # 21 tokens enter at 16, 53 at 48: one prompt token at least is left
    assert r["restored"] == (how == "hit" and n > 16)
    assert r["hit_tokens"] == (0 if how == "cold" else (n - 1) // 16 * 16)


@pytest.mark.parametrize("variant", sorted(MATH_VARIANTS))
def test_every_left_out_piece_fails_the_float32_bound(wrong, variant):
    """No gate, beta not doubled, RoPE where there is none, the squashed
    decay, no gate bias, the router's faults: each reads >= 5e-3 where the
    right model reads under 2e-5."""
    for how in ("cold", "hit"):
        assert max(wrong[how]["errs"][""]) < F32_TOL
        assert max(wrong[how]["errs"][variant]) > WRONG_TOL, (
            variant, max(wrong[how]["errs"][variant]))


@pytest.mark.parametrize("how", ["cold", "hit"])
def test_a_bfloat16_state_fails_the_state_limit(twice, how):
    bits = twice[53][how]["state"]["bf16_state"]["bits"]
    assert max(bits) == 16 > fam.CHECK_STATE_BITS_TOL


# ---------------------------------------------------------------------------
# state snapshots
# ---------------------------------------------------------------------------
def _serve_logged(eng, prompt, new):
    """-> ({position: (top-k values, ids)}, emitted, the slot's state)."""
    calls, again, held = fam.served(eng, prompt, new)
    return {p: (v, i) for p, v, i in calls}, again, held


@pytest.mark.parametrize("n", [33, 53, 64])
def test_a_snapshot_hit_equals_the_cold_request_bit_for_bit(twice, n):
    """The SAME request cold and from a snapshot, in one engine: every
    log-prob served from the hit's first chunk on, every emitted token and
    the slot's final state are the cold run's to the last bit."""
    pt.set_amp(False)
    eng = twice["eng"]
    eng.prefix_index.clear()
    prompt = _prompt(100 + n, n)
    cold, out_c, held_c = _serve_logged(eng, prompt, 10)
    before = _counters(eng)
    hit, out_h, held_h = _serve_logged(eng, prompt, 10)
    c = _counters(eng)
    entered = (n - 1) // 16 * 16
    assert c["prefix_hit_tokens"] - before["prefix_hit_tokens"] == entered
    assert c["state_snapshots_restored"] \
        - before["state_snapshots_restored"] == 1
    assert np.array_equal(out_c, out_h)
    assert set(hit) == {p for p in cold if p >= entered}
    for p, (values, ids) in hit.items():
        assert np.array_equal(ids, cold[p][1])
        assert values.tobytes() == cold[p][0].tobytes(), p
    assert held_h.tobytes() == held_c.tobytes()


def test_a_hit_is_cut_back_to_the_deepest_boundary_with_a_snapshot(plain):
    """Two prompts share 44 tokens (5 full pages and a half): the pages
    match 40, the deepest snapshot boundary among them is 32; the 8 between
    are prefilled again and counted."""
    eng = plain
    shared = _prompt(1, 44)
    a = np.concatenate([shared, _prompt(2, 9)])
    b = np.concatenate([shared, _prompt(3, 7)])
    alone = _alone(eng, b, 4)
    eng.generate_all([a], max_new_tokens=4)
    before = _counters(eng)
    got = eng.generate_all([b], max_new_tokens=4)[0]
    c = _counters(eng)
    assert np.array_equal(got, alone)
    assert c["prefix_hit_tokens"] - before.get("prefix_hit_tokens", 0) == 32
    assert c["state_snapshot_cutback_tokens"] \
        - before["state_snapshot_cutback_tokens"] == 8
    assert c["prompt_tokens_admitted"] - before["prompt_tokens_admitted"] \
        == b.size


def test_a_snapshot_one_boundary_off_fails_the_bound(twice):
    """The restore is on the compared path: with the snapshot rows moved
    by one (a row then holds another boundary's state) the hit replay
    reads far over the bound, the cold replay stays right."""
    pt.set_amp(False)
    config, eng, w = twice["config"], twice["eng"], twice["w"]

    r = fam.replay_twice(config, w, eng, _prompt(9, 53), 8,
                         between=fam.misplace_snapshots)
    assert max(r["cold"]["errs"][""]) < F32_TOL
    assert r["hit"]["restored"] == 1
    assert max(r["hit"]["errs"][""]) > WRONG_TOL
    # ... and the check's own reading of the restore sees it
    assert max(r["hit"]["state_vs_cold"]) > fam.CHECK_RESTORE_STATE_TOL
    assert max(twice[53]["hit"]["state_vs_cold"]) == 0.0
    assert twice[53]["hit"]["logprob_vs_cold"] == 0.0


@pytest.mark.parametrize("fault", [False, True])
def test_the_restore_is_read_in_the_engine_that_took_the_snapshots(fault):
    """``restore_under_traffic`` (the cell's check on the TIMED engine, no
    beam plane): prompts the engine served under its own load enter at
    the snapshot rows it took then, and leave the state their cold
    prefill leaves, to the bit; with the rows moved by one they do not."""
    pt.set_amp(False)
    eng = _engine(beam=False)
    pre = _prompt(31, 48)
    prompts = [np.concatenate([pre, _prompt(32 + i, 5 + 4 * i)])
               for i in range(3)]
    eng.generate_all(prompts, max_new_tokens=4)     # three slots at once
    if fault:
        fam.misplace_snapshots(eng)
    got = fam.restore_under_traffic(eng, prompts[:2] + [_prompt(40, 7)])
    assert [r["state_snapshots_restored"] for r in got] == [1, 1, 0]
    assert [r["prefix_hit_tokens"] for r in got] == [48, 48, 0]
    assert all(r["bits"] == [23] * len(r["bits"]) for r in got)
    worst = [max(r["state_vs_cold"]) for r in got]
    if fault:
        assert min(worst[:2]) > fam.CHECK_RESTORE_STATE_TOL
        assert worst[2] == 0.0      # entered at no snapshot
    else:
        assert worst == [0.0, 0.0, 0.0]
        assert all(r["first_token_equal"] for r in got)
    # an engine without a snapshot pool has nothing to read
    assert fam.restore_under_traffic(
        _engine(beam=False, n_snapshots=0, snapshot_stride=0), prompts) == []


def test_snapshots_are_evicted_alone_and_with_their_pages():
    """Two rows for prompts with three boundaries each: the oldest row
    goes when a new one is needed (its pages stay: a later request enters
    at a shallower boundary); a pool too small for two prompts evicts
    pages, and a snapshot goes with the page that ends at it. Whatever is
    left, what is served equals the request served alone."""
    pt.set_amp(False)
    eng = _engine(beam=False, n_snapshots=2, n_pages=10)
    a, b = _prompt(21, 53), _prompt(22, 53)
    first = eng.generate_all([a], max_new_tokens=3)[0]
    c = _counters(eng)
    assert c["state_snapshots_taken"] == 3 and c["state_snapshots_evicted"] == 1
    assert eng.prefix_index.snapshots_in_use() == 2
    eng.generate_all([b], max_new_tokens=3)    # 7 of 9 pages: evicts a's
    c = _counters(eng)
    assert eng.prefix_index.stats()["evictions"] > 0
    assert c["state_snapshots_evicted"] >= 3
    assert np.array_equal(eng.generate_all([a], max_new_tokens=3)[0], first)
    index = eng.prefix_index
    assert index.snapshots_in_use() == len(index._snaps) <= 2
    assert not index._snap_pins and not index._snap_orphans


def test_a_pinned_snapshot_outlives_its_page():
    pool = PagePool(6, 4)
    index = PrefixIndex(pool, n_snapshots=1, snapshot_stride=1)
    page = pool.alloc()
    key = index.insert(b"", [1, 2, 3, 4], page)
    row = index.alloc_snapshot()
    assert index.attach_snapshot(key, row)
    matched, shared, pages, key_at, got = index.lookup_snapshot(
        np.asarray([1, 2, 3, 4, 5]), 4)
    assert (matched, shared, pages, key_at, got) == (4, 4, [page], key, row)
    index.pin_snapshot(row)
    pool.decref(page)
    index.clear()                       # the page's entry goes, the row not
    assert index.alloc_snapshot() is None and index.snapshot_evictions == 1
    index.unpin_snapshot(row)
    assert index.alloc_snapshot() == row


def test_two_arrivals_over_one_cold_prefix_prefill_it_once(plain):
    """The stampede: two requests with the same 48-token preamble admitted
    side by side. The second sits out while the first prefills, enters at
    the last boundary they share and prefills its own tail only."""
    eng = plain
    pre = _prompt(31, 48)
    a = np.concatenate([pre, _prompt(32, 6)])
    b = np.concatenate([pre, _prompt(33, 11)])
    alone = [_alone(eng, p, 5) for p in (a, b)]
    c0 = _counters(eng)
    got = eng.generate_all([a, b], max_new_tokens=5)
    c = {k: v - c0.get(k, 0) for k, v in _counters(eng).items()}
    assert all(np.array_equal(g, s) for g, s in zip(got, alone))
    assert c["state_prefix_waited"] == 1 and c["state_prefix_adopted"] >= 1
    # a: 48 + 6 tokens in four chunks; b: its 11-token tail in one
    assert c["prefill_chunks"] == 5
    assert c["prefix_hit_tokens"] == 48
    assert c["state_snapshots_restored"] == 1


def test_an_engine_without_a_snapshot_pool_is_the_engine_it_was(plain):
    """No ``n_snapshots``: the index is refused and counted, the prefill
    program has no snapshot feed, and what forks or re-enters still
    raises (with a pool too)."""
    pt.set_amp(False)
    was = _engine(beam=False, n_snapshots=0, snapshot_stride=0)
    assert was.prefix_index is None and not was._snapshots
    assert "serving.snap_from" not in was._plane(was.prefill_chunk)
    p = _prompt(5, 40)
    was.generate_all([p, p], max_new_tokens=2)
    c = _counters(was)
    assert c["state_refused_prefix_lookups"] == 2
    assert c.get("prefix_hit_tokens", 0) == 0
    with_pool = plain
    for eng in (was, with_pool):
        for meta in (dict(beam_size=2), dict(resume_tokens=[1, 2])):
            req = Request({"prompt": p}, dict(meta, max_new_tokens=2), None)
            eng.admit([req])
            with pytest.raises(BlockNotSupportedError):
                req.future.result(timeout=1)
        with pytest.raises(BlockNotSupportedError):
            eng.export_slot(0)
    with pytest.raises(BlockNotSupportedError):
        GenerationEngine(with_pool.spec, with_pool.scope,
                         share_cache_with=with_pool)


@pytest.mark.parametrize("kw", [dict(snapshot_stride=0),
                                dict(snapshot_stride=1, prefill_chunk=16),
                                dict(prefix_sharing=False)])
def test_a_snapshot_pool_needs_whole_chunks_between_boundaries(kw):
    pt.set_amp(False)
    with pytest.raises(ValueError, match="n_snapshots"):
        _engine(beam=False, **{"snapshot_stride": 2, **kw}) \
            if "prefix_sharing" not in kw else GenerationEngine(
                fam.spec_of(tiny_config()), pt.Scope(), slots=2,
                page_size=8, prefill_chunk=16, snapshot_stride=2,
                n_snapshots=2, max_seq_len=64, prefix_sharing=False)


# ---------------------------------------------------------------------------
# the recurrence with beta above 1 and an unbounded decay
# ---------------------------------------------------------------------------
def _kda_case(seed, b, t, H=2, K=16, V=16):
    rng = np.random.default_rng(seed)

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = l2(rng.normal(size=(b, t, H, K))) * K ** -0.5
    k = l2(rng.normal(size=(b, t, H, K)))
    v = rng.normal(size=(b, t, H, V))
    g = -np.exp(rng.uniform(np.log(1.0), np.log(16.0), (1, 1, H, 1))) \
        * np.log1p(np.exp(rng.normal(size=(b, t, H, K)) - 3.0))
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(b, t, H)) * 2 - 1.0))
    assert beta.max() > 1.5
    S0 = rng.normal(size=(b, H, K, V))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, S0)]


def _scan_rows(q, k, v, g, beta, S0):
    outs = [fam.kda_scan(q[i], k[i], v[i], g[i], beta[i], state=S0[i])
            for i in range(q.shape[0])]
    return (jnp.stack([o for o, _ in outs]),
            jnp.stack([s for _, s in outs]))


@pytest.mark.parametrize("form,t", [("chunked", 5), ("chunked", 64),
                                    ("chunked", 130), ("recurrent", 7),
                                    ("decode_step", 1)])
def test_beta_above_one_through_every_form(form, t):
    q, k, v, g, beta, S0 = _kda_case(t, 2, t)
    want_o, want_S = _scan_rows(q, k, v, g, beta, S0)
    if form == "decode_step":
        state = S0[None]                    # [L = 1, slots, H, K, V]
        o, state = kda.kda_decode_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                       beta[:, 0], state, 0, interpret=True)
        o, S = o[:, None], state[0]
    else:
        o, S = (kda.kda_chunked if form == "chunked"
                else kda.kda_recurrent)(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S, want_S, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the expert layer's shares
# ---------------------------------------------------------------------------
def test_the_eight_held_shares_add_up_to_the_uncut_layer():
    """One expert layer: the routed part of each of the eight shares (2 of
    16 experts each) plus the shared expert ONCE is the uncut layer."""
    config = tiny_config()
    rng = np.random.default_rng(4)
    d, f, E = 32, 16, 16
    p = {"router_w": rng.normal(size=(d, E)) * 0.5,
         "router_b": rng.normal(size=(E,)) * 0.05,
         "moe_gate_w": rng.normal(size=(E, d, f)) * 0.2,
         "moe_up_w": rng.normal(size=(E, d, f)) * 0.2,
         "moe_down_w": rng.normal(size=(E, f, d)) * 0.2,
         "shared_gate_w": rng.normal(size=(d, f)) * 0.2,
         "shared_up_w": rng.normal(size=(d, f)) * 0.2,
         "shared_down_w": rng.normal(size=(f, d)) * 0.2}
    p = {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}
    h2 = jnp.asarray(rng.normal(size=(24, d)), jnp.float32)
    whole = fam.expert_layer(config, p, h2, held=(0, E))
    total = 0.0
    for first in range(0, E, 2):
        share = {**p, **{k: p[k][first:first + 2]
                         for k in ("moe_gate_w", "moe_up_w", "moe_down_w")}}
        routed, shared = fam.expert_layer(config, share, h2,
                                          held=(first, 2), parts=True)
        total = total + routed
    np.testing.assert_allclose(total + shared, whole, rtol=1e-5, atol=1e-5)
    # ... and the PROGRAM's share is the reference's share
    from paddle_tpu.ops.moe_ops import moe_topk

    y, _, _ = moe_topk(h2, p["router_w"], p["moe_gate_w"][4:6],
                       p["moe_up_w"][4:6], p["moe_down_w"][4:6], 2, True,
                       score="sigmoid", bias=p["router_b"], held=(4, 2),
                       shared=(p["shared_gate_w"], p["shared_up_w"],
                               p["shared_down_w"]))
    share = {**p, **{k: p[k][4:6]
                     for k in ("moe_gate_w", "moe_up_w", "moe_down_w")}}
    np.testing.assert_allclose(
        y, fam.expert_layer(config, share, h2, held=(4, 2)),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------
def test_block_attrs_round_trip_and_planes_by_kind():
    spec = fam.spec_of(tiny_config())
    blk = spec.block
    assert Block.from_attrs(json.loads(json.dumps(blk.attrs()))) == blk
    assert blk.attn_kinds == ("gqa", "kda", "kda", "kda")
    assert spec.plane_layers("gqa_qkv_w") == 2 == spec.layers_of(False)
    assert spec.plane_layers("kda_a_down_w") == 6
    assert spec.plane_layers("router_w") == 8
    assert (spec.cache_pools, spec.cache_row_width) == (2, 16)
    assert [s[0] for s in spec.slot_state()] == ["KdaState", "KdaConv"]
    slots = blk.stack_slots()
    assert {"GqaQkvW", "GqaGateW", "GqaOutW", "KdaADownW", "KdaAUpW",
            "KdaGateDownW", "KdaGateUpW", "KdaGateB"} <= set(slots)
    assert "KdaAW" not in slots and "KdaGateW" not in slots


def test_the_published_widths_count_up_to_the_issues_arithmetic():
    spec = fam.spec_of(bench_config())
    assert spec.n_params() == 3_308_377_920
    assert spec.state_bytes_per_slot == 13_025_280
    assert spec.cache_bytes_per_token == 4096
    by_key = {key: int(np.prod(shape))
              for _, key, shape, _ in spec.stack_planes()}
    assert sum(v for k, v in by_key.items() if k.startswith("gqa_")) \
        == 109_051_904
    assert sum(v for k, v in by_key.items() if k.startswith("kda_")) \
        == 137_740_480
    assert sum(v for k, v in by_key.items()
               if k.startswith(("router_", "shared_"))) == 17_039_680


@pytest.mark.parametrize("kw,msg", [
    (dict(layer_pattern=("gqa", "mla")), "latent block"),
    (dict(layer_pattern=("kda", "kda")), "ONE kind that caches"),
    (dict(layer_pattern=("gqa", "window+rope")), "every entry"),
    (dict(attn_gate="channel", layer_pattern=("kda", "mla"), attn="mla",
          kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
          v_head_dim=4), "channel"),
    (dict(kda_decay="bounded", kda_lower_bound=0.0), "kda_lower_bound"),
    (dict(kda_decay="linear"), "kda_decay"),
    (dict(kda_proj_rank=-1), "kda_proj_rank"),
])
def test_block_refuses_what_it_cannot_mean(kw, msg):
    base = dict(num_heads=2, use_rope=True, norm="rms_norm", bias=False,
                ffn="swiglu_moe", experts_per_tok=1, kda_head_dim=4,
                layer_pattern=("gqa", "kda"))
    with pytest.raises(ValueError, match=msg):
        Block(**{**base, **kw})


def test_the_cells_files_name_this_family_and_its_snapshot_pool():
    with open(os.path.join(ROOT, "benchmark", "mixes",
                           "agent-poisson-16k.json")) as f:
        mix = json.load(f)
    e, config = mix["engine"], bench_config()
    assert config["family"] == "kda_gqa_moe_lm"
    assert (e["snapshot_stride"] * e["page_size"]) % e["prefill_chunk"] == 0
    assert e["n_snapshots"] == 64 and e["slots"] == 64
    assert mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    sp = mix["prompt"]["shared_prefix"]
    assert sp["tokens"] % (e["snapshot_stride"] * e["page_size"]) == 0
    assert LMSpec  # (the spec builds: test_the_published_widths_...)
