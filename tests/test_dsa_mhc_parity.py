"""Learned sparse attention inside a NoPE latent layer (``LMSpec(index_topk=,
index_heads=, index_dim=, index_pool=, qk_rope_head_dim=0)``), the
manifold-constrained residual (``residual="mhc"``) and the clamped SwiGLU
(``ffn_limit``) — at a tiny size on the CPU against the plain float32
reference in ``benchmark/families/dsa_kda_moe_lm.py`` (the indexer scored
against every group and picked by a full sort, attention a masked softmax over
ALL positions, KDA token by token): d 32, 2 heads, published positions 2..6 of
a stack whose every fourth layer is sparse (kda, SPARSE, kda, kda, kda; layer 0
dense), 4 streams, an indexer of 4 heads of 8 over groups of 4 picking 16
tokens, experts 0..1 of a router over 8, through the normal path
(``GenerationEngine(spec, ..)``: pages of 8, chunks of 16).

Tolerances. float32 everywhere: the program (chunked prefill, pooled keys
through the cache, the exact top-k and the gather of what it picked, the
absorbed attention) and the reference run the same arithmetic in another
order: observed 1e-6 on log-probs, the bound is 2e-5; every wrong model of the
reference's ``VARIANTS`` that the traffic reaches lies >= 5e-3 away."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import dsa_kda_moe_lm as fam
from paddle_tpu.lm_spec import Block, BlockNotSupportedError, LMSpec
from paddle_tpu.ops import pipeline_ops
from paddle_tpu.ops.moe_ops import moe_topk
from paddle_tpu.serving import GenerationEngine

F32_TOL = 2e-5
WRONG_TOL = 5e-3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = {"slots": 3, "page_size": 8, "n_pages": 40, "max_len": 96,
          "prompt_buckets": [8, 16], "prefill_batch_buckets": [1, 2],
          "prefill_chunk": 16}
#: prompt lengths: inside index_topk 16; a second chunk that ends INSIDE a
#: group (21 = 16 + 5) and a page; three chunks; past three times index_topk
PROMPTS = (5, 21, 37, 50)
NEW = 12
WRONG_AT = (21, 50)     # where every wrong model is run too


def tiny_config(**top):
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "glm53f-tiny.json")) as f:
        config = json.load(f)
    config.update(top)
    return config


def _engine(config, seed=7, **engine_kw):
    eng, _ = fam.build_engine(config, {"engine": ENGINE}, seed, **engine_kw)
    return eng


@pytest.fixture(scope="module")
def served():
    """One float32 engine, four requests through its own ticks: ->
    {prompt_len: (errors by variant, emitted, served positions, more)}."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config, beam_width=8)
    w = fam.weights_of(None, eng.scope)
    rng = np.random.default_rng(0)
    out = {n: fam.served_errors(
        config, w, eng, rng.integers(0, 96, size=n), NEW,
        variants=("", "no_selection") + (
            tuple(fam.VARIANTS) if n in WRONG_AT else ())) for n in PROMPTS}
    out["counters"] = eng.metrics.snapshot()["counters"]
    out["gauges"] = eng.metrics.snapshot()["gauges"]
    return out


@pytest.mark.parametrize("n", PROMPTS)
def test_prefill_in_chunks_then_decode_agree_with_the_full_forward(served, n):
    errs, again, at, more = served[n]
    assert again.size == n + NEW
    assert at.size == -(-n // 16) + NEW - 1     # chunk ends + decode steps
    assert max(errs[""]) < F32_TOL


@pytest.mark.parametrize("n", PROMPTS)
def test_the_pick_is_the_reference_s_and_the_state_float32(served, n):
    """Scoring the pooled keys the ENGINE cached (running means written
    across chunk, page and group boundaries, then a token a tick) picks
    exactly the groups the reference's full sort picks."""
    more = served[n][3][""]
    assert max(more["pick_miss"]) == 0.0
    assert max(more["bits"]) == 0 and max(more["rel_err"]) < 1e-5


@pytest.mark.parametrize("variant", [
    "recent_pick", "no_selection", "no_tail", "no_mhc", "uniform_mix",
    "no_decay", "no_shared_expert"])
def test_every_wrong_model_lies_far_from_the_engine(served, variant):
    """... at the contexts past ``index_topk`` (a context inside it selects
    nothing: the selection's faults cannot show there)."""
    for n in WRONG_AT:
        assert max(served[n][0][variant]) > WRONG_TOL, (variant, n)


def test_a_context_under_index_topk_is_the_unselected_latent_layer(served):
    """While every group before the query's own can be picked the layer IS
    latent attention over the whole context: the reference without its
    indexer agrees there, and stops agreeing where selection starts."""
    errs, _, at, _ = served[5]
    inside = at < 16
    assert inside.all()         # positions 4 .. 15
    full = np.asarray(errs["no_selection"])
    assert full[inside].max() < F32_TOL
    assert np.asarray(served[50][0]["no_selection"]).max() > WRONG_TOL


def test_the_selection_is_counted_from_the_fed_planes(served):
    c, g = served["counters"], served["gauges"]
    assert c["dsa_layer_calls"] > 0     # one sparse layer a call
    # every query scored the groups before its own and read at most
    # index_topk rows where the walk would read its whole context
    assert 0 < c["dsa_rows_attended"] < c["dsa_rows_in_reach"]
    assert c["dsa_rows_attended"] <= 16 * c["dsa_queries"]
    assert 0 < c["dsa_dense_queries"] < c["dsa_queries"]
    assert c["dsa_groups_scored"] > 0
    # (what the indexer's pool costs a token: the spec's number, a gauge
    # since PR 60)
    assert g["mem/index_bytes_per_token"] == 1 * 8 / 4 * 4
    assert "prefill_attn_pages_read" not in c   # nothing walks a table


# ---------------------------------------------------------------------------
# the pooled keys' pool
# ---------------------------------------------------------------------------
def _write(pool, blk, k, pos0, n_valid, table, t):
    """One call of ``_index_write`` for a single row."""
    pos = pos0 + jnp.arange(t, dtype=jnp.int32)[None, :]
    valid = jnp.arange(t)[None, :] < n_valid
    page = jnp.where(valid, table[jnp.clip(pos // 8, 0, table.size - 1)], 0)
    return pipeline_ops._index_write(
        blk, pool, 0, jnp.pad(k, ((0, t - k.shape[0]), (0, 0)))[None], page,
        jnp.where(valid, pos % 8, 0), pos, valid)


@pytest.mark.parametrize("splits", [(16, 5), (3, 16, 2), (6, 1, 1, 1, 12),
                                    (13,), (1,) * 9])
def test_a_group_s_row_is_the_running_mean_across_calls(splits):
    """A chunk that ends inside a group leaves the group's row at the sum of
    its tokens so far / 4; the next call (a chunk, or a token a tick) adds
    its own on top; a page taken again needs no clearing."""
    blk = dataclasses.replace(fam.spec_of(tiny_config()).block)
    rng = np.random.default_rng(1)
    n = sum(splits)
    keys = jnp.asarray(rng.normal(size=(n, 8)), jnp.float32)
    table = jnp.asarray([3, 1, 2, 4], jnp.int32)
    pool = jnp.full((1, 6, 2, 8), 7.0)          # stale rows everywhere
    done = 0
    for part in splits:
        t = 16 if part > 1 else 1
        pool = _write(pool, blk, keys[done:done + part], done, part, table, t)
        done += part
        got = np.asarray(pool[0, table]).reshape(-1, 8)
        padded = np.zeros((-(-done // 4) * 4, 8), np.float32)
        padded[:done] = keys[:done]
        want = padded.reshape(-1, 4, 8).sum(axis=1) / 4
        np.testing.assert_allclose(got[:want.shape[0]], want, atol=1e-6)
    assert np.all(np.asarray(pool[0, 5]) == 7.0)    # no other page touched


# ---------------------------------------------------------------------------
# the residual streams
# ---------------------------------------------------------------------------
def _half_planes(rng, n, d):
    hc = n * n + 2 * n
    b = rng.normal(0, 0.5, hc)
    b[2 * n:] += 1.5 * np.eye(n).reshape(-1)
    # (z = x~ Phi has a standard deviation of ~0.6 here; at twice that the
    # 20 rounds leave a row sum 6e-5 from 1: the bound below is not free)
    return {"hc1_w": jnp.asarray(rng.normal(0, 0.05, (n * d, hc)),
                                 jnp.float32),
            "hc1_alpha": jnp.asarray(rng.uniform(0.5, 1.0, 3), jnp.float32),
            "hc1_b": jnp.asarray(b, jnp.float32)}


def test_the_stream_mixes_are_the_reference_s_and_doubly_stochastic():
    config = tiny_config()
    blk = fam.spec_of(config).block
    rng = np.random.default_rng(2)
    n, d, T = 4, 32, 11
    p = _half_planes(rng, n, d)
    X = jnp.asarray(rng.normal(size=(T, n, d)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    pre, post, res = fam.hc_mixes(config, p, X, "hc1")
    u, (post_p, res_p) = pipeline_ops._res_read(blk, p, X[None], "hc1")
    np.testing.assert_allclose(u[0], jnp.einsum("tn,tnd->td", pre, X),
                               atol=1e-5)
    np.testing.assert_allclose(post_p[0], post, atol=1e-6)
    np.testing.assert_allclose(res_p[0], res, atol=1e-6)
    # 20 rounds: rows and columns sum to 1; neither identity nor uniform
    assert np.abs(np.asarray(res).sum(axis=-1) - 1).max() < 1e-5
    assert np.abs(np.asarray(res).sum(axis=-2) - 1).max() < 1e-5
    assert np.all(np.asarray(res) > 0)
    assert np.abs(np.asarray(res) - 0.25).max() > 0.1
    assert np.abs(np.asarray(res) - np.eye(n)).max() > 0.1
    assert np.asarray(pre).std(axis=0).min() > 1e-3     # moves with the token
    out = pipeline_ops._res_write(X[None], y[None], (post_p, res_p))[0]
    want = jnp.einsum("tij,tjd->tid", res, X) + post[..., None] * y[:, None]
    np.testing.assert_allclose(out, want, atol=1e-5)


def test_one_stream_is_the_identity_and_one_add():
    """``residual="add"``: the pair is the carry itself and ``x + y``, so the
    programs of every other configuration lower to the text they lowered to
    (``tools/lowered_text.py`` hashes them, parent against change)."""
    blk = Block(num_heads=2)
    x, y = jnp.ones((1, 3, 8)), jnp.ones((1, 3, 8))
    assert pipeline_ops._res_read(blk, {}, x, "hc2") == (x, None)
    eqns = jax.make_jaxpr(lambda a, b: pipeline_ops._res_write(a, b, None))(
        x, y).eqns
    assert [e.primitive.name for e in eqns] == ["add"]


# ---------------------------------------------------------------------------
# the clamp
# ---------------------------------------------------------------------------
def _expert_planes(config, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    d, f, E = 32, 16, 8
    p = {"router_w": rng.normal(0, 0.3, (d, E)),
         "router_b": rng.normal(0, 0.05, E),
         "moe_gate_w": rng.normal(0, 0.2 * scale, (E, d, f)),
         "moe_up_w": rng.normal(0, 0.2 * scale, (E, d, f)),
         "moe_down_w": rng.normal(0, 0.2, (E, f, d)),
         "shared_gate_w": rng.normal(0, 0.2 * scale, (d, f)),
         "shared_up_w": rng.normal(0, 0.2 * scale, (d, f)),
         "shared_down_w": rng.normal(0, 0.2, (f, d))}
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def _moe(config, p, x, held, limit):
    first, count = held
    return moe_topk(
        x, p["router_w"], p["moe_gate_w"][first:first + count],
        p["moe_up_w"][first:first + count],
        p["moe_down_w"][first:first + count], 2, True, score="sigmoid",
        bias=p["router_b"], held=held, routed_scale=2.5, limit=limit,
        shared=(p["shared_gate_w"], p["shared_up_w"], p["shared_down_w"]))[0]


@pytest.mark.parametrize("scale,bites", [(1.0, False), (12.0, True)])
def test_the_clamp_is_the_reference_s_where_it_bites(scale, bites):
    """Seeded pre-activations never reach 10; scaled up they do, and the
    clamped layer then differs from the unclamped one and equals the
    reference's, routed experts and shared expert alike."""
    pt.set_amp(False)
    config = tiny_config()
    p = _expert_planes(config, 3, scale)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 32)),
                    jnp.float32)
    held = (0, 8)
    ref_p = {**p, "moe_gate_w": p["moe_gate_w"], "moe_up_w": p["moe_up_w"]}
    want = fam.expert_layer(config, ref_p, x, held=held)
    got = _moe(config, p, x, held, 10.0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    free = _moe(config, p, x, held, None)
    differs = float(jnp.abs(free - got).max())
    assert (differs > 1.0) if bites else (differs == 0.0)
    no_clamp = fam.expert_layer(config, ref_p, x, held=held,
                                variant="no_clamp")
    np.testing.assert_allclose(free, no_clamp, rtol=2e-4, atol=2e-4)


def test_the_dense_feed_forward_clamps_too():
    pt.set_amp(False)
    config = tiny_config()
    blk = dataclasses.replace(fam.spec_of(config).block, residual="add",
                              hc_mult=1, hc_iters=0)
    rng = np.random.default_rng(5)
    p = {"ln2_s": jnp.ones(32),
         "dense_gate_w": jnp.asarray(rng.normal(0, 3, (32, 48)), jnp.float32),
         "dense_up_w": jnp.asarray(rng.normal(0, 3, (32, 48)), jnp.float32),
         "dense_down_w": jnp.asarray(rng.normal(0, .2, (48, 32)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(1, 9, 32)), jnp.float32)
    got, _ = pipeline_ops._attn_out_ffn(blk, p, x, None, dense=True,
                                        mixer=False)
    h2 = fam._rms(x[0], p["ln2_s"], config["rms_norm_eps"])
    want = x[0] + fam.clamped_glu(config, h2, p["dense_gate_w"],
                                  p["dense_up_w"], p["dense_down_w"])
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)
    loose = x[0] + fam.clamped_glu(config, h2, p["dense_gate_w"],
                                   p["dense_up_w"], p["dense_down_w"],
                                   "no_clamp")
    assert float(jnp.abs(loose - want).max()) > 1.0


# ---------------------------------------------------------------------------
# the chip's share of the experts
# ---------------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer():
    """Expert parallelism over four chips of two experts each: the routed
    parts of the four shares plus the shared expert ONCE are the uncut
    layer; the program computes each share's part."""
    pt.set_amp(False)
    config = tiny_config()
    p = _expert_planes(config, 6, 6.0)      # (the clamp bites here too)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(16, 32)),
                    jnp.float32)
    whole = fam.expert_layer(config, p, x, held=(0, 8))
    total = jnp.zeros_like(x)
    for first in range(0, 8, 2):
        held = (first, 2)
        share = {**p, **{k: p[k][first:first + 2] for k in (
            "moe_gate_w", "moe_up_w", "moe_down_w")}}
        routed, shared = fam.expert_layer(config, share, x, held=held,
                                          parts=True)
        total = total + routed
        np.testing.assert_allclose(_moe(config, p, x, held, 10.0),
                                   routed + shared, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(total + shared, whole, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# what the spec and the engine refuse
# ---------------------------------------------------------------------------
def _spec(**kw):
    base = dict(
        vocab_size=96, d_model=32, n_layers=2, num_heads=2, use_rope=True,
        norm="rms_norm", bias=False, attn="mla", q_lora_rank=12,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=0,
        v_head_dim=8, layer_pattern=("kda", "mla"), kda_head_dim=16,
        ffn="swiglu_moe", num_experts=4, experts_per_tok=2, d_expert=16,
        index_heads=4, index_dim=8, index_topk=16, index_pool=4,
        residual="mhc", hc_mult=4, hc_iters=20, ffn_limit=10.0)
    return LMSpec(**{**base, **kw})


def test_a_latent_row_without_a_rotary_key_is_the_latent_alone():
    spec = _spec()
    assert (spec.cache_pools, spec.cache_row_width) == (1, 16)
    assert spec.index_bytes_per_token == 1 * 8 / 4 * 4
    keys = {key for _, key, _, _ in spec.stack_planes()}
    assert {"idx_q_w", "idx_k_w", "idx_k_norm_s", "idx_k_norm_b",
            "idx_head_w", "hc1_w", "hc2_alpha", "hc2_b"} <= keys
    assert dict((k, s) for _, k, s, _ in spec.stack_planes())["hc1_w"] == \
        [4 * 32, 24]
    assert Block.from_attrs(spec.block.attrs()) == spec.block


@pytest.mark.parametrize("kw,said", [
    (dict(layer_pattern=None, residual="add", hc_mult=1, hc_iters=0),
     "index_topk"),
    (dict(q_lora_rank=0), "index_topk"),
    (dict(index_topk=6), "index_topk"),
    (dict(index_topk=4), "index_topk"),
    (dict(hc_mult=1), "mhc"),
    (dict(hc_iters=0), "mhc"),
    (dict(residual="streams"), "residual"),
    (dict(expert_act="relu2"), "ffn_limit"),
    (dict(qk_rope_head_dim=3), "rotary"),
])
def test_the_spec_refuses_what_it_cannot_build(kw, said):
    with pytest.raises(ValueError, match=said):
        _spec(**kw)


def test_every_gate_that_refuses_state_or_latent_pages_refuses_this_spec():
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config)
    for call in (lambda: eng.export_slot(0), lambda: eng.adopt_slot(None),
                 lambda: eng._require_one_table("a test"),
                 lambda: GenerationEngine(fam.spec_of(config), eng.scope,
                                          share_cache_with=eng)):
        with pytest.raises(BlockNotSupportedError):
            call()
    with pytest.raises(BlockNotSupportedError, match="index_topk"):
        _engine(config, snapshot_stride=2, n_snapshots=4)
    with pytest.raises(BlockNotSupportedError):
        _engine(config, beam_width=2).generate_beam(
            np.arange(4), beam_size=2, max_new_tokens=2)
    block = fam.spec_of(config).block
    for gate in (block.require_stateless, block.require_mha,
                 block.require_one_kind):
        with pytest.raises(BlockNotSupportedError):
            gate("a test")
    with pytest.raises(ValueError, match="page_size"):
        fam.build_engine(config, {"engine": {**ENGINE, "page_size": 6,
                                             "max_len": 96}}, 7)
