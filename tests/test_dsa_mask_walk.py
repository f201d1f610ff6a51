"""A sparse latent layer's pick as a GROUP MASK on the page walks
(``ops/pipeline_ops._dsa_pick`` + ``kernels/paged_attention``'s
``group_mask``): the walk reads every row the table's row holds and masks the
groups that were not picked out of the softmax, where ``_dsa_attend`` gathers
the picked groups' rows. Same rows into the same softmax: the gather is the
ground truth here, the kernels run in interpret mode on the CPU.

Tolerances. float32 pages: both forms multiply in float32 and differ in the
order of the softmax's sums (online over pages against one pass over the
picks): observed 1-2e-6, the bound is 1e-5. bfloat16 pages: the CPU cannot
run the gathered form's batched bfloat16 products, so the walk (which rounds P
and the result to bfloat16) is held to the gather of the SAME bfloat16 values
multiplied in float32: observed 4e-3 on results of up to 2, the bound is 2e-2
(a wrong pick reads 0.1 and more); the picks are scored from the same
bfloat16 pooled keys on both sides."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import profiler
from paddle_tpu.kernels import paged_attention
from paddle_tpu.kernels.paged_attention import (paged_attention_decode,
                                                paged_attention_prefill)
from paddle_tpu.lm_spec import Block
from paddle_tpu.ops import pipeline_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PS, G, W, DI, HI, H = 16, 4, 128, 8, 2, 4
N, P = 24, 10               # pages of the pool, table width: NG = 40 groups
TOPK = 32                   # 8 groups a query: its own and the 7 best before
F32_TOL, BF16_TOL = 1e-5, 2e-2


def _blk(**kw):
    return Block(num_heads=H, use_rope=True, norm="rms_norm", bias=False,
                 attn="mla", q_lora_rank=8, kv_lora_rank=W,
                 qk_nope_head_dim=8, qk_rope_head_dim=0, v_head_dim=16,
                 index_heads=HI, index_dim=DI, index_topk=TOPK, index_pool=G,
                 layer_pattern=("mla",),
                 **kw)


def _case(rows, t, dtype, seed=0, ties=False):
    """The latent pool, the pooled keys' pool, a table a row (permuted
    pages), absorbed queries, the indexer's queries and head weights, and
    the queries' positions for ``rows`` of (start, real queries). ``ties``:
    the pooled keys take one of three values, so nearly every k-th score is
    tied beyond the k."""
    rng = np.random.default_rng(seed)
    b = len(rows)
    ck = jnp.asarray(rng.standard_normal((1, N, PS, W)), dtype)
    ci = rng.standard_normal((1, N, PS // G, DI))
    if ties:
        ci = rng.standard_normal((3, DI))[rng.integers(0, 3, ci.shape[:3])]
    table = np.stack([rng.permutation(np.arange(1, N))[:P]
                      for _ in range(b)]).astype(np.int32)
    q_lat = jnp.asarray(0.3 * rng.standard_normal((b, H, t, W)), dtype)
    q_i = jnp.asarray(rng.standard_normal((b, t, HI, DI)), jnp.float32)
    w_i = jnp.asarray(rng.standard_normal((b, t, HI)), jnp.float32)
    start = jnp.asarray([r[0] for r in rows], jnp.int32)
    real = jnp.asarray([r[1] for r in rows], jnp.int32)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    return (ck, jnp.asarray(ci, dtype), jnp.asarray(table), q_lat, q_i, w_i,
            start, real, pos)


def _gathered(blk, q_lat, q_i, w_i, ck, ci, table, pos):
    """``_dsa_attend`` on the operands' values, multiplied in float32."""
    return pipeline_ops._dsa_attend(
        blk, q_lat.astype(jnp.float32), q_i, w_i, ck.astype(jnp.float32), ci,
        0, table, pos)


def _close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=F32_TOL if dtype == jnp.float32 else BF16_TOL, rtol=0)


#: chunks of 16 queries: rows of (start, real queries)
CHUNKS = {
    # contexts inside index_topk: fewer than k groups before, all picked
    "under-topk": [(0, 16), (16, 16)],
    # past it: 25-36 groups before, 7 picked
    "over-topk": [(100, 16), (144, 16)],
    # a chunk that starts inside a group (26 = 6 G + 2) and crosses a page
    # (32) with 13 real queries, beside a padding row and a one-query row
    "crossing-and-padding": [(26, 13), (50, 0), (63, 1)],
}


@pytest.mark.parametrize("ties", [False, True], ids=["random", "forced-ties"])
@pytest.mark.parametrize("chunk", sorted(CHUNKS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_masked_chunk_walk_is_the_gather_of_the_picked_rows(dtype, chunk,
                                                            ties):
    """``_dsa_pick``'s mask on the chunk walk against ``_dsa_attend`` for
    every REAL query (a padding query leaves zeros on the walk; the gather
    never reads its row): a query whose own group lies partly in the future
    (every position not 3 mod 4) sees it up to itself."""
    rows, t, blk = CHUNKS[chunk], 16, _blk()
    ck, ci, table, q_lat, q_i, w_i, start, real, pos = _case(
        rows, t, dtype, ties=ties)
    want = _gathered(blk, q_lat, q_i, w_i, ck, ci, table, pos)  # [b,H,t,r]
    picked = pipeline_ops._dsa_pick(blk, q_i, w_i, ci, 0, table, pos)
    assert picked.shape == (len(rows), t, P * PS // G)
    assert picked.dtype == jnp.int8
    got = paged_attention_prefill(
        q_lat, ck, None, 0, table, start, real, interpret=True, sm_scale=1.0,
        value_width=W, group_mask=picked, group_rows=G)
    got = got.reshape(len(rows), t, H, W).transpose(0, 2, 1, 3)
    for s, (_, n) in enumerate(rows):
        assert not np.asarray(got[s, :, n:], np.float32).any()
        if n:
            _close(got[s, :, :n], want[s, :, :n], dtype)


@pytest.mark.parametrize("ties", [False, True], ids=["random", "forced-ties"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_masked_decode_walk_is_the_gather_of_the_picked_rows(dtype, ties):
    """A tick: rows past ``index_topk`` (101, the table's last key), inside
    it (17), with the own group partly in the future (30 keys: the query at
    29 = 7 G + 1) and a VACANT slot (no key: a zero row, nothing read but
    the scrap page)."""
    lengths = jnp.asarray([101, 0, 17, P * PS, 30], jnp.int32)
    rows, blk = [(int(n) - 1, 1) for n in lengths], _blk()
    ck, ci, table, q_lat, q_i, w_i, _, _, pos = _case(rows, 1, dtype,
                                                      seed=1, ties=ties)
    want = _gathered(blk, q_lat, q_i, w_i, ck, ci, table, pos)[:, :, 0]
    picked = pipeline_ops._dsa_pick(blk, q_i, w_i, ci, 0, table, pos)
    got = paged_attention_decode(
        q_lat[:, :, 0], ck, None, 0, table, lengths, interpret=True,
        sm_scale=1.0, name=paged_attention.MLA_KERNEL,
        group_mask=picked[:, 0], group_rows=G).reshape(len(rows), H, W)
    assert not np.asarray(got[1], np.float32).any()
    live = np.asarray(lengths) > 0
    _close(got[live], want[live], dtype)


def test_a_mask_of_every_group_is_the_unmasked_walk():
    """All ones: bit for bit what ``group_mask=None`` returns, chunk and
    tick (the mask only ever removes keys)."""
    rows, t = CHUNKS["crossing-and-padding"], 16
    ck, _, table, q_lat, _, _, start, real, _ = _case(rows, t, jnp.float32)
    ones = jnp.ones((len(rows), t, P * PS // G), bool)
    kw = dict(interpret=True, sm_scale=1.0, value_width=W)
    np.testing.assert_array_equal(
        np.asarray(paged_attention_prefill(q_lat, ck, None, 0, table, start,
                                           real, **kw)),
        np.asarray(paged_attention_prefill(q_lat, ck, None, 0, table, start,
                                           real, group_mask=ones,
                                           group_rows=G, **kw)))
    kw = dict(interpret=True, sm_scale=1.0, name=paged_attention.MLA_KERNEL)
    lengths = start + real
    np.testing.assert_array_equal(
        np.asarray(paged_attention_decode(q_lat[:, :, 0], ck, None, 0, table,
                                          lengths, **kw)),
        np.asarray(paged_attention_decode(q_lat[:, :, 0], ck, None, 0, table,
                                          lengths, group_mask=ones[:, 0],
                                          group_rows=G, **kw)))


@pytest.mark.parametrize("scores", ["random", "tied", "all-equal"])
def test_the_threshold_mask_is_the_top_k_index_set(scores):
    """``_picked_groups`` (a counted search for the k-th score, ranks among
    the equal ones only where a row is tied beyond the k) against
    ``lax.top_k`` + the own group on 1000 rows: the same SET, ties to the
    lower index, every group before where there are fewer than k."""
    rng = np.random.default_rng(3)
    n_groups, k, rows = 96, 7, 1000
    s = rng.standard_normal((rows, n_groups)).astype(np.float32)
    if scores == "tied":
        s = np.round(s * 2) / 2 + 0.0       # (no -0.0: ``_dsa_scores``)
    elif scores == "all-equal":
        s = np.zeros_like(s)
    own = rng.integers(0, n_groups, size=rows).astype(np.int32)
    own[:8] = np.arange(8)                  # fewer than k groups before
    z = np.where(np.arange(n_groups)[None] < own[:, None], s, -np.inf)
    got = np.asarray(jax.jit(
        lambda z, own: pipeline_ops._picked_groups(z, own, k))(
            jnp.asarray(z, jnp.float32), jnp.asarray(own)))
    top, pick = (np.asarray(a) for a in jax.lax.top_k(
        jnp.asarray(z, jnp.float32), k))
    want = np.zeros((rows, n_groups), bool)
    for r in range(rows):
        want[r, pick[r][top[r] > -np.inf]] = True
        want[r, own[r]] = True
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(own, k) + 1).all()


# ---------------------------------------------------------------------------
# the dispatch rule of ``_mla_paged_step`` under ``index_topk``
# ---------------------------------------------------------------------------
def _sparse_step(mask, t, dtype=jnp.float32):
    """One sparse layer of ``_mla_paged_step`` over toy projections ->
    (attend, its arguments)."""
    blk, b, d = _blk(), 2, 32
    rng = np.random.default_rng(0)
    ck = jnp.asarray(rng.standard_normal((1, N, PS, W)), dtype)
    ci = jnp.asarray(rng.standard_normal((1, N, PS // G, DI)), dtype)

    def plane(*shape):
        return jnp.asarray(0.3 * rng.standard_normal(shape), jnp.float32)

    p = {"kv_b_w": plane(W, H * 24), "ln1_s": jnp.ones(d), "q_a_w": plane(d, 8),
         "q_a_norm_s": jnp.ones(8), "idx_q_w": plane(8, HI * DI),
         "idx_k_w": plane(d, DI), "idx_k_norm_s": jnp.ones(DI),
         "idx_k_norm_b": jnp.zeros(DI), "idx_head_w": plane(d, HI)}
    proj = (plane(b, H, t, 8), jnp.zeros((b, H, t, 0)), plane(b, t, W),
            jnp.zeros((b, t, 0)))
    start = jnp.asarray([52, 121], jnp.int32)
    table = jnp.asarray(np.stack([rng.permutation(np.arange(1, N))[:P]
                                  for _ in range(b)]).astype(np.int32))
    at = start[:, None] + jnp.arange(t)[None, :]
    if mask == "chunk":
        mask = pipeline_ops.chunk_mask(start, jnp.asarray([t, t - 3]))
    else:
        mask = dict(lengths=start + 1)
    attend = pipeline_ops._mla_paged_step(
        blk, b, t, lambda layer_p, h: proj, mask,
        lambda layer_p, h, ctx, x_l: (ctx, None))
    return attend, (plane(b, t, d), ck, ci, 0, p, None, table,
                    jnp.take_along_axis(table, at // PS, axis=1), at % PS)


def _spy_on_the_walks(monkeypatch, backend):
    calls = []

    def spy(kernel):
        def run(*args, **kwargs):
            calls.append((kernel.__name__, sorted(kwargs)))
            return kernel(*args, interpret=True, **kwargs)
        return run

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for kernel in (paged_attention_decode, paged_attention_prefill):
        monkeypatch.setattr(paged_attention, kernel.__name__, spy(kernel))
    return calls


def _dsa_counts():
    return tuple(profiler.global_stat.as_dict().get(
        f"dsa/{k}_calls", {"total_ms": 0})["total_ms"]
        for k in ("walk", "gather"))


@pytest.mark.parametrize("mask,t,kernel", [
    ("chunk", 16, "paged_attention_prefill"),
    ("tick", 1, "paged_attention_decode")])
def test_a_sparse_layer_on_a_chip_hands_its_pick_to_the_walk(monkeypatch,
                                                             mask, t, kernel):
    """``index_topk`` + TPU + the unselected latent layer's own rule + a
    table no wider than ``MASK_WALK_KEYS``: the SAME kernel the unselected
    layer takes, with the pick as ``group_mask`` by GROUP (the table's width
    in groups, not in keys); the context and both pools of the gathered
    path; the trace-time counter says which a layer took."""
    calls = _spy_on_the_walks(monkeypatch, "tpu")
    before = _dsa_counts()
    attend, args = _sparse_step(mask, t)
    got, ck, ci, _ = attend(*args)
    assert [name for name, _ in calls] == [kernel]
    assert {"group_mask", "group_rows"} <= set(calls[0][1])
    assert _dsa_counts() == (before[0] + 1, before[1])
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    attend, args = _sparse_step(mask, t)
    want, ck_w, ci_w, _ = attend(*args)
    assert len(calls) == 1 and _dsa_counts() == (before[0] + 1,
                                                 before[1] + 1)
    real = slice(None) if t == 1 else slice(0, t - 3)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0],
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got)[1, real],
                               np.asarray(want)[1, real], atol=2e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(ck_w))
    np.testing.assert_array_equal(np.asarray(ci), np.asarray(ci_w))


@pytest.mark.parametrize("why,patch", [
    ("a table wider than the walk wins at", ("MASK_WALK_KEYS", P * PS - 1)),
    ("a step's groups no lane row holds", ("_mask_lanes", lambda groups: None)),
])
@pytest.mark.parametrize("mask,t", [("chunk", 16), ("tick", 1)])
def test_every_other_sparse_call_keeps_the_gather(monkeypatch, mask, t, why,
                                                  patch):
    """Off the rule (``mask_supported``) on a chip: ``_dsa_attend``, no
    kernel, counted as a gather."""
    calls = _spy_on_the_walks(monkeypatch, "tpu")
    monkeypatch.setattr(paged_attention, *patch)
    before = _dsa_counts()
    attend, args = _sparse_step(mask, t)
    ctx, *_ = attend(*args)
    assert not calls, why
    assert _dsa_counts() == (before[0], before[1] + 1)
    assert np.isfinite(np.asarray(ctx)[0]).all()


def test_mask_supported_reads_shapes_only():
    pool = jax.ShapeDtypeStruct((1, 2560, 256, 512), jnp.bfloat16)
    ok = paged_attention.mask_supported
    assert ok(pool, 132, 4, chunk=True) and ok(pool, 132, 4, chunk=False)
    assert paged_attention.MASK_WALK_KEYS >= 132 * 256
    wide = paged_attention.MASK_WALK_KEYS // 256 + 1
    assert not ok(pool, wide, 4, chunk=True)
    assert not ok(pool, 132, 3, chunk=False)    # groups across a page
    assert not ok(pool, 132, 0, chunk=False)
    # a page of 96 groups: neither whole lane rows nor a divisor of one
    assert not ok(jax.ShapeDtypeStruct((1, 8, 384, 512), jnp.bfloat16), 4, 4,
                  chunk=False)


def test_wrappers_refuse_a_mask_that_does_not_fit():
    rows, t = CHUNKS["over-topk"], 16
    ck, _, table, q_lat, _, _, start, real, _ = _case(rows, t, jnp.float32)
    ones = jnp.ones((len(rows), t, P * PS // G), bool)
    kw = dict(interpret=True, sm_scale=1.0, value_width=W)
    with pytest.raises(ValueError, match="group mask"):
        paged_attention_prefill(q_lat, ck, None, 0, table, start, real,
                                group_mask=ones[..., :-1], group_rows=G, **kw)
    with pytest.raises(ValueError, match="window"):
        paged_attention_prefill(q_lat, ck, None, 0, table, start, real,
                                window=8, group_mask=ones, group_rows=G, **kw)
    with pytest.raises(ValueError, match="group_rows"):
        paged_attention_decode(q_lat[:, :, 0], ck, None, 0, table, start,
                               interpret=True, sm_scale=1.0,
                               group_mask=ones[:, 0], group_rows=3)


# ---------------------------------------------------------------------------
# what the benchmark's hooks see, and what the other cells' calls are
# ---------------------------------------------------------------------------
def _glm_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5.3-flash.json")) as f:
        return json.load(f)


#: the masked calls' device events at ``glm53f-serve-longctx``'s shapes (32
#: slots, a table of 8448 groups, 64 heads over a 512-wide row, units of
#: 1024), as the chip's compiler prints them (operands by shape)
MASKED_EVENTS = {
    "tick": "%paged_mla_decode.3 = bf16[32,64,512]{2,1,0:T(8,128)(2,1)} "
            "custom-call(s32[1]{0} %c, s32[4224]{0} %r, s32[32]{0} %l, "
            "bf16[32,64,512]{2,1,0} %q, bf16[1,2560,256,512]{3,2,1,0} %ck, "
            "s8[32,1,8448]{2,1,0} %copy.9), "
            "custom_call_target=\"tpu_custom_call\"",
    "chunk": "%paged_mla_prefill.1 = bf16[1,1024,32768]{2,1,0:T(8,128)(2,1)} "
             "custom-call(s32[1]{0} %c, s32[132]{0} %b, s32[1]{0} %s, "
             "s32[1]{0} %n, bf16[1,64,1024,512]{3,2,1,0} %q, "
             "bf16[1,2560,256,512]{3,2,1,0} %ck, s8[1,1024,8448]{2,1,0} %gm), "
             "custom_call_target=\"tpu_custom_call\"",
}


@pytest.mark.parametrize("call", sorted(MASKED_EVENTS))
def test_the_benchmarks_hooks_name_the_masked_calls(call):
    """``dsa_kda_moe_lm.dsa_op`` (imported as it stands) tells the
    selection's device events by the table's width in groups: with the pick
    among their operands BY GROUP both masked calls are named, and the
    tick's is a tick op (``dsa_decode_roofline``'s time)."""
    from benchmark.families import dsa_kda_moe_lm as fam

    config = _glm_config()
    assert fam.dsa_op(MASKED_EVENTS[call], config) == "score"
    assert fam.dsa_tick_op(MASKED_EVENTS[call], config, 32) == (call == "tick")
    # (the pick pre-widened to keys, or not passed, would leave the tick's
    # call unnamed: it is the operand's width in GROUPS that names it)
    assert fam.dsa_op(MASKED_EVENTS["tick"].replace(
        ", s8[32,1,8448]{2,1,0} %copy.9", ""), config) is None


#: ``tools/lowered_text.py --kernels`` at the parent of the PR that gave the
#: kernels a group mask (38c07e3): the traced programs of the UNMASKED calls
PARENT_KERNEL_HASHES = {
    "kexaone-serve-reason verify": "7eb23f22dbd92644",
    "kexaone-serve-reason prefill": "0b44603474d347de",
    "kexaone-serve-reason.window verify": "071ac4ef4bcc4389",
    "kexaone-serve-reason.window prefill": "6fbd9362e5f1027b",
    "olmoe-serve-chat decode": "b4a32869532d8ced",
    "olmoe-serve-chat prefill": "f67e5d8a15012496",
    "mistral4-serve-longdoc decode": "2dc9f726da917940",
    "mistral4-serve-longdoc prefill": "69beb24e33e31f02",
    "ling3-serve-reason decode": "7dac15c3a5b1877f",
    "ling3-serve-reason prefill": "575620c7691e0038",
}


def test_the_unmasked_calls_trace_to_the_programs_they_traced_to():
    """``group_mask=None`` leaves every other cell's kernel call what it
    was: the traced program (kernel body, grid and operands) of the K/V and
    latent calls at kexaone's, olmoe's, mistral4's and ling3's shapes hashes
    to what the parent's did (``tools/lowered_text.py --kernels``). A later
    change that MEANS to move them regenerates the table with the tool."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lowered_text", os.path.join(ROOT, "tools", "lowered_text.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.kernel_hashes() == PARENT_KERNEL_HASHES


# ---------------------------------------------------------------------------
# the engine's count of what the walk reads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("walks", [True, False], ids=["walk", "gather"])
def test_the_engine_counts_the_rows_a_masked_walk_meets(walks):
    """``_count_selection`` beside ``dsa_rows_attended``: every query of a
    call whose sparse layer walks under its pick meets ALL the rows of the
    pages its row's walk reaches (``dsa_rows_walked``: a tick's by its
    length, a chunk's by ``chunk_pages_in_reach``), ONE layer's worth;
    nothing where the layer gathers (the CPU)."""
    import types

    from paddle_tpu.serving.generation import GenerationEngine

    class Counters(dict):
        def inc(self, name, n=1):
            self[name] = self.get(name, 0) + n

    eng = types.SimpleNamespace(
        spec=types.SimpleNamespace(index_pool=G, index_topk=TOPK,
                                   layers_of=lambda windowed: 1),
        _caches=[types.SimpleNamespace(table="serving.table", page_size=PS)],
        metrics=Counters(), _selection_walks=lambda tc: walks)
    table = np.asarray([[3, 4, 5, 0], [0, 0, 0, 0], [7, 8, 0, 0]], np.int32)
    # a tick: rows at positions 40 and 17 (the vacant slot is not counted)
    GenerationEngine._count_selection(eng, None, {
        "serving.table": table,
        "serving.pos": np.asarray([40, 0, 17], np.int32)})
    tick = dict(eng.metrics)
    assert tick["dsa_rows_attended"] == (8 + 5) * G     # 7 + own; 4 + own
    assert tick.get("dsa_rows_walked", 0) == (
        (3 + 2) * PS if walks else 0)           # pages 0..2 and 0..1
    # a chunk: 13 real queries from 26 (pages 0..2), a padding row
    eng.metrics.clear()
    GenerationEngine._count_selection(eng, 16, {
        "serving.table": table[[0, 2]],
        "serving.start": np.asarray([26, 0], np.int32),
        "serving.chunk_len": np.asarray([13, 0], np.int32)})
    assert eng.metrics["dsa_queries"] == 13
    assert eng.metrics.get("dsa_rows_walked", 0) == (
        13 * 3 * PS if walks else 0)
    assert eng.metrics["dsa_rows_in_reach"] == sum(range(27, 40))
