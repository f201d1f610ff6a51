"""The OLMoE block (RMSNorm, QK-norm, half-split RoPE, bias-free, dropless
top-k SwiGLU experts) built from ``LMSpec``, against the plain float32
reference in ``benchmark/families/moe_lm.py`` at a tiny size on the CPU:
logits, loss, every gradient, and chunked prefill + decode through
``GenerationEngine`` — all through the normal path
(``transformer_lm(spec=)`` / ``GenerationEngine(spec, ...)``).

Tolerances. Program and reference run the SAME float32 arithmetic in a
different order (fused qkv, sorted grouped matmuls against dense masked
experts, paged attention against a full causal softmax), so they differ
by float32 rounding: observed <= 3e-6 on logits of size ~1; the bound
below is 2e-5. bfloat16 operands round at 2^-9 = 2e-3 relative, a hundred
times the bound: ``test_bf16_arithmetic_fails_the_float32_tolerance``
pins that a run with bf16 matmul operands does NOT pass it. bfloat16
PAGES (K/V stored and attended in bf16, all else float32) get their own
bound, 3e-2 on log-probs (observed 4e-3 .. 2.3e-2: K/V rounding of 2e-3
relative on O(1) values through two layers), except where the rounding
flips a near-tie of a router's top-k."""
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import moe_lm
from paddle_tpu import layers, models
from paddle_tpu.lm_spec import Block, BlockNotSupportedError, LMSpec
from paddle_tpu.serving import GenerationEngine

F32_TOL = 2e-5
BF16_PAGE_TOL = 3e-2
T, B = 16, 3


def tiny_config(**assumed):
    return {
        "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 2,
        "vocab_size": 128, "num_experts": 8, "num_experts_per_tok": 2,
        "intermediate_size": 32, "norm_topk_prob": False,
        "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "max_position_embeddings": 64,
        "assumed": {"param_dtype": "float32", "page_dtype": "float32",
                    "router_aux_loss_coef": 0.01, **assumed}}


@pytest.fixture
def no_amp():
    pt.set_amp(False)


def _train_program(config, seed=3):
    """-> (exe, scope, main, logits, loss, {param name: grad var})."""
    from paddle_tpu.core.backward import append_backward

    spec = moe_lm.spec_of(config)
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
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
    return exe, scope, main, logits, loss, grads


def _feed(seed=0):
    ids = np.random.RandomState(seed).randint(0, 128, (B, T + 1))
    return {"ids": ids[:, :-1].astype("int64"),
            "tgt": ids[:, 1:].astype("int64")}


@pytest.fixture(scope="module")
def trained():
    """One forward + backward of the tiny model and the reference's loss
    and gradients on the same weights and batch."""
    from paddle_tpu.ops import common

    before = common._AMP
    pt.set_amp(False)
    config = tiny_config()
    exe, scope, main, logits, loss, grads = _train_program(config)
    w = moe_lm.weights_of(None, scope)
    w = {k: np.asarray(v) for k, v in w.items()}
    feed = _feed()
    names = sorted(grads)
    out = exe.run(main, feed=feed,
                  fetch_list=[logits, loss] + [grads[n] for n in names],
                  scope=scope)
    ref_loss, ref_grads = moe_lm.reference_grads(config, w, feed)
    common._AMP = before
    return dict(config=config, w=w, feed=feed, logits=np.asarray(out[0]),
                loss=float(np.asarray(out[1]).reshape(())),
                grads=dict(zip(names, map(np.asarray, out[2:]))),
                ref_loss=ref_loss, ref_grads=ref_grads)


def test_logits_match_the_reference_in_float32(trained):
    import jax

    with jax.default_matmul_precision("highest"):
        for row in range(B):
            ref = np.asarray(moe_lm.reference_logits(
                trained["config"], trained["w"], trained["feed"]["ids"][row]))
            np.testing.assert_allclose(trained["logits"][row], ref,
                                       rtol=0, atol=F32_TOL)


def test_loss_matches_the_reference(trained):
    # CE ~ log(128) plus 0.01 x the aux loss (~2, one per layer)
    assert abs(trained["loss"] - trained["ref_loss"]) < F32_TOL
    assert trained["loss"] > np.log(128) * 0.9


_PARAMS = (["tok_emb", "final_ln.scale", "lm_head.w"]
           + [f"lm_stack.stack_{k}" for k in moe_lm._STACK])


@pytest.mark.parametrize("name", _PARAMS)
def test_every_gradient_matches_the_reference(trained, name):
    got, ref = trained["grads"][name], np.asarray(trained["ref_grads"][name])
    assert got.shape == ref.shape
    assert np.abs(ref).max() > 0, "a gradient that is zero tests nothing"
    np.testing.assert_allclose(got, ref, rtol=0,
                               atol=F32_TOL * max(1.0, np.abs(ref).max()))


def test_bf16_arithmetic_fails_the_float32_tolerance():
    """The same program with bf16 matmul operands (AMP) is OUTSIDE the
    float32 bound: the bound can tell the precisions apart."""
    import jax

    config = tiny_config()
    pt.set_amp(True)
    try:    # (conftest's autouse fixture puts the policy back)
        exe, scope, main, logits, _, _ = _train_program(config)
        feed = _feed()
        got = np.asarray(exe.run(main, feed=feed, fetch_list=[logits],
                                 scope=scope)[0])
        w = {k: np.asarray(v)
             for k, v in moe_lm.weights_of(None, scope).items()}
    finally:
        pt.set_amp(False)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(moe_lm.reference_logits(config, w, feed["ids"][0]))
    assert np.abs(got[0].astype(np.float32) - ref).max() > 10 * F32_TOL


# ---------------------------------------------------------------------------
# serving: chunked prefill + decode through the pages
# ---------------------------------------------------------------------------
def _engine(config, seed=5, **kw):
    spec = moe_lm.spec_of(config)
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p_init", shape=[8], dtype="int64")
        out = models.transformer_lm_generate(p, spec=spec, max_new_tokens=1)
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    eng = GenerationEngine(spec, scope, slots=2, page_size=8,
                           max_seq_len=64, prompt_buckets=(8, 16),
                           prefill_batch_buckets=(1,), prefill_chunk=16,
                           eos_id=None, **kw)
    return eng, exe, prog, out


@pytest.mark.parametrize("page_dtype,tol", [("float32", F32_TOL * 5),
                                            ("bfloat16", BF16_PAGE_TOL)])
def test_chunked_prefill_and_decode_match_the_reference(no_amp, page_dtype,
                                                        tol):
    """A 40-token prompt in three chunks of 16, then 10 decode steps,
    through the page pool: the engine's top-8 log-probs at every chunk
    end and every step against the reference's FULL forward of the whole
    sequence (log-probs are sums of ~5 logits' worth of float32 rounding:
    5 x the logit bound)."""
    import jax

    config = tiny_config(page_dtype=page_dtype)
    eng, *_ = _engine(config, beam_width=8)
    assert str(eng.scope.get("serving.paged_cache_k").dtype) == page_dtype
    prompt = np.random.RandomState(1).randint(0, 128, 40)
    calls, out = moe_lm.served_logprobs(eng, prompt, 10)
    assert [c[0] for c in calls] == [15, 31, 39] + list(range(40, 49))
    w = {k: np.asarray(v)
         for k, v in moe_lm.weights_of(None, eng.scope).items()}
    with jax.default_matmul_precision("highest"):
        ref = jax.nn.log_softmax(
            moe_lm.reference_logits(config, w, out[:-1]), axis=-1)
    ref = np.asarray(ref)
    errs = np.array([np.abs(vals - ref[pos][ids]).max()
                     for pos, vals, ids in calls])
    if page_dtype == "float32":
        assert errs.max() < tol, errs
        assert all(ids[0] == np.argmax(ref[pos]) for pos, _, ids in calls)
    else:
        # a near-tie in a router's top-2 can flip when K/V are rounded to
        # bf16 (the router's own arithmetic is float32 in both): that
        # position then runs another expert and is far off (0.34 here).
        # At most one of the 12 positions may; the rest hold the bound,
        # and none of it is inside the float32 bound.
        assert np.sum(errs > tol) <= 1, errs
        assert np.median(errs) > F32_TOL * 5, "bf16 pages in the f32 bound?"
    # the greedy tokens are the argmax the plane reported
    np.testing.assert_array_equal(out[40:], [c[2][0] for c in calls[2:-1]]
                                  + [out[-1]])


def test_engine_counters_count_every_assignment_and_drop_none(no_amp):
    config = tiny_config()
    eng, *_ = _engine(config)
    before = dict(eng.metrics.snapshot()["counters"])
    eng.generate_all([np.arange(20) % 128, np.arange(5) + 7],
                     max_new_tokens=6)
    c = eng.metrics.snapshot()["counters"]
    took = c["moe_assignments"] - before.get("moe_assignments", 0)
    assert took > 0 and took % (2 * 2) == 0      # rows x top-2 x 2 layers
    assert c["moe_dropped_tokens"] == 0
    assert 0 < c["moe_hot_expert_rows"] <= took


def test_save_load_serve_keeps_the_spec(no_amp, tmp_path):
    config = tiny_config(page_dtype="bfloat16")
    eng, exe, prog, out = _engine(config)
    pt.io.save_inference_model(str(tmp_path), ["p_init"], [out], exe,
                               main_program=prog, scope=eng.scope)
    loaded = GenerationEngine.from_saved(
        str(tmp_path), max_seq_len=64, slots=2, page_size=8,
        prompt_buckets=(8, 16), prefill_batch_buckets=(1,),
        prefill_chunk=16, eos_id=None)
    want = moe_lm.spec_of(config)
    want.max_len = 64           # RoPE: the context bound is the caller's
    want.router_aux_loss_coef = 0.0     # training only, not in the program
    assert loaded.spec == want
    prompt = np.arange(12) * 5 % 128
    np.testing.assert_array_equal(
        loaded.generate_all([prompt], max_new_tokens=5)[0],
        eng.generate_all([prompt], max_new_tokens=5)[0])


# ---------------------------------------------------------------------------
# the expert layer's routing
# ---------------------------------------------------------------------------
def _moe_inputs(seed=0, N=12, d=16, E=8, f=32):
    import jax

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (N, d)),
            jax.random.normal(ks[1], (d, E)),
            jax.random.normal(ks[2], (E, d, f)) * 0.2,
            jax.random.normal(ks[3], (E, d, f)) * 0.2,
            jax.random.normal(ks[4], (E, f, d)) * 0.2)


def _dense_moe(x, r, g, u, dn, k):
    """Every expert on every token, masked by the top-k set."""
    import jax
    import jax.numpy as jnp

    p = jax.nn.softmax(x @ r, -1)
    top_p, top_e = jax.lax.top_k(p, k)
    gate = jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None],
                                top_e].set(top_p)
    act = (jax.nn.silu(jnp.einsum("nd,edf->nef", x, g))
           * jnp.einsum("nd,edf->nef", x, u))
    return jnp.einsum("ne,nef,efd->nd", gate, act, dn)


@pytest.mark.parametrize("case", ["exactly_k_experts_a_token",
                                  "counts_sum_to_n_times_k",
                                  "every_token_to_one_expert_drops_none",
                                  "permuting_tokens_permutes_outputs",
                                  "top1_is_k_equals_1"])
def test_routing_properties(no_amp, case):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.moe_ops import moe_topk

    x, r, g, u, dn = _moe_inputs()
    N, E = x.shape[0], r.shape[1]
    with jax.default_matmul_precision("highest"):
        if case == "exactly_k_experts_a_token":
            # one token at a time: its counts are k ones
            for i in range(N):
                _, counts, _ = moe_topk(x[i:i + 1], r, g, u, dn, 3)
                assert sorted(np.asarray(counts))[-3:] == [1, 1, 1]
                assert int(counts.sum()) == 3
        elif case == "counts_sum_to_n_times_k":
            for k in (1, 2, 8):
                y, counts, prob_mean = moe_topk(x, r, g, u, dn, k)
                assert int(counts.sum()) == N * k
                np.testing.assert_allclose(float(prob_mean.sum()), 1.0,
                                           atol=1e-6)
                np.testing.assert_allclose(
                    y, _dense_moe(x, r, g, u, dn, k), atol=1e-5)
        elif case == "every_token_to_one_expert_drops_none":
            # expert 0's logit dominates for every token: a capacity-based
            # router would drop most of them, this one serves all N
            r0 = r.at[:, 0].set(0.0)
            x0 = jnp.concatenate([x, jnp.ones((N, 1))], axis=1)
            r0 = jnp.concatenate([r0, jnp.zeros((1, E)).at[0, 0].set(50.)])
            pad = jnp.zeros((E, 1, g.shape[2]))
            g0, u0 = (jnp.concatenate([t, pad], axis=1) for t in (g, u))
            dn0 = jnp.concatenate([dn, jnp.zeros((E, dn.shape[1], 1))], 2)
            y, counts, _ = moe_topk(x0, r0, g0, u0, dn0, 2)
            assert int(counts[0]) == N and int(counts.sum()) == 2 * N
            np.testing.assert_allclose(
                y, _dense_moe(x0, r0, g0, u0, dn0, 2), atol=1e-5)
        elif case == "permuting_tokens_permutes_outputs":
            perm = np.random.RandomState(0).permutation(N)
            y, counts, _ = moe_topk(x, r, g, u, dn, 2)
            yp, countsp, _ = moe_topk(x[perm], r, g, u, dn, 2)
            np.testing.assert_allclose(yp, y[perm], atol=1e-6)
            np.testing.assert_array_equal(counts, countsp)
        else:
            y, counts, _ = moe_topk(x, r, g, u, dn, 1)
            np.testing.assert_array_equal(
                counts, np.bincount(np.argmax(x @ r, -1), minlength=E))


def test_expert_layer_is_differentiable_under_remat(no_amp):
    import jax

    from paddle_tpu.ops.moe_ops import moe_topk

    args = _moe_inputs()
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jax.checkpoint(
            lambda *b: moe_topk(*b, 2)[0])(*a).sum(), argnums=range(5))(*args)
        ref = jax.grad(lambda *a: _dense_moe(*a, 2).sum(),
                       argnums=range(5))(*args)
    for a, b in zip(got, ref):
        np.testing.assert_allclose(a, b, atol=1e-5)


# ---------------------------------------------------------------------------
# what keeps the GPT-2 block says so
# ---------------------------------------------------------------------------
_OLMOE_ATTRS = Block(num_heads=4, norm="rms_norm", qk_norm=True,
                     use_rope=True, rope_pairing="half", ffn="swiglu_moe",
                     experts_per_tok=2, bias=False).attrs()


@pytest.mark.parametrize("op", ["transformer_stack_beam_search"])
def test_gpt2_only_ops_refuse_another_spec_by_name(op):
    from paddle_tpu.core.registry import get_op

    with pytest.raises(BlockNotSupportedError, match=op):
        get_op(op).fn(dict(_OLMOE_ATTRS, max_new_tokens=1), {})


def test_gpt2_spec_is_the_default_block():
    """The GPT-2 program's attrs are the three keys it always carried."""
    spec = LMSpec(vocab_size=32, d_model=16, n_layers=2, num_heads=2)
    assert spec.block.is_gpt2
    assert spec.block.attrs() == {"num_heads": 2, "num_kv_heads": None,
                                  "use_rope": False}
    assert list(spec.block.stack_slots().values()) == [
        "ln1_s", "ln1_b", "qkv_w", "out_w", "ln2_s", "ln2_b",
        "ff_w1", "ff_b1", "ff_w2", "ff_b2"]
    assert Block.from_attrs(_OLMOE_ATTRS).attrs() == _OLMOE_ATTRS


# ---------------------------------------------------------------------------
# the engine's programs are pinned: warm-up manifests and the persistent
# compile cache key on them, so a refactor of the engine must not move them
# ---------------------------------------------------------------------------
_ENGINE_KW = dict(slots=2, page_size=8, prompt_buckets=(8, 16),
                  prefill_chunk=16)


def _tiny_spec(family, config):
    """The spec of a family's tiny configuration (benchmark/tests/data)."""
    import importlib
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "tests", "data", "configs",
                           config)) as f:
        return importlib.import_module(
            f"benchmark.families.{family}").spec_of(json.load(f))


@pytest.mark.parametrize("spec,kw,want", [
    (LMSpec(vocab_size=32, d_model=16, n_layers=2, num_heads=2, max_len=64),
     {},
     {"decode": "c393d3da6d29c622", "prefill16": "3f5e83d3a0887552",
      "prefill8": "ceb639374c3ffa76", "page_copy": "d5270f0b76e90d8b"}),
    (moe_lm.spec_of(tiny_config()),
     dict(max_seq_len=64, prefill_batch_buckets=(1,), eos_id=None),
     {"decode": "9828c54cf4fabc59", "prefill16": "134a2be61ec3cc06",
      "prefill8": "72006d4d2f9df612", "page_copy": "38351444fa9c0df6"}),
    # one case a shape of cache, recorded at the parent of PR 48 (which put
    # each kind of page cache behind one object and moved no program):
    # pages by layer kind (BOTH kinds' page copies), a latent pool, state a
    # slot, state with a snapshot pool
    (_tiny_spec("window_moe_lm", "smallthinker-tiny.json"),
     dict(max_seq_len=64, n_pages_window=12),
     {"decode": "98b45120d1a6c8fc", "prefill16": "81f86892a5eec66d",
      "prefill8": "33882d21b6dba658", "page_copy": "38351444fa9c0df6",
      "page_copy_window": "21c1bd1907528fce"}),
    (_tiny_spec("mla_moe_lm", "mistral4-tiny.json"), dict(max_seq_len=64),
     {"decode": "526811be43ea444e", "prefill16": "0ba61c842661ac3e",
      "prefill8": "a9ad73f07afc8b55", "page_copy": "59dce240be45b6b7"}),
    (_tiny_spec("kda_mla_moe_lm", "ling3-tiny.json"), dict(max_seq_len=64),
     {"decode": "e154cb44613136b4", "prefill16": "153476ed1a6e1c61",
      "prefill8": "6f1a44039526bb3b", "page_copy": "1a667a6dca82e5b9"}),
    (_tiny_spec("kda_gqa_moe_lm", "solar2-tiny.json"),
     dict(max_seq_len=64, snapshot_stride=2, n_snapshots=4),
     {"decode": "6bee585d3fc83ad6", "prefill16": "6ae54e0e828cc35a",
      "prefill8": "23442468fef902f7", "page_copy": "d5270f0b76e90d8b"}),
], ids=["gpt2", "olmoe", "window", "latent", "state", "state_snapshots"])
def test_engine_programs_are_bit_identical_to_the_recorded_ones(
        spec, kw, want):
    """``program_digest`` (the ``program_to_dict`` JSON, call sites
    stripped) of the decode step, every prefill chunk width and the page
    copy of every kind of cache. A change that means to move a program
    re-records them: PR 28 merged the two engine classes and moved none;
    PR 47 gave the decode and prefill programs ONE packed feed and the
    ``unpack_plane`` op that splits it (the page copy is the recorded one
    still)."""
    from paddle_tpu.core.manifest import program_digest

    eng = GenerationEngine(spec, **_ENGINE_KW, **kw)
    got = {"decode": program_digest(eng._decode_prog[0])}
    for cache in eng._caches:
        got["page_copy" + cache.suffix] = program_digest(
            eng._page_copy_prog_of(cache)[0])
    for tc in eng._chunk_widths:
        got[f"prefill{tc}"] = program_digest(eng._prefill_prog(tc)[0])
    assert got == want
