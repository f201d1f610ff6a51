"""A stack held by attention kind (``LMSpec(layer_pattern=("kda", .., "mla"))``:
Kimi Delta Attention layers with a recurrent state a SLOT beside latent
layers over the page pool, a leading dense layer, a sigmoid group-limited
router with a selection bias over a held share of the experts) — at a tiny
size on the CPU against the plain float32 reference in
``benchmark/families/kda_mla_moe_lm.py`` (KDA as the token-by-token
recurrence): d 32, 2 heads of 16, two periods of (kda, kda, mla), layer 0
dense, experts 0..1 held of a router over 8 in 4 groups of which 2 are kept,
top-2, through the normal path (``GenerationEngine(spec, ..)``).

Tolerances. float32 everywhere: program (chunked prefill from the slot's
state, the recurrent step, absorbed latent decode through the cache) and
reference (one scan over the sequence, expanded attention, no cache) run the
same arithmetic in another order: observed <= 2e-6 on log-probs, the bound
is 2e-5; every wrong model of the reference's ``VARIANTS`` lies >= 5e-3
away. bfloat16 pages and convolution history (all else float32):
``BF16_PAGE_TOL`` on the median error. bfloat16 matmul operands (AMP):
``BF16_TOL`` on the median, which every fault of the MATHEMATICS fails (the
two precision variants read what AMP itself reads at this size)."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import kda_mla_moe_lm as fam
from paddle_tpu.kernels import kda
from paddle_tpu.lm_spec import Block, BlockNotSupportedError, LMSpec
from paddle_tpu.ops import pipeline_ops
from paddle_tpu.ops.moe_ops import moe_topk
from paddle_tpu.serving import GenerationEngine

F32_TOL = 2e-5
WRONG_TOL = 5e-3
BF16_PAGE_TOL = 0.004
BF16_TOL = 0.03
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MATH_VARIANTS = [v for v in fam.VARIANTS
                 if v not in ("bf16_stated_f32", "bf16_state")]


def tiny_config(**assumed):
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "ling3-tiny.json")) as f:
        config = json.load(f)
    config["assumed"].update(assumed)
    return config


def bench_config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        return json.load(f)


ENGINE = {"slots": 3, "page_size": 8, "n_pages": 40, "max_len": 96,
          "prompt_buckets": [8, 16], "prefill_batch_buckets": [1, 2],
          "prefill_chunk": 16}


def _engine(seed=7, config=None, **engine):
    eng, _ = fam.build_engine(config or tiny_config(),
                              {"engine": {**ENGINE, **engine}}, seed,
                              beam_width=8)
    return eng


@pytest.fixture(scope="module")
def served():
    """One float32 engine, three requests of uneven chunking (5; 16 + 5;
    16 + 16 + 5 prompt tokens, 12 new each) through its own ticks: ->
    {prompt_len: (errors by variant, emitted)}."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config=config)
    w = fam.weights_of(None, eng.scope)
    rng = np.random.default_rng(0)
    out = {}
    for n in (5, 21, 37):
        errs, again, at, state = fam.served_errors(
            config, w, eng, rng.integers(0, 96, size=n), 12,
            variants=("",) + tuple(fam.VARIANTS))
        out[n] = (errs, again, at)
        out["state", n] = state
    out["counters"] = eng.metrics.snapshot()
    out["stats"] = eng.cache_stats()
    return out


def _kda_case(seed, b, t, H=2, K=16, V=16):
    rng = np.random.default_rng(seed)

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = l2(rng.normal(size=(b, t, H, K))) * K ** -0.5
    k = l2(rng.normal(size=(b, t, H, K)))
    v = rng.normal(size=(b, t, H, V))
    g = -5.0 / (1.0 + np.exp(-rng.normal(size=(b, t, H, K)) * 2))
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(b, t, H))))
    S0 = rng.normal(size=(b, H, K, V))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, S0)]


def _scan_rows(q, k, v, g, beta, S0):
    """The reference's token-by-token scan, a row at a time."""
    outs = [fam.kda_scan(q[i], k[i], v[i], g[i], beta[i], state=S0[i])
            for i in range(q.shape[0])]
    return (jnp.stack([o for o, _ in outs]),
            jnp.stack([s for _, s in outs]))


# ---------------------------------------------------------------------------
# the three forms of the recurrence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("t,block", [(5, 64), (64, 64), (100, 32),
                                     (130, 64), (256, 64)])
def test_chunked_form_equals_the_token_by_token_scan(t, block):
    """Blocks of ``block`` tokens from a non-zero state, log-decays down
    to -5 a token (exp(320) over a block: the pairwise exponents must
    never overflow)."""
    args = _kda_case(t, 2, t)
    o_ref, s_ref = _scan_rows(*args)
    o, s = jax.jit(lambda *a: kda.kda_chunked(*a, block=block))(*args)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)


@pytest.mark.parametrize("live", [None, (True, False, True),
                                  (False, False, False)])
def test_decode_kernel_equals_one_step_of_the_scan(live):
    """Interpret mode, the whole state array [L, S, H, K, V] in and out:
    layer 1 of the live rows advances, every other tile is bit for bit."""
    S, H, K, V = 3, 4, 8, 128
    q, k, v, g, beta, _ = _kda_case(3, S, 1, H, K, V)
    state = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, S, H, K, V)), jnp.float32)
    mask = None if live is None else jnp.asarray(live)
    o, new = kda.kda_decode_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], state, jnp.int32(1), mask,
                                 interpret=True)
    o_ref, s_ref = _scan_rows(q, k, v, g, beta, state[1])
    rows = [i for i in range(S) if live is None or live[i]]
    assert (np.asarray(new[0]) == np.asarray(state[0])).all()
    for i in range(S):
        if i in rows:
            np.testing.assert_allclose(o[i], o_ref[i, 0], atol=1e-5)
            np.testing.assert_allclose(new[1, i], s_ref[i], atol=1e-5)
        else:
            assert (np.asarray(new[1, i]) == np.asarray(state[1, i])).all()


def test_decode_kernel_compiles_for_the_v5e_with_the_state_whole():
    """The whole state array enters the custom call as it lies in HBM and
    leaves aliased to it: nothing state-sized is copied or sliced."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no compiler here: no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    dev = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=dev)

    S, H, K = 64, 32, 128
    compiled = jax.jit(kda.kda_decode_step, donate_argnums=(5,)).lower(
        arg((S, H, K)), arg((S, H, K)), arg((S, H, K)), arg((S, H, K)),
        arg((S, H)), arg((5, S, H, K, K)), arg((), jnp.int32),
        arg((S,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert f"%{kda.KERNEL}" in text
    mem = compiled.memory_analysis()
    state_bytes = 5 * S * H * K * K * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 8


def test_a_row_with_no_valid_token_leaves_state_and_history_alone():
    """``_kda_layer``, the decode form (row i IS slot i): a vacant or
    still-prefilling row (no valid token) keeps its tiles bit for bit
    while a live neighbour advances; the prefill form (rows name their
    slot): the padding row's write is dropped and a row that starts at
    position 0 reads zeros whatever the slot held."""
    spec = fam.spec_of(tiny_config())
    blk = spec.block
    rng = np.random.default_rng(5)
    p = {key: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
         for _, key, shape, _ in spec.stack_planes()
         if key.startswith("kda_") or key == "ln1_s"}
    state = jnp.asarray(rng.normal(size=(4, 3, 2, 16, 16)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(4, 3, 3, 96)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(3, 1, 32)), jnp.float32)
    rows = (None, jnp.asarray([7, 0, 3]), jnp.asarray([1, 0, 1]))
    _, s1, c1, _ = pipeline_ops._kda_layer(blk, p, h, state, conv, 2, rows)
    for arr0, arr1 in ((state, s1), (conv, c1)):
        a0, a1 = np.asarray(arr0), np.asarray(arr1)
        assert (a0[[0, 1, 3]] == a1[[0, 1, 3]]).all()     # other layers
        assert (a0[2, 1] == a1[2, 1]).all()               # the vacant row
        assert (a0[2, 0] != a1[2, 0]).any() and (a0[2, 2] != a1[2, 2]).any()
    # prefill rows: row 0 -> slot 1 from position 0, row 1 is padding
    hp = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
    rows = (jnp.asarray([1, 3]), jnp.asarray([0, 0]), jnp.asarray([5, 0]))
    ctx, s2, c2, _ = pipeline_ops._kda_layer(blk, p, hp, state, conv, 0,
                                             rows)
    zero = pipeline_ops._kda_layer(blk, p, hp, jnp.zeros_like(state),
                                   jnp.zeros_like(conv), 0, rows)
    np.testing.assert_array_equal(ctx[0, :5], zero[0][0, :5])
    np.testing.assert_array_equal(s2[0, 1], zero[1][0, 1])
    assert (np.asarray(s2)[0, [0, 2]] == np.asarray(state)[0, [0, 2]]).all()
    assert (np.asarray(s2)[1:] == np.asarray(state)[1:]).all()
    assert (np.asarray(c2)[0, [0, 2]] == np.asarray(conv)[0, [0, 2]]).all()


# ---------------------------------------------------------------------------
# the normal path against the reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [5, 21, 37])
def test_engine_equals_the_reference_in_float32(served, n):
    errs, again, at = served[n]
    assert again.size == n + 12
    # every chunk end, then every decode step
    assert list(at) == [min(c, n) - 1 for c in range(16, n + 16, 16)] \
        + list(range(n, n + 11))
    assert max(errs[""]) <= F32_TOL, max(errs[""])


@pytest.mark.parametrize("variant", sorted(fam.VARIANTS))
def test_every_wrong_model_fails_the_float32_bound(served, variant):
    worst = max(max(served[n][0][variant]) for n in (5, 21, 37))
    assert worst >= WRONG_TOL, (variant, worst)


@pytest.mark.parametrize("n", [5, 21, 37])
def test_the_slots_state_equals_the_references_recurrence(served, n):
    """What a request leaves in its slot (chunks, then decode steps) is the
    token-by-token scan's state after the last token fed, layer by layer,
    and uses float32's whole mantissa as the reference's does."""
    state = served["state", n][""]
    assert len(state["rel_err"]) == 4                   # the kda layers
    assert max(state["rel_err"]) <= F32_TOL, state
    assert state["bits"] == [0, 0, 0, 0]
    for wrong in ("no_decay", "no_delta", "no_lower_bound"):
        assert min(served["state", n][wrong]["rel_err"]) > 0.05, wrong


@pytest.mark.parametrize("n", [5, 21, 37])
def test_a_bfloat16_state_fails_the_state_limit(served, n):
    """The model whose state passes through bfloat16 differs from the
    engine's float32 state by 16 mantissa bits in every layer, over the
    limit; by distance a layer no router precedes is 0.2-0.5% away, which
    AMP alone also reads."""
    state = served["state", n]["bf16_state"]
    assert state["bits"] == [16] * 4
    assert min(state["bits"]) > fam.CHECK_STATE_BITS_TOL
    assert 1e-3 < state["rel_err"][0] < 1e-2, state


@pytest.mark.parametrize("lowered,reading", [(False, 0.0), (True, 16.0)])
def test_the_cells_check_sees_an_engine_that_lowers_its_state(
        monkeypatch, lowered, reading):
    """``reference_logit_gaps`` (what decides the cell's ``correct``): its
    third reading is 0 for this engine and 16 / ``CHECK_STATE_BITS_TOL`` of
    the limit for one whose state array holds bfloat16's values."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config=config)
    w = fam.weights_of(None, eng.scope)
    out = eng.generate_all([np.random.default_rng(3).integers(0, 96, 21)],
                           max_new_tokens=6)[0]
    if lowered:
        real = fam._engine

        def rounding(*a, **kw):
            twin = real(*a, **kw)
            run = twin.executor.run

            def run_then_round(*ra, **rkw):
                res = run(*ra, **rkw)
                S = twin.scope.get(fam._STATE_ARRAY)
                twin.scope.set(fam._STATE_ARRAY,
                               S.astype(jnp.bfloat16).astype(S.dtype))
                return res

            twin.executor.run = run_then_round
            return twin

        monkeypatch.setattr(fam, "_engine", rounding)
    got = fam.reference_logit_gaps(config, w, [(21, out)])
    assert got.shape == (3,)
    assert got[2] == pytest.approx(
        reading * fam.CHECK_LOGPROB_TOL / fam.CHECK_STATE_BITS_TOL)
    assert (got.max() > fam.CHECK_LOGPROB_TOL) == lowered, got


def test_the_engine_counts_the_state(served):
    snap, stats = served["counters"], served["stats"]
    c, g = snap["counters"], snap["gauges"]
    spec = fam.spec_of(tiny_config())
    per_slot = 4 * (2 * 16 * 16 * 4 + 3 * 96 * 4)
    assert spec.state_bytes_per_slot == per_slot
    assert g["mem/state_bytes_per_slot"] == per_slot
    assert g["mem/state_bytes_live"] == 0           # nothing in flight now
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["state_bytes_total"] == 3 * per_slot
    assert c["state_slots_started"] == 3
    assert c["state_refused_prefix_lookups"] == 3
    assert c.get("prefix_hits", 0) == 0
    steps = c["decode_steps"]
    assert c["kda_state_bytes"] == steps * 2 * 3 * per_slot
    assert c["state_bytes_live_ticks"] == steps * per_slot
    assert c["kv_bytes_held_ticks"] > 0
    assert c["kda_layer_calls"] >= 4 * steps
    # the router's counters count the five expert layers, as mistral4's do
    assert c["moe_layer_calls"] % 5 == 0 and c["moe_dropped_tokens"] == 0
    assert c["moe_held_assignments"] + c["moe_absent_assignments"] \
        == c["moe_assignments"]


def test_a_slots_second_tenant_starts_from_zero():
    """One slot, two requests one after the other: the second reads the
    log-probs a fresh engine serves it (position 0 reads a zero state and
    history whatever the first left)."""
    pt.set_amp(False)
    rng = np.random.default_rng(3)
    first, second = rng.integers(0, 96, size=30), rng.integers(0, 96, size=19)
    eng = _engine(slots=1)
    fam.served_logprobs(eng, first, 9)
    used, out_used = fam.served_logprobs(eng, second, 9)
    fresh, out_fresh = fam.served_logprobs(_engine(slots=1), second, 9)
    np.testing.assert_array_equal(out_used, out_fresh)
    for (p0, v0, i0), (p1, v1, i1) in zip(used, fresh):
        assert p0 == p1
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(v0, v1)


def test_requests_side_by_side_equal_requests_alone():
    """Three requests in flight at once (prefill chunks of one interleaved
    with decode ticks of the others): each emits what it emits alone."""
    pt.set_amp(False)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 96, size=n) for n in (40, 7, 23)]
    eng = _engine()
    together = eng.generate_all(prompts, max_new_tokens=10)
    for p, out in zip(prompts, together):
        alone = _engine(slots=1).generate_all([p], max_new_tokens=10)[0]
        np.testing.assert_array_equal(out, alone)


@pytest.mark.parametrize("dtype,bound", [("bfloat16", BF16_PAGE_TOL)])
def test_bf16_pages_and_history_hold_the_median(dtype, bound):
    pt.set_amp(False)
    config = tiny_config(page_dtype=dtype)
    eng = _engine(config=config)
    errs, _, _, _ = fam.served_errors(
        config, fam.weights_of(None, eng.scope), eng,
        np.random.default_rng(0).integers(0, 96, size=37), 12,
        variants=("",) + tuple(MATH_VARIANTS))
    assert np.median(errs[""]) <= bound, np.median(errs[""])
    for v in MATH_VARIANTS:
        assert np.median(errs[v]) > bound, (v, np.median(errs[v]))


def test_amp_holds_the_median_and_every_fault_of_the_mathematics_fails():
    pt.set_amp(True)
    try:
        config = tiny_config(page_dtype="bfloat16", param_dtype="bfloat16")
        eng = _engine(config=config)
        errs, _, _, _ = fam.served_errors(
            config, fam.weights_of(None, eng.scope), eng,
            np.random.default_rng(0).integers(0, 96, size=37), 12,
            variants=("",) + tuple(MATH_VARIANTS))
    finally:
        pt.set_amp(False)
    assert np.median(errs[""]) <= BF16_TOL, np.median(errs[""])
    for v in MATH_VARIANTS:
        assert np.median(errs[v]) > BF16_TOL, (v, np.median(errs[v]))


# ---------------------------------------------------------------------------
# the router and the held share
# ---------------------------------------------------------------------------
def _expert_weights(seed=0, d=32, f=16, E=8):
    rng = np.random.default_rng(seed)
    w = {"router_w": rng.normal(size=(d, E)),
         "router_b": rng.normal(size=(E,)) * 0.3,
         "moe_gate_w": rng.normal(size=(E, d, f)) * 0.2,
         "moe_up_w": rng.normal(size=(E, d, f)) * 0.2,
         "moe_down_w": rng.normal(size=(E, f, d)) * 0.2,
         "shared_gate_w": rng.normal(size=(d, f)) * 0.2,
         "shared_up_w": rng.normal(size=(d, f)) * 0.2,
         "shared_down_w": rng.normal(size=(f, d)) * 0.2}
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def _program_layer(config, w, x, held=None, bias=True, groups=True,
                   score="sigmoid", shared=True):
    first, count = held or (0, 8)
    sl = slice(first, first + count)
    return moe_topk(
        x, w["router_w"], w["moe_gate_w"][sl], w["moe_up_w"][sl],
        w["moe_down_w"][sl], config["num_experts_per_tok"],
        config["norm_topk_prob"], held=held,
        shared=(w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"])
        if shared else None,
        routed_scale=config["routed_scaling_factor"], score=score,
        bias=w["router_b"] if bias else None,
        n_group=config["n_group"] if groups else 1,
        topk_group=config["topk_group"] if groups else 1)


@pytest.mark.parametrize("bias,groups,score,variant", [
    (True, True, "sigmoid", ""),
    (False, True, "sigmoid", "no_router_bias"),
    (True, False, "sigmoid", "no_group_limit"),
    (True, True, "softmax", "softmax_router"),
])
def test_router_equals_the_reference(bias, groups, score, variant):
    """``moe_topk(score=, bias=, n_group=, topk_group=)`` against the
    reference's dense experts masked by ITS top-k set; each switch off is
    the reference's variant of that name."""
    pt.set_amp(False)
    config = tiny_config()
    w = _expert_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(24, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts, _ = _program_layer(config, w, x, bias=bias,
                                      groups=groups, score=score)
        ref = fam.expert_layer({**config, "num_experts": 8}, w, x,
                               held=(0, 8), variant=variant)
        s, chosen = fam.router_choice(config, x, w["router_w"],
                                      w["router_b"], variant)
    np.testing.assert_allclose(y, ref, atol=2e-5)
    np.testing.assert_array_equal(counts, chosen.sum(0))
    assert int(counts.sum()) == 24 * 2 and counts.shape == (8,)


def test_the_bias_changes_the_selection_and_not_the_weight():
    """One token, scores set by hand: the bias lifts expert 5 over expert
    1 into the top-2, and the chosen experts weigh in with their BARE
    scores s / sum s (x 2.5), the bias nowhere in the weights."""
    pt.set_amp(False)
    d, E = 8, 8
    logits = np.array([2.0, 1.0, -3, -3, -3, 0.5, -3, -3], np.float32)
    router_w = np.zeros((d, E), np.float32)
    router_w[0] = logits
    x = np.zeros((1, d), np.float32)
    x[0, 0] = 1.0
    bias = np.zeros(E, np.float32)
    bias[5] = 0.5
    rng = np.random.default_rng(0)
    gate_w, up_w = (jnp.asarray(rng.normal(size=(E, d, 4)), jnp.float32)
                    for _ in range(2))
    down_w = jnp.asarray(rng.normal(size=(E, 4, d)), jnp.float32)

    def run(b):
        return moe_topk(jnp.asarray(x), jnp.asarray(router_w), gate_w, up_w,
                        down_w, 2, True, score="sigmoid", routed_scale=2.5,
                        bias=None if b is None else jnp.asarray(b))

    s = 1.0 / (1.0 + np.exp(-logits))

    def expert(e):
        h = jax.nn.silu(x @ gate_w[e]) * (x @ up_w[e])
        return np.asarray(h @ down_w[e])[0]

    y0, c0, _ = run(None)
    y1, c1, _ = run(bias)
    assert list(np.nonzero(c0)[0]) == [0, 1]
    assert list(np.nonzero(c1)[0]) == [0, 5]
    for y, (a, b) in ((y0, (0, 1)), (y1, (0, 5))):
        want = 2.5 * (s[a] * expert(a) + s[b] * expert(b)) / (s[a] + s[b])
        np.testing.assert_allclose(y[0], want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_a_held_share_equals_the_references_share(first):
    pt.set_amp(False)
    config = tiny_config()
    w = _expert_weights(2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(16, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        y, counts, _ = _program_layer(config, w, x, held=(first, 2))
        sl = slice(first, first + 2)
        ref = fam.expert_layer(
            config, {**w, **{k: w[k][sl] for k in
                             ("moe_gate_w", "moe_up_w", "moe_down_w")}},
            x, held=(first, 2))
    np.testing.assert_allclose(y, ref, atol=2e-5)
    assert counts.shape == (8,) and int(counts.sum()) == 32


def test_the_four_held_shares_add_up_to_the_uncut_layer():
    """Shares (0, 2), (2, 2), (4, 2), (6, 2) of the router's 8, the shared
    expert counted once, against the layer with all 8 held."""
    pt.set_amp(False)
    config = tiny_config()
    w = _expert_weights(4)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(16, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, counts, _ = _program_layer(config, w, x)
        parts = [_program_layer(config, w, x, held=(f, 2), shared=(f == 0))
                 for f in (0, 2, 4, 6)]
    np.testing.assert_allclose(sum(y for y, _, _ in parts), whole, atol=3e-5)
    for _, c, _ in parts:
        np.testing.assert_array_equal(c, counts)


def test_todays_callers_of_moe_topk_are_unchanged_bit_for_bit():
    """The defaults are the call it always was: no new keyword, the same
    result as with every new one at its default."""
    pt.set_amp(False)
    w = _expert_weights(6)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(16, 32)),
                    jnp.float32)
    a = moe_topk(x, w["router_w"], w["moe_gate_w"], w["moe_up_w"],
                 w["moe_down_w"], 2, True)
    b = moe_topk(x, w["router_w"], w["moe_gate_w"], w["moe_up_w"],
                 w["moe_down_w"], 2, True, score="softmax", bias=None,
                 n_group=1, topk_group=1)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------
def test_block_attrs_round_trip_and_planes_by_kind():
    spec = fam.spec_of(tiny_config())
    attrs = spec.block.attrs()
    json.dumps(attrs)                    # a program's attrs are JSON
    assert Block.from_attrs(attrs) == spec.block
    assert attrs["layer_pattern"] == ["kda", "kda", "mla"]
    assert attrs["router_score"] == "sigmoid" and attrs["n_group"] == 4
    assert "q_lora_rank" not in attrs and attrs["first_dense"] == 1
    assert spec.block.attn_kinds == ("kda", "kda", "mla")
    assert spec.block.kinds is None and not spec.block.has_window
    layers = {key: spec.plane_layers(key)
              for _, key, _, _ in spec.stack_planes()}
    assert layers["ln1_s"] == 6 and layers["kda_qkv_w"] == 4
    assert layers["kv_a_w"] == 2 and layers["q_w"] == 2
    assert layers["dense_up_w"] == 1 and layers["router_b"] == 5
    assert "q_a_w" not in layers and "qkv_w" not in layers
    planes = {key: shape for _, key, shape, _ in spec.stack_planes()}
    assert planes["kda_qkv_w"] == [32, 3 * 32]
    assert planes["kda_conv_w"] == [4, 3 * 32]
    assert planes["q_w"] == [32, 2 * (8 + 4)]
    assert planes["attn_gate_w"] == [32, 2]
    assert planes["moe_gate_w"] == [2, 32, 16]          # the held experts
    assert spec.slot_state() == [
        ("KdaState", (2, 16, 16), "float32", 4),
        ("KdaConv", (3, 96), "float32", 4)]
    assert spec.layers_of(False) == 2 and spec.layers_of(True) == 0
    assert spec.cache_pools == 1 and spec.cache_row_width == 16 + 4
    assert spec.cache_bytes_per_token == 2 * 20 * 4


def test_the_published_widths_count_up_to_the_issues_arithmetic():
    spec = fam.spec_of(bench_config("ling-3.0-flash.json"))
    assert spec.n_params() == 3_691_552_544
    assert spec.state_bytes_per_slot == 5 * (2_097_152 + 73_728)
    assert spec.cache_row_width == 640 and spec.layers_of(False) == 1
    assert spec.cache_bytes_per_token == 1280
    assert spec.plane_layers("moe_gate_w") == 4
    assert spec.plane_layers("dense_gate_w") == 2


@pytest.mark.parametrize("widths,row", [((16, 4), 20), ((64, 64), 128),
                                        ((256, 64), 384), ((512, 64), 640)])
def test_a_latent_row_is_held_at_whole_lane_rows(widths, row):
    r, rope = widths
    blk = Block(num_heads=2, use_rope=True, norm="rms_norm", bias=False,
                attn="mla", q_lora_rank=0, kv_lora_rank=r,
                qk_nope_head_dim=8, qk_rope_head_dim=rope, v_head_dim=8)
    assert blk.cache_row(64) == (1, row)


@pytest.mark.parametrize("kw,msg", [
    (dict(layer_pattern=("kda", "full+rope")), "every entry"),
    (dict(layer_pattern=("kda", "mla"), attn="mha"), "latent block"),
    (dict(layer_pattern=("full+rope",), attn="mla"), "latent block"),
    (dict(layer_pattern=("kda", "mla"), kda_head_dim=0), "kda_head_dim"),
    (dict(router_score="tanh"), "router_score"),
    (dict(n_group=2, topk_group=3), "topk_group"),
    (dict(attn_gate="row"), "attn_gate"),
    # (a dense head under full / window K/V layers is a spec since PR 49;
    # one latent kind without a pattern still has no planes for it)
    (dict(first_dense=1, layer_pattern=None), "first_dense"),
    (dict(draft_block=True), "draft_block"),
])
def test_block_refuses_what_it_cannot_mean(kw, msg):
    base = dict(num_heads=2, use_rope=True, norm="rms_norm", bias=False,
                ffn="swiglu_moe", experts_per_tok=2, attn="mla",
                kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                v_head_dim=8, layer_pattern=("kda", "mla"), kda_head_dim=8)
    base.update(kw)
    if base["attn"] == "mha":
        for k in ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim"):
            base[k] = 0
    with pytest.raises(ValueError, match=msg):
        Block(**base)


@pytest.mark.parametrize("name", ["gpt2-medium.json", "olmoe-1b-7b.json",
                                  "smallthinker-21b-a3b.json",
                                  "mistral-small-4-119b.json"])
def test_every_spec_of_today_has_no_slot_state_and_its_old_attrs(name):
    """The benchmark's other LM configurations: an empty ``slot_state``,
    planes that all lead with ``n_layers``, and attrs without one of the
    keys this stack brought."""
    import importlib

    config = bench_config(name)
    family = importlib.import_module(
        "benchmark.families." + config["family"])
    if hasattr(family, "spec_of"):
        spec = family.spec_of(config)
    else:                               # the GPT-2 block: sizes alone
        sz = family.sizes(config)
        spec = LMSpec(vocab_size=sz["vocab_size"], d_model=sz["d_model"],
                      n_layers=sz["n_layers"], num_heads=sz["num_heads"],
                      max_len=sz["max_len"], d_ff=sz["d_ff"])
        assert spec.block.attrs() == {"num_heads": sz["num_heads"],
                                      "num_kv_heads": None,
                                      "use_rope": False}
    assert spec.slot_state() == [] and spec.state_bytes_per_slot == 0
    spec.block.require_stateless("anything")
    new = {"router_score", "router_bias", "n_group", "topk_group",
           "first_dense", "attn_gate", "kda_head_dim", "kda_conv",
           "kda_lower_bound"}
    assert not new & set(spec.block.attrs())
    assert {spec.plane_layers(key)
            for _, key, _, _ in spec.stack_planes()} == {spec.n_layers}
    assert spec.block.attn_kinds is None


# ---------------------------------------------------------------------------
# where a state cannot follow
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def refusing():
    pt.set_amp(False)
    return _engine()


def _submit(eng, **meta):
    from paddle_tpu.serving.batcher import Request

    req = Request({"prompt": [1, 2, 3]}, dict(max_new_tokens=2, **meta),
                  None)
    eng.admit([req])
    return req


@pytest.mark.parametrize("meta", [dict(beam_size=2),
                                  dict(resume_tokens=[4, 5])])
def test_requests_that_fork_or_re_enter_are_refused(refusing, meta):
    req = _submit(refusing, **meta)
    with pytest.raises(BlockNotSupportedError, match="recurrent state"):
        req.future.result(timeout=1)
    assert refusing.active == 0


@pytest.mark.parametrize("call", ["export_slot", "adopt_slot",
                                  "share_cache_with", "disagg",
                                  "handoff_payload"])
def test_every_handoff_entry_point_raises(refusing, call):
    from paddle_tpu.serving.batcher import Request
    from paddle_tpu.serving.disagg import DisaggEngine

    spec = refusing.spec
    with pytest.raises(BlockNotSupportedError):
        if call == "export_slot":
            refusing.export_slot(0)
        elif call == "adopt_slot":
            refusing.adopt_slot({"st": None})
        elif call == "share_cache_with":
            GenerationEngine(spec, refusing.scope, slots=3,
                             share_cache_with=refusing)
        elif call == "disagg":
            DisaggEngine.build(spec, scope=refusing.scope, slots=3)
        else:
            refusing.admit([Request({"prompt": [1], "handoff": {}}, {},
                                    None)])


@pytest.mark.parametrize("op", ["pipelined_transformer_stack",
                                "transformer_stack_generate"])
def test_the_train_and_one_shot_ops_refuse_the_stack(op):
    from paddle_tpu.core.registry import get_op

    spec = fam.spec_of(tiny_config())
    ins = {slot: [jnp.zeros([spec.plane_layers(key)] + shape)]
           for slot, key, shape, _ in spec.stack_planes()}
    ins.update(X=[jnp.zeros((1, 4, 32))],
               Prompt=[jnp.zeros((1, 4), jnp.int32)],
               TokEmb=[jnp.zeros((96, 32))], FinalLnS=[jnp.zeros((32,))],
               HeadW=[jnp.zeros((32, 96))])
    attrs = {**spec.block.attrs(), "max_new_tokens": 1}
    fn = get_op(op).fn
    with pytest.raises(BlockNotSupportedError, match="paged prefill"):
        fn(attrs, ins) if op.startswith("pipelined") else fn(attrs, ins,
                                                             None)
