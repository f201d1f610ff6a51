"""Layers that are a mixer OR a feed-forward alone, Mamba-2 as a second
recurrence, and ungated relu^2 experts in a latent
(``LMSpec(layer_pattern=("mamba2+none", "none+ffn", .., "gqa+none", ..))``)
— at a tiny size on the CPU against the plain float32 reference in
``benchmark/families/mamba2_gqa_moe_lm.py``: d 32, Mamba-2 with 4 heads of
16 in 2 groups over 16 state dimensions, 4 query / 2 KV heads of 8, experts
0..3 held of a router over 8 (top-3) in a latent of 16, through the normal
path (``GenerationEngine(spec, ..)``).

Tolerances. float32 everywhere: program (chunked SSD prefill from the slot's
state, the recurrent step, paged grouped-query decode, sorted grouped
experts) and reference (one scan over the sequence, full scores, dense
masked experts, no cache) run the same arithmetic in another order:
observed <= 2e-6 on log-probs, the bound is 2e-5; every wrong model of the
reference's ``VARIANTS`` that leaves a piece of the mathematics out lies
>= 5e-3 away."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import mamba2_gqa_moe_lm as fam
from paddle_tpu.kernels import mamba2
from paddle_tpu.lm_spec import Block, BlockNotSupportedError
from paddle_tpu.ops import pipeline_ops
from paddle_tpu.ops.moe_ops import moe_topk
from paddle_tpu.serving import GenerationEngine

F32_TOL = 2e-5
WRONG_TOL = 5e-3
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MATH_VARIANTS = [v for v in fam.VARIANTS
                 if v not in ("bf16_stated_f32", "bf16_state")]
ENGINE = {"slots": 3, "page_size": 8, "n_pages": 60, "max_len": 96,
          "prompt_buckets": [8, 16], "prefill_batch_buckets": [1],
          "prefill_chunk": 16, "mask_plane": 0}


def tiny_config(**assumed):
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           "nemotron3s-tiny.json")) as f:
        config = json.load(f)
    config["assumed"].update(assumed)
    return config


def bench_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return json.load(f)


def _engine(seed=7, config=None, beam=True, **engine):
    eng, _ = fam.build_engine(config or tiny_config(),
                              {"engine": {**ENGINE, **engine}}, seed,
                              **({"beam_width": 8} if beam else {}))
    return eng


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, 96, size=n)


@pytest.fixture(scope="module")
def served():
    """One float32 engine; prompts of 5, 21 and 37 tokens (12 new each)
    against the right model, the 37-token one against every wrong one."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config=config)
    w = fam.weights_of(None, eng.scope)
    out = {}
    for n in (5, 21, 37):
        out[n] = fam.served_errors(
            config, w, eng, _prompt(n, n), 12,
            variants=("",) + (tuple(fam.VARIANTS) if n == 37 else ()))
    out["counters"] = eng.metrics.snapshot()
    out["stats"] = eng.cache_stats()
    return out


# ---------------------------------------------------------------------------
# the three forms of the recurrence
# ---------------------------------------------------------------------------
def _mamba_case(seed, b, t, H=4, P=8, G=2, N=128):
    rng = np.random.default_rng(seed)
    f = jnp.float32
    x = jnp.asarray(rng.normal(size=(b, t, H, P)), f)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, size=(b, t, H)), f)
    g = -jnp.asarray(rng.uniform(1, 16, size=(H,)), f) * dt
    B, C = (jnp.asarray(rng.normal(size=(b, t, G, N)), f) for _ in range(2))
    S0 = jnp.asarray(rng.normal(size=(b, H, P, N)), f)
    return x, dt, g, B, C, S0


@pytest.mark.parametrize("t,block", [(5, 128), (128, 128), (100, 32),
                                     (37, 8), (256, 128)])
def test_chunked_form_equals_the_token_by_token_scan(t, block):
    x, dt, g, B, C, S0 = _mamba_case(0, 2, t)
    y, S = mamba2.mamba2_recurrent(x, dt, g, B, C, S0)
    y2, S2 = mamba2.mamba2_chunked(x, dt, g, B, C, S0, block=block)
    np.testing.assert_allclose(y2, y, atol=3e-5)
    np.testing.assert_allclose(S2, S, atol=3e-6)


@pytest.mark.parametrize("first,second", [(16, 8), (8, 16), (5, 32)])
def test_state_and_history_carry_across_chunk_sizes_that_differ(first,
                                                                second):
    """``_mamba_layer`` over one sequence cut at two places with two SSD
    block sizes: the state and the convolution's history it leaves in the
    slot, and every token's output, are those of the sequence in one
    piece."""
    spec = fam.spec_of(tiny_config())
    rng = np.random.default_rng(first)
    p = {key: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
         for _, key, shape, _ in spec.stack_planes()
         if key.startswith("mamba_") or key == "ln1_s"}
    T = 43
    h = jnp.asarray(rng.normal(size=(1, T, 32)), jnp.float32)
    cw = spec.block.mamba_conv_width
    state = jnp.asarray(rng.normal(size=(2, 3, 4, 16, 16)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(2, 3, 3, cw)), jnp.float32)

    def run(blk, cuts):
        s, c, outs, at = state, conv, [], 0
        for n in cuts:
            rows = (jnp.asarray([1]), jnp.asarray([at]), jnp.asarray([n]))
            y, s, c = pipeline_ops._mamba_layer(blk, p, h[:, at:at + n], s,
                                                c, 1, rows)
            outs.append(y)
            at += n
        return jnp.concatenate(outs, axis=1), s, c

    whole = run(dataclasses.replace(spec.block, mamba_chunk=64), [T])
    cut = run(dataclasses.replace(spec.block, mamba_chunk=second),
              [first, 20, T - 20 - first])
    for a, b in zip(whole, cut):
        np.testing.assert_allclose(a, b, atol=2e-5)
    # only slot 1 of layer 1 moved
    keep = np.ones((2, 3), bool)
    keep[1, 1] = False
    assert (np.asarray(cut[1])[keep] == np.asarray(state)[keep]).all()
    assert (np.asarray(cut[2])[keep] == np.asarray(conv)[keep]).all()


@pytest.mark.parametrize("live", [None, (True, False, True),
                                  (False, False, False)])
def test_decode_kernel_equals_one_step_of_the_scan(live):
    """Interpret mode, the whole state array [L, S, H, P, N] in and out:
    layer 1 of the live rows advances, every other tile is bit for bit."""
    S = 3
    x, dt, g, B, C, _ = _mamba_case(3, S, 1)
    state = jnp.asarray(np.random.default_rng(1).normal(
        size=(2, S, 4, 8, 128)), jnp.float32)
    mask = None if live is None else jnp.asarray(live)
    y, new = mamba2.mamba2_decode_step(
        x[:, 0] * dt[:, 0, :, None], jnp.exp(g[:, 0]), B[:, 0], C[:, 0],
        state, jnp.int32(1), mask, interpret=True)
    y_ref, s_ref = mamba2.mamba2_recurrent(x, dt, g, B, C, state[1])
    assert (np.asarray(new[0]) == np.asarray(state[0])).all()
    for i in range(S):
        if live is None or live[i]:
            np.testing.assert_allclose(y[i], y_ref[i, 0], atol=1e-5)
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

    S, H, P, G, N = 16, 128, 64, 8, 128
    compiled = jax.jit(mamba2.mamba2_decode_step, donate_argnums=(4,)).lower(
        arg((S, H, P)), arg((S, H)), arg((S, G, N)), arg((S, G, N)),
        arg((5, S, H, P, N)), arg((), jnp.int32),
        arg((S,), jnp.bool_)).compile()
    assert f"%{mamba2.KERNEL}" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = 5 * S * H * P * N * 4
    assert mem.alias_size_in_bytes >= state_bytes
    assert mem.temp_size_in_bytes < state_bytes // 8


def test_a_row_with_no_valid_token_leaves_state_and_history_alone():
    """``_mamba_layer``, the decode form (row i IS slot i): a vacant or
    still-prefilling row keeps its tiles bit for bit while a live neighbour
    advances; the prefill form: the padding row's write is dropped and a
    row that starts at position 0 reads zeros whatever the slot held."""
    spec = fam.spec_of(tiny_config())
    blk = spec.block
    rng = np.random.default_rng(5)
    p = {key: jnp.asarray(rng.normal(size=shape) * 0.3, jnp.float32)
         for _, key, shape, _ in spec.stack_planes()
         if key.startswith("mamba_") or key == "ln1_s"}
    cw = blk.mamba_conv_width
    state = jnp.asarray(rng.normal(size=(4, 3, 4, 16, 16)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(4, 3, 3, cw)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(3, 1, 32)), jnp.float32)
    rows = (None, jnp.asarray([7, 0, 3]), jnp.asarray([1, 0, 1]))
    _, s1, c1 = pipeline_ops._mamba_layer(blk, p, h, state, conv, 2, rows)
    for arr0, arr1 in ((state, s1), (conv, c1)):
        a0, a1 = np.asarray(arr0), np.asarray(arr1)
        assert (a0[[0, 1, 3]] == a1[[0, 1, 3]]).all()     # other layers
        assert (a0[2, 1] == a1[2, 1]).all()               # the vacant row
        assert (a0[2, 0] != a1[2, 0]).any() and (a0[2, 2] != a1[2, 2]).any()
    hp = jnp.asarray(rng.normal(size=(2, 8, 32)), jnp.float32)
    rows = (jnp.asarray([1, 3]), jnp.asarray([0, 0]), jnp.asarray([5, 0]))
    ctx, s2, c2 = pipeline_ops._mamba_layer(blk, p, hp, state, conv, 0, rows)
    zero = pipeline_ops._mamba_layer(blk, p, hp, jnp.zeros_like(state),
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
    """Chunked prefill (16-token chunks, SSD blocks of 8) then decode
    ticks: the served top-8 log-probs at every chunk end and decode step."""
    errs, again, positions, _, _ = served[n]
    assert again.size == n + 12
    assert len(positions) == -(-n // 16) + 11
    assert max(errs[""]) <= F32_TOL, errs[""]


@pytest.mark.parametrize("variant", sorted(MATH_VARIANTS))
def test_every_left_out_piece_fails_the_float32_bound(served, variant):
    errs = served[37][0]
    assert max(errs[variant]) >= WRONG_TOL, (variant, max(errs[variant]))


@pytest.mark.parametrize("n", [5, 21, 37])
def test_the_slots_state_equals_the_references_recurrence(served, n):
    state = served[n][3][""]
    assert max(state["rel_err"]) <= 1e-5, state
    assert max(state["bits"]) == 0


def test_a_bfloat16_state_fails_the_state_limit(served):
    state = served[37][3]["bf16_state"]
    assert min(state["bits"]) > fam.CHECK_STATE_BITS_TOL, state


def test_one_precision_lower_fails_the_float32_bound(served):
    errs = served[37][0]
    assert max(errs["bf16_stated_f32"]) >= 50 * F32_TOL


def test_the_engine_counts_the_state_and_the_latent(served):
    snap, stats = served["counters"], served["stats"]
    c, g = snap["counters"], snap["gauges"]
    spec = fam.spec_of(tiny_config())
    cw = 4 * 16 + 2 * 2 * 16
    per_slot = 4 * (4 * 16 * 16 * 4 + 3 * cw * 4)
    assert spec.state_bytes_per_slot == per_slot
    assert g["mem/state_bytes_per_slot"] == per_slot
    assert stats["state_bytes_total"] == 3 * per_slot
    assert c["state_slots_started"] == 3
    steps = c["decode_steps"]
    assert c["mamba_state_bytes"] == steps * 2 * 3 * per_slot
    assert c["mamba_layer_calls"] >= 4 * steps
    assert "kda_layer_calls" not in c and "kda_state_bytes" not in c
    # 5, 16 + 5 and 16 + 16 + 5 tokens in calls of 8 or 16 (the buckets), SSD
    # blocks of 8, over the 4 mamba layers
    assert c["mamba_chunks"] == 4 * (1 + (2 + 1) + (2 + 2 + 1))
    assert c["state_bytes_live_ticks"] == steps * per_slot
    assert c["kv_bytes_held_ticks"] > 0
    # four expert layers a call, every row through the latent twice
    assert c["moe_layer_calls"] % 4 == 0 and c["moe_dropped_tokens"] == 0
    assert c["moe_held_assignments"] + c["moe_absent_assignments"] \
        == c["moe_assignments"]


def test_a_prefill_call_is_spanned_as_a_mamba_unit():
    """A prefill call of this spec counts the SSD blocks it scans in
    ``mamba_chunks`` (one 16-token call over the 4 mamba layers in blocks
    of 8: 8), and the pass that ran it holds that one unit. (A span of
    its own round the call said the same and had no reader: PR 56 took it
    out; the test keeps its name so that its id stays.)"""
    pt.set_amp(False)
    eng = _engine(beam=False)
    eng.generate_all([_prompt(2, 11)], max_new_tokens=3)
    c = eng.metrics.snapshot()["counters"]
    assert c["mamba_chunks"] == 8
    assert c["prefills"] == 1 and c["pass_units"] == 1
    assert c["pass_rows_one_unit"] == 1


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
    with decode ticks of the others, vacant rows riding along): each emits
    what it emits alone."""
    pt.set_amp(False)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 96, size=n) for n in (40, 7, 23)]
    eng = _engine(beam=False)
    together = eng.generate_all(prompts, max_new_tokens=10)
    for p, out in zip(prompts, together):
        alone = _engine(slots=1, beam=False).generate_all(
            [p], max_new_tokens=10)[0]
        np.testing.assert_array_equal(out, alone)


def test_a_pattern_of_several_periods_scans_them():
    """The same ten layers as two periods of five under ONE ``lax.scan``
    (``n_layers`` 10 over a pattern of 5) serve what the one period of ten
    serves, token for token: planes are held by group either way."""
    pt.set_amp(False)
    config = tiny_config()
    eng = _engine(config=config, beam=False)
    spec = fam.spec_of(config)
    two = dataclasses.replace(spec, layer_pattern=spec.layer_pattern[:5])
    assert two.block.group_index(10) == spec.block.group_index(10)
    eng2 = GenerationEngine(
        two, eng.scope, slots=3, page_size=8, n_pages=60, max_seq_len=96,
        prompt_buckets=(8, 16), prefill_batch_buckets=(1,), prefill_chunk=16,
        eos_id=None, mask_plane=False)
    prompt = _prompt(9, 29)
    np.testing.assert_array_equal(
        eng.generate_all([prompt], max_new_tokens=8)[0],
        eng2.generate_all([prompt], max_new_tokens=8)[0])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def _expert_weights(seed, E=8, d=32, dl=16, f=24, fs=40):
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)

    return {"router_w": n(d, E), "router_b": n(E) * 0.1,
            "moe_latent_down_w": n(d, dl), "moe_latent_up_w": n(dl, d),
            "moe_up_w": n(E, dl, f), "moe_down_w": n(E, f, dl),
            "shared_up_w": n(d, fs), "shared_down_w": n(fs, d)}


def _program_layer(w, x, held=None, shared=True):
    first, count = held or (0, 8)
    return moe_topk(
        x, w["router_w"], None, w["moe_up_w"][first:first + count],
        w["moe_down_w"][first:first + count], 3, True, act="relu2",
        shared=(None, w["shared_up_w"], w["shared_down_w"]) if shared
        else None, held=held, routed_scale=5.0, score="sigmoid",
        bias=w["router_b"],
        latent=(w["moe_latent_down_w"], w["moe_latent_up_w"]))


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_a_held_share_equals_the_references_share(first):
    pt.set_amp(False)
    config = tiny_config()
    w = _expert_weights(first)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 32)),
                    jnp.float32)
    p = {**w, "moe_up_w": w["moe_up_w"][first:first + 2],
         "moe_down_w": w["moe_down_w"][first:first + 2]}
    with jax.default_matmul_precision("highest"):
        got, counts, _ = _program_layer(w, x, held=(first, 2))
        want = fam.expert_layer(config, p, x, held=(first, 2))
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert counts.shape == (8,) and int(counts.sum()) == 48


def test_the_four_held_shares_add_up_to_the_uncut_layer():
    """Shares (0, 2), (2, 2), (4, 2), (6, 2) of the router's 8, the shared
    expert counted once, against the layer with all 8 held — and the uncut
    REFERENCE's layer: the latent's up-projection is linear, so the shares
    add up behind it too."""
    pt.set_amp(False)
    config = tiny_config()
    w = _expert_weights(4)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(16, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, counts, _ = _program_layer(w, x)
        parts = [_program_layer(w, x, held=(f, 2), shared=(f == 0))
                 for f in (0, 2, 4, 6)]
        uncut = fam.expert_layer(config, w, x, held=(0, 8))
    np.testing.assert_allclose(sum(y for y, _, _ in parts), whole, atol=5e-5)
    np.testing.assert_allclose(whole, uncut, atol=5e-5)
    for _, c, _ in parts:
        np.testing.assert_array_equal(c, counts)


def test_gated_callers_of_moe_topk_are_unchanged_bit_for_bit():
    """``latent=None`` and a gate plane: the call it always was."""
    pt.set_amp(False)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    r, g, u, d = (jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
                  for s in ((32, 8), (8, 32, 24), (8, 32, 24), (8, 24, 32)))
    a = moe_topk(x, r, g, u, d, 2, True)
    b = moe_topk(x, r, g, u, d, 2, True, latent=None)
    for p, q in zip(a, b):
        np.testing.assert_array_equal(p, q)


# ---------------------------------------------------------------------------
# the spec
# ---------------------------------------------------------------------------
def test_block_attrs_round_trip_and_planes_by_group():
    spec = fam.spec_of(tiny_config())
    blk = spec.block
    assert Block.from_attrs(json.loads(json.dumps(blk.attrs()))) == blk
    assert blk.layer_parts == tuple(
        {"M": ("mamba2", False), "*": ("gqa", False), "E": (None, True)}[c]
        for c in "MEM*EMEM*E")
    assert blk.mixers == ("mamba2", "gqa")
    slots = blk.stack_slots()
    assert "MoeGateW" not in slots and "SharedGateW" not in slots
    assert {"MambaInW", "MambaConvB", "MoeLatentDownW", "MoeLatentUpW",
            "GqaQkvW"} <= set(slots)
    layers = {key: spec.plane_layers(key) for key in slots.values()}
    assert layers["ln1_s"] == 6 and layers["ln2_s"] == 4
    assert layers["mamba_in_w"] == 4 and layers["gqa_qkv_w"] == 2
    assert layers["router_w"] == layers["moe_up_w"] == 4
    assert spec.pool_layers(False) == 2 and spec.cache_pools == 2
    assert [(n, l) for n, _, _, l in spec.slot_state()] == [
        ("MambaState", 4), ("MambaConv", 4)]
    shapes = {key: shape for _, key, shape, _ in spec.stack_planes()}
    assert shapes["mamba_in_w"] == [32, 64 + 128 + 4]
    assert shapes["moe_up_w"] == [4, 16, 24]
    assert shapes["moe_down_w"] == [4, 24, 16]
    assert shapes["shared_up_w"] == [32, 40]


def test_the_published_widths_count_up_to_the_issues_arithmetic():
    spec = fam.spec_of(bench_config())
    assert spec.n_params() == 4_648_163_712
    assert spec.state_bytes_per_slot == 5 * (128 * 64 * 128 * 4
                                             + 3 * 10240 * 2)
    assert spec.cache_bytes_per_token == 1024
    assert spec.plane_layers("mamba_in_w") == spec.plane_layers(
        "router_w") == 5 and spec.plane_layers("gqa_qkv_w") == 1


_BASE = dict(num_heads=4, num_kv_heads=2, use_rope=True, norm="rms_norm",
             bias=False, ffn="swiglu_moe", experts_per_tok=2,
             mamba_heads=4, mamba_head_dim=8, mamba_groups=2, mamba_state=8)


@pytest.mark.parametrize("kw,msg", [
    (dict(layer_pattern=("mamba2+none", "none+ffn")), "ONE kind that caches"),
    (dict(layer_pattern=("mamba2+none", "gqa+none")), "no feed-forward"),
    (dict(layer_pattern=("mamba2+none", "ffn")), "every entry"),
    (dict(layer_pattern=("mamba2", "gqa+nope")), "every entry"),
    (dict(layer_pattern=("mamba2", "gqa"), mamba_heads=3), "mamba_heads"),
    (dict(layer_pattern=("mamba2", "gqa"), mamba_state=0), "mamba_heads"),
    (dict(layer_pattern=("mamba2", "gqa"), draft_block=True), "draft_block"),
    (dict(layer_pattern=("mamba2", "gqa"), expert_act="relu2",
          first_dense=1), "relu2"),
    (dict(expert_act="relu2"), "relu2"),
    (dict(expert_latent=16), "expert_latent"),
])
def test_block_refuses_what_it_cannot_mean(kw, msg):
    with pytest.raises(ValueError, match=msg):
        Block(**{**_BASE, **kw})


# ---------------------------------------------------------------------------
# where a state cannot follow
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def refusing():
    pt.set_amp(False)
    return _engine(beam=False)


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
                                  "handoff_payload", "snapshots"])
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
        elif call == "snapshots":
            GenerationEngine(spec, refusing.scope, slots=3, page_size=8,
                             prefill_chunk=16, snapshot_stride=2,
                             n_snapshots=4)
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


def test_the_cells_files_name_this_family():
    config = bench_config()
    assert config["family"] == "mamba2_gqa_moe_lm"
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert fam.letters_of(config) == "MEMEMEM*EME"
    assert len(config["hybrid_override_pattern"]) == 88
    with open(os.path.join(ROOT, "benchmark", "mixes",
                           "chat-poisson-5k.json")) as f:
        mix = json.load(f)
    assert mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    assert mix["engine"]["max_len"] == config["assumed"]["max_len"]
