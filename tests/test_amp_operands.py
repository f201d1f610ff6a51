"""The AMP operand copies of a float32 model served under bf16 AMP
(``GenerationEngine._adopt_scope``): an engine that holds them emits what
the same engine emits when its programs bind the float32 weights, to the
last bit; the copies follow the weights; and where the mechanism has
nothing to do (weights stored in bf16, no AMP, the train op) every program
is the one it was."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models
from paddle_tpu.ops import pipeline_ops
from paddle_tpu.serving import GenerationEngine, LMSpec
from paddle_tpu.serving.generation import _OPERAND_SOURCE, AMP_OPERAND

VOCAB, D, L, H, MAXLEN = 32, 16, 2, 2, 64
GPT2 = LMSpec(vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
              max_len=MAXLEN)
MOE = LMSpec(vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
             max_len=MAXLEN, use_rope=True, norm="rms_norm", bias=False,
             ffn="swiglu_moe", num_experts=4, experts_per_tok=2, d_expert=8,
             d_shared=8)
SPECS = {"gpt2": GPT2, "moe": MOE}
_ENGINE_KW = dict(slots=4, page_size=8, prompt_buckets=(8, 16),
                  prefill_chunk=8, beam_width=3)
_WEIGHTS = {}


def _weights(spec, seed):
    """The seeded startup of ``spec``, run once: name -> array."""
    key = (id(spec), seed)
    if key not in _WEIGHTS:
        scope, exe = pt.Scope(), pt.Executor(pt.TPUPlace())
        prog, startup = pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            p = layers.data("p_init", shape=[8], dtype="int64")
            models.transformer_lm_generate(p, spec=spec, max_new_tokens=1)
        startup.random_seed = seed
        exe.run(startup, scope=scope)
        _WEIGHTS[key] = {n: scope.get(n) for n in scope.keys()}
    return _WEIGHTS[key]


def _scope(spec, seed=7):
    scope = pt.Scope()
    for name, value in _weights(spec, seed).items():
        scope.set(name, value)
    return scope


def _engine(spec, scope=None, copies=True, cls=GenerationEngine, **kw):
    """An engine under AMP; ``copies=False``: the same engine with its
    programs bound to the float32 weights, as before the copies existed."""
    pt.set_amp(True)        # (conftest's autouse fixture puts it back)
    scope = _scope(spec) if scope is None else scope
    with pytest.MonkeyPatch.context() as mp:
        if not copies:
            mp.setattr(cls, "_amp_operand_names", lambda self: [])
        return cls(spec, scope, **{**_ENGINE_KW, **kw})


def _record(eng):
    """Every fetch of every ``Executor.run`` the engine makes from here
    on: tokens, and with the beam plane the top-k log-probs and ids."""
    calls, run = [], eng.executor.run

    def recording(*args, **kw):
        res = run(*args, **kw)
        calls.append([np.asarray(r) for r in res])
        return res

    eng.executor.run = recording
    return calls


def _gauge(eng, name):
    return eng.metrics.snapshot()["gauges"][name]


def _copy_names(scope):
    return sorted(n for n in scope.keys() if n.startswith(AMP_OPERAND))


def _prompts(rng, lengths):
    return [rng.randint(0, VOCAB, (n,)).astype("int64") for n in lengths]


def _decode_ticks(eng, rng):
    return eng.generate_all(_prompts(rng, (3, 5, 7, 2)), max_new_tokens=6)


def _chunked_prefill(eng, rng):
    # five chunks of 8 beside a short request that keeps ticking
    return eng.generate_all(_prompts(rng, (37, 4)), max_new_tokens=5)


def _prefix_hit(eng, rng):
    prompt, = _prompts(rng, (16,))
    first = eng.generate_all([prompt], max_new_tokens=4)
    hits = eng.metrics.counter("prefix_hit_tokens")
    second = eng.generate_all([prompt], max_new_tokens=4)
    assert eng.metrics.counter("prefix_hit_tokens") > hits
    return first + second


def _beam_plane(eng, rng):
    prompt, = _prompts(rng, (6,))
    ids, scores = eng.generate_beam(prompt, beam_size=3, max_new_tokens=5)
    return [np.asarray(ids), np.asarray(scores)]


TRAFFIC = {"decode_ticks": _decode_ticks, "chunked_prefill": _chunked_prefill,
           "prefix_hit": _prefix_hit, "beam_plane": _beam_plane}


@pytest.mark.parametrize("traffic", sorted(TRAFFIC))
@pytest.mark.parametrize("spec", sorted(SPECS))
def test_operand_copies_change_no_bit_of_what_an_engine_emits(spec, traffic):
    """Tokens, beam ids and scores, and every call's fetched top-3
    log-probs: the engine with the copies against the engine whose
    programs read the float32 weights and cast them in every call."""
    spec = SPECS[spec]
    got, want = [], []
    for copies, out in ((True, got), (False, want)):
        eng = _engine(spec, copies=copies)
        assert bool(eng._operands) == copies
        calls = _record(eng)
        out.append(TRAFFIC[traffic](eng, np.random.RandomState(3)))
        out.append(calls)
    assert len(got[1]) == len(want[1]) > 0
    # (a copy-on-write call fetches its witness alone)
    assert max(len(call) for call in got[1]) >= 3   # tokens, TopI, TopV
    for a, b in zip(got[0], want[0]):
        np.testing.assert_array_equal(a, b)
    for call_a, call_b in zip(got[1], want[1]):
        assert len(call_a) == len(call_b)
        for a, b in zip(call_a, call_b):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)


def test_copies_are_the_casts_a_call_would_make_and_the_weights_stay():
    scope = _scope(GPT2)
    before = {n: scope.get(n) for n in scope.keys()}
    eng = _engine(GPT2, scope)
    assert sorted(eng._operands) == sorted(
        ["lm_head.w"] + [f"lm_stack.stack_{k}"
                         for k in ("qkv_w", "out_w", "ff_w1", "ff_w2")])
    assert _copy_names(scope) == sorted(eng._operands.values())
    for name, copy in eng._operands.items():
        assert scope.get(name) is before[name]      # float32, untouched
        assert scope.get(copy).dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(scope.get(copy)),
            np.asarray(before[name].astype(jnp.bfloat16)))
    held = sum(scope.get(c).nbytes for c in eng._operands.values())
    assert _gauge(eng, "mem/amp_operand_bytes") == held > 0
    # tok_emb / pos_emb (gathered), norm scales and biases: no copy
    op = eng._decode_prog[0].global_block.ops[-1]
    assert op.attrs["param_dtype"] == "float32"
    bound = {slot: names[0] for slot, names in op.inputs.items()}
    assert bound["TokEmb"] == "tok_emb" and bound["PosEmb"] == "pos_emb"
    assert bound["Ln1S"] == "lm_stack.stack_ln1_s"
    assert bound["FfB1"] == "lm_stack.stack_ff_b1"
    assert bound["HeadW"] == AMP_OPERAND + "lm_head.w"
    assert bound["FfW1"] == AMP_OPERAND + "lm_stack.stack_ff_w1"


def test_moe_spec_keeps_its_router_in_float32():
    eng = _engine(MOE)
    assert "lm_stack.stack_router_w" not in eng._operands
    assert {"lm_stack.stack_moe_gate_w", "lm_stack.stack_moe_down_w",
            "lm_stack.stack_shared_up_w", "lm_stack.stack_qkv_w",
            "lm_head.w"} <= set(eng._operands)


@pytest.mark.parametrize("how", ["whole_scope", "one_tensor"])
def test_swap_params_remakes_the_copies_of_what_it_replaced(how):
    """After a swap the next call multiplies the NEW weights: the engine
    emits what a fresh engine on them emits; a copy whose weight the swap
    left alone is the array it was."""
    rng = np.random.RandomState(5)
    prompts = _prompts(rng, (5, 11, 3))
    eng = _engine(GPT2)
    eng.generate_all(prompts, max_new_tokens=4)
    old = {n: eng.scope.get(c) for n, c in eng._operands.items()}
    new = _weights(GPT2, 8)
    if how == "whole_scope":
        # a source that is itself a serving scope: its copies are skipped
        source = _engine(GPT2, _scope(GPT2, 8)).scope
        replaced = dict(new)
    else:
        source = replaced = {"lm_head.w": new["lm_head.w"]}
    eng.swap_params(source)
    for name, copy in eng._operands.items():
        if name in replaced:
            np.testing.assert_array_equal(
                np.asarray(eng.scope.get(copy)),
                np.asarray(new[name].astype(jnp.bfloat16)))
            assert _OPERAND_SOURCE[eng.scope][name] is eng.scope.get(name)
        else:
            assert eng.scope.get(copy) is old[name]
    got = eng.generate_all(prompts, max_new_tokens=4)
    for copies in (True, False):
        scope = _scope(GPT2, 7)
        for name, value in replaced.items():
            scope.set(name, value)
        want = _engine(GPT2, scope, copies=copies)
        for a, b in zip(got, want.generate_all(prompts, max_new_tokens=4)):
            np.testing.assert_array_equal(a, b)


def test_a_second_adopt_casts_only_the_weights_that_changed():
    """``from_saved`` loads into the scope after the engine was built and
    adopts again: the copy of a tensor the load replaced is remade, the
    others are reused."""
    eng = _engine(GPT2)
    old = {n: eng.scope.get(c) for n, c in eng._operands.items()}
    eng._adopt_scope()
    assert all(eng.scope.get(c) is old[n] for n, c in eng._operands.items())
    new = _weights(GPT2, 8)["lm_stack.stack_ff_w2"]
    eng.scope.set("lm_stack.stack_ff_w2", new)
    eng._adopt_scope()
    for name, copy in eng._operands.items():
        if name == "lm_stack.stack_ff_w2":
            np.testing.assert_array_equal(
                np.asarray(eng.scope.get(copy)),
                np.asarray(new.astype(jnp.bfloat16)))
        else:
            assert eng.scope.get(copy) is old[name]


@pytest.mark.parametrize("twin", ["beam_twin", "share_cache_with"])
def test_an_engine_on_a_served_scope_reuses_the_copies_it_finds(twin):
    eng = _engine(GPT2)
    old = {c: eng.scope.get(c) for c in eng._operands.values()}
    kw = (dict(share_cache_with=eng, beam_width=0) if twin != "beam_twin"
          else dict(beam_width=2))
    other = _engine(GPT2, eng.scope, **kw)
    assert other._operands == eng._operands
    assert all(eng.scope.get(c) is v for c, v in old.items())
    assert (_gauge(other, "mem/amp_operand_bytes")
            == _gauge(eng, "mem/amp_operand_bytes"))
    prompts = _prompts(np.random.RandomState(2), (4, 9))
    for a, b in zip(eng.generate_all(prompts, max_new_tokens=3),
                    other.generate_all(prompts, max_new_tokens=3)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["stored_bf16", "float32_without_amp"])
def test_no_copy_where_the_call_casts_nothing(case):
    """A spec stored in bf16 and a float32 spec without AMP: no copy in
    the scope, the gauge reads 0, and the programs name the weights and
    carry the attrs they always did."""
    if case == "stored_bf16":
        spec = LMSpec(vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                      max_len=MAXLEN, param_dtype="bfloat16")
        pt.set_amp(True)
    else:
        spec = GPT2
        pt.set_amp(False)
    eng = GenerationEngine(spec, _scope(spec), **_ENGINE_KW)
    assert eng._operands == {} and _copy_names(eng.scope) == []
    assert _gauge(eng, "mem/amp_operand_bytes") == 0
    eng.generate_all(_prompts(np.random.RandomState(1), (5, 12)),
                     max_new_tokens=3)
    progs = [eng._decode_prog[0]] + [p for p, _ in
                                     eng._prefill_progs.values()]
    assert len(progs) >= 2
    for prog in progs:
        op = prog.global_block.ops[-1]
        assert "param_dtype" not in op.attrs
        assert op.attrs == {**op.attrs, **spec.block.attrs()}
        weights = set(spec.param_names())
        named = {names[0] for names in op.inputs.values()}
        assert weights <= named
        assert not any(n.startswith(AMP_OPERAND) for n in named)
    assert _copy_names(eng.scope) == []


def test_memory_budget_prices_the_copies_beside_the_weights():
    """The float32 weights stay on the device behind the copies the
    programs read: the static peak counts both."""
    def peak(copies):
        eng = _engine(GPT2, copies=copies, mem_budget=1e12)
        return _gauge(eng, "mem/static_peak_bytes"), eng

    with_copies, eng = peak(True)
    without, _ = peak(False)
    assert with_copies - without == _gauge(eng, "mem/amp_operand_bytes")


def _drive_one(eng, payload):
    from paddle_tpu.serving import Request

    req = Request(payload, {"max_new_tokens": 6}, None)
    eng._drive([req])
    return np.asarray(req.future.result(timeout=0.1))


def test_seq2seq_engine_copies_only_what_its_ops_cast():
    """The encoder-decoder engine runs its own decoder ops, which cast
    the qkv projection and the head and multiply the rest in float32: it
    holds those two copies and emits what it emitted."""
    from paddle_tpu.decoding import Seq2SeqGenerationEngine, Seq2SeqSpec

    spec = Seq2SeqSpec(src_vocab_size=24, tgt_vocab_size=20, d_model=16,
                       n_layers=2, num_heads=2, max_src_len=16,
                       max_tgt_len=32)
    weights = pt.Scope()
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        src = layers.data("src", shape=[16], dtype="int64")
        slen = layers.data("slen", shape=[], dtype="int32")
        tgt = layers.data("tgt", shape=[4], dtype="int64")
        models.transformer_nmt_teacher(
            src, slen, tgt, src_vocab_size=24, tgt_vocab_size=20,
            d_model=16, n_layers=2, num_heads=2, max_src_len=16,
            max_tgt_len=32)
    startup.random_seed = 11
    pt.Executor(pt.TPUPlace()).run(startup, scope=weights)
    rng = np.random.RandomState(4)
    payloads = [{"src": rng.randint(2, 24, (n,)).astype("int64")}
                for n in (5, 9, 12)]
    outs = []
    for copies in (True, False):
        scope = pt.Scope()
        for name in weights.keys():
            scope.set(name, weights.get(name))
        eng = _engine(spec, scope, copies=copies,
                      cls=Seq2SeqGenerationEngine, slots=4, page_size=8,
                      prompt_buckets=None, prefill_chunk=None, beam_width=2)
        assert sorted(eng._operands) == (
            ["lm_head.w", "lm_stack.stack_qkv_w"] if copies else [])
        outs.append([_drive_one(eng, p) for p in payloads])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the train op: live float32 master weights, whose cast is work
# ---------------------------------------------------------------------------
def _parent_mm(blk, eq, a, w, name=None):
    """``_mm`` as it was before a block could state its weights' dtype
    (``name``: the tag the layer checkpoint saves a result under)."""
    a_c, w_c = pipeline_ops.amp_cast(a, w)
    pref = jnp.float32 if w.dtype == jnp.bfloat16 else None
    y = jnp.einsum(eq, a_c, w_c, precision=pipeline_ops.mxu_precision(),
                   preferred_element_type=pref)
    if name is not None:
        y = pipeline_ops.checkpoint_name(y, name)
    return y.astype(a.dtype)


def _train_step_text():
    """The lowered text of a tiny stacked LM's whole train step."""
    T = 16
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        logits = models.transformer_lm(
            ids, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
            max_len=MAXLEN, pipeline_stack=True, remat=True)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, VOCAB]),
            layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(
            loss, startup_program=startup)
    startup.random_seed = 3
    exe, scope = pt.Executor(pt.TPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)
    stack = next(op for op in main.global_block.ops
                 if op.type == "pipelined_transformer_stack")
    assert "param_dtype" not in stack.attrs
    feed = {"ids": np.zeros((2, T), np.int64),
            "tgt": np.zeros((2, T), np.int64)}
    fn, args = exe.as_function(main, feed, [loss], scope=scope)
    return jax.jit(fn).lower(*args).as_text()


def test_train_step_under_amp_lowers_to_the_text_it_lowered_to(monkeypatch):
    """The stated dtype is a serving engine's to give: a train program
    gives none, its float32 master weights are cast inside the step (they
    change every step), and the step lowers to the same text with the
    operand's dtype deciding, as it did."""
    pt.set_amp(True)
    text = _train_step_text()
    stack = f"tensor<{L}x{D}x{4 * D}xf32>"
    assert f"({stack}) -> tensor<{L}x{D}x{4 * D}xbf16>" in text
    monkeypatch.setattr(pipeline_ops, "_mm", _parent_mm)
    parent = _train_step_text()
    assert (hashlib.sha256(text.encode()).hexdigest()
            == hashlib.sha256(parent.encode()).hexdigest())
