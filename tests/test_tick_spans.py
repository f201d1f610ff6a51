"""PR 40's spans and counters where the serve tick's host work happens:
``serving/build_feed`` in the engine, ``executor/feed`` /
``executor/launch`` / ``executor/fetch`` in ``Executor.run``, and the
host bytes a tick hands it (``decode_feed_host_bytes``). Names, order and
parents are contract: the
benchmark's ``tick_idle_*`` readers split the chip's idle time by them.
And PR 47's feed: a call hands ``Executor.run`` ONE packed int32 plane
(``FeedPlane``), the mask of a call without a constrained row stays on the
device, and the tokens are those of the separate feeds."""
import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models, trace
from paddle_tpu.core.registry import get_op
from paddle_tpu.decoding import SamplingParams, TokenBanMask
from paddle_tpu.serving import GenerationEngine, LMSpec
from paddle_tpu.serving.generation import CallFeed, FeedPlane

VOCAB, D, L, H, MAXLEN, SLOTS = 32, 16, 2, 2, 64, 4
LONG = np.arange(20, dtype=np.int64) % VOCAB        # three chunks of 8
SHORT = (np.arange(6, dtype=np.int64) + 3) % VOCAB  # one grouped prefill

_WEIGHTS = {}


@pytest.fixture(autouse=True)
def _tracer_off_again():
    yield
    trace.disable()
    trace.get_tracer().clear()


def _engine(**kw):
    if not _WEIGHTS:
        scope, prog, startup = pt.Scope(), pt.Program(), pt.Program()
        with pt.program_guard(prog, startup):
            p = layers.data("p_init", shape=[8], dtype="int64")
            models.transformer_lm_generate(
                p, vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
                max_len=MAXLEN, max_new_tokens=1)
        startup.random_seed = 7
        pt.Executor(pt.TPUPlace()).run(startup, scope=scope)
        _WEIGHTS.update({n: scope.get(n) for n in scope.keys()})
    scope = pt.Scope()
    for n, v in _WEIGHTS.items():
        scope.set(n, v)
    eng = GenerationEngine(
        LMSpec(vocab_size=VOCAB, d_model=D, n_layers=L, num_heads=H,
               max_len=MAXLEN), scope, slots=SLOTS, page_size=8,
        prompt_buckets=(8, 16), prefill_chunk=8, **kw)
    eng.warmup()
    return eng


def _traced(eng, prompt):
    """Spans of one request with the tracer on, by id, and its tokens."""
    tracer = trace.enable(level=1)
    tracer.clear()
    out = eng.generate_all([prompt], max_new_tokens=3)[0]
    trace.disable()
    return {s.span_id: s for s in tracer.spans()}, np.asarray(out)


def _inside(spans, call):
    """The first ``call`` span, what the tracer holds of its call in time
    order (the last ``serving/build_feed`` before its executor spans,
    then every span under it) and a parent lookup."""
    by_start = sorted(spans.values(), key=lambda s: s.start)
    top = next(s for s in by_start if s.name == call)
    under = [s for s in by_start if top.start <= s.start
             and s.end <= top.end and s is not top]
    feed = next(s for s in under if s.name == "executor/feed")
    build = [s for s in by_start if s.name == "serving/build_feed"
             and s.end <= feed.start][-1]
    if build not in under:      # a prefill unit's: just before its span
        under.insert(0, build)
    return top, under, lambda s: spans.get(s.parent_id)


@pytest.mark.parametrize("call,prompt,phase", [
    ("serving/decode_step", LONG, "decode"),
    ("serving/prefill_chunk", LONG, "prefill_chunk"),
    ("serving/prefill_group", SHORT, "prefill_group"),
])
def test_a_call_shows_its_four_stages_in_order(call, prompt, phase):
    spans, _ = _traced(_engine(), prompt)
    top, under, parent = _inside(spans, call)
    assert [s.name for s in under] == [
        "serving/build_feed", "executor/feed", "executor/run",
        "executor/launch", "executor/fetch"]
    build, feed, run, launch, fetch = under
    # the one attr: ``tools/trace_summary.py`` prints a span's ``phase``
    assert build.attrs == {"phase": phase}
    assert not feed.attrs and not launch.attrs and not fetch.attrs
    # disjoint and in order: build, feed, launch, fetch
    assert build.end <= feed.start and feed.end <= launch.start
    assert launch.end <= fetch.start
    assert feed.end <= run.start                # directly before the run
    assert parent(launch) is run and parent(fetch) is run
    assert parent(feed) is top and parent(run) is top
    if phase == "decode":       # a tick's feeds are built INSIDE its span
        assert parent(build) is top
    else:                       # a prefill unit's OUTSIDE, just before
        assert build.end <= top.start and parent(build) is parent(top)
    # the attrs PR 30 put on a chunk were read by nothing: gone
    assert not any(k.startswith("ctx_pages") for k in top.attrs)


def test_run_async_launches_and_does_not_fetch():
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.fc(x, size=3)
    exe, scope = pt.Executor(pt.TPUPlace()), pt.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run_async(prog, feed=feed, fetch_list=[y], scope=scope).result()
    tracer = trace.enable(level=1)
    tracer.clear()
    handle = exe.run_async(prog, feed=feed, fetch_list=[y], scope=scope)
    trace.disable()
    spans = sorted(tracer.spans(), key=lambda s: s.start)
    assert [s.name for s in spans] == ["executor/feed", "executor/dispatch",
                                       "executor/launch"]
    feed_span, dispatch, launch = spans
    assert feed_span.end <= dispatch.start
    assert launch.parent_id == dispatch.span_id
    assert np.asarray(handle.result()[0]).shape == (2, 3)


def test_tokens_are_the_same_with_the_tracer_on_and_off():
    eng = _engine()
    off = np.asarray(eng.generate_all([LONG], max_new_tokens=3)[0])
    _, on = _traced(_engine(), LONG)
    assert np.array_equal(on, off)


@pytest.mark.parametrize("mask_plane", [True, False],
                         ids=["mask_on", "mask_off"])
def test_feed_bytes_count_what_the_executor_is_handed(mask_plane):
    eng = _engine(mask_plane=mask_plane)
    handed, run = [], eng.executor.run

    def seen(prog, feed=None, **kw):
        handed.append((prog, sum(v.nbytes for v in feed.values()
                                 if isinstance(v, np.ndarray))))
        return run(prog, feed=feed, **kw)

    eng.executor.run = seen
    before = dict(eng.metrics.snapshot()["counters"])
    eng.generate_all([LONG], max_new_tokens=3)
    after = eng.metrics.snapshot()["counters"]
    decode_prog = eng._decode_prog[0]
    ticks = [b for p, b in handed if p is decode_prog]
    units = [b for p, b in handed if p is not decode_prog]
    assert len(ticks) == 2 and len(units) == 3
    assert (after["decode_feed_host_bytes"]
            - before["decode_feed_host_bytes"]) == sum(ticks)
    # a prefill unit's bytes feed no metric, so nothing counts them
    assert "prefill_feed_host_bytes" not in after
    # ONE int32 plane: token, position, five policy columns, the table.
    # The [slots, vocab] mask of a tick without a constrained row is the
    # device's (PR 47): no host byte, with the mask plane or without
    assert ticks[0] == SLOTS * (7 + eng.pmax) * 4


# ---------------------------------------------------------------------------
# PR 47: one packed plane a call, the neutral mask on the device
# ---------------------------------------------------------------------------
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ENGINES = {}
#: two prompts that open with the same 32 tokens (whole pages, chunks and
#: snapshot strides of every engine below) and a short one
_SHARED = (np.arange(32, dtype=np.int64) * 5 + 1) % VOCAB
PROMPTS = [np.concatenate([_SHARED, [3, 9, 4, 1, 7]]),
           np.concatenate([_SHARED, [8, 2, 6]]),
           (np.arange(6, dtype=np.int64) + 3) % VOCAB]


def _family_engine(family, config, engine, seed):
    fam = importlib.import_module(f"benchmark.families.{family}")
    with open(os.path.join(ROOT, "benchmark", "tests", "data", "configs",
                           config)) as f:
        eng, _ = fam.build_engine(json.load(f), {"engine": engine}, seed)
    eng.warmup()
    return eng


def _spec_engine(spec):
    """One engine a spec, shared by the parity cases: GPT-2's block with
    the beam plane; layer kinds (a table by kind); a state a slot with a
    snapshot pool (a row's slot and snapshot rows in the plane)."""
    if spec not in _ENGINES:
        pt.set_amp(False)
        if spec == "plain":
            _ENGINES[spec] = _engine(beam_width=4)
        elif spec == "by_kind":
            _ENGINES[spec] = _family_engine(
                "window_moe_lm", "smallthinker-tiny.json",
                {"slots": 4, "page_size": 4, "n_pages": 120,
                 "n_pages_window": 40, "max_len": 64,
                 "prompt_buckets": [4, 8], "prefill_batch_buckets": [1, 2],
                 "prefill_chunk": 8}, 3)
        else:
            _ENGINES[spec] = _family_engine(
                "kda_gqa_moe_lm", "solar2-tiny.json",
                {"slots": 3, "page_size": 8, "n_pages": 80, "max_len": 128,
                 "prompt_buckets": [8, 16], "prefill_batch_buckets": [1],
                 "prefill_chunk": 16, "snapshot_stride": 2,
                 "n_snapshots": 8, "mask_plane": 1}, 7)
    return _ENGINES[spec]


def _policy(rows, vocab):
    return {"greedy": None,
            "sampled": SamplingParams(temperature=0.9, top_k=5, top_p=0.8,
                                      seed=11),
            "masked": SamplingParams(
                temperature=1.0, seed=7,
                logits_processor=TokenBanMask(vocab, [2, 3]))}[rows]


def _unpacked(eng, calls):
    """Serve through ``eng`` as before PR 47: every column of a call's
    plane a host feed of its own, and a host mask, straight to the paged
    op of a copy of the program that has no ``unpack_plane``. Appends each
    such call's feed names to ``calls``; -> what puts the engine back."""
    run, refs = eng.executor.run, {}

    def unpacked(prog, feed=None, **kw):
        if not isinstance(feed, CallFeed):
            return run(prog, feed=feed, **kw)
        if id(prog) not in refs:
            ref = prog.clone()
            block = ref.global_block
            block.remove_ops([op for op in block.ops
                              if op.type == "unpack_plane"])
            assert len(block.ops) == len(prog.global_block.ops) - 1
            refs[id(prog)] = ref
        alone = {name: np.ascontiguousarray(col)
                 for name, col in feed.columns.items()}
        if eng.mask_plane:
            alone["serving.mask"] = np.asarray(feed["serving.mask"])
        calls.append(sorted(alone))
        return run(refs[id(prog)], feed=alone, **kw)

    eng.executor.run = unpacked
    return lambda: setattr(eng.executor, "run", run)


@pytest.mark.parametrize("spec,rows", [
    ("plain", "greedy"), ("plain", "sampled"), ("plain", "masked"),
    ("plain", "beam"),
    ("by_kind", "greedy"), ("by_kind", "sampled"), ("by_kind", "masked"),
    ("state", "greedy"), ("state", "sampled"), ("state", "masked"),
])
def test_packed_feed_serves_the_tokens_of_the_separate_feeds(spec, rows):
    """Bit-identical tokens (and beam scores) from the packed plane and
    from a reference that feeds the unpacked planes straight to the op:
    the same integers, the same float bits, the same ones."""
    eng = _spec_engine(spec)

    def serve():
        for cache in eng._caches:
            if cache.index is not None:
                cache.index.clear()
        if rows == "beam":
            return list(eng.generate_beam(PROMPTS[0], beam_size=4,
                                          max_new_tokens=4))
        policy = _policy(rows, eng.spec.vocab_size)
        return eng.generate_all(PROMPTS, max_new_tokens=4,
                                sampling=[policy, None, policy])

    packed = serve()
    calls = []
    restore = _unpacked(eng, calls)
    try:
        alone = serve()
    finally:
        restore()
    assert len(packed) == len(alone)
    for a, b in zip(packed, alone):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the reference did run on the separate feeds, the second table and
    # the row's slot and snapshot rows among them where the spec has them
    fed = set().union(*calls)
    assert {"serving.tok", "serving.chunk", "serving.temp"} <= fed
    assert ("serving.block_table_w" in fed) == (spec == "by_kind")
    assert ({"serving.state_slot", "serving.snap_take"} <= fed) \
        == (spec == "state")
    if spec == "state" and rows != "beam":    # the second prompt's hit
        assert eng.metrics.counter("state_snapshots_restored") >= 2


def _delta(eng, before):
    after = eng.metrics.snapshot()["counters"]
    return {k: v - before.get(k, 0) for k, v in after.items()}


def test_a_call_hands_over_one_host_array_and_a_host_mask_only_when_masked():
    eng = _engine(prefix_sharing=False)     # every round prefills again
    units, run = [], eng.executor.run

    def seen(prog, feed=None, **kw):
        if prog is not eng._decode_prog[0]:
            units.append(prog)
        return run(prog, feed=feed, **kw)

    eng.executor.run = seen
    before = dict(eng.metrics.snapshot()["counters"])
    free = eng.generate_all([LONG, SHORT], max_new_tokens=4)
    got = _delta(eng, before)
    assert got["decode_steps"] > 0 and len(units) == 4
    assert got["decode_feed_host_arrays"] == got["decode_steps"]
    assert got["prefill_feed_host_arrays"] == len(units)
    assert got["mask_host_feeds"] == 0
    masked = SamplingParams(temperature=1.0, seed=7,
                            logits_processor=TokenBanMask(VOCAB, [2, 3]))
    alone = eng.generate_all([LONG], max_new_tokens=4, sampling=[masked])[0]
    del units[:]
    before = dict(eng.metrics.snapshot()["counters"])
    both = eng.generate_all([LONG, SHORT], max_new_tokens=4,
                            sampling=[masked, None])
    got = _delta(eng, before)
    # a call with the masked row built a host mask and handed over two
    # arrays; one without (SHORT's prefill, its ticks while LONG's chunks
    # stream in) fed the device's
    calls = got["decode_steps"] + len(units)
    assert 3 <= got["mask_host_feeds"] < calls
    assert (got["decode_feed_host_arrays"] + got["prefill_feed_host_arrays"]
            == calls + got["mask_host_feeds"])
    # ... and neither row's tokens depend on the other's mask
    np.testing.assert_array_equal(both[0], alone)
    np.testing.assert_array_equal(both[1], free[1])
    assert not np.isin(both[0][LONG.size:], [2, 3]).any()


def test_float_columns_cross_as_their_bits():
    """Temperature and top-p ride an int32 plane and come back the very
    floats: a denormal, 1.0, values bfloat16 cannot hold, the largest
    float below one."""
    values = np.asarray([1e-45, 1.0, 0.7, 0.1, np.nextafter(1, 0),
                         1.1754942e-38, 3.4e38, 0.0], np.float32)
    plane = FeedPlane("t", [("tok", "Tok", 0, "int32", 0),
                            ("temp", "Temperature", 0, "float32", 0.0),
                            ("table", "BlockTable", 3, "int32", 0),
                            ("topp", "TopP", 0, "float32", 1.0)])
    arr, cols = plane.new(values.size)
    assert (cols["topp"] == 1).all() and not arr[:, :5].any()
    cols["temp"][:] = values
    cols["topp"][:] = values[::-1]
    cols["table"][:] = np.arange(values.size * 3).reshape(-1, 3)
    attrs = {"widths": [0, 0, 3, 0],
             "dtypes": ["int32", "float32", "int32", "float32"]}
    unpack = get_op("unpack_plane").fn

    def split(x):
        return unpack(attrs, {"X": [x]})["Out"]

    for outs in (split(jnp.asarray(arr)), jax.jit(split)(jnp.asarray(arr))):
        tok, temp, table, topp = (np.asarray(o) for o in outs)
        assert temp.dtype == topp.dtype == np.float32
        assert temp.view(np.int32).tolist() == values.view(np.int32).tolist()
        assert topp.view(np.int32).tolist() \
            == values[::-1].view(np.int32).tolist()
        assert table.tolist() == cols["table"].tolist() and not tok.any()
    with pytest.raises(ValueError, match="cover 5 of the plane's 6"):
        unpack({"widths": [0, 0, 3], "dtypes": ["int32"] * 3},
               {"X": [jnp.asarray(arr)]})


def test_warm_up_then_fifty_mixed_ticks_compile_nothing():
    eng = _engine(prefill_batch_buckets=(1, 2, 4))
    misses = eng.cache_stats()["misses"]
    fresh = eng.executor.cache_stats()["fresh_compiles"]
    before = dict(eng.metrics.snapshot()["counters"])
    rng = np.random.RandomState(3)
    for seed in range(3):
        prompts = [rng.randint(0, VOCAB, (rng.randint(2, 20),))
                   .astype("int64") for _ in range(4)]
        eng.generate_all(prompts, max_new_tokens=20, sampling=[
            None, _policy("sampled", VOCAB), _policy("masked", VOCAB),
            SamplingParams(temperature=1.0, seed=seed)])
    got = _delta(eng, before)
    assert got["decode_steps"] >= 50 and got["mask_host_feeds"] > 0
    assert eng.cache_stats()["misses"] == misses
    assert eng.executor.cache_stats()["fresh_compiles"] == fresh
