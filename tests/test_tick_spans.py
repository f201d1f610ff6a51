"""PR 40's spans and counters where the serve tick's host work happens:
``serving/build_feed`` in the engine, ``executor/feed`` /
``executor/launch`` / ``executor/fetch`` in ``Executor.run``, and the
host bytes a tick hands it (``decode_feed_host_bytes``). Names, order and
parents are contract: the
benchmark's ``tick_idle_*`` readers split the chip's idle time by them."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models, trace
from paddle_tpu.serving import GenerationEngine, LMSpec

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
    # the mask plane is [slots, vocab] float32 of a tick's feeds; the
    # rest: five sampling planes, token (int64), position, the table
    small = SLOTS * (5 * 4 + 8 + 4) + SLOTS * eng.pmax * 4
    assert ticks[0] == small + mask_plane * SLOTS * VOCAB * 4
