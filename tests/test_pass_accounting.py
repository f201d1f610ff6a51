"""PR 56's pass accounting (``serving.generation.PassAccount``) and the
host spans of a pass, on the toy engines of ``tests/test_observability.py``:

(a) a scripted schedule lands passes of 0, 1 and 2 units in the right
    histogram and row counter, and each ``pass_multi_unit_by_*`` cause is
    bumped by the case that should and by no other;
(b) the three histograms' counts add up to ``decode_steps`` and the row
    counters to the rows the ticks decoded;
(c) one request decoding alone: its passes' durations add up to the time
    between its tokens;
(d) ``_drive`` and ``Server`` count alike;
(e) under a profiler session at tracer level 0 the new spans lie inside a
    ``serving/pass``, ``serving/register_prefix`` inside
    ``serving/after_unit``, and no two leaf spans of a pass overlap;
(f) a spec with a drafting block counts one pass a verify tick.

Names are contract: the benchmark's ``pass_*`` readers take the counters
and the histograms' ``_sum_ms`` / ``_count`` as window differences."""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import trace
from paddle_tpu.serving import Request, Server
from paddle_tpu.serving.batcher import DynamicBatcher
from paddle_tpu.serving.generation import PassAccount
from paddle_tpu.serving.metrics import MetricsRegistry

from test_observability import VOCAB, _Profile, _gen_engine

HISTS, ROWS = PassAccount.HISTS, PassAccount.ROWS
CAUSES = ("pass_multi_unit_by_split", "pass_multi_unit_by_deferred",
          "pass_multi_unit_by_group_and_chunk")
LONG = np.arange(20, dtype=np.int64) % VOCAB        # three chunks of 8
SHORT = (np.arange(6, dtype=np.int64) + 3) % VOCAB  # one grouped prefill
#: the leaf spans of a pass: PR 24's and PR 40's, then PR 56's
LEAVES = ("serving/build_feed", "serving/prefill_group",
          "serving/prefill_chunk", "serving/decode_step",
          "serving/beam_maintenance", "serving/prefill_pick",
          "serving/after_unit", "serving/cow_guard", "serving/after_tick")


@pytest.fixture(autouse=True)
def _tracer_off():
    yield
    trace.disable()
    trace.get_tracer().clear()


def _engine(**kw):
    kw = {**dict(prefill_chunk=8, prompt_buckets=(8, 16),
                 prefill_batch_buckets=(1,)), **kw}
    return _gen_engine(**kw)


def _counts(eng) -> dict:
    """The pass accounting as the serve driver reads it: counters, and a
    histogram's ``_count`` / ``_sum_ms``; 0 for what never counted."""
    snap = eng.metrics.snapshot()
    out = {k: 0 for k in HISTS + ROWS + CAUSES}
    out.update(pass_units=0, passes_without_tick=0)
    out = {**out, **{k: v for k, v in snap["counters"].items() if k in out}}
    for name in HISTS:
        h = snap["hist"].get(name, {"count": 0, "sum_ms": 0.0})
        out[name] = h["count"]
        out[name + "_sum_ms"] = h["sum_ms"]
    return out


def _a_pass(eng, batcher) -> dict:
    """What ONE ``serve_step`` added to the accounting."""
    before = _counts(eng)
    eng.serve_step(batcher, idle_wait_s=0)
    after = _counts(eng)
    return {k: after[k] - before[k] for k in after
            if not k.endswith("_sum_ms") and after[k] != before[k]}


def _run_out(eng, batcher, futs):
    for _ in range(300):
        eng.serve_step(batcher, idle_wait_s=0)
        if all(f.done() for f in futs):
            return
    raise AssertionError("the requests never finished")


def _adds_up(eng):
    """(b): every tick is in one histogram, every decoded row in one row
    counter."""
    got, c = _counts(eng), eng.metrics.snapshot()["counters"]
    assert sum(got[h] for h in HISTS) == c["decode_steps"]
    assert sum(got[r] for r in ROWS) == c.get("decode_live_rows",
                                              c["decode_tokens"])
    return got


# ---------------------------------------------------------------------------
# (a) a scripted schedule, pass by pass
# ---------------------------------------------------------------------------
def _split(eng_of):
    """Two short prompts admitted at once at ``prefill_batch_buckets``
    (1,): one group, two calls, then a tick over both."""
    eng = eng_of()
    batcher = DynamicBatcher(buckets=(1, 2, 4), max_wait_ms=1)
    futs = [batcher.submit({"prompt": (SHORT + i) % VOCAB},
                           max_new_tokens=3) for i in range(2)]
    first = _a_pass(eng, batcher)
    assert first == {"pass_multi_unit": 1, "pass_rows_multi_unit": 2,
                     "pass_units": 2, "pass_multi_unit_by_split": 1}
    return eng, batcher, futs


def _group_and_chunk(eng_of):
    """A short prompt admitted while a long one is mid-chunk: its group
    call, the long one's next chunk and a tick over the short one's row."""
    eng = eng_of()
    batcher = DynamicBatcher(buckets=(1, 2, 4), max_wait_ms=1)
    futs = [batcher.submit({"prompt": LONG}, max_new_tokens=3)]
    # the long prompt's first chunk: a unit and nobody decoding
    assert _a_pass(eng, batcher) == {"passes_without_tick": 1}
    futs.append(batcher.submit({"prompt": SHORT}, max_new_tokens=4))
    assert _a_pass(eng, batcher) == {
        "pass_multi_unit": 1, "pass_rows_multi_unit": 1, "pass_units": 2,
        "pass_multi_unit_by_group_and_chunk": 1}
    # the last chunk brings the long prompt's first token, so the tick
    # behind it holds both rows
    assert _a_pass(eng, batcher) == {
        "pass_one_unit": 1, "pass_rows_one_unit": 2, "pass_units": 1}
    assert _a_pass(eng, batcher) == {"pass_tick_only": 1,
                                     "pass_rows_tick_only": 2}
    return eng, batcher, futs


def _deferred(eng_of):
    """Two requests wait on the pool behind two that fill it; when those
    finish, ONE pass admits both deferred ones, a group call each."""
    eng = eng_of(n_pages=5, prefix_sharing=False, prefill_chunk=16,
                 prefill_batch_buckets=(1, 2))
    batcher = DynamicBatcher(buckets=(1, 2, 4), max_wait_ms=1)
    rng = np.random.RandomState(5)

    def submit():
        return batcher.submit(
            {"prompt": rng.randint(0, VOCAB, (9,)).astype("int64")},
            max_new_tokens=3)

    futs = [submit(), submit()]
    # both in one call at buckets (1, 2): one unit, a tick over two rows
    assert _a_pass(eng, batcher) == {
        "pass_one_unit": 1, "pass_rows_one_unit": 2, "pass_units": 1}
    futs += [submit(), submit()]
    seen = []
    while not (futs[0].done() and futs[1].done()):
        seen.append(_a_pass(eng, batcher))
    assert all(p == {"pass_tick_only": 1, "pass_rows_tick_only": 2}
               for p in seen), seen
    assert len(eng._deferred) == 2      # one blocked, one behind it
    assert _a_pass(eng, batcher) == {
        "pass_multi_unit": 1, "pass_rows_multi_unit": 2, "pass_units": 2,
        "pass_multi_unit_by_deferred": 1}
    return eng, batcher, futs


@pytest.mark.parametrize("case,cause", [
    (_split, "pass_multi_unit_by_split"),
    (_group_and_chunk, "pass_multi_unit_by_group_and_chunk"),
    (_deferred, "pass_multi_unit_by_deferred"),
], ids=["split", "group_and_chunk", "deferred"])
def test_a_pass_lands_in_its_mode_and_bumps_its_cause_alone(case, cause):
    eng, batcher, futs = case(_engine)
    _run_out(eng, batcher, futs)
    got = _adds_up(eng)
    assert {c: got[c] for c in CAUSES} == {c: int(c == cause)
                                           for c in CAUSES}
    assert got["pass_multi_unit"] == 1
    # every later pass was a tick alone (or, for the long prompt, one
    # chunk and a tick)
    assert got["pass_units"] == 2 + got["pass_one_unit"]


# ---------------------------------------------------------------------------
# (b) the counts add up, whatever the traffic
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("buckets", [(1,), (1, 2, 4)])
def test_every_tick_is_in_one_histogram_and_every_row_in_one_counter(
        buckets):
    eng = _engine(prefill_batch_buckets=buckets)
    rng = np.random.RandomState(31)
    prompts = [rng.randint(0, VOCAB, (rng.randint(2, 8),)).astype("int64")
               for _ in range(6)]
    prompts.append(rng.randint(0, VOCAB, (30,)).astype("int64"))
    eng.generate_all(prompts, max_new_tokens=5)
    got = _adds_up(eng)
    # units: every prefill call and every chunk ran in some pass
    c = eng.metrics.snapshot()["counters"]
    calls = eng.metrics.snapshot()["latency"]["prefill_ms"]["count"]
    assert got["pass_units"] + got["passes_without_tick"] \
        == calls + c["prefill_chunks"]
    # a group of four at buckets (1,) is four units; at (1, 2, 4) one
    assert (got["pass_multi_unit_by_split"] > 0) == (buckets == (1,))


def test_calls_outside_a_pass_count_into_nothing():
    eng = _engine()
    req = Request({"prompt": SHORT}, {"max_new_tokens": 3}, None)
    assert eng.admit([req]) == 1
    while eng.active:
        eng.prefill_tick()
        eng.decode_tick()
    assert req.future.result(timeout=1).size == SHORT.size + 3
    got = _counts(eng)
    assert not any(got.values()), got
    assert eng.metrics.counter("decode_steps") == 2


def test_the_account_allocates_nothing_and_says_what_it_is():
    reg = MetricsRegistry()
    acct = PassAccount(reg)
    with acct as same:
        assert same is acct
        acct.group(calls=3)
        acct.chunks += 1
        acct.rows = 2
    c = reg.snapshot()
    assert c["counters"] == {
        "pass_rows_multi_unit": 2, "pass_units": 4,
        "pass_multi_unit_by_split": 1,
        "pass_multi_unit_by_group_and_chunk": 1}
    assert c["hist"]["pass_multi_unit"]["count"] == 1
    with acct:      # reset at open: the last pass leaves nothing behind
        acct.rows = 1
    assert reg.counter("pass_rows_tick_only") == 1
    assert reg.counter("pass_units") == 4


@pytest.mark.parametrize("groups,chunks,deferred,causes", [
    ((2,), 0, False, ("split",)),
    ((1,), 1, False, ("group_and_chunk",)),
    # a deferred admission's group and a chunk, the likely shape under
    # pool pressure: a reader of ``by_deferred`` == 0 rules deferral out
    ((1,), 1, True, ("deferred", "group_and_chunk")),
    ((2,), 0, True, ("split", "deferred")),
    ((1, 1), 0, True, ("deferred",)),
    ((3,), 1, True, ("split", "deferred", "group_and_chunk")),
], ids=["split", "group+chunk", "deferred+chunk", "deferred_split",
        "deferred_groups", "all"])
def test_a_multi_unit_pass_bumps_each_cause_that_applies_once(
        groups, chunks, deferred, causes):
    reg = MetricsRegistry()
    acct = PassAccount(reg)
    with acct:
        acct.deferred = deferred    # as ``_admit_deferred`` sets it
        for calls in groups:
            acct.group(calls)
        acct.chunks += chunks
        acct.rows = 1
    assert {c: reg.counter(c) for c in CAUSES} == {
        c: int(c[len("pass_multi_unit_by_"):] in causes) for c in CAUSES}
    assert reg.counter("pass_units") == sum(groups) + chunks


# ---------------------------------------------------------------------------
# (c) a pass is a gap
# ---------------------------------------------------------------------------
def test_one_requests_passes_add_up_to_the_time_between_its_tokens():
    eng = _engine()
    eng.warmup()
    batcher = DynamicBatcher(buckets=(1, 2, 4), max_wait_ms=1)
    times = []
    fut = batcher.submit({"prompt": SHORT}, max_new_tokens=40,
                         on_token=lambda *_: times.append(
                             time.perf_counter()))
    _run_out(eng, batcher, [fut])
    got = _adds_up(eng)
    # the first pass holds the prefill, the first token and the first
    # tick (the second token); each later one is a tick and a token
    assert len(times) == 40
    assert got["pass_one_unit"] == 1 and got["pass_tick_only"] == 38
    between = (times[-1] - times[1]) * 1e3
    # (what separates them is the loop's time BETWEEN passes, which no
    # pass holds, and the two ends' tails: room for a loaded host)
    assert got["pass_tick_only_sum_ms"] == pytest.approx(between, rel=0.2,
                                                         abs=3.0)


# ---------------------------------------------------------------------------
# (d) _drive counts as a loaded server does
# ---------------------------------------------------------------------------
def test_drive_and_server_count_alike():
    prompts = [SHORT, LONG, (SHORT + 7) % VOCAB]
    driven = _engine()
    for p in prompts:       # one at a time: the schedule is the prompts'
        driven.generate_all([p], max_new_tokens=4)
    served = _engine()
    with Server(served, max_wait_ms=1.0) as srv:
        for p in prompts:
            srv.generate(p, max_new_tokens=4, timeout_s=120)
    a, b = _adds_up(driven), _adds_up(served)
    keep = [k for k in a if not k.endswith("_sum_ms")]
    assert {k: a[k] for k in keep} == {k: b[k] for k in keep}
    # two short prompts: a group call and a tick; the long one: two
    # chunks with nobody decoding, then the last chunk and a tick
    assert a["pass_one_unit"] == 3 and a["passes_without_tick"] == 2
    assert a["pass_multi_unit"] == 0


# ---------------------------------------------------------------------------
# (e) the spans of a pass, on the profiler's clock
# ---------------------------------------------------------------------------
def test_the_new_spans_tile_a_pass_under_the_profiler(tmp_path):
    eng = _engine(slots=5, beam_width=2, prefill_batch_buckets=(1, 2))
    rng = np.random.RandomState(9)
    with Server(eng, max_wait_ms=1.0) as srv:
        srv.generate((LONG + 5) % VOCAB, max_new_tokens=2, timeout_s=120)
        with _Profile(tmp_path) as prof:
            futs = [srv.submit({"prompt": LONG}, max_new_tokens=6),
                    srv.submit({"prompt": SHORT}, max_new_tokens=6),
                    srv.submit({"prompt": rng.randint(0, VOCAB, (9,))},
                               max_new_tokens=4, beam_size=2,
                               return_beams=True)]
            for f in futs:
                f.result(timeout=120)
    assert len(trace.get_tracer()) == 0         # level 0: nothing kept
    passes = [e for e in prof.host() if e[1] == "serving/pass"]
    assert passes
    # (the profile stops as the last future resolves, inside the last
    # pass: that pass's annotation never closes and is not kept, what it
    # had closed by then is)
    events = [e for e in prof.host() if e[2] < max(p[3] for p in passes)]

    def within(e, outer):
        return [o for o in outer
                if o[0] == e[0] and o[2] <= e[2] and e[3] <= o[3]]

    new = ("serving/beam_maintenance", "serving/prefill_pick",
           "serving/after_unit", "serving/register_prefix",
           "serving/cow_guard", "serving/after_tick")
    for name in new:
        found = [e for e in events if e[1] == name]
        assert found, name
        assert all(within(e, passes) for e in found), name
        assert not any(e[4] for e in found), name       # no attrs
    units = [e for e in events if e[1] == "serving/after_unit"]
    walks = [e for e in events if e[1] == "serving/register_prefix"]
    assert all(within(w, units) for w in walks)
    # one after_unit a prefill call, one after_tick a tick, each opening
    # where its call span closes
    calls = {n: sorted((e for e in events if e[1] == n), key=lambda e: e[2])
             for n in ("serving/prefill_group", "serving/prefill_chunk",
                       "serving/decode_step", "serving/after_unit",
                       "serving/after_tick")}
    prefill = sorted(calls["serving/prefill_group"]
                     + calls["serving/prefill_chunk"], key=lambda e: e[2])
    assert len(prefill) == len(calls["serving/after_unit"])
    assert len(calls["serving/decode_step"]) == len(
        calls["serving/after_tick"])
    for call, after in list(zip(prefill, calls["serving/after_unit"])) \
            + list(zip(calls["serving/decode_step"],
                       calls["serving/after_tick"])):
        assert call[3] <= after[2] <= call[3] + 1e-3
    # the leaves of one pass never overlap (a tick builds its feed
    # INSIDE its call span, PR 40: that one is the call's, not a leaf)
    ticks = calls["serving/decode_step"]
    for p in passes:
        leaves = sorted((e for e in events if e[1] in LEAVES
                         and within(e, [p]) and not (
                             e[1] == "serving/build_feed"
                             and within(e, ticks))), key=lambda e: e[2])
        for a, b in zip(leaves, leaves[1:]):
            assert a[3] <= b[2], (a[1], b[1])


# ---------------------------------------------------------------------------
# (f) a verify tick is one pass
# ---------------------------------------------------------------------------
def test_a_verify_tick_is_one_pass_and_its_live_rows():
    import test_mtp_parity as mtp
    from paddle_tpu.ops import common

    before = common._AMP
    pt.set_amp(False)
    try:
        eng = mtp._engine(mtp.build_model())
        eng.generate_all([mtp._prompt(7, seed=1), mtp._prompt(5, seed=2)],
                         max_new_tokens=12)
    finally:
        common._AMP = before
    got = _adds_up(eng)
    c = eng.metrics.snapshot()["counters"]
    # two positions a slot a tick: a tick that accepts its draft emits two
    # tokens and is still ONE pass, its slot ONE row
    assert sum(got[r] for r in ROWS) == c["decode_live_rows"]
    assert c["decode_tokens"] == c["decode_live_rows"] + c["mtp_accepted"]
    assert sum(got[h] for h in HISTS) == c["decode_steps"]
