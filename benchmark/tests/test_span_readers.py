"""The eight readers of the program's spans on hand-made inputs: a span
list on ``time.monotonic()`` for the training cells, a ``Trace`` with
host annotations and device events on one axis for the serving cell.
Every reader returns None where its span does not occur: the parent of
the PR that added a span has none. Run by hand: ``pytest
benchmark/tests``."""
import types

import pytest

from benchmark.layer_metrics import (dispatch_host_ms, feed_put_ms,
                                     feed_stack_ms, prefill_chunk_p50_ms,
                                     serve_idle_between_calls_pct,
                                     serve_pass_host_ms, setup_compile_s,
                                     setup_lower_s)
from benchmark.trace_reduce import Trace

CELL = types.SimpleNamespace(mix={"warmup_steps": 2})


def span(name, start, end, **attrs):
    return {"name": name, "start": start, "end": end, "attrs": attrs}


def trace(host=(), ops=(), window=(0.0, 10.0), offset=100.0):
    return Trace({0: list(ops)}, {}, {}, list(host), window, offset)


# the slice starts at profiler second 6 = monotonic 106; the window opens
# when the second warm-up step resolves, at monotonic 102
TRAIN = [
    span("executor/compile", 100.0, 100.5, restored=True, lower_s=0.2,
         compile_s=0.25),
    span("executor/compile", 100.6, 101.6, restored=False, lower_s=0.3,
         compile_s=0.6),
    span("trainer/resolve", 101.0, 101.5), span("trainer/resolve", 101.6, 102.0),
    span("trainer/feed_stack", 101.0, 101.9),       # warm-up: left out
    span("trainer/feed_stack", 102.0, 102.1),
    span("trainer/feed_stack", 103.0, 103.3),
    span("trainer/feed_stack", 105.9, 106.2),       # ends in the slice
    span("trainer/feed_stack", 107.0, 109.0),       # in the slice
    span("trainer/feed_put", 102.1, 102.11),
    span("trainer/feed_put", 103.3, 103.33),
    span("trainer/dispatch", 102.2, 102.204),
    span("trainer/dispatch", 103.4, 103.402),
    span("trainer/dispatch", 108.0, 108.5),
    span("trainer/resolve", 102.3, 103.0), span("trainer/resolve", 103.5, 104.0),
]


def test_train_readers_read_the_untraced_stretch():
    t = trace(window=(6.0, 9.0))
    assert feed_stack_ms.read(t, TRAIN, {}, CELL) == pytest.approx(200.0)
    assert feed_put_ms.read(t, TRAIN, {}, CELL) == pytest.approx(20.0)
    assert dispatch_host_ms.read(t, TRAIN, {}, CELL) == pytest.approx(3.0)


def test_feed_put_reads_the_mesh_route_too():
    spans = [s for s in TRAIN if s["name"] != "trainer/feed_put"]
    spans.append(span("executor/shard_feed", 102.2, 102.203))
    assert feed_put_ms.read(trace(window=(6.0, 9.0)), spans, {},
                            CELL) == pytest.approx(3.0)


@pytest.mark.parametrize("reader", [feed_stack_ms, feed_put_ms,
                                    dispatch_host_ms])
def test_train_readers_find_nothing(reader):
    t = trace(window=(6.0, 9.0))
    named = ("trainer/feed_stack", "trainer/feed_put", "trainer/dispatch")
    without = [s for s in TRAIN if s["name"] not in named]
    assert reader.read(t, without, {}, CELL) is None     # the parent
    assert reader.read(t, [], {}, CELL) is None          # no warm-up seen
    no_sync = trace(window=(6.0, 9.0), offset=None)
    assert reader.read(no_sync, TRAIN, {}, CELL) is None
    early = trace(window=(1.0, 2.0))        # slice before the window opens
    assert reader.read(early, TRAIN, {}, CELL) is None


def test_setup_readers():
    assert setup_compile_s.read(None, TRAIN, {}, CELL) == pytest.approx(1.5)
    assert setup_lower_s.read(None, TRAIN, {}, CELL) == pytest.approx(0.5)
    bare = [span("executor/compile", 0.0, 2.0, cache="miss")]   # the parent
    assert setup_compile_s.read(None, bare, {}, CELL) == pytest.approx(2.0)
    assert setup_lower_s.read(None, bare, {}, CELL) is None
    assert setup_compile_s.read(None, [], {}, CELL) is None


# one pass with a chunk and a tick, one with a tick alone, one with a
# grouped prefill inside its admission, one with no device call, and one
# that straddles the slice's end (left out)
HOST = [
    ("serving/pass", 1.000, 1.300), ("serving/admit", 1.000, 1.002),
    ("serving/prefill_chunk", 1.004, 1.120),
    ("serving/decode_step", 1.122, 1.298),
    ("serving/pass", 1.300, 1.470), ("serving/admit", 1.300, 1.301),
    ("serving/decode_step", 1.303, 1.469),
    ("serving/pass", 1.470, 1.700), ("serving/admit", 1.470, 1.560),
    ("serving/prefill_group", 1.472, 1.558),
    ("serving/decode_step", 1.562, 1.698),
    ("serving/pass", 1.700, 1.701),
    ("serving/pass", 1.900, 2.100), ("serving/decode_step", 1.901, 2.099),
    ("bench/submit", 1.2, 1.2001),
]
# the chip runs all through each call but for its first millisecond, and
# idles between calls
OPS = [("%fusion.1 = f32[8] fusion(...)", s + 0.001, e)
       for n, s, e in HOST if n in serve_pass_host_ms.CALLS]


def test_serve_pass_host_ms_is_the_pass_less_its_calls(capsys):
    t = trace(HOST, OPS, window=(1.0, 2.0))
    # 8, 4, 8 and 1 ms outside the calls; the straddling pass left out
    assert serve_pass_host_ms.read(t, [], {}, CELL) == pytest.approx(5.25)
    assert '"passes": 4' in capsys.readouterr().out
    assert prefill_chunk_p50_ms.read(t, [], {}, CELL) == pytest.approx(116.0)


def test_serve_idle_between_calls_pct(capsys):
    t = trace(HOST, OPS, window=(1.0, 2.0))
    # idle: 1 ms inside each of 6 calls in the window (5 in whole passes,
    # one in the straddling pass: outside every whole pass), 21 ms between
    # calls inside whole passes, 200 ms from 1.7 to 1.9 of which 1 ms is
    # the call-less pass's
    idle = 0.006 + 0.021 + 0.200
    got = serve_idle_between_calls_pct.read(t, [], {}, CELL)
    assert got == pytest.approx(100.0 * 0.021 / idle)
    assert '"in_calls_pct"' in capsys.readouterr().out


@pytest.mark.parametrize("reader", [serve_pass_host_ms,
                                    prefill_chunk_p50_ms,
                                    serve_idle_between_calls_pct])
def test_serve_readers_find_nothing(reader):
    bench_only = [e for e in HOST if e[0].startswith("bench/")]
    t = trace(bench_only, OPS, window=(1.0, 2.0))            # the parent
    assert reader.read(t, [], {}, CELL) is None


def test_a_chip_that_never_idled_has_no_share():
    busy = [("%fusion.1 = f32[8] fusion(...)", 0.5, 2.5)]
    t = trace(HOST, busy, window=(1.0, 2.0))
    assert serve_idle_between_calls_pct.read(t, [], {}, CELL) is None
