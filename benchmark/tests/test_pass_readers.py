"""PR 56's seven readers of the serve loop's pass on hand-made inputs:
the engine's pass accounting as the serve driver hands it over (window
differences of counters and of a histogram's ``_sum_ms`` / ``_count``)
and a ``Trace`` whose host plane holds the spans of a pass. Every reader
returns None where what it reads is absent: the parent of the PR has
neither the counters nor the spans. Run by hand: ``pytest
benchmark/tests``."""
import json
import types

import pytest

from benchmark.layer_metrics import (after_tick_host_ms, pass_multi_unit_pct,
                                     pass_one_unit_ms, pass_tick_only_ms,
                                     pass_unit_share_pct,
                                     pass_unnamed_host_ms,
                                     prefix_register_ms)
from benchmark.trace_reduce import Trace

CELL = types.SimpleNamespace(mix={})

# a window of 100 ticks over 900 rows: 80 alone, 15 behind one unit, 5
# behind two or more (3 by a split group, 2 by a group and a chunk)
COUNTERS = {
    "decode_steps": 100, "pass_units": 27, "passes_without_tick": 4,
    "pass_tick_only_count": 80, "pass_tick_only_sum_ms": 480.0,
    "pass_one_unit_count": 15, "pass_one_unit_sum_ms": 600.0,
    "pass_multi_unit_count": 5, "pass_multi_unit_sum_ms": 350.0,
    "pass_rows_tick_only": 760, "pass_rows_one_unit": 110,
    "pass_rows_multi_unit": 30,
    "pass_multi_unit_by_split": 3, "pass_multi_unit_by_group_and_chunk": 2,
}
# what the parent's engine counts of a window: none of it
PARENT = {"decode_steps": 100, "decode_tokens": 900, "tpot_count": 900,
          "tpot_sum_ms": 7000.0}


def trace(host, window=(1.0, 2.0)):
    op = ("%fusion.1 = f32[8] fusion(...)", window[0], window[1])
    return Trace({0: [op]}, {}, {}, list(host), window, None)


@pytest.mark.parametrize("reader,want", [
    (pass_tick_only_ms, 6.0), (pass_one_unit_ms, 40.0),
    (pass_unit_share_pct, 100.0 * 140 / 900),
    (pass_multi_unit_pct, 100.0 * 30 / 900),
])
def test_a_counter_reader_reads_the_windows_passes(reader, want):
    assert reader.read(None, [], COUNTERS, CELL) == pytest.approx(want)


@pytest.mark.parametrize("reader", [pass_tick_only_ms, pass_one_unit_ms,
                                    pass_unit_share_pct,
                                    pass_multi_unit_pct])
def test_a_counter_reader_finds_nothing_on_the_parent(reader, capsys):
    assert reader.read(None, [], PARENT, CELL) is None
    assert reader.read(None, [], {}, CELL) is None
    assert capsys.readouterr().out == ""


def test_a_mode_the_window_never_saw_reads_none_and_counts_zero(capsys):
    # a window of bare ticks: the engine counts, and no pass held a unit
    bare = {"decode_steps": 7, "pass_tick_only_count": 7,
            "pass_tick_only_sum_ms": 35.0, "pass_rows_tick_only": 21}
    assert pass_tick_only_ms.read(None, [], bare, CELL) == pytest.approx(5.0)
    assert pass_one_unit_ms.read(None, [], bare, CELL) is None
    assert pass_unit_share_pct.read(None, [], bare, CELL) == 0.0
    assert pass_multi_unit_pct.read(None, [], bare, CELL) == 0.0
    # ... and an engine that counted before the window and not inside it
    idle = dict.fromkeys(COUNTERS, 0)
    assert pass_tick_only_ms.read(None, [], idle, CELL) is None
    assert pass_unit_share_pct.read(None, [], idle, CELL) is None


def test_pass_multi_unit_pct_prints_the_causes(capsys):
    pass_multi_unit_pct.read(None, [], COUNTERS, CELL)
    line = json.loads(capsys.readouterr().out)["pass_units"]
    assert line == {
        "passes_tick_only": 80, "passes_one_unit": 15,
        "passes_multi_unit": 5, "rows_tick_only": 760,
        "rows_one_unit": 110, "rows_multi_unit": 30, "by_split": 3,
        "by_deferred": 0, "by_group_and_chunk": 2,
        "units_per_pass": pytest.approx(0.27), "passes_without_tick": 4,
        "decode_steps": 100}


def a_pass(at, chunk=False, hole=0.0, walk=0.0):
    """The host spans of one pass that opens at ``at``: the admission, a
    chunk (``walk`` seconds of it in the index) if asked, a tick; every
    stretch named but for ``hole`` seconds before the tick. -> (spans,
    the pass's end)."""
    out, t = [("serving/admit", at, at + 0.001)], at + 0.001
    out.append(("serving/prefill_pick", t, t + 0.0005))
    t += 0.0005
    if chunk:
        out += [("serving/build_feed", t, t + 0.001),
                ("serving/prefill_chunk", t + 0.001, t + 0.031),
                ("executor/launch", t + 0.002, t + 0.003),
                ("serving/after_unit", t + 0.031, t + 0.033 + walk)]
        if walk:
            out.append(("serving/register_prefix", t + 0.032,
                        t + 0.032 + walk))
        t += 0.033 + walk
    t += hole
    out += [("serving/cow_guard", t, t + 0.0002),
            ("serving/decode_step", t + 0.0002, t + 0.0062),
            ("serving/build_feed", t + 0.0003, t + 0.0010),
            ("serving/after_tick", t + 0.0062, t + 0.0082)]
    t += 0.0082
    return [("serving/pass", at, t)] + out, t


def host_of(*passes, start=1.0):
    host, t = [("bench/submit", 1.2, 1.2001)], start
    for kw in passes:
        spans, t = a_pass(t, **kw)
        host += spans
    return host


def test_span_readers_read_the_slices_passes(capsys):
    t = trace(host_of({}, dict(chunk=True, walk=0.004), {},
                      dict(chunk=True)))
    assert after_tick_host_ms.read(t, [], {}, CELL) == pytest.approx(2.0)
    # 4 ms in the index over two units
    assert prefix_register_ms.read(t, [], {}, CELL) == pytest.approx(2.0)
    assert pass_unnamed_host_ms.read(t, [], {}, CELL) == pytest.approx(
        0.0, abs=1e-9)
    assert '"passes": 4' in capsys.readouterr().out


def test_pass_unnamed_host_ms_reads_a_hole(capsys):
    t = trace(host_of({}, dict(hole=0.003), dict(chunk=True, hole=0.001),
                      {}))
    assert pass_unnamed_host_ms.read(t, [], {}, CELL) == pytest.approx(1.0)
    line = json.loads(capsys.readouterr().out)["pass_unnamed_host_ms"]
    assert line["passes"] == 4 and line["max"] == pytest.approx(3.0)
    assert line["p50"] == pytest.approx(0.5)
    # where the named host time went: four ticks' 2 ms, one unit's 2 ms
    by = line["host_by_span"]
    assert by["serving/after_tick"] == {
        "self_mean_ms_a_pass": pytest.approx(2.0),
        "self_max_ms": pytest.approx(2.0)}
    assert by["serving/after_unit"]["self_mean_ms_a_pass"] == pytest.approx(
        0.5)
    assert "serving/register_prefix" not in by      # no walk in this slice


def test_host_by_span_is_self_time(capsys):
    # a unit whose 4 ms walk lies inside a 6 ms after_unit, and an
    # admission that holds a 30 ms group call
    host = host_of(dict(chunk=True, walk=0.004))
    host += [("serving/pass", 1.5, 1.54), ("serving/admit", 1.5, 1.534),
             ("serving/build_feed", 1.501, 1.502),
             ("serving/prefill_group", 1.502, 1.532),
             ("serving/after_unit", 1.532, 1.533),
             ("serving/after_tick", 1.535, 1.54)]
    pass_unnamed_host_ms.read(trace(host), [], {}, CELL)
    by = json.loads(capsys.readouterr().out)[
        "pass_unnamed_host_ms"]["host_by_span"]
    assert by["serving/register_prefix"]["self_max_ms"] == pytest.approx(4.0)
    assert by["serving/after_unit"]["self_max_ms"] == pytest.approx(2.0)
    # 34 ms less the feed, the call and what followed it
    assert by["serving/admit"]["self_max_ms"] == pytest.approx(2.0)


def test_a_pass_across_the_slices_edge_is_left_out():
    host = host_of({}, dict(hole=0.002), start=1.98)    # the second: out
    t = trace(host)
    assert pass_unnamed_host_ms.read(t, [], {}, CELL) == pytest.approx(
        0.0, abs=1e-9)
    assert after_tick_host_ms.read(t, [], {}, CELL) == pytest.approx(2.0)


def test_an_engine_without_an_index_registers_nothing():
    t = trace(host_of(dict(chunk=True), dict(chunk=True)))
    assert prefix_register_ms.read(t, [], {}, CELL) == 0.0
    # a slice with no unit: nothing to divide by
    assert prefix_register_ms.read(trace(host_of({}, {})), [], {},
                                   CELL) is None


@pytest.mark.parametrize("reader", [after_tick_host_ms, prefix_register_ms,
                                    pass_unnamed_host_ms])
def test_a_span_reader_finds_nothing_on_the_parent(reader, capsys):
    new = ("serving/prefill_pick", "serving/after_unit",
           "serving/register_prefix", "serving/cow_guard",
           "serving/after_tick")
    parent = [ev for ev in host_of({}, dict(chunk=True, walk=0.002))
              if ev[0] not in new]
    assert reader.read(trace(parent), [], {}, CELL) is None
    assert reader.read(trace([]), [], {}, CELL) is None
    assert capsys.readouterr().out == ""
