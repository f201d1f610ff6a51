"""The five readers of PR 40 on hand-made ``Trace``s: a timeline written
on the TRUE clock in ms, its device events then moved ``skew`` EARLY, as
the profiler shows them. The cases are tier-1's too
(``tests/test_serve_idle_split.py`` imports this file). Run by hand:
``pytest benchmark/tests``."""
import types

import pytest

from benchmark.layer_metrics import (decode_feed_mb,
                                     serve_idle_in_module_pct,
                                     tick_idle_drain_ms, tick_idle_fill_ms,
                                     tick_idle_host_ms)
from benchmark.trace_reduce import Trace, idle_gaps, total

CELL = types.SimpleNamespace(mix={})
MS = 1e-3
SPAN = {"tick": "serving/decode_step", "chunk": "serving/prefill_chunk",
        "group": "serving/prefill_group"}


def call(kind, opened, module, fetch, *, close=None, enqueue=None,
         complete=None, bubble=None, build=None):
    """One ``Executor.run``: its launch opens at ``opened`` (closes 0.4
    later, or at ``close``), its module runs ``module`` = (start, end),
    or not at all (None), its fetch closes at ``fetch`` (None: nothing
    fetched). The runtime enqueues it at ``enqueue`` (left out: the
    instant the module starts) and runs its callbacks at ``complete``
    (left out: 0.05 after the module ends). ``bubble``: a stretch of the
    module no op covers."""
    if module is not None:
        enqueue = module[0] if enqueue is None else enqueue
        complete = module[1] + 0.05 if complete is None else complete
    return types.SimpleNamespace(
        kind=kind, opened=opened, module=module, fetch=fetch,
        close=opened + 0.4 if close is None else close, enqueue=enqueue,
        complete=complete, bubble=bubble, build=build)


def build(calls, skew=0.0, window=(0.0, 40.0), strays=(), passes=(),
          parent=False, runtime=True):
    """The ``Trace`` of ``calls``; ``strays`` are modules no call
    launched, ``passes`` extra ``serving/pass`` spans; ``parent``: the
    program has none of PR 40's spans; ``runtime`` False: the trace has
    none of the runtime's own events."""
    host = [("serving/pass", s * MS, e * MS) for s, e in passes]
    modules, ops = [], []
    for c in calls:
        last = c.close if c.fetch is None else c.fetch
        if c.kind in SPAN:
            first = c.build[0] if c.build and c.kind == "tick" else c.opened
            host.append((SPAN[c.kind], (first - 0.1) * MS,
                         (last + 0.05) * MS))
        if c.build and not parent:
            host.append(("serving/build_feed", c.build[0] * MS,
                         c.build[1] * MS))
            host.append(("executor/feed", c.build[1] * MS, c.opened * MS))
        if parent:
            continue
        host.append(("executor/launch", c.opened * MS, c.close * MS))
        if c.fetch is not None:
            host.append(("executor/fetch", (c.close + 0.01) * MS,
                         c.fetch * MS))
        if runtime and c.module is not None:
            host.append(("DoEnqueueProgram", c.enqueue * MS,
                         (c.enqueue + 0.04) * MS))
            host.append(("CompleteCallbacks", c.complete * MS,
                         (c.complete + 0.09) * MS))
    for module, bubble in ([(c.module, c.bubble) for c in calls if c.module]
                           + [(m, None) for m in strays]):
        start, end = module[0] - skew, module[1] - skew
        modules.append(("jit_run_traced(1)", start * MS, end * MS))
        cuts = ([start, end] if bubble is None else
                [start, bubble[0] - skew, bubble[1] - skew, end])
        ops += [("%fusion.1 = f32[8] fusion(...)", a * MS, b * MS)
                for a, b in zip(cuts[::2], cuts[1::2])]
    return Trace({0: ops}, {}, {0: modules}, host,
                 (window[0] * MS, window[1] * MS), None)


# two ticks: fill 1.4 / 1.2, drain 0.5 / 0.1, host 1.6 / 2.3; each module
# starts the instant it is enqueued, so the skew is recovered exactly
TWO_TICKS = [call("tick", 1.6, (3.0, 9.0), 9.5, build=(1.0, 1.5)),
             call("tick", 11.8, (13.0, 20.0), 20.1, build=(11.2, 11.7))]
# the same as the chip shows it: the enqueue 0.05 / 0.02 ms before the
# module starts, the callbacks 0.2 / 0.3 ms after it ends
PINNED = [call("tick", 1.6, (3.0, 9.0), 9.5, enqueue=2.95, complete=9.2),
          call("tick", 11.8, (13.0, 20.0), 20.5, enqueue=12.98,
               complete=20.3)]
# a pass of a chunk and a tick, twice
CHUNK_AND_TICK = [call("chunk", 1.0, (2.0, 8.0), 8.4),
                  call("tick", 9.0, (10.0, 14.0), 14.2),
                  call("chunk", 15.0, (17.0, 23.0), 23.4),
                  call("tick", 24.0, (25.0, 29.0), 29.2)]
# three ticks of fill 1.0, the third held up 30 ms by the host
ONE_STALL = [call("tick", 1.0, (2.0, 8.0), 8.2),
             call("tick", 9.0, (10.0, 16.0), 16.2),
             call("tick", 17.0, (48.0, 54.0), 54.2)]

CASES = [
    # a known skew is recovered from the runtime's events; the spans'
    # own bounds, 1.3 ms apart, are printed beside it and estimate nothing
    ("skew_recovered", dict(calls=TWO_TICKS, skew=1.5), dict(
        shift=1.5, lower=1.5, upper=1.55, span_lower=0.3, span_upper=1.6,
        idle=27.0, fill=1.3, drain=0.3, host=1.95, in_module=0.0,
        unmatched=(0, 0))),
    ("no_skew_reads_the_same", dict(calls=TWO_TICKS, skew=0.0), dict(
        shift=0.0, lower=0.0, upper=0.05, span_lower=-1.2, span_upper=0.1,
        idle=27.0, fill=1.3, drain=0.3, host=1.95, in_module=0.0,
        unmatched=(0, 0))),
    # the estimate is the LOWER bound: where the tightest enqueue comes
    # 0.02 ms before its module, fill reads 0.02 low and drain 0.02 high
    ("skew_to_the_tightest_enqueue", dict(calls=PINNED, skew=1.95), dict(
        shift=1.93, lower=1.93, upper=2.15, idle=27.0,
        fill=(1.38 + 1.18) / 2, drain=(0.52 + 0.52) / 2,
        host=(1.6 + 2.3) / 2, in_module=0.0, unmatched=(0, 0))),
    # which span covers the host seconds of a tick: 0.5 of building, 0.1
    # of the executor's feed, the rest of the pass and outside
    ("host_by_span", dict(calls=TWO_TICKS, skew=1.5,
                          passes=[(0.8, 11.1), (11.15, 21.1)]), dict(
        shift=1.5, idle=27.0, fill=1.3, drain=0.3, host=1.95,
        cover={"serving/build_feed": 1.0, "executor/feed": 0.2,
               "serving/admit": 0.0, "serving/pass": 1.85,
               "outside every pass": 0.85}, unmatched=(0, 0))),
    # a launch that returns long before its module starts: still fill
    ("launch_returns_early", dict(calls=[
        call("tick", 1.0, (6.0, 9.0), 9.2, close=1.1),
        call("tick", 10.0, (11.0, 14.0), 14.2)], skew=2.0), dict(
        shift=2.0, idle=34.0, fill=(5.0 + 1.0) / 2, drain=0.2,
        host=(1.0 + 0.8) / 2, in_module=0.0, unmatched=(0, 0))),
    # ticks and units are kept apart: the chunks' 1.0 / 2.0 ms of fill
    # are no tick's
    ("ticks_and_units_apart", dict(calls=CHUNK_AND_TICK, skew=1.0), dict(
        shift=1.0, idle=20.0, fill=1.0, drain=0.2, host=0.6,
        units={"n": 2, "fill_s": 3.0, "drain_s": 0.8, "host_s": 1.8},
        in_module=0.0, unmatched=(0, 0))),
    # the program's own bubbles are nobody's on the host
    ("bubbles_inside_a_module", dict(calls=[
        call("tick", 1.0, (2.0, 8.0), 8.2, bubble=(4.0, 5.5)),
        call("tick", 9.0, (10.0, 16.0), 16.2, bubble=(11.0, 11.5))],
        skew=0.7), dict(
        shift=0.7, idle=30.0, fill=1.0, drain=0.2, host=0.9,
        in_module=100.0 * 2.0 / 30.0, unmatched=(0, 0))),
    # one host stall moves a class's mean (the metric) and not its median
    ("one_stall_moves_the_mean", dict(calls=ONE_STALL, skew=1.2,
                                      window=(0.0, 60.0)), dict(
        shift=1.2, idle=42.0, fill=11.0, drain=0.2, host=2.6 / 3,
        median={"fill": 1.0, "drain": 0.2, "host": 0.8}, in_module=0.0,
        unmatched=(0, 0))),
    # the profiler runs on past the slice: a tick out there has no idle
    # second counted, so it is no tick of the mean either
    ("tick_after_the_slice", dict(calls=TWO_TICKS + [
        call("tick", 41.8, (43.0, 50.0), 50.1)], skew=1.5), dict(
        shift=1.5, idle=27.0, fill=1.3, drain=0.3, host=1.95, ticks=2,
        unmatched=(0, 0))),
    # ... nor is one the slice cuts
    ("tick_across_the_slices_end", dict(calls=TWO_TICKS + [
        call("tick", 36.0, (37.0, 43.0), 43.2)], skew=1.5), dict(
        shift=1.5, idle=24.0, fill=1.3, drain=0.3, host=1.95, ticks=2,
        unmatched=(0, 0))),
    # a module nobody launched (here: in the host's stretch) is counted,
    # and its seconds still fall in a class
    ("module_without_a_launch", dict(calls=TWO_TICKS, skew=1.5,
                                     strays=[(10.0, 10.5)]), dict(
        shift=1.5, idle=26.5, fill=1.3, drain=0.3, host=1.7,
        unmatched=(0, 1))),
    # a module after the slice is not the slice's to match
    ("module_after_the_slice", dict(calls=TWO_TICKS, skew=1.5,
                                    strays=[(41.0, 45.0)]), dict(
        shift=1.5, idle=27.0, fill=1.3, drain=0.3, unmatched=(0, 0))),
    # a launch whose module the trace lost: counted, its stretch is host
    ("launch_without_a_module", dict(calls=TWO_TICKS + [
        call("tick", 22.0, None, 30.0)], skew=1.5), dict(
        shift=1.5, idle=27.0, fill=1.3, drain=0.3, ticks=2,
        unmatched=(1, 0))),
    # a call that fetched nothing (run_async) sets no bound and drains
    # nothing: its module may end after the next launch opens
    ("async_call_sets_no_bound", dict(calls=[
        call("tick", 1.6, (3.0, 9.0), 9.5),
        call(None, 10.0, (10.5, 12.5), None),
        call("tick", 11.8, (13.0, 20.0), 20.1)], skew=1.5), dict(
        shift=1.5, lower=1.5, upper=1.55, span_lower=0.3, span_upper=1.6,
        idle=25.0, unmatched=(0, 0))),
    # units alone: the split stands, the per-tick readers have no tick
    ("no_tick_in_the_slice", dict(calls=[
        call("chunk", 1.0, (2.0, 8.0), 8.4),
        call("group", 9.0, (10.0, 14.0), 14.2)], skew=1.0), dict(
        no_tick=True, in_module=0.0)),
    # a runtime that names its events otherwise: the spans alone would
    # put the clock off by the size of the metrics, so there is no split
    ("no_runtime_events", dict(calls=TWO_TICKS, skew=1.5, runtime=False),
     None),
    # callbacks that ran before the module ended: no shift fits both
    ("crossed_bounds", dict(calls=[
        call("tick", 1.0, (1.5, 9.5), 6.0, complete=5.9),
        call("tick", 11.0, (12.0, 19.0), 19.5)], skew=1.0), None),
    # the parent: no executor/launch in the trace
    ("no_launch", dict(calls=TWO_TICKS, skew=1.5, parent=True), None),
    # a chip that never idled in the slice
    ("no_idle", dict(calls=[call("tick", 1.0, (1.5, 39.5), 39.8)],
                     window=(2.0, 39.0)), None),
]


def check(kwargs, want, capsys):
    """One case through the four trace readers: ``want`` None = every
    reader reads None and says why on stderr, never raises."""
    trace = build(**kwargs)
    readers = (tick_idle_fill_ms, tick_idle_drain_ms, tick_idle_host_ms,
               serve_idle_in_module_pct)
    got = [r.read(trace, [], {}, CELL) for r in readers]
    said = capsys.readouterr()
    if want is None:
        assert got == [None] * 4
        assert said.err.count("\n") == 4 and "serve_idle_split" not in said.out
        return
    found = tick_idle_fill_ms.split(trace, "test")
    # worked out once a trace: every reader got this very dict
    assert vars(trace)["_idle_split"][0] is found
    # the four classes add to the idle total, which is the chip's own
    parts = sum(found[kind][key] for kind in ("ticks", "units", "other")
                for key in ("fill_s", "drain_s", "host_s", "in_module_s"))
    assert parts == pytest.approx(found["idle_s"], rel=1e-9)
    ops = [(s + found["shift_ms"] * MS, e + found["shift_ms"] * MS)
           for s, e in trace.op_intervals(0)]
    assert found["idle_s"] == pytest.approx(
        total(idle_gaps(ops, trace.window)), rel=1e-9)
    assert '"serve_idle_split"' in said.out
    if want.get("no_tick"):
        assert got[:3] == [None] * 3 and got[3] == pytest.approx(
            want["in_module"])
        assert found["units"]["n"] == 2
        return
    approx = lambda v: pytest.approx(v, abs=1e-9)  # noqa: E731
    for key in ("shift", "shift_lower", "shift_upper", "span_lower",
                "span_upper"):
        short = key.replace("shift_", "")
        if short in want:
            assert found[key + "_ms"] == approx(want[short])
    # a known skew is recovered to within the bounds' width, and the
    # spans' own bounds hold it too
    for lo, hi in (("shift_lower_ms", "shift_upper_ms"),
                   ("span_lower_ms", "span_upper_ms")):
        assert found[lo] - 1e-9 <= kwargs["skew"] <= found[hi] + 1e-9
    assert found["idle_s"] == approx(want["idle"] * MS)
    for reader_at, key in enumerate(("fill", "drain", "host")):
        if key in want:
            assert got[reader_at] == approx(want[key])
    if "in_module" in want:
        assert got[3] == approx(want["in_module"])
    for key, value in want.get("median", {}).items():
        assert found["ticks"]["per_call_ms"][key]["median"] == approx(value)
    assert found["ticks"]["n"] == want.get("ticks", found["ticks"]["n"])
    assert (found["launches_unmatched"],
            found["modules_unmatched"]) == want["unmatched"]
    for key, value in want.get("units", {}).items():
        assert found["units"][key] == approx(
            value if key == "n" else value * MS)
    for name, value in want.get("cover", {}).items():
        assert found["ticks"]["host_by_span"][name] == approx(value * MS)
    assert len(found["longest"]) <= 10
    assert found["longest"][0][2] == max(p[2] for p in found["longest"])
    assert {p[0] for p in found["longest"]} <= {"fill", "drain", "host",
                                                "in_module"}


@pytest.mark.parametrize("kwargs,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_idle_split(kwargs, want, capsys):
    check(kwargs, want, capsys)


FEED_CASES = [
    ({"decode_feed_host_bytes": 6_442_000 * 400, "decode_steps": 400},
     6.442),
    ({"decode_steps": 400}, None),                      # the parent
    ({"decode_feed_host_bytes": 0, "decode_steps": 0}, None),   # no tick
]


@pytest.mark.parametrize("counters,want", FEED_CASES,
                         ids=["counted", "counter_missing", "no_tick"])
def test_decode_feed_mb(counters, want, capsys):
    got = decode_feed_mb.read(None, [], counters, CELL)
    assert got == (want if want is None else pytest.approx(want))
    assert bool(capsys.readouterr().err) == (want is None)
