"""Executor, serving: chip 0's idle time a decode tick between the open
of the tick's ``executor/launch`` and the start of the module it
launched: the chip waiting for the tick's feeds to cross and for the
enqueue. Source: program span + device trace. This file also holds the
split its neighbours read (``tick_idle_drain_ms``, ``tick_idle_host_ms``,
``serve_idle_in_module_pct``) and prints it, one line of stdout.

**The split.** Every second chip 0 ran no op in the traced slice falls in
one of four classes. *in-module*: inside an ``XLA Modules`` event (the
program's own bubbles; no host span can answer for them). Outside every
module, by the call it belongs to (``Executor.run`` calls are sequential
on the engine's thread, each one ``executor/launch`` and, where it
fetches, one ``executor/fetch``): *fill* from the launch's open to its
module's start, *drain* from the module's end to the fetch's close,
*host* from the previous call's fetch close to this launch's open
(and whatever no matched call covers). The four add to the idle total.
Launches and modules of the slice that found no partner are counted
(``launches_unmatched``, ``modules_unmatched``), never dropped in silence.
A call is a *tick* where its launch lies in a ``serving/decode_step``,
a *unit* in a ``serving/prefill_chunk`` or ``serving/prefill_group``.

**One clock.** Device events sit EARLY on the profiler's axis, by an
amount that differs from process to process (PR 40's traces: 0.62 to
1.95 ms; ``trace_reduce``'s header says 1.5), so each device time gets a
shift d added that must keep every module inside its call. The program's
spans alone bound it loosely (``launch_open - module_start <= d <=
fetch_close - module_end``: 2.0-2.9 ms apart, the lower holds the feed
copy, the upper the fetch's way back, 0.7-1.2 ms and not the 0.1 ms one
would hope): as wide as the metrics are large, so they are printed as a
cross-check (``span_lower_ms``, ``span_upper_ms``) and estimate nothing.
The estimate comes from the runtime's own host events, which
``host_tracer_level`` 2 puts in the trace: a module cannot start before
the first ``DoEnqueueProgram`` of its call opens, nor end after the
call's last ``CompleteCallbacks`` opens. Over a slice's 90-400 calls
those bounds stand 0.12-0.42 ms apart, and ``enqueue - module_start``
stays inside 0.2 ms of its largest value in every call. d = the LOWER
bound; its error is one-sided, at most upper - lower, by which *fill*
reads low and *drain* high. A trace without those events (a runtime that
names them otherwise) has no split: every reader says so and reads None
rather than a number known to be off by its own size.
Modules and calls are matched by overlap, first unshifted, then again
under the estimate until it stands.

**A tick.** The four classes' seconds are the whole slice's. The
per-tick numbers (the three ``tick_idle_*_ms`` metrics: the mean; the
median beside it in the line, which one host stall of 100 ms does not
move) are over the matched ticks that lie IN the slice, launch open to
fetch close: the profiler runs on past the slice, and a tick out there
has no idle second counted.

Every reader returns None, with the reason on stderr, where the trace
holds no ``executor/launch`` (the parent of PR 40), no module, no idle
second, no tick, no runtime event to set the clock by, or where the
bounds cross. The split is worked out once a trace and kept on it.
"""
import bisect
import heapq
import json
import statistics
import sys

from benchmark.trace_reduce import (attribute_gaps, busy_union, idle_gaps,
                                    total)

LAUNCH, FETCH = "executor/launch", "executor/fetch"
KINDS = {"serving/decode_step": "ticks", "serving/prefill_chunk": "units",
         "serving/prefill_group": "units"}
#: the runtime's own host events that bound a module on the host's clock
ENQUEUE, COMPLETE = "DoEnqueueProgram", "CompleteCallbacks"
#: what covers a *host* second, innermost first; the rest lies outside
#: every pass
HOST_COVER = ("serving/build_feed", "executor/feed", "serving/admit",
              "serving/pass")
#: the classes outside every module, as ``stretches`` returns them
CLASSES = ("fill", "drain", "host")
INF = float("inf")


def both(xs, ys):
    """The parts of the sorted, disjoint ``xs`` inside the sorted,
    disjoint ``ys``, as ``(start, end, index into ys)``."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if hi > lo:
            out.append((lo, hi, j))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def _first_inside(starts, lo, hi, last=False):
    """The first (``last``: the last) of the sorted ``starts`` in
    ``[lo, hi)``, or None."""
    i, j = bisect.bisect_left(starts, lo), bisect.bisect_left(starts, hi)
    if i >= j:
        return None
    return starts[j - 1] if last else starts[i]


def calls_of(host):
    """The slice's ``Executor.run`` calls in time order, each a dict:
    ``open`` / ``close`` of its launch, ``fetch_close`` (None where it
    fetched nothing to the host), ``end`` (the fetch's close, else the
    next launch's open), ``kind`` and the runtime's ``enqueue`` /
    ``complete`` instants inside it (None where the trace has none)."""
    named = {}
    for name, start, end in host:
        if name in (LAUNCH, FETCH, ENQUEUE, COMPLETE) or name in KINDS:
            named.setdefault(name, []).append((start, end))
    for evs in named.values():
        evs.sort()
    launches = named.get(LAUNCH, [])
    fetches = named.get(FETCH, [])
    fetch_opens = [s for s, _ in fetches]
    enqueues = [s for s, _ in named.get(ENQUEUE, [])]
    completes = [s for s, _ in named.get(COMPLETE, [])]
    kinds = sorted((s, e, kind) for name, kind in KINDS.items()
                   for s, e in named.get(name, []))
    kind_opens = [s for s, _, _ in kinds]
    out = []
    for i, (start, close) in enumerate(launches):
        nxt = launches[i + 1][0] if i + 1 < len(launches) else INF
        j = bisect.bisect_left(fetch_opens, close)
        fetch_close = (fetches[j][1] if j < len(fetches)
                       and fetches[j][0] < nxt else None)
        end = nxt if fetch_close is None else fetch_close
        k = bisect.bisect_right(kind_opens, start) - 1
        kind = (kinds[k][2] if k >= 0 and kinds[k][1] >= close
                else "other")
        out.append({"open": start, "close": close, "end": end,
                    "fetch_close": fetch_close, "kind": kind,
                    "enqueue": _first_inside(enqueues, start, end),
                    "complete": _first_inside(completes, start, end,
                                              last=True)})
    return out


def match(calls, modules, shift):
    """``{call index: module index}`` with the modules moved by
    ``shift``: a module goes to the call it overlaps most, a call keeps
    the module that overlaps it most."""
    opens = [c["open"] for c in calls]
    kept = {}
    for j, (start, end) in enumerate(modules):
        start, end = start + shift, end + shift
        best, i = (0.0, None), max(bisect.bisect_right(opens, start) - 1, 0)
        while i < len(calls) and calls[i]["open"] < end:
            over = min(end, calls[i]["end"]) - max(start, calls[i]["open"])
            if over > best[0]:
                best = (over, i)
            i += 1
        over, i = best
        if i is not None and over > kept.get(i, (0.0, None))[0]:
            kept[i] = (over, j)
    return {i: j for i, (_, j) in kept.items()}


def shift_bounds(calls, modules, pairs):
    """``(lower, upper, span_lower, span_upper)`` of the seconds to ADD to
    device times, over the matched calls that waited for their fetch: the
    first pair from the runtime's enqueue / complete events inside a call
    (-inf / inf where no call has one), the second from the program's
    spans alone. None where no such call is matched."""
    lower = span_lower = -INF
    upper = span_upper = INF
    for i, j in pairs.items():
        call, (start, end) = calls[i], modules[j]
        if call["fetch_close"] is None:
            continue
        span_lower = max(span_lower, call["open"] - start)
        span_upper = min(span_upper, call["fetch_close"] - end)
        if call["enqueue"] is not None:
            lower = max(lower, call["enqueue"] - start)
        if call["complete"] is not None:
            upper = min(upper, call["complete"] - end)
    return (None if span_upper == INF
            else (lower, upper, span_lower, span_upper))


def estimate_shift(calls, modules):
    """``(bounds, pairs)`` once the matching stands under the bounds'
    own estimate, their lower one; ``bounds`` None where nothing
    matches."""
    shift, pairs, bounds = 0.0, None, None
    for _ in range(8):
        again = match(calls, modules, shift)
        if again == pairs:
            break
        pairs = again
        bounds = shift_bounds(calls, modules, pairs)
        if bounds is None or bounds[0] == -INF:
            break
        shift = bounds[0]
    return bounds, pairs


def _clamp(x, lo, hi):
    return min(max(x, lo), hi)


def stretches(calls, modules, pairs):
    """``(fill, drain, host)``: for every call in order, the stretch
    from its launch's open to its module's start, from the module's end
    to its fetch's close, and from the call before (its fetch's close) to
    this launch's open; ``host`` has one more, after the last call. Each
    list is sorted and disjoint, and the three with the modules tile the
    axis: every bound is clamped between the launch's open and the next
    one's. A call with no module has no fill and no drain."""
    fill, drain, host, at = [], [], [], -INF
    for i, call in enumerate(calls):
        nxt = calls[i + 1]["open"] if i + 1 < len(calls) else INF
        start = end = waited = call["open"]
        if i in pairs:
            start = _clamp(modules[pairs[i]][0], call["open"], nxt)
            end = _clamp(modules[pairs[i]][1], start, nxt)
            waited = _clamp(call["fetch_close"] or end, end, nxt)
        host.append((at, call["open"]))
        fill.append((call["open"], start))
        drain.append((end, waited))
        at = waited
    host.append((at, INF))      # after the last call: nobody's
    return fill, drain, host


def _split(trace):
    """``(the split, None)`` or ``(None, why there is none)``."""
    calls = calls_of(trace.host)
    modules = sorted((s, e) for _, s, e in trace.device_modules.get(0, ()))
    if not calls or not modules:
        return None, (f"the trace holds {len(calls)} {LAUNCH} spans and "
                      f"{len(modules)} modules on chip 0: nothing to split")
    bounds, pairs = estimate_shift(calls, modules)
    if bounds is None:
        return None, "no module lies in a call that waited for its fetch"
    lower, upper, span_lower, span_upper = bounds
    if lower == -INF or upper == INF:
        return None, (f"no {ENQUEUE} or no {COMPLETE} event of the runtime "
                      f"lies in a matched call: nothing sets the clock "
                      f"closer than the spans' own bounds, "
                      f"{span_lower * 1e3:.3f} to {span_upper * 1e3:.3f} ms")
    if lower > upper:
        return None, (f"the shift's bounds cross: device times would need "
                      f"{lower * 1e3:.3f} ms added to start after their "
                      f"enqueue and {upper * 1e3:.3f} to end before their "
                      f"callbacks")
    shift = lower
    ops = [(s + shift, e + shift) for s, e in trace.op_intervals(0)]
    modules = [(s + shift, e + shift) for s, e in modules]
    idle = idle_gaps(ops, trace.window)
    outside = idle_gaps(ops + modules, trace.window)
    if not total(idle):
        return None, "chip 0 never idled in the slice"

    of_calls = dict(zip(CLASSES, stretches(calls, modules, pairs)))
    lo, hi = trace.window       # the profiler runs on past the slice
    in_slice = [i in pairs and c["open"] >= lo
                and of_calls["drain"][i][1] <= hi
                for i, c in enumerate(calls)]
    kind_of = [c["kind"] for c in calls] + ["other"]
    by_kind = {k: {"n": 0, "fill_s": 0.0, "drain_s": 0.0, "host_s": 0.0,
                   "in_module_s": 0.0, "per_call_ms": {},
                   "host_by_span": {}}
               for k in ("ticks", "units", "other")}
    pieces = []                 # (seconds, class, start, end)
    host_pieces = {k: [] for k in by_kind}
    per_call = {name: [0.0] * (len(calls) + 1) for name in CLASSES}
    for name in CLASSES:
        for s, e, i in both(outside, of_calls[name]):
            by_kind[kind_of[i]][name + "_s"] += e - s
            per_call[name][i] += e - s
            pieces.append((e - s, name, s, e))
            if name == "host":
                host_pieces[kind_of[i]].append((s, e))
    module_call = {j: i for i, j in pairs.items()}
    for s, e, j in both(idle, modules):
        kind = kind_of[module_call.get(j, len(calls))]
        by_kind[kind]["in_module_s"] += e - s
        pieces.append((e - s, "in_module", s, e))
    for kind, found in by_kind.items():
        mine = [i for i, inside in enumerate(in_slice)
                if inside and kind_of[i] == kind]
        found["n"] = len(mine)
        for name in CLASSES:
            ms = [1e3 * per_call[name][i] for i in mine]
            found["per_call_ms"][name] = {
                "mean": statistics.fmean(ms) if ms else None,
                "median": statistics.median(ms) if ms else None}

    spans = [ev for ev in trace.host
             if ev[0].startswith(("serving/", "executor/"))]
    covers = [(name, busy_union((s, e) for n, s, e in spans if n == name))
              for name in HOST_COVER]
    for kind, left in host_pieces.items():
        cover = by_kind[kind]["host_by_span"]
        for name, these in covers:
            cover[name] = sum(e - s for s, e, _ in both(left, these))
            left = [(s, e) for s, e, _ in
                    both(left, idle_gaps(these, (-INF, INF)))]
        cover["outside every pass"] = total(left)
    longest = [[name, attribute_gaps([(s, e)], spans, top=1)[0][0],
                1e3 * sec]
               for sec, name, s, e in heapq.nlargest(10, pieces)]
    return {"shift_ms": 1e3 * shift, "shift_lower_ms": 1e3 * lower,
            "shift_upper_ms": 1e3 * upper,
            "span_lower_ms": 1e3 * span_lower,
            "span_upper_ms": 1e3 * span_upper,
            "calls_matched": len(pairs),
            "launches_unmatched": sum(
                1 for i, c in enumerate(calls)
                if i not in pairs and c["open"] < hi and c["end"] > lo),
            "modules_unmatched": sum(
                1 for j, (s, e) in enumerate(modules)
                if j not in module_call and s < hi and e > lo),
            "idle_s": total(idle), **by_kind, "longest": longest}, None


def split(trace, who):
    """The split as a dict (see the module's docstring; seconds, and ms
    where a key says so), or None with the reason on stderr under the
    reader's name ``who``. Worked out once a trace: the four readers of
    a run share it through the ``Trace`` they are all handed."""
    kept = vars(trace)
    if "_idle_split" not in kept:
        kept["_idle_split"] = _split(trace)
    found, why = kept["_idle_split"]
    if found is None:
        print(f"{who}: {why}", file=sys.stderr, flush=True)
    return found


def per_tick_ms(found, who, name):
    """The mean of class ``name`` over the slice's matched ticks, ms;
    None where the split found nothing or the slice holds no tick."""
    if found is None:
        return None
    if not found["ticks"]["n"]:
        print(f"{who}: no decode tick of the slice has a module",
              file=sys.stderr, flush=True)
        return None
    return found["ticks"]["per_call_ms"][name]["mean"]


def read(trace, spans, counters, cell):
    found = split(trace, "tick_idle_fill_ms")
    if found is not None:
        print(json.dumps({"serve_idle_split": found}), flush=True)
    return per_tick_ms(found, "tick_idle_fill_ms", "fill")
