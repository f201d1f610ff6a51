"""Serving scheduler: mean time of a loop pass under NO named span: each
``serving/pass`` annotation wholly inside the traced slice, less the
union of the spans inside it that name a stretch of it (``NAMED``: the
admission, the feeds, the three call spans with the executor's four
inside them, and PR 56's ``serving/beam_maintenance``,
``serving/prefill_pick``, ``serving/after_unit``, ``serving/cow_guard``,
``serving/after_tick``). What is left is the Python between two ``with``
blocks: it exists to read ~0, and to say so when a later change puts host
work outside every span (before PR 56: 6-9 ms pieces a pass that nothing
named). The spread goes to stdout. Source: program span. None where the
trace holds no pass, or no ``serving/after_tick`` (the parent of PR 56,
whose passes are mostly unnamed by construction)."""
import bisect
import json

from benchmark.layer_metrics.after_tick_host_ms import inside
from benchmark.trace_reduce import busy_union, percentile, total

PASS = "serving/pass"
NAMED = ("serving/admit", "serving/build_feed", "serving/prefill_group",
         "serving/prefill_chunk", "serving/decode_step", "executor/feed",
         "executor/run", "executor/launch", "executor/fetch",
         "serving/beam_maintenance", "serving/prefill_pick",
         "serving/after_unit", "serving/register_prefix",
         "serving/cow_guard", "serving/after_tick")
#: of those, the host's own stretches (read by their SELF time: a group
#: call lies inside ``serving/admit``, ``serving/register_prefix`` inside
#: ``serving/after_unit``; a tick's ``serving/build_feed`` is counted
#: here though it lies inside its call span)
HOST = ("serving/admit", "serving/build_feed", "serving/beam_maintenance",
        "serving/prefill_pick", "serving/after_unit",
        "serving/register_prefix", "serving/cow_guard", "serving/after_tick")


def unnamed_ms(trace):
    """ms under no named span, one entry a pass wholly inside the
    slice."""
    named = sorted((s, e) for n, s, e in trace.host if n in NAMED)
    starts = [s for s, _ in named]
    out = []
    for s, e in inside(trace, PASS):
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        covered = busy_union(c for c in named[i:j] if c[1] <= e)
        out.append(1e3 * ((e - s) - total(covered)))
    return out


def host_by_span(trace, passes):
    """Where a pass's host time goes: for each host span of ``HOST``,
    its SELF time (the span less the named spans inside it: an
    admission's group call, a unit's index walk) as a mean ms a pass of
    the slice, and the longest single one."""
    named = sorted((s, e, n) for n, s, e in trace.host if n in NAMED)
    starts = [s for s, _, _ in named]
    out = {}
    for name in HOST:
        own = []
        for s, e in inside(trace, name):
            i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
            within = busy_union((a, b) for a, b, n in named[i:j]
                                if b <= e and (a, b, n) != (s, e, name))
            own.append(1e3 * ((e - s) - total(within)))
        if own:
            out[name] = {"self_mean_ms_a_pass": sum(own) / passes,
                         "self_max_ms": max(own)}
    return out


def read(trace, spans, counters, cell):
    if not inside(trace, "serving/after_tick"):
        return None
    ms = unnamed_ms(trace)
    if not ms:
        return None
    print(json.dumps({"pass_unnamed_host_ms": {
        "passes": len(ms), "p50": percentile(ms, 50),
        "p95": percentile(ms, 95), "max": max(ms),
        "host_by_span": host_by_span(trace, len(ms))}}), flush=True)
    return sum(ms) / len(ms)
