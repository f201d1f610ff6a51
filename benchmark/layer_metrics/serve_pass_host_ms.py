"""Serving scheduler: mean host time of one loop pass outside its device
calls: each ``serving/pass`` annotation wholly inside the traced slice,
less the ``serving/prefill_group``, ``serving/prefill_chunk`` and
``serving/decode_step`` annotations inside it (each wraps one
``Executor.run`` up to its blocking fetch). What is left is admission,
page and slot bookkeeping, building the feeds and the ``on_token``
callbacks. Source: program span (the program's annotations in the
xplane's host plane, on the profiler's clock). The spread goes to
stdout. None where the trace holds no pass."""
import json

from benchmark.trace_reduce import busy_union, percentile, total

PASS = "serving/pass"
CALLS = ("serving/prefill_group", "serving/prefill_chunk",
         "serving/decode_step")


def passes(trace):
    """``[((start, end), [call intervals inside it])]``, one entry for
    every pass wholly inside the traced slice, calls merged."""
    lo, hi = trace.window
    calls = [(s, e) for n, s, e in trace.host if n in CALLS]
    return [((s, e), busy_union(c for c in calls if c[0] >= s and c[1] <= e))
            for n, s, e in trace.host if n == PASS and s >= lo and e <= hi]


def read(trace, spans, counters, cell):
    host_ms = [1e3 * ((e - s) - total(calls))
               for (s, e), calls in passes(trace)]
    if not host_ms:
        return None
    print(json.dumps({"serve_pass_host_ms": {
        "passes": len(host_ms), "p50": percentile(host_ms, 50),
        "p95": percentile(host_ms, 95), "max": max(host_ms)}}), flush=True)
    return sum(host_ms) / len(host_ms)
