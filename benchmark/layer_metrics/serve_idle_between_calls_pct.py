"""Device, serving: of the seconds chip 0 ran no op in the traced slice,
the share that falls inside a ``serving/pass`` but outside its device
calls (``serve_pass_host_ms``): the chip waiting for the scheduler's
Python between two ``Executor.run``s. The rest lies inside a call
(launch, feed transfer, the fetch coming back) or outside every pass.
Host annotations and device events are read on one axis, the
profiler's: the number means something only as far as the two agree
(``trace_reduce``: device events sit about 1.5 ms early). Source:
program span + device trace. Where the rest lies goes to stdout. None
where the trace holds no pass or the chip never idled."""
import json

from benchmark.layer_metrics.serve_pass_host_ms import passes
from benchmark.trace_reduce import clip, idle_gaps, total


def read(trace, spans, counters, cell):
    found = passes(trace)
    gaps = idle_gaps(trace.op_intervals(0), trace.window)
    idle = total(gaps)
    if not found or not idle:
        return None
    in_pass = in_call = 0.0
    for span, calls in found:
        inside = clip(gaps, span)
        in_pass += total(inside)
        in_call += sum(total(clip(inside, call)) for call in calls)
    print(json.dumps({"serve_idle": {
        "idle_s": idle, "in_calls_pct": 100.0 * in_call / idle,
        "outside_passes_pct": 100.0 * (idle - in_pass) / idle}}),
        flush=True)
    return 100.0 * (in_pass - in_call) / idle
