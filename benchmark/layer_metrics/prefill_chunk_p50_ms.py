"""Program to XLA, serving: median time of one ``serving/prefill_chunk``
annotation in the traced slice: one chunk of one prompt through
``Executor.run`` up to its blocking fetch, which a decode tick in the
same pass waits behind. Source: program span (the xplane's host plane).
None where the slice holds no chunk."""
from benchmark.trace_reduce import percentile


def read(trace, spans, counters, cell):
    lo, hi = trace.window
    took = [1e3 * (e - s) for n, s, e in trace.host
            if n == "serving/prefill_chunk" and s >= lo and e <= hi]
    return percentile(took, 50)
