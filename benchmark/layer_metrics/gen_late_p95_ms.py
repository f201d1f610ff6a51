"""Load generator: p95 of (time a request was sent - time it was due)
over the requests due in the window. A starved generator would make a
slow server look fast. Source: the benchmark's own clock."""
from benchmark.trace_reduce import percentile


def read(trace, spans, counters, cell):
    return percentile(counters.get("gen_late_ms", ()), 95)
