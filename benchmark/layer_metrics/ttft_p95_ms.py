"""Entry points: p95 over the requests due in the window of (first
``on_token`` time - the time the request was due). Not an end-to-end
metric: at the rates this system sustains a window holds some fifty
requests, so fewer than three lie beyond the 95th percentile and six runs
of one code spread by 2.4-3.8% (my chip runs, PR 22). Source: the
benchmark's own clock."""
from benchmark.trace_reduce import percentile


def read(trace, spans, counters, cell):
    return percentile(counters.get("ttft_ms", ()), 95)
