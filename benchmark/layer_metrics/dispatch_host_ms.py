"""Executor: mean time of one ``trainer/dispatch`` span — the dispatch
loop's ``Executor.run_async``: normalize the feed, look the executable
up, enqueue it, write the in-flight state back — over the untraced part
of the window (``feed_stack_ms``). Source: program span (host
seconds)."""
from benchmark.layer_metrics.feed_stack_ms import mean_span_ms


def read(trace, spans, counters, cell):
    return mean_span_ms(trace, spans, cell, ("trainer/dispatch",))
