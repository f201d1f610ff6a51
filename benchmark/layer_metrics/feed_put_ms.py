"""Training loop: mean time of the host call that hands one step's feed
to the device — ``trainer/feed_put`` (the feed thread's ``device_put``s,
one chip) or ``executor/shard_feed`` (the executor sharding the feed over
a mesh, on the dispatch thread) — over the untraced part of the window
(``feed_stack_ms``). The call returns before the transfer ends: the
transfer itself is the device trace's. Source: program span (host
seconds). None where the program has neither span."""
from benchmark.layer_metrics.feed_stack_ms import mean_span_ms


def read(trace, spans, counters, cell):
    return mean_span_ms(trace, spans, cell,
                        ("trainer/feed_put", "executor/shard_feed"))
