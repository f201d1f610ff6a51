"""Serving scheduler: chip 0's idle time a decode tick between the close
of the previous call's ``executor/fetch`` and the open of the tick's
``executor/launch``: the engine's Python between two calls
(``_count_experts``, ``_emit``, the gauges, the pass's end, admission,
``serving/build_feed``, ``executor/feed``). Which span covers how much of
it is in the ``serve_idle_split`` line (``host_by_span``). Source:
program span + device trace; the split and its clock are
``tick_idle_fill_ms``'s. None where that finds nothing to split."""
from benchmark.layer_metrics.tick_idle_fill_ms import per_tick_ms, split


def read(trace, spans, counters, cell):
    return per_tick_ms(split(trace, "tick_idle_host_ms"),
                       "tick_idle_host_ms", "host")
