"""Serving scheduler: mean duration of the loop passes that ran a decode
tick and exactly ONE prefill unit (a group call or a chunk): the engine's
histogram ``pass_one_unit``, ``_sum_ms`` over ``_count`` over the window.
That is the body of the mode ``tpot_p95_ms`` sits in wherever
``pass_unit_share_pct`` is over 5: it moves with the unit and the tick,
not with the SHARE of gaps that hold a unit, which is what makes p95
wander from process to process on one schedule (PERF.md section 7).
Source: program counter. None on the parent of PR 56 and in a window
with no such pass."""
from benchmark.layer_metrics.pass_tick_only_ms import mean_ms


def read(trace, spans, counters, cell):
    return mean_ms(counters, "one_unit")
