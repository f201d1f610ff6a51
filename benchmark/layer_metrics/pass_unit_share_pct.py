"""Serving scheduler: the share of the rows the window's ticks decoded
whose pass ALSO ran a prefill unit: the engine's ``pass_rows_one_unit`` +
``pass_rows_multi_unit`` over all three ``pass_rows_*`` counters. One
reading a tick, weighted by the tick's decoding rows: the twin, from
inside the program, of ``gaps_over_tick_pct`` (which cuts the clients'
gaps at 1.5 x their median), and the cliff stands at 5 here as there. A
pass has no gap of ~0, so a drafting block's accepted pairs do not dilute
it. Source: program counter. None on the parent of PR 56."""
from benchmark.layer_metrics.pass_tick_only_ms import rows


def read(trace, spans, counters, cell):
    got = rows(counters)
    if got is None:
        return None
    return 100.0 * (got[1] + got[2]) / sum(got)
