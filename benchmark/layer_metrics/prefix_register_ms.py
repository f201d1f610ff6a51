"""Serving scheduler: mean time a prefill unit of the traced slice spent
in ``serving/register_prefix``: the prefix index's walk over every full
page the prompt holds so far, which the engine repeats after EVERY unit
(``_register_prefix``: a key chained and looked up a page, so a long
prompt's chunks pay 1 + 2 + .. + n pages: quadratic in the prompt; PERF.md
section 7, PR 48 (1)). Total seconds of the spans over the count of
``serving/after_unit`` spans, both wholly inside the slice; 0 where the
engine's index is off (a spec with state a slot and no snapshots).
Source: program span. None where the slice holds no unit, or the program
has no ``serving/after_unit`` (the parent of PR 56)."""
from benchmark.layer_metrics.after_tick_host_ms import inside


def read(trace, spans, counters, cell):
    units = inside(trace, "serving/after_unit")
    if not units:
        return None
    walks = inside(trace, "serving/register_prefix")
    return 1e3 * sum(e - s for s, e in walks) / len(units)
