"""Serving scheduler: mean duration of the loop passes that ran a decode
tick and NO prefill unit, as the engine timed them where the pass runs
(``serving.generation.PassAccount``: the histogram ``pass_tick_only``,
``_sum_ms`` over ``_count``, after - before over the window). A gap
between two tokens is one pass, so this is the body of the first mode of
the gaps: a tick and the pass's host work, no unit. Source: program
counter. This file also holds what its neighbours share (``mean_ms``,
``rows``)."""

#: the engine's histogram and row counter of each mode: 0, 1, 2+ units
MODES = ("tick_only", "one_unit", "multi_unit")


def mean_ms(counters, mode):
    """Mean ms of the window's passes of ``mode``; None where the engine
    counts no passes (the parent of PR 56) or the window held none."""
    n = counters.get(f"pass_{mode}_count")
    if not n:
        return None
    return counters[f"pass_{mode}_sum_ms"] / n


def rows(counters):
    """The rows the window's ticks decoded, by the mode of their pass:
    ``(tick_only, one_unit, multi_unit)``; None where the engine counts
    none of them or no tick ran."""
    got = [counters.get(f"pass_rows_{mode}") for mode in MODES]
    if all(n is None for n in got) or not sum(n or 0 for n in got):
        return None
    return tuple(n or 0 for n in got)


def read(trace, spans, counters, cell):
    return mean_ms(counters, "tick_only")
