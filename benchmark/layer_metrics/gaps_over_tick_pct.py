"""Serving scheduler: the share of the window's gaps between tokens that
are longer than ``CUT`` x the window's median gap: the gaps of a loop
pass that ran a prefill unit before its decode tick. A gap is a tick or
a tick plus a unit and nothing lies between, so ``tpot_p95_ms`` reads a
tick while this share is under 5, a tick plus a unit while it is over,
and an interpolation across the empty stretch AT 5: the cliff. The
number is the cell's distance from it (re-rate a cell that comes inside
3-7; PERF.md section 4). Source: the benchmark's own clock, the same
list of gaps that gives ``tpot_p95_ms``."""
import statistics

#: times the median gap. The median is a bare tick in every cell (the
#: share is far under 50); a tick plus the shortest unit is 2.0-3.9 x
#: it, a tick at full occupancy under 1.3 x (PERF.md section 4)
CUT = 1.5


def share(gaps_ms) -> float:
    """Percent of ``gaps_ms`` beyond ``CUT`` x their median; ``gaps_ms``
    is not empty."""
    cut = CUT * statistics.median(gaps_ms)
    return 100.0 * sum(1 for g in gaps_ms if g > cut) / len(gaps_ms)


def read(trace, spans, counters, cell):
    gaps = counters.get("gap_ms")
    if not gaps:
        return None
    return share(gaps)
