"""Serving scheduler: the share of the window's gaps between tokens that
lie beyond the MODE ``tpot_p95_ms`` sits in: longer than the median gap
plus ``STRETCH`` x (p95 - median), and than ``gaps_over_tick_pct``'s cut
(``CUT``: where p95 is a bare tick the two read the same). A gap is a tick plus 0, 1, 2, ...
prefill units and nothing lies between those modes, so p95 flips to the
next mode when this share passes 5: the cliff ABOVE p95, where
``gaps_over_tick_pct`` is the one between the first two modes
(gpt2m-serve-chat showed a third mode, a tick plus a four-row unit,
3-4 points of its gaps: PERF.md section 4). Under 5 by construction;
re-rate a cell in which it passes 4. Source: the benchmark's own clock,
the same list of gaps that gives ``tpot_p95_ms``."""
import statistics

from benchmark.layer_metrics.gaps_over_tick_pct import CUT
from benchmark.trace_reduce import percentile

#: the next mode lies a whole unit beyond p95's; half a unit is beyond
#: every gap of p95's own mode and short of every gap of the next
STRETCH = 1.5


def share(gaps_ms) -> float:
    """Percent of ``gaps_ms`` (not empty) beyond the mode of their p95."""
    med = statistics.median(gaps_ms)
    cut = max(CUT * med,
              med + STRETCH * (percentile(gaps_ms, 95) - med))
    return 100.0 * sum(1 for g in gaps_ms if g > cut) / len(gaps_ms)


def read(trace, spans, counters, cell):
    gaps = counters.get("gap_ms")
    if not gaps:
        return None
    return share(gaps)
