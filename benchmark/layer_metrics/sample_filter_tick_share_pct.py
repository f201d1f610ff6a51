"""Kernels: the share of the window's decode ticks whose sampling plane
had to SEARCH for a top-k / top-p cut-off: ticks in which some live slot
has temperature > 0 and a filter on (0 < top_k < V, or top_p < 1) over all
ticks, the engine's ``sample_filter_ticks`` / ``decode_steps``, after -
before. ``kernels/sampling.py`` runs a counted search only on such a
tick; on every other the thresholds are skipped whole. How far a cell's
tick is the searched one (0 for greedy-only traffic: the engine counts
every tick). Source: program counter."""
import sys


def read(trace, spans, counters, cell):
    """None with the reason on stderr, never an exception, where the
    program counts no ``sample_filter_ticks`` (the parent)."""
    ticks = counters.get("decode_steps")
    if "sample_filter_ticks" not in counters or not ticks:
        print("sample_filter_tick_share_pct: left out (the engine counts no "
              "sample_filter_ticks, or the window held no decode tick)",
              file=sys.stderr)
        return None
    return 100.0 * counters["sample_filter_ticks"] / ticks
