"""Training loop: share of the window the dispatch loop spent blocked on
the feed stage (goodput bucket ``data_wait``, after - before, over the
seconds it was read in: the window up to the start of the traced slice).
Source: program counter (host seconds)."""


def read(trace, spans, counters, cell):
    if "data_wait_s" not in counters:
        return None
    return 100.0 * counters["data_wait_s"] / counters["data_wait_window_s"]
