"""Entry points: share of the requests due in the window that completed
with time to first token and mean gap between tokens inside the mix's
``slo``. Source: the benchmark's own clock."""


def read(trace, spans, counters, cell):
    if not counters.get("due"):
        return None
    return 100.0 * counters["slo_met"] / counters["due"]
