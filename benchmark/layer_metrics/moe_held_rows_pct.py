"""Serving scheduler: the share of the router's assignments that fell to
the experts THIS chip holds: the engine's ``moe_held_assignments`` over
``moe_held_assignments`` + ``moe_absent_assignments`` (after - before over
the window). The chip's share of the experts (25 for 32 of 128) when
routing is even; the rest are rows the absent chips would compute, which
cost this chip a sort slot and nothing else. Every row of the static batch
counts, vacant slots and padding too. Source: program counter."""


def read(trace, spans, counters, cell):
    held = counters.get("moe_held_assignments")
    absent = counters.get("moe_absent_assignments")
    if held is None or absent is None or not held + absent:
        return None
    return 100.0 * held / (held + absent)
