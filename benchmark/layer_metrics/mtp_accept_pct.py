"""Serving scheduler: the share of the drafts a verify tick checked that
it accepted — the engine's ``mtp_accepted`` over ``mtp_drafted`` (live
slots that brought a draft into a tick; after - before over the window).
Acceptance is by exact match with the token the stack draws anyway, greedy
and sampled rows alike. Source: program counter."""


def read(trace, spans, counters, cell):
    """None where the program drafts nothing (every other engine, the
    parent)."""
    drafted = counters.get("mtp_drafted")
    if not drafted or counters.get("mtp_accepted") is None:
        return None
    return 100.0 * counters["mtp_accepted"] / drafted
