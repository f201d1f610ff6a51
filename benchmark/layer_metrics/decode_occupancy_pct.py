"""Serving scheduler: mean share of the decode slots that held a
decoding request over the decode steps of the window (the engine's
``decode_tokens`` / (``decode_steps`` x slots), after - before).
Source: program counter."""


def read(trace, spans, counters, cell):
    steps = counters.get("decode_steps", 0)
    if not steps:
        return None
    return 100.0 * counters["decode_tokens"] / (steps * counters["slots"])
