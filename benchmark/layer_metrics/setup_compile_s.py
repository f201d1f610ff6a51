"""Executor: seconds of set-up inside ``executor/compile`` spans, summed
over the run: building the block's function, lowering it and compiling
it or loading it from the persistent cache (the span's ``restored``).
The window has none (``window_fresh_compiles`` 0, no cache miss), so the
sum is set-up's: the startup program (``SGD.train`` runs it first, with
the driver's tracer already on) and the one training step. Source:
program span (host seconds)."""


def read(trace, spans, counters, cell):
    took = [s["end"] - s["start"] for s in spans
            if s["name"] == "executor/compile"]
    return sum(took) if took else None
