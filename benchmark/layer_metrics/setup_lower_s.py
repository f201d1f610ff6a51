"""Program to XLA: seconds of set-up spent tracing the block's ops and
lowering them to StableHLO (``jitted.lower``), the ``lower_s`` attribute
of the run's ``executor/compile`` spans, summed; what is left of
``setup_compile_s`` is building the block's function and XLA's compile
or the cache load. Source: program span (host seconds). None where the
spans carry no ``lower_s``."""


def read(trace, spans, counters, cell):
    took = [s["attrs"]["lower_s"] for s in spans
            if s["name"] == "executor/compile" and "lower_s" in s["attrs"]]
    return sum(took) if took else None
