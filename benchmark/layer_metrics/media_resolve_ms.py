"""Entry points: mean time a request with media spent in
``serving/media_resolve`` at admission — the resolver's call (a reference to
frames) and the digest of every frame's pixels (what keys its pages in the
prefix index) — on the engine's thread, so every decoding client waits
behind it once a request. The spans wholly inside the traced slice; where the
slice admitted no such request, the window's mean from the engine's
``media_resolve`` histogram. Source: program span. None where the program
resolves no media."""
from benchmark.layer_metrics.after_tick_host_ms import inside


def read(trace, spans, counters, cell):
    took = inside(trace, "serving/media_resolve") if trace else []
    if took:
        return 1e3 * sum(e - s for s, e in took) / len(took)
    n = counters.get("media_resolve_count")
    return counters["media_resolve_sum_ms"] / n if n else None
