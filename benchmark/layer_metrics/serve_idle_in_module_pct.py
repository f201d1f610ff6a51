"""Device, serving: of the seconds chip 0 ran no op in the traced slice,
the share INSIDE an ``XLA Modules`` event: the program's own bubbles
between its ops, which no host span can answer for. The rest is the
host's (``tick_idle_fill_ms`` / ``_drain_ms`` / ``_host_ms``). Source:
device trace, on ``tick_idle_fill_ms``'s clock. None where that finds
nothing to split."""
from benchmark.layer_metrics.tick_idle_fill_ms import split


def read(trace, spans, counters, cell):
    found = split(trace, "serve_idle_in_module_pct")
    if found is None:
        return None
    inside = sum(found[kind]["in_module_s"]
                 for kind in ("ticks", "units", "other"))
    return 100.0 * inside / found["idle_s"]
