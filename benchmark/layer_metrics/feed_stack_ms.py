"""Training loop: mean time of one ``trainer/feed_stack`` span — the feed
thread turning a batch of rows into one host array per feed
(``DataFeeder.feed``) — over the untraced part of the window: the spans
that start after the last warm-up step resolved and end before the
traced slice starts, because the profiler slows the host severalfold.
Source: program span (host seconds, the program's tracer read back on
``time.monotonic()``). None where the program has no such span.

``feed_put_ms`` and ``dispatch_host_ms`` read other spans over the same
stretch through ``mean_span_ms``."""


def untraced_stretch(trace, spans, cell):
    """(start, end) on ``time.monotonic()``: from the end of the last
    warm-up step's ``trainer/resolve`` (the window's opening, give or
    take the event handler) to the start of the traced slice."""
    if trace.monotonic_offset is None:
        return None
    resolved = sorted(s["end"] for s in spans
                      if s["name"] == "trainer/resolve")
    warmup = cell.mix["warmup_steps"]
    if len(resolved) < warmup:
        return None
    start, end = resolved[warmup - 1], trace.window[0] + trace.monotonic_offset
    return (start, end) if end > start else None


def mean_span_ms(trace, spans, cell, names):
    stretch = untraced_stretch(trace, spans, cell)
    if stretch is None:
        return None
    took = [s["end"] - s["start"] for s in spans if s["name"] in names
            and s["start"] >= stretch[0] and s["end"] <= stretch[1]]
    return 1e3 * sum(took) / len(took) if took else None


def read(trace, spans, counters, cell):
    return mean_span_ms(trace, spans, cell, ("trainer/feed_stack",))
