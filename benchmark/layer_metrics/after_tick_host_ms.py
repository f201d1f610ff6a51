"""Serving scheduler: mean time of one ``serving/after_tick`` annotation
in the traced slice: a decode tick from its call span's close to its
return: the emit loop over the tick's rows with the clients' ``on_token``
callbacks, ``_finish`` of the requests that ended (pages released, the
prompt's tail registered), beam rows and the gauges. Every decoding
client waits behind it once a token. Source: program span (the xplane's
host plane). This file also holds what its neighbours share
(``inside``). None where the slice holds no such span (the parent of
PR 56)."""


def inside(trace, name):
    """``[(start, end)]`` of the host events ``name`` wholly inside the
    traced slice."""
    lo, hi = trace.window
    return [(s, e) for n, s, e in trace.host
            if n == name and s >= lo and e <= hi]


def read(trace, spans, counters, cell):
    took = inside(trace, "serving/after_tick")
    if not took:
        return None
    return 1e3 * sum(e - s for s, e in took) / len(took)
