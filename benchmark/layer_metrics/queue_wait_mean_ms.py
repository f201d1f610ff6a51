"""Serving scheduler: mean time a request admitted in the window had
waited in the admission queue (the engine's ``queue_wait`` histogram:
sum / count, after - before). A mean and not a median: the program keeps
this one as fixed log buckets, whose quantiles are good to a third only,
and its tracer cannot be on in a serving run (PERF.md section 7).
Source: program counter."""


def read(trace, spans, counters, cell):
    n = counters.get("queue_wait_count", 0)
    return counters["queue_wait_sum_ms"] / n if n else None
