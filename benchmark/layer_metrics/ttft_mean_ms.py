"""Entry points: mean over the requests due in the window of (first
``on_token`` time - the time the request was due). Not an end-to-end
metric: a window holds some fifty requests, each waits a whole number of
160-280 ms loop passes, and whether one lands behind another's prefill
flips with a few milliseconds of host jitter: six runs of one code spread
by 1.9-4.3% on a shared host (the driver's check of PR 22), 0.7-1.3% on a
quiet one (my chip runs, PR 22), and a bound may be 10% at most. A
faster tick (S2/S3) puts hundreds of requests in a window and brings it
back end to end. Source: the benchmark's own clock."""


def read(trace, spans, counters, cell):
    ttft = counters.get("ttft_ms")
    return sum(ttft) / len(ttft) if ttft else None
