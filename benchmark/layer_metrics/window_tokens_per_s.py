"""Entry points: output tokens whose client-side ``on_token`` time falls
in the window / the window's seconds. Not an end-to-end metric in a cell
below the knee: there it is the offered load, less whatever a host stall
pushed past the window's closing edge (each request in flight emits one
token a tick, stall or not), so on a shared host six runs of one code
spread by 0.8-1.5% (the driver's check of PR 22) where a quiet host gave
0.13% (my chip runs, PR 22) — no bound fits both. The saturated cell
(PERF.md section 7), where it is the capacity, brings it back end to end.
Source: the benchmark's own clock."""


def read(trace, spans, counters, cell):
    return counters.get("tokens_per_s")
