"""Program -> XLA, serving: median host time of one decode step (feed,
run, blocking fetch) over the engine's reservoir of its last 4096 decode
steps at the close of the window (``metrics.snapshot()['latency']
['decode_step_ms']['p50']``; at this cell's rate that is the ramp and the
window). Source: program counter (host seconds round a blocking fetch)."""


def read(trace, spans, counters, cell):
    return counters.get("decode_step_p50_ms")
