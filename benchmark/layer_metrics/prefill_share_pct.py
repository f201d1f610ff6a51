"""Program -> XLA, serving: prefill's share of the engine's step time in
the window: (grouped prefills + prefill chunks) / (those + decode steps),
from the sums of the engine's ``prefill``, ``prefill_chunk`` and
``decode_step`` latency histograms, after - before. Source: program
counter (host seconds round a blocking fetch)."""


def read(trace, spans, counters, cell):
    prefill = (counters.get("prefill_sum_ms", 0.0)
               + counters.get("prefill_chunk_sum_ms", 0.0))
    total = prefill + counters.get("decode_step_sum_ms", 0.0)
    return 100.0 * prefill / total if total else None
