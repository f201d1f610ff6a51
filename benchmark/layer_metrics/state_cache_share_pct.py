"""Serving scheduler: which cache sets the batch — the bytes of recurrent
state the live slots hold over those plus the bytes of the latent pages
they hold, summed over the window's decode ticks (the engine's
``state_bytes_live_ticks`` / (that + ``kv_bytes_held_ticks``), after -
before; the tick-summed twins of the gauges ``mem/state_bytes_live`` and
``mem/kv_pages_in_use``). 100 would be a model with no pages at all.
Source: program counter. None where the engine counts no state (every
other family; the parent of the PR that brought it)."""


def read(trace, spans, counters, cell):
    state = counters.get("state_bytes_live_ticks")
    pages = counters.get("kv_bytes_held_ticks")
    if not state or pages is None:
        return None
    return 100.0 * state / (state + pages)
