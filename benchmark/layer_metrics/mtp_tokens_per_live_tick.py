"""Serving scheduler: what a live slot gets for a tick — tokens EMITTED by
the window's decode ticks (``decode_tokens``) over the live slots summed
over those ticks (``decode_live_rows``): 1 without a drafting block, 1 +
the share of ticks whose draft was accepted AND emitted with one (a request
that ends on the first of two tokens emits one). Source: program
counter."""


def read(trace, spans, counters, cell):
    """None where the program counts no live rows (an engine without a
    drafting block, the parent)."""
    rows = counters.get("decode_live_rows")
    if not rows or counters.get("decode_tokens") is None:
        return None
    return counters["decode_tokens"] / rows
