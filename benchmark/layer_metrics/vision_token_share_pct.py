"""Serving scheduler: the share of the window's prefilled prompt rows that
came from the vision tower's merger and not from the token table (the
engine's ``vision_tokens_prefilled`` / ``prompt_tokens_prefilled``, after -
before): 97 where every prompt is a clip and a question, 0 for text. Source:
program counter. None where the engine counts neither (no tower)."""


def read(trace, spans, counters, cell):
    rows = counters.get("prompt_tokens_prefilled")
    if not rows:
        return None
    return 100.0 * counters.get("vision_tokens_prefilled", 0) / rows
