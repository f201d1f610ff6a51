"""Serving scheduler: what the snapshot stride costs — of the prompt tokens
whose PAGES the prefix index matched in the window, the share that was
prefilled again because no state snapshot lay that deep: the engine's
``state_snapshot_cutback_tokens`` / (that + ``prefix_hit_tokens``), after -
before. 0: every matched page ended at or before a boundary with a snapshot.
Source: program counter. None where the engine takes no snapshots (every
other cell; the parent of the PR that brought them) or matched nothing."""


def read(trace, spans, counters, cell):
    cut = counters.get("state_snapshot_cutback_tokens")
    if cut is None:
        return None
    matched = cut + counters.get("prefix_hit_tokens", 0)
    return 100.0 * cut / matched if matched else None
