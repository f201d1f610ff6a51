"""Serving scheduler: the share of the window's admitted prompt tokens that
were NOT prefilled because cached pages AND a state snapshot covered them —
the engine's ``prefix_hit_tokens`` / ``prompt_tokens_admitted``, after -
before. A spec whose slots carry a recurrent state can enter a cached prefix
only at a snapshot boundary (``GenerationEngine(snapshot_stride=,
n_snapshots=)``), so this is what the snapshot pool buys: without it the
share is 0 and every such prompt prefills from its first token. Source:
program counter. None where the engine takes no snapshots (every other
cell; the parent of the PR that brought them)."""


def read(trace, spans, counters, cell):
    admitted = counters.get("prompt_tokens_admitted")
    if not admitted or counters.get("state_snapshots_taken") is None:
        return None
    return 100.0 * counters.get("prefix_hit_tokens", 0) / admitted
