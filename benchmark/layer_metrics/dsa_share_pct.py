"""Program to XLA: the sparse selection's share of the chip's busy time in
the traced slice — device time of the ops the family's ``dsa_op`` tells (the
indexer's scores over the pooled keys, the pick, the gather of the picked
rows, the attention over them, the pooled keys' write, the indexer's
projection), over the busy time of the slice. Source: device trace. The
split by part goes to stdout."""
import json
import sys

from benchmark.trace_reduce import clip, is_container, total

NAME, HOOK = "dsa_share_pct", "dsa_op"


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return share_of(trace, cell, NAME, HOOK)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"{NAME}: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None


def share_of(trace, cell, name, hook):
    """Device time of the ops ``cell.family.<hook>(text, config)`` names
    over the busy time of the traced slice, in percent."""
    tell = getattr(cell.family, hook, None)
    if tell is None:
        return None
    parts = {}
    for text, start, end in trace.device_ops.get(0, ()):
        if is_container(text):
            continue
        part = tell(text, cell.config)
        if part is not None:
            parts[part] = parts.get(part, 0.0) + total(
                clip([(start, end)], trace.window))
    busy = trace.busy_s(0)
    if not parts or not busy:
        return None
    print(json.dumps({name: {k: 100.0 * v / busy for k, v in parts.items()}}),
          flush=True)
    return 100.0 * sum(parts.values()) / busy
