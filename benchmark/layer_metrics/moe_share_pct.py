"""Program to XLA: the expert layer's share of the chip's busy time in
the traced slice — device time of the ops the family's ``moe_op`` tells
as grouped matmuls (ragged-dot calls, anything reading the stacked expert
weights) or routing (router product, softmax / top-k / sort over the
experts), over the busy time of the slice. Source: device trace. The
split by part, and the largest ops it left out, go to stdout."""
import json
import sys

from benchmark.trace_reduce import clip, is_container, strip_layouts, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads (see ``moe_roofline.read``)."""
    try:
        return _read(trace, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"moe_share_pct: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None


def _read(trace, cell):
    family = cell.family
    if not hasattr(family, "moe_op"):
        return None
    parts, rest = {}, {}
    for text, start, end in trace.device_ops.get(0, ()):
        if is_container(text):
            continue
        seconds = total(clip([(start, end)], trace.window))
        part = family.moe_op(text, cell.config)
        if part is None:
            rest[text] = rest.get(text, 0.0) + seconds
        else:
            parts[part] = parts.get(part, 0.0) + seconds
    busy = trace.busy_s(0)
    if not parts or not busy:
        return None
    others = sorted(rest.items(), key=lambda kv: -kv[1])[:12]
    print(json.dumps({
        "moe_share_pct": {k: 100.0 * v / busy for k, v in parts.items()},
        "largest_other_ops_pct": [[strip_layouts(t)[:140], 100.0 * s / busy]
                                  for t, s in others]}), flush=True)
    return 100.0 * sum(parts.values()) / busy
