"""Program to XLA: the KDA layers' share of the chip's busy time in the
traced slice — device time of the ops the family's ``kda_op`` tells (the
decode kernel, the chunked form and the state's gather and scatter, the
q | k | v / gate projections and the convolution), over the busy time of
the slice. Source: device trace. The split by part goes to stdout."""
import json
import sys

from benchmark.trace_reduce import clip, is_container, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return _read(trace, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"kda_share_pct: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None


def _read(trace, cell):
    family = cell.family
    if not hasattr(family, "kda_op"):
        return None
    parts = {}
    for text, start, end in trace.device_ops.get(0, ()):
        if is_container(text):
            continue
        part = family.kda_op(text, cell.config)
        if part is not None:
            parts[part] = parts.get(part, 0.0) + total(
                clip([(start, end)], trace.window))
    busy = trace.busy_s(0)
    if not parts or not busy:
        return None
    print(json.dumps({"kda_share_pct": {
        k: 100.0 * v / busy for k, v in parts.items()}}), flush=True)
    return 100.0 * sum(parts.values()) / busy
