"""Program to XLA: the Mamba-2 layers' share of the chip's busy time in the
traced slice — device time of the ops the family's ``mamba_op`` tells (the
decode kernel ``step``, the chunked scan and the state's gather and scatter
``scan``, the convolution and its history ``conv``, the in- and out-
projection with the gate and group norm ``project``), over the busy time of
the slice. Source: device trace. The split by part goes to stdout. None
where the family tells no Mamba-2 op (every other cell; a parent without
the layer cannot run the cell at all)."""
import json
import sys

from benchmark.trace_reduce import clip, is_container, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return _read(trace, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"mamba_share_pct: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None


def _read(trace, cell):
    family = cell.family
    if not hasattr(family, "mamba_op"):
        return None
    parts = {}
    for text, start, end in trace.device_ops.get(0, ()):
        if is_container(text):
            continue
        part = family.mamba_op(text, cell.config)
        if part is not None:
            parts[part] = parts.get(part, 0.0) + total(
                clip([(start, end)], trace.window))
    busy = trace.busy_s(0)
    if not parts or not busy:
        return None
    print(json.dumps({"mamba_share_pct": {
        k: 100.0 * v / busy for k, v in parts.items()}}), flush=True)
    return 100.0 * sum(parts.values()) / busy
