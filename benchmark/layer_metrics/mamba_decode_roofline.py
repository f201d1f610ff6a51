"""Kernels: the Mamba-2 decode step's share of its byte roofline in the
traced slice: sum over the ``mamba2_decode_step`` calls of the least time
the chip could take — every row's float32 state tiles read once and written
once plus the step's own inputs and read-out
(``families/mamba2_gqa_moe_lm.mamba_decode_cost``: the geometry is read off
the call's operands) over the bandwidth of ``peaks.json`` — over the sum of
their device time. A call is told by its name. Source: device trace."""
import json
import sys

from benchmark.trace_reduce import clip, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads (the parent of the PR that brought the kernel, a cell
    of another family)."""
    try:
        return _read(trace, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"mamba_decode_roofline: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def _read(trace, cell):
    family = cell.family
    if not hasattr(family, "mamba_decode_call"):
        print("mamba_decode_roofline: left out (the family tells no Mamba-2 "
              "decode kernel)", file=sys.stderr)
        return None
    bw = cell.peaks["hbm_bytes_per_s"]
    calls, spent, least = 0, 0.0, 0.0
    for text, start, end in trace.device_ops.get(0, ()):
        call = family.mamba_decode_call(text)
        if call is None:
            continue
        seconds = total(clip([(start, end)], trace.window))
        if not seconds:
            continue
        calls += 1
        spent += seconds
        least += family.mamba_decode_cost(cell.config, **call)["bytes"] / bw
    if not spent:
        print("mamba_decode_roofline: left out (no mamba2_decode_step call in "
              "the traced slice)", file=sys.stderr)
        return None
    print(json.dumps({"mamba_decode_roofline": {
        "calls": calls, "seconds": spent,
        "least_s_per_call": least / calls, "bound": "memory",
        "share_of_slice_pct": 100.0 * spent / trace.window_s}}), flush=True)
    return 100.0 * least / spent
