"""Kernels: the paged decode-attention calls' share of their byte roofline
in the traced slice: sum over the ``paged_attention_decode`` calls of the
least time the chip could take — the K and V pages a tick walks
(``families/paged_attention.decode_cost``) over the bandwidth of
``peaks.json`` — over the sum of their device time. A call is told by its
name and its page geometry is read off its pool operand; the pages a tick
walks are the WINDOW's mean (the engine's ``paged_attn_pages_read`` /
``decode_steps``: the trace does not say what a call read), so the sum is
what the metric is. Source: device trace (+ that one program counter)."""
import json
import sys

from benchmark.families import paged_attention
from benchmark.trace_reduce import clip, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads (the parent of the PR that brought the kernel): a
    traced run's numbers refuse nothing, a traced run that fails refuses
    the PR."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"paged_attn_roofline: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def _read(trace, counters, cell):
    steps = counters.get("decode_steps")
    if not steps or not counters.get("paged_attn_pages_read"):
        print("paged_attn_roofline: left out (the engine counts no "
              "paged_attn_pages_read)", file=sys.stderr)
        return None
    pages = counters["paged_attn_pages_read"] / steps
    bw = cell.peaks["hbm_bytes_per_s"]
    calls, spent, least = 0, 0.0, 0.0
    for text, start, end in trace.device_ops.get(0, ()):
        call = paged_attention.decode_call(text)
        if call is None:
            continue
        seconds = total(clip([(start, end)], trace.window))
        if not seconds:
            continue
        calls += 1
        spent += seconds
        least += paged_attention.decode_cost(pages, **call)["bytes"] / bw
    if not spent:
        print("paged_attn_roofline: left out (no paged_attention_decode "
              "call in the traced slice)", file=sys.stderr)
        return None
    print(json.dumps({"paged_attn_roofline": {
        "calls": calls, "seconds": spent, "pages_a_tick_mean": pages,
        "least_s_per_call": least / calls, "bound": "memory",
        "share_of_slice_pct": 100.0 * spent / trace.window_s}}), flush=True)
    return 100.0 * least / spent
