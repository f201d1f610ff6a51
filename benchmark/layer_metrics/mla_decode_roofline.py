"""Kernels: the latent decode-attention calls' share of their byte roofline
in the traced slice: sum over the ``paged_mla_decode`` calls of the least
time the chip could take — the latent pages a tick walks, each read ONCE
for key and value (``families/mla_moe_lm.mla_decode_cost``: 320 values a
token, not the stored row's lane padding) over the bandwidth of
``peaks.json`` — over the sum of their device time. A call is told by its
name and its page geometry is read off its one pool operand; the pages a
tick walks are the WINDOW's mean (the engine's ``paged_attn_pages_read``
/ ``decode_steps``: the trace does not say what a call read), so the sum is
what the metric is. Source: device trace (+ that one program counter)."""
import json
import sys

from benchmark.trace_reduce import clip, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads (the parent of the PR that brought the kernel, a cell
    of another family): a traced run's numbers refuse nothing, a traced
    run that fails refuses the PR."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"mla_decode_roofline: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def _read(trace, counters, cell):
    family = cell.family
    steps = counters.get("decode_steps")
    if not hasattr(family, "mla_decode_call") or not steps \
            or not counters.get("paged_attn_pages_read"):
        print("mla_decode_roofline: left out (no latent decode kernel "
              "or no paged_attn_pages_read counter)", file=sys.stderr)
        return None
    pages = counters["paged_attn_pages_read"] / steps
    bw = cell.peaks["hbm_bytes_per_s"]
    calls, spent, least = 0, 0.0, 0.0
    for text, start, end in trace.device_ops.get(0, ()):
        call = family.mla_decode_call(text)
        if call is None:
            continue
        seconds = total(clip([(start, end)], trace.window))
        if not seconds:
            continue
        calls += 1
        spent += seconds
        least += family.mla_decode_cost(cell.config, pages, **call)[
            "bytes"] / bw
    if not spent:
        print("mla_decode_roofline: left out (no paged_mla_decode call in "
              "the traced slice)", file=sys.stderr)
        return None
    print(json.dumps({"mla_decode_roofline": {
        "calls": calls, "seconds": spent, "pages_a_tick_mean": pages,
        "least_s_per_call": least / calls, "bound": "memory",
        "share_of_slice_pct": 100.0 * spent / trace.window_s}}), flush=True)
    return 100.0 * least / spent
