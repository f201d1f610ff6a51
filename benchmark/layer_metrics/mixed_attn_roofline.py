"""Kernels: the paged decode-attention calls' share of their byte roofline
in the traced slice, for a cache held by layer kind: sum over the
``paged_attention_decode`` calls of the least time the chip could take —
the K and V pages a call of ITS kind walks
(``families/window_moe_lm.mixed_attention_cost``) over the bandwidth of
``peaks.json`` — over the sum of their device time. A call is told by its
name; its kind by the layer count of its pool operand ([L_kind, N, ps,
Hkv*dh]); its page geometry is read off that operand; the pages a call of
a kind walks are the WINDOW's mean of that kind's counter
(``paged_attn_pages_read_global`` / ``_window`` over ``decode_steps``: the
trace does not say what a call read). A share over 100% is a wrong count.
Source: device trace (+ those program counters)."""
import json
import re
import sys

from benchmark.families import paged_attention
from benchmark.trace_reduce import clip, strip_layouts, total

_LAYERS = re.compile(r"\b[a-z]+\d+\[(\d+),\d+,\d+,\d+\]")


def read(trace, spans, counters, cell):
    """None with the reason on stderr, never an exception, where the
    program or the trace lacks what this reads."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"mixed_attn_roofline: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def pool_of(hlo_text):
    """(layers, page geometry) of the pool operand of a call of the
    kernel; None for any other device event."""
    call = paged_attention.decode_call(hlo_text)
    if call is None:
        return None
    pool = _LAYERS.search(strip_layouts(hlo_text).split("custom-call(", 1)[1])
    return int(pool.group(1)), call


def _read(trace, counters, cell):
    family = cell.family
    steps = counters.get("decode_steps")
    pages = {kind: counters.get(f"paged_attn_pages_read_{kind}")
             for kind in ("global", "window")}
    if not steps or not all(pages.values()) \
            or not hasattr(family, "mixed_attention_cost"):
        print("mixed_attn_roofline: left out (the engine counts no "
              "paged_attn_pages_read_global / _window)", file=sys.stderr)
        return None
    bw = cell.peaks["hbm_bytes_per_s"]
    spent, least, detail = 0.0, 0.0, {}
    for text, start, end in trace.device_ops.get(0, ()):
        pool = pool_of(text)
        if pool is None:
            continue
        kind = family.attention_call_kind(pool[0], cell.config)
        seconds = total(clip([(start, end)], trace.window))
        if kind is None or not seconds:
            continue
        t = family.mixed_attention_cost(pages[kind] / steps,
                                        **pool[1])["bytes"] / bw
        row = detail.setdefault(kind, {
            "calls": 0, "seconds": 0.0, "least_s_per_call": t,
            "pages_a_call_mean": pages[kind] / steps})
        row["calls"] += 1
        row["seconds"] += seconds
        spent += seconds
        least += t
    if not spent:
        print("mixed_attn_roofline: left out (no paged_attention_decode "
              "call of either kind in the traced slice)", file=sys.stderr)
        return None
    for row in detail.values():
        row["roofline_pct"] = (100.0 * row["calls"] * row["least_s_per_call"]
                               / row["seconds"])
    print(json.dumps({"mixed_attn_roofline": detail, "bound": "memory",
                      "share_of_slice_pct": 100.0 * spent / trace.window_s}),
          flush=True)
    return 100.0 * least / spent
