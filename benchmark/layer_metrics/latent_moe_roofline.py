"""Kernels: the grouped matmuls' share of their roofline in the traced
slice where the routed experts work in a LATENT (and the chip holds a SHARE
of the router's experts): as ``moe_held_roofline``, with the widths a call
is told and priced by asked of the FAMILY (``expert_widths(config)`` ->
(what an expert reads and writes, its inner width)) and not of the
configuration's ``hidden_size`` / ``moe_intermediate_size``. Sum over calls
of the least time the chip could take — max(FLOPs / peak, bytes /
bandwidth) from the family's ``grouped_matmul_cost`` — over the sum of
their device time. A call's static rows and widths are read off its
result shape ([N*k, f] for gate / up, [N*k, d] for down), but only the
rows that fell to HELD experts are work: rows = N*k x the window's held
share (the engine's ``moe_held_assignments`` / ``moe_assignments``), and
the weights read are those of the held experts a call touched
(``moe_touched_experts`` / ``moe_layer_calls``, which counts held experts
only for such a spec). Never the static N*k: three quarters of those rows
belong to absent chips and are masked. The trace does not say which groups
of one call were empty, so the per-shape shares on stdout are rough and
the sum is what the metric is. The counters are those of the TRACED SLICE
where the driver took them (``counters["slice"]``, PR 53), else the
window's: the sum is right only while the mean is over the calls that are
priced, and a slice holds its own share of prefill units among its ticks.
Source: device trace (+ those counters). None where the family states no
``expert_widths`` (every cell whose experts work at the model's width)."""
import json
import re
import sys

from benchmark.trace_reduce import clip, strip_layouts, total

_RESULT = re.compile(r"^%\S+ = \w+\[(\d+),(\d+)\] ")


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"latent_moe_roofline: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None


def _read(trace, counters, cell):
    family = cell.family
    in_slice = counters.get("slice")
    if in_slice and in_slice.get("moe_layer_calls") \
            and in_slice.get("moe_assignments"):
        counters = in_slice
    took = counters.get("moe_assignments")
    if not hasattr(family, "grouped_matmul_cost") \
            or not hasattr(family, "expert_widths") \
            or not counters.get("moe_layer_calls") or not took \
            or counters.get("moe_held_assignments") is None:
        return None
    held_share = counters["moe_held_assignments"] / took
    touched = counters["moe_touched_experts"] / counters["moe_layer_calls"]
    d, f = family.expert_widths(cell.config)
    peak, bw = cell.peaks["bf16_flops_per_s"], cell.peaks["hbm_bytes_per_s"]
    spent, least, detail = 0.0, 0.0, {}
    for text, start, end in trace.device_ops.get(0, ()):
        if family.moe_op(text, cell.config) != "grouped_matmul":
            continue
        m = _RESULT.match(strip_layouts(text))
        if not m or int(m.group(2)) not in (d, f):
            continue            # the call's metadata, a re-layout: no matmul
        static_rows, cols_out = int(m.group(1)), int(m.group(2))
        seconds = total(clip([(start, end)], trace.window))
        if not seconds:
            continue
        cols_in = f if cols_out == d else d
        c = family.grouped_matmul_cost(cell.config, static_rows * held_share,
                                       cols_in, cols_out, touched)
        t_flops, t_bytes = c["flops"] / peak, c["bytes"] / bw
        row = detail.setdefault(f"{static_rows}x{cols_in}->{cols_out}", {
            "calls": 0, "seconds": 0.0,
            "least_s_per_call": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"})
        row["calls"] += 1
        row["seconds"] += seconds
        spent += seconds
        least += max(t_flops, t_bytes)
    if not spent:
        return None
    for row in detail.values():
        row["roofline_pct"] = (100.0 * row["calls"] * row["least_s_per_call"]
                               / row["seconds"])
    print(json.dumps({"latent_moe_roofline": detail,
                      "held_share_pct": 100.0 * held_share,
                      "counted_over": ("slice" if counters is in_slice
                                       else "window"),
                      "touched_held_experts_mean": touched}), flush=True)
    return 100.0 * least / spent
