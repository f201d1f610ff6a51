"""Kernels: the grouped matmuls' share of their roofline in the traced
slice: sum over calls of the least time the chip could take — max(FLOPs /
peak, bytes / bandwidth) from the family's ``grouped_matmul_cost`` (the
weights of the experts that took a row + the rows in and out) and
``peaks.json`` — over the sum of their device time. A call's rows and
widths are read off its result shape ([rows, f] for gate / up, [rows, d]
for down); the experts a call touched are the WINDOW's mean over calls
and layers (the engine's ``moe_touched_experts`` / ``moe_layer_calls``:
the trace does not say which groups of one call were empty), so the
per-shape shares on stdout are rough and the sum is what the metric is.
Source: device trace (+ that one program counter)."""
import json
import re
import sys

from benchmark.trace_reduce import clip, strip_layouts, total

_RESULT = re.compile(r"^%\S+ = \w+\[(\d+),(\d+)\] ")


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads: a traced run's numbers refuse nothing, a traced run
    that fails refuses the PR."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"moe_roofline: left out ({type(exc).__name__}: {exc})",
              file=sys.stderr)
        return None


def _read(trace, counters, cell):
    family = cell.family
    if not hasattr(family, "grouped_matmul_cost") \
            or not counters.get("moe_layer_calls"):
        return None
    touched = counters["moe_touched_experts"] / counters["moe_layer_calls"]
    d, f = cell.config["hidden_size"], cell.config["intermediate_size"]
    peak, bw = cell.peaks["bf16_flops_per_s"], cell.peaks["hbm_bytes_per_s"]
    spent, least, detail = 0.0, 0.0, {}
    for text, start, end in trace.device_ops.get(0, ()):
        if family.moe_op(text, cell.config) != "grouped_matmul":
            continue
        m = _RESULT.match(strip_layouts(text))
        if not m or int(m.group(2)) not in (d, f):
            continue            # the call's metadata, a re-layout: no matmul
        rows, cols_out = int(m.group(1)), int(m.group(2))
        seconds = total(clip([(start, end)], trace.window))
        if not seconds:
            continue
        c = family.grouped_matmul_cost(cell.config, rows,
                                       f if cols_out == d else d, cols_out,
                                       touched)
        t_flops, t_bytes = c["flops"] / peak, c["bytes"] / bw
        k = f"{rows}x{f if cols_out == d else d}->{cols_out}"
        row = detail.setdefault(k, {
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
    print(json.dumps({"moe_roofline": detail,
                      "touched_experts_mean": touched}), flush=True)
    return 100.0 * least / spent
