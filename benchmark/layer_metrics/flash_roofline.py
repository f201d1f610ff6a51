"""Kernels: the flash-attention Mosaic calls' share of their roofline in
the traced slice: sum over calls of the least time the chip could take —
max(FLOPs / peak, bytes / bandwidth), from the family's shape functions
and ``peaks.json`` — over the sum of their device time. Source: device
trace. Which bound holds, and each kernel's own share, go to stdout."""
import json


def read(trace, spans, counters, cell):
    family = cell.family
    if not hasattr(family, "mosaic_kernel"):
        return None
    costs = family.mosaic_costs(cell.config, cell.mix, cell.chips)
    peak, bw = cell.peaks["bf16_flops_per_s"], cell.peaks["hbm_bytes_per_s"]
    spent, least, detail = 0.0, 0.0, {}
    for text, start, end in trace.mosaic_calls(chip=0):
        kernel = family.mosaic_kernel(text)
        if kernel is None:
            continue
        c = costs[kernel]
        t_flops, t_bytes = c["flops"] / peak, c["bytes"] / bw
        d = detail.setdefault(kernel, {
            "calls": 0, "seconds": 0.0, "least_s_per_call": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory"})
        d["calls"] += 1
        d["seconds"] += end - start
        spent += end - start
        least += max(t_flops, t_bytes)
    if not spent:
        return None
    for d in detail.values():
        d["roofline_pct"] = 100.0 * d["calls"] * d["least_s_per_call"] / d["seconds"]
    print(json.dumps({"flash_roofline": detail,
                      "share_of_slice_pct": 100.0 * spent / trace.window_s}),
          flush=True)
    return 100.0 * least / spent
