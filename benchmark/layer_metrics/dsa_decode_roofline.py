"""Kernels: the decode TICK's sparse selection against its roofline in the
traced slice: the least time the chip could take for the selection work the
slice's ticks did — pooled keys scored and picked latent rows attended (the
engine's ``dsa_tick_groups_scored`` / ``dsa_tick_rows_attended`` over the
calls of the slice, ONE layer's worth, times the ``dsa_layer_calls`` /
``dsa_calls`` layers that ran it), each read ONCE, with the indexer's and the absorbed
attention's operations (``families/dsa_kda_moe_lm.dsa_cost``), the larger of
bytes over bandwidth and operations over peak — over the device time of the
tick's ops that the family's ``dsa_op`` names (``dsa_tick_op``: results that
lead with the slot count). Priced by the WORK, whatever implements it: a
gather and a batched product today, a page-walking kernel later, read by the
same yardstick. Source: device trace (+ those program counters)."""
import json
import sys

from benchmark.trace_reduce import clip, is_container, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"dsa_decode_roofline: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def _read(trace, counters, cell):
    family = cell.family
    counted = counters.get("slice") or counters
    rows = counted.get("dsa_tick_rows_attended")
    if not hasattr(family, "dsa_cost") or not rows:
        print("dsa_decode_roofline: left out (no sparse selection counted)",
              file=sys.stderr)
        return None
    layers = counted["dsa_layer_calls"] / counted["dsa_calls"]
    cost = family.dsa_cost(cell.config, counted["dsa_tick_groups_scored"],
                           rows)
    by = {"memory": cost["bytes"] / cell.peaks["hbm_bytes_per_s"],
          "compute": cost["flops"] / cell.peaks["bf16_flops_per_s"]}
    bound = max(by, key=by.get)
    least = layers * by[bound]
    spent = 0.0
    for text, start, end in trace.device_ops.get(0, ()):
        if is_container(text) or family.dsa_op(text, cell.config) is None \
                or not family.dsa_tick_op(text, cell.config,
                                          counters["slots"]):
            continue
        spent += total(clip([(start, end)], trace.window))
    if not spent:
        print("dsa_decode_roofline: left out (no tick op of the selection "
              "in the traced slice)", file=sys.stderr)
        return None
    print(json.dumps({"dsa_decode_roofline": {
        "seconds": spent, "least_s": least, "bound": bound,
        "sparse_layers": layers, "ticks": counted["decode_steps"],
        "rows_attended_a_tick": rows / max(counted["decode_steps"], 1),
        "share_of_slice_pct": 100.0 * spent / trace.window_s}}), flush=True)
    return 100.0 * least / spent
