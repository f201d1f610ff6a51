"""Kernels: the vision tower's share of the chip's peak in the traced slice:
the operations of the frames the slice's units encoded (the engine's
``vision_frames_encoded`` over the calls of the slice, priced by the family's
``vision_cost``: the blocks' matrix products, the attention within a frame,
the patch embedding and the merger) over the published bf16 peak of
``peaks.json``, over the device time of the ops the family's ``vision_op``
tells. The tower has no Pallas kernel: this is what XLA makes of 1024-patch
frames of width 1152 with heads of 72. A frame two chunks share is encoded
(and counted) with each. Source: device trace (+ that program counter)."""
import json
import sys

from benchmark.trace_reduce import clip, is_container, total


def read(trace, spans, counters, cell):
    """None, never an exception, where the program or the trace lacks
    what this reads."""
    try:
        return _read(trace, counters, cell)
    except Exception as exc:  # noqa: BLE001 - the line leaves it out
        print(f"vision_encode_mfu_pct: left out ({type(exc).__name__}: "
              f"{exc})", file=sys.stderr)
        return None


def _read(trace, counters, cell):
    family = cell.family
    counted = counters.get("slice") or {}
    frames = counted.get("vision_frames_encoded")
    if not hasattr(family, "vision_cost") or not frames:
        print("vision_encode_mfu_pct: left out (no frame encoded in the "
              "traced slice)", file=sys.stderr)
        return None
    spent = 0.0
    for text, start, end in trace.device_ops.get(0, ()):
        if not is_container(text) \
                and family.vision_op(text, cell.config) is not None:
            spent += total(clip([(start, end)], trace.window))
    if not spent:
        return None
    least = family.vision_cost(cell.config, frames)["flops"] \
        / cell.peaks["bf16_flops_per_s"]
    print(json.dumps({"vision_encode_mfu_pct": {
        "frames": frames, "seconds": spent, "least_s": least,
        "ms_a_frame": 1e3 * spent / frames}}), flush=True)
    return 100.0 * least / spent
