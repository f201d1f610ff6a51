"""Serving scheduler: the share of the rows the window's ticks decoded
whose pass ran TWO OR MORE prefill units (``pass_rows_multi_unit`` over
all three ``pass_rows_*``): the share above the mode ``tpot_p95_ms`` sits
in, the twin from inside the program of ``gaps_over_p95_mode_pct``. Why a
pass held more than one unit goes to stdout, one JSON line
``pass_units``: the three causes the engine counts
(``pass_multi_unit_by_split``: a group beyond the largest batch bucket
went out in several calls; ``_by_deferred``: a deferred admission ran a
group of its own in the pass; ``_by_group_and_chunk``: a group call and
a chunk), the passes of each mode, the units a pass and the passes that
ran units and no tick. Source: program counter. None on the parent
of PR 56."""
import json

from benchmark.layer_metrics.pass_tick_only_ms import MODES, rows


def read(trace, spans, counters, cell):
    got = rows(counters)
    if got is None:
        return None
    passes = {mode: counters.get(f"pass_{mode}_count", 0) for mode in MODES}
    ticks = sum(passes.values())
    print(json.dumps({"pass_units": {
        **{f"passes_{mode}": n for mode, n in passes.items()},
        **{f"rows_{mode}": n for mode, n in zip(MODES, got)},
        **{cause: counters.get(f"pass_multi_unit_{cause}", 0)
           for cause in ("by_split", "by_deferred", "by_group_and_chunk")},
        "units_per_pass": (counters.get("pass_units", 0) / ticks
                           if ticks else None),
        "passes_without_tick": counters.get("passes_without_tick", 0),
        "decode_steps": counters.get("decode_steps"),
    }}), flush=True)
    return 100.0 * got[2] / sum(got)
