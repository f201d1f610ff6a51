"""Rating a serve cell: ONE rate a process through the cell's own driver.

    python3 benchmark/sweep.py --workload <cell> --rate <req/s>
        [--schedule <schedule_seed>] [--seed <n>] [--seconds 120]
        [--engine key=value ...]

The run is the cell's run (``drivers/serve.py``: a fresh engine, a cold
prefix cache, the mix's ramp, the same clocks and the same check) with
the mix's ``arrivals.rate_per_s`` (and, where given, its
``schedule_seed`` and keys of ``engine``) replaced: what it reads at a
rate is what the cell would read there. A warm engine carried across
rates reads the knee wrong (PR 30), so there is no loop over rates here.
Prints one JSON line: what ``README.md``'s rating procedure judges
(``slo_attain_pct``, requests in flight over the window,
``admission_deferred``, ``gaps_over_tick_pct``) with the tick, the unit,
occupancy, the gaps' percentiles, the pools' device layout and the
memory peak, and writes it to ``chiprun_out/sweep/<cell>_<rate>_<schedule
seed>_<seed>.json`` (a file a run: a later chip call's merge REPLACES a
file of the same name). Not part of a run of the benchmark: a
``benchmark`` PR's tool.
"""
import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOTES = ("slo_attain_pct", "requests_due", "requests_failed",
         "in_flight_over_window", "in_flight_at_close",
         "admission_deferred", "gaps_over_tick_pct",
         "gaps_over_p95_mode_pct", "gaps_by_median",
         "tpot_p50_ms", "tpot_p90_ms", "tpot_p99_ms", "ttft_mean_ms",
         "ttft_p50_ms", "ttft_p95_ms", "gen_late_p95_ms",
         "decode_step_p50_ms", "tokens_in_window", "logit_gap_max",
         "problems", "errors")


def _pools(eng) -> dict:
    """Shape, dtype and device layout of the engine's K pools (PR 25's
    lesson: read the layout before a cache is sized)."""
    out = {}
    for name in getattr(eng, "_cache_names", ()):
        if eng.scope.has(name) and name.endswith(("_k", "_kw")):
            a = eng.scope.get(name)
            out[name] = {"shape": list(a.shape), "dtype": str(a.dtype),
                         "layout": str(getattr(a, "format", None))}
    return out


def run_rate(cell, rate, schedule, engine, seed, seconds, devices,
             t0) -> dict:
    """One run of ``cell``'s driver at ``rate`` req/s on ``devices``."""
    from benchmark import harness
    from benchmark.layer_metrics import decode_occupancy_pct

    cell.mix["arrivals"]["rate_per_s"] = rate
    if schedule is not None:
        cell.mix["schedule_seed"] = schedule
    for key, value in engine.items():
        cell.mix["engine"][key] = int(value)
    cell.peaks = harness.peaks_for(cell, devices[0].device_kind)
    pools, engines = {}, []
    family, build = cell.family, cell.family.build_engine

    def build_engine(*a, **kw):
        eng, executors = build(*a, **kw)
        pools.update(_pools(eng))
        engines.append(eng)
        return eng, executors

    family.build_engine = build_engine
    try:
        m = cell.driver.run(cell, seed, seconds, False,
                            devices, t0)
    finally:
        family.build_engine = build
    device = harness.device_block(devices, m.executors,
                                  m.live_peak_bytes)
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    return {
        "cell": cell.name, "rate_per_s": rate,
        "schedule_seed": cell.mix["schedule_seed"], "seed": seed,
        "seconds": seconds, "engine": cell.mix["engine"],
        "correct": m.correct, "setup_s": m.setup_s,
        "tpot_p95_ms": m.end_to_end["tpot_p95_ms"],
        "occupancy_pct": decode_occupancy_pct.read(None, m.spans,
                                                   m.counters, cell),
        "prefill_chunk_p50_ms": engines[0].metrics.snapshot()[
            "latency"].get("prefill_chunk_ms", {}).get("p50"),
        "window_tokens_per_s": m.counters["tokens_per_s"],
        **{k: m.notes.get(k) for k in NOTES},
        "deferred_by_kind": [m.counters.get(k, 0) for k in
                             ("admit_deferred_global",
                              "admit_deferred_window")],
        "memory_peak_bytes": device["memory_peak_bytes"],
        "memory_limit_bytes": limit,
        "memory_peak_pct": (100.0 * device["memory_peak_bytes"] / limit
                            if limit else None),
        "pools": pools, "wall_s": time.monotonic() - t0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--schedule", type=int)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=120.0)
    ap.add_argument("--engine", nargs="*", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or cell.mix["kind"] != "serve":
        print(f"sweep: {cell.name} needs a TPU and a serve mix (found "
              f"{devices[0].platform!r}, kind {cell.mix['kind']!r}); "
              "nothing was run", file=sys.stderr)
        return 1
    line = run_rate(cell, args.rate, args.schedule,
                    dict(p.split("=") for p in args.engine), args.seed,
                    args.seconds, devices[:1], T0)
    out = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out, exist_ok=True)
    name = (f"{cell.name}_{args.rate:g}_{line['schedule_seed']}_"
            f"{args.seed}.json")
    with open(os.path.join(out, name), "w") as f:
        json.dump(line, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
