"""The data-driven harness: finds a cell's files by the names in
``BENCHMARK.json``, hands them to the driver its traffic mix names, and
turns what the driver measured into the one contract line.

    BENCHMARK.json  workloads[i] = {name, config, traffic, chips, why}
    configs/<config>.json      sizes, ``family`` -> families/<family>.py
    mixes/<traffic>.json       parameters, ``kind`` -> drivers/<kind>.py
    layer_metrics/<metric>.py  read(trace, spans, counters, cell) -> float
    peaks.json                 published peaks by ``device_kind``

Nothing here knows a cell, a model or a metric by name.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
#: traces of the last traced run of each cell; listed in .gitignore
OUT_DIR = os.path.join(ROOT, ".bench_out")
SETUP = "setup_s"


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its files loaded."""
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: List[dict]      # this cell's end-to-end metrics
    per_layer: List[dict]       # this cell's per-layer metrics
    peaks_table: dict
    peaks: Optional[dict] = None    # the running device's row

    @property
    def family(self):
        return importlib.import_module(
            f"benchmark.families.{self.config['family']}")

    @property
    def driver(self):
        return importlib.import_module(
            f"benchmark.drivers.{self.mix['kind']}")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, manifest: str = MANIFEST,
              data_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``workload``. ``manifest`` and ``data_dir`` (the
    directory holding ``configs/``, ``mixes/`` and ``peaks.json``) differ
    from the defaults only in the CPU rehearsal, which keeps a tiny
    configuration and mix of its own."""
    spec = _read_json(manifest)
    entry = next((w for w in spec["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in {manifest}: "
                       f"{[w['name'] for w in spec['workloads']]}")
    cfg = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = _read_json(os.path.join(os.path.dirname(manifest),
                                     cfg["file"]))
    mix = _read_json(os.path.join(data_dir, "mixes",
                                  entry["traffic"] + ".json"))
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config, mix=mix,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        peaks_table=_read_json(os.path.join(data_dir, "peaks.json")))


def peaks_for(cell: Cell, device_kind: str) -> dict:
    """The published peaks of the device the run is on; a device the
    table does not know is an error, never a default."""
    row = cell.peaks_table["devices"].get(device_kind)
    if row is None:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(cell.peaks_table['devices'])}): add its published "
            "peaks with their source before measuring on it")
    return row


# ---------------------------------------------------------------------------
# what a driver hands back
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Measured:
    correct: bool
    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]            # without setup_s
    counters: Dict[str, Any]                # for the per-layer readers
    spans: List[dict]                       # monotonic clock
    executors: list                         # for memory_analysis
    xplane: Optional[str] = None            # the traced slice, if any
    live_peak_bytes: Optional[int] = None   # where the driver read it early
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: each number ``correct`` compared, beside its limit:
    #: {name: {"value": .., "limit": ..}}; printed last, where given
    checks: Dict[str, dict] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TrainProgram:
    """What a family's ``build_train`` returns: the program behind
    ``trainer.SGD``, its scope and its main program."""
    sgd: Any
    scope: Any
    main: Any


# ---------------------------------------------------------------------------
# spans of the benchmark's own calls
# ---------------------------------------------------------------------------
class SpanLog:
    """``with log.span("bench/step"):`` — one
    ``jax.profiler.TraceAnnotation`` (so the call shows on the profiler's
    clock) and one record on ``time.monotonic()``."""

    def __init__(self):
        self.spans: List[dict] = []

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name, **attrs):
            yield
        self.spans.append({"name": name, "start": t0,
                           "end": time.monotonic(), "attrs": attrs})


class ProgramSpans:
    """The program's own tracer (``paddle_tpu.trace``), switched on for a
    traced run and read back on the ``time.monotonic()`` clock. The
    tracer keeps ``perf_counter() - epoch``; one recorded sync span gives
    the epoch without reaching into the tracer."""

    def __init__(self, capacity: int = 400_000):
        from paddle_tpu import trace

        self._trace = trace
        trace.enable(level=1)
        trace.get_tracer().configure(capacity=capacity)
        pc, mono = time.perf_counter(), time.monotonic()
        sync = trace.record("bench/clock_sync", pc, pc)
        # span.start = pc - epoch; monotonic = perf_counter + (mono - pc)
        self._to_mono = (pc - sync.start) + (mono - pc)

    def collect(self) -> List[dict]:
        out = []
        for sp in self._trace.get_tracer().spans():
            if sp.end is None or sp.name == "bench/clock_sync":
                continue
            out.append({"name": sp.name, "start": sp.start + self._to_mono,
                        "end": sp.end + self._to_mono,
                        "attrs": dict(sp.attrs)})
        self._trace.disable()
        return out


# ---------------------------------------------------------------------------
# the traced slice
# ---------------------------------------------------------------------------
class TraceSlice:
    """``start()`` ... ``stop()`` around a few steps or seconds, both on
    ONE thread (a TraceAnnotation closes on the thread that opened it).
    Writes under ``.bench_out/<workload>/`` inside the checkout."""

    def __init__(self, workload: str):
        self.dir = os.path.join(OUT_DIR, workload)
        self._slice = None
        self.started = False
        self.stopped = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no per-Python-call events
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = True
        self._slice = jax.profiler.TraceAnnotation("bench/trace_slice")
        self._slice.__enter__()
        with jax.profiler.TraceAnnotation(
                "bench/clock_sync", monotonic_ns=time.monotonic_ns()):
            pass

    def stop(self) -> None:
        """Close the slice; nothing to do if it never started or is
        already closed (a window can end before the slice does)."""
        import jax

        if not self.started or self.stopped:
            return
        self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stopped = True

    @property
    def xplane(self) -> Optional[str]:
        if not self.stopped:
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


# ---------------------------------------------------------------------------
# the device block
# ---------------------------------------------------------------------------
def _temp_bytes(executors) -> int:
    """Largest temporary arena of any executable these executors hold
    (per device, from XLA's ``memory_analysis``)."""
    worst = 0
    for exe in executors:
        for compiled in exe._cache.values():
            stats = compiled.aot.memory_analysis()
            worst = max(worst, int(getattr(stats, "temp_size_in_bytes", 0)))
    return worst


def live_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip (live buffers only)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_block(devices, executors, live: Optional[int] = None) -> dict:
    """The device as JAX reports it. ``memory_peak_bytes``: on this
    runtime ``peak_bytes_in_use`` counts live buffers only — it did not
    move when a step with 4.9 GB of XLA temporaries ran (my chip run,
    PR 22) — so the peak is the fullest chip's live peak plus the largest
    temporary arena of the executables the cell ran, which XLA allocates
    beside them for the length of a run. ``live``: the live peak where the
    driver read it before its correctness check put the reference's
    buffers on the chip."""
    import jax

    if live is None:
        live = live_peak_bytes(devices)
    temp = _temp_bytes(executors)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": live + temp,
            "memory_live_peak_bytes": live,
            "memory_temp_bytes": temp}


# ---------------------------------------------------------------------------
# one run of one cell
# ---------------------------------------------------------------------------
def read_layer_metrics(cell: Cell, trace, spans, counters) -> Dict[str, dict]:
    """Every per-layer metric of the cell through its own reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for metric in cell.per_layer:
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{metric['name']}")
        value = reader.read(trace, spans, counters, cell)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             devices, t0: float) -> dict:
    """Run the cell once on ``devices`` and return the contract line as a
    dict. ``t0``: ``time.monotonic()`` at process start, where set-up is
    counted from. Earlier lines (notes, the untraced numbers of a traced
    run) go to stdout on the way."""
    from benchmark import trace_reduce

    cell.peaks = peaks_for(cell, devices[0].device_kind)
    measured: Measured = cell.driver.run(cell, seed, seconds, traced,
                                         devices, t0)
    unit = {m["name"]: m["unit"] for m in cell.end_to_end}
    e2e = {SETUP: measured.setup_s, **measured.end_to_end}
    missing = sorted(set(unit) - set(e2e))
    if missing:
        raise RuntimeError(f"{cell.name}: the driver reported no {missing}")
    e2e = {k: {"value": float(v), "unit": unit[k]}
           for k, v in e2e.items() if k in unit}
    device = device_block(devices, measured.executors,
                          measured.live_peak_bytes)
    print(json.dumps({"cell": cell.name, "seed": seed, "seconds": seconds,
                      "traced": traced, "end_to_end": e2e,
                      "notes": measured.notes,
                      "memory": {k: device.pop(k) for k in
                                 ("memory_live_peak_bytes",
                                  "memory_temp_bytes")}}), flush=True)
    line = {"correct": bool(measured.correct),
            "attempted": int(measured.attempted),
            "failed": int(measured.failed), "metrics": e2e,
            "device": device}
    if traced:
        if measured.xplane is None:
            raise RuntimeError(f"{cell.name}: the traced run wrote no "
                               "xplane file")
        trace = trace_reduce.load(measured.xplane)
        line["metrics"] = read_layer_metrics(cell, trace, measured.spans,
                                             measured.counters)
        device["busy_s"] = trace.mean_busy_s
        device["window_s"] = trace.window_s
        if trace.monotonic_offset is not None:
            extra = [(s["name"], s["start"] - trace.monotonic_offset,
                      s["end"] - trace.monotonic_offset)
                     for s in measured.spans
                     if not s["name"].startswith("bench/")]
        else:
            extra = []
        line["breakdown"] = trace.breakdown(extra_host_spans=extra)
    if measured.checks:
        line["checks"] = measured.checks    # the line's LAST key
        for name, c in measured.checks.items():
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr, flush=True)
    return line
