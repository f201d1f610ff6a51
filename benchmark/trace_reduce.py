"""xplane -> numbers: the benchmark's own reduction of a profiler trace.

Kept with the yardstick so every PR computes the same number the same
way. Two layers:

* pure functions over plain event lists (``busy_union``, ``idle_gaps``,
  ``per_op_sums``, ``exposed_seconds``, ``attribute_gaps``) — checked on a
  hand-built list in ``tests/test_trace_reduce.py``;
* ``load(path)`` — reads one ``.xplane.pb`` with nothing but JAX
  (``jax.profiler.ProfileData``) into a :class:`Trace` those functions
  are applied to; checked on a small recorded trace.

Times are seconds on the profiler's clock unless a name ends in ``_ns``.
What a TPU trace looks like (TPU v5 lite, jax 0.9.0, looked at by hand in
PR 22): one plane ``/device:TPU:<i>`` per chip with the lines ``XLA Ops``
(one event per executed HLO op, named by the op's whole HLO text,
``%fusion.3 = bf16[..]{layout} fusion(...)``; a ``while`` is an event that
spans its body's events; an async pair shows as two slivers,
``*-start`` and ``*-done``), ``Async XLA Ops`` (one event per async pair,
from start to done), ``XLA Modules`` (one event per executable run) and
``Steps``. Host threads live in ``/host:CPU``, one line per thread; the
``python`` lines carry every ``jax.profiler.TraceAnnotation`` with its
keyword arguments as stats. A Mosaic (Pallas) kernel is a ``custom-call``
with ``custom_call_target="tpu_custom_call"`` named after the jaxpr
region it sits in (``checkpoint.21``, ``closed_call.12``): the kernel's
own name is NOT in the trace. Device events sit about 1.5 ms before the
host events that launched them (the two clocks are not aligned closer).
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start, end)
Event = Tuple[str, float, float]        # (name, start, end)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ASYNC_LINE = "Async XLA Ops"
#: opcodes that only wrap other ops' events: counting them would count
#: their bodies twice in the per-op sums (the busy union is unaffected)
CONTAINER_OPCODES = frozenset({"while", "conditional", "call"})
COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")
MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%(\S+) = (?:\([^()]*\)|\S+) ([\w\-]+)\(")
CLOCK_SYNC = "bench/clock_sync"
#: the annotation the harness holds open from just after ``start_trace``
#: to just before ``stop_trace``: the traced slice
TRACE_SLICE = "bench/trace_slice"


# ---------------------------------------------------------------------------
# pure reductions
# ---------------------------------------------------------------------------
def busy_union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    merged: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def idle_gaps(intervals: Iterable[Interval],
              window: Interval) -> List[Interval]:
    """The parts of ``window`` no interval covers, in time order."""
    gaps, cursor = [], window[0]
    for start, end in busy_union(clip(intervals, window)):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if window[1] > cursor:
        gaps.append((cursor, window[1]))
    return gaps


def strip_layouts(hlo_text: str) -> str:
    """``bf16[8,128]{1,0:T(8,128)(2,1)}`` -> ``bf16[8,128]``."""
    return _LAYOUT.sub("", hlo_text)


@functools.lru_cache(maxsize=65536)     # a step's ops repeat every step
def parse_op(hlo_text: str) -> Tuple[str, str]:
    """(op name, opcode) of a device event's HLO text, e.g.
    ``("fusion.3", "fusion")``; a text that does not parse (another
    runtime's naming) is its own name with the opcode ``""``."""
    m = _HLO.match(strip_layouts(hlo_text))
    return (m.group(1), m.group(2)) if m else (hlo_text, "")


def is_container(hlo_text: str) -> bool:
    return parse_op(hlo_text)[1] in CONTAINER_OPCODES


def is_collective(hlo_text: str) -> bool:
    name, opcode = parse_op(hlo_text)
    return bool(COLLECTIVE.search(opcode) or COLLECTIVE.search(name))


def per_op_sums(events: Iterable[Event]) -> Dict[str, float]:
    """Seconds per distinct event text; container ops (``while`` ...)
    left out."""
    sums: Dict[str, float] = {}
    for text, start, end in events:
        if is_container(text):
            continue
        sums[text] = sums.get(text, 0.0) + (end - start)
    return sums


def exposed_seconds(marked: Iterable[Interval],
                    others: Iterable[Interval]) -> float:
    """Seconds of ``marked`` (say, collectives) during which none of
    ``others`` (every other op on that device) runs."""
    exposed = busy_union(marked)
    cover = busy_union(others)
    out = 0.0
    for start, end in exposed:
        out += (end - start) - total(clip(cover, (start, end)))
    return out


def attribute_gaps(gaps: Sequence[Interval], host_spans: Iterable[Event],
                   top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps, each named after the host span that
    covers most of it — of two that cover it alike, the shorter, which
    says more — or ``unattributed`` when none overlaps; as
    ``[name, seconds]``, longest first."""
    spans = list(host_spans)
    out = []
    for start, end in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, best_key = "unattributed", (0.0, 0.0)
        for name, s0, s1 in spans:
            cover = min(end, s1) - max(start, s0)
            key = (round(cover, 9), -(s1 - s0))
            if cover > 0 and key > best_key:
                best, best_key = name, key
        out.append((best, end - start))
    return out


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, q in [0, 100]; None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


# ---------------------------------------------------------------------------
# one recorded trace
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Trace:
    """What the readers get. ``device_ops[i]`` are chip i's ``XLA Ops``
    events, ``host`` every host-thread event (TraceAnnotations included),
    all in seconds on the profiler's clock. ``window`` is the traced
    slice: the ``bench/trace_slice`` annotation where the trace has one,
    else first to last device event over all chips. ``monotonic_offset`` maps the profiler's
    clock onto ``time.monotonic()`` (monotonic = profiler + offset), from
    the ``bench/clock_sync`` marker; None without one."""
    device_ops: Dict[int, List[Event]]
    device_async: Dict[int, List[Event]]
    device_modules: Dict[int, List[Event]]
    host: List[Event]
    window: Interval
    monotonic_offset: Optional[float] = None

    # -- device ---------------------------------------------------------
    def op_intervals(self, chip: int) -> List[Interval]:
        return [(s, e) for _, s, e in self.device_ops.get(chip, ())]

    def busy_s(self, chip: int) -> float:
        return total(busy_union(clip(self.op_intervals(chip), self.window)))

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def mean_busy_s(self) -> float:
        chips = sorted(self.device_ops)
        return sum(self.busy_s(c) for c in chips) / max(len(chips), 1)

    def op_seconds(self, chip: int = 0) -> Dict[str, float]:
        return per_op_sums(self.device_ops.get(chip, ()))

    def collective_seconds(self, chip: int = 0) -> Tuple[float, float]:
        """(in flight, exposed) seconds of chip's collectives inside the
        window. In flight: the union of the collective ops' events and of
        their async start-to-done spans. Exposed: the part of that during
        which no other op runs on the chip."""
        ops = [ev for ev in self.device_ops.get(chip, ())
               if not is_container(ev[0])]
        coll = [(s, e) for n, s, e in ops if is_collective(n)]
        coll += [(s, e) for n, s, e in self.device_async.get(chip, ())
                 if is_collective(n)]
        coll = clip(coll, self.window)
        rest = [(s, e) for n, s, e in ops if not is_collective(n)]
        return total(busy_union(coll)), exposed_seconds(coll, rest)

    def mosaic_calls(self, chip: int = 0) -> List[Event]:
        return [ev for ev in self.device_ops.get(chip, ())
                if MOSAIC_CALL in ev[0]]

    # -- host -----------------------------------------------------------
    def bench_spans(self) -> List[Event]:
        return [ev for ev in self.host if ev[0].startswith("bench/")
                and ev[0] not in (CLOCK_SYNC, TRACE_SLICE)]

    # -- the breakdown the result line carries ---------------------------
    def breakdown(self, extra_host_spans: Iterable[Event] = (),
                  chip: int = 0, top: int = 10, top_gaps: int = 5) -> dict:
        ops = sorted(self.op_seconds(chip).items(), key=lambda kv: -kv[1])
        gaps = idle_gaps(self.op_intervals(chip), self.window)
        spans = self.bench_spans() + list(extra_host_spans)
        return {
            "device_ops": [[strip_layouts(text)[:160], sec]
                           for text, sec in ops[:top]],
            "idle_gaps": [[name, sec] for name, sec in
                          attribute_gaps(gaps, spans, top=top_gaps)],
        }


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``. Raises if no device plane holds an op:
    a traced run in which nothing ran on the device is no measurement."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    lines = {OPS_LINE: {}, ASYNC_LINE: {}, MODULES_LINE: {}}
    host: List[Event] = []
    offset = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name][chip] = [
                        (ev.name, ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    host.append((ev.name, start,
                                 start + ev.duration_ns * 1e-9))
                    if ev.name == CLOCK_SYNC and offset is None:
                        mono = _stat(ev, "monotonic_ns")
                        if mono is not None:
                            offset = int(mono) * 1e-9 - start
    device_ops = lines[OPS_LINE]
    starts = [s for evs in device_ops.values() for _, s, _ in evs]
    ends = [e for evs in device_ops.values() for _, _, e in evs]
    if not starts:
        raise ValueError(f"{path}: no op ran on a device in the traced "
                         "slice")
    window = next(((s, e) for n, s, e in host if n == TRACE_SLICE),
                  (min(starts), max(ends)))
    return Trace(device_ops, lines[ASYNC_LINE], lines[MODULES_LINE], host,
                 window, offset)
