"""The reduction from a profiler trace to numbers, on a hand-built event
list and on a small trace recorded on the chip (TPU v5 lite, jax 0.9.0,
PR 22: three runs of a four-matmul program under ``bench/step``
annotations, 25 KB). Run by hand: ``pytest benchmark/tests``."""
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")


# ---------------------------------------------------------------------------
# hand-built
# ---------------------------------------------------------------------------
def test_busy_union_merges_overlap_and_touching():
    assert tr.busy_union([(5, 6), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 6)]
    assert tr.total(tr.busy_union([(0, 2), (1, 3)])) == 3


def test_idle_gaps_inside_a_window():
    busy = [(1, 2), (4, 6), (5, 7)]
    assert tr.idle_gaps(busy, (0, 10)) == [(0, 1), (2, 4), (7, 10)]
    # events outside the window are clipped, not counted
    assert tr.idle_gaps([(-5, 1), (9, 20)], (0, 10)) == [(1, 9)]
    assert tr.idle_gaps([], (0, 3)) == [(0, 3)]


def test_per_op_sums_leave_containers_out():
    body = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    loop = ("%while.2 = (s32[]{:T(128)}, f32[8]{0:T(8,128)}) "
            "while((s32[], f32[8]) %tuple), body=%b")
    events = [(loop, 0.0, 10.0), (body, 1.0, 2.0), (body, 3.0, 5.0)]
    assert tr.per_op_sums(events) == {body: 3.0}
    assert tr.parse_op(loop) == ("while.2", "while")
    assert tr.parse_op(body) == ("fusion.1", "fusion")
    assert tr.parse_op("not hlo") == ("not hlo", "")


def test_exposed_collective_time():
    # all-reduce in flight 0..10; compute covers 2..5 and 7..8
    assert tr.exposed_seconds([(0, 10)], [(2, 5), (7, 8), (20, 30)]) == 6
    # two collectives that overlap count once
    assert tr.exposed_seconds([(0, 4), (2, 6)], [(5, 6)]) == 5
    assert tr.is_collective(
        "%all-reduce-start.3 = f32[4]{0} all-reduce-start(f32[4]{0} %g)")
    assert tr.is_collective(
        "%fusion.9 = f32[4]{0} fusion(f32[4]{0} %g), calls=%all-reduce.1") \
        is False
    assert tr.is_collective("%add.1 = f32[4]{0} add(f32[4]{0} %a)") is False


def test_trace_collectives_use_sync_and_async_lines():
    ar_start = "%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %g)"
    ar_done = "%all-reduce-done.1 = f32[4]{0} all-reduce-done(f32[4]{0} %s)"
    mm = "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %x), kind=kOutput"
    t = tr.Trace(
        device_ops={0: [(ar_start, 0.0, 0.1), (mm, 0.1, 4.0),
                        (ar_done, 4.0, 6.0)]},
        device_async={0: [(ar_start, 0.0, 6.0)]},
        device_modules={}, host=[], window=(0.0, 8.0))
    in_flight, exposed = t.collective_seconds(chip=0)
    assert in_flight == pytest.approx(6.0)
    assert exposed == pytest.approx(2.1)      # the start sliver + the wait
    assert t.busy_s(0) == pytest.approx(6.0) and t.window_s == 8.0


def test_gap_attribution_prefers_the_most_specific_covering_span():
    gaps = [(0.0, 1.0), (5.0, 8.0), (10.0, 10.5)]
    spans = [("bench/train", 0.0, 9.0), ("bench/reader_next", 4.5, 8.5),
             ("bench/submit", 0.2, 0.4)]
    assert tr.attribute_gaps(gaps, spans, top=3) == [
        ("bench/reader_next", 3.0),    # both cover it; the shorter wins
        ("bench/train", 1.0),          # covers all of it; submit only 0.2
        ("unattributed", 0.5)]
    assert tr.attribute_gaps(gaps, spans, top=1) == [
        ("bench/reader_next", 3.0)]


def test_percentile():
    assert tr.percentile([], 95) is None
    assert tr.percentile([3.0], 95) == 3.0
    assert tr.percentile([0, 10], 50) == 5
    assert tr.percentile(list(range(101)), 95) == 95


# ---------------------------------------------------------------------------
# recorded on the chip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    return tr.load(RECORDED)


def test_recorded_planes_and_window(recorded):
    assert sorted(recorded.device_ops) == [0]
    assert len(recorded.device_ops[0]) == 15        # 3 runs x 5 ops
    assert len(recorded.device_modules[0]) == 3
    # no bench/trace_slice in this recording: first to last device event
    assert recorded.window == pytest.approx((0.044697106, 0.066563187))
    # 3 runs of ~49 us in a 21.9 ms slice
    assert recorded.busy_s(0) == pytest.approx(147.6e-6, rel=1e-2)
    assert recorded.mean_busy_s == recorded.busy_s(0)
    assert 100 * (1 - recorded.mean_busy_s / recorded.window_s) > 99


def test_recorded_annotations_and_clock(recorded):
    steps = recorded.bench_spans()
    assert [n for n, _, _ in steps] == ["bench/step"] * 3
    # the clock_sync marker carried time.monotonic_ns()
    assert recorded.monotonic_offset == pytest.approx(27.2418, abs=1e-3)


def test_recorded_breakdown(recorded):
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 5 and len(b["idle_gaps"]) <= 5
    name, seconds = b["device_ops"][0]
    assert name.startswith("%fusion = bf16[] fusion(") and "{" not in name
    assert seconds == pytest.approx(68.3e-6, rel=1e-2)
    # the two long gaps lie between runs: the host was in bench/step (the
    # next run's dispatch) for the part a span covers at all
    assert [g[0] for g in b["idle_gaps"][:2]] == ["bench/step"] * 2
    assert b["idle_gaps"][0][1] == pytest.approx(10.9e-3, rel=1e-2)
    assert recorded.mosaic_calls() == []
    assert recorded.collective_seconds() == (0, 0.0)


def test_a_trace_with_no_device_op_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(4).block_until_ready()           # on the CPU: no TPU plane
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    with pytest.raises(ValueError, match="no op ran on a device"):
        tr.load(str(path))
