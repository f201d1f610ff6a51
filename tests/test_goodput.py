"""Training observatory (ISSUE 18): goodput/badput accounting, live MFU
gauges, and straggler detection across the elastic plane.

Acceptance pins:
- taxonomy completeness: per-bucket seconds sum to >=99% of the measured
  pass wall on BOTH loop paths, and a run with forced fresh compiles +
  a synchronous checkpoint + an injected transient retry attributes
  nonzero seconds to exactly those buckets;
- straggler pin: 3 concurrent StreamingTrainers on one master, one
  throttled — the master flags it within the run (labeled
  ``trainer_step_seconds``/``trainer_straggler`` series + trace record)
  while the throttle leaves training bitwise-unchanged;
- runlog regression: ``examples_per_sec`` is resolve-ordered under
  ``async_depth>1`` (the dispatch-anchored wall measured only the
  resolve block and OVERSTATED throughput).

Tier-1 budget: module-level shared trainer builders, tiny models; the
async completeness variant and the solo-throttle bitwise leg are
``@pytest.mark.slow``.
"""
import io
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import dataset, event as evt, layers, profiler, trace
from paddle_tpu.master import Master, MasterClient, MasterServer
from paddle_tpu.online import StreamingTrainer
from paddle_tpu.resilience import CheckpointConfig, FaultPlan
from paddle_tpu.serving.metrics import MetricsRegistry
from paddle_tpu.trace import BUCKETS, GoodputMeter, RunLog
from paddle_tpu.trace.flight import get_recorder
from paddle_tpu.trace.slo import SLO, SLOTracker
from paddle_tpu.trainer import SGD

VOCAB, SLOTS, DD = 128, dataset.ctr.SLOTS, dataset.ctr.DENSE_DIM


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------
def _build_fc(dim=16, seed=3):
    main, startup = pt.Program(), pt.Program()
    startup.random_seed = seed
    with pt.program_guard(main, startup):
        x = layers.data("x", shape=[dim])
        y = layers.data("y", shape=[1], dtype="int64")
        h = layers.fc(x, size=dim, act="relu")
        logits = layers.fc(h, size=3)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        sgd = SGD(cost=loss,
                  optimizer=pt.optimizer.SGDOptimizer(learning_rate=0.1),
                  feed_list=[x, y], place=pt.CPUPlace(), scope=pt.Scope())
    return sgd


def _rows(n, dim=16, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.rand(batch, dim).astype("float32")
    ys = rng.randint(0, 3, size=(batch, 1)).astype("int64")
    rows = [(xs[i], ys[i]) for i in range(batch)]

    def reader():
        for _ in range(n):
            yield rows
    return reader


def _build_ctr(seed=7):
    """Order-seeded CTR bundle (the test_elastic builder): identically
    built bundles initialize bit-identically."""
    main, startup = pt.Program(), pt.Program()
    startup.random_seed = seed
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[SLOTS], dtype="int64")
        dense = layers.data("dense", shape=[DD])
        label = layers.data("label", shape=[1])
        logit = pt.models.wide_deep(ids, dense, vocab_size=VOCAB,
                                    embed_dim=4, hidden_sizes=(8,))
        loss, _ = pt.models.wide_deep_loss(logit, label)
        sgd = SGD(loss, pt.optimizer.SGDOptimizer(learning_rate=0.05),
                  [ids, dense, label], scope=pt.Scope())
    return sgd


def _okeys(scope):
    import re

    def key(name):
        m = re.search(r"_(\d+)$", name)
        return (0, int(m.group(1))) if m else (1, name)
    return sorted(scope.keys(), key=key)


def _assert_scopes_bitwise(a, b):
    ka, kb = _okeys(a), _okeys(b)
    assert len(ka) == len(kb)
    for na, nb in zip(ka, kb):
        np.testing.assert_array_equal(np.asarray(a.get(na)),
                                      np.asarray(b.get(nb)),
                                      err_msg=f"{na} vs {nb}")


# ---------------------------------------------------------------------------
# GoodputMeter unit surface
# ---------------------------------------------------------------------------
class TestGoodputMeter:
    def test_account_measure_move_and_totals(self):
        m = GoodputMeter()
        m.account("device_compute", 0.3)
        m.account("data_wait", 0.1)
        with m.measure("checkpoint_stall"):
            time.sleep(0.002)
        m.move("device_compute", "fresh_compile", 0.1)
        snap = m.snapshot()
        assert snap["buckets"]["device_compute"] == pytest.approx(0.2)
        assert snap["buckets"]["fresh_compile"] == pytest.approx(0.1)
        assert snap["buckets"]["checkpoint_stall"] >= 0.002
        # buckets and total are rounded to 6dp independently: the sum
        # of rounded buckets can drift a few microseconds off the total
        assert snap["total_s"] == pytest.approx(
            sum(snap["buckets"].values()), abs=1e-5)
        assert m.goodput_fraction() == pytest.approx(
            0.2 / snap["total_s"], rel=1e-3)
        with pytest.raises(KeyError):
            m.account("not_a_bucket", 1.0)

    def test_mfu_from_priced_flops(self):
        m = GoodputMeter(peak_flops=1e9)
        assert m.note_step(0.1) is None       # unpriced -> no MFU
        m.set_program_flops(5e7)
        mfu = m.note_step(0.1)                # 5e8 flops/s vs 1e9 peak
        assert mfu == pytest.approx(0.5)
        assert m.mfu_ema == pytest.approx(0.5)
        m.note_step(0.05)                     # 1e9/s -> mfu 1.0
        assert m.mfu == pytest.approx(1.0)
        assert 0.5 < m.mfu_ema < 1.0          # EMA trails
        assert m.steps == 3                   # every step counts, MFU
        #                                       only once priced

    def test_no_mfu_on_a_device_without_a_published_peak(self):
        """The meter resolves its peak from the RUNNING device's
        ``device_kind`` through ``costmodel.DEVICE_PEAKS``. The CPU mesh
        is not in the table, so a priced program still yields no MFU —
        nothing divides a CPU step by the v5e peak."""
        import jax

        from paddle_tpu.analysis import costmodel

        assert jax.devices()[0].device_kind not in costmodel.DEVICE_PEAKS
        m = GoodputMeter()
        assert m.peak_flops is None
        m.set_program_flops(5e7)
        assert m.note_step(0.1) is None
        assert m.mfu is None and m.mfu_ema is None
        reg = MetricsRegistry()
        m.account("device_compute", 0.1)
        m.publish(reg)
        gauges = reg.snapshot()["gauges"]
        assert "mfu" not in gauges and "mfu_ema" not in gauges
        assert m.telemetry()["mfu"] is None
        # the table itself: the chip this repo measures on is known,
        # anything else is a located error, never a default
        assert costmodel.device_peaks("TPU v5 lite") == (197e12, 819e9)
        with pytest.raises(KeyError, match="no published peak"):
            costmodel.device_peaks("TPU v9 imaginary")

    def test_publish_prometheus_series_and_ratio_counters(self):
        reg = MetricsRegistry()
        m = GoodputMeter()
        m.account("device_compute", 0.9)
        m.account("data_wait", 0.1)
        m.publish(reg, job="train")
        snap = reg.snapshot()
        assert snap["counters"]["goodput_good_ms_total"] == 900
        assert snap["counters"]["goodput_total_ms_total"] == 1000
        assert snap["gauges"]["goodput_fraction"] == pytest.approx(0.9)
        text = reg.prometheus_text()
        assert 'bucket="device_compute"' in text
        assert 'job="train"' in text
        # counters are cumulative + monotonic across publishes
        m.account("device_compute", 0.5)
        m.publish(reg, job="train")
        snap2 = reg.snapshot()
        assert snap2["counters"]["goodput_good_ms_total"] == 1400
        assert snap2["counters"]["goodput_total_ms_total"] == 1500

    def test_telemetry_payload(self):
        m = GoodputMeter(peak_flops=1e9)
        m.set_program_flops(1e8)
        m.account("device_compute", 1.0)
        m.note_step(0.2)
        t = m.telemetry(last_step_wall_s=0.25)
        assert t["step_wall_s"] == pytest.approx(0.25)
        assert t["steps"] == 1
        assert t["goodput"] == pytest.approx(1.0)
        assert t["mfu"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# taxonomy completeness (ACCEPTANCE)
# ---------------------------------------------------------------------------
class TestTaxonomyCompleteness:
    def _measured_pass_wall(self, trainer, reader, **kw):
        """Train with a wall clock pinned to the pass window (first
        BeginPass -> last EndPass): the decomposition's denominator."""
        t = {"t0": None, "t1": None}

        def handler(e):
            if isinstance(e, evt.BeginPass) and t["t0"] is None:
                t["t0"] = time.perf_counter()
            elif isinstance(e, evt.EndPass):
                t["t1"] = time.perf_counter()

        trainer.train(reader, event_handler=handler, **kw)
        return t["t1"] - t["t0"]

    def test_sync_buckets_sum_to_99pct_of_wall(self):
        tr = _build_fc()
        tr.train(_rows(2), num_passes=1,
                 event_handler=lambda e: None)  # warm compile/init
        wall = self._measured_pass_wall(tr, _rows(40), num_passes=2)
        snap = tr.goodput.snapshot()
        assert snap["steps"] == 80
        covered = snap["total_s"] / wall
        assert covered >= 0.99, (covered, snap)
        # and nothing is double counted either
        assert covered <= 1.02, (covered, snap)
        # every second lands in a named bucket (sum == total, modulo
        # the independent 6dp rounding of each bucket)
        assert snap["total_s"] == pytest.approx(
            sum(snap["buckets"].values()), abs=1e-5)
        assert set(snap["buckets"]) == set(BUCKETS)

    @pytest.mark.slow  # same contract as the sync pin, async loop
    def test_async_buckets_sum_to_99pct_of_wall(self):
        tr = _build_fc(seed=5)
        tr.train(_rows(2), num_passes=1, async_depth=3,
                 event_handler=lambda e: None)
        wall = self._measured_pass_wall(tr, _rows(40), num_passes=2,
                                        async_depth=3)
        snap = tr.goodput.snapshot()
        covered = snap["total_s"] / wall
        assert covered >= 0.99, (covered, snap)
        assert covered <= 1.02, (covered, snap)

    def test_badput_lands_in_named_buckets(self, tmp_path):
        """Forced fresh compiles (a mid-pass batch-shape change), a
        synchronous checkpoint, and an injected transient executor
        retry each attribute NONZERO seconds to exactly their bucket."""
        tr = _build_fc(seed=9)
        rng = np.random.RandomState(1)

        def reader():  # batch sizes 8 and 12 -> two compiled shapes
            for i in range(8):
                b = 8 if i % 2 == 0 else 12
                xs = rng.rand(b, 16).astype("float32")
                ys = rng.randint(0, 3, size=(b, 1)).astype("int64")
                yield [(xs[j], ys[j]) for j in range(b)]

        ck = CheckpointConfig(str(tmp_path / "ck"), every_n_steps=2,
                              background=False,
                              install_signal_handlers=False)
        with FaultPlan().at(step=3, kind="executor_error").active() \
                as plan:
            tr.train(reader, num_passes=1, checkpoint=ck,
                     event_handler=lambda e: None)
            assert ("executor_error", 3) in plan.fired_log
        b = tr.goodput.snapshot()["buckets"]
        assert b["fresh_compile"] > 0, b
        assert b["checkpoint_stall"] > 0, b
        # the step retry backs off 10ms before retrying -> visible
        assert b["recovery_rollback"] >= 0.005, b
        assert b["device_compute"] > 0 and b["data_wait"] > 0, b

    def test_goodput_false_disables_accounting(self):
        tr = _build_fc(seed=11)
        tr.train(_rows(2), num_passes=1, goodput=False,
                 event_handler=lambda e: None)
        assert tr.goodput is None

    def test_shared_meter_accumulates_across_calls(self):
        tr = _build_fc(seed=13)
        m = GoodputMeter()
        tr.train(_rows(2), num_passes=1, goodput=m,
                 event_handler=lambda e: None)
        s1 = m.total_seconds()
        tr.train(_rows(2), num_passes=1, goodput=m,
                 event_handler=lambda e: None)
        assert m.total_seconds() > s1
        assert tr.goodput is m


# ---------------------------------------------------------------------------
# runlog regression: resolve-ordered walls (ACCEPTANCE satellite)
# ---------------------------------------------------------------------------
class _FakeClock:
    """Stand-in for the ``time`` module inside runlog: a settable
    perf_counter plus a real time() for the header."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def time(self):
        return 0.0


class TestRunLogResolveOrdered:
    def _drive(self, clock, rl, script):
        for t, e in script:
            clock.now = t
            rl(e)

    def test_async_reordered_walls_and_throughput(self, monkeypatch):
        """Under ``async_depth>1`` BeginIteration k+1 fires BEFORE
        EndIteration k resolves. The journal wall must be the interval
        between consecutive RESOLVES (0.5s here, 32 ex/s), not the
        dispatch-anchored remainder (0.4s, 40 ex/s — the old
        overstatement)."""
        from paddle_tpu.trace import runlog as runlog_mod

        clock = _FakeClock()
        monkeypatch.setattr(runlog_mod, "time", clock)
        sink = io.StringIO()
        rl = RunLog(sink)
        e0 = evt.EndIteration(0, 0, 1.0, batch_size=16,
                              host_wall_s=0.1, device_wall_s=0.4,
                              mfu=0.5)
        e1 = evt.EndIteration(0, 1, 1.0, batch_size=16,
                              host_wall_s=0.1, device_wall_s=0.4,
                              mfu=0.7)
        self._drive(clock, rl, [
            (100.0, evt.BeginPass(0)),
            (100.0, evt.BeginIteration(0, 0)),   # dispatch 0
            (100.1, evt.BeginIteration(0, 1)),   # dispatch 1 (pipelined)
            (100.5, e0),                         # resolve 0
            (101.0, e1),                         # resolve 1
            (101.0, evt.EndPass(0)),
        ])
        rows = [json.loads(line) for line in
                sink.getvalue().splitlines()]
        iters = [r for r in rows if r["type"] == "iteration"]
        assert iters[0]["wall_ms"] == pytest.approx(500.0)
        assert iters[0]["examples_per_sec"] == pytest.approx(32.0)
        # the regression: dispatch-anchored accounting yielded 400ms/40
        assert iters[1]["wall_ms"] == pytest.approx(500.0)
        assert iters[1]["examples_per_sec"] == pytest.approx(32.0)
        # goodput split + live MFU ride the same rows
        for it in iters:
            assert it["host_wall_ms"] == pytest.approx(100.0)
            assert it["device_wall_ms"] == pytest.approx(400.0)
        assert iters[0]["mfu"] == pytest.approx(0.5)
        assert iters[1]["mfu_ema"] == pytest.approx(
            0.1 * 0.7 + 0.9 * 0.5)

    def test_sync_walls_identical_to_dispatch_anchored(self, monkeypatch):
        """Synchronous runs resolve in dispatch order, so the
        resolve-ordered wall equals the old per-iteration wall."""
        from paddle_tpu.trace import runlog as runlog_mod

        clock = _FakeClock()
        monkeypatch.setattr(runlog_mod, "time", clock)
        sink = io.StringIO()
        rl = RunLog(sink)
        self._drive(clock, rl, [
            (100.0, evt.BeginPass(0)),
            (100.0, evt.BeginIteration(0, 0)),
            (100.2, evt.EndIteration(0, 0, 1.0, batch_size=8)),
            (100.2, evt.BeginIteration(0, 1)),
            (100.5, evt.EndIteration(0, 1, 1.0, batch_size=8)),
        ])
        iters = [json.loads(line) for line in
                 sink.getvalue().splitlines()
                 if json.loads(line)["type"] == "iteration"]
        assert iters[0]["wall_ms"] == pytest.approx(200.0)
        assert iters[1]["wall_ms"] == pytest.approx(300.0)


# ---------------------------------------------------------------------------
# goodput SLO objective (ratio kind over cumulative counters)
# ---------------------------------------------------------------------------
class TestGoodputSLO:
    def test_ratio_objective_burns_on_badput(self):
        slo = SLO(goodput=0.9, target=0.9, windows_s=(10.0, 30.0),
                  burn_thresholds=(2.0, 1.5))
        clock = {"t": 0.0}
        tracker = SLOTracker(slo, clock=lambda: clock["t"])

        def snap(good_ms, total_ms):
            return {"counters": {"goodput_good_ms_total": good_ms,
                                 "goodput_total_ms_total": total_ms}}

        # healthy: 95% goodput
        for i in range(1, 5):
            clock["t"] = i * 5.0
            tracker.sample(snap(950 * i, 1000 * i))
        st = tracker.status()
        assert st["objectives"]["goodput"]["attainment"] \
            == pytest.approx(0.95)
        assert not st["alerting"]
        # collapse: the next windows are pure badput
        for i in range(5, 9):
            clock["t"] = i * 5.0
            tracker.sample(snap(3800, 1000 * i))
        st = tracker.status()
        obj = st["objectives"]["goodput"]
        assert obj["attainment"] < 0.9
        assert all(w["burn_rate"] > 1.5 for w in obj["burn"].values())
        assert obj["alerting"] and st["alerting"]

    def test_objectives_and_to_dict_carry_goodput(self):
        slo = SLO(goodput=0.85)
        obj = slo.objectives()["goodput"]
        assert obj == {"kind": "ratio", "good": "goodput_good_ms_total",
                       "total": "goodput_total_ms_total", "target": 0.85}
        assert slo.to_dict()["goodput"] == 0.85


# ---------------------------------------------------------------------------
# flight recorder covers training (satellite)
# ---------------------------------------------------------------------------
class TestTrainingFlightRecorder:
    def test_trainer_source_registered_and_dumped_on_error(self):
        tr = _build_fc(seed=17)
        tr.train(_rows(3), num_passes=1, event_handler=lambda e: None)
        rec = get_recorder()
        doc = rec.bundle("probe")
        states = [v for k, v in doc["state"].items()
                  if k.startswith("trainer#")]
        assert states, list(doc["state"])
        st = states[-1]
        assert st["position"]["pass_id"] == 0
        assert st["goodput"]["steps"] == 3
        assert len(st["recent_step_walls_s"]) == 3

        # an unhandled step-loop error auto-dumps (in-memory bundle;
        # files only land when $PADDLE_TPU_FLIGHT_DIR is set)
        rec._last_auto_dump = 0.0  # defeat the crash-loop throttle
        with FaultPlan().at(step=2, kind="crash").active():
            with pytest.raises(Exception):
                tr.train(_rows(3), num_passes=1,
                         event_handler=lambda e: None)
        assert rec.last_bundle["reason"] == "trainer_error"
        assert rec.last_bundle["error"] is not None


# ---------------------------------------------------------------------------
# straggler plane: master unit level
# ---------------------------------------------------------------------------
class TestStragglerMaster:
    def _master_with_telemetry(self, walls):
        m = Master(timeout_s=60)
        toks = {tid: m.register_trainer(tid, lease_s=30.0)
                for tid in walls}
        for _ in range(4):
            for tid, w in walls.items():
                m.heartbeat(toks[tid],
                            telemetry={"step_wall_s": w, "steps": 4,
                                       "goodput": 0.8, "mfu": 0.2})
        return m, toks

    def test_skew_detection_and_recovery(self):
        m, toks = self._master_with_telemetry(
            {"fast-a": 0.01, "fast-b": 0.012, "slow": 0.05})
        ts = m.train_status()
        assert ts["stragglers"] == ["slow"]
        assert ts["stragglers_detected_total"] == 1
        assert ts["trainers"]["slow"]["straggler"] is True
        assert ts["skew"] > 2.0
        # catches back up -> flag clears, detection counter does not
        for _ in range(32):
            m.heartbeat(toks["slow"],
                        telemetry={"step_wall_s": 0.011, "steps": 40})
        ts = m.train_status()
        assert ts["stragglers"] == []
        assert ts["stragglers_detected_total"] == 1

    def test_single_trainer_never_flagged(self):
        m, _ = self._master_with_telemetry({"only": 0.5})
        assert m.train_status()["stragglers"] == []

    def test_prometheus_labeled_trainer_series(self):
        m, _ = self._master_with_telemetry(
            {"fast-a": 0.01, "fast-b": 0.012, "slow": 0.05})
        text = m.prometheus_text()
        assert 'trainer_step_seconds{trainer="slow"} 0.05' in text
        assert 'trainer_straggler{trainer="slow"} 1' in text
        assert 'trainer_straggler{trainer="fast-a"} 0' in text
        assert 'trainer_goodput_fraction{trainer="fast-a"} 0.8' in text
        assert 'trainer_mfu{trainer="fast-a"} 0.2' in text
        assert "master_straggler 1" in text
        assert "master_stragglers_detected_total 1" in text

    def test_detection_emits_trace_record_and_stat(self):
        before = profiler.global_stat.as_dict(
            prefix="master/straggler_detected").get(
            "master/straggler_detected", {}).get("total_ms", 0)
        trace.enable(level=1)
        m, _ = self._master_with_telemetry(
            {"fast-a": 0.01, "fast-b": 0.012, "slow": 0.05})
        after = profiler.global_stat.as_dict(
            prefix="master/straggler_detected")[
            "master/straggler_detected"]["total_ms"]
        assert after >= before + 1
        recs = [s for s in trace.get_tracer().spans()
                if s.name == "master/straggler_detected"]
        assert recs and recs[-1].attrs["trainer"] == "slow"
        assert recs[-1].attrs["skew"] > 2.0


# ---------------------------------------------------------------------------
# the 3-trainer straggler pin (ACCEPTANCE)
# ---------------------------------------------------------------------------
def _slow_handler(delay_s):
    def handler(e):
        if isinstance(e, evt.EndIteration):
            time.sleep(delay_s)
    return handler


@pytest.mark.slow  # tier-1 budget (PR 20): the 3-trainer skew drill is
# the heaviest goodput case; the meter/decomposition/MFU contracts stay
# tier-1 via the other goodput tests
def test_straggler_pin_three_trainers(tmp_path):
    """ACCEPTANCE PIN: 3 StreamingTrainers heartbeat one master
    concurrently; one is throttled 6x per step. The master's skew check
    flags exactly the slow trainer DURING the run — exported as the
    labeled ``trainer_straggler`` gauge and a
    ``master/straggler_detected`` trace record — within the K
    heartbeats the run itself takes."""
    descs = dataset.ctr.task_descs(6, records_per_shard=32, vocab=VOCAB)
    srv = MasterServer(timeout_s=30, port=0)
    addr = srv.start()
    seen = {"stragglers": set(), "polls": 0}
    try:
        trainers = {}
        threads = []
        for tid, delay in (("fast-a", 0.0), ("fast-b", 0.0),
                           ("slow-c", 0.03)):
            b = _build_ctr()
            st = StreamingTrainer(
                b, addr, dataset.ctr.task_reader, task_descs=descs,
                batch_size=16,
                checkpoint=CheckpointConfig(
                    # one 2-step task per checkpoint: elastic acks are
                    # deferred until a generation covers them, so the
                    # cadence must divide the task length or the fleet
                    # parks on NO_TASK waiting for acks that never flush
                    str(tmp_path / f"ck_{tid}"), every_n_steps=2,
                    background=False),
                max_passes=1, trainer_id=tid,
                install_signal_handlers=False, telemetry_every_s=0.01)
            trainers[tid] = st
            handler = _slow_handler(delay) if delay else None
            th = threading.Thread(target=st.run,
                                  kwargs={"event_handler": handler})
            threads.append(th)
        for th in threads:
            th.start()
        # poll the detector while the fleet runs: detection must land
        # within the run's own heartbeats, not post-hoc.  Snapshot the
        # prometheus text AT detection time — once fast trainers leave
        # the fleet the 2-trainer nearest-rank median equals the slow
        # trainer's own mean and the gauge legitimately clears.
        flagged_text = ""
        flag_polls: dict = {}
        while any(th.is_alive() for th in threads):
            now = set(srv.master.train_status()["stragglers"])
            seen["stragglers"] |= now
            for tid in now:
                flag_polls[tid] = flag_polls.get(tid, 0) + 1
            if "slow-c" in now and not flagged_text:
                flagged_text = srv.master.prometheus_text()
            seen["polls"] += 1
            time.sleep(0.02)
        for th in threads:
            th.join()
        ts = srv.master.train_status()
        text = srv.master.prometheus_text()
    finally:
        srv.stop()

    # slow-c must be flagged, and dominantly so: threaded trainers on a
    # loaded CPU host can transiently spike a fast trainer over the skew
    # bar for a beat or two, but the throttled one stays flagged
    assert "slow-c" in seen["stragglers"], seen
    others = {t: n for t, n in flag_polls.items() if t != "slow-c"}
    assert all(flag_polls["slow-c"] > n for n in others.values()), \
        flag_polls
    assert ts["stragglers_detected_total"] >= 1
    assert 'trainer_straggler{trainer="slow-c"} 1' in flagged_text
    assert 'trainer_step_seconds{trainer="slow-c"}' in text
    # per-trainer digests carried goodput/MFU telemetry too
    assert ts["trainers"]["slow-c"]["goodput"] is not None
    # each trainer exits at ITS OWN pass boundary and the first
    # PASS_DONE recycles the queue for the rest of the fleet, so the
    # fleet drains the queue a whole number of times (up to one full
    # pass per trainer — how many exactly is a scheduling race)
    done = sum(st.tasks_finished for st in trainers.values())
    assert done >= len(descs) and done % len(descs) == 0, done


@pytest.mark.slow  # the bitwise half of the pin: throttling is pure
# wall time — a throttled run's math is unchanged
def test_throttled_run_bitwise_identical(tmp_path):
    descs = dataset.ctr.task_descs(3, records_per_shard=32, vocab=VOCAB)

    def solo(tag, handler):
        srv = MasterServer(timeout_s=30, port=0)
        addr = srv.start()
        b = _build_ctr()
        st = StreamingTrainer(
            b, addr, dataset.ctr.task_reader, task_descs=descs,
            batch_size=16,
            checkpoint=CheckpointConfig(str(tmp_path / tag),
                                        every_n_steps=2,
                                        background=False),
            max_passes=1, trainer_id=tag,
            install_signal_handlers=False, telemetry_every_s=0.01)
        try:
            st.run(event_handler=handler)
        finally:
            srv.stop()
        return b

    b_fast = solo("fast", None)
    b_slow = solo("slow", _slow_handler(0.02))
    _assert_scopes_bitwise(b_fast.scope, b_slow.scope)


# ---------------------------------------------------------------------------
# streaming trainer exposes its meter (observatory glue)
# ---------------------------------------------------------------------------
def test_streaming_trainer_goodput_state(tmp_path):
    descs = dataset.ctr.task_descs(2, records_per_shard=32, vocab=VOCAB)
    srv = MasterServer(timeout_s=30, port=0)
    addr = srv.start()
    b = _build_ctr()
    st = StreamingTrainer(
        b, addr, dataset.ctr.task_reader, task_descs=descs,
        batch_size=16,
        checkpoint=CheckpointConfig(str(tmp_path / "ck"),
                                    every_n_steps=2, background=False),
        max_passes=1, trainer_id="obs", install_signal_handlers=False,
        telemetry_every_s=0.01)
    try:
        stats = st.run()
    finally:
        srv.stop()
    assert st.goodput is not None
    snap = st.goodput.snapshot()
    # the elastic buckets the plain trainer never touches are live here
    assert snap["buckets"]["master_wait"] > 0, snap
    assert snap["buckets"]["checkpoint_stall"] > 0, snap
    assert stats is not None
    # state() surfaces the same waterfall for /metrics + flight dumps
    assert st.state()["goodput"]["total_s"] == pytest.approx(
        snap["total_s"], rel=0.2)
    # and the flight recorder can see it
    doc = get_recorder().bundle("probe")
    states = [v for k, v in doc["state"].items()
              if k.startswith("streaming_trainer#")]
    assert states and states[-1]["trainer_id"] == "obs"


# ---------------------------------------------------------------------------
# trace_summary --goodput waterfall (tool glue)
# ---------------------------------------------------------------------------
def test_trace_summary_goodput_waterfall(tmp_path):
    import sys

    tr = _build_fc(seed=21)
    path = str(tmp_path / "run.jsonl")
    with RunLog(path) as rl:
        # an explicit peak: the CPU mesh has none in the device table,
        # so the default meter prices no MFU here
        tr.train(_rows(6), num_passes=1, event_handler=lambda e: None,
                 run_log=rl, goodput=GoodputMeter(peak_flops=1e12))
    sys.path.insert(0, "tools")
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    out = trace_summary.summarize_goodput(path)
    assert "device_compute" in out and "goodput:" in out
    assert "MFU" in out
    # the per-trainer skew table renders from a master exposition
    mm = tmp_path / "master.txt"
    mm.write_text('trainer_step_seconds{trainer="a"} 0.01\n'
                  'trainer_step_seconds{trainer="b"} 0.012\n'
                  'trainer_step_seconds{trainer="c"} 0.06\n'
                  'trainer_straggler{trainer="c"} 1\n')
    out = trace_summary.summarize_goodput(path, master_metrics=str(mm))
    assert "STRAG" in out and "5.00x" in out
