"""The two pure pieces the rating of a serve cell rests on: the reader of
``gaps_over_tick_pct`` (the distance of ``tpot_p95_ms`` from the cliff
between a tick and a tick plus a prefill unit) and the driver's
``slo_met`` (whose limit on the time to first token may grow with the
prompt); and the ``rated`` block a re-rated mix carries (PR 53): what the
rate was set from, as data. Run by hand: ``pytest benchmark/tests``."""
import glob
import json
import os
import time

import pytest

from benchmark import harness
from benchmark.drivers.serve import slo_met
from benchmark.layer_metrics import (gaps_over_p95_mode_pct,
                                     gaps_over_tick_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MIXES = os.path.join(os.path.dirname(os.path.dirname(DATA)), "mixes")
FLAT = {"ttft_ms": 1000, "mean_gap_ms": 100}
GROWS = dict(FLAT, ttft_ms_per_prompt_token=0.4)


def _tiny_cell():
    return harness.load_cell(
        "tiny-serve", manifest=os.path.join(DATA, "BENCHMARK.json"),
        data_dir=DATA)


def _bimodal(n_ticks, n_units, tick=20.0, unit=57.0):
    """Gaps of a window: a bare tick, or a tick behind one prefill unit,
    each with a little jitter that stays on its side of the cut."""
    ticks = [tick + 0.02 * (i % 50) for i in range(n_ticks)]
    return ticks + [tick + unit + 0.05 * (i % 40) for i in range(n_units)]


@pytest.mark.parametrize("n_ticks, n_units, want", [
    (1000, 0, 0.0),         # every gap a tick
    (975, 25, 2.5),         # under the cliff: p95 is a tick
    (945, 55, 5.5),         # on it: p95 is interpolated between the modes
    (900, 100, 10.0),       # over it: p95 is a tick plus a unit
])
def test_share_counts_the_gaps_beyond_the_cut(n_ticks, n_units, want):
    gaps = _bimodal(n_ticks, n_units)
    counters = {"gap_ms": gaps}
    assert gaps_over_tick_pct.read(None, [], counters, None) == \
        pytest.approx(want)


def test_cut_lies_between_the_modes():
    # a tick at full occupancy is < 1.3 x the median and is not counted;
    # the shortest tick plus unit (gpt2m: 9 + 9 ms) is 2 x it and is
    gaps = [9.0] * 90 + [9.0 * 1.3] * 5 + [9.0 * 2.0] * 5
    assert gaps_over_tick_pct.share(gaps) == pytest.approx(5.0)


@pytest.mark.parametrize("ticks, units, fours, want", [
    (975, 25, 0, 2.5),      # p95 a bare tick: gaps_over_tick_pct's reading
    (800, 200, 0, 0.0),     # p95 a tick plus a unit, no mode beyond it
    (800, 165, 35, 3.5),    # a third mode (gpt2m: a four-row unit) above
    (700, 200, 100, 0.0),   # past that cliff: p95 IS the third mode
])
def test_share_beyond_the_mode_of_p95(ticks, units, fours, want):
    gaps = (_bimodal(ticks, units, tick=10.0, unit=9.0)
            + [10.0 + 18.0 + 0.01 * (i % 30) for i in range(fours)])
    assert gaps_over_p95_mode_pct.read(None, [], {"gap_ms": gaps}, None) \
        == pytest.approx(want)


@pytest.mark.parametrize("reader", [gaps_over_tick_pct,
                                    gaps_over_p95_mode_pct])
@pytest.mark.parametrize("counters", [{}, {"gap_ms": []}])
def test_nothing_to_read_gives_none(reader, counters):
    assert reader.read(None, [], counters, None) is None


@pytest.mark.parametrize("slo, prompt, first_ms, gap_ms, met", [
    (FLAT, 100, 999.0, 50.0, True),         # the flat limit, as it was
    (FLAT, 100, 1001.0, 50.0, False),
    (FLAT, 6528, 1001.0, 50.0, False),      # and blind to the prompt
    (FLAT, 100, 500.0, 100.5, False),       # the mean gap alone misses
    (GROWS, 6528, 3500.0, 50.0, True),      # 1000 + 0.4 x 6528 = 3611.2
    (GROWS, 6528, 3700.0, 50.0, False),
    (GROWS, 100, 1039.0, 50.0, True),       # a short prompt gains 40 ms
    (GROWS, 100, 1041.0, 50.0, False),
    (GROWS, 6528, 3500.0, 100.5, False),
])
def test_slo_met(slo, prompt, first_ms, gap_ms, met):
    assert slo_met(slo, prompt, first_ms, gap_ms) is met


def _rated_mixes():
    """Every serve mix under ``mixes/`` that carries a ``rated`` block."""
    out = []
    for path in sorted(glob.glob(os.path.join(MIXES, "*.json"))):
        with open(path) as f:
            mix = json.load(f)
        if mix.get("kind") == "serve" and "rated" in mix:
            out.append(pytest.param(mix, id=os.path.basename(path)[:-5]))
    return out


#: the keys of a ``rated`` block: the PR that rated the mix, the knee its
#: sweep showed, the rate as a share of it, the tick and the unit it was
#: rated at (README step 6 compares the ledger's with these), and the
#: check runs' ranges
RATED_KEYS = {"pr", "knee_req_s", "share_of_knee", "decode_step_p50_ms",
              "prefill_chunk_p50_ms", "gaps_over_tick_pct", "tpot_p95_ms",
              "runs"}


@pytest.mark.parametrize("mix", _rated_mixes())
def test_a_rated_mix_says_what_its_rate_was_set_from(mix):
    rated = mix["rated"]
    assert set(rated) >= RATED_KEYS
    assert 0.6 <= rated["share_of_knee"] <= 0.9
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        rated["share_of_knee"] * rated["knee_req_s"], abs=0.05)
    lo, hi = rated["gaps_over_tick_pct"]
    # one side of the cliff in every run, and outside the 3-7 stretch
    assert lo <= hi and (hi < 3.0 or lo > 7.0)
    lo, hi = rated["tpot_p95_ms"]
    assert 0 < lo <= hi
    assert rated["decode_step_p50_ms"] > 0
    assert rated["prefill_chunk_p50_ms"] > 0
    assert rated["runs"] >= 10


def test_sweep_runs_the_cells_driver_at_the_rate_asked(tmp_path):
    """``sweep.py`` less its look for a chip: the tiny serve cell at twice
    its rate, through the cell's own driver; the cell's mix on disk is
    left as it was."""
    import jax

    from benchmark import sweep

    cell = _tiny_cell()
    rate = 2 * cell.mix["arrivals"]["rate_per_s"]
    line = sweep.run_rate(cell, rate, 5, {"n_pages": 48}, 3, 2.0,
                          jax.devices()[:1], time.monotonic())
    assert line["correct"] is True and line["rate_per_s"] == rate
    assert line["schedule_seed"] == 5 and line["engine"]["n_pages"] == 48
    assert line["requests_due"] == round(rate * 2.0)
    assert 0 <= line["gaps_over_tick_pct"] <= 100
    assert len(line["in_flight_over_window"]) == 7
    assert line["pools"] and line["tpot_p95_ms"] > 0


@pytest.mark.parametrize("fault", ["token_altered", "answer_cut"])
def test_a_broken_timed_path_reads_not_correct(fault, monkeypatch):
    """The rest of a run with the timed path broken underneath: the
    server hands back every answer with its last token replaced (the
    reference's logit of that token lies far below its best), or one
    token short. ``correct`` comes out false and ``checks`` says why."""
    import jax
    import numpy as np

    from paddle_tpu.serving import Server

    real = Server.submit

    class Broken:
        def __init__(self, fut):
            self.fut = fut

        def result(self, timeout=None):
            out = np.array(self.fut.result(timeout=timeout))
            if fault == "answer_cut":
                return out[:-1]
            out[-1] = (out[-1] + 7) % 61
            return out

    monkeypatch.setattr(Server, "submit",
                        lambda self, *a, **kw: Broken(real(self, *a, **kw)))
    line = harness.run_cell(_tiny_cell(), 3, 2.0, False, jax.devices()[:1],
                            time.monotonic())
    assert line["correct"] is False and list(line)[-1] == "checks"
    checks = line["checks"]
    if fault == "answer_cut":
        assert checks["answers_malformed"]["value"] > 0
    else:
        assert checks["answers_malformed"]["value"] == 0
        assert (checks["logit_gap_max"]["value"]
                > checks["logit_gap_max"]["limit"])
