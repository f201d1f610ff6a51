"""Every driver end to end on the CPU mesh at a tiny configuration and
mix (``tests/data``; four virtual devices for the dp4 mix). The device
check lives in ``run.py`` alone, so the rehearsal calls the harness with
CPU devices in hand — there is no rehearsal flag — and ``run.py`` itself
must refuse to start here. Every number these runs print names
``platform: cpu`` and made-up peaks: none is a measurement.

The traced runs read the per-layer metrics off the small trace recorded
on the chip (a CPU trace has no device plane and is refused).
Run by hand: ``pytest benchmark/tests`` (~2 min)."""
import contextlib
import io
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import harness, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(workload, traced, seconds=2.0, monkeypatch=None):
    import jax

    cell = harness.load_cell(workload, manifest=MANIFEST, data_dir=DATA)
    if traced:
        real = trace_reduce.load
        monkeypatch.setattr(trace_reduce, "load",
                            lambda path: real(RECORDED))
        monkeypatch.setattr(harness, "OUT_DIR",
                            os.path.join(DATA, ".bench_out"))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        line = harness.run_cell(cell, 3, seconds, traced,
                                jax.devices()[:cell.chips], time.monotonic())
    # what run.py prints last must survive a JSON round trip unchanged
    assert json.loads(json.dumps(line)) == line
    return cell, line, buf.getvalue()


@pytest.mark.parametrize("workload", ["tiny-train", "tiny-train-dp4",
                                      "tiny-image", "tiny-serve"])
def test_untraced_line_is_the_contract(workload):
    cell, line, _ = run(workload, traced=False)
    # a driver that compares numbers prints each beside its limit, last
    assert set(line) - {"checks"} == LINE_KEYS
    assert "checks" not in line or list(line)[-1] == "checks"
    assert ("checks" in line) == (cell.mix["kind"] == "serve")
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"      # never read as a chip
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    want = {m["name"]: m["unit"] for m in cell.end_to_end}
    assert "setup_s" in want and len(want) >= 2
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("workload", ["tiny-train", "tiny-serve"])
def test_traced_line_carries_the_layer_metrics(workload, monkeypatch):
    cell, line, out = run(workload, traced=True, monkeypatch=monkeypatch)
    assert set(line) - {"checks"} == LINE_KEYS | {"breakdown"}
    assert "checks" not in line or list(line)[-1] == "checks"
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    names = {m["name"] for m in cell.per_layer}
    got = set(line["metrics"])
    # a reader that finds nothing returns nothing: the recorded trace has
    # no Mosaic call, so flash_roofline is left out of tiny-train's line
    assert got <= names and names - got <= {"flash_roofline"}
    fresh = [k for k in got if k.endswith("window_fresh_compiles")]
    assert fresh and line["metrics"][fresh[0]]["value"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    # the untraced numbers of a traced run go on an earlier line
    first = json.loads(out.splitlines()[0])
    assert first["traced"] is True and "setup_s" in first["end_to_end"]


def test_a_new_cell_is_files_and_one_entry(tmp_path):
    """The README's example: ``gpt2m-serve-burst`` = one new mix file and
    one ``workloads`` entry, no edit to any file that is there."""
    real = harness._read_json(harness.MANIFEST)
    mixes = tmp_path / "mixes"
    mixes.mkdir()
    base = harness._read_json(os.path.join(
        harness.BENCH_DIR, "mixes", "chat-poisson.json"))
    base["arrivals"]["cv"] = 2.5
    (mixes / "chat-burst.json").write_text(json.dumps(base))
    (tmp_path / "peaks.json").write_text(json.dumps(
        harness._read_json(os.path.join(harness.BENCH_DIR, "peaks.json"))))
    real["workloads"].append({
        "name": "gpt2m-serve-burst", "config": "gpt2-medium",
        "traffic": "chat-burst", "chips": 1, "why": "arrivals cv 2.5"})
    for m in real["end_to_end"] + real["per_layer"]:
        if "gpt2m-serve-chat" in m.get("workloads", ()):
            m["workloads"].append("gpt2m-serve-burst")
    for c in real["configs"]:       # found from the checkout's root
        c["file"] = os.path.join(harness.ROOT, c["file"])
    manifest = str(tmp_path / "BENCHMARK.json")
    with open(manifest, "w") as f:
        json.dump(real, f)
    cell = harness.load_cell("gpt2m-serve-burst", manifest=manifest,
                             data_dir=str(tmp_path))
    assert cell.mix["kind"] == "serve" and cell.config["n_layer"] == 24
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    assert len(cell.per_layer) == len(harness.load_cell(
        "gpt2m-serve-chat").per_layer)
    assert cell.driver.__name__ == "benchmark.drivers.serve"
    assert cell.family.__name__ == "benchmark.families.stacked_lm"


def test_every_real_cell_loads_and_names_files_that_exist():
    spec = harness._read_json(harness.MANIFEST)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.driver and cell.family
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert os.path.exists(os.path.join(
                harness.BENCH_DIR, "layer_metrics", m["name"] + ".py"))
            moved = next(e for e in spec["end_to_end"]
                         if e["name"] == m["moves"])
            assert harness._applies(moved, w["name"])


def test_unknown_device_kind_is_an_error():
    cell = harness.load_cell("gpt2m-train")
    assert harness.peaks_for(cell, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for(cell, "cpu")


def test_run_py_refuses_to_start_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "gpt2m-train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""            # no result, no metric
    assert "needs 1 TPU chip" in proc.stderr
