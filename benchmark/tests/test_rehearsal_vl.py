"""The ``dsa_gqa_moe_vl`` family and its four readers on the CPU mesh: the
serve driver end to end at a toy Keye-VL-2.0-shaped configuration
(``tests/data``: its own manifest ``BENCHMARK-vl.json``, a twin of the
configuration and of the mix; every prompt a clip resolved by the family's
resolver), the readers on hand-built counters and device events, the real
configuration file against the catalog row's published keys, the cell's
schedule (the checked requests it deals) and the check that adding the cell
changed no file the benchmark had. Every number these runs print names
``platform: cpu``: none is a measurement. Run by hand: ``pytest
benchmark/tests`` (not part of tier-1)."""
import contextlib
import io
import json
import os
import subprocess
import time

import numpy as np
import pytest

from benchmark import harness, trace_reduce, traffic
from benchmark.families import dsa_gqa_moe_vl as fam
from benchmark.layer_metrics import (dsa_decode_roofline, dsa_share_pct,
                                     media_resolve_ms, vision_encode_mfu_pct,
                                     vision_share_pct, vision_token_share_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-vl.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "keye2-serve-video"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROOT = os.path.dirname(harness.BENCH_DIR)


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=2.0):
    import jax

    cell = harness.load_cell("tiny-serve-video", manifest=MANIFEST,
                             data_dir=DATA)
    if traced:
        real = trace_reduce.load
        monkeypatch.setattr(trace_reduce, "load",
                            lambda path: real(RECORDED))
        monkeypatch.setattr(harness, "OUT_DIR",
                            os.path.join(DATA, ".bench_out"))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        line = harness.run_cell(cell, 2**31 + 5, seconds, traced,
                                jax.devices()[:1], time.monotonic())
    assert json.loads(json.dumps(line)) == line
    return cell, line, buf.getvalue()


def test_untraced_line_is_the_contract():
    cell, line, out = run(traced=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    notes = json.loads(out.splitlines()[0])["notes"]
    assert notes["logit_gap_positions"] == 4    # the family's four readings
    assert notes["logit_gap_max"] <= cell.mix["check"]["logit_gap_tol"]
    assert line["checks"]["logit_gap_max"]["limit"] == fam.CHECK_LOGPROB_TOL


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense text model's: no tower op, no span in it
    assert {"vision_token_share_pct", "media_resolve_ms",
            "dsa_select_share_pct"} <= got
    assert not got & {"vision_share_pct", "vision_encode_mfu_pct",
                      "dsa_decode_roofline", "dsa_share_pct"}
    # every prompt a preamble, ONE clip and a question
    assert 0.0 < line["metrics"]["vision_token_share_pct"]["value"] < 100.0
    assert line["metrics"]["media_resolve_ms"]["value"] > 0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        return json.load(f)


class Cell:
    config = _config()
    family = fam
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(events):
    ops, t = [], 0.0
    for text, seconds in events:
        ops.append((text, t, t + seconds))
        t += seconds
    return trace_reduce.Trace({0: ops}, {}, {}, [], (0.0, t * 2))


SCORE = ("%fusion.11 = f32[1,128,25600]{2,1,0} fusion(f32[1,128,16,25600]"
         "{3,2,1,0} %s, f32[1,128,16]{2,1,0} %w)")
KEYS = ("%fusion.10 = bf16[1,25600,64]{2,1,0} fusion(bf16[6,1200,256,64]"
        "{3,2,1,0} %pool, s32[1,100]{1,0} %tbl)")
PICK = ("%fusion.12 = s8[1,128,25600]{2,1,0} fusion(f32[1,128,25600]{2,1,0} "
        "%s, f32[1,128,1]{2,1,0} %thr)")
WALK = ("%paged_attention_prefill.3 = bf16[1,1024,4096]{2,1,0} custom-call("
        "bf16[1,32,1024,128]{3,2,1,0} %q, bf16[6,1200,256,512]{3,2,1,0} %k), "
        "custom_call_target=\"tpu_custom_call\"")
TICK_WALK = ("%paged_attention_decode.2 = bf16[16,32,128]{2,1,0} custom-call("
             "bf16[16,32,128]{2,1,0} %q, bf16[6,1200,256,512]{3,2,1,0} %k), "
             "custom_call_target=\"tpu_custom_call\"")
POOL = ("%scatter.2 = bf16[6,1200,256,64]{3,2,1,0} scatter(bf16[6,1200,256,"
        "64]{3,2,1,0} %pool, s32[1024,3]{1,0} %ix, bf16[1024,64]{1,0} %k)")
PROJECT = ("%fusion.4 = f32[1,1024,1024]{2,1,0} fusion(f32[1,1024,2048]"
           "{2,1,0} %h, bf16[2048,1024]{1,0} %w)")
HEAD = "%fusion.30 = f32[16,151936]{1,0} fusion(bf16[2048,151936]{1,0} %w)"
EXPERT = ("%grouped_matmul.5 = f32[8192,768]{1,0} custom-call(bf16[8192,2048]"
          "{1,0} %a, bf16[768,2048,768]{2,1,0} %w, s32[128]{0} %g), "
          "custom_call_target=\"tpu_custom_call\"")
PATCH = ("%fusion.40 = f32[1024,1152]{1,0} fusion(f32[1024,588]{1,0} %p, "
         "bf16[588,1152]{1,0} %w)")
V_ATTN = ("%fusion.41 = f32[16,1024,1024]{2,1,0} fusion(f32[1024,16,72]"
          "{2,1,0} %q, f32[1024,16,72]{2,1,0} %k)")
V_MLP = ("%fusion.42 = f32[1024,4304]{1,0} fusion(f32[1024,1152]{1,0} %a, "
         "bf16[4,1152,4304]{2,1,0} %w)")
V_MERGE = ("%fusion.43 = f32[256,2048]{1,0} fusion(f32[256,4608]{1,0} %a, "
           "bf16[4608,2048]{1,0} %w)")


def test_the_hooks_tell_the_tower_and_the_selection_from_the_rest():
    cfg = Cell.config
    assert [fam.dsa_op(t, cfg) for t in (
        SCORE, KEYS, PICK, WALK, TICK_WALK, POOL, PROJECT)] == [
            "score", "score", "pick", "attend", "attend", "pool", "project"]
    assert [fam.vision_op(t, cfg) for t in (PATCH, V_ATTN, V_MLP, V_MERGE)] \
        == ["patch", "attn", "mlp", "merge"]
    for other in (HEAD, EXPERT, PATCH, V_ATTN, V_MLP, V_MERGE):
        assert fam.dsa_op(other, cfg) is None
    for other in (HEAD, EXPERT, SCORE, PICK, WALK, POOL, PROJECT):
        assert fam.vision_op(other, cfg) is None
    assert fam.dsa_tick_op(TICK_WALK, cfg, 16)
    assert not fam.dsa_tick_op(WALK, cfg, 16)
    assert fam.moe_op(EXPERT, cfg) == "grouped_matmul"
    assert fam.moe_op(V_MLP, cfg) is None and fam.moe_op(HEAD, cfg) is None


def test_shares_are_device_time_over_busy_time(capsys):
    tr = _trace([(PATCH, 1e-3), (V_ATTN, 2e-3), (SCORE, 1e-3), (HEAD, 4e-3)])
    assert vision_share_pct.read(tr, [], {}, Cell) == pytest.approx(37.5)
    assert '"attn": 25.0' in capsys.readouterr().out
    assert dsa_share_pct.read(tr, [], {}, Cell) == pytest.approx(12.5)
    assert vision_share_pct.read(None, [], {}, Cell) is None
    assert vision_share_pct.read(_trace([(HEAD, 1e-3)]), [], {}, Cell) is None

    class Other(Cell):
        from benchmark.families import dsa_kda_moe_lm as family

    assert vision_share_pct.read(tr, [], {}, Other) is None
    capsys.readouterr()


def test_the_tower_s_share_of_the_peak_prices_frames(capsys):
    """One frame: 2 x 1024 patches x (588 x 1152 + 4 blocks x 15.2 M) + the
    attention within it + the merger = ~147 GFLOP = 0.75 ms at 197 TFLOP/s;
    tower ops at twice that read 50%."""
    cost = fam.vision_cost(Cell.config, 1.0)["flops"]
    block = 4 * 1152 * 1152 + 2 * 1152 * 4304
    assert cost == 2.0 * 1024 * (588 * 1152 + 4 * block) \
        + 4 * 4.0 * 1024 * 1024 * 1152 \
        + 2.0 * 256 * (4608 * 4608 + 4608 * 2048)
    least = 5 * cost / 197e12
    tr = _trace([(V_ATTN, least), (V_MLP, least), (HEAD, 1.0)])
    got = vision_encode_mfu_pct.read(
        tr, [], {"slice": {"vision_frames_encoded": 5}}, Cell)
    assert got == pytest.approx(50.0, rel=1e-6)
    assert vision_encode_mfu_pct.read(tr, [], {}, Cell) is None
    assert vision_encode_mfu_pct.read(None, [], {"slice": {
        "vision_frames_encoded": 5}}, Cell) is None
    capsys.readouterr()


def test_the_counter_and_span_readers():
    assert vision_token_share_pct.read(None, [], {
        "vision_tokens_prefilled": 8192, "prompt_tokens_prefilled": 8448},
        Cell) == pytest.approx(100 * 8192 / 8448)
    assert vision_token_share_pct.read(None, [], {}, Cell) is None
    assert media_resolve_ms.read(None, [], {
        "media_resolve_sum_ms": 90.0, "media_resolve_count": 3}, Cell) == 30.0
    assert media_resolve_ms.read(None, [], {}, Cell) is None
    tr = trace_reduce.Trace({}, {}, {}, [("serving/media_resolve", 1.0, 1.02),
                                         ("serving/media_resolve", 2.0, 2.04),
                                         ("serving/after_tick", 2.1, 2.2)],
                            (0.0, 3.0))
    assert media_resolve_ms.read(tr, [], {}, Cell) == pytest.approx(30.0)


def test_the_tick_s_selection_is_priced_at_this_kind_s_rows(capsys):
    """A tick of 16 slots at 8k of context, ONE layer's worth: 8191 keys
    scored and 2048 tokens attended a slot: 16 x (8191 x 64 + 2048 x 2 x
    512) x 2 B = 83.9 MB = 0.10 ms at 819 GB/s; six layers ran it."""
    cost = fam.dsa_cost(Cell.config, 16 * 8191, 16 * 2048)
    assert cost["bytes"] == 16 * (8191 * 64 + 2048 * 2 * 512) * 2
    assert cost["flops"] == 2.0 * 16 * (8191 * 16 * 64
                                        + 2048 * 32 * 2 * 128)
    least = 6 * cost["bytes"] / 819e9
    counted = {"dsa_tick_rows_attended": 16 * 2048,
               "dsa_tick_groups_scored": 16 * 8191, "dsa_calls": 1,
               "dsa_layer_calls": 6, "decode_steps": 1}
    tick_score = SCORE.replace("[1,128,", "[16,1,")
    tr = _trace([(tick_score, least), (TICK_WALK, least), (WALK, 1.0),
                 (HEAD, 1.0)])
    got = dsa_decode_roofline.read(tr, [], {"slice": counted, "slots": 16},
                                   Cell)
    assert got == pytest.approx(50.0, rel=1e-6)
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_depth_alone():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in row["config"].items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 6
    assert config["reduced_from"]["num_hidden_layers"] == 48
    assert config["reduced_from"]["vision_num_hidden_layers"] == 27
    assert config["assumed"]["vision"]["num_hidden_layers"] == 4
    assert config["source"].startswith(row["source_url"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = json.load(f)["configs"][-1]
    assert entry["source"] == row["source_url"]
    assert cell.family is fam and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"vision_share_pct", "vision_encode_mfu_pct",
            "vision_token_share_pct", "media_resolve_ms", "dsa_share_pct",
            "dsa_select_share_pct", "dsa_decode_roofline", "moe_share_pct",
            "paged_attn_page_share_pct",
            "prefill_attn_page_share_pct"} <= names
    # both expert rooflines index a key this configuration publishes as
    # something else (intermediate_size 6144: the dense width) or count a
    # held share it has none of
    # ... and paged_attn_roofline prices a slice's tick calls by the WINDOW's
    # mean pages a tick: at this cell's 12% occupancy a slice's ticks are
    # lighter than the mean and it read 178% (my chip run, PR 60)
    assert not names & {"moe_roofline", "moe_held_roofline",
                        "paged_attn_roofline", "mla_decode_roofline",
                        "kda_share_pct"}
    assert cell.mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    spec = fam.spec_of(config)
    assert spec.n_params() == 4_467_787_840
    assert spec.vision_param_count() == 93_165_376
    assert (spec.d_model, spec.num_heads, spec.kv_heads, spec.head_dim) == (
        2048, 32, 4, 128)
    assert (spec.num_experts, spec.experts_per_tok, spec.d_expert) == (
        128, 8, 768)
    assert (spec.index_heads, spec.index_dim, spec.index_topk,
            spec.index_pool) == (16, 64, 2048, 1)
    assert spec.vocab_size == 151936 and spec.mrope_section == (16, 24, 24)
    assert (spec.cache_bytes_per_token, spec.index_bytes_per_token) == (
        6 * 2 * 512 * 2, 6 * 64 * 2)
    assert spec.vision.tokens_per_frame == 256
    e = cell.mix["engine"]
    longest = cell.mix["prompt"]["user"]["max"] + cell.mix["output"]["max"]
    assert longest <= e["max_len"] == 25600 == config["assumed"]["max_len"]
    assert (e["page_size"], e["prefill_chunk"], e["slots"],
            e["n_pages"]) == (256, 1024, 16, 1200)
    assert cell.mix["prompt"]["shared_prefix"]["prob"] == 0
    for key in ("bytes", "deployment"):
        assert config[key]


def test_every_drawn_prompt_is_a_preamble_a_clip_and_a_question():
    cell = harness.load_cell(CELL)
    v = fam.vision_of(cell.config)
    rng = np.random.RandomState(3)
    for n in (2304, 8448, 24832, 5000):
        ids = fam.draw_prompt_ids(rng, n, cell.config)
        spans, _, row = v.media_layout(ids)
        assert ids.size == n and len(spans) == 1
        first, frames = spans[0]
        assert first == 25 and frames == (n - 26 - 16) // 256
        assert 16 <= n - first - frames * 256 - 1 < 16 + 256
        assert (row >= 0).sum() == frames * 256
    assert 8 <= (2304 - 42) // 256 and (24832 - 42) // 256 == 96


def test_the_schedule_deals_the_checked_requests_asked_for():
    """The first ``greedy_requests`` greedy requests due in the window are
    what the check replays: FOUR, three of them past 4096 tokens of context,
    as the issue asks, and all inside ONE padded length of the reference
    (one compile)."""
    cell = harness.load_cell(CELL)
    mix = cell.mix
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = json.load(f)["run_seconds"]
    planned = traffic.schedule(mix, 1, mix["ramp_s"], seconds,
                               lambda rng, n: np.zeros(n, np.int64))
    due = [p for p in planned if p.due >= mix["ramp_s"]]
    checked = [p for p in due if p.sampling is None][
        :mix["check"]["greedy_requests"]]
    contexts = [p.prompt.size + min(p.max_new_tokens,
                                    fam.CHECK_REPLAY_TOKENS)
                for p in checked]       # what the check replays of each
    assert len(contexts) == 4 and sum(c > 4096 for c in contexts) >= 3
    assert len({fam._padded(c - 1) for c in contexts}) == 1
    longest = max(p.max_new_tokens for p in planned)
    assert longest <= mix["output"]["max"] == 512
    assert mix["drain_s"] * 1e3 >= longest * mix["slo"]["mean_gap_ms"]
    assert mix["engine"]["beam_width"] == fam.CHECK_TOPK
    assert mix["rated"]["share_of_knee"] == 0.75
    assert mix["arrivals"]["rate_per_s"] == pytest.approx(
        0.75 * mix["rated"]["knee_req_s"], rel=0.05)


def test_the_cell_is_files_and_entries_only():
    """No file the benchmark had is edited beyond the cell's name on the
    lists of the metrics it reports."""
    out = subprocess.run(
        ["git", "diff", "--name-only", "--diff-filter=MDRT", "HEAD", "--",
         "benchmark", "BENCHMARK.json"],
        cwd=ROOT, capture_output=True, text=True)
    if out.returncode:
        pytest.skip("not a git checkout")
    assert set(out.stdout.split()) <= {"BENCHMARK.json"}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [c["name"] for c in manifest["configs"]][-1] \
        == "keye-vl-2.0-30b-a3b"
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert len(manifest["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert [m["name"] for m in manifest["per_layer"]][-4:] == [
        "vision_share_pct", "vision_encode_mfu_pct",
        "vision_token_share_pct", "media_resolve_ms"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
