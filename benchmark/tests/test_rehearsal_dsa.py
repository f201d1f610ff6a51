"""The ``dsa_kda_moe_lm`` family and its four readers on the CPU mesh: the
serve driver end to end at a toy GLM-5.3-Flash-shaped configuration
(``tests/data``: its own manifest ``BENCHMARK-dsa.json``, a twin of the
configuration and of the mix), the readers on hand-built counters and device
events, the real configuration file against the catalog row's published keys,
the cell's schedule (the checked requests it deals) and the check that adding
the cell changed no file the benchmark had. Every number these runs print
names ``platform: cpu``: none is a measurement. Run by hand: ``pytest
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
from benchmark.families import dsa_kda_moe_lm as fam
from benchmark.layer_metrics import (dsa_decode_roofline, dsa_select_share_pct,
                                     dsa_share_pct, mhc_share_pct,
                                     moe_held_roofline,
                                     paged_attn_page_share_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-dsa.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "glm53f-serve-longctx"
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

    cell = harness.load_cell("tiny-serve-longctx", manifest=MANIFEST,
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
    assert notes["logit_gap_positions"] == 5    # the family's five readings
    assert notes["logit_gap_max"] <= cell.mix["check"]["logit_gap_tol"]
    assert line["checks"]["logit_gap_max"]["limit"] == fam.CHECK_LOGPROB_TOL


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense MHA model's: no selection op in it
    assert {"dsa_select_share_pct", "moe_held_rows_pct",
            "state_cache_share_pct"} <= got
    assert not got & {"dsa_decode_roofline", "dsa_share_pct",
                      "mhc_share_pct", "kda_decode_roofline"}
    # prompts of 18 to 60 tokens under a pick of 16: the reads follow it
    assert 20.0 < line["metrics"]["dsa_select_share_pct"]["value"] < 100.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "glm-5.3-flash.json")) as f:
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


#: device events of the cell's programs, as the chip's compiler names them
SCORE = ("%fusion.11 = f32[32,1,8448]{2,1,0} fusion(bf16[32,1,32,128]{3,2,1,0}"
         " %q, bf16[32,8448,128]{2,1,0} %keys)")
PICK = ("%custom-call.7 = (f32[32,1,511]{2,1,0}, s32[32,1,511]{2,1,0}) "
        "custom-call(f32[32,1,8448]{2,1,0} %s), custom_call_target=\"TopK\"")
GATHER = ("%gather.3 = bf16[32,1,512,2048]{3,2,1,0} gather(bf16[1,163840,"
          "2048]{2,1,0} %pool, s32[32,1,512,2]{3,2,1,0} %ix)")
ATTEND = ("%fusion.12 = f32[32,64,1,2048]{3,2,1,0} fusion(bf16[32,64,1,512]"
          "{3,2,1,0} %q, bf16[32,1,2048,512]{3,2,1,0} %rows)")
CHUNK_ATTEND = ("%fusion.13 = f32[1,64,128,2048]{3,2,1,0} fusion(bf16[1,128,"
                "2048,512]{3,2,1,0} %rows)")
POOL = ("%scatter.2 = bf16[1,2560,64,128]{3,2,1,0} scatter(bf16[1,2560,64,"
        "128]{3,2,1,0} %pool, s32[32,3]{1,0} %ix, bf16[32,128]{1,0} %k)")
STREAM = ("%fusion.20 = f32[32,1,4,4096]{3,2,1,0} fusion(f32[32,1,4,4]"
          "{3,2,1,0} %res, f32[32,1,4,4096]{3,2,1,0} %x)")
MIX = ("%fusion.21 = f32[32,1,24]{2,1,0} fusion(f32[32,1,16384]{2,1,0} %v, "
       "f32[16384,24]{1,0} %phi)")
HEAD = "%fusion.30 = f32[32,19360]{1,0} fusion(bf16[4096,19360]{1,0} %w)"
EXPERT = ("%fusion.31 = f32[256,2048]{1,0} fusion(bf16[256,4096]{1,0} %a, "
          "bf16[4,4096,2048]{2,1,0} %w)")


def test_the_hooks_tell_the_selection_and_the_streams_from_the_rest():
    cfg = Cell.config
    assert [fam.dsa_op(t, cfg) for t in (
        SCORE, PICK, GATHER, ATTEND, CHUNK_ATTEND, POOL)] == [
            "score", "pick", "gather", "attend", "attend", "pool"]
    assert fam.dsa_op("%fusion.2 = f32[32,1,4096]{2,1,0} fusion(f32[32,1,"
                      "1536]{2,1,0} %c, bf16[1536,4096]{1,0} %w)",
                      cfg) == "project"
    # as the chip's compiler really named them (my chip run, PR 58): the
    # gather's result flattened, its page ids a fusion over the table
    assert fam.dsa_op("%fusion.2776 = bf16[65536,2048]{1,0} fusion(bf16[1,"
                      "163840,2048]{2,1,0} %pool, s32[65536]{0} %ix), "
                      "kind=kCustom", cfg) == "gather"
    assert fam.dsa_op("%fusion.2774 = s32[65536]{0} fusion(s32[132]{0} %tbl, "
                      "s32[65536]{0} %b), kind=kCustom", cfg) == "gather"
    assert fam.dsa_op("%reshape.3 = bf16[128,2048,512]{2,1,0} reshape(bf16["
                      "65536,2048]{1,0} %f)", cfg) == "attend"
    for other in (STREAM, MIX, HEAD, EXPERT,
                  "%fusion.9 = f32[8192,2048]{1,0} fusion(bf16[144,4096,2048]"
                  "{2,1,0} %w)"):
        assert fam.dsa_op(other, cfg) is None
    assert fam.mhc_op(STREAM, cfg) == "stream"
    assert fam.mhc_op(MIX, cfg) == "mix"
    for other in (SCORE, PICK, GATHER, ATTEND, POOL, HEAD, EXPERT):
        assert fam.mhc_op(other, cfg) is None
    assert fam.dsa_tick_op(ATTEND, cfg, 32)
    assert not fam.dsa_tick_op(CHUNK_ATTEND, cfg, 32)
    assert fam.moe_op(EXPERT, cfg) == "shared_expert"
    assert fam.moe_op(PICK, cfg) is None and fam.kda_op(ATTEND, cfg) is None


def test_shares_are_device_time_over_busy_time(capsys):
    tr = _trace([(SCORE, 1e-3), (GATHER, 2e-3), (STREAM, 1e-3),
                 (HEAD, 4e-3)])
    assert dsa_share_pct.read(tr, [], {}, Cell) == pytest.approx(37.5)
    assert mhc_share_pct.read(tr, [], {}, Cell) == pytest.approx(12.5)
    assert '"gather": 25.0' in capsys.readouterr().out
    assert dsa_share_pct.read(None, [], {}, Cell) is None
    assert mhc_share_pct.read(_trace([(HEAD, 1e-3)]), [], {}, Cell) is None

    class Other(Cell):
        from benchmark.families import kda_mla_moe_lm as family

    assert dsa_share_pct.read(tr, [], {}, Other) is None


def test_select_share_is_rows_read_over_rows_in_reach(capsys):
    counted = {"dsa_rows_attended": 2048 * 10, "dsa_rows_in_reach": 16384 * 10}
    assert dsa_select_share_pct.read(None, [], counted, Cell) == 12.5
    assert dsa_select_share_pct.read(None, [], {}, Cell) is None
    capsys.readouterr()
    # ... and beside it, how many queries selected nothing
    counted.update(dsa_queries=10, dsa_dense_queries=1,
                   dsa_groups_scored=4095 * 10)
    assert dsa_select_share_pct.read(None, [], counted, Cell) == 12.5
    detail = json.loads(capsys.readouterr().out)["dsa_select_share_pct"]
    assert detail["dense_queries_pct"] == 10.0
    assert detail["groups_scored_a_query"] == 4095


def test_decode_roofline_prices_the_work_not_the_implementation(capsys):
    """A tick of 32 slots at 16k of context, ONE sparse layer: 4095 groups
    scored and 2048 rows attended a slot: 32 x (4095 x 128 + 2048 x 512) x
    2 B = 100.7 MB = 0.123 ms at 819 GB/s (the operations take 0.05 ms).
    Tick ops at twice that read 50%; a chunk's ops are not the tick's."""
    cost = fam.dsa_cost(Cell.config, 32 * 4095, 32 * 2048)
    assert cost["bytes"] == 32 * (4095 * 128 + 2048 * 512) * 2
    assert cost["flops"] == 2.0 * 32 * (4095 * 32 * 128
                                        + 2048 * 64 * (512 + 512))
    least = cost["bytes"] / 819e9
    assert least > cost["flops"] / 197e12
    counted = {"dsa_tick_rows_attended": 32 * 2048 * 3,
               "dsa_tick_groups_scored": 32 * 4095 * 3, "dsa_calls": 4,
               "dsa_layer_calls": 4, "decode_steps": 3}
    tr = _trace([(SCORE, 2 * least), (GATHER, 2 * least),
                 (ATTEND, 2 * least), (CHUNK_ATTEND, 1.0), (HEAD, 1.0)])
    got = dsa_decode_roofline.read(tr, [], {"slice": counted, "slots": 32},
                                   Cell)
    assert got == pytest.approx(50.0, rel=1e-6)
    assert '"bound": "memory"' in capsys.readouterr().out
    assert dsa_decode_roofline.read(tr, [], {"slots": 32}, Cell) is None
    assert dsa_decode_roofline.read(None, [], {"slice": counted,
                                               "slots": 32}, Cell) is None


def test_the_unedited_readers_read_this_family(capsys):
    """``moe_held_roofline`` indexes ``hidden_size`` /
    ``moe_intermediate_size`` and calls the family's ``moe_op`` /
    ``grouped_matmul_cost``; ``paged_attn_page_share_pct`` reads the pages
    the slots hold against the table (the width the scores are masked
    over)."""
    cfg = Cell.config
    call = ("%grouped_matmul.5 = f32[256,2048]{1,0} custom-call(bf16[256,"
            "4096]{1,0} %a, bf16[144,4096,2048]{2,1,0} %w, s32[36]{0} %g), "
            "custom_call_target=\"tpu_custom_call\"")
    assert fam.moe_op(call, cfg) == "grouped_matmul"
    counted = {"moe_assignments": 1024, "moe_held_assignments": 128,
               "moe_absent_assignments": 896, "moe_touched_experts": 100,
               "moe_layer_calls": 4}
    c = fam.grouped_matmul_cost(cfg, 32.0, 4096, 2048, 25.0)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    got = moe_held_roofline.read(_trace([(call, 2 * least)]), [], counted,
                                 Cell)
    assert got == pytest.approx(50.0, rel=1e-6)
    assert paged_attn_page_share_pct.read(
        None, [], {"paged_attn_pages_read": 32 * 50,
                   "paged_attn_table_pages": 32 * 132}, Cell) \
        == pytest.approx(100 * 50 / 132)
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_three():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-5.3-Flash")
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in row["config"].items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 36, 19360)
    assert {k: config["reduced_from"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 45, "n_routed_experts": 288,
        "vocab_size": 154880}
    assert config["router_outputs"] == 288
    assert config["source"].startswith(row["source_url"])
    assert cell.family is fam and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"dsa_select_share_pct", "dsa_decode_roofline", "dsa_share_pct",
            "mhc_share_pct", "kda_decode_roofline", "kda_share_pct",
            "state_cache_share_pct", "moe_held_roofline",
            "moe_held_rows_pct", "moe_kernel_calls_pct", "moe_share_pct",
            "paged_attn_page_share_pct"} <= names
    # n_routed_experts is the HELD count here, so moe_load_imbalance would
    # scale by 36 for 288; the sparse tick runs no paged_mla_decode call
    assert not names & {"moe_load_imbalance", "mla_decode_roofline",
                        "moe_roofline", "paged_attn_roofline"}
    assert cell.mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    spec = fam.spec_of(config)
    assert spec.experts_held == (0, 36) and spec.num_experts == 288
    assert spec.n_params() == 4_718_182_798
    assert spec.layer_pattern == ("kda", "mla", "kda", "kda", "kda")
    assert (spec.first_dense, spec.residual, spec.hc_mult,
            spec.hc_iters) == (1, "mhc", 4, 20)
    assert (spec.index_heads, spec.index_dim, spec.index_topk,
            spec.index_pool, spec.qk_rope_head_dim) == (32, 128, 2048, 4, 0)
    assert (spec.cache_row_width, spec.cache_bytes_per_token,
            spec.index_bytes_per_token) == (512, 1024, 64)
    assert spec.ffn_limit == 10 and spec.kda_proj_rank == 128
    e = cell.mix["engine"]
    longest = cell.mix["prompt"]["user"]["max"] + cell.mix["output"]["max"]
    assert longest <= e["max_len"] == 33792 == config["assumed"]["max_len"]
    assert (e["page_size"], e["prefill_chunk"], e["slots"]) == (256, 1024, 32)
    assert e["page_size"] == config["assumed"]["page_size"]
    assert cell.mix["prompt"]["shared_prefix"]["prob"] == 0
    assert cell.mix["prompt"]["user"]["min"] >= 2 * config["index_topk"]
    assert set(config["assumed"]) >= {
        "kda_proj_rank", "mhc_init", "mhc_norm", "hc_values", "indexer",
        "swiglu", "router", "state_dtype", "left_out", "embedding_scale",
        "max_len", "kda_gate_values", "router_bias_std"}
    for key in ("bytes", "deployment"):
        assert config[key]


def test_the_schedule_deals_the_checked_requests_asked_for():
    """The first ``greedy_requests`` greedy requests due in the window are
    what the check replays: FOUR, three of them past 8192 tokens of context
    = 4 x ``index_topk``, as the issue asks, and all inside ONE padded
    length of the reference (one compile)."""
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
    assert len(contexts) == 4 and sum(c > 8192 for c in contexts) >= 3
    assert max(fam._padded(c - 1) for c in contexts) == 10240
    assert mix["ramp_s"] <= 20
    longest = max(p.max_new_tokens for p in planned)
    assert longest <= mix["output"]["max"] == 768
    assert len(due) == 15
    # the drain outlasts the longest answer at the SLO's mean gap
    assert mix["drain_s"] * 1e3 >= longest * mix["slo"]["mean_gap_ms"]
    assert mix["engine"]["beam_width"] == fam.CHECK_TOPK


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
    assert [c["name"] for c in manifest["configs"]][-1] == "glm-5.3-flash"
    assert [w["name"] for w in manifest["workloads"]][-1] == CELL
    assert [m["name"] for m in manifest["per_layer"]][-4:] == [
        "dsa_select_share_pct", "dsa_decode_roofline", "dsa_share_pct",
        "mhc_share_pct"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
