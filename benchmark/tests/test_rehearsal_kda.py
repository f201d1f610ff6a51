"""The ``kda_mla_moe_lm`` family and its three readers on the CPU mesh: the
serve driver end to end at a toy Ling-3.0-shaped configuration
(``tests/data``: its own manifest ``BENCHMARK-kda.json``, a twin of the
configuration and of the mix), the readers on hand-built counters and
device events, the real configuration file against the catalog row's
published keys, and the check that adding the cell changed no file the
benchmark had. Every number these runs print names ``platform: cpu``: none
is a measurement. Run by hand: ``pytest benchmark/tests`` (not part of
tier-1)."""
import contextlib
import io
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.families import kda_mla_moe_lm as fam
from benchmark.layer_metrics import (kda_decode_roofline, kda_share_pct,
                                     moe_held_roofline, state_cache_share_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-kda.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "ling3-serve-reason"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=2.0):
    import jax

    cell = harness.load_cell("tiny-serve-reason", manifest=MANIFEST,
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
    assert notes["logit_gap_positions"] > 0
    assert notes["logit_gap_max"] <= cell.mix["check"]["logit_gap_tol"]
    assert notes["prefix_hit_tokens"] == 0      # the index is not consulted


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense MHA model's: no KDA kernel in it
    assert {"moe_held_rows_pct", "state_cache_share_pct"} <= got
    assert not got & {"kda_decode_roofline", "mla_decode_roofline"}
    assert 0.0 < line["metrics"]["state_cache_share_pct"]["value"] < 100.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "ling-3.0-flash.json")) as f:
        return json.load(f)


def _kda_call(name="kda_decode_step.3", slots=256):
    return (f"%{name} = (f32[{slots},2,16,128]{{3,2,1,0}}, f32[5,{slots},32,"
            f"128,128]{{4,3,2,1,0}}) custom-call(s32[1]{{0}} %l, f32[{slots},"
            f"2,128,16]{{3,2,1,0}} %a, f32[5,{slots},32,128,128]{{4,3,2,1,0:"
            "T(8,128)} %s), custom_call_target=\"tpu_custom_call\"")


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


def test_kda_roofline_counts_the_state_once_each_way(capsys):
    """One call = one layer of a 256-slot tick: 256 x 32 x 128 x 128 x 4 B
    read and written = 1.07 GB = 1.31 ms at 819 GB/s. Calls at twice that
    read 50%; an op that is not the kernel is not counted."""
    call = fam.kda_decode_call(_kda_call())
    assert call == {"slots": 256, "heads": 32, "k": 128, "v": 128}
    cost = fam.kda_decode_cost(Cell.config, **call)
    assert cost["bytes"] == 2 * 256 * 2_097_152
    least = cost["bytes"] / 819e9
    assert least == pytest.approx(1.311e-3, rel=0.01)
    tr = _trace([(_kda_call(), 2 * least),
                 (_kda_call("kda_decode_step.7"), 2 * least),
                 (_kda_call("paged_mla_decode.1"), 1.0),
                 ("%fusion.1 = f32[256,39296]{1,0} fusion()", 1e-3)])
    assert kda_decode_roofline.read(tr, [], {}, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert '"calls": 2' in capsys.readouterr().out
    assert kda_decode_roofline.read(
        _trace([("%fusion.1 = f32[8]{0} fusion()", 1e-3)]), [], {},
        Cell) is None
    assert kda_decode_roofline.read(None, [], {}, Cell) is None

    class Other(Cell):
        from benchmark.families import mla_moe_lm as family

    assert kda_decode_roofline.read(tr, [], {}, Other) is None
    assert "left out" in capsys.readouterr().err


def test_kda_share_tells_the_layers_parts(capsys):
    cfg = Cell.config
    assert fam.kda_op(_kda_call(), cfg) == "step"
    assert fam.kda_op("%fusion.5 = f32[1,32,128,128]{3,2,1,0} fusion("
                      "f32[5,256,32,128,128]{4,3,2,1,0} %s)", cfg) == "state"
    assert fam.kda_op("%fusion.6 = f32[1,32,64,64]{3,2,1,0} fusion("
                      "f32[1,32,64,64,128]{4,3,2,1,0} %d)", cfg) == "state"
    assert fam.kda_op("%fusion.7 = f32[256,12288]{1,0} fusion(bf16[256,2560]"
                      "{1,0} %h, bf16[2560,12288]{1,0} %w)", cfg) == "project"
    assert fam.kda_op("%fusion.8 = f32[256,39296]{1,0} fusion()", cfg) is None
    tr = _trace([(_kda_call(), 3e-3),
                 ("%fusion.7 = f32[256,12288]{1,0} fusion(bf16[2560,12288]"
                  "{1,0} %w)", 1e-3),
                 ("%fusion.8 = f32[256,39296]{1,0} fusion()", 4e-3)])
    assert kda_share_pct.read(tr, [], {}, Cell) == pytest.approx(50.0)
    assert '"step": 37.5' in capsys.readouterr().out
    assert kda_share_pct.read(None, [], {}, Cell) is None


def test_state_share_reads_the_tick_summed_counters():
    counted = {"state_bytes_live_ticks": 3e9, "kv_bytes_held_ticks": 1e9}
    assert state_cache_share_pct.read(None, [], counted, Cell) == 75.0
    assert state_cache_share_pct.read(None, [], {}, Cell) is None
    assert state_cache_share_pct.read(
        None, [], {"kv_bytes_held_ticks": 5}, Cell) is None


def test_the_unedited_expert_readers_read_this_family(capsys):
    """``moe_held_roofline`` indexes ``hidden_size`` /
    ``moe_intermediate_size`` and calls the family's ``moe_op`` /
    ``grouped_matmul_cost``: a 256-row tick routes 2048 assignments, a
    quarter to the 128 held experts."""
    cfg = Cell.config
    ragged = ("%ragged-dot.5 = f32[2048,768]{1,0} custom-call(bf16[2048,2560]"
              "{1,0} %a, bf16[512,2560,768]{2,1,0} %w, s32[512]{0} %g), "
              "custom_call_target=\"ragged_dot\"")
    assert fam.moe_op(ragged, cfg) == "grouped_matmul"
    assert fam.moe_op("%fusion.2 = f32[256,768]{1,0} fusion(bf16[4,2560,768]"
                      "{2,1,0} %s)", cfg) == "shared_expert"
    assert fam.moe_op("%fusion.3 = f32[256,512]{1,0} fusion(f32[256,2560]"
                      "{1,0} %h, bf16[2560,512]{1,0} %r)", cfg) == "route"
    assert fam.moe_op("%sort.1 = f32[256,39296]{1,0} sort(f32[256,39296]"
                      "{1,0} %z)", cfg) is None
    counted = {"moe_assignments": 8000, "moe_held_assignments": 2000,
               "moe_absent_assignments": 6000, "moe_touched_experts": 400,
               "moe_layer_calls": 4}
    c = fam.grouped_matmul_cost(cfg, 512.0, 2560, 768, 100.0)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    tr = _trace([(ragged, 2 * least)])
    assert moe_held_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_three():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in row["config"].items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "num_experts",
                      "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 128, 39296)
    assert {k: config["reduced_from"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 42, "num_experts": 512, "vocab_size": 157184}
    assert config["router_outputs"] == 512
    assert config["source"].startswith(row["source_url"])
    assert cell.family is fam and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"kda_decode_roofline", "kda_share_pct", "state_cache_share_pct",
            "mla_decode_roofline", "moe_held_roofline", "moe_held_rows_pct",
            "moe_share_pct", "paged_attn_page_share_pct"} <= names
    # num_experts is the HELD count in this file (the published key, cut),
    # so the unedited moe_load_imbalance would scale by 128 for 512
    assert not names & {"moe_load_imbalance", "moe_roofline",
                        "paged_attn_roofline", "mixed_attn_roofline",
                        "kv_held_vs_uniform_pct"}
    assert cell.mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    spec = fam.spec_of(config)
    assert spec.experts_held == (0, 128) and spec.num_experts == 512
    assert spec.n_params() == 3_691_552_544
    assert spec.layer_pattern == ("kda",) * 5 + ("mla",)
    e = cell.mix["engine"]
    longest = cell.mix["prompt"]["user"]["max"] + cell.mix["output"]["max"]
    assert longest <= e["max_len"] == 12288 == config["assumed"]["max_len"]
    assert (e["page_size"], e["prefill_chunk"]) == (256, 256)
    assert cell.mix["prompt"]["shared_prefix"]["prob"] == 0
    assert set(config["assumed"]) >= {
        "kda_decay", "kda_gate", "kda_conv", "mla_qk_norm", "mla_gate",
        "router", "router_bias_values", "state_dtype", "mtp",
        "embedding_scale", "max_len"}
