"""The ``kda_gqa_moe_lm`` family, its two readers and the accepted readers its
cell lists, on the CPU mesh: the serve driver end to end at a toy
Solar-Open2-shaped configuration (``tests/data``: its own manifest
``BENCHMARK-kda-gqa.json``, a twin of the configuration and of the mix) with
snapshot hits in the window, the readers on hand-built counters and on this
family's op texts, the real configuration file against the catalog row's
published keys, and the check that adding the cell changed no file the
benchmark had. Every number these runs print names ``platform: cpu``: none is
a measurement. Run by hand: ``pytest benchmark/tests`` (not part of tier-1)."""
import contextlib
import io
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.families import kda_gqa_moe_lm as fam
from benchmark.families import paged_attention
from benchmark.layer_metrics import (kda_decode_roofline, kda_share_pct,
                                     moe_held_roofline, moe_held_rows_pct,
                                     paged_attn_page_share_pct,
                                     paged_attn_roofline,
                                     state_cache_share_pct,
                                     state_snapshot_cutback_pct,
                                     state_snapshot_hit_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-kda-gqa.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "solar2-serve-agent"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=3.0):
    import jax

    cell = harness.load_cell("tiny-serve-agent", manifest=MANIFEST,
                             data_dir=DATA)
    if traced:
        real = trace_reduce.load
        monkeypatch.setattr(trace_reduce, "load",
                            lambda path: real(RECORDED))
        monkeypatch.setattr(harness, "OUT_DIR",
                            os.path.join(DATA, ".bench_out"))
    buf, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(err):
        line = harness.run_cell(cell, 2**31 + 5, seconds, traced,
                                jax.devices()[:1], time.monotonic())
    assert json.loads(json.dumps(line)) == line
    return cell, line, buf.getvalue(), err.getvalue()


def _check_line(err):
    return next(json.loads(ln)["kda_gqa_moe_lm.check"]
                for ln in err.splitlines()
                if ln.startswith('{"kda_gqa_moe_lm.check"'))


def test_untraced_line_is_the_contract():
    cell, line, out, err = run(traced=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    notes = json.loads(out.splitlines()[0])["notes"]
    assert notes["logit_gap_positions"] > 0
    assert notes["logit_gap_max"] <= cell.mix["check"]["logit_gap_tol"]
    assert notes["prefix_hit_tokens"] > 0       # snapshot hits in the window
    check = _check_line(err)
    # every checked request with a boundary in its prompt is replayed from
    # a snapshot, and serves what its cold replay served
    assert check["replays_from_a_snapshot"] >= 1
    assert check["hit_replays_equal_to_cold"] == check["requests"]
    assert check["state_bits_differ_max"] == 0
    # ... and the TIMED engine itself (its slots, the rows the window
    # took) restores the checked prompts to their cold state, to the bit
    assert check["timed_engine_slots"] == cell.mix["engine"]["slots"]
    assert check["timed_prompts_from_a_snapshot"] >= 1
    assert check["timed_restore_state_vs_cold_max"] == 0.0
    assert check["timed_first_tokens_equal_to_cold"] == check["requests"]
    assert check["timed_engine_counted_to_the_drain"][
        "state_snapshots_taken"] >= 1


def test_a_misplaced_snapshot_in_the_timed_engine_is_not_correct(
        monkeypatch):
    """The control, through the harness's own comparison: the TIMED
    engine's snapshot rows are moved by one after the drain, before the
    family's hook runs; the twin is sound, so only the reading taken on
    the timed engine can fail the run, and it does."""
    real = fam.reference_logit_gaps

    def planted(config, w, results):
        fam.misplace_snapshots(fam._ENGINES[id(config)][1])
        return real(config, w, results)

    monkeypatch.setattr(fam, "reference_logit_gaps", planted)
    cell, line, out, err = run(traced=False)
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["logit_gap_max"]["value"] \
        > line["checks"]["logit_gap_max"]["limit"]
    check = _check_line(err)
    assert check["timed_prompts_from_a_snapshot"] >= 1
    assert check["timed_restore_state_vs_cold_max"] \
        > fam.CHECK_RESTORE_STATE_TOL
    assert check["restore_state_vs_cold_max"] == 0.0        # the twin's
    assert check["served_logprob_err_p95"] < cell.mix["check"]["logit_gap_tol"]


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense MHA model's: no KDA kernel in it
    assert {"moe_held_rows_pct", "state_cache_share_pct",
            "state_snapshot_hit_pct", "state_snapshot_cutback_pct",
            "paged_attn_page_share_pct"} <= got
    assert "kda_decode_roofline" not in got
    assert 0.0 < line["metrics"]["state_cache_share_pct"]["value"] < 100.0
    assert 0.0 < line["metrics"]["state_snapshot_hit_pct"]["value"] < 100.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "solar-open2-250b.json")) as f:
        return json.load(f)


def _kda_call(name="kda_decode_step.3", slots=64):
    return (f"%{name} = (f32[{slots},4,16,128]{{3,2,1,0}}, f32[3,{slots},64,"
            f"128,128]{{4,3,2,1,0}}) custom-call(s32[1]{{0}} %l, f32[{slots},"
            f"4,128,16]{{3,2,1,0}} %a, f32[3,{slots},64,128,128]{{4,3,2,1,0:"
            "T(8,128)} %s), custom_call_target=\"tpu_custom_call\"")


def _gqa_call(name="paged_attention_decode.2"):
    return (f"%{name} = bf16[64,8,1024]{{2,1,0}} custom-call(s32[1]{{0}} %l, "
            "s32[64,64]{1,0} %t, s32[64]{0} %n, bf16[64,64,1024]{2,1,0} %q, "
            "bf16[1,3072,256,1024]{3,2,1,0:T(8,128)(2,1)} %k, "
            "bf16[1,3072,256,1024]{3,2,1,0:T(8,128)(2,1)} %v), "
            "custom_call_target=\"tpu_custom_call\"")


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


def test_the_two_new_readers_read_the_engines_counters():
    counted = {"prompt_tokens_admitted": 1000, "prefix_hit_tokens": 900,
               "state_snapshots_taken": 3,
               "state_snapshot_cutback_tokens": 100}
    assert state_snapshot_hit_pct.read(None, [], counted, Cell) == 90.0
    assert state_snapshot_cutback_pct.read(None, [], counted, Cell) == 10.0
    # an engine that takes no snapshots (every other cell, the parent)
    plain = {"prompt_tokens_admitted": 1000, "prefix_hit_tokens": 500}
    assert state_snapshot_hit_pct.read(None, [], plain, Cell) is None
    assert state_snapshot_cutback_pct.read(None, [], plain, Cell) is None
    assert state_snapshot_hit_pct.read(None, [], {}, Cell) is None
    # nothing matched yet: no share to give
    assert state_snapshot_cutback_pct.read(
        None, [], {"state_snapshot_cutback_tokens": 0}, Cell) is None
    assert state_snapshot_hit_pct.read(
        None, [], {"prompt_tokens_admitted": 10, "state_snapshots_taken": 0},
        Cell) == 0.0


def test_kda_roofline_reads_64_heads_of_state(capsys):
    """One call = one layer of a 64-slot tick: 64 x 64 x 128 x 128 x 4 B
    read and written = 537 MB = 0.66 ms at 819 GB/s."""
    call = fam.kda_decode_call(_kda_call())
    assert call == {"slots": 64, "heads": 64, "k": 128, "v": 128}
    cost = fam.kda_decode_cost(Cell.config, **call)
    assert cost["bytes"] == 2 * 64 * 4_194_304
    least = cost["bytes"] / 819e9
    tr = _trace([(_kda_call(), 2 * least),
                 (_kda_call("kda_decode_step.7"), 2 * least),
                 (_gqa_call(), 1.0)])
    assert kda_decode_roofline.read(tr, [], {}, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    capsys.readouterr()


def test_the_one_kind_attention_reader_reads_the_gqa_call(capsys):
    """``paged_attn_roofline`` (unedited) tells the softmax layer's call by
    its name and reads the page geometry off its pool operand: a tick that
    walks 800 pages of 256 x 1024 bf16, K and V."""
    call = paged_attention.decode_call(_gqa_call())
    assert call == {"page_size": 256, "kv_width": 1024, "itemsize": 2}
    assert paged_attention.decode_call(_kda_call()) is None
    counted = {"decode_steps": 10, "paged_attn_pages_read": 8000,
               "paged_attn_table_pages": 10 * 64 * 64}
    least = 2.0 * 800 * 256 * 1024 * 2 / 819e9
    tr = _trace([(_gqa_call(), 4 * least), (_kda_call(), 1.0)])
    assert paged_attn_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(25.0, rel=1e-6)
    assert paged_attn_page_share_pct.read(None, [], counted, Cell) \
        == pytest.approx(100.0 * 8000 / (10 * 64 * 64))
    capsys.readouterr()


def test_kda_share_tells_the_layers_parts_and_not_the_gqa_projections(capsys):
    """On op texts of the cell's own traced run (my chip run, PR 45)."""
    cfg = Cell.config
    assert fam.kda_op(_kda_call(), cfg) == "step"
    assert fam.kda_op("%fusion.5 = f32[1,64,128,128]{3,2,1,0} fusion("
                      "f32[3,64,64,128,128]{4,3,2,1,0} %s)", cfg) == "state"
    assert fam.kda_op("%fusion.4 = f32[3,64,64,128,128]{4,3,2,1,0} fusion("
                      "f32[3,64,64,128,128]{4,3,2,1,0} %snap)", cfg) == "state"
    assert fam.kda_op("%multiply_reduce_fusion.21 = (f32[64,64,64], "
                      "f32[64,64,64]) fusion(f32[64,64,128] %copy.465, "
                      "f32[64,64,128] %copy.466, pred[64,64] %i)", cfg) \
        == "state"
    # q | k | v + convolution: the stack's or the taps' axis rides along
    for text in (
            "%divide_multiply_fusion.2 = (f32[64,1,24576], bf16[64,1,24576]) "
            "fusion(f32[64,3,24576] %fusion.170, f32[24576] %bitcast.733, "
            "bf16[64,4096] %m, bf16[3,4096,24576] %ro_args_5_.1)",
            "%fusion.243 = f32[1,256,24576] fusion(bf16[256,4096] %m, "
            "bf16[3,4096,24576] %ro_args_5_.1), kind=kOutput",
            "%fusion.35 = bf16[192,24576] fusion(bf16[64,4,24576] %copy.132, "
            "s32[192] %reshape.1032), kind=kCustom",
            "%copy.116 = bf16[3,64,3,24576] copy(bf16[3,64,3,24576] %rw)",
            "%fusion.9 = f32[64,8192]{1,0} fusion(bf16[64,128]{1,0} %h, "
            "bf16[3,128,8192]{2,1,0} %w)",
            "%fusion.10 = bf16[64,128]{1,0} fusion(bf16[64,4096]{1,0} %h, "
            "bf16[3,4096,128]{2,1,0} %w)"):
        assert fam.kda_op(text, cfg) == "project", text
    # the head and the sampling plane have the SAME width (an eighth of
    # the vocabulary = 3 x 64 x 128) and are not KDA's
    for text in (
            "%broadcast_divide_fusion = (f32[64,24576], f32[64,24576]) "
            "fusion(f32[64] %b, bf16[4096,24576] %ro_args_2_.1, f32[64,4096] "
            "%g, f32[4096] %c, f32[64] %a), kind=kOutput",
            "%iota_reduce_fusion.1 = (bf16[64], s32[64]) fusion(u32[64] %g, "
            "f32[64,24576] %get-tuple-element.523, f32[64] %b)",
            "%select_reduce_fusion.4 = f32[64] fusion(f32[64,24576] %g, "
            "s32[64,24576] %i, s32[64] %f), kind=kLoop",
            "%multiply_reduce_fusion.6 = f32[24576] fusion(bf16[4096,24576] "
            "%ro_args_2_.1, f32[4096] %fusion.633), kind=kLoop",
            # ... nor the softmax layer's [4096, 8192] projections
            "%fusion.278 = bf16[64,8192] fusion(bf16[1,4096,8192] %ro_args_18_.1"
            ", bf16[8,8,64,128] %b, bf16[64,4096] %f, f32[4096] %c)"):
        assert fam.kda_op(text, cfg) is None, text
    tr = _trace([(_kda_call(), 3e-3),
                 ("%fusion.9 = f32[64,8192]{1,0} fusion(bf16[3,128,8192]"
                  "{2,1,0} %w)", 1e-3),
                 ("%fusion.8 = f32[64,320]{1,0} fusion()", 4e-3)])
    assert kda_share_pct.read(tr, [], {}, Cell) == pytest.approx(50.0)
    assert '"step": 37.5' in capsys.readouterr().out


def test_state_share_reads_the_tick_summed_counters():
    counted = {"state_bytes_live_ticks": 3e9, "kv_bytes_held_ticks": 1e9}
    assert state_cache_share_pct.read(None, [], counted, Cell) == 75.0


def test_the_unedited_expert_readers_read_this_family(capsys):
    """``moe_held_roofline`` indexes ``hidden_size`` /
    ``moe_intermediate_size`` and calls the family's ``moe_op`` /
    ``grouped_matmul_cost``: a 64-row tick routes 512 assignments, an
    eighth to the 40 held experts."""
    cfg = Cell.config
    ragged = ("%ragged-dot.5 = f32[512,1280]{1,0} custom-call(bf16[512,4096]"
              "{1,0} %a, bf16[160,4096,1280]{2,1,0} %w, s32[160]{0} %g), "
              "custom_call_target=\"ragged_dot\"")
    assert fam.moe_op(ragged, cfg) == "grouped_matmul"
    assert fam.moe_op("%fusion.1 = bf16[40,4096,1280]{2,1,0} fusion(bf16[4,40,"
                      "4096,1280]{3,2,1,0} %w)", cfg) == "grouped_matmul"
    assert fam.moe_op("%fusion.2 = f32[64,1280]{1,0} fusion(bf16[4,4096,1280]"
                      "{2,1,0} %s)", cfg) == "shared_expert"
    assert fam.moe_op("%fusion.3 = f32[64,320]{1,0} fusion(f32[64,4096]"
                      "{1,0} %h, bf16[4096,320]{1,0} %r)", cfg) == "route"
    assert fam.moe_op("%sort.1 = f32[64,24576]{1,0} sort(f32[64,24576]"
                      "{1,0} %z)", cfg) is None
    counted = {"moe_assignments": 8000, "moe_held_assignments": 1000,
               "moe_absent_assignments": 7000, "moe_touched_experts": 100,
               "moe_layer_calls": 4}
    c = fam.grouped_matmul_cost(cfg, 64.0, 4096, 1280, 25.0)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    tr = _trace([(ragged, 2 * least)])
    assert moe_held_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert moe_held_rows_pct.read(None, [], counted, Cell) \
        == pytest.approx(12.5)
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_three():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Solar-Open2-250B")
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in row["config"].items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 40, 24576)
    assert {k: config["reduced_from"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 48, "n_routed_experts": 320,
        "vocab_size": 196608}
    assert config["router_outputs"] == 320
    assert config["source"].startswith(row["source_url"])
    assert cell.family is fam and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"kda_decode_roofline", "kda_share_pct", "state_cache_share_pct",
            "paged_attn_roofline", "moe_held_roofline", "moe_held_rows_pct",
            "moe_share_pct", "paged_attn_page_share_pct",
            "state_snapshot_hit_pct", "state_snapshot_cutback_pct"} <= names
    # n_routed_experts is the HELD count in this file (the published key,
    # cut), so the unedited moe_load_imbalance has no key to scale by
    assert not names & {"moe_load_imbalance", "moe_roofline",
                        "mla_decode_roofline", "mixed_attn_roofline",
                        "kv_held_vs_uniform_pct"}
    assert cell.mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    spec = fam.spec_of(config)
    assert spec.experts_held == (0, 40) and spec.num_experts == 320
    assert spec.n_params() == 3_308_377_920
    assert spec.layer_pattern == ("gqa", "kda", "kda", "kda")
    e, sp = cell.mix["engine"], cell.mix["prompt"]["shared_prefix"]
    longest = (sp["tokens"] + cell.mix["prompt"]["user"]["max"]
               + cell.mix["output"]["max"])
    assert longest <= e["max_len"] == 16384 == config["assumed"]["max_len"]
    assert (e["page_size"], e["prefill_chunk"], e["snapshot_stride"],
            e["n_snapshots"], e["slots"]) == (256, 256, 16, 64, 64)
    assert (sp["prob"], sp["count"], sp["tokens"]) == (0.8, 6, 12288)
    assert set(config["assumed"]) >= {
        "gqa", "gqa_gate", "kda_decay", "kda_proj_rank", "kda_gate",
        "kda_conv", "router", "router_bias_values", "state_dtype",
        "embedding_scale", "max_len"}
