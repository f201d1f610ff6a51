"""The ``window_mtp_moe_lm`` family, its two readers and the accepted
readers its cell lists, on the CPU mesh: the serve driver end to end at a
toy K-EXAONE-shaped configuration (``tests/data``: its own manifest
``BENCHMARK-window-mtp.json``, a twin of the configuration and of the mix)
with verify ticks in the window, the readers on hand-built counters and on
this family's op texts, the real configuration file against the catalog
row's published keys, and the check that adding the cell changed no file the
benchmark had. Every number these runs print names ``platform: cpu``: none is
a measurement. Run by hand: ``pytest benchmark/tests`` (not part of
tier-1)."""
import contextlib
import io
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.families import window_mtp_moe_lm as fam
from benchmark.layer_metrics import (kv_held_vs_uniform_pct,
                                     mixed_attn_roofline, moe_held_roofline,
                                     moe_held_rows_pct, mtp_accept_pct,
                                     mtp_tokens_per_live_tick)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-window-mtp.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "kexaone-serve-reason"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=3.0):
    import jax

    cell = harness.load_cell("tiny-serve-reason-mtp", manifest=MANIFEST,
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
    return next(json.loads(ln)["window_mtp_moe_lm.check"]
                for ln in err.splitlines()
                if ln.startswith('{"window_mtp_moe_lm.check"'))


def test_untraced_line_is_the_contract():
    cell, line, out, err = run(traced=False)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    notes = json.loads(out.splitlines()[0])["notes"]
    assert notes["logit_gap_positions"] == 4        # the four readings
    assert notes["logit_gap_max"] <= cell.mix["check"]["logit_gap_tol"]
    check = _check_line(err)
    assert check["served_positions"] > 0 and check["draft_positions"] > 0
    assert check["draft_unequal_share"] == 0.0
    assert check["draft_logprob_err_p90"] < fam.CHECK_DRAFT_LOGPROB_TOL
    assert check["replays_equal_to_timed"] == check["requests"]
    assert check["replay_mtp_accepted"] > 0         # pairs were served


@pytest.mark.parametrize("variant", ["mtp_halves_swapped", "mtp_windowed"])
def test_a_wrong_drafting_block_is_not_correct(monkeypatch, variant):
    """The control, through the harness's own comparison: the reference's
    drafting block gets its halves swapped, or reads only a window of its
    K/V rows; the emitted tokens and the stack's logits cannot see either,
    a draft reading fails the run (the argmax for the halves, the block's
    log-probs alone for the window)."""
    real = fam._rows_logits

    def wrong(config, w, seq, rows, draft_rows=None, v=""):
        return real(config, w, seq, rows, draft_rows, v or variant)

    monkeypatch.setattr(fam, "_rows_logits", wrong)
    cell, line, out, err = run(traced=False)
    assert line["correct"] is False and line["failed"] == 0
    check = _check_line(err)
    assert check["draft_logprob_err_p90"] > fam.CHECK_DRAFT_LOGPROB_TOL
    assert (check["draft_unequal_share"] > fam.CHECK_DRAFT_UNEQUAL_TOL) \
        == (variant == "mtp_halves_swapped")
    assert check["served_logprob_err_p90"] < fam.CHECK_LOGPROB_TOL


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense MHA model's: no call of two kinds in it
    assert {"mtp_accept_pct", "mtp_tokens_per_live_tick",
            "kv_held_vs_uniform_pct", "moe_held_rows_pct",
            "tick_feed_host_arrays"} <= got
    assert "mixed_attn_roofline" not in got
    assert 0.0 < line["metrics"]["mtp_accept_pct"]["value"] <= 100.0
    assert 1.0 < line["metrics"]["mtp_tokens_per_live_tick"]["value"] <= 2.0
    # one plane a tick (a tick under way at an edge of the window has fed
    # its plane and not yet counted its step: a hundredth either way here)
    assert line["metrics"]["tick_feed_host_arrays"]["value"] \
        == pytest.approx(1.0, abs=0.03)
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


def _call(layers, pages, name="paged_attention_decode.2", rows=128):
    pool = f"bf16[{layers},{pages},64,1024]{{3,2,1,0:T(8,128)(2,1)}}"
    return (f"%{name} = bf16[{rows},8,1024]{{2,1,0}} custom-call(s32[1]{{0}} "
            f"%l, s32[{rows * 96}]{{0}} %t, s32[{rows}]{{0}} %n, "
            f"bf16[{rows},64,1024]{{2,1,0}} %q, {pool} %k, {pool} %v), "
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


def test_the_two_scheduler_readers_read_the_engines_counters():
    counted = {"mtp_drafted": 1000, "mtp_accepted": 520,
               "decode_tokens": 1500, "decode_live_rows": 1000}
    assert mtp_accept_pct.read(None, [], counted, Cell) == 52.0
    assert mtp_tokens_per_live_tick.read(None, [], counted, Cell) == 1.5
    # an engine without a drafting block (every other cell, the parent)
    plain = {"decode_tokens": 1500, "decode_steps": 100}
    assert mtp_accept_pct.read(None, [], plain, Cell) is None
    assert mtp_tokens_per_live_tick.read(None, [], plain, Cell) is None
    assert mtp_accept_pct.read(None, [], {}, Cell) is None


def test_the_kinds_are_told_by_three_and_six_pool_layers(capsys):
    """The full-attention pools hold the stack's two such layers AND the
    drafting block's (3), the window pools six: the unedited
    ``mixed_attn_roofline`` reads them (a verify tick's walk reads the
    pages in reach ONCE for both positions, so the pages read are also the
    least it has to: no second roofline of the walk)."""
    cfg = Cell.config
    assert fam.attention_call_kind(3, cfg) == "global"
    assert fam.attention_call_kind(6, cfg) == "window"
    assert fam.attention_call_kind(2, cfg) is None
    assert fam.pool_layer_counts(cfg) == {"global": 3, "window": 6}
    page = 2.0 * 64 * 1024 * 2          # the K and the V tile, bf16
    # ten ticks (hand numbers): 4000 global pages a layer a tick read, 460
    # window pages
    counted = {"decode_steps": 10,
               "paged_attn_pages_read_global": 40000,
               "paged_attn_pages_read_window": 4600}
    read_g, read_w = 4000 * page / 819e9, 460 * page / 819e9
    tr = _trace([(_call(3, 4096), 2 * read_g), (_call(6, 512), 2 * read_w),
                 (_call(3, 4096, "paged_attention_decode.7"), 2 * read_g)])
    assert mixed_attn_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    capsys.readouterr()


def test_the_unedited_cache_and_expert_readers_read_this_family(capsys):
    cfg = Cell.config
    # kv_held_vs_uniform_pct indexes num_hidden_layers and the 0 / 1 twin
    # of layer_types: 6 window + 2 full layers of the stack
    counted = {"kv_pages_uniform_equiv": 1000, "kv_pages_held_global": 1000,
               "kv_pages_held_window": 100}
    assert kv_held_vs_uniform_pct.read(None, [], counted, Cell) \
        == pytest.approx(100.0 * (1000 * 2 + 100 * 6) / 8000)
    ragged = ("%ragged-dot.5 = f32[1024,2048]{1,0} custom-call(bf16[1024,6144]"
              "{1,0} %a, bf16[56,6144,2048]{2,1,0} %w, s32[56]{0} %g), "
              "custom_call_target=\"ragged_dot\"")
    assert fam.moe_op(ragged, cfg) == "grouped_matmul"
    for text, part in (
            ("%fusion.1 = bf16[8,6144,2048]{2,1,0} fusion(bf16[1,8,6144,2048]"
             "{3,2,1,0} %w)", "grouped_matmul"),
            ("%fusion.2 = f32[128,2048]{1,0} fusion(bf16[7,6144,2048]{2,1,0} "
             "%s)", "shared_expert"),
            ("%fusion.3 = f32[128,128]{1,0} fusion(f32[128,6144]{1,0} %h, "
             "bf16[6144,128]{1,0} %r)", "route"),
            ("%sort.1 = f32[128,19200]{1,0} sort(f32[128,19200]{1,0} %z)",
             None),
            # the dense layer's planes are no expert's
            ("%fusion.4 = f32[128,18432]{1,0} fusion(bf16[1,6144,18432]"
             "{2,1,0} %d)", None)):
        assert fam.moe_op(text, cfg) == part, text
    counted = {"moe_assignments": 16000, "moe_held_assignments": 1000,
               "moe_absent_assignments": 15000, "moe_touched_experts": 64,
               "moe_layer_calls": 8}
    c = fam.grouped_matmul_cost(cfg, 64.0, 6144, 2048, 8.0)
    least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
    tr = _trace([(ragged, 2 * least)])
    assert moe_held_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert moe_held_rows_pct.read(None, [], counted, Cell) \
        == pytest.approx(6.25)
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_three():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in row["config"].items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "num_experts",
                      "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 8, 19200)
    assert {k: config["reduced_from"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    assert config["router_outputs"] == 128
    assert config["source"].startswith(row["source_url"])
    assert cell.family is fam and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mtp_accept_pct", "mtp_tokens_per_live_tick",
            "mixed_attn_roofline",
            "kv_held_vs_uniform_pct", "moe_share_pct", "moe_held_roofline",
            "moe_held_rows_pct", "tick_feed_host_arrays"} <= names
    # num_experts is the HELD count here; decode_tokens / (steps x slots)
    # would read past the live share
    assert not names & {"moe_load_imbalance", "decode_occupancy_pct",
                        "paged_attn_roofline", "paged_attn_page_share_pct"}
    assert cell.mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    spec = fam.spec_of(config)
    assert spec.experts_held == (0, 8) and spec.num_experts == 128
    assert spec.layer_pattern == ("window+rope",) * 3 + ("full+rope",)
    assert (spec.first_dense, spec.window, spec.draft_block) == (1, 128, True)
    assert spec.n_params() == 4_394_717_184
    assert spec.draft_param_count() == 529_299_456
    e = cell.mix["engine"]
    longest = cell.mix["prompt"]["user"]["max"] + cell.mix["output"]["max"]
    assert longest <= e["max_len"] == 6144 == config["assumed"]["max_len"]
    assert (e["page_size"], e["prefill_chunk"], e["slots"]) == (64, 256, 64)
    assert cell.mix["prompt"]["shared_prefix"]["prob"] == 0.0
    assert set(config["assumed"]) >= {
        "norm_placement", "qk_norm", "positions", "router", "mtp",
        "mtp_projection_layout", "mtp_init", "embedding_scale", "max_len"}
