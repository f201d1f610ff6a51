"""The ``mamba2_gqa_moe_lm`` family and its three readers on the CPU mesh:
the serve driver end to end at a toy Nemotron-H-shaped configuration
(``tests/data``: its own manifest ``BENCHMARK-mamba2.json``, a twin of the
configuration and of the mix), the readers on hand-built counters and device
events, the real configuration file against the catalog row's published keys,
and the real cell's entries. Every number these runs print names ``platform:
cpu``: none is a measurement. Run by hand: ``pytest benchmark/tests`` (not
part of tier-1). The mix's ``rated`` block is held by
``test_serve_rating.py``'s test of every rated mix."""
import contextlib
import io
import json
import os
import time

import pytest

from benchmark import harness, trace_reduce
from benchmark.families import mamba2_gqa_moe_lm as fam
from benchmark.layer_metrics import (latent_moe_roofline,
                                     mamba_decode_roofline, mamba_share_pct,
                                     moe_held_roofline, state_cache_share_pct)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-mamba2.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
CELL = "nemotron3s-serve-chat"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=2.0):
    import jax

    cell = harness.load_cell("tiny-serve-chat", manifest=MANIFEST,
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
    # the recorded trace is a dense MHA model's: no Mamba-2 kernel in it
    assert {"moe_held_rows_pct", "state_cache_share_pct",
            "moe_kernel_calls_pct"} <= got
    assert not got & {"mamba_decode_roofline", "latent_moe_roofline"}
    assert 0.0 < line["metrics"]["state_cache_share_pct"]["value"] < 100.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
def _config():
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "nemotron-3-super-120b-a12b.json")) as f:
        return json.load(f)


def _step_call(name="mamba2_decode_step.3", slots=128):
    return (f"%{name} = (f32[{slots},8,64,16]{{3,2,1,0}}, f32[5,{slots},128,"
            f"64,128]{{4,3,2,1,0}}) custom-call(s32[1]{{0}} %l, f32[{slots},"
            f"8,64,16]{{3,2,1,0}} %a, f32[{slots},8,64,16]{{3,2,1,0}} %x, "
            f"f32[{slots},8,1,128]{{3,2,1,0}} %b, f32[{slots},8,1,128]"
            f"{{3,2,1,0}} %c, f32[5,{slots},128,64,128]{{4,3,2,1,0:T(8,128)}}"
            " %s), custom_call_target=\"tpu_custom_call\"")


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


def test_mamba_roofline_counts_the_state_once_each_way_and_its_inputs(
        capsys):
    """One call = one layer of a 128-slot tick: 128 x 128 x 64 x 128 x 4 B
    read and written = 1.07 GB, plus 1.3% of columns and rows = 1.33 ms at
    819 GB/s. Calls at twice that read 50%; an op that is not the kernel is
    not counted."""
    call = fam.mamba_decode_call(_step_call())
    assert call == {"slots": 128, "heads": 128, "p": 64, "n": 128,
                    "groups": 8}
    cost = fam.mamba_decode_cost(Cell.config, **call)
    state = 2 * 128 * 4_194_304
    assert state < cost["bytes"] < 1.02 * state
    least = cost["bytes"] / 819e9
    tr = _trace([(_step_call(), 2 * least),
                 (_step_call("mamba2_decode_step.7"), 2 * least),
                 (_step_call("kda_decode_step.1"), 1.0),
                 ("%fusion.1 = f32[128,32768]{1,0} fusion()", 1e-3)])
    assert mamba_decode_roofline.read(tr, [], {}, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert '"calls": 2' in capsys.readouterr().out
    assert mamba_decode_roofline.read(
        _trace([("%fusion.1 = f32[8]{0} fusion()", 1e-3)]), [], {},
        Cell) is None
    assert mamba_decode_roofline.read(None, [], {}, Cell) is None

    class Other(Cell):
        from benchmark.families import kda_mla_moe_lm as family

    assert mamba_decode_roofline.read(tr, [], {}, Other) is None
    assert mamba_share_pct.read(tr, [], {}, Other) is None
    assert latent_moe_roofline.read(tr, [], {"moe_layer_calls": 1,
                                             "moe_assignments": 1,
                                             "moe_held_assignments": 1,
                                             "moe_touched_experts": 1},
                                    Other) is None
    assert "left out" in capsys.readouterr().err


def test_mamba_share_tells_the_layers_parts(capsys):
    cfg = Cell.config
    assert fam.mamba_op(_step_call(), cfg) == "step"
    assert fam.mamba_op("%fusion.5 = f32[1,128,64,128]{3,2,1,0} fusion("
                        "f32[5,128,128,64,128]{4,3,2,1,0} %s)", cfg) == "scan"
    assert fam.mamba_op("%fusion.6 = f32[1,8,16,128,128]{4,3,2,1,0} fusion("
                        "f32[1,128,128,128]{3,2,1,0} %d)", cfg) == "scan"
    assert fam.mamba_op("%fusion.7 = f32[1,259,10240]{2,1,0} fusion(bf16[1,3,"
                        "10240]{2,1,0} %h)", cfg) == "conv"
    assert fam.mamba_op("%fusion.8 = f32[256,18560]{1,0} fusion(bf16[256,4096]"
                        "{1,0} %h, bf16[4096,18560]{1,0} %w)",
                        cfg) == "project"
    assert fam.mamba_op("%fusion.9 = f32[256,4096]{1,0} fusion(bf16[256,8192]"
                        "{1,0} %y, bf16[8192,4096]{1,0} %w)",
                        cfg) == "project"
    assert fam.mamba_op("%fusion.10 = f32[128,32768]{1,0} fusion()",
                        cfg) is None
    assert fam.mamba_op("%fusion.11 = f32[256,4096]{1,0} fusion(bf16[4096,"
                        "4608]{1,0} %w)", cfg) is None
    tr = _trace([(_step_call(), 3e-3),
                 ("%fusion.8 = f32[256,18560]{1,0} fusion(bf16[4096,18560]"
                  "{1,0} %w)", 1e-3),
                 ("%fusion.10 = f32[128,32768]{1,0} fusion()", 4e-3)])
    assert mamba_share_pct.read(tr, [], {}, Cell) == pytest.approx(50.0)
    assert '"step": 37.5' in capsys.readouterr().out
    assert mamba_share_pct.read(None, [], {}, Cell) is None


def test_the_latent_reader_prices_a_call_at_the_familys_widths(capsys):
    """A 128-slot tick routes 2816 assignments a layer, a quarter to the 128
    held experts: the up product's result is [2816, 2688], the down
    product's [2816, 1024]. ``moe_held_roofline`` prices by ``hidden_size``
    4096: it skips the down product and prices the up product four times
    too high (it reads over 100), which is why the cell is not on its
    list."""
    cfg = Cell.config
    up = ("%grouped_matmul.5 = f32[2816,2688]{1,0} custom-call(s32[1]{0} %l, "
          "bf16[2816,1024]{1,0} %a, bf16[640,1024,2688]{2,1,0} %w), "
          "custom_call_target=\"tpu_custom_call\"")
    down = ("%grouped_matmul.6 = f32[2816,1024]{1,0} custom-call(s32[1]{0} "
            "%l, bf16[2816,2688]{1,0} %a, bf16[640,2688,1024]{2,1,0} %w), "
            "custom_call_target=\"tpu_custom_call\"")
    assert fam.moe_op(up, cfg) == fam.moe_op(down, cfg) == "grouped_matmul"
    assert fam.moe_op("%fusion.2 = f32[128,1024]{1,0} fusion(bf16[5,4096,"
                      "1024]{2,1,0} %s)", cfg) == "latent"
    assert fam.moe_op("%fusion.3 = f32[128,5376]{1,0} fusion(bf16[5,4096,"
                      "5376]{2,1,0} %s)", cfg) == "shared_expert"
    assert fam.moe_op("%fusion.4 = f32[128,512]{1,0} fusion(f32[128,4096]"
                      "{1,0} %h, bf16[4096,512]{1,0} %r)", cfg) == "route"
    assert fam.moe_op("%sort.1 = f32[128,32768]{1,0} sort(f32[128,32768]"
                      "{1,0} %z)", cfg) is None
    assert fam.expert_widths(cfg) == (1024, 2688)
    counted = {"moe_assignments": 8000, "moe_held_assignments": 2000,
               "moe_absent_assignments": 6000, "moe_touched_experts": 500,
               "moe_layer_calls": 5}
    least = 0.0
    for cols_in, cols_out in ((1024, 2688), (2688, 1024)):
        c = fam.grouped_matmul_cost(cfg, 2816 / 4, cols_in, cols_out, 100.0)
        least += max(c["flops"] / 197e12, c["bytes"] / 819e9)
    tr = _trace([(up, least), (down, least)])
    assert latent_moe_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    assert moe_held_roofline.read(tr, [], counted, Cell) > 100.0
    capsys.readouterr()


def test_state_share_reads_the_tick_summed_counters():
    counted = {"state_bytes_live_ticks": 3e9, "kv_bytes_held_ticks": 1e9}
    assert state_cache_share_pct.read(None, [], counted, Cell) == 75.0


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_three():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    cell = harness.load_cell(CELL)
    config = cell.config
    differ = {k for k, v in row["config"].items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers", "n_routed_experts",
                      "vocab_size"} == set(config["reduced"])
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (11, 128, 32768)
    assert {k: config["reduced_from"][k] for k in config["reduced"]} == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072}
    assert config["router_outputs"] == 512
    assert config["source"].startswith(row["source_url"])
    assert cell.family is fam and cell.mix["kind"] == "serve"
    assert cell.chips == 1
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"mamba_decode_roofline", "mamba_share_pct", "latent_moe_roofline",
            "state_cache_share_pct", "moe_held_rows_pct", "moe_share_pct",
            "moe_kernel_calls_pct", "paged_attn_page_share_pct"} <= names
    # readers that price by hidden_size, or read another recurrence
    assert not names & {"moe_held_roofline", "moe_roofline",
                        "moe_load_imbalance", "kda_decode_roofline",
                        "kda_share_pct", "mixed_attn_roofline"}
    assert cell.mix["check"]["logit_gap_tol"] == fam.CHECK_LOGPROB_TOL
    spec = fam.spec_of(config)
    assert spec.experts_held == (0, 128) and spec.num_experts == 512
    assert spec.n_params() == 4_648_163_712
    assert fam.letters_of(config) == "MEMEMEM*EME"
    e = cell.mix["engine"]
    longest = cell.mix["prompt"]["user"]["max"] + cell.mix["output"]["max"]
    assert longest <= e["max_len"] == 5120 == config["assumed"]["max_len"]
    assert (e["page_size"], e["prefill_chunk"], e["n_pages"]) == (256, 256,
                                                                  2560)
    assert cell.mix["prompt"]["shared_prefix"]["prob"] == 0
    assert cell.mix["arrivals"]["cv"] == 1.0
    assert set(config["assumed"]) >= {
        "mamba_equations", "mamba_dt_clamp", "mamba_values", "attention",
        "router", "router_bias_values", "latent_experts", "state_dtype",
        "mtp", "embedding_scale", "max_len", "residual_stream"}
    assert set(config) >= {"bytes", "deployment", "reduced_from"}
