"""The ``window_moe_lm`` family and its two readers on the CPU mesh: the
serve driver end to end at a toy SmallThinker-shaped configuration
(``tests/data``: its own manifest ``BENCHMARK-window.json``, a twin of the
configuration and of the mix), the readers on hand-built counters and
device events, and the real configuration file against the catalog row's
published keys. Every number these runs print names ``platform: cpu``:
none is a measurement. Run by hand: ``pytest benchmark/tests`` (not part
of tier-1)."""
import contextlib
import io
import json
import os
import time

import numpy as np
import pytest

from benchmark import harness, trace_reduce
from benchmark.families import window_moe_lm
from benchmark.layer_metrics import (kv_held_vs_uniform_pct,
                                     mixed_attn_roofline)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MANIFEST = os.path.join(DATA, "BENCHMARK-window.json")
RECORDED = os.path.join(DATA, "small_tpu_v5e.xplane.pb")
#: the numbers of the catalog row's ``config`` (model-configs guide,
#: SmallThinker-21BA3B-Instruct)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
    "moe_num_primary_experts": 64, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True, "num_attention_heads": 28,
    "num_hidden_layers": 52, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936, "model_name": "smallthinker_21b_instruct"}


@pytest.fixture(autouse=True)
def amp_left_as_found():
    from paddle_tpu.ops import common

    before = common._AMP
    yield
    common._AMP = before


def run(traced, monkeypatch=None, seconds=2.0):
    import jax

    cell = harness.load_cell("tiny-serve-mixed", manifest=MANIFEST,
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


def test_traced_line_reads_the_counters_and_skips_what_the_trace_lacks(
        monkeypatch):
    cell, line, _ = run(traced=True, monkeypatch=monkeypatch)
    got = set(line["metrics"])
    # the recorded trace is a dense one-kind model's: no call of the
    # decode kernel in it, so the device-trace reader is left out
    assert {"kv_held_vs_uniform_pct", "moe_load_imbalance"} <= got
    assert "mixed_attn_roofline" not in got
    assert 0.0 < line["metrics"]["kv_held_vs_uniform_pct"]["value"] < 100.0
    assert line["metrics"]["serve_window_fresh_compiles"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on hand-built counters and events
# ---------------------------------------------------------------------------
CONFIG = {"num_hidden_layers": 12, "sliding_window_layout": [0, 1, 1, 1] * 13}


def _call(layers, pages, name="paged_attention_decode.7"):
    return (f"%{name} = bf16[32,8,512]{{2,1,0}} custom-call(s32[1]{{0}} %l, "
            f"s32[6144]{{0}} %t, s32[32]{{0}} %n, bf16[32,32,512]{{2,1,0}} "
            f"%q, bf16[{layers},{pages},64,512]{{3,2,1,0:T(8,128)(2,1)}} %k, "
            f"bf16[{layers},{pages},64,512]{{3,2,1,0:T(8,128)(2,1)}} %v), "
            "custom_call_target=\"tpu_custom_call\"")


class Cell:
    config = CONFIG
    family = window_moe_lm
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(events):
    ops, t = [], 0.0
    for text, seconds in events:
        ops.append((text, t, t + seconds))
        t += seconds
    return trace_reduce.Trace({0: ops}, {}, {}, [], (0.0, t * 2))


def test_held_against_uniform_weighs_each_kind_by_its_layers(capsys):
    """3 global layers holding 1000 pages a tick and 9 window layers
    holding 400 against one table of 1000 for all 12: (3000 + 3600) /
    12000 = 55%. None, with the reason on stderr, where the program counts
    none of it (a one-kind engine, the parent)."""
    counted = {"kv_pages_held_global": 1000 * 7, "kv_pages_held_window":
               400 * 7, "kv_pages_uniform_equiv": 1000 * 7}
    assert kv_held_vs_uniform_pct.read(None, [], counted, Cell) \
        == pytest.approx(55.0)
    same = dict(counted, kv_pages_held_window=1000 * 7)
    assert kv_held_vs_uniform_pct.read(None, [], same, Cell) \
        == pytest.approx(100.0)
    assert kv_held_vs_uniform_pct.read(None, [], {}, Cell) is None
    assert "left out" in capsys.readouterr().err
    missing = {"kv_pages_uniform_equiv": 7}
    assert kv_held_vs_uniform_pct.read(None, [], missing, Cell) is None
    assert "KeyError" in capsys.readouterr().err


def test_mixed_roofline_is_least_time_over_device_time_by_kind(capsys):
    """A global call walks 800 pages a tick, a window call 300: K and V
    tiles of 64 x 512 bf16 = 131,072 B a page, so 128 and 48 us at 819
    GB/s. Calls that took twice their least time read 50%; the kind comes
    from the pool operand's layer count."""
    cost = window_moe_lm.mixed_attention_cost(800, 64, 512, 2)
    assert cost["bytes"] == 2 * 800 * 64 * 512 * 2
    least_g, least_w = cost["bytes"] / 819e9, cost["bytes"] * 3 / 8 / 819e9
    assert least_g == pytest.approx(128e-6, rel=0.01)
    counted = {"decode_steps": 10, "paged_attn_pages_read_global": 8000,
               "paged_attn_pages_read_window": 3000}
    tr = _trace([(_call(3, 3072), 2 * least_g), (_call(9, 1792), 2 * least_w),
                 (_call(9, 1792, "paged_attention_decode.9"), 2 * least_w),
                 (_call(5, 64), 1.0),        # neither kind: not counted
                 ("%fusion.1 = f32[32,151936]{1,0} fusion()", 1e-3)])
    assert mixed_attn_roofline.read(tr, [], counted, Cell) \
        == pytest.approx(50.0, rel=1e-6)
    out = capsys.readouterr().out
    assert '"global"' in out and '"window"' in out
    assert window_moe_lm.attention_call_kind(3, CONFIG) == "global"
    assert window_moe_lm.attention_call_kind(9, CONFIG) == "window"
    assert window_moe_lm.attention_call_kind(12, CONFIG) is None
    # a missing counter, no call of the kernel, no trace at all: left out
    assert mixed_attn_roofline.read(
        tr, [], {"decode_steps": 10}, Cell) is None
    assert mixed_attn_roofline.read(
        _trace([("%fusion.1 = f32[8]{0} fusion()", 1e-3)]), [], counted,
        Cell) is None
    assert mixed_attn_roofline.read(None, [], counted, Cell) is None
    assert "left out" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the real configuration and cell
# ---------------------------------------------------------------------------
def test_configuration_holds_the_published_keys_and_cuts_depth_only():
    cell = harness.load_cell("smallthinker-serve-mixed")
    config = cell.config
    differ = {k for k, v in PUBLISHED.items() if config.get(k, "?") != v}
    assert differ == {"num_hidden_layers"} == set(config["reduced"])
    assert config["num_hidden_layers"] == 12
    assert config["reduced_from"] == {"num_hidden_layers": 52}
    # the aliases the existing readers index repeat published keys
    assert (config["intermediate_size"], config["num_experts"],
            config["num_experts_per_tok"]) == (768, 64, 6)
    assert cell.family is window_moe_lm and cell.mix["kind"] == "serve"
    assert [m["name"] for m in cell.end_to_end] == ["tpot_p95_ms",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert {"kv_held_vs_uniform_pct", "mixed_attn_roofline", "moe_roofline",
            "moe_share_pct", "moe_load_imbalance"} <= names
    assert not names & {"paged_attn_roofline", "paged_attn_page_share_pct"}
    spec = window_moe_lm.spec_of(config)
    assert spec.block.layer_pattern == ("full+nope", "window+rope",
                                        "window+rope", "window+rope")
    assert (spec.layers_of(False), spec.layers_of(True)) == (3, 9)
    # the issue's arithmetic: 398,627,840 a layer, 5,561,448,960 in all
    per_layer = (spec.n_params() - 2 * 151936 * 2560 - 2560) // 12
    assert per_layer == 398_627_840
    assert spec.n_params() == 5_561_448_960
    e = cell.mix["engine"]
    longest = (cell.mix["prompt"]["shared_prefix"]["tokens"]
               + cell.mix["prompt"]["user"]["max"]
               + cell.mix["output"]["max"])
    assert longest == 12288 == e["max_len"]
    assert (e["n_pages"], e["n_pages_window"]) == (3072, 1792)


def test_the_checked_requests_include_two_beyond_the_shared_prefix():
    """``check.greedy_requests``: at least two of the greedy requests the
    driver checks against the reference hold a context beyond 6144 tokens
    (the mix's ``schedule_seed`` was chosen so)."""
    from benchmark import traffic

    cell = harness.load_cell("smallthinker-serve-mixed")
    mix = cell.mix
    planned = traffic.schedule(
        mix, 1, mix["ramp_s"], 51,
        lambda rng, n: np.zeros(n, np.int64))
    due = [p for p in planned if p.due >= mix["ramp_s"]]
    checked = [p for p in due
               if p.sampling is None][:mix["check"]["greedy_requests"]]
    assert len(checked) == 6
    assert sum(p.prompt.size + p.max_new_tokens > 6144
               for p in checked) >= 2
