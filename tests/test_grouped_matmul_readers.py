"""The benchmark's expert-layer readers on the grouped-matmul kernel's
device events: each MoE family's ``moe_op`` tells the kernel's call, at its
cell's unit and tick shapes, as ``"grouped_matmul"`` (by the operand shaped
like the expert stack, as it tells ``ragged_dot``'s), the rooflines read
rows and width off its result, and ``moe_kernel_calls_pct`` reads the
engine's counter — the guard against a roofline that falls silent when the
call changes. Imports the benchmark, edits nothing in it."""
import pytest

from benchmark import harness, trace_reduce
from benchmark.layer_metrics import (moe_held_roofline, moe_kernel_calls_pct,
                                     moe_roofline, moe_share_pct)

#: cell -> (its family, MoE layers x experts held = the planes of the
#: flattened stack, experts held = groups, the rows of its calls)
CELLS = {
    "olmoe-serve-chat": ("moe_lm", 8 * 64, 64, (256, 512, 1024)),
    "smallthinker-serve-mixed": ("window_moe_lm", 12 * 64, 64, (192, 1536)),
    "mistral4-serve-longdoc": ("mla_moe_lm", 6 * 32, 32, (256, 1024)),
    "ling3-serve-reason": ("kda_mla_moe_lm", 4 * 128, 128, (1024,)),
    "solar2-serve-agent": ("kda_gqa_moe_lm", 4 * 40, 40, (512, 2048)),
    "kexaone-serve-reason": ("window_mtp_moe_lm", 7 * 8, 8, (1024, 2048)),
}


def kernel_event(rows, k_in, n_out, planes, groups, name="grouped_matmul.7"):
    """The text a chip trace shows for one call of the kernel (my chip run,
    PR 51, smallthinker's tick: ``%grouped_matmul.64 = f32[192,768]
    custom-call(s32[1] %get-tuple-element.1370, s32[66]
    %broadcast_minimum_fusion.27, s32[66] %broadcast_select_fusion.16,
    s32[65] ..``): the five prefetched scalars, the sorted rows, the whole
    stack flattened; the result a plain [rows, cols] float32."""
    tm = 128 if rows % 128 == 0 else 64
    v = rows // tm + groups - 1
    return (f"%{name} = f32[{rows},{n_out}]{{1,0:T(8,128)}} custom-call("
            f"s32[1]{{0:T(128)}} %get-tuple-element.1370, "
            f"s32[{v}]{{0:T(128)}} %broadcast_minimum_fusion.27, "
            f"s32[{v}]{{0:T(128)}} %broadcast_select_fusion.16, "
            f"s32[{groups + 1}]{{0:T(128)}} %pad_add_fusion.12, "
            f"s32[1]{{0:T(128)}} %dynamic_slice.40, "
            f"bf16[{rows},{k_in}]{{1,0:T(8,128)(2,1)}} %fusion.311, "
            f"bf16[{planes},{k_in},{n_out}]{{2,1,0:T(8,128)(2,1)}} "
            "%get-tuple-element.1561), "
            "custom_call_target=\"tpu_custom_call\"")


def _widths(cell):
    cfg = cell.config
    return cfg["hidden_size"], cfg.get("moe_intermediate_size",
                                       cfg["intermediate_size"])


def _cases():
    for name, (family, planes, groups, rows) in CELLS.items():
        for n in rows:
            yield pytest.param(name, family, planes, groups, n,
                               id=f"{name}-{n}")


@pytest.mark.parametrize("name,family,planes,groups,rows", _cases())
def test_the_family_tells_the_kernels_call_and_the_rooflines_read_it(
        name, family, planes, groups, rows):
    cell = harness.load_cell(name)
    assert cell.config["family"] == family
    d, f = _widths(cell)
    for k_in, n_out in ((d, f), (f, d)):
        text = kernel_event(rows, k_in, n_out, planes, groups)
        assert cell.family.moe_op(text, cell.config) == "grouped_matmul"
        assert trace_reduce.parse_op(text)[0] == "grouped_matmul.7"
        for reader in (moe_roofline, moe_held_roofline):
            m = reader._RESULT.match(trace_reduce.strip_layouts(text))
            assert (int(m.group(1)), int(m.group(2))) == (rows, n_out)


def _trace(events):
    ops, t = [], 0.0
    for text, seconds in events:
        ops.append((text, t, t + seconds))
        t += seconds
    return trace_reduce.Trace({0: ops}, {}, {}, [], (0.0, t * 2))


@pytest.mark.parametrize("name,reader,more", [
    ("smallthinker-serve-mixed", moe_roofline, {}),
    ("kexaone-serve-reason", moe_held_roofline,
     {"moe_assignments": 16 * 2048, "moe_held_assignments": 2048}),
])
def test_a_roofline_prices_the_kernels_calls(name, reader, more, capsys):
    """A unit's three calls at twice their least time read 50%, and the
    stdout detail lists them by shape."""
    cell = harness.load_cell(name)
    cell.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    _, planes, groups, rows = CELLS[name]
    d, f = _widths(cell)
    counters = {"moe_layer_calls": 10, "moe_touched_experts": 10 * groups,
                **more}
    n = rows[-1]
    held = more.get("moe_held_assignments", 1) / more.get(
        "moe_assignments", 1)
    events = []
    for i, (k_in, n_out) in enumerate(((d, f), (d, f), (f, d))):
        c = cell.family.grouped_matmul_cost(
            cell.config, n * held if more else n, k_in, n_out, groups)
        least = max(c["flops"] / 197e12, c["bytes"] / 819e9)
        events.append((kernel_event(n, k_in, n_out, planes, groups,
                                    f"grouped_matmul.{i}"), 2 * least))
    tr = _trace(events)
    assert reader.read(tr, [], counters, cell) == pytest.approx(50.0)
    assert f'"{n}x{d}->{f}"' in capsys.readouterr().out
    assert moe_share_pct.read(tr, [], counters, cell) == pytest.approx(100.0)


def test_kernel_calls_pct_reads_the_engines_counter():
    read = moe_kernel_calls_pct.read
    assert read(None, [], {"moe_layer_calls": 40,
                           "moe_kernel_layer_calls": 10}, None) == 25.0
    # a MoE engine whose calls all stayed on ragged_dot counts 0, not none
    assert read(None, [], {"moe_layer_calls": 40,
                           "moe_kernel_layer_calls": 0}, None) == 0.0
    # the parent's engine (no such counter), a model without experts
    assert read(None, [], {"moe_layer_calls": 40}, None) is None
    assert read(None, [], {}, None) is None
