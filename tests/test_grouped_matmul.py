"""The grouped-matmul kernel (kernels/grouped_matmul.py) against
``jax.lax.ragged_dot`` on the same operands, in Pallas interpret mode on
the CPU mesh, and the rule by which ``ops/moe_ops.moe_topk`` chooses
between them (``grouped_supported``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.ops import moe_ops

M, K, N, E = 512, 256, 256, 8       # four row tiles of 128

#: name -> group sizes [E] over M rows
SIZES = {
    "even": [M // E] * E,
    # vacant slots all route alike: top-2 experts take every row
    "all_alike": [0, 0, M // 2, 0, 0, M // 2, 0, 0],
    "empty_groups": [0, 100, 0, 0, 312, 0, 100, 0],
    # group 1 owns rows 100 .. 399: tiles 0, 1, 2 and 3
    "straddles_three_tiles": [100, 300, 12, 0, 40, 20, 20, 20],
    "one_row": [0, 0, 0, 1, 0, 0, 0, 0],
    # rows behind the last group (the held form's absent assignments)
    "rows_behind": [30, 0, 150, 20, 0, 0, 7, 1],
}


def _operands(dtype, planes=E, seed=0, w_dtype=jnp.bfloat16):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.standard_normal((M, K)), dtype)
    w = jnp.asarray(0.1 * rng.standard_normal((planes, K, N)), w_dtype)
    return rows, w


def _ragged(rows, w, sizes):
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes,
                              preferred_element_type=jnp.float32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(SIZES))
def test_kernel_is_ragged_dot_on_the_rows_the_groups_own(name, dtype):
    """Same operands, same accumulation: the kernel's rows are
    ``ragged_dot``'s to the last float32 bits of a K-long sum, a row no
    group of a visited tile owns reads zero, and a group without rows is
    never visited."""
    rows, w = _operands(dtype)
    sizes = jnp.asarray(SIZES[name], jnp.int32)
    got = np.asarray(gm.grouped_matmul(rows, w, sizes, interpret=True))
    want = np.asarray(_ragged(rows, w, sizes))
    owned = int(sizes.sum())
    np.testing.assert_allclose(got[:owned], want[:owned], atol=2e-5, rtol=0)
    visited = -(-owned // 128) * 128        # the tail of the last tile
    assert not got[owned:visited].any()
    group, tile, offsets, live = gm.visits(sizes, M, 128)
    group, tile = np.asarray(group), np.asarray(tile)
    pairs = {(int(g), t) for g, (lo, hi) in enumerate(
        zip(np.asarray(offsets)[:-1], np.asarray(offsets)[1:]))
        for t in range(M // 128) if max(lo, t * 128) < min(hi, t * 128 + 128)}
    assert int(live[0]) == len(pairs) <= M // 128 + E - 1
    assert set(zip(group[:len(pairs)], tile[:len(pairs)])) == pairs
    # the padding repeats the last live visit: no block is fetched for it
    assert {(g, t) for g, t in zip(group[len(pairs) - 1:],
                                   tile[len(pairs) - 1:])} \
        == {(group[len(pairs) - 1], tile[len(pairs) - 1])}


@pytest.mark.parametrize("layer", [0, 2])
def test_layer_reads_its_planes_of_the_whole_stack(layer):
    """``layer``: group g multiplies by plane ``layer * E + g`` of the
    flattened stack — the sliced layer's result, nothing sliced."""
    rows, w = _operands(jnp.bfloat16, planes=3 * E, seed=layer)
    sizes = jnp.asarray(SIZES["empty_groups"], jnp.int32)
    got = jax.jit(lambda l: gm.grouped_matmul(
        rows, w, sizes, layer=l, interpret=True))(jnp.int32(layer))
    want = _ragged(rows, w[layer * E:(layer + 1) * E], sizes)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_float32_planes_are_cast_a_block_at_a_time():
    """float32-stored weights under AMP rows: the block is cast to the
    rows' type as ``moe_topk`` casts the stack — the same product."""
    rows, w = _operands(jnp.bfloat16, w_dtype=jnp.float32)
    sizes = jnp.asarray(SIZES["even"], jnp.int32)
    got = gm.grouped_matmul(rows, w, sizes, interpret=True)
    np.testing.assert_allclose(got, _ragged(rows, w, sizes), atol=2e-5,
                               rtol=0)


def _on_a_chip(monkeypatch, min_rows=64):
    """Report a TPU, lower the threshold to toy shapes, and run the kernel
    (where chosen) in interpret mode -> the list its calls are noted in."""
    calls = []
    kernel = gm.grouped_matmul

    def spy(rows, w, sizes, **kwargs):
        calls.append((rows.shape, w.shape, sizes.shape))
        return kernel(rows, w, sizes, interpret=True, **kwargs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gm, "MIN_ROWS", min_rows)
    monkeypatch.setattr(gm, "grouped_matmul", spy)
    return calls


def _layer_call(amp, held, n=32, d=128, f=256, n_experts=8, k=2, layers=3,
                layer=1, seed=0):
    """-> a closure running ``moe_topk`` under ``layer`` on toy weights."""
    rng = np.random.default_rng(seed)
    here = held[1] if held else n_experts
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, n_experts)), jnp.float32)
    gate, up = (jnp.asarray(0.1 * rng.standard_normal((layers, here, d, f)),
                            jnp.bfloat16) for _ in range(2))
    down = jnp.asarray(0.1 * rng.standard_normal((layers, here, f, d)),
                       jnp.bfloat16)

    def run():
        pt.set_amp(amp)   # (conftest's autouse fixture puts the policy back)
        return jax.jit(lambda l: moe_ops.moe_topk(
            x, router, gate, up, down, k, True, layer=l, held=held))(
                jnp.int32(layer))
    return run


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "held"])
def test_moe_topk_on_the_kernel_is_moe_topk_on_ragged_dot(monkeypatch, held,
                                                          amp):
    """End to end through ``moe_topk`` under ``layer`` (and ``held``, whose
    absent assignments sort behind every group and are visited by nothing):
    y, counts and prob_mean of the kernel's path against today's."""
    run = _layer_call(amp, held)
    want = run()
    calls = _on_a_chip(monkeypatch)
    got = run()
    here = held[1] if held else 8
    # three calls a layer, each on the whole stack and the E held sizes
    assert calls == [((64, 128), (3 * here, 128, 256), (here,))] * 2 \
        + [((64, 256), (3 * here, 256, 128), (here,))]
    np.testing.assert_allclose(got[0], want[0], atol=1e-5 if not amp
                               else 2e-5, rtol=0)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    assert np.isfinite(np.asarray(got[0])).all()


#: why -> (backend, layer, min_rows, d, f)
KEEPS = {
    "cpu": ("cpu", 1, 64, 128, 256),
    "no_layer": ("tpu", None, 64, 128, 256),
    "under_the_threshold": ("tpu", 1, 65, 128, 256),
    "width_off_the_lane_tile": ("tpu", 1, 64, 128, 192),
}


def _lowered(layer, d, f):
    """(the StableHLO, the jaxpr) of ``moe_topk`` under AMP on 64 assignment
    rows: the serving form (``layer``: stacks of three layers) or the train
    form."""
    pt.set_amp(True)      # (conftest's autouse fixture puts the policy back)
    x = jnp.zeros((32, d), jnp.float32)
    router = jnp.zeros((d, 8), jnp.float32)
    lead = (8,) if layer is None else (3, 8)
    w, w_down = (jnp.zeros(lead + dims, jnp.bfloat16)
                 for dims in ((d, f), (f, d)))

    def call(*l):
        return moe_ops.moe_topk(x, router, w, w, w_down, 2, True,
                                layer=l[0] if l else None)

    args = () if layer is None else (jnp.int32(layer),)
    return (jax.jit(call).lower(*args).as_text(),
            str(jax.make_jaxpr(call)(*args)))


@pytest.mark.parametrize("why", sorted(KEEPS))
def test_everything_else_keeps_ragged_dot(monkeypatch, why):
    """Off a TPU, without ``layer``, under ``MIN_ROWS`` rows and for a width
    off the lane tile ``grouped_supported`` is false and the lowered
    ``moe_topk`` is that of a process that never saw a chip: three
    ``ragged_dot``, no Pallas call, the same text."""
    backend, layer, min_rows, d, f = KEEPS[why]
    want = _lowered(layer, d, f)
    calls = _on_a_chip(monkeypatch, min_rows)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert not moe_ops.experts_on_kernel(64, d, f, layer)
    got = _lowered(layer, d, f)
    assert got == want and not calls
    assert got[1].count("= ragged_dot") == 3 and "pallas_call" not in got[1]


def test_supported_reads_shape_dtype_layer_and_backend_only(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ok = (gm.MIN_ROWS, 2560, 768, jnp.bfloat16, 0)
    assert gm.grouped_supported(*ok)
    assert gm.grouped_supported(2 * gm.MIN_ROWS, 768, 2560, jnp.float32,
                                jnp.int32(3))
    for i, bad in enumerate([gm.MIN_ROWS - 16, 2560 + 64, 768 + 8,
                             jnp.int8, None]):
        assert not gm.grouped_supported(*ok[:i], bad, *ok[i + 1:])
    # rows of no whole sublane tile
    assert not gm.grouped_supported(gm.MIN_ROWS + 8, 2560, 768,
                                    jnp.bfloat16, 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not gm.grouped_supported(*ok)


def test_call_sites_of_one_shape_share_one_trace(monkeypatch):
    """Two sites (a Python layer index here, a traced one there; sizes as
    the router counts them or cast) of one (shape, dtype): the inner
    function is traced ONCE — a second site costs a start-up no second
    tracing and lowering."""
    rows, w = _operands(jnp.bfloat16, planes=2 * E, seed=7)
    sizes = jnp.asarray(SIZES["even"], jnp.int32)
    traced, visits = [], gm.visits
    monkeypatch.setattr(gm, "visits", lambda *a: traced.append(a[1:])
                        or visits(*a))
    gm._visit.clear_cache()

    @jax.jit
    def two(l):
        a = gm.grouped_matmul(rows, w, sizes, layer=0, interpret=True)
        b = gm.grouped_matmul(rows + a[:, :1].astype(rows.dtype), w,
                              sizes.astype(jnp.uint8), layer=l,
                              interpret=True)
        return a + b

    two(jnp.int32(1)).block_until_ready()
    assert traced == [(M, 128)]


def test_wrapper_refuses_mismatched_operands():
    rows, w = _operands(jnp.bfloat16)
    sizes = jnp.asarray(SIZES["even"], jnp.int32)
    with pytest.raises(ValueError, match=r"no \[M, K\] x \[G, K, N\]"):
        gm.grouped_matmul(rows[:, :128], w, sizes, interpret=True)
    with pytest.raises(ValueError, match="planes are not layers"):
        gm.grouped_matmul(rows, w, sizes[:5], interpret=True)
    with pytest.raises(ValueError, match="planes are not layers"):
        gm.grouped_matmul(rows, jnp.concatenate([w, w]), sizes,
                          interpret=True)         # a stack without ``layer``
    with pytest.raises(ValueError, match="not whole tiles"):
        gm.grouped_matmul(rows[:500], w, sizes, interpret=True)


def test_no_rows_no_visit():
    """Sizes of zero throughout (a held share no assignment fell to): no
    live visit, every index in range."""
    group, tile, offsets, live = gm.visits(jnp.zeros((E,), jnp.int32), M, 128)
    assert int(live[0]) == 0 and not np.asarray(tile).any()
    assert np.asarray(group).max() < E and not np.asarray(offsets).any()
    out = gm.grouped_matmul(*_operands(jnp.bfloat16),
                            jnp.zeros((E,), jnp.int32), interpret=True)
    assert out.shape == (M, N)
