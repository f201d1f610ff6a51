"""The paged attention kernels (kernels/paged_attention.py: the decode
walk and the prefill chunk's) against the semantic ground truth —
``reference_attention`` over ``_gather_pages`` — in Pallas interpret mode
on the CPU mesh, and the rule that chooses between them in
``_scan_paged_layers``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import paged_attention
from paddle_tpu.kernels.flash_attention import reference_attention
from paddle_tpu.kernels.paged_attention import (paged_attention_decode,
                                                paged_attention_prefill)
from paddle_tpu.ops.pipeline_ops import _gather_pages, _scan_paged_layers

L, N, PS, P = 2, 24, 16, 4          # layers, pages, page size, table width
#: (page dtype, heads, d_head): the two serving cells' rows, 16x64 float32
#: and 16x128 bf16
WIDTHS = [pytest.param(jnp.float32, 16, 64, id="f32-16x64"),
          pytest.param(jnp.bfloat16, 16, 128, id="bf16-16x128")]


def _pools(dtype, width, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((L, N, PS, width)), dtype)
                 for _ in range(2))


def _queries(dtype, b, heads, d_head, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(2 * rng.standard_normal((b, heads, d_head)), dtype)


def _reference(q, ck, cv, layer, table, lengths):
    heads = q.shape[1]
    ctx = reference_attention(
        q[:, :, None, :], _gather_pages(ck, layer, table, heads),
        _gather_pages(cv, layer, table, heads), lengths=lengths)
    return ctx.transpose(0, 2, 1, 3).reshape(q.shape[0], -1)


def _table(rows):
    """rows: a list of page lists -> [b, P] int32, padded with the scrap
    page 0 as the engine pads it."""
    out = np.zeros((len(rows), P), np.int32)
    for s, pages in enumerate(rows):
        out[s, :len(pages)] = pages
    return out


#: name -> (table rows, lengths)
CONTEXTS = {
    # every slot vacant: Pos 0, table all zeros, one key on the scrap page
    "vacant": ([[], [], []], [1, 1, 1]),
    "page_boundary": ([[3], [5, 9], [7, 2, 11]], [PS, 2 * PS, 2 * PS + 1]),
    "full_table": ([[4, 8, 15, 16], [23, 1, 2, 3]], [P * PS, P * PS]),
    # two requests behind one shared prefix (pages 6, 7), diverging after
    "shared_pages": ([[6, 7, 12], [6, 7, 13], [6, 7]],
                     [2 * PS + 5, 2 * PS + 9, 2 * PS]),
    "permuted_table": ([[21, 3, 17, 2], [9, 20, 1]],
                       [3 * PS + 7, 2 * PS + 2]),
    "ragged": ([[], [10], [11, 12], [13, 14, 18, 19]],
               [1, PS - 1, PS + 1, 4 * PS - 3]),
    # reference_attention gives a fully masked row zeros, not NaN
    "no_key": ([[5], [6]], [0, 3]),
}


def _tolerance(dtype, got_ref, truth):
    """float32: 1e-5. bf16: the reference's OWN distance from the float32
    answer on the same bf16 operands (it rounds p and the result to bf16
    as the kernel does), doubled, and never under one bf16 ulp of the
    largest value."""
    if dtype == jnp.float32:
        return 1e-5
    ulp = 2.0 ** -8 * float(np.abs(truth).max())
    return max(2 * float(np.abs(got_ref - truth).max()), ulp)


@pytest.mark.parametrize("context", sorted(CONTEXTS))
@pytest.mark.parametrize("dtype,heads,d_head", WIDTHS)
def test_kernel_is_reference_attention_over_the_gathered_pages(
        dtype, heads, d_head, context):
    rows, lengths = CONTEXTS[context]
    table, lengths = jnp.asarray(_table(rows)), jnp.asarray(lengths,
                                                            jnp.int32)
    ck, cv = _pools(dtype, heads * d_head)
    q = _queries(dtype, len(rows), heads, d_head)
    layer = jnp.int32(1)
    got = paged_attention_decode(q, ck, cv, layer, table, lengths,
                                 interpret=True)
    assert got.shape == (len(rows), heads * d_head) and got.dtype == dtype
    want = _reference(q, ck, cv, layer, table, lengths)
    f32 = [a.astype(jnp.float32) for a in (q, ck, cv)]
    truth = np.asarray(_reference(*f32, layer, table, lengths))
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    tol = _tolerance(dtype, want, truth)
    np.testing.assert_allclose(got, truth, atol=tol, rtol=0)
    np.testing.assert_allclose(got, want, atol=2 * tol, rtol=0)
    if context == "no_key":
        assert not got[0].any()


@pytest.mark.parametrize("dtype,heads,d_head", WIDTHS)
def test_only_the_pages_a_row_holds_are_read(dtype, heads, d_head):
    """Every page no row holds — the other layer whole, the pages past a
    row's length that its table still names — is NaN: the result is finite
    and bitwise what the clean pools give. (The gathered reference reads
    them all: 0 x NaN.)"""
    rows = [[3, 4, 23], [], [5, 22, 21]]
    lengths = [2 * PS, 1, PS + 2]       # rows 0 and 2 name pages unheld
    held = [0, 3, 4, 5, 22]             # 0: the vacant row's scrap page
    table, lens = jnp.asarray(_table(rows)), jnp.asarray(lengths, jnp.int32)
    ck, cv = _pools(dtype, heads * d_head, seed=3)
    q = _queries(dtype, 3, heads, d_head)
    layer = jnp.int32(0)
    clean = paged_attention_decode(q, ck, cv, layer, table, lens,
                                   interpret=True)
    poison = np.ones((L, N), bool)
    poison[0, held] = False
    mask = jnp.asarray(poison)[:, :, None, None]
    ck_p, cv_p = (jnp.where(mask, jnp.nan, a) for a in (ck, cv))
    assert bool(jnp.isnan(ck_p[0, 23]).all() & jnp.isnan(ck_p[1]).all())
    got = paged_attention_decode(q, ck_p, cv_p, layer, table, lens,
                                 interpret=True)
    got, clean = (np.asarray(a.astype(jnp.float32)) for a in (got, clean))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert np.isnan(np.asarray(_reference(
        q, ck_p, cv_p, layer, table, lens).astype(jnp.float32))).any()


def test_a_row_depends_on_its_own_context_alone():
    """Batch invariance: a row's context is the same whatever the other
    rows hold and wherever it sits in the batch."""
    dtype, heads, d_head = jnp.float32, 16, 64
    ck, cv = _pools(dtype, heads * d_head, seed=5)
    q = _queries(dtype, 3, heads, d_head)
    layer = jnp.int32(1)
    rows, lengths = [[9, 2, 14], [], [7, 8, 1, 20]], [2 * PS + 4, 1, 4 * PS]
    full = paged_attention_decode(
        q, ck, cv, layer, jnp.asarray(_table(rows)),
        jnp.asarray(lengths, jnp.int32), interpret=True)
    alone = paged_attention_decode(
        q[:1], ck, cv, layer, jnp.asarray(_table(rows[:1])),
        jnp.asarray(lengths[:1], jnp.int32), interpret=True)
    swapped = paged_attention_decode(
        q[::-1], ck, cv, layer, jnp.asarray(_table(rows[::-1])),
        jnp.asarray(lengths[::-1], jnp.int32), interpret=True)
    np.testing.assert_array_equal(np.asarray(full[0]), np.asarray(alone[0]))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(swapped[::-1]))


# ---------------------------------------------------------------------------
# the prefill chunk's walk
# ---------------------------------------------------------------------------
C_N, C_PS, C_P, C_T = 48, 16, 24, 32    # pages, page size, table width, chunk
#: name -> the rows' (start, real tokens of the chunk)
CHUNKS = {
    # the first chunk of a prompt, whole
    "b1-start0": [(0, C_T)],
    # mid-page with padding queries; page-aligned, ten pages deep
    "b2-ragged": [(37, 11), (10 * C_PS, C_T)],
    # a short first chunk, a deep one, a padding row, a tail of five tokens
    "b4-padding-row": [(0, 20), (300, C_T), (4 * C_PS, 0), (203, 5)],
}
#: (page dtype, query heads, cached heads, the row budget of a query tile:
#: None = the kernel's, one tile here; 1024 splits 64 heads into two tiles)
CHUNK_WIDTHS = [
    pytest.param(jnp.bfloat16, 16, 16, None, id="bf16-16x128"),
    pytest.param(jnp.float32, 16, 16, None, id="f32-16x128"),
    pytest.param(jnp.bfloat16, 28, 4, None, id="bf16-28/4x128"),
    pytest.param(jnp.bfloat16, 64, 8, 1024, id="bf16-64/8x128-two-tiles"),
]


def _chunk_case(dtype, heads, kv_heads, rows, seed=0, d_head=128):
    """Pools, a table a row (the pages its chunk reaches, permuted; the
    tail the scrap page), queries, start and lengths."""
    rng = np.random.default_rng(seed)
    width = kv_heads * d_head
    ck, cv = (jnp.asarray(rng.standard_normal((L, C_N, C_PS, width)), dtype)
              for _ in range(2))
    table = np.zeros((len(rows), C_P), np.int32)
    for s, (start, n) in enumerate(rows):
        held = -(-(start + n) // C_PS) if n else 0
        table[s, :held] = rng.permutation(np.arange(1, C_N))[:held]
    q = jnp.asarray(2 * rng.standard_normal((len(rows), heads, C_T, d_head)),
                    dtype)
    start, lengths = (jnp.asarray([r[i] for r in rows], jnp.int32)
                      for i in (0, 1))
    return q, ck, cv, table, start, lengths


def _chunk_reference(q, ck, cv, layer, table, start, lengths, window):
    """The gathered path's answer with the padding queries' rows zeroed
    (it attends them too; nobody reads them)."""
    kv_heads = ck.shape[-1] // q.shape[-1]
    m = dict(causal=True, q_pos0=start)
    if window is not None:
        m.update(window=window, k_pos0=jnp.zeros_like(start))
    table = jnp.asarray(table)
    ctx = reference_attention(q, _gather_pages(ck, layer, table, kv_heads),
                              _gather_pages(cv, layer, table, kv_heads), **m)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(q.shape[0], q.shape[2], -1)
    real = jnp.arange(q.shape[2])[None, :] < lengths[:, None]
    return jnp.where(real[..., None], ctx, 0)


@pytest.mark.parametrize("window", [None, 128, 4096],
                         ids=["full", "window128", "window4096"])
@pytest.mark.parametrize("chunk", sorted(CHUNKS))
@pytest.mark.parametrize("dtype,heads,kv_heads,tile_rows", CHUNK_WIDTHS)
def test_chunk_walk_is_reference_attention_over_the_gathered_pages(
        monkeypatch, dtype, heads, kv_heads, tile_rows, chunk, window):
    if tile_rows is not None:
        monkeypatch.setattr(paged_attention, "_CHUNK_ROWS", tile_rows)
        assert paged_attention._query_tile(C_T, heads, dtype) == C_T // 2
    q, ck, cv, table, start, lengths = _chunk_case(dtype, heads, kv_heads,
                                                   CHUNKS[chunk])
    layer = jnp.int32(1)
    got = paged_attention_prefill(q, ck, cv, layer, jnp.asarray(table), start,
                                  lengths, interpret=True, window=window)
    assert got.shape == (len(table), C_T, heads * 128) and got.dtype == dtype
    want = _chunk_reference(q, ck, cv, layer, table, start, lengths, window)
    f32 = [a.astype(jnp.float32) for a in (q, ck, cv)]
    truth = np.asarray(_chunk_reference(*f32, layer, table, start, lengths,
                                        window))
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    tol = _tolerance(dtype, want, truth)
    np.testing.assert_allclose(got, truth, atol=tol, rtol=0)
    np.testing.assert_allclose(got, want, atol=2 * tol, rtol=0)
    # padding queries and the padding row: zeros, as the reference gives a
    # fully masked row
    for s, (_, n) in enumerate(CHUNKS[chunk]):
        assert not got[s, n:].any()


@pytest.mark.parametrize("window", [None, 40], ids=["full", "window"])
def test_chunk_walk_never_reads_a_page_out_of_reach(window):
    """Every page outside ``chunk_pages_in_reach`` — the other layer whole,
    pages no row holds, on a window layer the pages behind the window — is
    NaN, and every table entry outside the walk names a page that does not
    exist: the result is finite and bitwise what the clean operands give.
    (The gathered reference reads them all: 0 x NaN.)"""
    rows = [(37, 11), (10 * C_PS, C_T), (4 * C_PS, 0), (203, 5)]
    q, ck, cv, table, start, lengths = _chunk_case(jnp.float32, 4, 2, rows,
                                                   seed=3)
    layer = jnp.int32(0)
    clean = paged_attention_prefill(q, ck, cv, layer, jnp.asarray(table),
                                    start, lengths, interpret=True,
                                    window=window)
    first, end = paged_attention.chunk_pages_in_reach(
        np.asarray(start), np.asarray(lengths), C_PS, window, xp=np)
    poison, walked = np.ones((L, C_N), bool), np.full_like(table, 10 ** 6)
    for s in range(len(rows)):
        walked[s, first[s]:end[s]] = table[s, first[s]:end[s]]
        poison[0, table[s, first[s]:end[s]]] = False
    assert (first[1] > 0) == (window is not None) and end[2] == first[2]
    mask = jnp.asarray(poison)[:, :, None, None]
    ck_p, cv_p = (jnp.where(mask, jnp.nan, a) for a in (ck, cv))
    got = paged_attention_prefill(q, ck_p, cv_p, layer, jnp.asarray(walked),
                                  start, lengths, interpret=True,
                                  window=window)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    assert np.isnan(np.asarray(_chunk_reference(
        q, ck_p, cv_p, layer, table, start, lengths, window))).any()


#: the latent chunk walk: (row width W, latent r) of the two latent cells
LATENT_WIDTHS = [pytest.param(384, 256, id="w384-r256"),
                 pytest.param(640, 512, id="w640-r512")]
#: name -> (chunk, heads, rows (start, real tokens), the module's tile
#: constants for the case or None: the kernel's own)
LATENT_CHUNKS = {
    # eight heads in one score tile; a start that is not page-aligned, a
    # row that ends mid-tile, a padding row, a tail of five tokens
    "t64-ragged": (64, 8, [(37, 11), (10 * C_PS, 64), (4 * C_PS, 0),
                           (203, 5)], None),
    # the whole chunk one query tile, a head a step
    "t256-one-tile": (256, 2, [(100, 256)], None),
    # two query tiles (the second's real queries end mid-tile: each tile
    # stops at ITS last real key), two heads a step, blocks of four pages
    "t256-two-tiles-blocks": (256, 4, [(3 * C_PS + 5, 200), (0, 256)],
                              dict(_CHUNK_ROWS=512, _LATENT_SCORE_ROWS=256,
                                   _CHUNK_KEYS=4 * C_PS)),
}
L_N, L_P = 96, 40       # pages of the latent pool, table width


def _latent_case(W, r, chunk, seed=0, dtype=jnp.bfloat16):
    """The one pool, a table a row (the pages its chunk reaches, permuted;
    the unheld tail names a page that does not exist), queries as wide as
    the pool's row, start and lengths."""
    t, heads, rows, _ = LATENT_CHUNKS[chunk]
    rng = np.random.default_rng(seed)
    ck = jnp.asarray(rng.standard_normal((L, L_N, C_PS, W)), dtype)
    table = np.full((len(rows), L_P), 10 ** 6, np.int32)
    for s, (start, n) in enumerate(rows):
        held = -(-(start + n) // C_PS) if n else 0
        table[s, :held] = rng.permutation(np.arange(1, L_N))[:held]
    q = jnp.asarray(0.3 * rng.standard_normal((len(rows), heads, t, W)),
                    dtype)
    start, lengths = (jnp.asarray([x[i] for x in rows], jnp.int32)
                      for i in (0, 1))
    return q, ck, table, start, lengths


def _latent_reference(q, ck, layer, table, start, lengths, r):
    """``_mla_paged_step``'s gathered form (``ck[l, tbl]`` +
    ``reference_attention``, the row's first r columns the value) as the
    walk returns it, [b, Tc, H * r], the padding queries' rows zeroed."""
    b, _, t, W = q.shape
    tbl = jnp.clip(jnp.asarray(table), 0, ck.shape[1] - 1)
    lat = ck[layer, tbl].reshape(b, 1, -1, W)
    o = reference_attention(q, lat, lat[..., :r], sm_scale=1.0, causal=True,
                            q_pos0=start)
    real = jnp.arange(t)[None, :] < lengths[:, None]
    return jnp.where(real[..., None], o.transpose(0, 2, 1, 3).reshape(
        b, t, -1), 0)


@pytest.fixture
def latent_tiles(monkeypatch):
    """-> set(chunk): the case's tile constants in place. The jitted walk
    reads them as it traces, so no trace outlives a set of them."""
    def set_tiles(chunk):
        tiles = LATENT_CHUNKS[chunk][3]
        for name, value in (tiles or {}).items():
            monkeypatch.setattr(paged_attention, name, value)
        return tiles

    paged_attention._chunk_walk.clear_cache()
    yield set_tiles
    paged_attention._chunk_walk.clear_cache()


@pytest.mark.parametrize("chunk", sorted(LATENT_CHUNKS))
@pytest.mark.parametrize("W,r", LATENT_WIDTHS)
def test_latent_chunk_walk_is_the_gathered_absorbed_attention(
        latent_tiles, W, r, chunk):
    """ONE pool as key and value (``cache_v=None``): every head's queries
    [Tc, W] against the page tile whole, the tile's first r columns the
    value, the scale handed in: ``reference_attention`` over ``ck[l, tbl]``
    as ``_mla_paged_step`` gathers it, zeros for padding queries and rows."""
    t, heads, rows, _ = LATENT_CHUNKS[chunk]
    if latent_tiles(chunk):
        assert paged_attention._query_tile(t, heads, jnp.bfloat16) == t // 2
    q, ck, table, start, lengths = _latent_case(W, r, chunk)
    layer = jnp.int32(1)
    got = paged_attention_prefill(q, ck, None, layer, jnp.asarray(table),
                                  start, lengths, interpret=True,
                                  sm_scale=1.0, value_width=r)
    assert got.shape == (len(rows), t, heads * r) and got.dtype == ck.dtype
    want = _latent_reference(q, ck, layer, table, start, lengths, r)
    truth = np.asarray(_latent_reference(
        q.astype(jnp.float32), ck.astype(jnp.float32), layer, table, start,
        lengths, r))
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    tol = _tolerance(jnp.bfloat16, want, truth)
    np.testing.assert_allclose(got, truth, atol=tol, rtol=0)
    np.testing.assert_allclose(got, want, atol=2 * tol, rtol=0)
    for s, (_, n) in enumerate(rows):
        assert not got[s, n:].any()


@pytest.mark.parametrize("chunk", ["t64-ragged", "t256-two-tiles-blocks"])
def test_latent_chunk_walk_never_reads_a_page_out_of_reach(latent_tiles,
                                                           chunk):
    """Every page outside ``chunk_pages_in_reach`` (the other layer whole,
    pages no row holds) is NaN and the table's unheld tails name a page that
    does not exist: finite, and bitwise what the clean pool gives; a float32
    pool multiplies as float32 (1e-5 of the gathered form)."""
    latent_tiles(chunk)
    W, r = 384, 256
    q, ck, table, start, lengths = _latent_case(W, r, chunk, seed=3,
                                                dtype=jnp.float32)
    layer = jnp.int32(0)
    held = np.where(table < L_N, table, 0)
    clean = paged_attention_prefill(q, ck, None, layer, jnp.asarray(held),
                                    start, lengths, interpret=True,
                                    sm_scale=1.0, value_width=r)
    poison = np.ones((L, L_N), bool)
    poison[0, table[table < L_N]] = False
    ck_p = jnp.where(jnp.asarray(poison)[:, :, None, None], jnp.nan, ck)
    got = paged_attention_prefill(q, ck_p, None, layer, jnp.asarray(table),
                                  start, lengths, interpret=True,
                                  sm_scale=1.0, value_width=r)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_latent_reference(
            q, ck, layer, table, start, lengths, r)), atol=1e-5, rtol=0)


def test_the_engine_counts_the_pages_the_chunk_walk_reads():
    """``PageCache.chunk_pages_read`` is the kernel's rule summed over a
    unit's rows: a full layer from page 0, a window layer from its
    window's page, to the chunk's last real key; a padding row none."""
    from paddle_tpu.serving.paging import PageCache, PagePool

    start = np.array([0, 300, 64, 1000], np.int32)
    lengths = np.array([20, 256, 0, 256], np.int32)
    kw = dict(layers=1, row_width=128, count=lambda *a: None)
    full = PageCache("global", PagePool(8, 64), None, **kw)
    assert full.chunk_pages_read(start, lengths, 96) == 1 + 9 + 0 + 20
    win = PageCache("window", PagePool(8, 64), None, window=128, **kw)
    # (300 - 127) // 64 = 2 .. 8; (1000 - 127) // 64 = 13 .. 19
    assert win.chunk_pages_read(start, lengths, 96) == 1 + 7 + 0 + 7
    # a table narrower than the reach bounds the walk, as the kernel's
    assert full.chunk_pages_read(start, lengths, 16) == 1 + 9 + 0 + 16


# ---------------------------------------------------------------------------
# Mosaic takes the kernel at the two serving cells' shapes: interpret mode
# cannot see tiling, VMEM or DMA-slice refusals; the chip's compiler is
# installed here and compiles for a chip that is described, not attached
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no compiler here: no test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype,layers,pages,heads,d_head,table_width", [
    pytest.param(jnp.float32, 24, 400, 16, 64, 16, id="gpt2m-serve-chat"),
    pytest.param(jnp.bfloat16, 8, 1024, 16, 128, 32, id="olmoe-serve-chat"),
])
def test_kernel_compiles_for_the_v5e_with_the_pool_whole(
        one_chip, dtype, layers, pages, heads, d_head, table_width):
    """The whole pool enters the custom call as it lies in HBM: no copy,
    slice or re-layout of anything pool- or layer-sized is compiled in."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, ps, width = 32, 64, heads * d_head
    pool = arg((layers, pages, ps, width), dtype)
    text = jax.jit(paged_attention_decode).lower(
        arg((slots, heads, d_head), dtype), pool, pool, arg((), jnp.int32),
        arg((slots, table_width), jnp.int32),
        arg((slots,), jnp.int32)).compile().as_text()
    call = [ln for ln in text.splitlines()
            if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(call) == 1 and "%paged_attention_decode" in call[0]
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(",
                     re.sub(r"\{[^{}]*\}", "", text), re.M)
    assert ops
    assert {op for shape, op in ops
            if f"{pages},{ps},{width}]" in shape} == {"parameter"}


def _prefill_unit_text(cell_name, one_chip, monkeypatch):
    """The 256-token prefill unit of a serving cell, lowered from the op at
    the cell's own shapes (its spec, its engine's pools and table) and
    compiled for the described chip -> (the HLO text, spec, engine
    settings, table width)."""
    import paddle_tpu as pt
    from benchmark import harness
    from paddle_tpu.lm_spec import DRAFT_SLOT_PREFIX
    from paddle_tpu.ops.pipeline_ops import transformer_stack_paged_prefill

    cell = harness.load_cell(cell_name)
    e = cell.mix["engine"]
    spec = cell.family.spec_of(cell.config)
    pt.set_amp(True)    # (conftest's autouse fixture puts the policy back)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ps, dt = e["page_size"], spec.param_dtype
    P = e["max_len"] // ps
    row = (ps, spec.cache_row_width)
    rows = {"Chunk": ((1, e["prefill_chunk"]), "int32"),
            "StartPos": ((1,), "int32"), "Lengths": ((1,), "int32"),
            "BlockTable": ((1, P), "int32"),
            "Temperature": ((1,), "float32"), "TopK": ((1,), "int32"),
            "TopP": ((1,), "float32"), "Seed": ((1,), "int32"),
            "Step": ((1,), "int32")}
    pools = {n: ((spec.pool_layers(False), e["n_pages"], *row),
                 spec.page_dtype)
             for n in ("CacheK", "CacheV")[:spec.cache_pools]}
    if spec.index_topk:     # the pooled indexer keys, under the same ids
        pools["CacheIndex"] = ((spec.pool_layers(False), e["n_pages"],
                                ps // spec.index_pool, spec.index_dim),
                               spec.page_dtype)
    if spec.block.has_window:
        rows["BlockTableW"] = ((1, P), "int32")
        pools.update({n: ((spec.pool_layers(True), e["n_pages_window"],
                           *row), spec.page_dtype)
                      for n in ("CacheKW", "CacheVW")})
    weights = {"TokEmb": ((spec.vocab_size, spec.d_model), dt),
               "FinalLnS": ((spec.d_model,), dt),
               "HeadW": ((spec.d_model, spec.vocab_size), dt)}
    for slot, key, shape, _ in spec.stack_planes():
        weights[slot] = ((spec.plane_layers(key), *shape), dt)
    if spec.draft_block:
        rows["DraftNext"] = ((1,), "int32")
        for slot, _, shape, _ in spec.draft_planes():
            weights[slot] = (tuple(shape), dt)
        for slot, _, shape, _ in spec.draft_spec().stack_planes():
            weights[DRAFT_SLOT_PREFIX + slot] = ((1, *shape), dt)
    state = {}
    for name, shape, dtype, layers in spec.slot_state():
        state[name] = ((layers, e["slots"], *shape), dtype)
        if "n_snapshots" in e:
            state[name + "Snap"] = ((layers, e["n_snapshots"], *shape), dtype)
    if state:
        rows["StateSlot"] = ((1,), "int32")
    if "n_snapshots" in e:
        rows.update({n: ((1,), "int32") for n in ("SnapFrom", "SnapTake")})
    shapes = {**rows, **pools, **weights, **state}
    names = sorted(shapes)
    attrs = dict(spec.block.attrs(), page_size=ps, temperature=0.0, top_k=0)

    def step(*args):
        outs = transformer_stack_paged_prefill(
            attrs, {k: [a] for k, a in zip(names, args)})
        return {k: v[0] for k, v in outs.items()}

    text = jax.jit(step, donate_argnums=tuple(
        names.index(n) for n in (*pools, *state))).lower(*[
            jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1],
                                 sharding=one_chip)
            for n in names]).compile().as_text()
    return re.sub(r"\{[^{}]*\}", "", text), spec, e, P


@pytest.mark.parametrize("cell_name,calls,experts", [
    # two full + six window layers and the drafting block's full layer;
    # seven expert layers and the drafting block's, three products each
    ("kexaone-serve-reason", 9, 24),
    # one period under the scan: a full layer, three window layers
    ("smallthinker-serve-mixed", 4, 12),
    # the one softmax layer of the period, beside three recurrent ones
    ("solar2-serve-agent", 1, 12),
])
def test_prefill_unit_on_the_v5e_walks_the_pages_of_whole_pools(
        one_chip, monkeypatch, cell_name, calls, experts):
    """The prefill unit of the three cells whose temporaries the gathered
    scores sized, compiled for the chip at the cell's shapes: every K/V
    layer's attention is the chunk walk under its OWN call name, the pools
    enter each call whole (the scan's carry, no slice, copy or re-layout),
    and nothing shaped like the gathered table [.., P, ps, row] or its
    float32 scores [b, heads.., Tc, P * ps] is compiled in (kexaone's table
    is as wide as its stream, 6144: [Tc, 6144] alone is the hidden state)."""
    flat, spec, e, P = _prefill_unit_text(cell_name, one_chip, monkeypatch)
    ps, Tc, W = e["page_size"], e["prefill_chunk"], spec.cache_row_width
    walks = [ln for ln in flat.splitlines() if "custom-call(" in ln
             and "%paged_attention_prefill" in ln and "tpu_custom_call" in ln]
    assert len(walks) == calls
    assert "%paged_attention_decode" not in flat
    kinds = [(spec.pool_layers(False), e["n_pages"])]
    if spec.block.has_window:
        kinds.append((spec.pool_layers(True), e["n_pages_window"]))
    pools = tuple(f"[{layers},{n},{ps},{W}]" for layers, n in kinds)
    # each call reads the K and the V pool of ONE kind, whole
    assert all(sum(c.count(f"bf16{pool}") for pool in pools) == 2
               for c in walks)
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", flat, re.M)
    moved = {op for shape, op in ops if shape.endswith(pools)
             and not shape.startswith("(")}
    assert moved <= {"parameter", "get-tuple-element", "scatter", "fusion",
                     "bitcast"}, moved
    assert not [shape for shape, _ in ops
                if re.search(rf"f32\[(\d+,){{2,}}{Tc},{P * ps}\]", shape)
                or f",{P},{ps},{W}]" in shape or f"[{P},{ps},{W}]" in shape]
    # the expert layers' three products a layer are the grouped-matmul
    # kernel's (a unit's rows are over its threshold), each on the WHOLE
    # flattened stack; XLA's ragged_dot is gone from the unit
    rows = Tc * spec.experts_per_tok
    products = [ln for ln in flat.splitlines() if "custom-call(" in ln
                and "%grouped_matmul" in ln and "tpu_custom_call" in ln]
    assert len(products) == experts and "ragged" not in flat
    stacks = tuple(f"f32[{rows},{n}] custom-call(" for n in
                   (spec.d_expert, spec.d_model))
    assert all(any(st in c for st in stacks) for c in products)


@pytest.mark.parametrize("cell_name,calls,experts,r", [
    # six latent layers under one scan; their expert layers' three products
    ("mistral4-serve-longdoc", 1, 3, 256),
    # the one latent layer of the period beside five recurrent ones; four
    # expert layers after the two dense
    ("ling3-serve-reason", 1, 12, 512),
])
def test_latent_prefill_unit_on_the_v5e_walks_the_pages_of_the_one_pool(
        one_chip, monkeypatch, cell_name, calls, experts, r):
    """The prefill unit of the two latent cells compiled for the chip at the
    cell's shapes: the latent layers' attention is the chunk walk under its
    OWN call name (not the K/V walk's, not the tick's), the ONE pool enters
    the call whole, and nothing shaped like the gathered table [.., P, ps,
    W] or its float32 scores [b, heads, Tc, P * ps] is compiled in (ling3's
    table is as wide as its recurrent layers' projection, 12288: [1, Tc,
    12288] alone is theirs)."""
    flat, spec, e, P = _prefill_unit_text(cell_name, one_chip, monkeypatch)
    ps, Tc, W = e["page_size"], e["prefill_chunk"], spec.cache_row_width
    assert (spec.cache_pools, spec.block.kv_lora_rank) == (1, r)
    walks = [ln for ln in flat.splitlines() if "custom-call(" in ln
             and "%paged_mla_prefill" in ln and "tpu_custom_call" in ln]
    assert len(walks) == calls
    assert "%paged_attention_prefill" not in flat
    assert "%paged_mla_decode" not in flat
    pool = f"[{spec.pool_layers(False)},{e['n_pages']},{ps},{W}]"
    assert all(c.count(f"bf16{pool}") == 1 for c in walks)
    # the walk hands back the latent's columns alone, a token's heads side
    # by side
    assert all(f"bf16[1,{Tc},{spec.num_heads * r}] custom-call(" in c
               for c in walks)
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", flat, re.M)
    moved = {op for shape, op in ops if shape.endswith(pool)
             and not shape.startswith("(")}
    assert moved <= {"parameter", "get-tuple-element", "scatter", "fusion",
                     "bitcast"}, moved
    assert not [shape for shape, _ in ops
                if re.search(rf"f32\[(\d+,){{2,}}{Tc},{P * ps}\]", shape)
                or f",{P},{ps},{W}]" in shape or f"[{P},{ps},{W}]" in shape
                or f"[1,{P * ps},{W}]" in shape]
    products = [ln for ln in flat.splitlines() if "custom-call(" in ln
                and "%grouped_matmul" in ln and "tpu_custom_call" in ln]
    assert len(products) == experts and "ragged" not in flat


@pytest.mark.parametrize("heads,W,r,pages,table_width,chunk", [
    pytest.param(32, 384, 256, 1536, 80, 256, id="mistral4-256"),
    pytest.param(32, 384, 256, 1536, 80, 64, id="mistral4-64"),
    pytest.param(32, 640, 512, 4096, 48, 256, id="ling3-256"),
])
def test_latent_chunk_walk_compiles_for_the_v5e_with_the_one_pool_whole(
        one_chip, heads, W, r, pages, table_width, chunk):
    """The latent chunk walk alone at the two latent cells' shapes: one
    custom call under its own name, one pool operand, nothing pool-sized
    moved, the result the latent's columns of every head."""
    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, ps = 6, 256

    def call(q, pool, layer, table, start, lengths):
        return paged_attention_prefill(q, pool, None, layer, table, start,
                                       lengths, sm_scale=1.0, value_width=r)

    text = jax.jit(call).lower(
        arg((1, heads, chunk, W), jnp.bfloat16),
        arg((layers, pages, ps, W), jnp.bfloat16), arg((), jnp.int32),
        arg((1, table_width), jnp.int32), arg((1,), jnp.int32),
        arg((1,), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%paged_mla_prefill" in calls[0]
    assert calls[0].count(f"[{layers},{pages},{ps},{W}]") == 1
    assert f"bf16[1,{chunk},{heads * r}]" in calls[0]
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(",
                     re.sub(r"\{[^{}]*\}", "", text), re.M)
    assert {op for shape, op in ops
            if f"{pages},{ps},{W}]" in shape} == {"parameter"}


def test_masked_latent_walks_compile_for_the_v5e_and_the_hooks_name_them(
        one_chip):
    """The two walks under a group mask at ``glm53f-serve-longctx``'s shapes
    (64 heads over ONE 512-wide row, bf16 pages of 256, a table of 132 pages
    = 8448 groups of 4, 32 slots, units of 1024): each ONE custom call under
    the unselected layer's name, the pool whole, the pick an int8 operand BY
    GROUP — which is what ``dsa_kda_moe_lm.dsa_op`` (the benchmark's hook,
    imported as it stands) names a selection op by, and the tick's result
    leads with the slot count (``dsa_tick_op``)."""
    import json
    import os

    from benchmark.families import dsa_kda_moe_lm as fam
    from paddle_tpu.kernels.paged_attention import MLA_KERNEL

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "glm-5.3-flash.json")) as f:
        config = json.load(f)
    slots, pages, ps, W, H, P, Tc, G = 32, 2560, 256, 512, 64, 132, 1024, 4
    NG = P * ps // G
    pool = arg((1, pages, ps, W), jnp.bfloat16)

    def tick(q, pool, layer, table, lengths, picked):
        return paged_attention_decode(q, pool, None, layer, table, lengths,
                                      sm_scale=1.0, name=MLA_KERNEL,
                                      group_mask=picked, group_rows=G)

    def chunk(q, pool, layer, table, start, lengths, picked):
        return paged_attention_prefill(q, pool, None, layer, table, start,
                                       lengths, sm_scale=1.0, value_width=W,
                                       group_mask=picked, group_rows=G)

    texts = {
        "tick": jax.jit(tick).lower(
            arg((slots, H, W), jnp.bfloat16), pool, arg((), jnp.int32),
            arg((slots, P), jnp.int32), arg((slots,), jnp.int32),
            arg((slots, NG), jnp.int8)).compile().as_text(),
        "chunk": jax.jit(chunk).lower(
            arg((1, H, Tc, W), jnp.bfloat16), pool, arg((), jnp.int32),
            arg((1, P), jnp.int32), arg((1,), jnp.int32),
            arg((1,), jnp.int32), arg((1, Tc, NG), jnp.int8)
        ).compile().as_text()}
    for what, name, result in (
            ("tick", "%paged_mla_decode", f"bf16[{slots},{H},{W}]"),
            ("chunk", "%paged_mla_prefill", f"bf16[1,{Tc},{H * W}]")):
        # (the compiled text names the operands' shapes under the call's
        # layout constraints; a device event's text has them inline)
        calls = [ln.strip().removeprefix("ROOT ")
                 for ln in texts[what].splitlines()
                 if "custom-call(" in ln and "tpu_custom_call" in ln]
        assert len(calls) == 1 and calls[0].startswith(f"{name}."), calls
        assert fam.dsa_op(calls[0], config) == "score"
        assert fam.dsa_tick_op(calls[0], config, slots) == (what == "tick")
        flat = re.sub(r"\{[^{}]*\}", "", texts[what])
        call = re.sub(r"\{[^{}]*\}", "", calls[0])
        assert f"= {result} custom-call(" in call
        assert call.count(f"bf16[1,{pages},{ps},{W}]") == 1
        assert re.search(rf"s8\[(\d+,)+{NG}\]", call)
        ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", flat,
                         re.M)
        assert {op for shape, op in ops
                if f"{pages},{ps},{W}]" in shape} == {"parameter"}


def test_sparse_prefill_unit_on_the_v5e_hands_its_pick_to_the_walk(
        one_chip, monkeypatch):
    """``glm53f-serve-longctx``'s 1024-token unit compiled for the chip at
    the cell's shapes: the sparse latent layer's attention is ONE
    ``paged_mla_prefill`` call with the pick as an int8 group mask among its
    operands, the latent pool enters it whole, and nothing of the gathered
    form is compiled in: no gathered rows ``[queries x picks, G W]``, no
    re-layout ``[tile, picked rows, W]``, no groups-of-four view of the pool
    (``[N ps / G, G W]``), no sort over the table's groups; the trace-time
    counter says the layer walked."""
    from paddle_tpu import profiler

    def count(name):
        return profiler.global_stat.as_dict().get(
            name, {"total_ms": 0})["total_ms"]

    before = count("dsa/walk_calls"), count("dsa/gather_calls")
    flat, spec, e, P = _prefill_unit_text("glm53f-serve-longctx", one_chip,
                                          monkeypatch)
    assert (count("dsa/walk_calls"), count("dsa/gather_calls")) == (
        before[0] + 1, before[1])
    ps, Tc, W, G = (e["page_size"], e["prefill_chunk"], spec.cache_row_width,
                    spec.index_pool)
    NG, N = P * ps // G, e["n_pages"]
    walks = [ln for ln in flat.splitlines() if "custom-call(" in ln
             and "%paged_mla_prefill" in ln and "tpu_custom_call" in ln]
    assert len(walks) == 1 and f"s8[1,{Tc},{NG}]" in walks[0]
    assert walks[0].count(f"bf16[1,{N},{ps},{W}]") == 1
    shapes = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", flat,
                        re.M)
    picked = (spec.index_topk, spec.index_topk + G)     # rows a query
    assert not [shape for shape, op in shapes
                if f"{N * ps // G},{G * W}]" in shape       # the pool by group
                or (shape.startswith("bf16[") and shape.endswith(
                    tuple(f",{rows},{W}]" for rows in picked)))
                or (op == "gather" and shape.endswith(f",{G * W}]"))
                or (op == "sort" and f",{NG}]" in shape)]


def test_latent_kernel_compiles_for_the_v5e_with_the_one_pool_whole(one_chip):
    """Latent decode at the ``mistral4-serve-longdoc`` cell's shapes: 32
    query heads over ONE 384-wide pool row (320 values at whole lane
    rows), 256-token pages, the key tile also the value: one custom call
    under its own name, one pool operand, nothing pool-sized moved."""
    from paddle_tpu.kernels.paged_attention import MLA_KERNEL

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    slots, layers, pages, ps, width, heads = 64, 6, 1536, 256, 384, 32

    def call(q, pool, layer, table, lengths):
        return paged_attention_decode(q, pool, None, layer, table, lengths,
                                      sm_scale=1.0, name=MLA_KERNEL)

    text = jax.jit(call).lower(
        arg((slots, heads, width), jnp.bfloat16),
        arg((layers, pages, ps, width), jnp.bfloat16), arg((), jnp.int32),
        arg((slots, 80), jnp.int32),
        arg((slots,), jnp.int32)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if "custom-call(" in ln and "tpu_custom_call" in ln]
    assert len(calls) == 1 and "%paged_mla_decode" in calls[0]
    assert calls[0].count(f"[{layers},{pages},{ps},{width}]") == 1
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(",
                     re.sub(r"\{[^{}]*\}", "", text), re.M)
    assert {op for shape, op in ops
            if f"{pages},{ps},{width}]" in shape} == {"parameter"}


def test_decode_step_on_the_v5e_keeps_the_pool_the_scan_carry(one_chip,
                                                              monkeypatch):
    """The whole decode op compiled for the chip: the kernel sits in the
    layer loop, the pools are donated and updated in place, and the only
    pool-shaped ops left are the two scatters — handing the carry to the
    custom call whole costs no copy, dynamic-slice or re-layout."""
    from paddle_tpu.lm_spec import LMSpec
    from paddle_tpu.ops.pipeline_ops import transformer_stack_paged_decode

    spec = LMSpec(vocab_size=512, d_model=256, n_layers=3, num_heads=4,
                  max_len=256)
    slots, pages, ps, table_width = 8, 96, 32, 8
    shapes = {
        "Tok": ((slots,), "int32"), "Pos": ((slots,), "int32"),
        "BlockTable": ((slots, table_width), "int32"),
        "CacheK": ((spec.n_layers, pages, ps, spec.d_model), "float32"),
        "CacheV": ((spec.n_layers, pages, ps, spec.d_model), "float32"),
        "TokEmb": ((spec.vocab_size, spec.d_model), "float32"),
        "PosEmb": ((spec.max_len, spec.d_model), "float32"),
        "FinalLnS": ((spec.d_model,), "float32"),
        "FinalLnB": ((spec.d_model,), "float32"),
        "HeadW": ((spec.d_model, spec.vocab_size), "float32")}
    for slot, _key, shape, _fan in spec.stack_planes():
        shapes[slot] = ((spec.n_layers, *shape), "float32")
    names = sorted(shapes)
    attrs = dict(spec.block.attrs(), page_size=ps)

    def step(*args):
        outs = transformer_stack_paged_decode(
            attrs, {k: [a] for k, a in zip(names, args)})
        return {k: v[0] for k, v in outs.items()}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    donate = tuple(names.index(n) for n in ("CacheK", "CacheV"))
    text = jax.jit(step, donate_argnums=donate).lower(*[
        jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1], sharding=one_chip)
        for n in names]).compile().as_text()
    assert "%paged_attention_decode" in text
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(",
                     re.sub(r"\{[^{}]*\}", "", text), re.M)
    pool = f"[{spec.n_layers},{pages},{ps},{spec.d_model}]"
    layer = f"[{pages},{ps},{spec.d_model}]"
    gathered = f"[{slots * table_width},{ps},{spec.d_model}]"
    moved = {op for shape, op in ops
             if shape.endswith((pool, layer, gathered))
             and not shape.startswith("(")}
    assert moved <= {"parameter", "get-tuple-element", "scatter", "fusion",
                     "bitcast"}, moved
    assert not [shape for shape, op in ops if gathered in shape]
    # one in-place scatter fusion a pool, nothing else writes a pool
    assert sorted(op for shape, op in ops if shape.endswith(pool)
                  and op in ("fusion", "scatter")) == [
        "fusion", "fusion", "scatter", "scatter"]


def test_decode_step_with_layer_kinds_on_the_v5e_walks_both_pools(
        one_chip, monkeypatch):
    """The decode op of a stack with layer kinds at SmallThinker-21BA3B's
    published widths (2560; 28 query / 4 KV heads of 128; 64 ReGLU experts
    of 768, top-6; window 4096; vocabulary 151936) and one period (a
    global NoPE layer, three window RoPE layers), compiled for the chip:
    every layer's attention is the kernel — grouped queries, and for the
    window kind a walk that starts at the window — against its OWN kind's
    pool, and nothing shaped like a gathered table-width context or a
    copied pool is compiled in."""
    from paddle_tpu.lm_spec import LMSpec
    from paddle_tpu.ops.pipeline_ops import transformer_stack_paged_decode

    spec = LMSpec(
        vocab_size=151936, d_model=2560, n_layers=4, num_heads=28,
        num_kv_heads=4, head_dim=128, use_rope=True, max_len=16384,
        norm="rms_norm", norm_eps=1e-6, rope_theta=1.5e6,
        rope_pairing="half", ffn="swiglu_moe", num_experts=64,
        experts_per_tok=6, d_expert=768, norm_topk_prob=True, bias=False,
        param_dtype="bfloat16", page_dtype="bfloat16",
        layer_pattern=("full+nope", "window+rope", "window+rope",
                       "window+rope"), window=4096, expert_act="relu",
        router_input="attn_input")
    slots, ps, table_width, pages_g, pages_w = 32, 64, 192, 3072, 1792
    bf = "bfloat16"
    shapes = {
        "Tok": ((slots,), "int32"), "Pos": ((slots,), "int32"),
        "BlockTable": ((slots, table_width), "int32"),
        "BlockTableW": ((slots, table_width), "int32"),
        "CacheK": ((1, pages_g, ps, 512), bf),
        "CacheV": ((1, pages_g, ps, 512), bf),
        "CacheKW": ((3, pages_w, ps, 512), bf),
        "CacheVW": ((3, pages_w, ps, 512), bf),
        "TokEmb": ((spec.vocab_size, spec.d_model), bf),
        "FinalLnS": ((spec.d_model,), bf),
        "HeadW": ((spec.d_model, spec.vocab_size), bf)}
    for slot, _key, shape, _fan in spec.stack_planes():
        shapes[slot] = ((spec.n_layers, *shape), bf)
    names = sorted(shapes)
    attrs = dict(spec.block.attrs(), page_size=ps)

    def step(*args):
        outs = transformer_stack_paged_decode(
            attrs, {k: [a] for k, a in zip(names, args)})
        return {k: v[0] for k, v in outs.items()}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    donate = tuple(names.index(n) for n in ("CacheK", "CacheV", "CacheKW",
                                            "CacheVW"))
    text = jax.jit(step, donate_argnums=donate).lower(*[
        jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1], sharding=one_chip)
        for n in names]).compile().as_text()
    flat = re.sub(r"\{[^{}]*\}", "", text)
    calls = [ln for ln in flat.splitlines()
             if "custom-call(" in ln and "%paged_attention_decode" in ln
             and "tpu_custom_call" in ln]
    # one call a layer of the period (the loop has a single trip and is
    # unrolled): one against the global pool, three against the window's
    assert len(calls) == 4
    assert sum(f"bf16[1,{pages_g},{ps},512]" in c for c in calls) == 1
    assert sum(f"bf16[3,{pages_w},{ps},512]" in c for c in calls) == 3
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = (\S+) ([\w\-]+)\(", flat, re.M)
    pools = (f"[1,{pages_g},{ps},512]", f"[3,{pages_w},{ps},512]")
    moved = {op for shape, op in ops if shape.endswith(pools)
             and not shape.startswith("(")}
    assert moved <= {"parameter", "get-tuple-element", "scatter", "fusion",
                     "bitcast"}, moved
    # no context gathered at the table's width (or the window's span)
    assert not [shape for shape, _ in ops
                if f"[{slots * table_width},{ps},512]" in shape
                or f"[{slots},{table_width * ps}," in shape]


@pytest.mark.parametrize("operands", ["copies", "float32_weights"])
def test_decode_step_of_a_float32_model_under_amp_on_the_v5e_casts_no_stack(
        one_chip, monkeypatch, operands):
    """The decode op at GPT-2 medium's widths (1024, 16 heads, FFN 4096,
    vocabulary 50304; two layers) under AMP, compiled for the chip. Handed
    the float32 weights, as an engine's programs were before it held AMP
    operand copies, the call opens by rewriting every matmul stack as bf16
    (one ``convert`` a stack, hoisted out of the layer loop: 2.1 ms of
    every call at 24 layers) and the head with them; handed the copies an
    engine holds now (``LMSpec.amp_operand_names``, the block stating
    float32), no weight stack is converted and no float32 stack is read.
    The first form is compiled too so that this fails if the copies stop
    doing anything."""
    import paddle_tpu as pt
    from paddle_tpu.lm_spec import LMSpec
    from paddle_tpu.ops.pipeline_ops import transformer_stack_paged_decode

    spec = LMSpec(vocab_size=50304, d_model=1024, n_layers=2, num_heads=16,
                  max_len=1024)
    slots, pages, ps, table_width = 32, 96, 64, 16
    copied = {"HeadW"} | {
        slot for slot, key, _, _ in spec.stack_planes()
        if f"lm_stack.stack_{key}" in spec.amp_operand_names()}
    assert copied == {"HeadW", "QkvW", "OutW", "FfW1", "FfW2"}
    held = copied if operands == "copies" else set()
    f32 = "float32"
    shapes = {
        "Tok": ((slots,), "int32"), "Pos": ((slots,), "int32"),
        "BlockTable": ((slots, table_width), "int32"),
        "CacheK": ((spec.n_layers, pages, ps, spec.d_model), f32),
        "CacheV": ((spec.n_layers, pages, ps, spec.d_model), f32),
        "TokEmb": ((spec.vocab_size, spec.d_model), f32),
        "PosEmb": ((spec.max_len, spec.d_model), f32),
        "FinalLnS": ((spec.d_model,), f32),
        "FinalLnB": ((spec.d_model,), f32),
        "HeadW": ((spec.d_model, spec.vocab_size), f32)}
    for slot, _key, shape, _fan in spec.stack_planes():
        shapes[slot] = ((spec.n_layers, *shape), f32)
    shapes = {slot: (shape, "bfloat16" if slot in held else dtype)
              for slot, (shape, dtype) in shapes.items()}
    names = sorted(shapes)
    attrs = dict(spec.block.attrs(), page_size=ps)
    if held:
        attrs["param_dtype"] = spec.param_dtype

    def step(*args):
        outs = transformer_stack_paged_decode(
            attrs, {k: [a] for k, a in zip(names, args)})
        return {k: v[0] for k, v in outs.items()}

    pt.set_amp(True)    # (conftest's autouse fixture puts the policy back)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    donate = tuple(names.index(n) for n in ("CacheK", "CacheV"))
    text = jax.jit(step, donate_argnums=donate).lower(*[
        jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1], sharding=one_chip)
        for n in names]).compile().as_text()
    assert "%paged_attention_decode" in text
    flat = re.sub(r"\{[^{}]*\}", "", text)
    stack = rf"\[{spec.n_layers},(?:1024|3072|4096),(?:1024|3072|4096)\]"
    converted = re.findall(rf"= bf16({stack}) convert\(", flat)
    read_f32 = re.findall(rf"= f32{stack} parameter\(", flat)
    head_cast = re.findall(r"= bf16\[1024,50304\] convert\(", flat)
    if held:
        assert not converted and not read_f32 and not head_cast
    else:
        assert sorted(converted) == ["[2,1024,1024]", "[2,1024,3072]",
                                     "[2,1024,4096]", "[2,4096,1024]"]
        assert read_f32 and head_cast


@pytest.mark.parametrize("remat,flash_fwd,matmuls", [(True, 1, 16),
                                                     ("full", 2, 18)])
def test_stacked_train_step_on_the_v5e_runs_its_forward_scan_once(
        one_chip, monkeypatch, remat, flash_fwd, matmuls):
    """The stacked LM's whole train step (GPT-2 medium's width, heads and
    context; two layers) compiled for the chip through the Executor: ONE
    forward scan and one backward scan, and a layer's Mosaic calls are one
    ``flash_dq``, one ``flash_dkv`` and ONE ``flash_fwd`` under
    ``remat=True`` (the layer checkpoint saves the call's own residuals,
    and of the forward's projections the backward scan's body holds the
    FFN's first alone: the step's ``convolution`` instructions are the
    forward's four, the backward's eight, that one and the head's three),
    two under ``"full"`` (forward, recompute: and the qkv and out
    projections again, 18). With the generic grad op tracing the stack a
    second time (before core/backward.py paired them) the chip's compiler
    kept three loops and three ``flash_fwd``: XLA does not merge two loops.
    Under AMP, as the train cells run it: every flash call takes bf16
    packed ``[batch, T, heads * d_head]`` operands at the blocks the kernel
    file picks for them, so a block shape Mosaic refuses for bf16 fails
    HERE.
    (Lives here because one file a worker may describe the topology.)"""
    import chip_smoke
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    T, V, B = 1024, 512, 2
    pt.set_amp(True)    # (conftest's autouse fixture puts the policy back)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        logits = models.transformer_lm(
            ids, vocab_size=V, d_model=1024, n_layers=2, num_heads=16,
            max_len=T, pipeline_stack=True, remat=remat)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, V]),
            layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=1e-4).minimize(
            loss, startup_program=startup)
    scope = pt.Scope()
    for name, v in main.global_block.vars.items():
        if v.persistable:
            scope.set(name, jax.ShapeDtypeStruct(
                tuple(v.shape), np.dtype(str(v.dtype)), sharding=one_chip))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # ... which would also switch the TPU's on-disk compile cache on for
    # the rest of this worker's tests
    monkeypatch.setattr("paddle_tpu.core.executor."
                        "_maybe_enable_compilation_cache", lambda: None)
    from paddle_tpu import trace

    tracer = trace.get_tracer()
    texts, gauges = [], []
    # compiled twice from ONE call site (source locations are part of the
    # text): as the cells run it, then with tracing on, as a traced run
    for level in (0, 1) if remat is True else (0,):
        exe = pt.Executor(pt.TPUPlace())
        tracer.clear()
        trace.enable(level=level)
        try:
            assert exe.warm_signature(main, {"ids": ((B, T), "int64"),
                                             "tgt": ((B, T), "int64")},
                                      [loss.name], scope=scope)
            gauges += [s.attrs["mem/stack_saved_bytes"]
                       for s in tracer.spans() if s.name == "executor/compile"]
        finally:
            trace.disable()
            tracer.clear()
        assert exe.cache_stats()["paired_vjp_ops"] == 1
        (compiled,) = exe._cache.values()
        texts.append(compiled.aot.as_text())
    text = texts[0]
    assert len(re.findall(r"= .* while\(", text)) == 2
    calls = chip_smoke.mosaic_calls(text)
    assert sorted(name for name, _ in calls) == [
        "flash_dkv", "flash_dq"] + ["flash_fwd"] * flash_fwd
    assert text.count(" convolution(") == matmuls
    assert not chip_smoke.flash_operands_not_bf16(calls, B, T, 1024)
    # the check can fail: it tells a float32-fed call
    assert chip_smoke.flash_operands_not_bf16(
        [("flash_fwd", ["s32[2]"] + ["f32[2,1024,1024]"] * 3)], 2, T, 1024)
    if remat is True:
        # with tracing on the op also asks JAX what a layer's backward
        # holds (the gauge on the compile span: 5 d of bf16 a token a
        # layer + logsumexp), and the step still compiles to the SAME
        # text, source locations included: they reach the compile cache's
        # key through the Mosaic calls
        assert gauges == [2 * (B * T * 5 * 1024 * 2 + B * 16 * T * 4)]
        assert texts[1] == text


# ---------------------------------------------------------------------------
# the dispatch rule of _scan_paged_layers
# ---------------------------------------------------------------------------
def _spy_on_the_walks(monkeypatch, backend):
    """``jax.default_backend`` answered as ``backend``; the kernels, where
    chosen, run in interpret mode -> the calls made, by name."""
    calls = []

    def spy(kernel):
        def run(*args, **kwargs):
            calls.append((kernel.__name__, kwargs))
            return kernel(*args, interpret=True, **kwargs)
        return run

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    for kernel in (paged_attention_decode, paged_attention_prefill):
        monkeypatch.setattr(paged_attention, kernel.__name__, spy(kernel))
    return calls


def _run_layers(monkeypatch, backend, dtype=jnp.float32, heads=4, kv_heads=4,
                d_head=32, t=1, causal=False, ps=PS, chunk=False,
                lower=False):
    """One pass of ``_scan_paged_layers`` over toy projections with the
    backend reported as ``backend``; the kernels, where chosen, run in
    interpret mode. ``causal``: a block-causal mask without the chunk's
    length; ``chunk``: a prefill chunk's own mask (``chunk_mask``), every
    query real. -> (h, how often a kernel was traced), or with ``lower``
    the pass's lowered text."""
    from paddle_tpu.ops.pipeline_ops import chunk_mask

    calls = _spy_on_the_walks(monkeypatch, backend)
    b, d = 3, heads * d_head
    width = kv_heads * d_head
    rng = np.random.default_rng(0)
    ck, cv = (jnp.asarray(rng.standard_normal((L, N, ps, width)), dtype)
              for _ in range(2))
    h = jnp.asarray(rng.standard_normal((b, t, d)), jnp.float32)
    table = jnp.asarray(_table([[3, 4], [5, 9], [6, 7, 8, 10]]))
    pos = jnp.asarray([ps + 2, 0, 2 * ps + 5], jnp.int32)
    at = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    page_id = jnp.take_along_axis(table, at // ps, axis=1)

    def project(p, x):
        q = x.reshape(b, t, heads, d_head).transpose(0, 2, 1, 3)
        kv = (x * p["w"]).reshape(b, t, heads, d_head)[:, :, :kv_heads]
        kv = kv.transpose(0, 2, 1, 3)
        return q, kv, 0.5 * kv

    if chunk:
        mask = chunk_mask(pos, jnp.full((b,), t, jnp.int32))
    elif causal:
        mask = dict(causal=True, q_pos0=pos)
    else:
        mask = dict(lengths=pos + t)
    def run(h, ck, cv):
        return _scan_paged_layers(
            {"w": jnp.linspace(0.5, 1.5, L)[:, None]}, h, ck, cv, table,
            page_id, at % ps, project, mask,
            lambda p, x, ctx, _x: (x + ctx.astype(x.dtype), None))[0]

    if lower:
        return jax.jit(run).lower(h, ck, cv).as_text()
    return np.asarray(run(h, ck, cv)), [name for name, _ in calls]


def test_a_decode_step_on_a_chip_takes_the_kernel(monkeypatch):
    """t == 1 + TPU + a lane-aligned row + lengths-only mask: the kernel,
    once in the scanned layer body, and the same h as the gathered path."""
    got, traced = _run_layers(monkeypatch, "tpu")
    want, none = _run_layers(monkeypatch, "cpu")
    assert (traced, none) == (["paged_attention_decode"], [])
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("heads,kv_heads", [(2, 2), (4, 2)],
                         ids=["mha", "gqa"])
def test_a_prefill_chunk_on_a_chip_takes_the_chunk_walk(monkeypatch, heads,
                                                        kv_heads):
    """A chunk's own mask + TPU + heads of whole lane rows + a chunk of
    whole sublane tiles: the chunk walk, once in the scanned layer body,
    never the decode kernel, and the same h as the gathered path."""
    kw = dict(heads=heads, kv_heads=kv_heads, d_head=128, t=16, chunk=True)
    got, traced = _run_layers(monkeypatch, "tpu", **kw)
    want, none = _run_layers(monkeypatch, "cpu", **kw)
    assert (traced, none) == (["paged_attention_prefill"], [])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("why,kwargs", [
    ("not a TPU", dict(backend="cpu")),
    ("not a TPU", dict(backend="gpu")),
    ("a block-causal mask without the chunk's length: t > 1",
     dict(backend="tpu", t=4, causal=True)),
    ("block-causal mask", dict(backend="tpu", causal=True)),
    ("row narrower than the lanes", dict(backend="tpu", heads=2, kv_heads=2)),
    ("grouped-query over a row narrower than the lanes",
     dict(backend="tpu", heads=4, kv_heads=2)),
    ("page of half a bf16 tile", dict(backend="tpu", dtype=jnp.bfloat16,
                                      ps=8)),
    ("a chunk off the chip", dict(backend="cpu", heads=2, kv_heads=2,
                                  d_head=128, t=16, chunk=True)),
    ("a chunk of heads narrower than the lanes",
     dict(backend="tpu", heads=4, kv_heads=4, d_head=64, t=16, chunk=True)),
    ("a chunk of half a sublane tile",
     dict(backend="tpu", heads=2, kv_heads=2, d_head=128, t=4, chunk=True)),
    ("a chunk over pages of half a bf16 tile",
     dict(backend="tpu", heads=2, kv_heads=2, d_head=128, t=16, chunk=True,
          dtype=jnp.bfloat16, ps=8)),
])
def test_everything_else_keeps_the_gathered_reference(monkeypatch, why,
                                                      kwargs):
    got, traced = _run_layers(monkeypatch, **kwargs)
    assert not traced, why
    assert np.isfinite(got).all()


def _latent_step(mask, W=128, r=128, rope_d=32, t=16, dtype=jnp.float32):
    """One layer of ``_mla_paged_step`` over toy projections (a latent of r
    and rope_d rotary columns in a pool row W wide) -> (attend, its
    arguments)."""
    from paddle_tpu.lm_spec import Block
    from paddle_tpu.ops import pipeline_ops

    blk = Block(num_heads=4, use_rope=True, norm="rms_norm", bias=False,
                attn="mla", q_lora_rank=8, kv_lora_rank=r,
                qk_nope_head_dim=8, qk_rope_head_dim=rope_d, v_head_dim=16)
    b = 2
    rng = np.random.default_rng(0)
    ck = jnp.asarray(rng.standard_normal((1, 6, 16, W)), dtype)
    p = {"kv_b_w": jnp.asarray(rng.standard_normal((r, 4 * 24)),
                               jnp.float32)}
    proj = tuple(jnp.asarray(0.3 * rng.standard_normal(shape), jnp.float32)
                 for shape in ((b, 4, t, 8), (b, 4, t, rope_d), (b, t, r),
                               (b, t, rope_d)))
    start = jnp.asarray([0, 16], jnp.int32)
    table = jnp.asarray([[1, 0, 0], [2, 3, 0]], jnp.int32)
    at = start[:, None] + jnp.arange(t)[None, :]
    if mask == "chunk":
        mask = pipeline_ops.chunk_mask(start, jnp.full((b,), t, jnp.int32))
    elif mask == "causal":
        mask = dict(causal=True, q_pos0=start)
    attend = pipeline_ops._mla_paged_step(
        blk, b, t, lambda layer_p, h: proj, mask,
        lambda layer_p, h, ctx, x_l: (ctx, None))
    return attend, (jnp.zeros((b, t, 64)), ck, None, 0, p, None, table,
                    jnp.take_along_axis(table, at // 16, axis=1), at % 16)


@pytest.mark.parametrize("W,r,rope_d", [(256, 128, 64), (384, 256, 64)],
                         ids=["w256-r128", "w384-r256"])
def test_a_latent_chunk_on_a_chip_takes_the_chunk_walk(monkeypatch, W, r,
                                                       rope_d):
    """``_mla_paged_step`` under a chunk's own mask + TPU + row and latent
    of whole lane rows: the chunk walk over the ONE pool (no V pool, the
    scale on the queries, the latent the value), never the decode kernel,
    and the context of the gathered path."""
    calls = _spy_on_the_walks(monkeypatch, "tpu")
    attend, args = _latent_step("chunk", W, r, rope_d)
    got, *_ = attend(*args)
    assert calls == [("paged_attention_prefill",
                      {"sm_scale": 1.0, "value_width": r})]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    attend, args = _latent_step("chunk", W, r, rope_d)
    want, *_ = attend(*args)
    assert len(calls) == 1 and got.shape == want.shape == (2, 16, 4 * 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4,
                               rtol=0)


@pytest.mark.parametrize("why,backend,mask,W,r", [
    ("not a TPU", "cpu", "chunk", 256, 128),
    ("a latent narrower than a lane row", "tpu", "chunk", 128, 96),
    ("a block-causal mask without the chunk's length", "tpu", "causal",
     256, 128),
])
def test_every_other_latent_chunk_keeps_the_gathered_form(monkeypatch, why,
                                                          backend, mask, W,
                                                          r):
    """Off the rule (``chunk_supported``) neither kernel: the absorbed
    gathered attention, the chunk's length left behind (the same context
    with and without it)."""
    calls = _spy_on_the_walks(monkeypatch, backend)
    ctxs = []
    for m in (mask, "causal"):
        attend, args = _latent_step(m, W, r)
        ctx, *_ = attend(*args)
        ctxs.append(np.asarray(ctx))
    assert not calls, why
    assert ctxs[0].shape == (2, 16, 4 * 16) and np.isfinite(ctxs[0]).all()
    np.testing.assert_array_equal(*ctxs)


def _parent_mla_attend(blk, b, t, project, mask, finish):
    """``_mla_paged_step``'s ``attend`` as it was before a latent chunk
    could walk: the decode kernel or the gathered form."""
    from paddle_tpu.ops import pipeline_ops as po

    r, rope_d = blk.kv_lora_rank, blk.qk_rope_head_dim
    scale = po._sm_scale(blk)

    def attend(h, ck, cv, l, layer_p, x_l, tbl, ix_page, ix_row, **_kw):
        q_nope, q_rope, c_kv, k_rope = project(layer_p, h)
        W = ck.shape[-1]
        row = jnp.concatenate([c_kv, k_rope], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0), (0, W - row.shape[-1])))
        ck = ck.at[l, ix_page, ix_row].set(row.astype(ck.dtype))
        w_uk, w_uv = po._mla_up(blk, layer_p)
        q_abs = po._mm(blk, "bhtn,rhn->bhtr", q_nope, w_uk)
        q_lat = jnp.concatenate([q_abs, q_rope], axis=-1) * scale
        q_lat = jnp.pad(q_lat, ((0, 0),) * 3 + ((0, W - r - rope_d),))
        if t == 1 and set(mask) == {"lengths"} \
                and paged_attention.supported(W, ck, t):
            o_lat = paged_attention.paged_attention_decode(
                q_lat[:, :, 0], ck, None, l, tbl, mask["lengths"],
                sm_scale=1.0, name=paged_attention.MLA_KERNEL)
            o_lat = o_lat.reshape(b, -1, 1, W)
        else:
            lat = ck[l, tbl].reshape(b, 1, tbl.shape[1] * ck.shape[2], W)
            o_lat = reference_attention(q_lat.astype(ck.dtype), lat,
                                        lat[..., :r], sm_scale=1.0,
                                        **po._gathered_mask(mask))
        ctx = po._mm(blk, "bhtr,rhv->bthv", o_lat[..., :r].astype(h.dtype),
                     w_uv).reshape(b, t, -1)
        h, stats = finish(layer_p, h, ctx, x_l)
        return h, ck, cv, stats

    return attend


def _paged_texts(monkeypatch):
    """The lowered text of every kind of paged layer step this file drives,
    as the CPU mesh runs them: a latent chunk at lane-aligned and at
    unaligned widths, a latent tick, and the K/V stack's tick, verify-free
    chunk and block-causal call (``_run_layers``' operands)."""
    texts = {}
    for name, kw in (("latent-chunk", dict(mask="chunk", W=384, r=256,
                                           rope_d=64)),
                     ("latent-chunk-narrow", dict(mask="chunk", W=128, r=96)),
                     ("latent-causal", dict(mask="causal", W=256, r=128))):
        attend, args = _latent_step(**kw)
        texts[name] = jax.jit(attend).lower(*args).as_text()

    for name, kw in (("kv-tick", {}),
                     ("kv-chunk", dict(heads=2, kv_heads=2, d_head=128, t=16,
                                       chunk=True)),
                     ("kv-gqa-chunk", dict(heads=4, kv_heads=2, d_head=128,
                                           t=16, chunk=True)),
                     ("kv-causal", dict(t=4, causal=True))):
        texts[name] = _run_layers(monkeypatch, "cpu", lower=True, **kw)
    return texts


def test_the_cpu_programs_lower_to_the_text_they_lowered_to(monkeypatch):
    """Off the chip nothing changed: the latent block's prefill chunk (and
    its other calls) lower byte for byte to what the step's parent form
    lowers to (kept above: decode kernel or gather, no chunk walk, the old
    ``chunk_supported``), and so does every K/V program."""
    from paddle_tpu.ops import pipeline_ops

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    texts = _paged_texts(monkeypatch)
    assert "reduce" in texts["latent-chunk"]    # the gathered softmax
    monkeypatch.setattr(pipeline_ops, "_mla_paged_step", _parent_mla_attend)
    rule = paged_attention.chunk_supported
    monkeypatch.setattr(
        paged_attention, "chunk_supported",
        lambda q_shape, pool, mask: rule(q_shape, pool, mask))
    parent = _paged_texts(monkeypatch)
    assert sorted(texts) == sorted(parent) and len(texts) == 7
    for name in texts:
        assert texts[name] == parent[name], name


@pytest.mark.parametrize("dtype,ps,ok", [
    (jnp.float32, 8, True), (jnp.float32, 4, False),
    (jnp.bfloat16, 16, True), (jnp.bfloat16, 8, False)])
def test_supported_reads_shapes_dtype_and_backend_only(monkeypatch, dtype,
                                                       ps, ok):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = jax.ShapeDtypeStruct((2, 8, ps, 256), dtype)
    assert paged_attention.supported(256, pool, 1) is ok
    # the two positions of a verify tick stay on the page walk; a chunk not
    assert paged_attention.supported(256, pool, 2) is ok
    assert not paged_attention.supported(256, pool, 3)
    assert paged_attention.supported(512, pool, 1) is ok    # Hkv = H / 2
    assert not paged_attention.supported(384, pool, 1)      # no whole groups
    narrow = jax.ShapeDtypeStruct((2, 8, ps, 64), dtype)
    assert not paged_attention.supported(64, narrow, 1)
    # the chunk form: the same page rule, over [b, H, t, dh] and the mask
    chunk = paged_attention.CHUNK_MASK
    assert paged_attention.chunk_supported((1, 2, 64, 128), pool, chunk) is ok
    assert paged_attention.chunk_supported((1, 4, 64, 128), pool, chunk) is ok
    assert not paged_attention.chunk_supported((1, 2, 2, 128), pool, chunk)
    assert not paged_attention.chunk_supported((1, 3, 64, 128), pool, chunk)
    assert not paged_attention.chunk_supported((1, 4, 64, 64), pool, chunk)
    assert not paged_attention.chunk_supported((1, 2, 60, 128), pool, chunk)
    assert not paged_attention.chunk_supported(
        (1, 2, 64, 128), pool, {"causal", "q_pos0"})
    # a latent pool (no V pool: the row's first ``value_width`` columns the
    # value): queries as wide as the row, row and latent of whole lane rows
    for W, r, fits in ((384, 256, True), (640, 512, True), (128, 128, True),
                       (384, 320, False), (320, 256, False),
                       (128, 96, False), (384, 0, False), (384, 512, False)):
        lat = jax.ShapeDtypeStruct((1, 8, ps, W), dtype)
        assert paged_attention.chunk_supported(
            (1, 32, 256, W), lat, chunk, r) is (ok and fits), (W, r)
        assert paged_attention.chunk_supported(
            (1, 32, 64, W), lat, chunk, r) is (ok and fits), (W, r)
    lat = jax.ShapeDtypeStruct((1, 8, ps, 384), dtype)
    assert not paged_attention.chunk_supported((1, 32, 2, 384), lat, chunk,
                                               256)
    assert not paged_attention.chunk_supported((1, 32, 60, 384), lat, chunk,
                                               256)
    assert not paged_attention.chunk_supported((1, 32, 64, 256), lat, chunk,
                                               256)       # q not the row's
    assert not paged_attention.chunk_supported(
        (1, 32, 64, 384), lat, {"causal", "q_pos0"}, 256)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not paged_attention.chunk_supported((1, 2, 64, 128), pool, chunk)
    assert not paged_attention.chunk_supported((1, 32, 64, 384), lat, chunk,
                                               256)


def test_wrapper_refuses_mismatched_operands():
    ck, cv = _pools(jnp.float32, 256)
    table = jnp.zeros((2, P), jnp.int32)
    lengths = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="does not match the pools"):
        paged_attention_decode(jnp.zeros((2, 4, 32)), ck, cv, 0, table,
                               lengths, interpret=True)
    with pytest.raises(ValueError, match=r"\[b, H, dh\]"):
        paged_attention_decode(jnp.zeros((2, 256)), ck, cv, 0, table,
                               lengths, interpret=True)
    with pytest.raises(ValueError, match="does not match the pools"):
        paged_attention_prefill(jnp.zeros((2, 3, 16, 128)), ck, cv, 0, table,
                                lengths, lengths, interpret=True)
    with pytest.raises(ValueError, match=r"\[b, H, Tc, dh\]"):
        paged_attention_prefill(jnp.zeros((2, 2, 128)), ck, cv, 0, table,
                                lengths, lengths, interpret=True)
    with pytest.raises(ValueError, match="whole sublane tiles"):
        paged_attention_prefill(jnp.zeros((2, 2, 12, 128)), ck, cv, 0, table,
                                lengths, lengths, interpret=True)
    # one pool: queries as wide as its row, and a value width inside it
    for q_width, value_width in ((128, 128), (256, None), (256, 512)):
        with pytest.raises(ValueError, match="does not match the pools"):
            paged_attention_prefill(jnp.zeros((2, 2, 16, q_width)), ck, None,
                                    0, table, lengths, lengths,
                                    interpret=True, value_width=value_width)
    with pytest.raises(ValueError, match="does not match the pools"):
        paged_attention_prefill(jnp.zeros((2, 2, 16, 128)), ck, cv, 0, table,
                                lengths, lengths, interpret=True,
                                value_width=128)
