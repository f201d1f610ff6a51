"""Grouped matmul for sorted assignment rows: the expert layer's three
products in the serving programs, prefill unit and decode tick (Pallas, TPU).

``rows`` [M, K] are sorted by group, group g owning the ``sizes[g]`` rows
after those of groups < g; row r of group g is multiplied by ``w[g]``.
``jax.lax.ragged_dot`` does the same and its cost follows the STATIC rows:
at a tick's 192-256 rows it streams the touched experts' weights at 52-60%
of the chip's bandwidth, at a unit's 512-2048 rows at a third of it (the
table below).
Here the grid walks VISITS: the (row tile, group) pairs in which the group
owns a row of the tile, ``M / tm + E - 1`` of them at most, built on the
device from ``sizes`` and handed to the index maps as prefetched scalars. A
visit multiplies its row tile [tm, K] by a [K, tn] block of the group's
weights (operands as they come, bf16 under AMP, float32 accumulation) and
stores under a mask of the rows the group owns. Columns are the OUTER grid
axis, so a group that straddles row tiles keeps its block (consecutive
visits, one block index: no second fetch) and a row tile's result stays in
fast memory until every group in it has stored. Visits past the live count
repeat the last live visit's indices: nothing is fetched, nothing computed.
A group without rows is never visited, so its weights are never read; rows
behind the last group (the ``held`` form's absent assignments) are visited
by nothing and hold whatever was there: ``moe_topk`` masks them.

``layer`` is a prefetched scalar: ``w`` is then the WHOLE stack flattened
to [L * E, K, N] and group g reads plane ``layer * E + g``; no layer is
sliced and no ``L * E``-wide size vector is built.

``grouped_supported`` is the whole dispatch rule of ``ops/moe_ops.moe_topk``.

Both implementations by shape (``python tools/grouped_matmul_sweep.py
--row-tile 64 128 --seed 51001``; my chip run, PR 51, one v5e): ms a call and
the share of 819 GB/s its bytes (the touched experts' planes + the owned
rows in and out) make of it, ``jax.lax.ragged_dot`` under ``layer`` (L * E
groups, as ``moe_topk`` called it) against this kernel at row tile 128 (64
for 192 rows), group sizes drawn evenly over the router's experts. Held
shares: mistral4 32 of 128, ling3 128 of 512, solar2 40 of 320, kexaone 8
of 128, so 1/4 .. 1/16 of the static rows are owned.

    cell          rows  K -> N        touched  MB     ragged_dot    kernel
    olmoe          256  2048 -> 1024   64/64   270.5  0.572 (58%)   0.385 (86%)
    olmoe          512  2048 -> 1024   64/64   272.6  0.879 (38%)   0.389 (86%)
    olmoe         1024  2048 -> 1024   64/64   276.8  0.894 (38%)   0.400 (84%)
    smallthinker   192  2560 ->  768   62/64   245.4  0.541 (55%)   0.352 (85%)
    smallthinker  1536  2560 ->  768   64/64   264.2  0.992 (32%)   0.396 (82%)
    mistral4       256  4096 -> 2048   29/32   487.8  0.988 (60%)   0.679 (88%)
    mistral4      1024  4096 -> 2048   32/32   540.9  1.705 (39%)   0.762 (87%)
    ling3         1024  2560 ->  768  112/128  442.5  1.635 (33%)   0.623 (87%)
    solar2         512  4096 -> 1280   27/40   283.9  1.054 (33%)   0.413 (84%)
    solar2        2048  4096 -> 1280   40/40   422.5  1.549 (33%)   0.598 (86%)
    kexaone       1024  6144 -> 2048    8/8    202.3  0.654 (38%)   0.308 (80%)
    kexaone       2048  6144 -> 2048    8/8    203.8  0.652 (38%)   0.310 (80%)

The down products (N -> K of each row) read within 3% of these, both ways;
with half the tokens routed alike (vacant slots) both fall with the touched
experts and keep their shares (olmoe 256: 54 touched, 0.489 against 0.330
ms). ``ragged_dot`` holds 52-60% of the bandwidth up to 256 rows and 32-39%
above, whatever the rows own; the kernel 80-88% at every shape, so it takes
every call of the serving form, a tick's as a unit's. Fewer rows than any
cell runs (``--cells olmoe mistral4 --rows 16 32 64 128 --seed 51002``,
even draw, ms at olmoe's / mistral4's widths): 16 rows 0.120 / 0.096
against 0.098 / 0.095 (level within 2% at mistral4's), 32: 0.196 / 0.214
against 0.172 / 0.205, 64: 0.275 / 0.371 against 0.239 / 0.343, 128: 0.390
/ 0.544 against 0.312 / 0.460; ahead by 8-15% at 64 rows and level at 16,
hence ``MIN_ROWS``. Row tile 64 against 128: within 2% either way (the MXU's
cost a visit is the plane's 128 x 128 tiles, not the rows), so 128, which
has fewer visits. A reading is a chain of 48 dependent calls over the
stack's layers by the host clock, the visit list's XLA ops included.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the call's name: on the chip the HLO instruction carries it
#: (``%grouped_matmul.3 = f32[1536,768] custom-call(.., bf16[768,2560,768])``)
KERNEL = "grouped_matmul"

#: rows a visit multiplies at most. Every visit pays a whole tile of MXU
#: work whatever the group owns of it (a mean group holds 16-24 rows)
ROW_TILE = 128
#: bytes of one weight block [K, tn] (two are in flight)
_BLOCK_BYTES = 6 << 20
_VMEM = 64 << 20
#: rows from which ``moe_topk`` calls the kernel on a TPU (the table:
#: ahead of ``ragged_dot`` by 8-15% here, level with it at 16 rows)
MIN_ROWS = 64


def _row_tile(m: int, dtype) -> int | None:
    """The largest row tile of whole sublane tiles that divides ``m``."""
    sub = 32 // jnp.dtype(dtype).itemsize            # 8 float32, 16 bf16
    for tm in (ROW_TILE, 64, 32, 16, 8):
        if tm >= sub and m % tm == 0:
            return tm
    return None


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """The widest column tile of whole lane tiles that divides ``n`` and
    keeps a [k, tn] block inside ``_BLOCK_BYTES``."""
    lanes = n // 128
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and k * (n // parts) * itemsize <= _BLOCK_BYTES:
            return n // parts
    return 128


def grouped_supported(rows: int, k_in: int, n_out: int, dtype,
                      layer) -> bool:
    """Whether ``moe_topk`` multiplies ``rows`` sorted assignment rows
    [rows, k_in] of ``dtype`` by [.., k_in, n_out] expert planes with this
    kernel: a TPU backend, the serving form (``layer`` not None: the whole
    stack and an index, where no gradient is ever taken), widths of whole
    lane tiles, rows of whole sublane tiles, and ``MIN_ROWS`` rows or more."""
    return (jax.default_backend() == "tpu" and layer is not None
            and rows >= MIN_ROWS and k_in % 128 == 0 and n_out % 128 == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
            and _row_tile(rows, dtype) is not None)


def visits(sizes, m: int, tm: int):
    """The visit list of ``sizes`` [E] over ``m`` rows in tiles of ``tm``:
    (group [V], tile [V], offsets [E + 1], live [1]), V = m / tm + E - 1.
    Visit v < live multiplies row tile ``tile[v]`` by group ``group[v]``,
    which owns rows ``offsets[g] <= r < offsets[g + 1]``; visits are ordered
    by group, so by tile too. Visits >= live repeat the last live one."""
    n_groups = sizes.shape[0]
    # sizes that sum beyond the rows own what is there
    ends = jnp.minimum(jnp.cumsum(sizes.astype(jnp.int32)), m)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    first = starts // tm
    tiles = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(tiles)                     # visits of groups <= g
    live = upto[-1]
    v = jnp.minimum(jnp.arange(m // tm + n_groups - 1, dtype=jnp.int32),
                    jnp.maximum(live - 1, 0))
    # the group of visit v: as many groups as end at or before it
    group = jnp.minimum(jnp.sum(upto[None, :] <= v[:, None], axis=1,
                                dtype=jnp.int32), n_groups - 1)
    tile = first[group] + v - (upto[group] - tiles[group])
    offsets = jnp.concatenate([starts[:1], ends])
    return group, tile, offsets, live.reshape(1)


def _kernel(layer_ref, group_ref, tile_ref, off_ref, live_ref, rows_ref,
            w_ref, o_ref, *, precision):
    from jax.experimental import pallas as pl

    del layer_ref                                # the index maps' alone
    v = pl.program_id(1)
    tm = rows_ref.shape[0]

    @pl.when(v < live_ref[0])
    def _():
        g, t = group_ref[v], tile_ref[v]
        rows = rows_ref[...]
        acc = jnp.dot(rows, w_ref[...].astype(rows.dtype),
                      precision=precision,
                      preferred_element_type=jnp.float32)       # [tm, tn]
        r = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        owned = (r >= off_ref[g]) & (r < off_ref[g + 1])
        # the first visit of a row tile finds another tile's result (or
        # nothing yet) in the block: rows no group owns read zero
        fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
        kept = jnp.where(fresh, 0.0, o_ref[...])
        o_ref[...] = jnp.where(owned, acc, kept)


def grouped_matmul(rows, w, sizes, *, layer=None, precision=None,
                   interpret=False):
    """``rows`` [M, K] (bf16 | float32) x ``w`` [G, K, N] by groups of
    ``sizes`` [E] int32 -> [M, N] float32, as
    ``jax.lax.ragged_dot(rows, w.astype(rows.dtype), sizes,
    preferred_element_type=float32)`` gives it for the rows the groups own
    (a weight block is cast to the rows' type as it is used, never the
    stack). ``layer`` (a traced scalar): ``w`` is [L * E, K, N] and group g
    reads plane ``layer * E + g``; None: G == E. Rows behind the last group
    are not written."""
    if rows.ndim != 2 or w.ndim != 3 or sizes.ndim != 1 \
            or w.shape[1] != rows.shape[1]:
        raise ValueError(f"rows {rows.shape} / w {w.shape} / sizes "
                         f"{sizes.shape} are no [M, K] x [G, K, N] by [E]")
    if w.shape[0] % sizes.shape[0] or (layer is None
                                       and w.shape[0] != sizes.shape[0]):
        raise ValueError(f"{w.shape[0]} planes are not layers of "
                         f"{sizes.shape[0]} groups")
    if _row_tile(rows.shape[0], rows.dtype) is None or w.shape[2] % 128:
        raise ValueError(f"rows {rows.shape} -> {w.shape[2]} columns are "
                         "not whole tiles")
    # operands of ONE type whatever the caller holds them as: the call
    # sites of one program (three a layer kind) then share a trace and a
    # lowering a shape, where each site of the bare ``pallas_call`` costs
    # ~0.3 s of every start-up
    return _visit(rows, w, sizes.astype(jnp.int32),
                  jnp.reshape(0 if layer is None else layer,
                              (1,)).astype(jnp.int32),
                  precision=precision, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("precision", "interpret"))
def _visit(rows, w, sizes, layer, *, precision, interpret):
    """``grouped_matmul``'s call, on checked operands."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = w.shape[2]
    n_groups = sizes.shape[0]
    tm = _row_tile(m, rows.dtype)
    tn = _col_tile(k, n, jnp.dtype(w.dtype).itemsize)
    group, tile, offsets, live = visits(sizes, m, tm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,      # layer, group, tile, offsets, live
        grid=(n // tn, group.shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, layer, group, tile, *_:
                         (tile[v], 0)),
            pl.BlockSpec((None, k, tn), lambda j, v, layer, group, *_:
                         (layer[0] * n_groups + group[v], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, layer, group, tile, *_:
                               (tile[v], j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, precision=precision),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name=KERNEL,
    )(layer, group, tile, offsets, live, rows, w)
