"""Paged decode attention: one Pallas TPU kernel that walks the block table.

The decode tick of the paged serving step attends ONE new token per batch
row to that row's cached context, which lives as pages of a pool
[L, N, ps, Hkv*dh] addressed through a block table [b, P]. The gathered
form (``ops/pipeline_ops._gather_pages`` + ``reference_attention``) moves
b * P pages a layer and pool whatever the rows hold, re-lays the gathered
context out so that d_head is minor, and passes over it twice. This kernel
reads only the pages a row HOLDS, straight from the whole pool:

* the block table, the lengths and the layer index are scalar-prefetch
  operands; the pools stay in HBM (``memory_space=ANY``) and are never
  sliced or copied — row s's page i is DMA'd from ``pool[layer,
  table[s, i]]`` as one contiguous, lane-dense [ps, Hkv*dh] tile, double
  buffered, ``ceil(length / ps)`` of them (at least one: a vacant slot
  reads the scrap page once). The walk is one chain over (row, page) in
  table order: the last page of a row prefetches the first of the next.
* heads are split in VMEM, not in HBM: the page tile is multiplied by a
  block-diagonal query Q_bd [H, H*dh] (row h holds q_h in columns
  h*dh..h*dh+dh-1, zeros elsewhere), so scores = Q_bd @ K_page^T is
  [H, ps] with no per-head lane slicing (d_head 64 is half a lane row);
  P @ V_page is [H, H*dh], of which row h's own dh columns are its
  context; the masked reduction over rows leaves [H*dh] — the context row
  as the out-projection reads it. The extra MXU work (H x) hides under
  the page's bytes.
* the mathematics are ``reference_attention``'s: queries in the pool's
  dtype, float32 scores and softmax (online over pages: running max and
  denominator), 1/sqrt(dh) scale, probabilities cast to the page dtype
  before P @ V, float32 accumulation, keys j < length only. A row's result
  depends on that row's pages alone, walked in table order.

* grouped queries (H = G * Hkv query heads over Hkv cached heads): the
  page tile stays [ps, Hkv*dh] — G times narrower than the queries — and
  the block-diagonal query is [H_pad, Hkv*dh] with row n in the columns of
  KV head n // G, handed in already expanded (a [b, H_pad, Hkv*dh] operand,
  a few KB a row beside the pages). Row n's context sits in acc[n, (n //
  G)*dh ..]; G rows share a column block, so the result leaves as [G,
  Hkv*dh] — row g holding heads g, G+g, 2G+g, .. side by side, selected by
  one 0/1 matmul — and the wrapper puts the heads back in order.
* a window (``window=w``, static): a row of ``length`` keys attends keys
  ``length - w <= j < length`` only; its page walk STARTS at page
  ``max(0, length - w) // ps`` (pages behind it may be gone from the
  table) and masks inside that first page.

* latent attention (``cache_v=None``, ``name=MLA_KERNEL``): in these terms
  ONE cached head of the row's whole width under all H query heads (G = H),
  the value the SAME tile as the key (the page is read once for both
  roles: scores Q [H, W] @ tile^T, context P @ tile, of which the caller
  keeps the latent's columns), and the scale handed in (``sm_scale``; the
  caller's queries carry their own). The body, the page walk and the
  online softmax are the ones above; the call has a name of its own
  because its bytes are counted differently (one pool).

* a prefill chunk (``paged_attention_prefill``, the call name
  ``PREFILL_KERNEL``): Tc queries a row at positions ``start .. start + Tc
  - 1``, the first ``lengths`` of them real. The same operands and the
  same mathematics in a chunk-shaped body (``_prefill_kernel``): the grid
  is (row, query tile); a tile of tq queries walks the pages ITS queries
  can reach (from the window's first page to the page of its last real
  key, never the table's width) in blocks of up to 1024 keys, each page one
  contiguous [ps, Hkv*dh] DMA, double buffered; per cached head the
  tile's G * tq query rows [G*tq, dh] meet the lane-aligned [keys, dh]
  column block of the page tile, so the float32 scores [G*tq, keys] live
  in VMEM and nowhere else (no block-diagonal query here: at chunk shapes
  it would multiply the MXU work by Hkv). Padding queries and padding
  rows attend nothing and leave zeros.

* a latent block's prefill chunk (``cache_v=None`` there too, the call name
  ``MLA_PREFILL_KERNEL``): the chunk-shaped body over ONE pool. The head
  loop steps over groups of QUERY heads (as many as make a score tile of
  ``_LATENT_SCORE_ROWS`` rows) that all meet the SAME [keys, W] tile: scores
  Q [rows, W] @ tile^T, context P @ the tile's lane-aligned first r
  columns (the latent: a token's value), so the result is [b, Tc, H*r] and
  the second dot does r / W of the whole tile's work. The scale is handed
  in (the caller's queries carry theirs). By my chip runs (PR 55,
  ``tools/mla_prefill_sweep.py``; 32 heads, bf16 pages of 256, one row; ms
  a call, share of the MXU's 197 TFLOP/s by 2 x heads x unmasked (query,
  key) pairs x (W + r)):

  ====================================  ========  ============  =====
  chunk behind keys (W, r, table)       gathered  walk          MXU
  ====================================  ========  ============  =====
  256 behind 16896 (384, 256, 20480)    2.82      1.17          77.5%
  64 behind 16896                       0.77      0.34          66.2%
  256 behind 0                          2.78      0.13          5.4%
  256 behind 12032 (640, 512, 12288)    1.90      1.39          83.6%
  256 behind 3840                       1.88      0.50          75.7%
  256 behind 256                        1.89      0.18          20.2%
  64 behind 448                         0.39      0.08          13.7%
  ====================================  ========  ============  =====

  The gathered form costs the table's width whatever the row holds; the
  walk the keys walked: it wins at every length, so the rule has no length
  in it. Tiles (query rows a grid step : rows of a score tile : keys a
  step) 4096:512:1024 as above; 8192:512:1024, 2048:512:1024 and
  4096:1024:1024 read within 1% of it at 16896 keys (1.165-1.186), 256
  score rows +6%, 2048 keys +8%, 512 keys +9% (but 0.10-0.12 where the
  context is under 512 keys: a block's tail is multiplied and masked).

* a group mask (``group_mask`` with ``group_rows``, on the decode and the
  chunk walk): a SPARSE latent layer's pick (``ops/pipeline_ops._dsa_pick``:
  the best ``index_topk / G - 1`` groups of G = ``group_rows`` consecutive
  keys before the query's own, and its own) handed over as one int8 flag a
  GROUP of the table in logical order, [b, NG] a tick, [b, Tc, NG] a chunk
  (NG = table width x page / G). The walk is the unselected layer's (every
  page the row holds, straight from the pool as it lies: no index list, no
  gathered row, no groups-of-G view of the pool); a key is seen iff the
  body's own rule holds AND its group is flagged. The row's (the query
  tile's) flags ride a ``BlockSpec`` into VMEM; a step's slice of them
  (``_mask_lanes``: whole lane rows, or the one lane row the step's groups
  lie in) is widened to the step's keys by ONE small 0/1 product a step
  (``_group_spread``), not once a head step, and tiled over the head group.
  The products over rows that were not picked run on an MXU that idled
  under the gather. By my chip runs (PR 59, ``tools/mla_prefill_sweep.py
  --cells glm53f``: 64 heads, W = r = 512, bf16 pages of 256, a table of
  33792 keys, ONE random pick of 511 groups + its own a query; the gathered
  form = ``_dsa_attend``'s: page ids, the picked groups' rows in query
  tiles of 128, a batched product over them; ms a call of 1024 queries,
  share of the MXU's 197 TFLOP/s by 2 x heads x causal (query, key) pairs x
  (W + r), picked or not):

  ====================  ========  ======  =====
  1024 queries behind   gathered  walk    MXU
  ====================  ========  ======  =====
  0 keys                35.20     1.03    34.0%
  4096                  35.58     4.18    75.1%
  8192                  35.64     7.34    80.8%
  16384                 35.67     13.66   84.3%
  32768 (the table's)   35.68     26.25   86.4%
  ====================  ========  ======  =====

  The gathered form costs the picks (2 MB of rows a query) whatever the
  context; the walk 0.77 ms a 1024 keys walked: they cross near 46k keys
  of reach, past the table. ``MASK_WALK_KEYS`` (the widest TABLE the masked
  walk takes, ``mask_supported``) stands under that, at 40960: a full row
  of such a table walks in ~32 ms. The threshold of the k-th score over
  [128, 8448] float32 (a unit's tile; standalone calls, host dispatch
  included): ``lax.top_k`` 1.35 ms, the counted search 0.59, the whole of
  ``_picked_groups`` 0.48; over the tick's [32, 8448]: 0.89 / 0.50 / 0.52.

  The same walks under a mask of ``group_rows`` 1 over K and V pools (PR 60:
  learned sparse attention on a stack of K/V layers, one indexer key a TOKEN;
  the step's flags are whole lane rows, 256 a page and 1024 a block, and the
  0/1 product that widens them is an identity). By my chip runs (PR 60,
  ``tools/keye2_chip_check.py kernel``: 32 query heads over K and V pools of
  4 x 128, bf16 pages of 256, a table of 25,600 keys, 2047 picked keys + its
  own a query; ms a call of ONE layer; the gathered form =
  ``_dsa_attend_kv``'s; the pick = ``_dsa_pick``: the scores over the table's
  width and the k-th score's counted search):

  ====================  ========  ======  ========  ======
  1024 queries behind   gathered  walk    unmasked  pick
  ====================  ========  ======  ========  ======
  0 keys                          0.36    0.37      1.88
  8192                  132.6     1.44    1.28      1.88
  16384                           2.49    2.26      2.30
  24576 (the table's)             3.56    3.21      2.37
  16 slots x 1 (tick)   gathered  walk    unmasked  pick
  8192                  5.02      0.58    0.60      0.62
  24576                           1.44    1.48      0.60
  ====================  ========  ======  ========  ======

  The mask costs ~10% of a chunk's walk and nothing of a tick's; the gather
  (2 x 1 KB rows a pick: 4 GB a 1024-query unit) loses by 90 x at 8k keys,
  and by the walk's slope (0.13 ms a 1024 keys walked a unit, 0.055 a tick)
  would cross it only near 1M keys of reach (a tick: ~90k): for this shape
  ``MASK_WALK_KEYS`` is far on the safe side, and stays one number for both.

The jnp gather + ``reference_attention`` stays the semantic ground truth
and the path of every other shape (the CPU, unaligned widths, a head
narrower than the lanes under a chunk): the two
positions of a verify tick stay on the page walk
(``paged_attention_verify``). ``supported`` (a tick) and
``chunk_supported`` (a prefill chunk) are the whole dispatch rule, read
off the operands (a sparse layer's pick besides: ``mask_supported``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: the call names (``pallas_call(name=...)``): what the benchmark's
#: roofline readers tell a call by
KERNEL = "paged_attention_decode"
MLA_KERNEL = "paged_mla_decode"
#: a prefill chunk's call: a name of its own, so that no reader of the
#: decode calls (a TICK's page read) counts it
PREFILL_KERNEL = "paged_attention_prefill"
#: a latent block's prefill chunk (one pool as key and value): its own name,
#: so that no reader of the K/V chunk walk or of the latent TICK counts it
MLA_PREFILL_KERNEL = "paged_mla_prefill"

#: query positions a row of a verify tick may bring (``supported``)
VERIFY_POSITIONS = 2

#: Q_bd's rows are padded to this many (zero rows score 0 against every
#: key and are masked out of the result): one sublane tile of bf16, two of
#: float32, so the two matmuls never see a ragged M
_ROW_TILE = 16


def _sublane_tile(dtype) -> int:
    """Rows of one (sublane x 128-lane) tile of ``dtype`` on the TPU."""
    return 8 * max(4 // jnp.dtype(dtype).itemsize, 1)


def _mask_lanes(groups: int):
    """The lanes of a group mask one step of a walk loads for the ``groups``
    groups of its keys: the groups themselves where they are whole lane
    rows, else the one lane row they lie in (``groups`` a divisor of 128: a
    step's groups never straddle two); None where neither holds."""
    if groups % 128 == 0:
        return groups
    return 128 if groups and 128 % groups == 0 else None


def mask_supported(pool, table_width: int, group_rows: int,
                   chunk: bool) -> bool:
    """Whether a walk over ``pool`` [L, N, ps, W] can take a GROUP mask
    (``group_mask``: groups of ``group_rows`` consecutive keys, never across
    a page): whole groups a page, a step's groups loadable as lane rows
    (``_mask_lanes``; a tick's step is a page, a chunk's a block of pages),
    and a table of at most ``MASK_WALK_KEYS`` keys: the walk reads EVERY
    row the table's row holds, so past that width reading the picked rows
    alone is the cheaper form."""
    ps = pool.shape[2]
    if group_rows < 1 or ps % group_rows \
            or table_width * ps > MASK_WALK_KEYS:
        return False
    per = _block_pages(pool) if chunk else 1
    return _mask_lanes(per * ps // group_rows) is not None


def supported(q_width: int, pool, t: int) -> bool:
    """Whether the decode kernel can take this call: one query token a
    row (or the ``VERIFY_POSITIONS`` of a verify tick:
    ``paged_attention_verify``), a TPU backend, whole groups of query
    heads over the cached heads
    (a query row of ``q_width`` = H*dh floats against a pool [L, N, ps,
    Hkv*dh]: H a multiple of Hkv), a lane-aligned row and a page of whole
    sublane tiles. Everything here is a shape, a dtype or the backend:
    nothing names a model."""
    ps, width = pool.shape[2:]
    return (1 <= t <= VERIFY_POSITIONS and jax.default_backend() == "tpu"
            and q_width % width == 0 and width % 128 == 0
            and ps % _sublane_tile(pool.dtype) == 0)


#: the keys of a prefill chunk's mask (``ops/pipeline_ops``): block-causal
#: from ``q_pos0`` [b], the first ``q_len`` [b] queries of a row real
CHUNK_MASK = frozenset({"causal", "q_pos0", "q_len"})
#: query rows (heads x queries) one grid step of the chunk walk holds: its
#: running max / denominator / accumulator are ~1.5 KB of VMEM a row
_CHUNK_ROWS = 4096
#: keys one step of the chunk walk meets, at most (whole pages; at least
#: one), and the bytes of its K block (as many again of V, both twice: two
#: in flight). By my chip runs (PR 50; 64 / 8 heads of 128, bf16, 12k keys):
#: 2.66 ms a call at 256 keys, 1.39 at 512, 0.90 at 1024, and no loss at 1k
#: keys: a step's fixed part (running max, denominator, rescale of the
#: accumulator) is as many vector ops as its scores at 128 keys
_CHUNK_KEYS = 1024
_CHUNK_BLOCK_BYTES = 2 * 2 ** 20
#: the latent chunk walk (one pool, every query head over the SAME tile):
#: query rows (heads x queries) of one score tile, the K/V form's order (its
#: G * tq: 512 at 64 / 8 heads; the module's table: my chip runs, PR 55)
_LATENT_SCORE_ROWS = 512
#: the widest table (in keys) whose rows a walk under a GROUP mask reads
#: whole (``mask_supported``): the walk's time follows the keys the row
#: holds, the gathered form's the picks alone; they cross near 46k keys of
#: reach at 2048 picked keys a query (the module's table: my chip runs,
#: PR 59), and a table's rows may all be full
MASK_WALK_KEYS = 40960
#: the chunk walk's VMEM: the tile's queries and context (double buffered by
#: the pipeline), two K and two V blocks, its float32 state and a few
#: [rows of a head, keys] score temporaries come to 20-30 MB at float32
#: pages, over the compiler's default
_CHUNK_VMEM = 64 * 2 ** 20


def _block_pages(pool) -> int:
    """Pages of ``pool`` [L, N, ps, W] one step of the chunk walk meets: up
    to ``_CHUNK_KEYS`` keys and ``_CHUNK_BLOCK_BYTES`` of K, at least one."""
    ps, width = pool.shape[2:]
    keys = min(_CHUNK_KEYS, _CHUNK_BLOCK_BYTES
               // (width * jnp.dtype(pool.dtype).itemsize))
    return max(keys // ps, 1)


def _query_tile(t: int, heads: int, dtype):
    """The queries one grid step of the chunk walk takes: the largest
    divisor of ``t`` in whole sublane tiles with heads * tq <=
    ``_CHUNK_ROWS`` (the smallest such divisor where none fits); None
    where ``t`` is not whole tiles."""
    tile = _sublane_tile(dtype)
    fits = [d for d in range(tile, t + 1, tile) if t % d == 0]
    if not fits:
        return None
    return max([d for d in fits if heads * d <= _CHUNK_ROWS] or fits[:1])


def chunk_supported(q_shape, pool, mask, value_width=None) -> bool:
    """Whether the chunk walk (``paged_attention_prefill``) can take this
    call: queries ``q_shape`` = [b, H, t, dh] of a prefill chunk (more
    positions than a verify tick's, a mask of the ``CHUNK_MASK`` kind), a
    TPU backend, whole groups of query heads over the cached heads, a head
    of whole lane rows (the body slices the page tile by head), a page and
    a chunk of whole sublane tiles. ``value_width`` (a latent pool: no V
    pool, the row's first ``value_width`` columns the value): queries as
    wide as the pool's row, and row and value of whole lane rows. Shapes,
    a dtype, the mask's keys and the backend: nothing names a model."""
    _, heads, t, d_head = q_shape
    ps, width = pool.shape[2:]
    if value_width is None:
        rows_fit = width % d_head == 0 and (heads * d_head) % width == 0
    else:
        rows_fit = d_head == width and 0 < value_width <= width \
            and value_width % 128 == 0
    return (t > VERIFY_POSITIONS and set(mask) == CHUNK_MASK
            and jax.default_backend() == "tpu"
            and d_head % 128 == 0 and rows_fit
            and ps % _sublane_tile(pool.dtype) == 0
            and _query_tile(t, heads, pool.dtype) is not None)


def _group_spread(lanes, keys, group_rows, off):
    """The 0/1 matrix [lanes, keys] that widens a step's slice of a group
    mask to its keys: key k of the step belongs to lane ``off + k //
    group_rows`` of the slice (by comparisons: no vector division)."""
    g = jax.lax.broadcasted_iota(jnp.int32, (lanes, keys), 0) - off
    k = jax.lax.broadcasted_iota(jnp.int32, (lanes, keys), 1)
    return jnp.where((k >= g * group_rows) & (k < (g + 1) * group_rows),
                     1.0, 0.0).astype(jnp.bfloat16)


def _mask_slice(g0, groups, lanes):
    """(at, off): a step whose ``groups`` groups start at group ``g0`` of
    its row's mask loads lanes ``at .. at + lanes - 1`` of it (``lanes`` =
    ``_mask_lanes(groups)``) and finds its first group at lane ``off`` of
    them."""
    from jax.experimental import pallas as pl

    if lanes == groups:
        return pl.multiple_of(g0, lanes), 0
    at = pl.multiple_of((g0 // lanes) * lanes, lanes)
    return at, g0 - at


def _picked_keys(picks, spread, tiles=1):
    """picks [n, lanes] (a step's slice of the group mask, 0 / 1) -> [tiles *
    n, keys] bool, which keys of the step lie in a picked group (the n rows
    ``tiles`` times over): one small 0/1 product a step, exact in any
    precision."""
    wide = jax.lax.dot_general(
        picks.astype(jnp.float32).astype(jnp.bfloat16), spread,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if tiles > 1:
        wide = jnp.concatenate([wide] * tiles, axis=0)
    return wide > 0.5


def _decode_kernel(layer_ref, table_ref, len_ref, q_ref, k_hbm, *rest,
                   d_head, pmax, group=1, window=None, sm_scale=None,
                   shared_kv=False, positions=1, group_rows=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rest = list(rest)
    # one pool: the key tile is the value tile
    v_hbm = None if shared_kv else rest.pop(0)
    # a group mask [1, 1, NG]: which groups of ``group_rows`` keys the row
    # may see, beside the length's rule
    mask_ref = rest.pop(0) if group_rows else None
    if shared_kv:
        o_ref, kbuf, sems, cur_ref, m_ref, l_ref, acc_ref = rest
        vbuf = None
    else:
        o_ref, kbuf, vbuf, sems, cur_ref, m_ref, l_ref, acc_ref = rest
    s, rows = pl.program_id(0), pl.num_programs(0)
    ps, width = kbuf.shape[1:]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d_head)
    # indices clamped as the gather clamps them: a DMA outside the pool
    # would fault the chip where XLA reads the nearest page
    layer = jnp.clip(layer_ref[0], 0, k_hbm.shape[0] - 1)
    length = len_ref[s]
    # a verify tick (``positions`` > 1): query position j of the row holds
    # length + j keys; the walk covers the LAST position's pages and starts
    # at the first position's window
    last = length if positions == 1 else length + (positions - 1)
    n_pages = jnp.clip((last + ps - 1) // ps, 1, pmax)

    def first_page(row):
        """Where row ``row``'s walk starts: 0, or the window's page."""
        if window is None:
            return 0
        return jnp.maximum(len_ref[row] - window, 0) // ps

    page0 = first_page(s)

    def page_copies(buf, row, i):
        page = jnp.clip(table_ref[row * pmax + i], 0, k_hbm.shape[1] - 1)
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, page], kbuf.at[buf],
                                       sems.at[0, buf])
        if shared_kv:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[layer, page], vbuf.at[buf],
                                      sems.at[1, buf]))

    def start(buf, row, i):
        for c in page_copies(buf, row, i):
            c.start()

    @pl.when(s == 0)
    def _():
        cur_ref[0] = 0
        start(0, 0, page0)

    buf0 = cur_ref[0]
    # float32 pages are multiplied as float32 (the MXU's default is one
    # bf16 pass, ~1e-2 off on the chip where the gathered reference is
    # exact to 1e-6); bf16 products are exact in the float32 accumulator
    precision = (jax.lax.Precision.HIGHEST if kbuf.dtype == jnp.float32
                 else None)
    # the block-diagonal query: row h keeps columns h*dh..h*dh+dh-1 (the
    # padding rows past H own no column). Selected in float32: Mosaic does
    # not re-tile the mask for bf16
    shape = acc_ref.shape                           # [positions * hp, width]
    row_id = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col_id = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    hp_one = shape[0] // positions      # rows of ONE position (heads, padded)
    if positions > 1:
        # rows j * hp_one .. hold position j's heads: the position a row
        # belongs to, and its head, by comparisons
        row_pos = sum((row_id >= j * hp_one).astype(jnp.int32)
                      for j in range(1, positions))
        row_id = row_id - row_pos * hp_one
    # the cached head a query row reads: n // group, by comparisons (a
    # padding row past H lands past the last head or on zero queries)
    kv_id = row_id if group == 1 else sum(
        (row_id >= j * group).astype(jnp.int32)
        for j in range(1, width // d_head))
    own = (col_id >= kv_id * d_head) & (col_id < (kv_id + 1) * d_head)
    if group == 1 and positions == 1:
        q_bd = jnp.where(own, q_ref[0].astype(jnp.float32), 0.0).astype(
            q_ref.dtype)
    else:
        q_bd = q_ref[0]                     # expanded by the wrapper
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def page(i, _):
        buf = (buf0 + i - page0) % 2

        @pl.when(i + 1 < n_pages)
        def _():
            start(1 - buf, s, i + 1)

        @pl.when((i + 1 == n_pages) & (s + 1 < rows))
        def _():
            start(1 - buf, s + 1, first_page(jnp.minimum(s + 1, rows - 1)))

        for c in page_copies(buf, s, i):
            c.wait()
        k = kbuf[buf]
        v = k if shared_kv else vbuf[buf]
        sc = jax.lax.dot_general(
            q_bd, k, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32) * sm_scale      # [hp, ps]
        key = i * ps + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        held = length
        if positions > 1:       # a later position holds as many more keys
            held = length + sum(
                (jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
                 >= j * hp_one).astype(jnp.int32)
                for j in range(1, positions))
        seen = key < held
        if window is not None:
            seen = seen & (key >= held - window)
        if mask_ref is not None:
            gp = ps // group_rows                   # groups a page
            lanes = _mask_lanes(gp)
            at, off = _mask_slice(i * gp, gp, lanes)
            picks = jnp.broadcast_to(
                mask_ref[0, :, pl.ds(at, lanes)].astype(jnp.float32),
                (sc.shape[0], lanes))
            seen = seen & _picked_keys(
                picks, _group_spread(lanes, ps, group_rows, off))
        sc = jnp.where(seen, sc, -jnp.inf)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        # a row with no key yet (length 0) keeps exp() off inf - inf
        m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        p = jnp.exp(sc - m_safe)
        alpha = jnp.exp(m - m_safe)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)                 # [hp, width]
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(page0, n_pages, page, 0)
    cur_ref[0] = (buf0 + n_pages - page0) % 2
    # each row's own dh columns, normalised; a row without a key has
    # accumulated nothing and stays zero
    l = l_ref[...]
    ctx = jnp.where(own, acc_ref[...] / jnp.where(l > 0, l, 1.0), 0.0)
    if group == 1 and positions == 1:
        o_ref[0] = jnp.sum(ctx, axis=0, keepdims=True).astype(o_ref.dtype)
    else:
        # G rows share a column block: out[g] = the sum of rows g, G+g,
        # 2G+g, .. (one per cached head), a 0/1 selection done as an exact
        # float32 matmul [G_pad, hp] x [hp, width]; a verify tick's
        # positions stack their G_pad rows (out row p * G_pad + g from the
        # rows p * hp_one + ..)
        gp, hp = o_ref.shape[1], shape[0]
        g_id = jax.lax.broadcasted_iota(jnp.int32, (gp, hp), 0)
        r_id = jax.lax.broadcasted_iota(jnp.int32, (gp, hp), 1)
        at = 0      # where the out row's position starts among the rows
        if positions > 1:
            gp_one = gp // positions
            g_pos = sum((g_id >= j * gp_one).astype(jnp.int32)
                        for j in range(1, positions))
            g_id, at = g_id - g_pos * gp_one, g_pos * hp_one
        sel = (g_id < group) & functools.reduce(
            jnp.logical_or, [r_id == at + j * group + g_id
                             for j in range(width // d_head)])
        o_ref[0] = jax.lax.dot_general(
            sel.astype(jnp.float32), ctx,
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _mask_operand(group_mask, group_rows, pool, table_width, per):
    """``group_mask`` [.., NG] (bool or int8; NG = the table's groups) as the
    kernels load it: int8, its groups padded (never at lane-aligned widths)
    to whole steps of ``per`` pages and whole loads of ``_mask_lanes``."""
    ps = pool.shape[2]
    if not group_rows or ps % group_rows:
        raise ValueError(f"group_rows {group_rows} does not divide the "
                         f"page's {ps} rows")
    groups = per * ps // group_rows
    lanes = _mask_lanes(groups)
    n_groups = table_width * ps // group_rows
    if lanes is None or group_mask.shape[-1] != n_groups:
        raise ValueError(
            f"a group mask {group_mask.shape} does not fit a table of "
            f"{table_width} pages of {ps // group_rows} groups walked "
            f"{groups} groups a step")
    steps = -(-table_width // per)
    padded = -(-steps * groups // lanes) * lanes
    mask = group_mask.astype(jnp.int8)
    if padded != n_groups:
        mask = jnp.pad(mask, ((0, 0),) * (mask.ndim - 1)
                       + ((0, padded - n_groups),))
    return mask


def paged_attention_decode(q, cache_k, cache_v, layer, table, lengths,
                           interpret=False, window=None, sm_scale=None,
                           name=KERNEL, group_mask=None, group_rows=None):
    """Attention of one query token a row over the pages the row holds.

    q [b, H, dh] (cast to the pools' dtype), cache_k / cache_v the WHOLE
    pools [L, N, ps, Hkv*dh] (H a multiple of Hkv: query head n reads
    cached head n // (H/Hkv)), layer a scalar int32, table [b, P] int32,
    lengths [b] int32 (keys j < length attend; 0 gives a zero row),
    ``window`` (static) keeps keys ``j >= length - window`` only -> the
    context [b, H*dh] in the pools' dtype, a token's heads side by side
    as the out-projection reads them.

    ``cache_v=None`` (latent attention): q [b, H, W] against the ONE pool
    [L, N, ps, W], every head reading the row's whole width as key AND
    value -> [b, H*W] (the caller keeps the latent's columns of each
    head); ``sm_scale`` replaces 1/sqrt(dh); ``name`` is the call's.

    ``group_mask`` [b, NG] (bool or int8) with ``group_rows``: the row sees
    key j iff ``j < length`` AND ``group_mask[s, j // group_rows]``, NG =
    the table's width x the page's rows / ``group_rows`` groups in LOGICAL
    order (a group never crosses a page). The walk is the same (every page
    the row holds); the mask rides into VMEM a row and a page's slice of it
    is widened to the page's keys inside the kernel."""
    if q.ndim != 3:
        raise ValueError(f"q must be [b, H, dh], got {q.shape}")
    return _walk(q[:, None], cache_k, cache_v, layer, table, lengths,
                 interpret, window, sm_scale, name, group_mask,
                 group_rows)[:, 0]


def _walk(q, cache_k, cache_v, layer, table, lengths, interpret, window,
          sm_scale, name, group_mask=None, group_rows=None):
    """The kernel's call for q [b, t, H, dh]: t query positions a row (one:
    a decode tick; more: a verify tick, position j holding ``lengths + j``
    keys) -> [b, t, H*dh]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, heads, d_head = q.shape
    ps, width = cache_k.shape[2:]
    shared_kv = cache_v is None
    if (heads * d_head) % width or width % d_head \
            or (not shared_kv and cache_v.shape != cache_k.shape):
        raise ValueError(f"q {q.shape} does not match the pools "
                         f"{cache_k.shape} / "
                         f"{None if shared_kv else cache_v.shape}")
    kv_heads = width // d_head
    group = heads // kv_heads
    pmax = table.shape[1]
    hp = -(-heads // _ROW_TILE) * _ROW_TILE
    dtype = cache_k.dtype
    if group == 1 and t == 1:
        q_in, q_rows, out_rows = q.reshape(b, 1, width).astype(dtype), 1, 1
    else:
        # the block-diagonal query [b, t * H_pad, Hkv*dh]: row n of a
        # position = q_n in the columns of cached head n // group, zeros
        # elsewhere; a verify tick's positions stack their H_pad rows
        own = (jnp.arange(heads)[:, None] // group
               == jnp.arange(kv_heads)[None, :])
        q_in = jnp.where(own[None, None, :, :, None],
                         q.astype(dtype)[:, :, :, None],
                         jnp.zeros((), dtype)).reshape(b, t, heads, width)
        q_in = jnp.pad(q_in, ((0, 0), (0, 0), (0, hp - heads), (0, 0)))
        q_in = q_in.reshape(b, t * hp, width)
        q_rows, out_rows = t * hp, t * (-(-group // 8) * 8)
    pools = (cache_k,) if shared_kv else (cache_k, cache_v)
    masks, mask_specs = (), []
    if group_mask is not None:
        if t != 1 or group_mask.shape[:-1] != (b,):
            raise ValueError(f"a group mask {group_mask.shape} needs one "
                             f"query position a row of {b}, got {t}")
        mask = _mask_operand(group_mask, group_rows, cache_k, pmax, 1)
        masks = (mask[:, None],)
        mask_specs = [pl.BlockSpec((1, 1, mask.shape[-1]),
                                   lambda s, *_: (s, 0, 0))]
    kernel = functools.partial(_decode_kernel, d_head=d_head, pmax=pmax,
                               group=group, window=window, sm_scale=sm_scale,
                               shared_kv=shared_kv, positions=t,
                               group_rows=group_rows if masks else None)
    rows = t * hp
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, the flattened table, lengths
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, q_rows, width), lambda s, *_: (s, 0, 0)),
        ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools] + mask_specs,
        out_specs=pl.BlockSpec((1, out_rows, width),
                               lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            # K pages, two in flight (and V pages: not under one pool)
            pltpu.VMEM((2, ps, width), dtype) for _ in pools] + [
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),            # buffer of the next page
            pltpu.VMEM((rows, 1), jnp.float32),     # running max
            pltpu.VMEM((rows, 1), jnp.float32),     # running denominator
            pltpu.VMEM((rows, width), jnp.float32),  # un-normalised P @ V
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, out_rows, width), dtype),
        # the (row, page) chain carries its DMA from one grid step to the
        # next: the steps run in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), lengths.astype(jnp.int32),
      q_in, *pools, *masks)
    if group == 1 and t == 1:
        return out.reshape(b, 1, width)
    # out[b, p * G_pad + g, j*dh..] is head j*group + g of position p: back
    # to head order
    return out.reshape(b, t, out_rows // t, kv_heads, d_head)[
        :, :, :group].transpose(0, 1, 3, 2, 4).reshape(b, t, heads * d_head)


def paged_attention_verify(q, cache_k, cache_v, layer, table, lengths,
                           interpret=False, window=None):
    """Attention of the t consecutive query positions of a VERIFY tick over
    the pages a row holds: q [b, H, t, dh], query j of row s sitting at
    position ``lengths[s] - 1 + j`` and seeing keys ``< lengths[s] + j``
    (its own K/V row and the earlier queries' were written before the
    call), inside ``window`` on a window layer -> [b, t, H*dh].

    ONE walk a row: the t positions fold into the query-head group of each
    cached head (t * G rows a KV head, t * H_pad rows of scores a page, a
    later position's rows masked past ITS length), so every page the row
    holds is read once for all of them. The walk starts at the FIRST
    position's window and ends at the last position's page."""
    return _walk(q.transpose(0, 2, 1, 3), cache_k, cache_v, layer, table,
                 lengths, interpret, window, None, KERNEL)


def chunk_pages_in_reach(start, length, ps, window=None, xp=jnp):
    """(first, end): the pages ``first <= i < end`` of its table a chunk
    row's walk reads: queries at positions ``start .. start + length - 1``
    reach back to position 0 (a full layer) or to ``start - window + 1``,
    and forward to the chunk's last real key. A padding row (``length`` 0)
    reads none. ``xp``: ``numpy`` for host arrays (the engine counts with
    this rule what the kernel walks)."""
    first = (xp.zeros_like(start) if window is None
             else xp.maximum(start - window + 1, 0) // ps)
    return first, xp.where(length > 0, (start + length + ps - 1) // ps, first)


def _prefill_kernel(layer_ref, table_ref, start_ref, len_ref, q_ref, k_hbm,
                    *rest, d_head, pmax, group, window, sm_scale=None,
                    shared_kv=False, group_rows=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rest = list(rest)
    # one pool: the key tile's first columns are the value
    v_hbm = None if shared_kv else rest.pop(0)
    # a group mask [1, tq, NG]: which groups of ``group_rows`` keys each of
    # the tile's queries may see, beside the causal rule
    mask_ref = rest.pop(0) if group_rows else None
    if shared_kv:
        o_ref, kbuf, sems, m_ref, l_ref, acc_ref = rest
        vbuf = kbuf
    else:
        o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref = rest
    s, qt = pl.program_id(0), pl.program_id(1)
    ps = k_hbm.shape[2]
    # the head loop's steps: the cached heads, ``group`` query heads over
    # each; under one pool ``group`` query heads a step over the SAME tile
    steps, rows, d_value = acc_ref.shape        # rows = group * tq
    tq = rows // group
    keys = kbuf.shape[1]                        # a block: whole pages
    per = keys // ps
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d_head)
    layer = jnp.clip(layer_ref[0], 0, k_hbm.shape[0] - 1)
    start, length = start_ref[s], len_ref[s]
    # this tile's queries sit at chunk offsets q0 .. q0 + tq - 1, of which
    # the first ``real`` are real; the pages they reach: [first, end)
    q0 = qt * tq
    real = jnp.clip(length - q0, 0, tq)
    first, end = chunk_pages_in_reach(start + q0, real, ps, window)
    end = jnp.minimum(end, pmax)
    n_blocks = (jnp.maximum(end - first, 0) + per - 1) // per

    def page_copies(buf, j, c):
        """Page ``c`` of block ``j`` into its rows of buffer ``buf``."""
        # a block's tail past the walk's end reads the last page in reach
        # again (its keys sit past every query: masked)
        i = jnp.minimum(first + j * per + c, end - 1)
        page = jnp.clip(table_ref[s * pmax + i], 0, k_hbm.shape[1] - 1)
        at = pl.ds(pl.multiple_of(c * ps, ps), ps)
        k_copy = pltpu.make_async_copy(k_hbm.at[layer, page],
                                       kbuf.at[buf, at], sems.at[0, buf, c])
        if shared_kv:
            return (k_copy,)
        return (k_copy,
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      vbuf.at[buf, at], sems.at[1, buf, c]))

    def each_page(buf, j, do):
        def one(c, _):
            for copy in page_copies(buf, j, c):
                do(copy)
            return 0
        jax.lax.fori_loop(0, per, one, 0)

    @pl.when(n_blocks > 0)
    def _():
        each_page(0, 0, lambda copy: copy.start())

    precision = (jax.lax.Precision.HIGHEST if kbuf.dtype == jnp.float32
                 else None)
    # row r of a cached head's [group * tq] query rows is query r % tq of
    # head r // tq of its group: the query's position, by comparisons; a
    # padding query sits at -1, before every key
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    i = r - tq * sum((r >= j * tq).astype(jnp.int32)
                     for j in range(1, group))
    q_pos = jnp.where(q0 + i < length, start + q0 + i, -1)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    if mask_ref is not None:
        groups = keys // group_rows                 # groups a block
        lanes = _mask_lanes(groups)
        # (whole lane rows a block: one spread for every block)
        spread0 = (_group_spread(lanes, keys, group_rows, 0)
                   if lanes == groups else None)

    def block(j, _):
        buf = j % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            each_page(1 - buf, j + 1, lambda copy: copy.start())

        each_page(buf, j, lambda copy: copy.wait())
        key = (first + j * per) * ps + jax.lax.broadcasted_iota(
            jnp.int32, (rows, keys), 1)
        seen = key <= q_pos
        if window is not None:
            seen = seen & (q_pos - key < window)
        if mask_ref is not None:
            # the block's slice of the tile's mask, widened to its keys
            # once a block and tiled over the head group's query rows
            at, off = _mask_slice((first + j * per) * (ps // group_rows),
                                  groups, lanes)
            seen = seen & _picked_keys(
                mask_ref[0, :, pl.ds(at, lanes)],
                spread0 if spread0 is not None
                else _group_spread(lanes, keys, group_rows, off), group)

        def head(h, _):
            if shared_kv:   # every head: the whole tile, its first columns
                cols, v_cols = slice(None), slice(0, d_value)
            else:
                cols = v_cols = pl.ds(pl.multiple_of(h * d_head, d_head),
                                      d_head)
            q = q_ref[0, pl.ds(h * group, group)].reshape(rows, d_head)
            sc = jax.lax.dot_general(
                q, kbuf[buf, :, cols],
                dimension_numbers=(((1,), (1,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32)             # [rows, keys]
            if sm_scale != 1.0:
                sc = sc * sm_scale
            sc = jnp.where(seen, sc, -jnp.inf)
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
            # a query with no key yet keeps exp() off inf - inf
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(sc - m_safe)
            alpha = jnp.exp(m - m_safe)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p.astype(vbuf.dtype), vbuf[buf, :, v_cols],
                dimension_numbers=(((1,), (0,)), ((), ())),
                precision=precision,
                preferred_element_type=jnp.float32)             # [rows, dv]
            m_ref[h] = m_new
            return 0

        # a LOOP over the cached heads, not an unrolled body: unrolled it is
        # a third faster at 12k keys (my chip run, PR 50) and costs every
        # start-up ~0.3 s a call site in tracing and lowering
        jax.lax.fori_loop(0, steps, head, 0)
        return 0

    jax.lax.fori_loop(0, n_blocks, block, 0)
    # normalised, a token's heads side by side; a query without a key has
    # accumulated nothing and stays zero
    for h in range(steps):
        l = l_ref[h]
        ctx = acc_ref[h] / jnp.where(l > 0, l, 1.0)
        for g in range(group):
            n = h * group + g
            o_ref[0, :, n * d_value:(n + 1) * d_value] = ctx[
                g * tq:(g + 1) * tq].astype(o_ref.dtype)


def paged_attention_prefill(q, cache_k, cache_v, layer, table, start,
                            lengths, interpret=False, window=None,
                            sm_scale=None, value_width=None, group_mask=None,
                            group_rows=None):
    """Attention of a prefill CHUNK over the pages each row holds.

    q [b, H, Tc, dh] (cast to the pools' dtype): query i of row s sits at
    position ``start[s] + i`` and is real while ``i < lengths[s]``; it sees
    keys ``j <= start[s] + i`` (its own K/V row and the chunk's earlier
    ones were written before the call), inside ``window`` (static) on a
    window layer. cache_k / cache_v the WHOLE pools [L, N, ps, Hkv*dh],
    layer a scalar int32, table [b, P] int32 -> the context [b, Tc, H*dh]
    in the pools' dtype, a token's heads side by side; zeros for a padding
    query. The walk reads pages ``chunk_pages_in_reach`` of each row (by
    query tile: a tile stops at ITS last real key), never a page past the
    chunk's last real key.

    ``cache_v=None`` (latent attention, the call ``MLA_PREFILL_KERNEL``):
    q [b, H, Tc, W] against the ONE pool [L, N, ps, W], every head reading
    the row's whole width as key and its first ``value_width`` columns as
    value -> [b, Tc, H*value_width]; ``sm_scale`` replaces 1/sqrt(dh).

    ``group_mask`` [b, Tc, NG] (bool or int8) with ``group_rows`` (no
    window): query i of row s sees key j iff ``j <= start[s] + i`` AND
    ``group_mask[s, i, j // group_rows]``, NG = the table's width x the
    page's rows / ``group_rows`` groups in LOGICAL order. The walk is the
    same (the pages the tile's queries reach); the tile's [tq, NG] mask
    rides into VMEM and a block's slice of it is widened to the block's
    keys once a block."""
    if q.ndim != 4:
        raise ValueError(f"q must be [b, H, Tc, dh], got {q.shape}")
    heads, t, d_head = q.shape[1:]
    width = cache_k.shape[3]
    if cache_v is None:
        bad = d_head != width or not 0 < (value_width or 0) <= width
    else:
        bad = ((heads * d_head) % width or width % d_head
               or cache_v.shape != cache_k.shape or value_width is not None)
    if bad:
        raise ValueError(f"q {q.shape} does not match the pools "
                         f"{cache_k.shape} / "
                         f"{None if cache_v is None else cache_v.shape}"
                         f" (value_width {value_width})")
    if _query_tile(t, heads, cache_k.dtype) is None:
        raise ValueError(f"a chunk of {t} queries is not whole sublane "
                         f"tiles of {jnp.dtype(cache_k.dtype).name}")
    # operands of ONE type whatever the caller holds them as (a layer index
    # is a Python int here, a scan's counter there): the K/V layers of one
    # prefill program then share a trace and a lowering a kind of pool,
    # where each call site of the bare ``pallas_call`` cost ~0.3 s of every
    # start-up, cold or warm
    if group_mask is not None:
        if window is not None or group_mask.shape[:-1] != (q.shape[0], t):
            raise ValueError(f"a group mask {group_mask.shape} needs a full "
                             f"layer's chunk [{q.shape[0]}, {t}, NG], "
                             f"window {window}")
        group_mask = _mask_operand(group_mask, group_rows, cache_k,
                                   table.shape[1], _block_pages(cache_k))
    return _chunk_walk(
        q.astype(cache_k.dtype), cache_k, cache_v,
        jnp.reshape(layer, (1,)).astype(jnp.int32),
        table.reshape(-1).astype(jnp.int32), start.astype(jnp.int32),
        lengths.astype(jnp.int32), pmax=table.shape[1], interpret=interpret,
        window=window, sm_scale=sm_scale, value_width=value_width,
        group_mask=group_mask,
        group_rows=None if group_mask is None else group_rows)


@functools.partial(jax.jit, static_argnames=(
    "pmax", "interpret", "window", "sm_scale", "value_width", "group_rows"))
def _chunk_walk(q, cache_k, cache_v, layer, table, start, lengths, *, pmax,
                interpret, window, sm_scale=None, value_width=None,
                group_mask=None, group_rows=None):
    """``paged_attention_prefill``'s call, on checked operands: layer [1],
    table [b * pmax] flattened, start / lengths [b], all int32; a group
    mask as ``_mask_operand`` leaves it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, t, d_head = q.shape
    ps, width = cache_k.shape[2:]
    dtype = cache_k.dtype
    tq = _query_tile(t, heads, dtype)
    shared_kv = cache_v is None
    if shared_kv:
        # one cached "head" of the row's whole width under ALL the query
        # heads: the head loop steps over groups of query heads instead, as
        # many as make a score tile of ``_LATENT_SCORE_ROWS`` rows
        group = max(g for g in range(1, heads + 1) if heads % g == 0
                    and (g == 1 or g * tq <= _LATENT_SCORE_ROWS))
        steps, d_value = heads // group, value_width
    else:
        steps, d_value = width // d_head, d_head
        group = heads // steps
    per = _block_pages(cache_k)
    pools = (cache_k,) if shared_kv else (cache_k, cache_v)
    masks, mask_specs = (), []
    if group_mask is not None:
        masks = (group_mask,)
        mask_specs = [pl.BlockSpec((1, tq, group_mask.shape[-1]),
                                   lambda s, i, *_: (s, i, 0))]
    kernel = functools.partial(_prefill_kernel, d_head=d_head, pmax=pmax,
                               group=group, window=window, sm_scale=sm_scale,
                               shared_kv=shared_kv, group_rows=group_rows)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer, the flattened table, start, lengths
        grid=(b, t // tq),
        in_specs=[
            pl.BlockSpec((1, heads, tq, d_head),
                         lambda s, i, *_: (s, 0, i, 0)),
        ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools] + mask_specs,
        out_specs=pl.BlockSpec((1, tq, heads * d_value),
                               lambda s, i, *_: (s, i, 0)),
        scratch_shapes=[
            # K (and V: not under one pool) blocks of ``per`` pages, two in
            # flight
            pltpu.VMEM((2, per * ps, width), dtype) for _ in pools] + [
            pltpu.SemaphoreType.DMA((2, 2, per)),
            pltpu.VMEM((steps, group * tq, 1), jnp.float32),  # running max
            pltpu.VMEM((steps, group * tq, 1), jnp.float32),  # denominator
            pltpu.VMEM((steps, group * tq, d_value), jnp.float32),  # P @ V
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, heads * d_value), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM),
        interpret=interpret,
        name=MLA_PREFILL_KERNEL if shared_kv else PREFILL_KERNEL,
    )(layer, table, start, lengths, q, *pools, *masks)
