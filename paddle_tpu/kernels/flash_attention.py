"""Flash attention: Pallas TPU kernel with online softmax.

The long-context workhorse. The reference framework predates Transformers
(SURVEY.md §5.7) — its closest analogues are the fused CUDA cell kernels
(/root/reference/paddle/cuda/src/hl_cuda_lstm.cu) whose role (keep the hot
loop's working set on-chip instead of round-tripping HBM) this kernel plays
for attention: O(T^2) scores never materialise in HBM; each (row, lane
block, q-block) grid cell streams K/V blocks through VMEM, maintaining the
running max/denominator of the softmax (the standard online-softmax
recurrence), so HBM traffic is O(T*d) instead of O(T^2).

The kernels address heads on the LANE axis: operands are ``[rows, T, H *
d]`` and a grid cell takes ``lane_block(H, d)`` columns of a row, i.e.
whole heads filling whole 128-lane rows (two at d_head 64), walked one
after another. ``flash_attention_packed`` hands them the rows a fused qkv
projection writes, as they are; ``flash_attention`` over ``[B, H, T, D]``
is the same kernels on the ``[B * H, T, D]`` view (one head a row, the
full minor axis a block).

On non-TPU backends (the CPU test mesh) ``flash_attention`` is the
pure-jnp reference — same semantics, XLA-fused — for both passes. On TPU
the BACKWARD is also Pallas (``_flash_dq_kernel`` / ``_flash_dkv_kernel``):
p-tiles are recomputed from the forward's saved logsumexp per block, so the
backward's HBM traffic stays O(T*d) like the forward's. (The earlier
jnp-recompute backward materialised the [T, T] probabilities and made
transformer training HBM-bound — 180 GB/step at d1024/L8/T2048 — see
PERF.md.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# Blocks: 512 x 512 for the three kernels, whatever the operand type.
# `tools/flash_block_sweep.py` on a v5e under JAX 0.9.0 (PR 57), causal,
# bf16 operands PACKED [b, T, H * d_head], ms a call by block_q x block_k
# (a chain of 24 dependent calls by the host clock: 0.1-0.2 ms of every
# reading is the chain's carry, the same along a row; a traced train step
# has the kernels of the first shape at 0.73 (forward), 0.67, 0.71 ms):
#
#   [b, T, H, d_head]  kernel 256x256 128x512 256x512 512x256 512x512 512x1024
#   [8, 1024, 16, 64]  fwd     1.086   0.869   0.864   1.074   0.817   0.932
#   (the LM train      dq      0.946   0.991   0.833   0.881   0.767   0.878
#   cells)             dkv     1.049   1.180   1.037   0.818   0.814   0.953
#   [8, 2048, 16, 64]  fwd     3.151   2.498   2.139   2.914   2.100   2.311
#                      dq      2.814   2.766   2.279   2.441   2.099   2.277
#                      dkv     3.481   3.624   3.135   2.472   2.407   2.783
#   [8, 2048, 8, 128]  fwd     1.639   1.356   1.140   1.537   1.120   1.230
#                      dq      1.505   1.488   1.240   1.320   1.136   1.225
#                      dkv     1.907   1.923   1.678   1.414   1.319   1.502
#
# (128 x 256: 1.2-1.3, 4.0-4.1 and 2.1-2.2; 128 x 1024 and 256 x 1024 lose
# to 512 x 512 in every row.) float32 operands at the first shape pick the
# same pair (0.802 / 0.775 / 0.853 against 0.821 / 0.846 / 1.085 at 256 x
# 512). The same calls over the [b * H, T, d_head] view of the unpacked
# entry (`--layout rows`), 512 x 512: 0.840 / 0.743 / 0.865 at the first
# shape, 2.260 / 2.168 / 2.742 at the second; at [8, 1024, 32, 32] (four
# heads a block) 1.517 / 1.401 / 1.502 packed against 1.727 / 1.526 /
# 1.865. The kernels are bound by the float32 passes over the [block_q,
# block_k] score tile and by what a loop turn costs whatever its width
# (two lane reductions, the [block_q, 1] statistics, the accumulator's
# rescale), not by their dots: at d_head 64 a dot fills half the MXU
# whether it contracts 64 lanes or 128 with half of them zeroed, and bf16
# operands moved the kernels by under 10%. So a smaller block's larger
# skip under the causal mask (10/16 of the square at 256 x 256 and T
# 1024, against 3/4) loses to its extra turns, a block_k beyond 512 to
# the skip it gives up, and a second, mask-free loop for the blocks below
# the diagonal was slower in 106 of 108 readings, by up to 9% (PR 41:
# measured, then removed).
#
# Taking a head out of its lane block (PR 57, the first shape, 512 x 512):
# zeroing the other heads' lanes of the small operand and contracting the
# whole block (kept) read 0.817 / 0.760 / 0.818; a static lane slice of
# every operand (``q[:, h*d:(h+1)*d]``, accumulators [block, d_head],
# stores into the slice) 0.862 / 0.794 / 0.878, slower at all nine block
# pairs tried (measured, then removed).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _pick_block(t, preferred):
    b = min(preferred, t)
    while t % b:
        b //= 2
    return max(b, 1)


def _out_struct(shape, dtype, like):
    """Kernel output type varying over the same manual mesh axes as
    ``like`` — what ``shard_map``'s vma typing needs from a pallas_call
    (empty outside a shard_map)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def yarn_inv_freq(dim, base, scaling):
    """The ``dim // 2`` rotary frequencies under YaRN (``scaling``: an
    ``lm_spec.RopeScaling``). Pair i turns ``original_max * theta_i /
    2 pi`` times in the original context, ``theta_i = base^(-2i/dim)``;
    the pair index at which that is r turns is ``dim * ln(original_max /
    (2 pi r)) / (2 ln base)``. Pairs below ``floor`` of it at ``beta_fast``
    keep ``theta_i``, pairs above ``ceil`` of it at ``beta_slow`` get
    ``theta_i / factor``, and in between the two are mixed linearly in
    the pair index (the DeepSeek-V3 / HF ``yarn`` convention, whole-index
    ends)."""
    import numpy as np

    half = dim // 2
    theta = base ** (-np.arange(half, dtype=np.float64) / half)

    def index_at(turns):
        return (dim * math.log(scaling.original_max / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(index_at(scaling.beta_fast)), 0)
    high = min(math.ceil(index_at(scaling.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return jnp.asarray(theta / scaling.factor * ramp + theta * (1 - ramp),
                       jnp.float32)


def rotary(x, pos0=0, base=10000.0, pairing="interleaved", scaling=None,
           time_axis=2):
    """Rotary position embedding over [B, H, T, D] heads (``time_axis``
    1: [B, T, H, D], the view of a projection's row), positions
    pos0..pos0+T-1: pair i rotates by pos * base^(-2i/D). ``pairing``
    says which two coordinates pair i is: ``"interleaved"`` (RoFormer,
    (x[2i], x[2i+1])) or ``"half"`` (GPT-NeoX / Llama / OLMoE checkpoints,
    (x[i], x[i + D/2])). The single source of truth for RoPE math — the
    per-layer encoder op and the stacked/decode path both call it; the
    offset form serves incremental decode. ``pos0`` may be a [B] array
    of PER-ROW offsets (the slot-decode path, where every batch row sits
    at its own sequence position). ``scaling`` (an ``lm_spec.RopeScaling``):
    YaRN frequencies (``yarn_inv_freq``) and its cos / sin scale."""
    D = x.shape[-1]
    T = x.shape[time_axis]
    other = 3 - time_axis   # the heads' axis: cos / sin broadcast over it
    half = D // 2
    if scaling is None:
        inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        inv = yarn_inv_freq(D, base, scaling)
    pos0 = jnp.asarray(pos0, jnp.float32)
    if pos0.ndim:  # per-row offsets: [B] -> angles [B, T, half]
        pos = pos0[:, None] + jnp.arange(T, dtype=jnp.float32)[None, :]
        ang = pos[:, :, None] * inv[None, None, :]
        # [B, 1, T, half] ([B, T, 1, half])
        cos = jnp.expand_dims(jnp.cos(ang), other).astype(x.dtype)
        sin = jnp.expand_dims(jnp.sin(ang), other).astype(x.dtype)
    else:
        pos = pos0 + jnp.arange(T, dtype=jnp.float32)
        ang = pos[:, None] * inv[None, :]  # [T, half]
        cos = jnp.expand_dims(jnp.cos(ang), (0, other)).astype(x.dtype)
        sin = jnp.expand_dims(jnp.sin(ang), (0, other)).astype(x.dtype)
    if scaling is not None and scaling.cos_sin_scale != 1.0:
        cos, sin = cos * scaling.cos_sin_scale, sin * scaling.cos_sin_scale
    if pairing == "half":
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x1 * sin + x2 * cos
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape)


def reference_attention(q, k, v, lengths=None, causal=False, sm_scale=None,
                        q_pos0=0, k_pos0=None, window=None):
    """Pure-jnp attention over [B, H, T, D]; the semantic ground truth.

    K/V may carry Hkv < H head planes (grouped-query attention, query
    head h reading kv head h // (H//Hkv)): the group structure stays in
    the einsum — no [B, H, T, D] expansion is ever materialised, which is
    the point of the smaller cache on the decode hot path.

    ``q_pos0`` offsets the queries' GLOBAL positions for causal masking —
    a window of w queries starting at cache position p attends key j iff
    j <= p + i (the block-causal mask incremental verify needs). It may
    be a [B] array of PER-ROW offsets (the paged chunked-prefill path,
    where every batch row resumes at its own context length).

    ``k_pos0`` [B]: key j of row b sits at GLOBAL position k_pos0[b] + j
    (the keys are a slice of the context: a window layer's gathered
    pages). ``window``: a query at position i sees key position j only
    while i - j < window; under ``lengths`` the query is the row's last
    position, lengths - 1."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    H, Hkv = q.shape[1], k.shape[1]
    if H != Hkv:
        if H % Hkv:
            raise ValueError(f"query heads {H} not a multiple of kv heads "
                             f"{Hkv}")
        rep = H // Hkv
        qg = q.reshape(q.shape[0], Hkv, rep, q.shape[2], q.shape[3])
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                       preferred_element_type=jnp.float32) * sm_scale
        s = s.reshape(q.shape[0], H, q.shape[2], k.shape[2])
    else:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
    T = q.shape[2], k.shape[2]
    if k_pos0 is not None or window is not None:
        B = q.shape[0]
        kj = jnp.arange(T[1])[None, :] + (
            0 if k_pos0 is None else jnp.asarray(k_pos0).reshape(-1, 1))
        kj = jnp.broadcast_to(kj, (B, T[1]))[:, None, None, :]
        keep = jnp.ones((B, 1, T[0], T[1]), bool)
        if causal:
            qi = (jnp.asarray(q_pos0).reshape(-1, 1)
                  + jnp.arange(T[0])[None, :])[:, None, :, None]
            keep = keep & (qi >= kj)
            if window is not None:
                keep = keep & (qi - kj < window)
        if lengths is not None:
            keep = keep & (kj < lengths[:, None, None, None])
            if window is not None:
                keep = keep & (kj >= lengths[:, None, None, None] - window)
        s = jnp.where(keep, s, -jnp.inf)
        causal, lengths = False, None
    if causal:
        p0 = jnp.asarray(q_pos0)
        if p0.ndim:  # per-row offsets: [B] -> mask [B, 1, Tq, Tk]
            qi = p0[:, None] + jnp.arange(T[0])[None, :]
            kj = jnp.arange(T[1])
            s = jnp.where(qi[:, None, :, None] >= kj[None, None, None, :],
                          s, -jnp.inf)
        else:
            qi = q_pos0 + jnp.arange(T[0])[:, None]
            kj = jnp.arange(T[1])[None, :]
            s = jnp.where(qi >= kj, s, -jnp.inf)
    if lengths is not None:
        kj = jnp.arange(T[1])[None, None, None, :]
        s = jnp.where(kj < lengths[:, None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (padding queries) produce NaN-free zeros
    p = jnp.where(jnp.isnan(p), 0.0, p)
    p = p.astype(v.dtype)
    if H != Hkv:
        pg = p.reshape(p.shape[0], Hkv, rep, p.shape[2], p.shape[3])
        og = jnp.einsum("bgrqk,bgkd->bgrqd", pg, v)
        return og.reshape(q.shape[:3] + v.shape[-1:])
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _live_blocks(qb, block_q, block_k, n_blocks, causal):
    """How many k-blocks q-block ``qb`` walks: under the causal mask the
    blocks wholly above the diagonal contribute nothing and are skipped."""
    if not causal:
        return n_blocks
    last = (qb + 1) * block_q  # exclusive bound on visible columns
    return jnp.minimum(n_blocks, (last + block_k - 1) // block_k)


def _column_bound(qb, block_q, length, causal):
    """Row i of q-block ``qb`` sees the key columns below this bound:
    ``min(i + 1, length)`` under the causal mask ([bq, 1]), ``length``
    without. With it a block's whole mask is ONE compare a score against
    a column vector moved by the block's offset (the kernels are bound by
    the float32 passes over the score tile, not by their dots)."""
    if not causal:
        return length
    row = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    return jnp.minimum(row + 1, length)


def _head_lanes(width, heads, h):
    """[1, width] mask of the lanes head ``h`` of ``heads`` holds."""
    d = width // heads
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    return (lane >= h * d) & (lane < (h + 1) * d)


def _only_head(lanes, *tiles):
    """The tiles with every lane outside ``lanes`` zeroed: contracted over
    the whole lane block they give ONE head's product exactly (the other
    heads' lanes add exact zeros). ``lanes`` None (one head a block): the
    tiles as they are."""
    if lanes is None:
        return tiles
    return tuple(jnp.where(lanes, t, jnp.zeros_like(t)) for t in tiles)


def _keep_head(lanes, new, old):
    """``new``'s lanes of one head laid over ``old`` (the heads walked
    before it; None: nothing yet)."""
    if lanes is None or old is None:
        return new
    return jnp.where(lanes, new, old)


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                  causal, sm_scale, kv_len, heads, rows_per_len):
    """One q-block of the ``heads`` heads of one lane block, walked one
    after another. A head's scores contract the WHOLE lane block with the
    other heads' lanes of q zeroed; ``p @ v`` is [bq, lanes] whose lanes
    of that head are right, and one select a head keeps them."""
    from jax.experimental import pallas as pl

    qb = pl.program_id(2)
    block_q, w = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0]  # [bq, w] — native dtype (bf16 under AMP): MXU-fast dots
    # lengths arrive via scalar prefetch (rank-1 SMEM blocks of size 1 do
    # not lower on Mosaic); ``rows_per_len`` grid rows share one
    length = len_ref[pl.program_id(0) // rows_per_len]

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, w), jnp.float32)
    ub = _live_blocks(qb, block_q, block_k, kv_len // block_k, causal)
    bound = _column_bound(qb, block_q, length, causal)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    out = None
    for h in range(heads):
        lanes = _head_lanes(w, heads, h) if heads > 1 else None
        (q_h,) = _only_head(lanes, q)

        def body(j, carry):
            m, l, acc = carry
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(
                q_h, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
            s = jnp.where(col < bound - j * block_k, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # keep -inf rows stable (fully masked so far)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)  # a masked score: exp(-inf) = 0 exactly
            alpha = jnp.exp(m - m_safe)  # m = -inf (nothing seen yet): 0
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(0, ub, body, (m0, l0, acc0))
        out = _keep_head(lanes, acc / jnp.maximum(l, 1e-30), out)
        # logsumexp residual for the flash backward; fully-masked rows get
        # +inf so exp(s - lse) is exactly 0 for them in the recompute.
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
        lse_ref[h, 0] = lse[:, 0]
    o_ref[0] = out.astype(o_ref.dtype)


def lane_block(num_heads, head_dim):
    """Columns of the lane block a grid step of the kernels takes out of
    ``[b, T, num_heads * head_dim]`` rows: whole heads, a whole number of
    128-lane rows (or the full row where it is narrower), so every load
    and store is lane-dense: ``max(128, head_dim)``, i.e. 2 heads a block
    at d_head 64, 1 at 128, 4 at 32. None where no such block exists (a
    head width that neither divides nor is divided by 128, or a row that
    is not whole blocks): the ``[B, H, T, D]`` entry serves those."""
    cols = num_heads * head_dim
    if head_dim % 128 == 0:
        return head_dim
    if 128 % head_dim:
        return None
    if cols <= 128:
        return cols
    return 128 if cols % 128 == 0 else None


class _RowLayout:
    """How the grid (row, lane block, block i of T) walks ``[rows, T,
    num_heads * d]`` operands: ``lens`` the lengths operand ([n] int32, n
    dividing rows: row r reads ``lens[r // rows_per_len]``; ``lengths``
    None: every key), ``heads`` a lane block, ``lane_blocks`` a row, and
    the BlockSpecs. The [B * H, T, D] view of the unpacked entry is the
    case of one head a row whose block is the full minor axis, whatever
    D."""

    def __init__(self, q, k, lengths, num_heads):
        rows, _, cols = q.shape
        self.w = (cols if num_heads == 1
                  else lane_block(num_heads, cols // num_heads))
        self.lane_blocks = cols // self.w
        self.heads = num_heads // self.lane_blocks
        if lengths is None:
            lengths = jnp.full((rows,), k.shape[1], jnp.int32)
        self.lens = lengths.astype(jnp.int32)
        self.rows_per_len = rows // lengths.shape[0]

    def _spec(self, block, index):
        from jax.experimental import pallas as pl

        return pl.BlockSpec(block, lambda r, hb, i, lens: index(r, hb, i))

    def tile(self, t):
        """A [t, w] tile: block i of the row's lane block."""
        return self._spec((1, t, self.w), lambda r, hb, i: (r, i, hb))

    def whole(self, T):
        """The row's lane block over all of T."""
        return self._spec((1, T, self.w), lambda r, hb, i: (r, 0, hb))

    def stat(self, t, whole=False):
        """The lane block's heads' rows of a [rows * num_heads, 1, T]
        float32 statistic: block i of t columns (``whole``: all t)."""
        return self._spec(
            (self.heads, 1, t), lambda r, hb, i:
            (r * self.lane_blocks + hb, 0, 0 if whole else i))


def _flash_forward(q, k, v, lengths, causal, sm_scale, block_q, block_k,
                   interpret, num_heads=1):
    """q [rows, Tq, H * d], k / v [rows, Tk, H * d] -> (o like q,
    logsumexp [rows * H, 1, Tq] float32). ``lengths``: ``_RowLayout``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Tq, _ = q.shape
    Tk = k.shape[1]
    lay = _RowLayout(q, k, lengths, num_heads)
    block_q = _pick_block(Tq, block_q)
    block_k = _pick_block(Tk, block_k)
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                          sm_scale=sm_scale, kv_len=Tk, heads=lay.heads,
                          rows_per_len=lay.rows_per_len),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # lengths, there before the body runs
            grid=(R, lay.lane_blocks, Tq // block_q),
            in_specs=[lay.tile(block_q), lay.whole(Tk), lay.whole(Tk)],
            out_specs=[lay.tile(block_q), lay.stat(block_q)],
        ),
        out_shape=[_out_struct(q.shape, q.dtype, q),
                   _out_struct((R * num_heads, 1, Tq), jnp.float32, q)],
        interpret=interpret,
        name="flash_fwd",
    )(lay.lens, q, k, v)


def _flash_dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                     dq_ref, *, block_k, causal, sm_scale, kv_len, heads,
                     rows_per_len):
    from jax.experimental import pallas as pl

    qb = pl.program_id(2)
    block_q, w = q_ref.shape[1], q_ref.shape[2]
    length = len_ref[pl.program_id(0) // rows_per_len]

    ub = _live_blocks(qb, block_q, block_k, kv_len // block_k, causal)
    bound = _column_bound(qb, block_q, length, causal)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    dq = None
    for h in range(heads):
        lanes = _head_lanes(w, heads, h) if heads > 1 else None
        # [bq, w] native dtype, the other heads' lanes zeroed
        q, do = _only_head(lanes, q_ref[0], do_ref[0])
        lse = lse_ref[h, 0][:, None]              # [bq, 1]
        dd = dd_ref[h, 0][:, None]                # [bq, 1] rowsum(dO * O)

        def body(j, acc):
            k = k_ref[0, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, pl.ds(j * block_k, block_k), :]
            s = jax.lax.dot_general(
                q, k, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            p = jnp.where(col < bound - j * block_k,
                          jnp.exp(s - lse), 0.0)           # [bq, bk]
            dp = jax.lax.dot_general(
                do, v, dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [bq, bk]
            ds = p * (dp - dd)
            return acc + jax.lax.dot_general(
                ds.astype(k.dtype), k,
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        dq = _keep_head(lanes, jax.lax.fori_loop(
            0, ub, body, jnp.zeros((block_q, w), jnp.float32)), dq)
    dq_ref[0] = (dq * sm_scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                      dk_ref, dv_ref, *, block_q, causal, sm_scale, q_len,
                      heads, rows_per_len):
    """dk, dv of one k-block. The score tile is held TRANSPOSED, [bk, bq]:
    ``K Q^T`` and ``V dO^T`` contract the operands' last axes, ``P^T dO``
    and ``dS^T Q`` are plain products, so no [bq, bk] tile is transposed
    on its way into a dot, and logsumexp / delta are read as the [1, bq]
    rows they are stored as. Here k and v are the operands whose other
    heads' lanes are zeroed."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(2)
    block_k, w = k_ref.shape[1], k_ref.shape[2]
    length = len_ref[pl.program_id(0) // rows_per_len]

    n_blocks = q_len // block_q
    lb = (kb * block_k) // block_q if causal else 0
    # the first query that sees key row r: the key's own position under
    # the causal mask, 0 without, none (q_len) for a key beyond ``length``
    k_row = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)
    first = jnp.where(k_row < length, k_row if causal else 0, q_len)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
    a_bt = (((1,), (1,)), ((), ()))
    a_b = (((1,), (0,)), ((), ()))

    dk = dv = None
    for h in range(heads):
        lanes = _head_lanes(w, heads, h) if heads > 1 else None
        k, v = _only_head(lanes, k_ref[0], v_ref[0])   # [bk, w] native dtype

        def body(i, carry):
            dk_acc, dv_acc = carry
            q = q_ref[0, pl.ds(i * block_q, block_q), :]
            do = do_ref[0, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[h, :, pl.ds(i * block_q, block_q)]        # [1, bq]
            dd = dd_ref[h, :, pl.ds(i * block_q, block_q)]          # [1, bq]
            s = jax.lax.dot_general(
                k, q, a_bt, preferred_element_type=jnp.float32) * sm_scale
            p = jnp.where(col >= first - i * block_q,
                          jnp.exp(s - lse), 0.0)                    # [bk, bq]
            dv_acc = dv_acc + jax.lax.dot_general(
                p.astype(do.dtype), do, a_b,
                preferred_element_type=jnp.float32)                 # [bk, w]
            dp = jax.lax.dot_general(
                v, do, a_bt, preferred_element_type=jnp.float32)    # [bk, bq]
            ds = p * (dp - dd)
            dk_acc = dk_acc + jax.lax.dot_general(
                ds.astype(q.dtype), q, a_b,
                preferred_element_type=jnp.float32)                 # [bk, w]
            return dk_acc, dv_acc

        z = jnp.zeros((block_k, w), jnp.float32)
        dk_acc, dv_acc = jax.lax.fori_loop(lb, n_blocks, body, (z, z))
        dk = _keep_head(lanes, dk_acc, dk)
        dv = _keep_head(lanes, dv_acc, dv)
    dk_ref[0] = (dk * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, lengths, g, causal, sm_scale, block_q,
                    block_k, interpret, num_heads=1):
    """Blockwise flash backward over ``_flash_forward``'s operands, its
    result ``o``, its logsumexp and the cotangent ``g`` (like ``o``):
    recomputes p tiles from the saved logsumexp instead of materialising
    [T, T] — HBM stays O(T*d), matching the forward's memory story (the
    whole point of the kernel). -> dq, dk, dv like q, k, v."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, Tq, _ = q.shape
    Tk = k.shape[1]
    lay = _RowLayout(q, k, lengths, num_heads)
    # D_i = rowsum(dO * O) a head: one cheap fused elementwise+reduce in XLA
    dd = jnp.sum((g.astype(jnp.float32) * o.astype(jnp.float32))
                 .reshape(R, Tq, num_heads, -1), axis=-1)
    dd = dd.transpose(0, 2, 1).reshape(R * num_heads, 1, Tq)

    bq = _pick_block(Tq, block_q)
    bk = _pick_block(Tk, block_k)
    static = dict(causal=causal, sm_scale=sm_scale, heads=lay.heads,
                  rows_per_len=lay.rows_per_len)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_k=bk, kv_len=Tk, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, lay.lane_blocks, Tq // bq),
            in_specs=[lay.tile(bq), lay.whole(Tk), lay.whole(Tk),
                      lay.tile(bq), lay.stat(bq), lay.stat(bq)],
            out_specs=lay.tile(bq),
        ),
        out_shape=_out_struct(q.shape, q.dtype, q),
        interpret=interpret,
        name="flash_dq",
    )(lay.lens, q, k, v, g, lse, dd)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=bq, q_len=Tq, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, lay.lane_blocks, Tk // bk),
            in_specs=[lay.whole(Tq), lay.tile(bk), lay.tile(bk),
                      lay.whole(Tq), lay.stat(Tq, whole=True),
                      lay.stat(Tq, whole=True)],
            out_specs=[lay.tile(bk), lay.tile(bk)],
        ),
        out_shape=[_out_struct(k.shape, k.dtype, k),
                   _out_struct(v.shape, v.dtype, v)],
        interpret=interpret,
        name="flash_dkv",
    )(lay.lens, q, k, v, g, lse, dd)
    return dq, dk, dv


def _rows(a):
    """[B, H, T, D] as [B * H, T, D]: one head a row ([b, T, H * d] as
    it is)."""
    return a.reshape((-1,) + a.shape[-2:])


def _reference(q, k, v, lengths, causal, sm_scale, num_heads):
    """``reference_attention`` in the entry's layout (``num_heads`` None:
    [B, H, T, D]; else [b, T, num_heads * d])."""
    if num_heads is None:
        return reference_attention(q, k, v, lengths, causal, sm_scale)

    def heads(a):
        return a.reshape(a.shape[:2] + (num_heads, -1)).transpose(0, 2, 1, 3)

    out = reference_attention(heads(q), heads(k), heads(v), lengths, causal,
                              sm_scale)
    return out.transpose(0, 2, 1, 3).reshape(q.shape[:2] + (-1,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _attention(q, k, v, lengths, causal, sm_scale, num_heads=None):
    if jax.default_backend() == "tpu":
        out, _ = _flash_forward(_rows(q), _rows(k), _rows(v), lengths,
                                causal, sm_scale, DEFAULT_BLOCK_Q,
                                DEFAULT_BLOCK_K, interpret=False,
                                num_heads=num_heads or 1)
        return out.reshape(q.shape)
    return _reference(q, k, v, lengths, causal, sm_scale, num_heads)


#: ``checkpoint_name`` tags of what the backward reads: the operands as
#: the kernel was handed them, its result and its logsumexp. A
#: ``jax.checkpoint`` whose policy saves these names (the layer scan of
#: ``ops/pipeline_ops.py``) keeps the call's OWN residuals, so its backward
#: runs no second forward; a name on the caller's copy of the result
#: cannot do that, logsumexp never leaves this file. Outside such a
#: checkpoint the tags lower to nothing. From the packed entry the saved
#: planes are the lane-dense [b, T, H * d] operands themselves.
RESIDUAL_NAMES = ("flash_qkv", "flash_out", "flash_lse")


def _attention_fwd(q, k, v, lengths, causal, sm_scale, num_heads=None):
    tag_qkv, tag_out, tag_lse = RESIDUAL_NAMES
    if jax.default_backend() == "tpu":
        qkv = checkpoint_name((_rows(q), _rows(k), _rows(v)), tag_qkv)
        out, lse = _flash_forward(*qkv, lengths, causal, sm_scale,
                                  DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                                  interpret=False, num_heads=num_heads or 1)
        out = checkpoint_name(out, tag_out)
        lse = checkpoint_name(lse, tag_lse)
        return out.reshape(q.shape), qkv + (out, lse, lengths)
    # (no logsumexp here: the backward differentiates the reference)
    q, k, v = checkpoint_name((q, k, v), tag_qkv)
    out = checkpoint_name(
        _reference(q, k, v, lengths, causal, sm_scale, num_heads), tag_out)
    return out, (q, k, v, None, None, lengths)


def _attention_bwd(causal, sm_scale, num_heads, res, g):
    q, k, v, o, lse, lengths = res
    if lse is not None:
        dq, dk, dv = _flash_backward(q, k, v, o, lse, lengths, _rows(g),
                                     causal, sm_scale, DEFAULT_BLOCK_Q,
                                     DEFAULT_BLOCK_K, interpret=False,
                                     num_heads=num_heads or 1)
        kv_shape = g.shape[:-2] + k.shape[-2:]
        return (dq.reshape(g.shape), dk.reshape(kv_shape),
                dv.reshape(kv_shape), None)

    def f(q, k, v):
        return _reference(q, k, v, lengths, causal, sm_scale, num_heads)

    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def _lane_padded(q, k, v, lengths, causal, sm_scale, num_heads):
    """``_attention`` on a TPU with the T axes zero-padded up to 128-lane
    multiples, so the kernels' block slicing is Mosaic-aligned for ANY
    sequence length. K padding becomes masked columns (lengths caps at
    the true Tk); padded Q rows compute garbage that is sliced away — and
    contribute nothing to dk/dv because the slice's cotangent is
    zero-padded."""
    Tq, Tk = q.shape[-2], k.shape[-2]
    pq, pk = (-Tq) % 128, (-Tk) % 128
    if jax.default_backend() != "tpu" or not (pq or pk):
        return _attention(q, k, v, lengths, causal, sm_scale, num_heads)

    def pad(a, n):
        return jnp.pad(a, ((0, 0),) * (a.ndim - 2) + ((0, n), (0, 0)))

    if pk and lengths is None:
        lengths = jnp.full((q.shape[0],), Tk, jnp.int32)
    out = _attention(pad(q, pq), pad(k, pk), pad(v, pk), lengths, causal,
                     sm_scale, num_heads)
    return out[..., :Tq, :]


def _batch_local(mesh, q, k, v, lengths, causal, sm_scale, num_heads):
    """The kernel on each device's LOCAL batch shard. A Mosaic call is
    opaque to the GSPMD partitioner (it refuses: "Mosaic kernels cannot
    be automatically partitioned"), so inside a sharded executor block the
    kernel runs as a ``shard_map`` island: batch split over the plan's
    data axis, everything else replicated — the layout GSPMD gives the
    rest of a block whose weights are not head-sharded. A batch the axis
    does not divide runs replicated."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.context import current_data_axis

    axis = current_data_axis()
    if axis is not None and q.shape[0] % mesh.shape[axis]:
        axis = None
    spec = P(axis)
    args = (q, k, v) if lengths is None else (q, k, v, lengths)

    def local(q, k, v, lengths=None):
        return _lane_padded(q, k, v, lengths, causal, sm_scale, num_heads)

    return shard_map(local, mesh=mesh, in_specs=(spec,) * len(args),
                     out_specs=spec)(*args)


def _entry(q, k, v, lengths, causal, sm_scale, num_heads):
    """What both entries do around ``_attention``: the AMP rule, the
    ``shard_map`` island, the caller's dtype back, and the trace-time
    count of which entry a compiled program took."""
    from .. import profiler
    from ..ops.common import amp_cast
    from ..parallel.context import current_mesh

    profiler.global_stat.add_count(
        "flash/unpacked_calls" if num_heads is None else "flash/packed_calls",
        1)
    out_dtype = q.dtype
    q, k, v = amp_cast(q, k, v)
    mesh = current_mesh()
    if (jax.default_backend() == "tpu" and mesh is not None
            and mesh.size > 1
            and not jax.sharding.get_abstract_mesh().manual_axes):
        # (already-manual callers — the GPipe stages — hold local shards)
        out = _batch_local(mesh, q, k, v, lengths, causal, sm_scale,
                           num_heads)
    else:
        out = _lane_padded(q, k, v, lengths, causal, sm_scale, num_heads)
    return out.astype(out_dtype)


def flash_attention(q, k, v, lengths=None, causal=False, sm_scale=None):
    """Scaled-dot-product attention over [B, H, T, D] tensors.

    Pallas flash kernel on TPU, jnp reference elsewhere; differentiable via
    recompute. ``lengths`` [B] masks K/V padding columns.

    Under AMP float32 q / k / v are matmul operands like any other
    (``ops.common.amp_cast``): the kernels, their saved residuals and the
    cotangent they are handed are bf16 (accumulation, softmax statistics
    and logsumexp stay float32), and the result and the gradients come
    back in the caller's dtype. Without AMP, or for operands already
    bf16, nothing is cast.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    return _entry(q, k, v, lengths, causal, float(sm_scale), None)


def flash_attention_packed(q, k, v, num_heads, lengths=None, causal=False,
                           sm_scale=None):
    """``flash_attention`` over heads PACKED on the minor axis: q
    [b, Tq, num_heads * d], k / v [b, Tk, num_heads * d] (the rows a
    fused qkv projection writes and an out-projection reads) -> [b, Tq,
    num_heads * d]. The same kernels, the same AMP rule and saved
    residuals; a grid step takes ``lane_block(num_heads, d)`` columns
    (whole heads, lane-dense), so no [b, H, T, d] array is ever made.
    Callers ask ``lane_block`` first: a head width it has no block for
    (None) takes the [B, H, T, D] entry."""
    d = q.shape[-1] // num_heads
    if lane_block(num_heads, d) is None:
        raise ValueError(
            f"{num_heads} heads of {d}: no lane block of whole heads "
            "(lane_block); flash_attention over [B, H, T, D] runs them")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    return _entry(q, k, v, lengths, causal, float(sm_scale), num_heads)
