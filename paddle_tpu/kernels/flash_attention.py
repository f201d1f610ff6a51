"""Flash attention: Pallas TPU kernel with online softmax.

The long-context workhorse. The reference framework predates Transformers
(SURVEY.md §5.7) — its closest analogues are the fused CUDA cell kernels
(/root/reference/paddle/cuda/src/hl_cuda_lstm.cu) whose role (keep the hot
loop's working set on-chip instead of round-tripping HBM) this kernel plays
for attention: O(T^2) scores never materialise in HBM; each (batch*head,
q-block) grid cell streams K/V blocks through VMEM, maintaining the running
max/denominator of the softmax (the standard online-softmax recurrence), so
HBM traffic is O(T*d) instead of O(T^2).

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
# `tools/flash_block_sweep.py` on a v5e under JAX 0.9.0 (PR 41), causal,
# bf16 operands, ms a call by block_q x block_k (a chain of 24 dependent
# calls by the host clock: 0.1-0.2 ms of every reading is the chain's
# carry, the same along a row; a traced train step has the kernels of
# the first shape at 0.70 / 0.78 (two forwards), 0.68, 0.75 ms):
#
#   [rows, T, d_head] kernel 256x256 128x512 256x512 512x256 512x512 512x1024
#   [128, 1024, 64]   fwd     1.104   0.870   0.857   1.099   0.842   0.971
#   (the LM train     dq      0.921   0.988   0.805   0.864   0.751   0.856
#   cells)            dkv     1.122   1.238   1.065   0.890   0.866   1.009
#   [128, 2048, 64]   fwd     3.290   2.628   2.289   3.101   2.262   2.517
#                     dq      2.886   2.840   2.354   2.531   2.165   2.351
#                     dkv     3.894   3.935   3.423   2.909   2.734   3.094
#   [64, 2048, 128]   fwd     1.612   1.275   1.107   1.500   1.076   1.202
#                     dq      1.402   1.370   1.134   1.234   1.054   1.144
#                     dkv     1.842   1.875   1.628   1.352   1.265   1.457
#
# (128 x 128: 1.8-2.0, 6.3-7.1 and 3.1-3.5.)
# float32 operands at [128, 1024, 64] pick the same pair (0.955 / 0.857 /
# 1.323 against 0.976 / 0.979 / 1.392 at 256 x 512). The kernels are
# bound by the float32 passes over the [block_q, block_k] score tile and
# by what a loop turn costs whatever its width (two lane reductions, the
# [block_q, 1] statistics, the accumulator's rescale), not by their dots:
# at d_head 64 a dot fills half the MXU, and bf16 operands moved the
# kernels by under 10%. So a smaller block's larger skip under the causal
# mask (10/16 of the square at 256 x 256 and T 1024, against 3/4) loses
# to its extra turns, a block_k beyond 512 to the skip it gives up, and a
# second, mask-free loop for the blocks below the diagonal was slower in
# 106 of 108 readings, by up to 9% (measured, then removed).
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


def rotary(x, pos0=0, base=10000.0, pairing="interleaved", scaling=None):
    """Rotary position embedding over [B, H, T, D] heads, positions
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
    T = x.shape[2]
    half = D // 2
    if scaling is None:
        inv = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        inv = yarn_inv_freq(D, base, scaling)
    pos0 = jnp.asarray(pos0, jnp.float32)
    if pos0.ndim:  # per-row offsets: [B] -> angles [B, T, half]
        pos = pos0[:, None] + jnp.arange(T, dtype=jnp.float32)[None, :]
        ang = pos[:, :, None] * inv[None, None, :]
        cos = jnp.cos(ang)[:, None].astype(x.dtype)  # [B, 1, T, half]
        sin = jnp.sin(ang)[:, None].astype(x.dtype)
    else:
        pos = pos0 + jnp.arange(T, dtype=jnp.float32)
        ang = pos[:, None] * inv[None, :]  # [T, half]
        cos = jnp.cos(ang)[None, None].astype(x.dtype)
        sin = jnp.sin(ang)[None, None].astype(x.dtype)
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


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                  causal, sm_scale, kv_len):
    from jax.experimental import pallas as pl

    qb = pl.program_id(1)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0]  # [bq, d] — native dtype (bf16 under AMP): MXU-fast dots
    # lengths arrive via scalar prefetch (rank-1 SMEM blocks of size 1 do
    # not lower on Mosaic); index by the batch*head grid position
    length = len_ref[pl.program_id(0)]

    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    ub = _live_blocks(qb, block_q, block_k, kv_len // block_k, causal)
    bound = _column_bound(qb, block_q, length, causal)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
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
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # logsumexp residual for the flash backward; fully-masked rows get +inf
    # so exp(s - lse) is exactly 0 for them in the backward recompute.
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
    lse_ref[0, 0] = lse[:, 0]


def _flash_forward(q, k, v, lengths, causal, sm_scale, block_q, block_k,
                   interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    BH = B * H
    q3 = q.reshape(BH, Tq, D)
    k3 = k.reshape(BH, Tk, D)
    v3 = v.reshape(BH, Tk, D)
    if lengths is None:
        lens = jnp.full((B,), Tk, jnp.int32)
    else:
        lens = lengths.astype(jnp.int32)
    lens_bh = jnp.repeat(lens, H)  # [BH]

    block_q = _pick_block(Tq, block_q)
    block_k = _pick_block(Tk, block_k)
    grid = (BH, Tq // block_q)

    kernel = functools.partial(_flash_kernel, block_k=block_k, causal=causal,
                               sm_scale=sm_scale, kv_len=Tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lens_bh, available before the body runs
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, lens: (b, i, 0)),
            pl.BlockSpec((1, Tk, D), lambda b, i, lens: (b, 0, 0)),
            pl.BlockSpec((1, Tk, D), lambda b, i, lens: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, lens: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, lens: (b, 0, i)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[_out_struct((BH, Tq, D), q.dtype, q),
                   _out_struct((BH, 1, Tq), jnp.float32, q)],
        interpret=interpret,
        name="flash_fwd",
    )(lens_bh, q3, k3, v3)
    return out.reshape(B, H, Tq, D), lse


def _flash_dq_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                     dq_ref, *, block_k, causal, sm_scale, kv_len):
    from jax.experimental import pallas as pl

    qb = pl.program_id(1)
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    q = q_ref[0]                              # [bq, d] native dtype
    do = do_ref[0]                            # [bq, d]
    lse = lse_ref[0, 0][:, None]              # [bq, 1]
    dd = dd_ref[0, 0][:, None]                # [bq, 1] rowsum(dO * O)
    length = len_ref[pl.program_id(0)]

    ub = _live_blocks(qb, block_q, block_k, kv_len // block_k, causal)
    bound = _column_bound(qb, block_q, length, causal)
    col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

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
    acc = jax.lax.fori_loop(
        0, ub, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (acc * sm_scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(len_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                      dk_ref, dv_ref, *, block_q, causal, sm_scale, q_len):
    """dk, dv of one k-block. The score tile is held TRANSPOSED, [bk, bq]:
    ``K Q^T`` and ``V dO^T`` contract the operands' last axes, ``P^T dO``
    and ``dS^T Q`` are plain products, so no [bq, bk] tile is transposed
    on its way into a dot, and logsumexp / delta are read as the [1, bq]
    rows they are stored as."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    k = k_ref[0]                              # [bk, d] native dtype
    v = v_ref[0]                              # [bk, d]
    length = len_ref[pl.program_id(0)]

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

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, :, pl.ds(i * block_q, block_q)]        # [1, bq]
        dd = dd_ref[0, :, pl.ds(i * block_q, block_q)]          # [1, bq]
        s = jax.lax.dot_general(
            k, q, a_bt, preferred_element_type=jnp.float32) * sm_scale
        p = jnp.where(col >= first - i * block_q,
                      jnp.exp(s - lse), 0.0)                    # [bk, bq]
        dv_acc = dv_acc + jax.lax.dot_general(
            p.astype(do.dtype), do, a_b,
            preferred_element_type=jnp.float32)                 # [bk, d]
        dp = jax.lax.dot_general(
            v, do, a_bt, preferred_element_type=jnp.float32)    # [bk, bq]
        ds = p * (dp - dd)
        dk_acc = dk_acc + jax.lax.dot_general(
            ds.astype(q.dtype), q, a_b,
            preferred_element_type=jnp.float32)                 # [bk, d]
        return dk_acc, dv_acc

    z = jnp.zeros((block_k, d), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(lb, n_blocks, body, (z, z))
    dk_ref[0] = (dk_acc * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, lengths, g, causal, sm_scale, block_q,
                    block_k, interpret):
    """Blockwise flash backward: recomputes p tiles from the saved
    logsumexp instead of materialising [T, T] — HBM stays O(T*d), matching
    the forward's memory story (the whole point of the kernel)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    BH = B * H
    q3, k3, v3 = (t.reshape(BH, -1, D) for t in (q, k, v))
    do3 = g.reshape(BH, Tq, D)
    # D_i = rowsum(dO * O): one cheap fused elementwise+reduce in XLA
    dd = jnp.sum(do3.astype(jnp.float32)
                 * o.reshape(BH, Tq, D).astype(jnp.float32),
                 axis=-1)[:, None, :]          # [BH, 1, Tq]
    if lengths is None:
        lens = jnp.full((B,), Tk, jnp.int32)
    else:
        lens = lengths.astype(jnp.int32)
    lens_bh = jnp.repeat(lens, H)

    bq = _pick_block(Tq, block_q)
    bk = _pick_block(Tk, block_k)

    dq_kernel = functools.partial(_flash_dq_kernel, block_k=bk,
                                  causal=causal, sm_scale=sm_scale,
                                  kv_len=Tk)
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, Tq // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i, lens: (b, i, 0)),
                pl.BlockSpec((1, Tk, D), lambda b, i, lens: (b, 0, 0)),
                pl.BlockSpec((1, Tk, D), lambda b, i, lens: (b, 0, 0)),
                pl.BlockSpec((1, bq, D), lambda b, i, lens: (b, i, 0)),
                pl.BlockSpec((1, 1, bq), lambda b, i, lens: (b, 0, i)),
                pl.BlockSpec((1, 1, bq), lambda b, i, lens: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, bq, D), lambda b, i, lens: (b, i, 0)),
        ),
        out_shape=_out_struct((BH, Tq, D), q.dtype, q),
        interpret=interpret,
        name="flash_dq",
    )(lens_bh, q3, k3, v3, do3, lse, dd)

    dkv_kernel = functools.partial(_flash_dkv_kernel, block_q=bq,
                                   causal=causal, sm_scale=sm_scale,
                                   q_len=Tq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BH, Tk // bk),
            in_specs=[
                pl.BlockSpec((1, Tq, D), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((1, Tq, D), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, 1, Tq), lambda b, j, lens: (b, 0, 0)),
                pl.BlockSpec((1, 1, Tq), lambda b, j, lens: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bk, D), lambda b, j, lens: (b, j, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j, lens: (b, j, 0)),
            ],
        ),
        out_shape=[_out_struct((BH, Tk, D), k.dtype, k),
                   _out_struct((BH, Tk, D), v.dtype, v)],
        interpret=interpret,
        name="flash_dkv",
    )(lens_bh, q3, k3, v3, do3, lse, dd)
    return (dq.reshape(B, H, Tq, D), dk.reshape(B, H, Tk, D),
            dv.reshape(B, H, Tk, D))


def _pad_to_lanes(q, k, v, lengths):
    """Zero-pad the T axes up to 128-lane multiples so the kernels' block
    slicing is Mosaic-aligned for ANY sequence length. K padding becomes
    masked columns (lengths caps at the true Tk); padded Q rows compute
    garbage that callers slice away — and contribute nothing to dk/dv
    because their incoming gradient is zero-padded."""
    Tq, Tk = q.shape[2], k.shape[2]
    pq = (-Tq) % 128
    pk = (-Tk) % 128
    if pq == 0 and pk == 0:
        return q, k, v, lengths, Tq
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if lengths is None:
        lengths = jnp.full((q.shape[0],), Tk, jnp.int32)
    return q, k, v, lengths, Tq


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention(q, k, v, lengths, causal, sm_scale):
    if jax.default_backend() == "tpu":
        qp, kp, vp, lens, Tq = _pad_to_lanes(q, k, v, lengths)
        out, _ = _flash_forward(qp, kp, vp, lens, causal, sm_scale,
                                DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                                interpret=False)
        return out[:, :, :Tq]
    return reference_attention(q, k, v, lengths, causal, sm_scale)


#: ``checkpoint_name`` tags of what the backward reads: the operands as
#: the kernel was handed them, its result and its logsumexp. A
#: ``jax.checkpoint`` whose policy saves these names (the layer scan of
#: ``ops/pipeline_ops.py``) keeps the call's OWN residuals, so its backward
#: runs no second forward; a name on the caller's copy of the result
#: cannot do that, logsumexp never leaves this file. Outside such a
#: checkpoint the tags lower to nothing.
RESIDUAL_NAMES = ("flash_qkv", "flash_out", "flash_lse")


def _attention_fwd(q, k, v, lengths, causal, sm_scale):
    tag_qkv, tag_out, tag_lse = RESIDUAL_NAMES
    if jax.default_backend() == "tpu":
        qp, kp, vp, lens, Tq = _pad_to_lanes(q, k, v, lengths)
        out, lse = _flash_forward(qp, kp, vp, lens, causal, sm_scale,
                                  DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K,
                                  interpret=False)
        qp, kp, vp = checkpoint_name((qp, kp, vp), tag_qkv)
        # the result is tagged MERGED, [B, T, H * D] (the merge its caller
        # makes anyway): a scan that saves [B, H, T, 64] stacks it with
        # each 64-wide row padded to the 128 lanes, twice the bytes
        # (0.4 GB and 0.75 points of train_mfu in gpt2m-train, PERF.md)
        B, H, T, D = out.shape
        merged = checkpoint_name(
            out.transpose(0, 2, 1, 3).reshape(B, T, H * D), tag_out)
        out = merged.reshape(B, T, H, D).transpose(0, 2, 1, 3)
        lse = checkpoint_name(lse, tag_lse)
        return out[:, :, :Tq], (qp, kp, vp, out, lse, lens,
                                (Tq, k.shape[2]))
    # (no logsumexp here: the backward differentiates the reference)
    q, k, v = checkpoint_name((q, k, v), tag_qkv)
    out = checkpoint_name(
        reference_attention(q, k, v, lengths, causal, sm_scale), tag_out)
    return out, (q, k, v, None, None, lengths, None)


def _attention_bwd(causal, sm_scale, res, g):
    q, k, v, o, lse, lengths, orig = res
    if lse is not None:
        Tq, Tk = orig
        if g.shape[2] != q.shape[2]:
            g = jnp.pad(g, ((0, 0), (0, 0),
                            (0, q.shape[2] - g.shape[2]), (0, 0)))
        dq, dk, dv = _flash_backward(q, k, v, o, lse, lengths, g, causal,
                                     sm_scale, DEFAULT_BLOCK_Q,
                                     DEFAULT_BLOCK_K, interpret=False)
        return dq[:, :, :Tq], dk[:, :, :Tk], dv[:, :, :Tk], None

    def f(q, k, v):
        return reference_attention(q, k, v, lengths, causal, sm_scale)

    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_attention.defvjp(_attention_fwd, _attention_bwd)


def _batch_local(mesh, q, k, v, lengths, causal, sm_scale):
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
        return _attention(q, k, v, lengths, causal, sm_scale)

    return shard_map(local, mesh=mesh, in_specs=(spec,) * len(args),
                     out_specs=spec)(*args)


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
    from ..ops.common import amp_cast
    from ..parallel.context import current_mesh

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    out_dtype = q.dtype
    q, k, v = amp_cast(q, k, v)
    mesh = current_mesh()
    if (jax.default_backend() == "tpu" and mesh is not None
            and mesh.size > 1
            and not jax.sharding.get_abstract_mesh().manual_axes):
        # (already-manual callers — the GPipe stages — hold local shards)
        out = _batch_local(mesh, q, k, v, lengths, causal, float(sm_scale))
    else:
        out = _attention(q, k, v, lengths, causal, float(sm_scale))
    return out.astype(out_dtype)
