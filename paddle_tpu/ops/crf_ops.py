"""Linear-chain CRF ops: log-likelihood, viterbi decode, chunk evaluation.

TPU-native replacement for the reference's CRF stack:
- linear_chain_crf_op.{cc,h} — forward alpha recursion + per-sequence
  log-likelihood (the fluid op; CPU-only in the reference)
- crf_decoding_op.{cc,h} — viterbi decode
- legacy CRFLayer / CRFDecodingLayer (gserver/layers/CRFLayer.cpp,
  LinearChainCRF.cpp)
- chunk_eval_op.cc / ChunkEvaluator (gserver/evaluators/ChunkEvaluator.cpp)

The reference walks each sequence with per-row C++ loops over LoD offsets.
Here the alpha/viterbi recursions run as one ``lax.scan`` over the padded
time axis for the whole batch (finished rows carry state through), and the
[tag, tag] transition inner products batch onto the MXU/VPU.

Transition parameter layout matches the reference (linear_chain_crf_op.h):
``Transition`` is [num_tags + 2, num_tags]; row 0 = start weights a_j,
row 1 = end weights b_j, rows 2.. = w_{ij} (from tag i to tag j).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import maybe, out, single
from .sequence_ops import time_mask


def _split_transition(trans):
    return trans[0], trans[1], trans[2:]  # start [T], end [T], w [T, T]


@register_op("linear_chain_crf", has_loop=True,
             optional_inputs=("Length",))
def linear_chain_crf(attrs, ins):
    """Negative log-likelihood of tag paths under a linear-chain CRF.

    Inputs: Emission [b, T, n] (unnormalised scores), Transition [n+2, n],
    Label [b, T] int, Length [b]. Outputs LogLikelihood [b, 1] (actually the
    NEGATIVE log-likelihood, matching the reference's sign convention where
    the op output feeds a mean cost), plus Alpha for parity.
    """
    emission = single(ins, "Emission")
    trans = single(ins, "Transition")
    label = single(ins, "Label")
    lengths = maybe(ins, "Length")
    if label.ndim == 3:
        label = label[..., 0]
    b, T, n = emission.shape
    if lengths is None:
        lengths = jnp.full((b,), T, jnp.int32)
    start_w, end_w, w = _split_transition(trans)
    mask = time_mask(lengths, T, emission.dtype)  # [b, T]

    # ---- partition function: alpha recursion in log space -----------------
    em_tm = jnp.swapaxes(emission, 0, 1)  # [T, b, n]
    mask_tm = jnp.swapaxes(mask, 0, 1)  # [T, b]
    alpha0 = start_w[None, :] + em_tm[0]  # [b, n]

    def alpha_step(alpha, xs):
        em_t, m_t = xs
        # logsumexp_i(alpha_i + w_ij) + em_j
        scores = alpha[:, :, None] + w[None, :, :]  # [b, n, n]
        new_alpha = jax.nn.logsumexp(scores, axis=1) + em_t
        alpha = jnp.where(m_t[:, None] > 0, new_alpha, alpha)
        return alpha, alpha

    alpha_last, alphas = jax.lax.scan(alpha_step, alpha0,
                                      (em_tm[1:], mask_tm[1:]))
    log_z = jax.nn.logsumexp(alpha_last + end_w[None, :], axis=-1)  # [b]

    # ---- gold path score --------------------------------------------------
    path_em = jnp.take_along_axis(emission, label[..., None],
                                  axis=2)[..., 0]  # [b, T]
    em_score = jnp.sum(path_em * mask, axis=1)
    trans_pairs = w[label[:, :-1], label[:, 1:]]  # [b, T-1]
    em_score = em_score + jnp.sum(trans_pairs * mask[:, 1:], axis=1)
    first_tag = label[:, 0]
    last_idx = jnp.maximum(lengths - 1, 0)
    last_tag = jnp.take_along_axis(label, last_idx[:, None], axis=1)[:, 0]
    path_score = em_score + start_w[first_tag] + end_w[last_tag]

    nll = (log_z - path_score)[:, None]  # [b, 1]
    alpha_full = jnp.concatenate([alpha0[None], alphas], axis=0)
    return out(LogLikelihood=nll, Alpha=jnp.swapaxes(alpha_full, 0, 1))


@register_op("crf_decoding", optional_inputs=("Length", "Label"))
def crf_decoding(attrs, ins):
    """Viterbi decode (crf_decoding_op.h): best tag path per row.

    Without Label: ViterbiPath [b, T] int64 best tags (padding positions 0).
    With Label (reference behaviour for evaluation): outputs per-position
    0/1 correctness instead.
    """
    emission = single(ins, "Emission")
    trans = single(ins, "Transition")
    lengths = maybe(ins, "Length")
    label = maybe(ins, "Label")
    b, T, n = emission.shape
    if lengths is None:
        lengths = jnp.full((b,), T, jnp.int32)
    start_w, end_w, w = _split_transition(trans)
    mask = time_mask(lengths, T, emission.dtype)
    em_tm = jnp.swapaxes(emission, 0, 1)
    mask_tm = jnp.swapaxes(mask, 0, 1)

    v0 = start_w[None, :] + em_tm[0]  # [b, n]

    def vit_step(v, xs):
        em_t, m_t = xs
        scores = v[:, :, None] + w[None, :, :]  # [b, from, to]
        best_prev = jnp.argmax(scores, axis=1).astype(jnp.int32)  # [b, n]
        new_v = jnp.max(scores, axis=1) + em_t
        v = jnp.where(m_t[:, None] > 0, new_v, v)
        # frozen rows backtrack to "stay" (identity) so padding is harmless
        best_prev = jnp.where(m_t[:, None] > 0, best_prev,
                              jnp.arange(n, dtype=jnp.int32)[None, :])
        return v, best_prev

    v_last, back = jax.lax.scan(vit_step, v0, (em_tm[1:], mask_tm[1:]))
    # back: [T-1, b, n] — back[t][b][j] = best tag at t for tag j at t+1
    final_tag = jnp.argmax(v_last + end_w[None, :], axis=-1).astype(jnp.int32)

    def backtrack(tag, bp_t):
        prev = jnp.take_along_axis(bp_t, tag[:, None], axis=1)[:, 0]
        return prev, tag

    # reverse scan: ys[t] = tag at position t+1, final carry = tag at 0
    first_tag, path_rev = jax.lax.scan(backtrack, final_tag, back,
                                       reverse=True)
    path = jnp.concatenate([first_tag[None], path_rev], axis=0)  # [T, b]
    path = jnp.swapaxes(path, 0, 1) * mask.astype(jnp.int32)  # zero padding
    if label is not None:
        if label.ndim == 3:
            label = label[..., 0]
        correct = (path == label.astype(jnp.int32)).astype(jnp.int64)
        correct = correct * mask.astype(jnp.int64)
        return out(ViterbiPath=correct)
    return out(ViterbiPath=path.astype(jnp.int64))


@register_op("chunk_eval", optional_inputs=("Length",))
def chunk_eval(attrs, ins):
    """Chunk-level precision/recall/F1 (chunk_eval_op.cc, IOB scheme).

    Counts chunks in Inference and Label tag sequences and the matches
    between them. Supports chunk_scheme "IOB" with num_chunk_types k: tag
    2*c = B-type_c, 2*c+1 = I-type_c (the reference's default encoding).
    Outputs Precision/Recall/F1-Score [1] plus raw counts.
    """
    inference = single(ins, "Inference")
    label = single(ins, "Label")
    lengths = maybe(ins, "Length")
    if inference.ndim == 3:
        inference = inference[..., 0]
    if label.ndim == 3:
        label = label[..., 0]
    b, T = label.shape[:2]
    if lengths is None:
        lengths = jnp.full((b,), T, jnp.int32)
    num_types = int(attrs.get("num_chunk_types", 1))
    mask = time_mask(lengths, T, jnp.int32)
    valid = mask > 0

    def chunk_info(tags):
        """IOB starts + membership. Tags 2c=B-c, 2c+1=I-c for c<num_types;
        any tag >= 2*num_types is Outside. A chunk starts at B-c, or at I-c
        when the previous position is not B-c/I-c of the same type."""
        tags = tags.astype(jnp.int32)
        ctype = tags // 2
        in_chunk = (ctype < num_types) & valid
        is_b = (tags % 2) == 0
        prev_t = jnp.pad(ctype, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
        prev_in = jnp.pad(in_chunk, ((0, 0), (1, 0)),
                          constant_values=False)[:, :-1]
        cont = prev_in & (prev_t == ctype)
        starts = in_chunk & (is_b | ~cont)
        return starts, in_chunk

    inf_starts, inf_in = chunk_info(inference)
    lab_starts, lab_in = chunk_info(label)
    n_inf = jnp.sum(inf_starts)
    n_lab = jnp.sum(lab_starts)

    # A label chunk [s, e] matches an inference chunk iff tags agree on every
    # position of [s, e], chunk starts coincide throughout (so the inference
    # chunk starts at s with no inner boundary), and the inference chunk does
    # not continue past e (at e+1 it must be outside or a fresh start). The
    # continuation check applies only at label-chunk END positions — inner
    # positions are legitimately followed by continuation.
    sagree = inf_starts == lab_starts
    # Matching is by (begin, end, TYPE) — chunk_eval_op.h Segment::operator==
    # — so compare chunk types, not raw B-/I- tags; an I-initiated inference
    # chunk with the right span and type still matches.
    tag_eq = ((inference.astype(jnp.int32) // 2 == label.astype(jnp.int32) // 2)
              & inf_in)
    cont_inf = inf_in & ~inf_starts  # position continues an inference chunk
    cont_lab = lab_in & ~lab_starts
    next_within = (jnp.arange(T)[None, :] + 1) < lengths[:, None]
    cont_inf_next = (jnp.pad(cont_inf, ((0, 0), (0, 1)))[:, 1:]
                     & next_within)
    cont_lab_next = (jnp.pad(cont_lab, ((0, 0), (0, 1)))[:, 1:]
                     & next_within)
    lab_end = lab_in & ~cont_lab_next  # last position of its label chunk
    end_ok = jnp.where(lab_end, ~cont_inf_next, True)
    agree = tag_eq & sagree & end_ok & valid

    # Per-label-chunk segment-min of agreement: segment ids by cumsum of
    # label starts; non-chunk positions go to a dump segment.
    max_chunks = T + 1
    lab_seg = jnp.cumsum(lab_starts.astype(jnp.int32), axis=1)
    flat_seg = lab_seg + jnp.arange(b)[:, None] * max_chunks
    dump = b * max_chunks
    flat_seg = jnp.where(lab_in, flat_seg, dump)
    seg_min = jax.ops.segment_min(
        agree.astype(jnp.int32).reshape(-1), flat_seg.reshape(-1),
        num_segments=dump + 1)
    seg_cnt = jax.ops.segment_sum(
        lab_in.astype(jnp.int32).reshape(-1), flat_seg.reshape(-1),
        num_segments=dump + 1)
    matched = jnp.sum((seg_min[:dump] > 0) & (seg_cnt[:dump] > 0))

    eps = 1e-10
    precision = matched / jnp.maximum(n_inf, 1)
    recall = matched / jnp.maximum(n_lab, 1)
    f1 = 2 * precision * recall / jnp.maximum(precision + recall, eps)
    one = lambda x: jnp.reshape(x.astype(jnp.float32), (1,))
    return {
        "Precision": [one(precision)],
        "Recall": [one(recall)],
        "F1-Score": [one(f1)],
        "NumInferChunks": [jnp.reshape(n_inf.astype(jnp.int64), (1,))],
        "NumLabelChunks": [jnp.reshape(n_lab.astype(jnp.int64), (1,))],
        "NumCorrectChunks": [jnp.reshape(matched.astype(jnp.int64), (1,))],
    }
