"""CTC: loss (forward-backward) and greedy decoding.

TPU-native replacement for the reference's warp-ctc integration
(/root/reference/paddle/cuda/src/hl_warpctc_wrap.cc dynloads Baidu
warp-ctc; /root/reference/paddle/gserver/layers/WarpCTCLayer.cpp drives
it) and the CTC error evaluator's best-path decoding
(/root/reference/paddle/gserver/evaluators/CTCErrorEvaluator.cpp:60-156).

The loss is the standard log-space alpha recursion over the extended
(blank-interleaved) label sequence, expressed as one ``lax.scan`` over time
with the whole batch vectorized per step — static shapes throughout, so XLA
pipelines the scan body on the VPU. No custom backward is needed: the scan
is reverse-differentiable and ``jax.vjp`` in the generic grad op yields
exactly the classic CTC gradient (the soft alignment posteriors), the same
quantity warp-ctc computes by hand with its beta recursion.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .common import maybe, out, single

_NEG_INF = -1e30


def _log_softmax(x):
    return x - jax.scipy.special.logsumexp(x, axis=-1, keepdims=True)


@register_op("warpctc", has_loop=True,
             optional_inputs=("LogitsLength", "LabelLength"))
def warpctc(attrs, ins):
    """CTC loss per sequence.

    Inputs: Logits [b, T, C] (unnormalized), Label [b, L] int (padded),
    optional LogitsLength [b], LabelLength [b]. Attr ``blank`` (default 0),
    ``norm_by_times`` divides each loss by its logit length
    (WarpCTCLayer.cpp's normByTimes). Output Loss [b, 1].
    """
    logits = single(ins, "Logits")
    label = single(ins, "Label").astype(jnp.int32)
    if label.ndim == 3:
        label = label[..., 0]
    b, T, C = logits.shape
    L = label.shape[1]
    blank = int(attrs.get("blank", 0))
    logit_len = maybe(ins, "LogitsLength")
    label_len = maybe(ins, "LabelLength")
    logit_len = (jnp.full((b,), T, jnp.int32) if logit_len is None
                 else logit_len.reshape(-1).astype(jnp.int32))
    label_len = (jnp.full((b,), L, jnp.int32) if label_len is None
                 else label_len.reshape(-1).astype(jnp.int32))

    logp = _log_softmax(logits.astype(jnp.float32))  # [b, T, C]

    # extended sequence z = [blank, l1, blank, l2, ..., blank], len S = 2L+1
    S = 2 * L + 1
    s_idx = jnp.arange(S)
    z = jnp.where(s_idx % 2 == 0, blank,
                  label[:, jnp.minimum(s_idx // 2, L - 1)])  # [b, S]
    # positions past the true extended length are invalid
    ext_len = 2 * label_len + 1
    valid = s_idx[None, :] < ext_len[:, None]  # [b, S]
    # transition from s-2 allowed iff z[s] != z[s-2] (and s even => blank,
    # which always equals z[s-2] when both blanks — standard CTC rule)
    z_prev2 = jnp.concatenate(
        [jnp.full((b, 2), -1, z.dtype), z[:, :-2]], axis=1)
    skip_ok = (z != z_prev2) & (s_idx[None, :] >= 2)

    # alpha[0]: start in z[0] (blank) or z[1] (first label)
    emit0 = jnp.take_along_axis(logp[:, 0, :], z, axis=1)  # [b, S]
    alpha0 = jnp.where(s_idx[None, :] <= 1, emit0, _NEG_INF)
    alpha0 = jnp.where(valid, alpha0, _NEG_INF)

    def step(alpha, logp_t):
        stay = alpha
        diag = jnp.concatenate(
            [jnp.full((b, 1), _NEG_INF), alpha[:, :-1]], axis=1)
        skip = jnp.concatenate(
            [jnp.full((b, 2), _NEG_INF), alpha[:, :-2]], axis=1)
        skip = jnp.where(skip_ok, skip, _NEG_INF)
        merged = jnp.logaddexp(jnp.logaddexp(stay, diag), skip)
        emit = jnp.take_along_axis(logp_t, z, axis=1)
        new = jnp.where(valid, merged + emit, _NEG_INF)
        return new, new

    # scan over time; gather each sequence's alpha at its own final frame
    _, alphas = jax.lax.scan(step, alpha0, jnp.swapaxes(logp, 0, 1)[1:])
    alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # [T, b, S]
    t_last = jnp.clip(logit_len - 1, 0, T - 1)
    alpha_T = alphas[t_last, jnp.arange(b)]  # [b, S]
    end1 = jnp.take_along_axis(alpha_T, (ext_len - 1)[:, None], axis=1)
    end2 = jnp.take_along_axis(
        alpha_T, jnp.maximum(ext_len - 2, 0)[:, None], axis=1)
    loss = -jnp.logaddexp(end1, end2)[:, 0]  # [b]
    # empty labels: loss = -sum log p(blank) over the frames
    blank_lp = jnp.cumsum(logp[:, :, blank], axis=1)
    empty_loss = -jnp.take_along_axis(blank_lp, t_last[:, None], axis=1)[:, 0]
    loss = jnp.where(label_len == 0, empty_loss, loss)
    if attrs.get("norm_by_times", False):
        loss = loss / jnp.maximum(logit_len.astype(jnp.float32), 1.0)
    return out(Loss=loss[:, None])


@register_op("ctc_greedy_decode", optional_inputs=("LogitsLength",))
def ctc_greedy_decode(attrs, ins):
    """Best-path CTC decoding: per-frame argmax, collapse repeats, drop
    blanks (CTCErrorEvaluator.cpp:60-104's path computation), all with
    static shapes: kept tokens are compacted to the front of a [b, T]
    buffer via a cumsum-position scatter.

    Outputs: Out [b, T] int32 (padded with ``blank``), OutLength [b, 1].
    """
    logits = single(ins, "Logits")
    b, T, C = logits.shape
    blank = int(attrs.get("blank", 0))
    logit_len = maybe(ins, "LogitsLength")
    logit_len = (jnp.full((b,), T, jnp.int32) if logit_len is None
                 else logit_len.reshape(-1).astype(jnp.int32))
    path = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [b, T]
    t_idx = jnp.arange(T)[None, :]
    in_range = t_idx < logit_len[:, None]
    prev = jnp.concatenate(
        [jnp.full((b, 1), -1, path.dtype), path[:, :-1]], axis=1)
    keep = (path != blank) & (path != prev) & in_range  # [b, T]
    pos = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1  # target slot
    pos = jnp.where(keep, pos, T)  # dropped frames scatter out of range
    dec = jnp.full((b, T), blank, jnp.int32)
    dec = jax.vmap(
        lambda d, p, v: d.at[p].set(v, mode="drop"))(dec, pos, path)
    n = keep.astype(jnp.int32).sum(axis=1)
    return {"Out": [dec], "OutLength": [n[:, None]]}
