"""Recurrent ops: LSTM / GRU cells and full scans.

TPU-native replacement for the reference's recurrent machinery:
- fused CUDA cells   /root/reference/paddle/operators/math/detail/
  lstm_gpu_kernel.h, gru_gpu_kernel.h (+ legacy hl_cuda_lstm.cu)
- batch reordering   /root/reference/paddle/operators/math/sequence2batch.h
  (reorders concatenated LoD rows into time-major batches so each timestep is
  one GEMM)
- the ops            /root/reference/paddle/operators/lstm_op.cc, gru_op.cc,
  lstm_unit_op.cc, gru_unit_op.cc

Design: inputs are already dense-padded [batch, T, ...] (see sequence_ops),
so no sequence2batch reordering exists at all — a transpose to time-major +
``jax.lax.scan`` gives XLA one fused while-loop whose body is a single
[b, h] x [h, gates*h] MXU matmul plus elementwise gate math (which XLA fuses
into the matmul's epilogue). Finished rows (t >= Length[b]) carry their state
through unchanged and emit zeros, reproducing LoD semantics.

Gate layouts follow the reference:
- LSTM Weight [h, 4h] ordered (candidate, input, forget, output) — the
  reference's {W_ch, W_ih, W_fh, W_oh} (lstm_op.cc:125-135); optional
  peephole weights (W_ic, W_fc, W_oc) live in Bias columns 4h:7h.
- GRU  Weight [h, 3h]: columns [0:2h] = (update, reset) gates, [2h:3h] =
  candidate; Bias [1, 3h]; h' = (1-u)*h + u*candidate (gru_op.cc:142).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from . import common
from .common import maybe, out, single
from .sequence_ops import time_mask

_ACT = {
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "relu": jax.nn.relu,
    "identity": lambda x: x,
}


def _lstm_step(h, c, gates, bias, peep, act_g, act_cand, act_cell):
    """One LSTM step. gates: [b, 4h] = x_proj + h @ W (pre-activation),
    columns ordered (candidate, input, forget, output) per lstm_op.cc.
    ``act_cand`` acts on the candidate gate, ``act_cell`` on the cell state
    in h = o * act_cell(c) (lstm_op.h:106-111)."""
    hdim = h.shape[-1]
    if bias is not None:
        gates = gates + bias[..., : 4 * hdim]
    gc, gi, gf, go = jnp.split(gates, 4, axis=-1)
    if peep is not None:
        wic, wfc, woc = jnp.split(peep, 3, axis=-1)
        gi = gi + wic * c
        gf = gf + wfc * c
    i = act_g(gi)
    f = act_g(gf)
    c_new = f * c + i * act_cand(gc)
    if peep is not None:
        go = go + woc * c_new
    o = act_g(go)
    h_new = o * act_cell(c_new)
    return h_new, c_new


@register_op("lstm", has_loop=True,
             optional_inputs=("Bias", "H0", "C0", "Length"))
def lstm(attrs, ins):
    """Full LSTM scan (reference lstm_op.cc `dynamic_lstm`).

    Input: [b, T, 4h] pre-projected x (the layer does x @ Wx outside the
    recurrence as ONE big [b*T, d] x [d, 4h] matmul — time-parallel on the
    MXU; only the h-recurrence is sequential).
    """
    x = single(ins, "Input")  # [b, T, 4h]
    w = single(ins, "Weight")  # [h, 4h]
    bias = maybe(ins, "Bias")  # [1, 4h] or [1, 7h] w/ peepholes
    lengths = maybe(ins, "Length")
    h0 = maybe(ins, "H0")
    c0 = maybe(ins, "C0")
    b, T, four_h = x.shape
    hdim = four_h // 4
    use_peep = attrs.get("use_peepholes", False)
    reverse = attrs.get("is_reverse", False)
    act_g = _ACT[attrs.get("gate_activation", "sigmoid")]
    act_cand = _ACT[attrs.get("candidate_activation", "tanh")]
    act_cell = _ACT[attrs.get("cell_activation", "tanh")]

    peep = None
    if bias is not None and use_peep:
        peep = bias[..., 4 * hdim: 7 * hdim]
    h = h0 if h0 is not None else jnp.zeros((b, hdim), x.dtype)
    c = c0 if c0 is not None else jnp.zeros((b, hdim), x.dtype)

    xs = jnp.swapaxes(x, 0, 1)  # [T, b, 4h]
    mask = (jnp.swapaxes(time_mask(lengths, T, x.dtype), 0, 1)[..., None]
            if lengths is not None else None)

    x_cast, w_cast = common.amp_cast(xs, w)

    def step(carry, inp):
        h, c = carry
        if mask is None:
            xt, m = inp, None
        else:
            xt, m = inp
        gates = xt + jnp.dot(common.amp_cast(h), w_cast,
                             precision=common.mxu_precision()).astype(h.dtype)
        h_new, c_new = _lstm_step(h, c, gates, bias, peep, act_g, act_cand,
                                  act_cell)
        if m is not None:
            h_new = m * h_new + (1 - m) * h
            c_new = m * c_new + (1 - m) * c
            y = (h_new * m, c_new * m)
        else:
            y = (h_new, c_new)
        return (h_new, c_new), y

    seq = x_cast if mask is None else (x_cast, mask)
    (h, c), (ys, cs) = jax.lax.scan(step, (h, c), seq, reverse=reverse)
    hidden = jnp.swapaxes(ys, 0, 1)  # [b, T, h]
    cell = jnp.swapaxes(cs, 0, 1)
    return out(Hidden=hidden, Cell=cell, LastH=h, LastC=c)


@register_op("gru", has_loop=True,
             optional_inputs=("Bias", "H0", "Length"))
def gru(attrs, ins):
    """Full GRU scan (reference gru_op.cc `dynamic_gru`).

    Input: [b, T, 3h] pre-projected x. Reference formulas (gru_op.cc:142):
    m = act(x_m + (r . h) @ W_m); h' = (1-u)*h + u*m.
    """
    x = single(ins, "Input")  # [b, T, 3h]
    w = single(ins, "Weight")  # [h, 3h]: [:, :2h] gates, [:, 2h:] candidate
    bias = maybe(ins, "Bias")
    lengths = maybe(ins, "Length")
    h0 = maybe(ins, "H0")
    b, T, three_h = x.shape
    hdim = three_h // 3
    reverse = attrs.get("is_reverse", False)
    act_g = _ACT[attrs.get("gate_activation", "sigmoid")]
    act_c = _ACT[attrs.get("activation", "tanh")]

    h = h0 if h0 is not None else jnp.zeros((b, hdim), x.dtype)

    xs = jnp.swapaxes(x, 0, 1)
    if bias is not None:
        xs = xs + bias
    mask = (jnp.swapaxes(time_mask(lengths, T, x.dtype), 0, 1)[..., None]
            if lengths is not None else None)
    prec = common.mxu_precision()
    xs, wg, wc = common.amp_cast(xs, w[:, : 2 * hdim], w[:, 2 * hdim:])

    def step(h, inp):
        if mask is None:
            xt, m = inp, None
        else:
            xt, m = inp
        xg, xc = xt[..., : 2 * hdim], xt[..., 2 * hdim:]
        g = act_g(xg + jnp.dot(common.amp_cast(h), wg,
                               precision=prec).astype(h.dtype))
        u, r = g[..., :hdim], g[..., hdim:]
        cand = act_c(xc + jnp.dot(common.amp_cast(r * h), wc,
                                  precision=prec).astype(h.dtype))
        h_new = (1.0 - u) * h + u * cand
        if m is not None:
            h_new = m * h_new + (1 - m) * h
            y = h_new * m
        else:
            y = h_new
        return h_new, y

    seq = xs if mask is None else (xs, mask)
    h, ys = jax.lax.scan(step, h, seq, reverse=reverse)
    return out(Hidden=jnp.swapaxes(ys, 0, 1), LastH=h)


@register_op("simple_rnn", has_loop=True,
             optional_inputs=("Bias", "H0", "Length"))
def simple_rnn(attrs, ins):
    """Plain recurrent layer (reference gserver RecurrentLayer.cpp, the v1
    ``recurrent_layer``): out_t = act(in_t + out_{t-1} @ W + b). ``Input``
    is [b, T, h] ALREADY at hidden width (the v1 contract: the projection
    into the layer happens outside, e.g. via mixed_layer); only the h@W
    recurrence is sequential."""
    x = single(ins, "Input")  # [b, T, h]
    w = single(ins, "Weight")  # [h, h]
    bias = maybe(ins, "Bias")
    lengths = maybe(ins, "Length")
    h0 = maybe(ins, "H0")
    b, T, hdim = x.shape
    reverse = attrs.get("is_reverse", False)
    act = _ACT[attrs.get("activation", "tanh")]

    h = h0 if h0 is not None else jnp.zeros((b, hdim), x.dtype)
    xs = jnp.swapaxes(x, 0, 1)  # [T, b, h]
    if bias is not None:
        xs = xs + bias
    mask = (jnp.swapaxes(time_mask(lengths, T, x.dtype), 0, 1)[..., None]
            if lengths is not None else None)
    prec = common.mxu_precision()
    xs, w_cast = common.amp_cast(xs, w)

    def step(h, inp):
        if mask is None:
            xt, m = inp, None
        else:
            xt, m = inp
        h_new = act(xt + jnp.dot(common.amp_cast(h), w_cast,
                                 precision=prec).astype(h.dtype))
        if m is not None:
            h_new = m * h_new + (1 - m) * h
            y = h_new * m
        else:
            y = h_new
        return h_new, y

    seq = xs if mask is None else (xs, mask)
    h, ys = jax.lax.scan(step, h, seq, reverse=reverse)
    return out(Hidden=jnp.swapaxes(ys, 0, 1), LastH=h)


@register_op("lstm_unit", optional_inputs=("Bias",))
def lstm_unit(attrs, ins):
    """Single LSTM step (lstm_unit_op.cc): gates already projected, [b, 4h]."""
    gates = single(ins, "X")
    c_prev = single(ins, "C_prev")
    bias = maybe(ins, "Bias")
    forget_bias = attrs.get("forget_bias", 0.0)
    hdim = c_prev.shape[-1]
    if bias is not None:
        gates = gates + bias
    # gate layout (i, f, o, g) matches lstm_unit_op.h:63-66
    gi, gf, go, gc = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf + forget_bias)
    c = f * c_prev + i * jnp.tanh(gc)
    h = jax.nn.sigmoid(go) * jnp.tanh(c)
    return out(C=c, H=h)


@register_op("gru_unit", optional_inputs=("Bias",))
def gru_unit(attrs, ins):
    """Single GRU step (gru_unit_op.cc): Input [b, 3h] pre-projected."""
    xt = single(ins, "Input")
    h_prev = single(ins, "HiddenPrev")
    w = single(ins, "Weight")  # [h, 3h]
    bias = maybe(ins, "Bias")
    act_g = _ACT[attrs.get("gate_activation", "sigmoid")]
    act_c = _ACT[attrs.get("activation", "tanh")]
    hdim = h_prev.shape[-1]
    if bias is not None:
        xt = xt + bias
    prec = common.mxu_precision()
    xg, xc = xt[..., : 2 * hdim], xt[..., 2 * hdim:]
    g = act_g(xg + jnp.dot(h_prev, w[:, : 2 * hdim], precision=prec))
    u, r = g[..., :hdim], g[..., hdim:]
    cand = act_c(xc + jnp.dot(r * h_prev, w[:, 2 * hdim:], precision=prec))
    h = (1.0 - u) * h_prev + u * cand  # gru_unit_op.cc:122
    return out(Hidden=h, Gate=g, ResetHiddenPrev=r * h_prev)
