"""Fused 1x1-conv + BN-epilogue Pallas kernels.

PERF.md's ResNet-50 roofline: the bs256 train step is HBM-bound, with
~8 GB/step of bare elementwise traffic (residual adds) and the BN
normalize reading/writing every conv output around the dot. The
reference runs these as separate cudnn conv + BN + eltwise kernels
(/root/reference/paddle/operators/conv_cudnn_op.cu.cc,
batch_norm_op.cc, elementwise_add_op.cc); XLA fuses better than cudnn
but still materializes the raw conv output around the training-mode BN
reduction. These kernels attack the structure directly:

- ``conv1x1_stats``: one pass computing y_raw = x @ W while
  accumulating the per-channel sum and sum-of-squares in VMEM across
  the R grid — the BN statistics come out of the SAME pass that writes
  the conv output, removing the separate stats-reduce read of y_raw.
- ``scale_shift_act``: one elementwise pass y = act(y*scale+shift+res)
  applying the folded BN affine, the residual add, and the activation
  in a single read/write — where XLA's scheduler leaves the residual
  fork as its own kernel (the measured 11.2 ms/step), this folds it.
- ``conv1x1_epilogue``: the inference-mode full fusion — running stats
  are known up front, so the affine+act+residual ride in the dot
  kernel's output tile and the raw conv output NEVER touches HBM.

Off the chip (CPU tests) the kernels run in interpret mode, or as the
plain XLA composition when a shape has no tile. ON the chip a shape with
no tile inside the VMEM budget raises, naming the shape: a fused op that
was asked for never quietly becomes the unfused one.
The backward stays XLA: the fused-linear-backward tombstone (PERF.md)
showed hand-written backward contractions lose under the 16 MB
scoped-vmem limit; forward epilogue fusion does not fight that wall
because the weight tile is small and the accumulator is [2, O].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_BUDGET = 12 * 1024 * 1024


def _pick_block_r(R: int, I: int, O: int, itemsize: int) -> int:
    """Largest R tile dividing R that fits the VMEM budget (0 = none)."""
    fixed = I * O * itemsize + 2 * O * 4  # weight tile + stats accum
    if fixed > _VMEM_BUDGET:
        return 0
    for b in (1024, 512, 256, 128):
        if R % b:
            continue
        tiles = b * I * itemsize * 2 + 2 * b * O * itemsize
        if fixed + tiles <= _VMEM_BUDGET:
            return b
    return 0


def _use_pallas(kernel: str, block_r: int, shape, dtype,
                interpret: bool) -> bool:
    """Whether the Pallas path runs (else the plain XLA composition). On
    TPU a shape without a tile is an error naming it; off the chip the
    kernel runs only in interpret mode."""
    on_tpu = jax.default_backend() == "tpu"
    if block_r == 0:
        if on_tpu:
            raise ValueError(
                f"{kernel}: no row tile (128..2048 rows dividing the row "
                f"count) of {tuple(shape)} {jnp.dtype(dtype).name} fits "
                f"the {_VMEM_BUDGET >> 20} MiB VMEM budget; use the "
                f"unfused conv2d + batch_norm ops for this layer")
        return False
    return on_tpu or interpret


def _stats_kernel(x_ref, w_ref, y_ref, stat_ref, acc_ref, *, nsteps,
                  precision):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    y = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    acc_ref[0, :] += jnp.sum(y, axis=0)
    acc_ref[1, :] += jnp.sum(y * y, axis=0)

    @pl.when(step == nsteps - 1)
    def _done():
        stat_ref[...] = acc_ref[...]


def conv1x1_stats(x2, w, precision=None, interpret=False):
    """y_raw = x2 @ w plus per-channel (sum, sumsq) in one pass.

    x2: [R, I]; w: [I, O]. Returns (y_raw [R, O] in x2.dtype,
    stats [2, O] f32).
    """
    R, I = x2.shape
    O = w.shape[1]
    block_r = _pick_block_r(R, I, O, x2.dtype.itemsize)
    if not _use_pallas("conv1x1_stats", block_r, (R, I, O), x2.dtype,
                       interpret):
        y = jax.lax.dot_general(x2, w, (((1,), (0,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        stats = jnp.stack([jnp.sum(y, axis=0), jnp.sum(y * y, axis=0)])
        return y.astype(x2.dtype), stats
    nsteps = R // block_r
    y, stats = pl.pallas_call(
        functools.partial(_stats_kernel, nsteps=nsteps,
                          precision=precision),
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((block_r, I), lambda i: (i, 0)),
            pl.BlockSpec((I, O), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_r, O), lambda i: (i, 0)),
            pl.BlockSpec((2, O), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, O), x2.dtype),
            jax.ShapeDtypeStruct((2, O), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((2, O), jnp.float32)],
        interpret=interpret,
    )(x2, w)
    return y, stats


def _epilogue_kernel(x_ref, w_ref, sc_ref, sh_ref, res_ref, o_ref, *,
                     act, precision):
    y = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    y = y * sc_ref[...] + sh_ref[...]
    if res_ref is not None:
        y = y + res_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def conv1x1_epilogue(x2, w, scale, shift, residual=None, act=None,
                     precision=None, interpret=False):
    """Inference-mode full fusion: act((x2 @ w) * scale + shift [+ res]).

    The raw conv output never reaches HBM. scale/shift are the folded
    BN affine ([O] f32): scale = gamma*rsqrt(var+eps),
    shift = beta - mean*scale.
    """
    R, I = x2.shape
    O = w.shape[1]
    block_r = _pick_block_r(R, I, O, x2.dtype.itemsize)
    if not _use_pallas("conv1x1_epilogue", block_r, (R, I, O), x2.dtype,
                       interpret):
        y = jax.lax.dot_general(x2, w, (((1,), (0,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        y = y * scale + shift
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        return y.astype(x2.dtype)
    nsteps = R // block_r
    ins = [x2, w, scale.reshape(1, O).astype(jnp.float32),
           shift.reshape(1, O).astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((block_r, I), lambda i: (i, 0)),
        pl.BlockSpec((I, O), lambda i: (0, 0)),
        pl.BlockSpec((1, O), lambda i: (0, 0)),
        pl.BlockSpec((1, O), lambda i: (0, 0)),
    ]
    if residual is not None:
        ins.append(residual)
        in_specs.append(pl.BlockSpec((block_r, O), lambda i: (i, 0)))
        kern = functools.partial(_epilogue_kernel, act=act,
                                 precision=precision)
    else:
        def kern(x_ref, w_ref, sc_ref, sh_ref, o_ref):
            return _epilogue_kernel(x_ref, w_ref, sc_ref, sh_ref, None,
                                    o_ref, act=act, precision=precision)
    return pl.pallas_call(
        kern,
        grid=(nsteps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_r, O), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, O), x2.dtype),
        interpret=interpret,
    )(*ins)


def _apply_kernel(y_ref, sc_ref, sh_ref, res_ref, o_ref, *, act):
    y = y_ref[...].astype(jnp.float32) * sc_ref[...] + sh_ref[...]
    if res_ref is not None:
        y = y + res_ref[...].astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def scale_shift_act(y_raw, scale, shift, residual=None, act=None,
                    interpret=False):
    """One elementwise pass: act(y_raw*scale + shift [+ residual]).

    Folds the BN affine, the residual fork, and the activation into a
    single read/write of the [R, O] activation.
    """
    R, O = y_raw.shape
    block_r = 0
    itemsize = y_raw.dtype.itemsize
    # Mirror _pick_block_r's accounting: every R-streamed tile (y_raw in,
    # y out, optional residual in) is DOUBLE-BUFFERED by Pallas while the
    # grid walks R — 2 streams without a residual, 3 with one, i.e.
    # ~4-6x b*O*itemsize resident, not a single copy of each.
    streams = 3 if residual is not None else 2
    fixed = 2 * O * 4  # scale + shift f32 rows, revisited (not streamed)
    for b in (2048, 1024, 512, 256, 128):
        if R % b == 0 and (2 * streams * b * O * itemsize + fixed) \
                <= _VMEM_BUDGET:
            block_r = b
            break
    if not _use_pallas("scale_shift_act", block_r, (R, O), y_raw.dtype,
                       interpret):
        y = y_raw.astype(jnp.float32) * scale + shift
        if residual is not None:
            y = y + residual.astype(jnp.float32)
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        return y.astype(y_raw.dtype)
    nsteps = R // block_r
    ins = [y_raw, scale.reshape(1, O).astype(jnp.float32),
           shift.reshape(1, O).astype(jnp.float32)]
    in_specs = [
        pl.BlockSpec((block_r, O), lambda i: (i, 0)),
        pl.BlockSpec((1, O), lambda i: (0, 0)),
        pl.BlockSpec((1, O), lambda i: (0, 0)),
    ]
    if residual is not None:
        ins.append(residual)
        in_specs.append(pl.BlockSpec((block_r, O), lambda i: (i, 0)))
        kern = functools.partial(_apply_kernel, act=act)
    else:
        def kern(y_ref, sc_ref, sh_ref, o_ref):
            return _apply_kernel(y_ref, sc_ref, sh_ref, None, o_ref,
                                 act=act)
    return pl.pallas_call(
        kern,
        grid=(nsteps,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_r, O), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(y_raw.shape, y_raw.dtype),
        interpret=interpret,
    )(*ins)
