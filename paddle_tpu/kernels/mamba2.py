"""Mamba-2 (arXiv:2405.21060): the selective state-space layer in its
state-space-duality (SSD) form, ONE scalar decay a head, as the paged
serving ops run it.

A head keeps a state S [P, N] in float32 (P channels of the head, N state
dimensions). A token with input x [P], step dt (> 0, after its softplus),
log-decay g = -exp(A_log) dt (<= 0) and the group's input / output vectors
B, C [N] (the heads of one group share them) does

    S = exp(g) S + (dt x) B^T           decay, then write
    y = S C                             read-out (the caller adds D x)

Three forms of it live here, all float32:

- ``mamba2_recurrent`` — the recurrence itself, token by token
  (``lax.scan``): the ground truth of the other two and the decode step off
  the chip.
- ``mamba2_chunked`` — a prefill chunk, in blocks of ``block`` tokens, as
  matmuls (the paper's SSD form). With G_i the running sum of g inside a
  block and S_0 the state entering it,

      y_i = exp(G_i) S_0 C_i + sum_{j <= i} exp(G_i - G_j) (C_i . B_j) dt_j x_j
      S_C = exp(G_C) S_0 + sum_j exp(G_C - G_j) (dt_j x_j) B_j^T

  The pairwise exponent is taken per PAIR (always <= 0). Blocks follow one
  another under ``lax.scan``; tokens past a row's valid length carry g = 0
  and dt = 0 and leave the state as it is.
- ``mamba2_decode_step`` — ONE Pallas TPU kernel for the decode tick: a
  (slot, head-group) block loads its S tiles [P, N] once from the whole
  state array [L, slots, H, P, N] at the layer's index (scalar prefetch),
  applies decay, write and read-out on the VPU and writes the tiles back to
  the same buffer (``input_output_aliases``): the tick moves the state's
  bytes once each way and nothing else of that size. What a tile needs
  along its sublanes (the decay, dt x) arrives as COLUMNS ([P, heads]: a
  lane slice broadcasts along the lanes), B and C as the group's rows; the
  read-out leaves as columns. A row that is not live is handed decay 1 and
  dt x = 0 and leaves its state bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["KERNEL", "mamba2_chunked", "mamba2_decode_step",
           "mamba2_recurrent", "supported"]

#: the decode kernel's call name (``pallas_call(name=...)``): what the
#: benchmark's ``mamba_decode_roofline`` tells a call by
KERNEL = "mamba2_decode_step"
#: heads of one kernel block at most: 16 x [64, 128] float32 = 512 KB in,
#: 512 KB out (a block never straddles two groups)
_HEAD_BLOCK = 16


def _by_group(a, groups: int):
    """[b, H, ..] -> [b, G, H / G, ..]: head n belongs to group n // (H/G)."""
    return a.reshape(a.shape[:1] + (groups, a.shape[1] // groups)
                     + a.shape[2:])


def mamba2_recurrent(x, dt, g, B, C, state):
    """x [b, t, H, P], dt, g [b, t, H], B, C [b, t, G, N], state [b, H, P,
    N] (all float32) -> (y [b, t, H, P], the state after token t)."""
    G = B.shape[2]
    hi = jax.lax.Precision.HIGHEST

    def step(S, inp):
        x_t, dt_t, g_t, B_t, C_t = inp
        S = _by_group(S * jnp.exp(g_t)[..., None, None], G)
        xdt = _by_group(x_t * dt_t[..., None], G)             # [b,G,k,P]
        S = S + xdt[..., None] * B_t[:, :, None, None, :]
        y = jnp.einsum("bgkpn,bgn->bgkp", S, C_t, precision=hi)
        return S.reshape(x_t.shape + S.shape[-1:]), y.reshape(x_t.shape)

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, g, B, C))
    state, y = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(y, 0, 1), state


def mamba2_chunked(x, dt, g, B, C, state, block: int = 128):
    """The same function as ``mamba2_recurrent`` over a chunk of t tokens,
    in blocks of ``block`` (the chunk is padded to whole blocks with tokens
    that do nothing: g = 0, dt = 0)."""
    b, t, H, P = x.shape
    G, N = B.shape[2:]
    Cn = min(block, t)
    pad = -t % Cn
    if pad:
        x, dt, g, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (x, dt, g, B, C))
    n = (t + pad) // Cn
    hi = jax.lax.Precision.HIGHEST
    lower = jnp.tril(jnp.ones((Cn, Cn), bool))

    def blocks(a):      # [b, n*C, ..] -> [n, b, C, ..]
        return jnp.moveaxis(a.reshape((b, n, Cn) + a.shape[2:]), 1, 0)

    def one(S0, inp):
        x_c, dt_c, g_c, B_c, C_c = inp      # [b, C, H, P], [b, C, H], ..
        Gs = jnp.cumsum(g_c, axis=1)                          # [b, C, H]
        Gh = jnp.moveaxis(Gs, 1, 2)                           # [b, H, C]
        # pairwise decay exp(G_i - G_j), j <= i: the exponent is taken per
        # pair and held to <= 0 before exp (the masked half is dropped)
        dec = jnp.exp(jnp.where(lower, Gh[..., :, None] - Gh[..., None, :],
                                -jnp.inf))                    # [b, H, C, C]
        cb = jnp.einsum("bign,bjgn->bgij", C_c, B_c, precision=hi)
        m = _by_group(dec, G) * cb[:, :, None]                # [b,G,k,C,C]
        xdt = (x_c * dt_c[..., None]).reshape(b, Cn, G, H // G, P)
        S0g = _by_group(S0, G)                                # [b,G,k,P,N]
        eG = jnp.exp(Gs).reshape(b, Cn, G, H // G)
        y = jnp.einsum("bgkij,bjgkp->bigkp", m, xdt, precision=hi) \
            + eG[..., None] * jnp.einsum("bign,bgkpn->bigkp", C_c, S0g,
                                         precision=hi)
        tail = jnp.exp(Gs[:, -1:] - Gs).reshape(b, Cn, G, H // G)  # <= 1
        S = eG[:, -1, :, :, None, None] * S0g + jnp.einsum(
            "bjgkp,bjgn->bgkpn", xdt * tail[..., None], B_c, precision=hi)
        return S.reshape(S0.shape), y.reshape(b, Cn, H, P)

    state, y = jax.lax.scan(one, state, tuple(
        blocks(a) for a in (x, dt, g, B, C)))
    return jnp.moveaxis(y, 0, 1).reshape(b, n * Cn, H, P)[:, :t], state


def supported(state, t: int) -> bool:
    """Whether the decode kernel runs this call: one token a row, a TPU,
    float32 tiles of whole (8, 128) vregs."""
    P, N = state.shape[-2:]
    return (t == 1 and jax.default_backend() == "tpu"
            and state.dtype == jnp.float32 and P % 8 == 0 and N % 128 == 0)


def _step_kernel(layer_ref, a_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref,
                 s_out_ref, *, heads):
    """One (slot, head block): s_ref / s_out_ref [1, 1, heads, P, N] (the
    same HBM tiles), a / xdt / y [1, 1, P, heads] columns, b / c [1, 1, 1,
    N] the block's group's rows."""
    del layer_ref
    B, C = b_ref[0, 0], c_ref[0, 0]                           # [1, N]
    for h in range(heads):
        S = s_ref[0, 0, h] * a_ref[0, 0, :, h:h + 1] \
            + xdt_ref[0, 0, :, h:h + 1] * B
        s_out_ref[0, 0, h] = S
        y_ref[0, 0, :, h:h + 1] = jnp.sum(S * C, axis=1, keepdims=True)


def mamba2_decode_step(xdt, a, B, C, state, layer, live=None,
                       interpret=False):
    """One token of every slot against the WHOLE state array.

    xdt [S, H, P] (dt x), a [S, H] (the decay exp(g)), B, C [S, G, N]
    (float32), state [L, S, H, P, N] float32, layer a scalar int32 (the
    layer's index within the state's layers), ``live`` [S] bool (None:
    every row) -> (y [S, H, P] = S C of the advanced state, the state array
    with layer ``layer`` of the live rows advanced by one token, every
    other tile as it was; the buffer is donated)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, P = xdt.shape
    G, N = B.shape[1:]
    per = H // G                                    # heads of one group
    hb = next(n for n in (_HEAD_BLOCK, 8, 4, 2, 1) if per % n == 0)
    nh = H // hb
    if live is not None:
        a = jnp.where(live[:, None], a, 1.0)
        xdt = jnp.where(live[:, None, None], xdt, 0.0)

    def cols(v):        # [S, H, P] -> [S, nh, P, hb]
        return v.reshape(S, nh, hb, P).transpose(0, 1, 3, 2)

    col = pl.BlockSpec((1, 1, P, hb), lambda s, j, *_: (s, j, 0, 0))
    row = pl.BlockSpec((1, 1, 1, N), lambda s, j, *_: (s, j * hb // per,
                                                        0, 0))
    tiles = pl.BlockSpec((1, 1, hb, P, N),
                         lambda s, j, l: (l[0], s, j, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, nh),
            in_specs=[col, col, row, row, tiles],
            out_specs=[col, tiles]),
        out_shape=[jax.ShapeDtypeStruct((S, nh, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 5 (after the scalar) is the state: updated in place
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      cols(jnp.broadcast_to(a[..., None], xdt.shape)), cols(xdt),
      B[:, :, None, :], C[:, :, None, :], state)
    return y.transpose(0, 1, 3, 2).reshape(S, H, P), state
