"""Kimi Delta Attention (arXiv:2510.26692): the gated delta rule with a
per-channel decay, as the paged serving ops run it.

A head keeps a state S [K, V] in float32. A token with query q, key k
[K] (both L2-normalised by the caller, q scaled by K^-1/2), value v [V],
log-decay g [K] (<= 0) and write strength beta does

    S' = diag(exp(g)) S                 the decay, per key channel
    S  = S' + beta k (v - S'^T k)^T     the delta rule: overwrite what k reads
    o  = S^T q

Three forms of it live here, all float32:

- ``kda_recurrent`` — the recurrence itself, token by token (``lax.scan``):
  the ground truth of the other two and the decode step off the chip.
- ``kda_chunked`` — a prefill chunk, in blocks of ``BLOCK`` tokens, as
  matmuls (the paper's WY / UT-transform form). With G_i the running sum of
  g inside a block and S_0 the state entering it, the pseudo-values
  u_i = beta_i (v_i - (diag(exp g_i) S_{i-1})^T k_i) solve the unit
  lower-triangular system

      (I + diag(beta) A) U = diag(beta) (V - (K . exp G) S_0),
      A[i, j] = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])   (j < i)

  and then O = (Q . exp G) S_0 + B U with B[i, j] = sum_c q_i[c] k_j[c]
  exp(G_i[c] - G_j[c]) (j <= i), S_C = diag(exp G_C) S_0 + (K . exp(G_C -
  G))^T U. A and B are formed with their exponent taken per PAIR (always
  <= 0), never as (K . exp G)(K / exp G)^T: a log-decay of -5 a token over
  64 tokens is exp(320), beyond float32. Blocks follow one another under
  ``lax.scan``; tokens past a row's valid length carry g = 0, beta = 0 and
  leave the state as it is.
- ``kda_decode_step`` — ONE Pallas TPU kernel for the decode tick: a
  (slot, head-group) block loads its S tiles [K, V] once from the whole
  state array [L, slots, H, K, V] at the layer's index (scalar prefetch),
  applies decay, delta update and read-out on the VPU and writes the tiles
  back to the same buffer (``input_output_aliases``): the tick moves the
  state's bytes once each way and nothing else of that size. The vectors a
  tile needs along its sublanes (exp g, k, beta k, q) arrive as COLUMNS
  ([K, heads]: a lane slice broadcasts along the lanes), the value as a
  row. A row that is not live is handed decay 1 and beta 0 and leaves its
  state bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["KERNEL", "kda_chunked", "kda_decode_step", "kda_recurrent",
           "supported"]

#: the decode kernel's call name (``pallas_call(name=...)``): what the
#: benchmark's ``kda_decode_roofline`` tells a call by
KERNEL = "kda_decode_step"
#: tokens of one block of the chunked form
BLOCK = 64
#: heads of one kernel block: 16 x [128, 128] float32 = 1 MB in, 1 MB out
_HEAD_BLOCK = 16


def kda_recurrent(q, k, v, g, beta, state):
    """q, k, g [b, t, H, K], v [b, t, H, V], beta [b, t, H], state [b, H,
    K, V] (all float32) -> (o [b, t, H, V], the state after token t)."""
    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        pred = jnp.einsum("bhkv,bhk->bhv", S, k_t,
                          precision=jax.lax.Precision.HIGHEST)
        S = S + (b_t[..., None] * k_t)[..., None] * (v_t - pred)[..., None, :]
        o = jnp.einsum("bhkv,bhk->bhv", S, q_t,
                       precision=jax.lax.Precision.HIGHEST)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state, o = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def kda_chunked(q, k, v, g, beta, state, block=BLOCK):
    """The same function as ``kda_recurrent`` over a chunk of t tokens, in
    blocks of ``block`` (the chunk is padded to whole blocks with tokens
    that do nothing: g = 0, beta = 0)."""
    b, t, H, K = q.shape
    C = min(block, t)
    pad = -t % C
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
            a.ndim - 2)) for a in (q, k, v, g, beta))
    n = (t + pad) // C
    hi = jax.lax.Precision.HIGHEST

    def blocks(a):      # [b, n*C, H, ..] -> [n, b, H, C, ..]
        a = a.reshape((b, n, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=jnp.float32)

    def one(S0, x):
        q_c, k_c, v_c, g_c, b_c = x         # [b, H, C, K | V], beta [b, H, C]
        G = jnp.cumsum(g_c, axis=2)
        # pairwise decay exp(G_i - G_j), j <= i: the exponent is taken per
        # pair and held to <= 0 before exp (the masked half is dropped)
        diff = G[:, :, :, None, :] - G[:, :, None, :, :]      # [b,H,C,C,K]
        dec = jnp.exp(jnp.where(lower[:, :, None], diff, -jnp.inf))
        kk = k_c[:, :, None, :, :] * dec                      # k_j exp(..)
        A = jnp.where(strict, jnp.sum(k_c[:, :, :, None, :] * kk, -1), 0.0)
        B = jnp.sum(q_c[:, :, :, None, :] * kk, -1)           # j <= i
        eG = jnp.exp(G)
        rhs = b_c[..., None] * (v_c - jnp.einsum(
            "bhck,bhkv->bhcv", k_c * eG, S0, precision=hi))
        U = jax.scipy.linalg.solve_triangular(
            eye + b_c[..., None] * A, rhs, lower=True, unit_diagonal=True)
        o = jnp.einsum("bhck,bhkv->bhcv", q_c * eG, S0, precision=hi) \
            + jnp.einsum("bhcj,bhjv->bhcv", B, U, precision=hi)
        tail = jnp.exp(G[:, :, -1:, :] - G)                   # <= 1
        S = eG[:, :, -1, :, None] * S0 + jnp.einsum(
            "bhck,bhcv->bhkv", k_c * tail, U, precision=hi)
        return S, o

    state, o = jax.lax.scan(one, state, tuple(
        blocks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)             # [b,n,C,H,V]
    return o.reshape(b, n * C, H, -1)[:, :t], state


def supported(state, t: int) -> bool:
    """Whether the decode kernel runs this call: one token a row, a TPU,
    float32 tiles of whole (8, 128) vregs."""
    K, V = state.shape[-2:]
    return (t == 1 and jax.default_backend() == "tpu"
            and state.dtype == jnp.float32 and K % 8 == 0 and V % 128 == 0)


def _step_kernel(layer_ref, a_ref, k_ref, kb_ref, q_ref, v_ref, s_ref,
                 o_ref, s_out_ref, *, heads):
    """One (slot, head group): s_ref / s_out_ref [1, 1, heads, K, V] (the
    same HBM tiles), a / k / kb / q [1, 1, K, heads] columns, v / o [1, 1,
    heads, V] rows."""
    del layer_ref
    for h in range(heads):
        S = s_ref[0, 0, h] * a_ref[0, 0, :, h:h + 1]          # the decay
        pred = jnp.sum(S * k_ref[0, 0, :, h:h + 1], axis=0, keepdims=True)
        S = S + kb_ref[0, 0, :, h:h + 1] * (v_ref[0, 0, h:h + 1, :] - pred)
        s_out_ref[0, 0, h] = S
        o_ref[0, 0, h:h + 1, :] = jnp.sum(S * q_ref[0, 0, :, h:h + 1],
                                          axis=0, keepdims=True)


def kda_decode_step(q, k, v, g, beta, state, layer, live=None,
                    interpret=False):
    """One token of every slot against the WHOLE state array.

    q, k, g [S, H, K], v [S, H, V], beta [S, H] (float32), state [L, S, H,
    K, V] float32, layer a scalar int32 (the layer's index within the
    state's layers), ``live`` [S] bool (None: every row) -> (o [S, H, V],
    the state array with layer ``layer`` of the live rows advanced by one
    token, every other tile as it was; the buffer is donated)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, K = q.shape
    V = v.shape[-1]
    hb = next(n for n in (_HEAD_BLOCK, 8, 4, 2, 1) if H % n == 0)
    nh = H // hb
    a = jnp.exp(g)
    kb = beta[..., None] * k
    if live is not None:
        a = jnp.where(live[:, None, None], a, 1.0)
        kb = jnp.where(live[:, None, None], kb, 0.0)

    def cols(x):        # [S, H, K] -> [S, nh, K, hb]
        return x.reshape(S, nh, hb, K).transpose(0, 1, 3, 2)

    col = pl.BlockSpec((1, 1, K, hb), lambda s, j, *_: (s, j, 0, 0))
    row = pl.BlockSpec((1, 1, hb, V), lambda s, j, *_: (s, j, 0, 0))
    tiles = pl.BlockSpec((1, 1, hb, K, V),
                         lambda s, j, l: (l[0], s, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S, nh),
            in_specs=[col, col, col, col, row, tiles],
            out_specs=[row, tiles]),
        out_shape=[jax.ShapeDtypeStruct((S, nh, hb, V), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after the scalar) is the state: updated in place
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), cols(a), cols(k),
      cols(kb), cols(q), v.reshape(S, nh, hb, V), state)
    return o.reshape(S, H, V), state
