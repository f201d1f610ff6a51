"""Vocabulary-parallel fused head + cross-entropy (Megatron-style).

The tensor-parallel sibling of ops/loss_ops.fused_head_cross_entropy:
the head weight [d, vocab] shards its vocab dim over the model axis,
every device runs the chunked online-logsumexp over ITS shard only, and
three tiny per-row collectives (pmax + two psums over [tokens]-sized
vectors) combine the shard statistics — the [tokens, vocab] logits never
materialize on any device AND no device ever holds the whole head.
Backward psums the partial dX over the vocab axis and the shard-local
dW over the data axis. Both directions reuse the serial op's per-chunk
bodies (_fhce_lse_chunk/_fhce_grad_chunk), so the two paths cannot
drift numerically.

The reference's closest analogue is the pserver owning sharded softmax
parameters (/root/reference/paddle/pserver/ParameterServer2.h:94-100);
here the collectives ride ICI in-graph via shard_map.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P



def _axes(mesh, data_axis, vp_axis):
    """(x2 spec, w spec, per-row spec, fori-carry varying axes)."""
    d = data_axis if data_axis in mesh.axis_names else None
    varying = (vp_axis,) + ((d,) if d else ())
    return P(d, None), P(None, vp_axis), P(d), varying


def _shard_local_labels(labl, base, vl):
    """Global labels -> shard-local ids; labels owned by OTHER shards map
    to -1 (never gathered). A bare ``labl - base`` would let a foreign
    label land in the zero-padded tail chunk window [vl, n_chunks*chunk)
    and gather a -inf masked logit, poisoning the psummed loss."""
    return jnp.where((labl >= base) & (labl < base + vl), labl - base, -1)


def vp_fused_head_lse(x2, w, lab, chunk, mesh, vp_axis, data_axis):
    """(global lse [n], global label-logit [n], global row logit-sum [n])
    over a vocab-sharded w."""
    from ..ops.loss_ops import _fhce_chunks, _fhce_lse_chunk, _fhce_w3

    nshard = mesh.shape[vp_axis]
    vocab = w.shape[1]
    if vocab % nshard:
        raise ValueError(
            f"vocab_parallel fused head needs vocab ({vocab}) divisible "
            f"by the {vp_axis!r} axis size ({nshard})")
    vl = vocab // nshard
    chunk_l, n_chunks_l = _fhce_chunks(vl, chunk)
    xs, ws, vs, varying = _axes(mesh, data_axis, vp_axis)

    @functools.partial(shard_map, mesh=mesh, in_specs=(xs, ws, vs),
                       out_specs=(vs, vs, vs))
    def run(x2l, wl, labl):
        base = jax.lax.axis_index(vp_axis) * vl
        lab_l = _shard_local_labels(labl, base, vl)
        w3 = _fhce_w3(wl, chunk_l, n_chunks_l, vl)
        n = x2l.shape[0]
        # carries become device-varying once shard data mixes in
        # (shard_map vma typing) — pcast them up front
        zeros = jnp.zeros((n,), jnp.float32)
        carry = tuple(
            jax.lax.pcast(a, varying, to="varying")
            for a in (jnp.full((n,), -jnp.inf, jnp.float32),
                      zeros, zeros, zeros))
        m, s, ll, rs = jax.lax.fori_loop(
            0, n_chunks_l,
            lambda i, c: _fhce_lse_chunk(x2l, w3, i, chunk_l, vl,
                                         lab_l, c),
            carry)
        lse_l = m + jnp.log(s)
        m_g = jax.lax.pmax(lse_l, vp_axis)
        lse_g = m_g + jnp.log(jax.lax.psum(jnp.exp(lse_l - m_g), vp_axis))
        ll_g = jax.lax.psum(ll, vp_axis)
        rs_g = jax.lax.psum(rs, vp_axis)
        return lse_g, ll_g, rs_g

    return run(x2, w, lab)


def vp_fused_head_grad(x2, w, lab, dl, lse, chunk, mesh, vp_axis,
                       data_axis, smoothing=0.0):
    """(dX [n, d] psummed over vocab shards, dW [d, vocab] shard-local,
    psummed over the data axis)."""
    from ..ops.loss_ops import _fhce_chunks, _fhce_grad_chunk, _fhce_w3

    nshard = mesh.shape[vp_axis]
    vocab = w.shape[1]
    vl = vocab // nshard
    chunk_l, n_chunks_l = _fhce_chunks(vl, chunk)
    xs, ws, vs, varying = _axes(mesh, data_axis, vp_axis)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(xs, ws, vs, vs, vs),
                       out_specs=(xs, ws))
    def run(x2l, wl, labl, dll, lseg):
        base = jax.lax.axis_index(vp_axis) * vl
        lab_l = _shard_local_labels(labl, base, vl)
        w3 = _fhce_w3(wl, chunk_l, n_chunks_l, vl)
        lse2 = lseg[:, None]
        dl2 = dll[:, None]
        d = x2l.shape[1]
        n = x2l.shape[0]

        def body(i, carry):
            dx_acc, dw_acc = carry
            dx_c, dw_c = _fhce_grad_chunk(x2l, w3, i, chunk_l, vl,
                                          lab_l, lse2, dl2,
                                          smoothing=smoothing,
                                          full_vocab=vocab)
            return (dx_acc + dx_c,
                    jax.lax.dynamic_update_index_in_dim(dw_acc, dw_c, i,
                                                        axis=1))

        carry = tuple(
            jax.lax.pcast(a, varying, to="varying")
            for a in (jnp.zeros((n, d), jnp.float32),
                      jnp.zeros((d, n_chunks_l, chunk_l), jnp.float32)))
        dx, dw = jax.lax.fori_loop(0, n_chunks_l, body, carry)
        # dX sums each row's contributions across vocab shards; dW sums
        # each shard's rows across the DATA axis (every dp group saw only
        # its slice of the batch)
        dx = jax.lax.psum(dx, vp_axis)
        if data_axis in mesh.axis_names:
            dw = jax.lax.psum(dw, data_axis)
        dw = dw.reshape(d, n_chunks_l * chunk_l)[:, :vl]
        return dx, dw

    return run(x2, w, lab, dl, lse)
