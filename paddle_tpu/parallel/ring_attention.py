"""Ring attention: exact attention over sequences sharded across devices.

The long-context scaling path (SURVEY.md §5.7: a NEW capability — the
reference has no sequence parallelism of any kind; its long-sequence story
is LoD batching). Sequences are sharded on the time axis over a mesh axis;
each device keeps its local Q shard resident and the K/V shards rotate
around the ring via ``jax.lax.ppermute`` (XLA lowers this to ICI
neighbour-exchange, overlapping the transfer with the local blockwise
attention compute). The online-softmax accumulators (running max m,
denominator l, weighted sum acc) make the result exact — identical to full
attention — while per-device memory stays O(T/n * T/n) per block pair and
peak activation is O(T/n * d).

This is the in-graph-collective replacement for what a CUDA framework would
build from NCCL send/recv (the reference's closest machinery:
/root/reference/paddle/operators/nccl_op.cc, send_op.cc) — here it is one
``shard_map``-ped function XLA can schedule and fuse.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P



def _block_attn(q, k, v, m, l, acc, q_off, k_off, causal, sm_scale):
    """One blockwise-attention accumulation step (online softmax).

    q [b, h, tq, d]; k/v [b, h, tk, d]; m/l [b, h, tq, 1]; acc like q (f32).
    q_off/k_off are the GLOBAL positions of the local shards — causality is
    decided in global coordinates.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sm_scale
    if causal:
        qi = q_off + jnp.arange(q.shape[2])[:, None]
        kj = k_off + jnp.arange(k.shape[2])[None, :]
        s = jnp.where(qi >= kj, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh, seq_axis="sp", causal=False, sm_scale=None):
    """Exact attention with q/k/v sharded on the time axis of ``mesh``.

    q, k, v: [B, H, T, D] global tensors (or already-sharded arrays).
    Returns [B, H, T, D] with the same sequence sharding as q.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[seq_axis]
    T = q.shape[2]
    assert T % n == 0, f"seq len {T} not divisible by ring size {n}"
    shard_t = T // n
    spec = P(None, None, seq_axis, None)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec)
    def ring(ql, kl, vl):
        idx = jax.lax.axis_index(seq_axis)
        q_off = idx * shard_t
        m = jnp.full(ql.shape[:2] + (ql.shape[2], 1), -jnp.inf, jnp.float32)
        l = jnp.zeros_like(m)
        acc = jnp.zeros(ql.shape, jnp.float32)
        # type the carries as device-varying so the fori_loop carry types
        # stay fixed once ppermuted K/V mix in (shard_map vma typing)
        m, l, acc = (jax.lax.pcast(a, (seq_axis,), to="varying")
                     for a in (m, l, acc))
        perm = [(i, (i + 1) % n) for i in range(n)]

        def attend(c, kc, vc, m, l, acc):
            # K/V chunk currently held arrived from device (idx - c) % n
            src = (idx - c) % n
            return _block_attn(ql, kc, vc, m, l, acc, q_off, src * shard_t,
                               causal, sm_scale)

        def step(c, carry):
            kc, vc, m, l, acc = carry
            m, l, acc = attend(c, kc, vc, m, l, acc)
            # rotate K/V around the ring (ICI neighbour exchange)
            kc = jax.lax.ppermute(kc, seq_axis, perm)
            vc = jax.lax.ppermute(vc, seq_axis, perm)
            return (kc, vc, m, l, acc)

        # last chunk attends outside the loop — no wasted final rotation
        kc, vc, m, l, acc = jax.lax.fori_loop(
            0, n - 1, step, (kl, vl, m, l, acc))
        m, l, acc = attend(n - 1, kc, vc, m, l, acc)
        out = acc / jnp.maximum(l, 1e-30)
        return out.astype(ql.dtype)

    if isinstance(q, jax.core.Tracer):
        # inside a jit trace (the executor's whole-block compile): shard_map
        # in_specs tell GSPMD how to reshard; no explicit placement possible
        return ring(q, k, v)
    qs = jax.device_put(q, NamedSharding(mesh, spec)) \
        if not _is_sharded(q) else q
    ks = jax.device_put(k, NamedSharding(mesh, spec)) \
        if not _is_sharded(k) else k
    vs = jax.device_put(v, NamedSharding(mesh, spec)) \
        if not _is_sharded(v) else v
    return ring(qs, ks, vs)


def _is_sharded(x):
    sh = getattr(x, "sharding", None)
    return sh is not None and not getattr(sh, "is_fully_replicated", True)
