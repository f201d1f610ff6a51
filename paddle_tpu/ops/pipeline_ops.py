"""Pipelined transformer stack op: L pre-LN blocks with stacked weights.

The layer stack carries every weight with a leading layer axis [L, ...],
which buys two TPU-native wins at once: a single ``lax.scan`` over layers
(one compiled block body instead of L inlined copies — the XLA compile-time
idiom for deep stacks), and pipeline parallelism for free — when the
executor mesh has a ``pp`` axis the same stacked tensors shard their layer
axis across stages and run under the GPipe schedule
(parallel/pipeline.gpipe). The reference's closest machinery places whole
layer ranges on devices by config and moves activations by memcpy
(/root/reference/paddle/gserver/gradientmachines/ParallelNeuralNetwork.cpp);
here placement is a sharding spec and movement is an ICI ppermute.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .. import trace
from ..core.registry import register_op
from ..kernels.flash_attention import (RESIDUAL_NAMES, flash_attention,
                                       flash_attention_packed, lane_block,
                                       rotary)
from ..lm_spec import (DRAFT_PLANES, DRAFT_SLOT_PREFIX, DRAFT_SLOTS,
                       OPTIONAL_STACK_SLOTS, SNAPSHOT_SLOTS, STATE_SLOTS,
                       Block, BlockNotSupportedError)
from .common import amp_cast, maybe, mxu_precision, out, single
from .moe_ops import moe_topk
from .vision_tower import VISION_SLOTS, splice_media, vision_params

_EPS = 1e-5
#: what the train stack's layer checkpoint saves a layer under
#: ``remat=True``, beside the stream the scan carries: the flash call's own
#: residuals (its operands, result and logsumexp) and the out-projection's
#: result, tagged before its upcast (``_mm``), so under AMP each saved
#: plane is the bf16 value the step rounds to anyway: 5 d a token. From
#: these and the stream the backward rebuilds the attention half
#: elementwise and runs ONE forward matmul again, the FFN's first (its
#: result before bias and GELU is 4 d a token more and did not fit GPT-2
#: medium at 8 x 1024 on a v5e beside the rest: PERF.md section 5 has
#: the table of sets tried). An expert layer is recomputed likewise.
_STACK_SAVED = RESIDUAL_NAMES + ("attn_out",)
_LM_OPTIONAL = ("PosEmb", "FinalLnB") + OPTIONAL_STACK_SLOTS


def _ln(x, scale, bias, eps=_EPS):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rms(x, scale, eps):
    """RMSNorm over the last axis, float32 whatever the stored dtype of
    its weight: u * rsqrt(mean(u^2) + eps) * w."""
    u = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(u), axis=-1, keepdims=True)
    return (u * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def _norm(blk, x, scale, bias=None):
    """The spec's norm over the last axis."""
    if blk.norm == "rms_norm":
        return _rms(x, scale, blk.norm_eps)
    if bias is None:
        bias = jnp.zeros((), x.dtype)
    return _ln(x, scale, bias, blk.norm_eps)


def _mm(blk, eq, a, w, name=None):
    """``einsum(eq, a, w)`` back in a.dtype (the float32 residual
    stream); ``name``: the ``checkpoint_name`` of the contraction's own
    result, before that upcast (``_STACK_SAVED``). float32 weights: bf16
    operands under AMP and a result rounded through bf16
    (``preferred_element_type`` None), as the GPT-2 block always did. Weights STORED in bf16 are used as they are with float32
    accumulation (no float32 copy of a stored-bf16 weight is ever made on
    the device; without AMP jnp promotes inside the contraction). Which of
    the two rules holds is decided by the dtype the weight is STATED in,
    not by the operand's: ``blk.param_dtype`` where the program states
    one (a serving engine hands the bf16 copy ``amp_cast`` would make of a
    float32 weight, ``GenerationEngine._adopt_scope``, and the result is
    the float32 weight's to the last bit), else ``w.dtype`` (the operand
    IS the weight)."""
    a_c, w_c = amp_cast(a, w)
    stated = jnp.dtype(blk.param_dtype) if blk.param_dtype else w.dtype
    pref = jnp.float32 if stated == jnp.bfloat16 else None
    y = jnp.einsum(eq, a_c, w_c, precision=mxu_precision(),
                   preferred_element_type=pref)
    if name is not None:
        y = checkpoint_name(y, name)
    return y.astype(a.dtype)


def _stack_params(blk, ins):
    """The block's stacked weights by key, from the op's input slots."""
    # slots: "Ln1S" "Ln1B" "QkvW" "QNormS" "KNormS" "OutW" "Ln2S" "Ln2B"
    # "FfW1" "FfB1" "FfW2" "FfB2" "RouterW" "MoeGateW" "MoeUpW" "MoeDownW"
    return {key: single(ins, slot)
            for slot, key in blk.stack_slots().items()}


def _block(blk, p, x, causal, rope=None):
    """One block of the spec; p holds per-layer (no leading dim) weights
    under the keys of ``blk.stack_slots()``. -> (x, stats): stats is None
    for a dense FFN, (counts [E], router prob mean [E]) for experts.
    ``rope``: whether THIS layer rotates q / k (a ``layer_pattern``;
    None: as ``blk.use_rope`` says). A window layer is the causal block
    here: the callers hold T to the window. Heads the kernels have a lane
    block for (``lane_block``: a width that divides or is divided by 128)
    stay PACKED on the minor axis from the qkv projection's row to the
    out-projection's operand: no [b, H, T, dh] array, no transpose."""
    b, T, d = x.shape
    packed = (not blk.is_mla
              and lane_block(blk.num_heads, blk.dh(d)) is not None)
    q, k, v = _attn_proj(blk, p, x, rope=rope, heads_first=not packed)
    k, v = _expand_kv(k, v, blk.num_heads, axis=2 if packed else 1)
    if packed:
        ctx = flash_attention_packed(
            *(a.reshape(b, T, -1) for a in (q, k, v)), blk.num_heads,
            causal=causal)
    else:
        ctx = flash_attention(q, k, v, causal=causal,
                              sm_scale=_sm_scale(blk))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, T, -1)
    return _attn_out_ffn(blk, p, x, ctx)


def _scan_stack(kinds, body, carry, xs):
    """``lax.scan`` of ``body(carry, x_l, kind) -> (carry, y_l)`` over the
    stack's layers (every leaf of ``xs`` leads with L); ``kind`` is the
    layer's (windowed, rotates). A stack of one kind is one scan over L
    with ``kind`` None. Under a ``layer_pattern``
    the scan runs over PERIODS with the period's layers unrolled in its
    body (stacks viewed [L/p, p, ...]), so each position of the period is
    traced with its own kind and nothing is unrolled L times; the ys come
    back stacked [L, ...] either way. ``kinds``: the block's (``None``:
    one kind)."""
    if kinds is None:
        return jax.lax.scan(lambda c, x_l: body(c, x_l, None), carry, xs)
    p = len(kinds)
    tmap = jax.tree_util.tree_map

    def period(c, x_p):
        ys = []
        for j, kind in enumerate(kinds):
            c, y = body(c, tmap(lambda a: a[j], x_p), kind)
            ys.append(y)
        return c, tmap(lambda *a: jnp.stack(a), *ys)

    carry, ys = jax.lax.scan(period, carry, tmap(
        lambda a: a.reshape((a.shape[0] // p, p) + a.shape[1:]), xs))
    return carry, tmap(lambda a: a.reshape((-1,) + a.shape[2:]), ys)


def _require_uniform_planes(blk, who):
    """The one-scan ops slice EVERY plane by the layer's index."""
    if blk.rope == "mrope" or blk.sparse_kv or blk.vision_heads:
        raise BlockNotSupportedError(
            f"{who} embeds token ids, rotates by ONE position axis and "
            "attends every key; this spec brings "
            + ", ".join(w for w, on in (
                ("three-axis rotary ids (rope='mrope')", blk.rope == "mrope"),
                ("learned sparse attention over K/V pages (index_topk)",
                 blk.sparse_kv),
                ("a vision tower (vision)", blk.vision_heads)) if on)
            + ": the paged prefill / decode ops behind GenerationEngine / "
            "Server run it")
    if blk.first_dense and not blk.attn_kinds:
        raise BlockNotSupportedError(
            f"{who} scans planes that all lead with the layer axis; this "
            f"stack's first {blk.first_dense} layer(s) run a dense FFN whose "
            "planes lead with their own count (and the experts' with the "
            "rest): the paged prefill / decode ops run it")


def _hold_to_window(blk, T, who):
    """The dense attention paths run a window layer as a causal one."""
    if blk.has_window and T > blk.window:
        raise BlockNotSupportedError(
            f"{who} over {T} tokens with window layers of {blk.window}: "
            "its dense attention has no window mask; the paged prefill / "
            "decode ops serve longer contexts")


def _attn_proj(blk, p, h, pos0=0, rope=None, heads_first=True):
    """norm 1 + qkv projection -> q [b, H, t, dh], k/v [b, Hkv, t, dh]
    (``heads_first`` False: [b, t, H, dh] and [b, t, Hkv, dh], the views
    of the projection's row as it is, nothing transposed).
    Hkv < H is grouped-query attention: the stacked qkv weight is
    [L, d, d + 2*Hkv*dh] and the KV planes (and decode caches) shrink by
    H/Hkv. ``qk_norm``: RMSNorm over the WHOLE q and k vectors before the
    head split. RoPE rotates q/k at absolute positions pos0..pos0+t-1
    (rotated keys enter the decode cache, so cached rows never re-rotate);
    ``rope`` overrides ``blk.use_rope`` for one layer of a pattern. The
    head width is ``blk.head_dim`` when the spec states one (H*dh need
    not be d: qkv_w is [d, H*dh + 2*Hkv*dh])."""
    if blk.is_mla:
        q_nope, q_rope, c_kv, k_rope = _mla_latent(blk, p, h, pos0)
        k, v = _mla_expand(blk, p, c_kv, k_rope)
        return jnp.concatenate([q_nope, q_rope], axis=-1), k, v
    num_heads, num_kv_heads = blk.num_heads, blk.kv_heads
    b, t, d = h.shape
    head_d = blk.dh(d)
    d_q, d_kv = head_d * num_heads, head_d * num_kv_heads
    hn = _norm(blk, h, p["ln1_s"], p.get("ln1_b"))
    qkv = _mm(blk, "btd,de->bte", hn, p["qkv_w"])
    q = qkv[..., :d_q]
    k = qkv[..., d_q:d_q + d_kv]
    v = qkv[..., d_q + d_kv:]
    if blk.qk_norm and not blk.qk_norm_heads:
        q = _rms(q, p["q_norm_s"], blk.norm_eps)
        k = _rms(k, p["k_norm_s"], blk.norm_eps)

    def heads(a, n, scale=None):
        a = a.reshape(b, t, n, head_d)
        if scale is not None:       # RMSNorm a head, one scale of head_d
            a = _rms(a, scale, blk.norm_eps)
        return a.transpose(0, 2, 1, 3) if heads_first else a

    per_head = blk.qk_norm and blk.qk_norm_heads
    q, k, v = (heads(q, num_heads, p["q_norm_s"] if per_head else None),
               heads(k, num_kv_heads, p["k_norm_s"] if per_head else None),
               heads(v, num_kv_heads))
    if blk.rope == "mrope":
        # pos0: the tokens' (temporal, height, width) ids [b, t, 3]
        time_axis = 2 if heads_first else 1
        q = _mrope(blk, q, pos0, time_axis)
        k = _mrope(blk, k, pos0, time_axis)
    elif blk.use_rope if rope is None else rope:
        time_axis = 2 if heads_first else 1
        q = rotary(q, pos0, blk.rope_theta, blk.rope_pairing,
                   time_axis=time_axis)
        k = rotary(k, pos0, blk.rope_theta, blk.rope_pairing,
                   time_axis=time_axis)
    return q, k, v


def _mrope(blk, x, ids, time_axis=2):
    """Three-axis rotary (``Block.rope`` "mrope") of heads x [b, H, t, dh]
    (``time_axis`` 1: [b, t, H, dh]) by the tokens' ids [b, t, 3]: half-split
    pairs, pair i turning by the id of the axis ``mrope_section`` gives it
    (the first section[0] pairs the temporal id, ..) times theta^(-i / half)."""
    half = x.shape[-1] // 2
    inv = blk.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    axis = jnp.asarray([a for a, n in enumerate(blk.mrope_section)
                        for _ in range(n)], jnp.int32)
    ang = jnp.take(ids.astype(jnp.float32), axis, axis=-1) * inv  # [b,t,half]
    other = 3 - time_axis
    cos = jnp.expand_dims(jnp.cos(ang), other).astype(x.dtype)
    sin = jnp.expand_dims(jnp.sin(ang), other).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def _sm_scale(blk):
    """The attention's softmax scale where it is not 1/sqrt(head width):
    a latent block's ``(nope + rope)^-0.5 * m^2`` (``RopeScaling.
    softmax_mscale``); None: the default."""
    if not blk.is_mla:
        return None
    scale = (blk.qk_nope_head_dim + blk.qk_rope_head_dim) ** -0.5
    if blk.rope_scaling is not None:
        scale *= blk.rope_scaling.softmax_mscale
    return scale


def _mla_latent(blk, p, h, pos0=0):
    """The latent block's projections of h [b, t, d] at positions pos0 ..
    (a scalar or [b]) -> q_nope [b, H, t, nope], q_rope [b, H, t, rope]
    (rotated), c_kv [b, t, r] (after its norm) and k_rope [b, t, rope]
    (rotated: ONE rotary key a token, shared by all heads) — [c_kv |
    k_rope] is the token's cache row. Both query parts carry the
    position's temperature ``a(i) = 1 + temp_beta * ln(1 + floor(i /
    original_max))`` (1 below ``original_max``)."""
    b, t, _ = h.shape
    H, nope, rope = blk.num_heads, blk.qk_nope_head_dim, blk.qk_rope_head_dim
    r, sc = blk.kv_lora_rank, blk.rope_scaling
    hn = _norm(blk, h, p["ln1_s"], p.get("ln1_b"))
    if blk.q_lora_rank:
        c_q = _rms(_mm(blk, "btd,dr->btr", hn, p["q_a_w"]), p["q_a_norm_s"],
                   blk.norm_eps)
        q = _mm(blk, "btr,re->bte", c_q, p["q_b_w"])
    else:                               # a full-rank query: no bottleneck
        q = _mm(blk, "btd,de->bte", hn, p["q_w"])
    q = q.reshape(b, t, H, nope + rope).transpose(0, 2, 1, 3)
    kv_a = _mm(blk, "btd,de->bte", hn, p["kv_a_w"])
    c_kv = _rms(kv_a[..., :r], p["kv_a_norm_s"], blk.norm_eps)

    def rot(a):
        return rotary(a, pos0, blk.rope_theta, blk.rope_pairing, scaling=sc)

    if rope:
        q_nope, q_rope = q[..., :nope], rot(q[..., nope:])
        k_rope = rot(kv_a[:, None, :, r:])[:, 0]
    else:                   # NoPE: the latent is the whole row
        q_nope, q_rope, k_rope = q, q[..., nope:], kv_a[..., r:]
    if sc is not None and sc.temp_beta:
        pos = (jnp.asarray(pos0, jnp.int32).reshape(-1, 1)
               + jnp.arange(t, dtype=jnp.int32)[None, :])       # [b | 1, t]
        a = 1.0 + sc.temp_beta * jnp.log1p(
            (pos // sc.original_max).astype(jnp.float32))
        q_nope, q_rope = (q_nope * a[:, None, :, None],
                          q_rope * a[:, None, :, None])
    return q_nope, q_rope, c_kv, k_rope


def _mla_up(blk, p):
    """kv_b_w [r, H * (nope + dv)] as (W_UK [r, H, nope], W_UV
    [r, H, dv])."""
    w = p["kv_b_w"].reshape(blk.kv_lora_rank, blk.num_heads, -1)
    return w[..., :blk.qk_nope_head_dim], w[..., blk.qk_nope_head_dim:]


def _mla_expand(blk, p, c_kv, k_rope):
    """Keys and values of every head from the latent: c_kv [b, T, r],
    k_rope [b, T, rope] -> k [b, H, T, nope + rope], v [b, H, T, dv]."""
    w_uk, w_uv = _mla_up(blk, p)
    k_nope = _mm(blk, "btr,rhn->bhtn", c_kv, w_uk)
    v = _mm(blk, "btr,rhv->bhtv", c_kv, w_uv)
    k_r = jnp.broadcast_to(k_rope[:, None].astype(k_nope.dtype),
                           k_nope.shape[:3] + k_rope.shape[-1:])
    return jnp.concatenate([k_nope, k_r], axis=-1), v


def _expand_kv(k, v, num_heads, axis=1):
    """Broadcast Hkv heads (on ``axis``) to their H/Hkv query groups."""
    rep = num_heads // k.shape[axis]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=axis)
        v = jnp.repeat(v, rep, axis=axis)
    return k, v


def _res_read(blk, p, x, half):
    """A half block's input from the residual carry, and what its output
    is written back with (``_res_write``). ``residual="add"``: the carry
    [b, t, d] itself and None. ``"mhc"`` (manifold-constrained
    hyper-connections, arXiv:2512.24880): the carry is X [b, t, n, d]
    float32; from x~ = RMSNorm(vec X) (no scale, eps ``hc_eps``) and the
    half's planes ``<half>_w`` [n d, n | n | n n], ``<half>_alpha`` [3],
    ``<half>_b``: H_pre = sigmoid(.) [n], H_post = 2 sigmoid(.) [n], H_res =
    ``hc_iters`` Sinkhorn rounds (rows, then columns, each sum + ``hc_eps``)
    of exp(.) [n, n] -> (sum_i H_pre[i] X[i], (H_post, H_res)), all float32."""
    if blk.residual != "mhc":
        return x, None
    b, t, n, d = x.shape
    f32 = jnp.float32
    v = x.reshape(b, t, n * d)
    v = v * jax.lax.rsqrt(jnp.mean(jnp.square(v), axis=-1, keepdims=True)
                          + blk.hc_eps)
    z = jnp.einsum("btk,km->btm", v, p[half + "_w"].astype(f32),
                   precision=jax.lax.Precision.HIGHEST)
    alpha, bias = p[half + "_alpha"].astype(f32), p[half + "_b"].astype(f32)
    pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + bias[n:2 * n])
    res = jnp.exp(alpha[2] * z[..., 2 * n:].reshape(b, t, n, n)
                  + bias[2 * n:].reshape(n, n))
    for _ in range(blk.hc_iters):
        res = res / (jnp.sum(res, axis=-1, keepdims=True) + blk.hc_eps)
        res = res / (jnp.sum(res, axis=-2, keepdims=True) + blk.hc_eps)
    return jnp.einsum("btn,btnd->btd", pre, x), (post, res)


def _res_write(x, y, mix):
    """The carry after a half block whose output is y [b, t, d]: ``x + y``,
    or (``mix`` = (H_post, H_res) of ``_res_read``) X[i] <- sum_j H_res[i, j]
    X[j] + H_post[i] y."""
    if mix is None:
        return x + y
    post, res = mix
    return (jnp.einsum("btij,btjd->btid", res, x)
            + post[..., None] * y[:, :, None, :].astype(x.dtype))


def _attn_out_ffn(blk, p, x, ctx, out_key="out_w", dense=False, mixer=True,
                  ffn=True, mix=None):
    """Out-projection + residual + FFN half of a block; x [b, t, d] the
    block's input, ctx [b, t, H*dh]. -> (x, stats), stats as ``_block``
    says. A block whose router reads the attention's input
    (``router_input="attn_input"``) routes from norm 1 of ``x``: the same
    expression the attention half computed, which XLA shares. A stack held
    by kind names the layer's out-projection (``out_key``) and whether it
    is one of the leading ``dense`` SwiGLU layers; a position of its
    pattern that is HALF a block leaves the other half out (``mixer``
    False: no out-projection, ctx None; ``ffn`` False: the mixer's
    residual alone, stats None). Both halves go through the residual
    pair (``_res_read`` / ``_res_write``: x is the CARRY, [b, t, n, d] under
    ``residual="mhc"``, and ``mix`` what the mixer half's read left to write
    its output back with); ``ffn_limit`` clamps the gated feed-forwards."""
    early = blk.is_moe and blk.router_input == "attn_input"
    router_x = _norm(blk, x, p["ln1_s"], p.get("ln1_b")) if early else None
    if mixer:
        x = _res_write(x, _mm(blk, "btd,de->bte", ctx.astype(x.dtype),
                              p[out_key], name="attn_out"), mix)
    if not ffn:
        return x, None
    u, mix = _res_read(blk, p, x, "hc2")
    h2 = _norm(blk, u, p["ln2_s"], p.get("ln2_b"))
    lim = blk.ffn_limit
    if dense:
        gate = _mm(blk, "btd,df->btf", h2, p["dense_gate_w"])
        gate = jax.nn.silu(jnp.minimum(gate, lim) if lim else gate)
        up = _mm(blk, "btd,df->btf", h2, p["dense_up_w"])
        ff = gate * (jnp.clip(up, -lim, lim) if lim else up)
        return _res_write(x, _mm(blk, "btf,fd->btd", ff, p["dense_down_w"]),
                          mix), None
    if blk.is_moe:
        b, t, d = u.shape
        more = {}
        if early:
            more["router_x"] = router_x.reshape(b * t, d)
        if blk.expert_act != "silu":
            more["act"] = blk.expert_act
        if blk.shared_expert:
            more["shared"] = (p.get("shared_gate_w"), p["shared_up_w"],
                              p["shared_down_w"])
        if blk.expert_latent:
            more["latent"] = (p["moe_latent_down_w"], p["moe_latent_up_w"])
        if blk.experts_held is not None:
            more["held"] = blk.experts_held
        if blk.routed_scale != 1.0:
            more["routed_scale"] = blk.routed_scale
        if blk.router_score != "softmax":
            more["score"] = blk.router_score
        if blk.router_bias:
            more["bias"] = p["router_b"]
        if blk.n_group > 1:
            more.update(n_group=blk.n_group, topk_group=blk.topk_group)
        if lim:
            more["limit"] = lim
        y, counts, prob_mean = moe_topk(
            h2.reshape(b * t, d), p["router_w"], p.get("moe_gate_w"),
            p["moe_up_w"], p["moe_down_w"], blk.experts_per_tok,
            blk.norm_topk_prob, layer=p.get("layer"), **more)
        return _res_write(x, y.reshape(b, t, d), mix), (counts, prob_mean)
    ff = _mm(blk, "btd,df->btf", h2, p["ff_w1"])
    if blk.bias:
        ff = ff + p["ff_b1"]
    ff = _mm(blk, "btf,fd->btd", jax.nn.gelu(ff), p["ff_w2"])
    if blk.bias:
        ff = ff + p["ff_b2"]
    return x + ff, None


def _saved_bytes(body, carry, layer_p):
    """Bytes of the planes one layer's backward holds of its forward
    beside its arguments (the stream and the layer's weights): what the
    layer checkpoint saves, as JAX reports it, traced abstractly."""
    held = jax.eval_shape(lambda c, p: jax.vjp(body, c, p)[1], carry, layer_p)
    args = collections.Counter(
        (a.shape, a.dtype)
        for a in jax.tree_util.tree_leaves((carry, layer_p)))
    saved = 0
    for a in jax.tree_util.tree_leaves(held):
        if args[a.shape, a.dtype]:      # an argument read again
            args[a.shape, a.dtype] -= 1
        else:
            saved += a.size * a.dtype.itemsize
    return saved


# the GPT-2 block's ten planes (the seq2seq family's encoder / decoder
# stacks are this block under their own slot prefixes)
_STACK_SLOTS = Block(num_heads=1).stack_slots()


def _aux_loss(blk, stats, n_experts):
    """Load-balance loss of the scanned layers' (counts [L, E], router
    prob mean [L, E]): sum over layers of E * sum_e f_e * P_e, f_e the
    share of the layer's assignments sent to expert e (a constant of the
    backward pass), P_e the mean router probability."""
    counts, prob_mean = stats
    f = counts.astype(jnp.float32) / jnp.sum(
        counts, axis=-1, keepdims=True).astype(jnp.float32)
    return n_experts * jnp.sum(jax.lax.stop_gradient(f) * prob_mean)


@register_op("pipelined_transformer_stack", has_loop=True,
             optional_inputs=OPTIONAL_STACK_SLOTS)
def pipelined_transformer_stack(attrs, ins):
    """X [b, T, d] + stacked block weights (leading dim L) -> Out [b, T, d]
    (and, for a ``swiglu_moe`` block, AuxLoss [1]: the load-balance loss
    summed over layers, see ``_aux_loss``).

    attrs: the block (``lm_spec.Block.attrs()``), causal, n_microbatches,
    remat. With a ``pp`` mesh axis the stack runs the GPipe schedule
    (layer axis sharded into stages, each stage scanning its local L/S
    layers); otherwise one scan over all L.
    """
    from ..parallel.context import current_mesh, mesh_axis

    x = single(ins, "X")
    blk = Block.from_attrs(attrs)
    # optional stack slots (a block leaves out what it has no use for),
    # read via _stack_params: "Ln1B" "Ln2B" "QNormS" "KNormS" "FfW1"
    # "FfB1" "FfW2" "FfB2" "RouterW" "MoeGateW" "MoeUpW" "MoeDownW"
    # "QkvW" | "QaW" "QaNormS" "QbW" "KvaW" "KvaNormS" "KvbW";
    # "SharedGateW" "SharedUpW" "SharedDownW"
    # a stack held by attention kind (``Block._slots_by_kind``): "OutW"
    # "QW" "AttnGateW" "RouterB" "KdaQkvW" "KdaConvW" "KdaAW" "KdaDtBias"
    # "KdaALog" "KdaBetaW" "KdaGateW" "KdaNormS" "KdaOutW" "DenseGateW"
    # "DenseUpW" "DenseDownW" "KdaADownW" "KdaAUpW" "KdaGateDownW"
    # "KdaGateUpW" "KdaGateB" "GqaQkvW" "GqaGateW" "GqaOutW"
    # "MambaInW" "MambaConvW" "MambaConvB" "MambaDtBias" "MambaALog" "MambaD"
    # "MambaNormS" "MambaOutW" "MoeLatentDownW" "MoeLatentUpW"
    # a sparse latent layer's indexer and the residual streams' mixes:
    # "IdxQW" "IdxKW" "IdxKNormS" "IdxKNormB" "IdxHeadW" "Hc1W" "Hc1Alpha"
    # "Hc1B" "Hc2W" "Hc2Alpha" "Hc2B"
    params = _stack_params(blk, ins)
    causal = attrs.get("causal", True)

    remat = attrs.get("remat", False)
    if remat not in (False, True, "full"):
        raise ValueError(f"remat {remat!r}: False (save everything), True "
                         "(the stream and _STACK_SAVED) or 'full' (the "
                         "stream only)")
    if blk.is_mla or blk.experts_held is not None or blk.attn_kinds:
        raise BlockNotSupportedError(
            "pipelined_transformer_stack (training) was never held to a "
            "reference for latent attention, a held share of the experts "
            "or a stack held by attention kind with a recurrent state (no "
            "gradient test exists for any): the paged prefill / decode ops "
            "run this spec")

    _require_uniform_planes(blk, "pipelined_transformer_stack (training)")
    _hold_to_window(blk, x.shape[1], "pipelined_transformer_stack")

    def scan_stats(p, h):
        def body(carry, layer_p, kind):
            return _block(blk, layer_p, carry, causal, kind and kind[1])

        if remat:
            # "full" (no policy): the stream only, the backward runs the
            # whole layer forward again (a model whose saved set does not
            # fit); True: the stream and _STACK_SAVED
            body = jax.checkpoint(
                body, static_argnums=(2,), policy=None if remat == "full"
                else jax.checkpoint_policies.save_only_these_names(
                    *_STACK_SAVED))
        scanned = _scan_stack(blk.kinds, body, h, p)
        span = trace.current_span()
        if span is not None:
            # gauge: the saved planes' bytes over the scanned layers. AFTER
            # the scan: the body's traced form is cached with the source
            # locations of whoever traces it first, and they reach the
            # compile cache's key through the Mosaic calls
            layer_p = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), p)
            kind = blk.kinds[0] if blk.kinds else None
            span.set_attr("mem/stack_saved_bytes",
                          p["ln1_s"].shape[0] * _saved_bytes(
                              lambda c, lp: body(c, lp, kind)[0], h, layer_p))
        return scanned

    def scan_layers(p, h):
        return scan_stats(p, h)[0]

    pipe_axis = attrs.get("pipe_axis") or "pp"
    pp = mesh_axis(pipe_axis)
    L = params["ln1_s"].shape[0]
    if pp > 1:
        from ..parallel.pipeline import gpipe

        if blk.is_moe:
            raise BlockNotSupportedError(
                "a swiglu_moe stack under a pp mesh axis: the GPipe "
                "schedule carries no per-layer router statistics")
        blk.require_one_kind("a pp pipeline (stages of whole layers)")
        blk.require_no_draft("a pp pipeline (stages of whole layers)")
        if L % pp:
            raise ValueError(
                f"{L} layers not divisible by pipeline size {pp}")
        mesh = current_mesh()
        data_axis = attrs.get("data_axis") or "dp"
        if data_axis not in mesh.axis_names:
            data_axis = None
        y = gpipe(scan_layers, params, x, mesh, axis=pipe_axis,
                  n_microbatches=attrs.get("n_microbatches") or pp,
                  data_axis=data_axis)
        return out(Out=y)
    y, stats = scan_stats(params, x)
    if blk.is_moe:
        aux = _aux_loss(blk, stats, params["router_w"].shape[-1])
        return out(Out=y, AuxLoss=aux.reshape(1))
    return out(Out=y)


def _unpack_lm_ins(blk, ins):
    """Shared input unpacking for the decode ops: (prompt, embeddings,
    final norm, head, stacked block params). PosEmb is absent under RoPE
    (rotation replaces the learned table), FinalLnB under RMSNorm."""
    return (single(ins, "Prompt"), single(ins, "TokEmb"),
            maybe(ins, "PosEmb"), single(ins, "FinalLnS"),
            maybe(ins, "FinalLnB"), single(ins, "HeadW"),
            _stack_params(blk, ins))


def _embed_rows(tok_emb, ids):
    """Embedding rows as the float32 residual stream (a table stored in
    bf16 is read as it is; only the gathered rows are upcast)."""
    return tok_emb[ids].astype(jnp.float32)


def _embed_fn(tok_emb, pos_emb):
    def embed(ids, pos0):
        if pos_emb is None:  # RoPE: positions live in the attention rotation
            return _embed_rows(tok_emb, ids)
        t = ids.shape[1]
        return (_embed_rows(tok_emb, ids)
                + jax.lax.dynamic_slice_in_dim(pos_emb, pos0, t, 0)[None])

    return embed


def _logits_fn(ln_s, ln_b, head_w, blk=Block(num_heads=1)):
    def logits_of(h_last):
        hn = _norm(blk, h_last, ln_s, ln_b)
        return _mm(blk, "bd,dv->bv", hn, head_w).astype(jnp.float32)

    return logits_of


def _prefill(blk, params, x, b, Tp):
    """Run the stack over the prompt capturing every layer's K/V:
    returns (hidden [b, Tp, d], ks, vs [L, b, Hkv, Tp, dh]) — the caches
    hold KV heads only (the GQA memory win). Under RoPE the cached keys
    are already rotated at their absolute positions."""
    def prefill_body(h, layer_p, kind=None):
        q, k, v = _attn_proj(blk, layer_p, h, rope=kind and kind[1])
        kx, vx = _expand_kv(k, v, blk.num_heads)
        ctx = flash_attention(q, kx, vx, causal=True,
                              sm_scale=_sm_scale(blk))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, Tp, -1)
        return _attn_out_ffn(blk, layer_p, h, ctx)[0], (k, v)

    return _scan_stack(blk.kinds, prefill_body, x, params)


def _decode_layer_fn(blk, params, d):
    """One-token decode through all layers against the cache; returns a
    fn(h1, (layer_p, ck_l, cv_l), pos, kind) for ``_scan_stack`` (pos =
    the query's position; cache rows < pos+1 are visible, on a window
    layer the last ``blk.window`` of them). Caches store Hkv heads;
    queries expand to their groups at attention time."""
    from ..kernels.flash_attention import reference_attention

    def layer(h1, inp, pos, kind=None):
        layer_p, ck_l, cv_l = inp
        windowed, rope = kind or (False, None)
        more = dict(window=blk.window) if windowed else {}
        q, k, v = _attn_proj(blk, layer_p, h1, pos0=pos, rope=rope)
        ck_l = jax.lax.dynamic_update_slice_in_dim(ck_l, k, pos, 2)
        cv_l = jax.lax.dynamic_update_slice_in_dim(cv_l, v, pos, 2)
        # reference_attention reads the Hkv cache natively (grouped
        # einsum) — no [b, H, T, dh] expansion on the decode hot path
        ctx = reference_attention(
            q, ck_l, cv_l, lengths=jnp.full((h1.shape[0],), pos + 1),
            sm_scale=_sm_scale(blk), **more)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(h1.shape[0], 1, -1)
        return _attn_out_ffn(blk, layer_p, h1, ctx)[0], (ck_l, cv_l)

    return layer


def _make_pick(temperature, top_k, vocab, rng):
    """Next-token selection shared by the decode ops: argmax when
    ``temperature`` == 0 (draws nothing — the op's needs_rng predicate
    keeps the scope RNG untouched), otherwise temperature/top-k sampling
    folding ``step`` into the rng so every call draws fresh."""
    if top_k and not 0 < top_k <= vocab:
        raise ValueError(f"top_k {top_k} outside [1, vocab {vocab}]")

    def pick(logits, step):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        z = logits
        if top_k:
            kth = jax.lax.top_k(z, top_k)[0][:, -1:]
            z = jnp.where(z >= kth, z, -jnp.inf)
        return jax.random.categorical(jax.random.fold_in(rng, step),
                                      z / temperature, axis=-1)

    return pick


@register_op("transformer_stack_generate", optional_inputs=_LM_OPTIONAL,
             needs_rng=lambda attrs: (attrs.get("temperature") or 0) > 0)
def transformer_stack_generate(attrs, ins, rng):
    """Incremental decoding with a per-layer KV cache.

    Prompt [b, Tp] int + the stacked block weights + TokEmb [V, d],
    PosEmb [maxlen, d], FinalLnS/FinalLnB [d], HeadW [d, V]
    -> Out [b, Tp + max_new_tokens] int.

    The serving path the training stack earns: prefill runs the blocks
    once over the prompt while capturing every layer's K/V; the decode
    loop is a lax.scan over steps — one token embeds, attends against the
    cache (position-masked), appends its K/V, and the next id comes from
    argmax (temperature attr == 0) or temperature/top-k sampling through
    the executor's RNG plane. O(T) work per token instead of O(T^2)
    re-forwarding; everything static-shaped for XLA (the cache is
    preallocated at Tp + N).
    """
    blk = Block.from_attrs(attrs)
    # optional stack slots (a block leaves out what it has no use for),
    # read via _stack_params: "Ln1B" "Ln2B" "QNormS" "KNormS" "FfW1"
    # "FfB1" "FfW2" "FfB2" "RouterW" "MoeGateW" "MoeUpW" "MoeDownW"
    # "QkvW" | "QaW" "QaNormS" "QbW" "KvaW" "KvaNormS" "KvbW";
    # "SharedGateW" "SharedUpW" "SharedDownW"
    # and "PosEmb" "FinalLnB" via _unpack_lm_ins
    # a stack held by attention kind (``Block._slots_by_kind``): "OutW"
    # "QW" "AttnGateW" "RouterB" "KdaQkvW" "KdaConvW" "KdaAW" "KdaDtBias"
    # "KdaALog" "KdaBetaW" "KdaGateW" "KdaNormS" "KdaOutW" "DenseGateW"
    # "DenseUpW" "DenseDownW" "KdaADownW" "KdaAUpW" "KdaGateDownW"
    # "KdaGateUpW" "KdaGateB" "GqaQkvW" "GqaGateW" "GqaOutW"
    # "MambaInW" "MambaConvW" "MambaConvB" "MambaDtBias" "MambaALog" "MambaD"
    # "MambaNormS" "MambaOutW" "MoeLatentDownW" "MoeLatentUpW"
    # a sparse latent layer's indexer and the residual streams' mixes:
    # "IdxQW" "IdxKW" "IdxKNormS" "IdxKNormB" "IdxHeadW" "Hc1W" "Hc1Alpha"
    # "Hc1B" "Hc2W" "Hc2Alpha" "Hc2B"
    (prompt, tok_emb, pos_emb, ln_s, ln_b, head_w,
     params) = _unpack_lm_ins(blk, ins)
    if blk.attn_kinds:
        raise BlockNotSupportedError(
            "transformer_stack_generate keeps one dense K/V cache a layer "
            "and cannot hold a stack by attention kind "
            f"({list(blk.attn_kinds)}): the paged prefill / decode ops "
            "behind GenerationEngine run it")
    _require_uniform_planes(blk, "transformer_stack_generate")
    N = attrs["max_new_tokens"]
    temperature = attrs.get("temperature") or 0.0
    top_k = attrs.get("top_k") or 0
    b, Tp = prompt.shape
    L, d = params["ln1_s"].shape
    Ttot = Tp + N
    if pos_emb is not None and Ttot > pos_emb.shape[0]:
        raise ValueError(
            f"prompt {Tp} + {N} new tokens exceeds max_len "
            f"{pos_emb.shape[0]}")
    _hold_to_window(blk, Tp, "transformer_stack_generate's prefill")
    embed = _embed_fn(tok_emb, pos_emb)
    logits_of = _logits_fn(ln_s, ln_b, head_w, blk)
    vocab = head_w.shape[1]
    pick = _make_pick(temperature, top_k, vocab, rng)

    # ---- prefill: run the stack over the prompt, capturing K/V -------
    h, (ks, vs) = _prefill(blk, params, embed(prompt, 0), b, Tp)
    pad = [(0, 0)] * 5
    pad[3] = (0, N)  # [L, b, Hkv, Tp, dh] -> [L, b, Hkv, Ttot, dh]
    cache_k = jnp.pad(ks, pad)
    cache_v = jnp.pad(vs, pad)
    next_tok = pick(logits_of(h[:, -1]), 0)  # [b]
    decode_layer = _decode_layer_fn(blk, params, d)

    # ---- decode: one token at a time against the cache ---------------
    def step(carry, n):
        tok, ck, cv = carry
        pos = Tp + n
        x1 = embed(tok[:, None], pos)  # [b, 1, d]
        h1, (ck, cv) = _scan_stack(
            blk.kinds, lambda h1, inp, kind: decode_layer(h1, inp, pos, kind),
            x1, (params, ck, cv))
        nxt = pick(logits_of(h1[:, 0]), n + 1)
        return (nxt, ck, cv), nxt

    if N == 0:
        return out(Out=prompt)
    # prefill already produced token Tp; the scan decodes the remaining
    # N - 1 (emitting each step's OWN result — no wasted final step)
    (_, _, _), toks = jax.lax.scan(
        step, (next_tok, cache_k, cache_v), jnp.arange(N - 1))
    generated = jnp.concatenate(
        [next_tok[:, None], jnp.moveaxis(toks, 0, 1)], axis=1)  # [b, N]
    return out(Out=jnp.concatenate(
        [prompt, generated.astype(prompt.dtype)], axis=1))


@register_op("transformer_stack_beam_search", optional_inputs=("PosEmb",))
def transformer_stack_beam_search(attrs, ins):
    """Beam search over the KV-cache decode path.

    Same inputs as transformer_stack_generate; attrs: num_heads,
    max_new_tokens, beam_size, length_penalty (GNMT-style
    ((5+len)/6)^alpha score normalisation), eos_id (-1 = none).
    Out [b, K, Tp + N] int (beams sorted best-first) and
    Scores [b, K] f32 (length-normalised log-probs).

    The beam dimension rides the batch axis (caches live at [L, b*K, ...])
    and every step reorders each layer's cache by the surviving beams'
    parent index — one gather per layer, the TPU-native equivalent of the
    reference's beam_search op family shuffling LoD rows
    (/root/reference/paddle/operators/beam_search_op.cc).
    """
    blk = Block.from_attrs(attrs)
    blk.require_gpt2("transformer_stack_beam_search")
    (prompt, tok_emb, pos_emb, ln_s, ln_b, head_w,
     params) = _unpack_lm_ins(blk, ins)
    N = attrs["max_new_tokens"]
    K = attrs.get("beam_size", 4)
    alpha = attrs.get("length_penalty") or 0.0
    eos_id = attrs.get("eos_id", -1)
    if eos_id is None:
        eos_id = -1
    b, Tp = prompt.shape
    L, d = params["ln1_s"].shape
    V = head_w.shape[1]
    Ttot = Tp + N
    if pos_emb is not None and Ttot > pos_emb.shape[0]:
        raise ValueError(
            f"prompt {Tp} + {N} new tokens exceeds max_len "
            f"{pos_emb.shape[0]}")
    if N < 1:
        raise ValueError("beam search needs max_new_tokens >= 1")
    if not 0 < K <= V:
        raise ValueError(f"beam_size {K} outside [1, vocab {V}]")
    embed = _embed_fn(tok_emb, pos_emb)
    logits_of = _logits_fn(ln_s, ln_b, head_w, blk)

    # ---- prefill over the bare batch, then tile to beams --------------
    h, (ks, vs) = _prefill(blk, params, embed(prompt, 0), b, Tp)
    pad = [(0, 0)] * 5
    pad[3] = (0, N)
    cache_k = jnp.repeat(jnp.pad(ks, pad), K, axis=1)  # [L, b*K, Hkv, T, dh]
    cache_v = jnp.repeat(jnp.pad(vs, pad), K, axis=1)

    # first expansion: top-K tokens of the prompt's next-token distribution
    logp0 = jax.nn.log_softmax(logits_of(h[:, -1]), axis=-1)  # [b, V]
    scores, tok0 = jax.lax.top_k(logp0, K)  # [b, K] each
    tokens = jnp.full((b, K, N), eos_id if eos_id >= 0 else 0,
                      dtype=prompt.dtype)
    tokens = tokens.at[:, :, 0].set(tok0.astype(prompt.dtype))
    alive = (tok0 != eos_id) if eos_id >= 0 else jnp.ones((b, K), bool)
    decode_layer = _decode_layer_fn(blk, params, d)

    def step(carry, n):
        tokens, scores, alive, ck, cv = carry
        pos = Tp + 1 + n
        cur = jax.lax.dynamic_index_in_dim(tokens, n, 2,
                                           keepdims=False)  # [b, K]
        x1 = embed(cur.reshape(b * K)[:, None], pos - 1)  # query at pos-1
        h1, (ck, cv) = jax.lax.scan(
            lambda h1, inp: decode_layer(h1, inp, pos - 1),
            x1, (params, ck, cv))
        logp = jax.nn.log_softmax(logits_of(h1[:, 0]),
                                  axis=-1).reshape(b, K, V)
        # finished beams: only the eos continuation keeps their score
        if eos_id >= 0:
            frozen = jnp.full((V,), -jnp.inf).at[eos_id].set(0.0)
            logp = jnp.where(alive[:, :, None], logp, frozen[None, None])
        cand = scores[:, :, None] + logp  # [b, K, V]
        scores_new, flat_idx = jax.lax.top_k(cand.reshape(b, K * V), K)
        parent = flat_idx // V  # [b, K]
        tok = (flat_idx % V).astype(tokens.dtype)

        # reorder beam state by parent
        batch_ix = jnp.arange(b)[:, None]
        tokens = tokens[batch_ix, parent]  # [b, K, N]
        alive_p = alive[batch_ix, parent]
        tokens = jax.lax.dynamic_update_index_in_dim(
            tokens, tok, n + 1, 2)
        alive = alive_p & (tok != eos_id) if eos_id >= 0 \
            else jnp.ones((b, K), bool)
        # caches: [L, b*K, ...] gather along the beam-batch axis
        flat_parent = (jnp.arange(b)[:, None] * K + parent).reshape(b * K)
        ck = ck[:, flat_parent]
        cv = cv[:, flat_parent]
        return (tokens, scores_new, alive, ck, cv), None

    # zero-length scan (N == 1) returns the carry unchanged
    (tokens, scores, alive, _, _), _ = jax.lax.scan(
        step, (tokens, scores, alive, cache_k, cache_v),
        jnp.arange(N - 1))

    if alpha:
        # GNMT length normalisation over generated (non-frozen) length
        if eos_id >= 0:
            gen_len = jnp.minimum(
                jnp.argmax(tokens == eos_id, axis=2) + 1, N).astype(
                jnp.float32)
            gen_len = jnp.where((tokens == eos_id).any(axis=2), gen_len,
                                float(N))
        else:
            gen_len = jnp.full((b, K), float(N))
        norm = ((5.0 + gen_len) / 6.0) ** alpha
        scores = scores / norm
    order = jnp.argsort(-scores, axis=1)
    batch_ix = jnp.arange(b)[:, None]
    tokens = tokens[batch_ix, order]
    scores = scores[batch_ix, order]
    prompts = jnp.repeat(prompt[:, None, :], K, axis=1)
    return out(Out=jnp.concatenate([prompts, tokens], axis=2),
               Scores=scores)


# ---------------------------------------------------------------------------
# Paged-cache decode ops: the continuous-batching serving path
# (paddle_tpu/serving/generation.py; vLLM's PagedAttention layout). The KV
# cache is a PAGE POOL [L, N, page_size, Hkv*dh] living in the scope as
# persistable state; a per-row int32 block table maps logical positions to
# physical pages, so a sequence holds exactly ceil(len / page_size) pages —
# and a page shared by several sequences (a common system prompt) is
# stored ONCE, each sharer's table pointing at the same physical page.
# Page 0 is the scrap page: padding rows and vacant decode slots write
# there and nothing ever attends to it. Both ops read AND write the pool,
# so the executor threads it as donated read-write state (in-place buffer
# update, no cache copy per step); ``_scan_paged_layers`` says what a step
# moves.
#
# Why a token's K/V is ONE row of Hkv*dh floats: the TPU runtime derives
# an array's device layout from its shape alone, and puts the LARGEST
# dimension on the 128 lanes when the last one is narrower than 128. A
# pool [L, N, Hkv, ps, dh] with dh 64 therefore lived on the v5e with the
# PAGE axis on the lanes (minor-to-major {1,4,3,2,0}): every page was
# strided through its whole layer, and each tick re-laid out every
# layer's pool round the scatter and the gather and copied the whole pool
# once (~95 of a 162 ms tick, all proportional to N). With Hkv*dh last a
# page is contiguous and lane-dense on the device as it is in this shape.
# ---------------------------------------------------------------------------

_SAMPLING_SLOTS = ("Temperature", "TopK", "TopP", "Seed", "Step", "Mask")


def _row_sampling(ins):
    """The per-row sampling plane, when fed: (temperature [rows], top_k
    [rows], top_p [rows], seed [rows], step [rows], mask [rows, V] or
    None) — or None when the program predates per-request sampling (the
    legacy engine-wide attrs path)."""
    temp = maybe(ins, "Temperature")
    if temp is None:
        return None
    return (temp, single(ins, "TopK"), single(ins, "TopP"),
            single(ins, "Seed"), single(ins, "Step"), maybe(ins, "Mask"))


def _pick_rows(attrs, ins, rng, vocab, logits, step0=0):
    """Next-token selection for the paged decode family: the per-row
    plane (kernels/sampling.sample_rows — seeds are INPUTS, the scope
    RNG stays untouched) when fed, else the legacy engine-wide
    attrs/rng path."""
    from ..kernels.sampling import sample_rows

    plane = _row_sampling(ins)
    if plane is None:
        pick = _make_pick(attrs.get("temperature") or 0.0,
                          attrs.get("top_k") or 0, vocab, rng)
        return pick(logits, step0)
    temp, top_k, top_p, seed, step, mask = plane
    return sample_rows(logits, temp, top_k, top_p, seed, step, mask)


def _maybe_topk(attrs, ins, logits, outs, draft_logits=None):
    """Attach TopV/TopI (each row's top-``emit_topk`` masked log-probs)
    to ``outs`` when the program asks for the beam plane; a drafting
    block's rows (``draft_logits``, unmasked: a draft is a plain argmax)
    go BELOW the stack's — how a check reads the block's logits."""
    k = attrs.get("emit_topk") or 0
    if k:
        from ..kernels.sampling import top_logprobs

        vals, ids = top_logprobs(logits, int(k), maybe(ins, "Mask"))
        if draft_logits is not None:
            more = top_logprobs(draft_logits, int(k))
            vals = jnp.concatenate([vals, more[0]])
            ids = jnp.concatenate([ids, more[1]])
        outs["TopV"], outs["TopI"] = [vals], [ids]
    return outs


def _gather_pages(pool, layer, table, num_kv_heads):
    """Layer ``layer``'s pages of pool [L, N, ps, Hkv*dh] gathered by
    table [b, P] -> the flattened context [b, Hkv, P*ps, dh]: flattened
    position j holds the token at sequence position j (table entry i
    covers positions i*ps..(i+1)*ps-1, and a page keeps its rows in
    position order). ONE gather at (layer, page) straight from the whole
    pool — no layer slice of the pool is ever materialised."""
    b, P = table.shape
    ps, width = pool.shape[2:]
    ctx = pool[layer, table]  # [b, P, ps, Hkv*dh]
    ctx = ctx.reshape(b, P * ps, num_kv_heads, width // num_kv_heads)
    return ctx.transpose(0, 2, 1, 3)


#: planes the paged layer loop hands to the block WHOLE (with the layer's
#: index under "layer") instead of slicing layer l out: see ``moe_topk``
_RESIDENT_PLANES = ("moe_gate_w", "moe_up_w", "moe_down_w")


def _window_span(table, mask, t, ps, window):
    """The entries of ``table`` [b, P] a window layer can still reach:
    -> (span [b, n] page ids, k_pos0 [b] the position of the span's first
    row). n = the pages ``window + t - 1`` consecutive keys can touch,
    never the table width; entries past the table repeat its last page at
    positions no query reaches."""
    P = table.shape[1]
    if "lengths" in mask:
        first_key = jnp.maximum(mask["lengths"] - window, 0)
    else:
        first_key = jnp.maximum(mask["q_pos0"] - window + 1, 0)
    first = (first_key // ps).astype(jnp.int32)
    n = min(P, (window + t + ps - 3) // ps + 1)
    entries = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    span = jnp.take_along_axis(table, jnp.minimum(entries, P - 1), axis=1)
    return span, first * ps


def _scan_paged_layers(params, h, cache_k, cache_v, table, page_id,
                       page_row, project, mask, finish, xs=None, blk=None,
                       win=None):
    """The layer loop of every paged op: h [b, t, d] through the L
    stacked blocks with the page pools [L, N, ps, Hkv*dh] as the scan's
    CARRY, updated in place.

    Per layer l: ``project(layer_p, h)`` -> q [b, H, t, dh], k/v
    [b, Hkv, t, dh]; the t new tokens of each batch row are written with
    ONE scatter per pool at (l, page_id, page_row) — page_id / page_row
    are [b, t] (decode: t == 1), the three index arrays are adjacent and
    the update window is a token's whole [Hkv*dh] row, contiguous in the
    pool; the context is attended either by a paged attention kernel, which
    reads the pages each row holds (a chunk: reaches) straight from the
    whole pool (on a chip: a decode step, ``paged_attention.supported`` and
    a ``lengths``-only ``mask``; a verify tick's ``first_len``; a prefill
    chunk, ``paged_attention.chunk_supported`` and ``chunk_mask``), or
    gathered at (l, table) and attended with ``reference_attention`` under
    the same mask (everything else — the CPU, a head narrower than the
    lanes under a chunk: the ground truth);
    ``finish(layer_p, h, ctx, x_l)``
    -> (h, stats) closes the block, x_l being layer l's slice of the
    optional scanned-over ``xs`` and stats what the layer reports (None,
    or an expert layer's (counts, router prob mean)). Returns (h,
    cache_k, cache_v, stats stacked over layers, the window kind's pools
    or None).

    A stack whose layers differ in kind (``blk.kinds``, a
    ``layer_pattern``) holds its cache BY KIND: ``cache_k`` / ``cache_v``
    / ``table`` / ``page_id`` / ``page_row`` are then the FULL-attention
    layers' pools [Lg, Ng, ..] and table, ``win`` = (cache_kw, cache_vw,
    table_w, page_id_w, page_row_w) the window layers' [Lw, Nw, ..]
    (None: no window layer). The same loop runs it (``_scan_stack``
    unrolls a period in the scan's body): each layer writes and reads its
    own kind's pool at its index WITHIN the kind, and ``project(layer_p,
    h, rope)`` takes the layer's positions. A window layer attends keys
    ``0 <= i - j < blk.window``: the kernels start their page walk at the
    window's first page, the gathered form gathers the window's span of
    the table (``_window_span``), not its width. A stack of one kind is
    the period of one layer, no window and ``project``'s own positions.

    Pages hold K/V in the POOL's dtype (the spec's ``page_dtype``): new
    rows are cast on the way in, and queries are cast to it so the
    attention contractions read the pages as stored.

    The pools are never scanned-over inputs or stacked outputs: that form
    sliced layer l's pool out, re-laid it out round the scatter, restacked
    it and copied the whole pool once per call — all proportional to N,
    none to the tokens in flight. As carry the donated buffers ARE the
    loop state and the only pool-shaped ops are the two in-place
    scatters; the kernels take the carry whole and address it at (l,
    page), so they add none."""
    b, t, _ = h.shape
    whole = {k: params[k] for k in _RESIDENT_PLANES if k in params}
    params = {k: v for k, v in params.items() if k not in whole}
    # (a K/V block with selection: ``cache_v`` is (V pool, indexer's pool))
    attend = _paged_layer_step(b, t, cache_k.shape[2], project, mask, finish,
                               blk if blk is not None and blk.is_mla
                               else None,
                               blk if blk is not None and blk.sparse_kv
                               else None)
    ix = (page_id.reshape(b, t), page_row.reshape(b, t))
    kinds = blk.kinds if blk is not None else None
    ckw, cvw, table_w, page_id_w, page_row_w = win or (None,) * 5
    ix_w = (None if win is None
            else (page_id_w.reshape(b, t), page_row_w.reshape(b, t)))
    # (the planes every layer has lead with L; a dense head's lead with less)
    n_layers = max(a.shape[0] for a in jax.tree_util.tree_leaves(params))
    within = None       # a layer's index within its kind: l itself for one
    if kinds is not None:
        seen = {False: 0, True: 0}
        within = []
        for l in range(n_layers):
            windowed = kinds[l % len(kinds)][0]
            within.append(seen[windowed])
            seen[windowed] += 1
        within = jnp.asarray(within, jnp.int32)
    # leading dense layers (``first_dense`` of a full / window stack): the
    # dense planes lead with THEIR count, the experts' with the rest
    fd = blk.first_dense if blk is not None else 0

    def layer(carry, inp, kind):
        h, ck, cv, ckw, cvw = carry
        layer_p, l, l_kind, x_l = inp
        if whole and "dense_gate_w" not in layer_p:
            layer_p = {**layer_p, **whole, "layer": l - fd if fd else l}
        windowed, rope = kind or (False, None)
        if l_kind is None:
            l_kind = l
        if windowed:
            h, ckw, cvw, stats = attend(h, ckw, cvw, l_kind, layer_p, x_l,
                                        table_w, *ix_w, window=blk.window,
                                        rope=rope)
        else:
            h, ck, cv, stats = attend(h, ck, cv, l_kind, layer_p, x_l,
                                      table, *ix, rope=rope)
        return (h, ck, cv, ckw, cvw), stats

    carry = (h, cache_k, cache_v, ckw, cvw)
    if not fd:
        carry, stats = _scan_stack(
            kinds, layer, carry,
            (params, jnp.arange(n_layers, dtype=jnp.int32), within, xs))
    else:
        carry, stats = _dense_head_then_scan(
            kinds, layer, carry, params, within, xs, fd, n_layers)
    h, cache_k, cache_v, ckw, cvw = carry
    return h, cache_k, cache_v, stats, (None if win is None else (ckw, cvw))


def _dense_head_then_scan(kinds, layer, carry, params, within, xs, fd,
                          n_layers):
    """``_scan_paged_layers``' walk for a full / window stack whose first
    ``fd`` layers run a dense FFN: the periods that hold a dense layer are
    unrolled (their positions differ from the later periods'), the whole
    periods after them run under ``_scan_stack``. A plane of the ``dense``
    group leads with fd, one of the ``experts`` group with n_layers - fd,
    every other with n_layers (``LMSpec.plane_layers``). -> (carry, the
    EXPERT layers' stats stacked)."""
    tmap = jax.tree_util.tree_map
    group = {k: Block.plane_group(k) for k in params}
    P = len(kinds) if kinds else 1
    head = min(-(-fd // P) * P, n_layers)
    stats = []
    for l in range(head):
        p_l = {k: v[l - fd if group[k] == "experts" else l]
               for k, v in params.items()
               if group[k] != ("experts" if l < fd else "dense")}
        carry, st = layer(
            carry, (p_l, jnp.asarray(l, jnp.int32),
                    None if within is None else within[l],
                    tmap(lambda a: a[l], xs)),
            kinds[l % P] if kinds else None)
        if st is not None:
            stats.append(st)
    stats = [tmap(lambda *a: jnp.stack(a), *stats)] if stats else []
    if head < n_layers:
        rest = {k: v[head - fd if group[k] == "experts" else head:]
                for k, v in params.items() if group[k] != "dense"}
        carry, ys = _scan_stack(kinds, layer, carry, (
            rest, jnp.arange(head, n_layers, dtype=jnp.int32),
            None if within is None else within[head:],
            tmap(lambda a: a[head:], xs)))
        stats.append(ys)
    return carry, tmap(lambda *a: jnp.concatenate(a), *stats)


#: bytes one query tile of a sparse layer may hold: of gathered latent rows
#: (``_dsa_attend``), of the indexer's per-head scores (``_dsa_pick``)
_DSA_TILE_BYTES = 1 << 28


def _dsa_project(blk, p, h):
    """The indexer's projections of a sparse latent layer from the stream h
    [b, t, d] (norm 1 and the query latent are the expressions
    ``_mla_latent`` computes, which XLA shares): -> qI [b, t, Hi, Di], kI
    [b, t, Di] (LayerNorm'd: the token's indexer key) and the heads' weights
    w [b, t, Hi] = (hn W_w) Hi^-1/2 Di^-1/2, no rotation anywhere."""
    b, t, _ = h.shape
    Hi, Di = blk.index_heads, blk.index_dim
    hn = _norm(blk, h, p["ln1_s"], p.get("ln1_b"))
    # (a K/V layer has no query latent: its indexer reads the normed stream)
    c_q = hn if not blk.is_mla else _rms(
        _mm(blk, "btd,dr->btr", hn, p["q_a_w"]), p["q_a_norm_s"],
        blk.norm_eps)
    q_i = _mm(blk, "btr,re->bte", c_q, p["idx_q_w"]).reshape(b, t, Hi, Di)
    k_i = _ln(_mm(blk, "btd,de->bte", hn, p["idx_k_w"]),
              p["idx_k_norm_s"].astype(jnp.float32),
              p["idx_k_norm_b"].astype(jnp.float32), blk.norm_eps)
    w = _mm(blk, "btd,dh->bth", hn, p["idx_head_w"]) * (Hi * Di) ** -0.5
    return q_i, k_i, w


def _index_write(blk, pool, l, k_i, ix_page, ix_row, pos, valid):
    """The call's indexer keys k_i [b, t, Di] into the pooled-key pool
    [L, N, ps / G, Di] (G = ``index_pool``), in place: group g of a
    sequence (positions G g .. G g + G - 1, never across a page) holds the
    running mean sum / G of the keys that have arrived. Each group a row of
    the call touches is written ONCE, at the row's last token in it: the sum
    of the call's tokens of the group (float32), on top of what the pool
    held if the group began before this call, else on zero (a page needs no
    clearing when it is taken). ``pos`` [b, t] the tokens' positions,
    ``valid`` [b, t] which are real."""
    G = blk.index_pool
    t = k_i.shape[1]
    f32 = jnp.float32
    if G == 1:      # one key a token: a row write, no running mean
        page = jnp.where(valid, ix_page, pool.shape[1])
        return pool.at[l, page, ix_row].set(k_i.astype(pool.dtype),
                                            mode="drop")
    k_i = jnp.where(valid[..., None], k_i.astype(f32), 0.0)
    j = pos % G                                         # place in its group
    acc = k_i
    for back in range(1, min(G, t)):                    # the group's earlier
        prev = jnp.pad(k_i, ((0, 0), (back, 0), (0, 0)))[:, :t]
        acc = acc + jnp.where((j >= back)[..., None], prev, 0.0)
    g_row = ix_row // G
    began_before = (pos - j) < pos[:, :1]
    old = jnp.where(began_before[..., None],
                    pool[l, ix_page, g_row].astype(f32), 0.0)
    nxt_valid = jnp.pad(valid[:, 1:], ((0, 0), (0, 1)))   # none after t
    last = valid & ((j == G - 1) | ~nxt_valid)
    page = jnp.where(last, ix_page, pool.shape[1])      # others: dropped
    return pool.at[l, page, g_row].set((old + acc / G).astype(pool.dtype),
                                       mode="drop")


def _dsa_scores(keys, q_i, w_i, qpos, G):
    """The indexer's scores of queries q_i [b, T, Hi, Di] (head weights w_i
    [b, T, Hi]) at positions ``qpos`` [b, T] over a row's pooled keys [b,
    NG, Di]: I[t, g] = sum_j w_j relu(qI_j . kbar_g) in float32 (a -0.0
    folded onto 0.0: one score, whoever orders them), -inf for every group
    not wholly before the query's own. -> (scores [b, T, NG], own [b, T]
    the query's group)."""
    f32 = jnp.float32
    s = jnp.einsum("bthd,bgd->bthg", q_i.astype(keys.dtype), keys,
                   preferred_element_type=f32)
    s = jnp.einsum("bthg,bth->btg", jax.nn.relu(s), w_i.astype(f32))
    own = qpos // G                                     # [b, T]
    before = jnp.arange(keys.shape[1], dtype=jnp.int32) < own[..., None]
    return jnp.where(before, jnp.where(s == 0, 0.0, s), -jnp.inf), own


def _query_tiles(t, tile, *arrays):
    """``arrays`` [b, t, ..] as ``lax.map`` walks them in tiles of ``tile``
    queries: [t / tile, b, tile, ..]."""
    return tuple(a.reshape((a.shape[0], t // tile, tile) + a.shape[2:])
                 .swapaxes(0, 1) for a in arrays)


def _dsa_tile(t, per_query):
    """Queries a tile of a sparse layer's chunk: the largest divisor of t
    whose ``per_query`` bytes each stay under ``_DSA_TILE_BYTES``."""
    tile = max(1, min(t, _DSA_TILE_BYTES // per_query))
    while t % tile:
        tile -= 1
    return tile


def _attend_picked(q, groups, l, tbl, pick, ok, qpos, gp, r):
    """Attention of queries q [b, H, T, W] at ``qpos`` [b, T] over the
    latent rows of the groups they picked: ``pick`` [b, T, K] group ids in
    the table's logical order (``ok``: which are picks at all), ``groups``
    [L, N gp, G W] the latent pool by group (layer ``l`` of it is read),
    ``gp`` groups a page. The picked groups' rows are gathered (page ids
    off ``tbl``, then G rows of W a group, ONE gather at (l, group)) and
    attended, keys beyond the query masked -> [b, H, T, r] float32."""
    b, _, _, W = q.shape
    G = groups.shape[2] // W
    page = jnp.take_along_axis(
        tbl, (pick // gp).reshape(b, -1), axis=1).reshape(pick.shape)
    rows = groups[l, page * gp + pick % gp]             # [b, T, K, G W]
    rows = rows.reshape(b, rows.shape[1], -1, W)
    kpos = (pick[..., None] * G
            + jnp.arange(G, dtype=jnp.int32)).reshape(b, -1, rows.shape[2])
    seen = jnp.repeat(ok, G, axis=-1) & (kpos <= qpos[..., None])
    sc = jnp.einsum("bhtw,btkw->bhtk", q.astype(rows.dtype), rows,
                    preferred_element_type=jnp.float32)
    sc = jnp.where(seen[:, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhtk,btkr->bhtr", pr.astype(rows.dtype),
                      rows[..., :r], preferred_element_type=jnp.float32)


def _dsa_attend(blk, q_lat, q_i, w_i, ck, ci, l, tbl, pos):
    """Sparse attention of one latent layer: queries q_lat [b, H, t, W]
    (absorbed, scaled) at positions ``pos`` [b, t] against the latent pool
    ck [L, N, ps, W] THROUGH the indexer's pool ci [L, N, ps / G, Di]. The
    row's pooled keys are scored (``_dsa_scores``), the ``index_topk / G -
    1`` best picked exactly (``lax.top_k``: ties to the lower index), the
    query's own group added, and ONLY the picked groups' latent rows are
    gathered (G rows of W contiguous a group) and attended, keys beyond the
    query masked. A chunk runs in query tiles whose gathered rows stay under
    ``_DSA_TILE_BYTES``. The semantic ground truth of the selection, and the
    path of every call the masked page walk cannot take (``_dsa_pick``).
    -> o_lat [b, H, t, r]."""
    b, H, t, W = q_lat.shape
    G, r = blk.index_pool, blk.kv_lora_rank
    L, N, ps, _ = ck.shape
    gp = ps // G                                        # groups a page
    n_groups = tbl.shape[1] * gp
    k_pick = min(blk.index_topk // G - 1, n_groups)
    keys = ci[l, tbl].reshape(b, n_groups, -1)          # [b, NG, Di]
    groups = ck.reshape(L, N * gp, G * W)               # a group's rows
    tile = _dsa_tile(t, b * (k_pick + 1) * G * W * ck.dtype.itemsize)

    def attend(args):
        q, qi, wi, qpos = args          # [b, H, T, W] [b, T, Hi, Di] ..
        s, own = _dsa_scores(keys, qi, wi, qpos, G)
        top, pick = jax.lax.top_k(s, k_pick)
        pick = jnp.concatenate([pick.astype(jnp.int32), own[..., None]], -1)
        ok = jnp.concatenate([top > -jnp.inf,
                              jnp.ones_like(own[..., None], bool)], -1)
        return _attend_picked(q, groups, l, tbl, pick, ok, qpos, gp, r)

    if tile == t:
        return attend((q_lat, q_i, w_i, pos))
    n = t // tile
    o = jax.lax.map(attend, (
        q_lat.reshape(b, H, n, tile, W).transpose(2, 0, 1, 3, 4),
        *_query_tiles(t, tile, q_i, w_i, pos)))         # [n, b, H, tile, r]
    return o.transpose(1, 2, 0, 3, 4).reshape(b, H, t, r)


def _dsa_attend_kv(blk, q, q_i, w_i, ck, cv, ci, l, tbl, pos):
    """Sparse attention of one K/V layer, the reads following the pick:
    queries q [b, H, t, dh] at positions ``pos`` [b, t] against the pools ck /
    cv [L, N, ps, Hkv dh] THROUGH the indexer's pool ci [L, N, ps, Di] (one
    key a token). The row's indexer keys are scored (``_dsa_scores``), the
    ``index_topk - 1`` best positions before the query picked exactly
    (``lax.top_k``: ties to the lower index), its own added, and ONLY the
    picked tokens' K and V rows are gathered and attended, all H heads over
    the same set. The semantic ground truth of the selection on this kind,
    and the path of every call the masked page walk cannot take. -> ctx
    [b, t, H dh] float32. A chunk runs in query tiles whose gathered rows
    stay under ``_DSA_TILE_BYTES``."""
    b, H, t, dh = q.shape
    ps, width = ck.shape[2:]
    hkv = width // dh
    n_keys = tbl.shape[1] * ps
    k_pick = min(blk.index_topk - 1, n_keys)
    keys = ci[l, tbl].reshape(b, n_keys, -1)
    tile = _dsa_tile(t, 2 * b * (k_pick + 1) * width * ck.dtype.itemsize)

    def attend(args):
        qt, qi, wi, qpos = args     # [b, H, T, dh] [b, T, Hi, Di] ..
        s, own = _dsa_scores(keys, qi, wi, qpos, 1)
        top, pick = jax.lax.top_k(s, k_pick)
        pick = jnp.concatenate([pick.astype(jnp.int32), own[..., None]], -1)
        ok = jnp.concatenate([top > -jnp.inf,
                              jnp.ones_like(own[..., None], bool)], -1)
        page = jnp.take_along_axis(
            tbl, (pick // ps).reshape(b, -1), axis=1).reshape(pick.shape)
        T = qt.shape[2]
        rk = ck[l, page, pick % ps].reshape(b, T, -1, hkv, dh)
        rv = cv[l, page, pick % ps].reshape(b, T, -1, hkv, dh)
        qg = qt.reshape(b, hkv, H // hkv, T, dh).astype(rk.dtype)
        sc = jnp.einsum("bgrtd,btkgd->bgrtk", qg, rk,
                        preferred_element_type=jnp.float32) * dh ** -0.5
        sc = jnp.where(ok[:, None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bgrtk,btkgd->btgrd", pr.astype(rv.dtype), rv,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, T, H * dh)

    if tile == t:
        return attend((q, q_i, w_i, pos))
    n = t // tile
    o = jax.lax.map(attend, (
        q.reshape(b, H, n, tile, dh).transpose(2, 0, 1, 3, 4),
        *_query_tiles(t, tile, q_i, w_i, pos)))         # [n, b, tile, H dh]
    return o.swapaxes(0, 1).reshape(b, t, H * dh)


def _picked_groups(scores, own, k_pick):
    """The set ``_dsa_attend`` picks, as a mask: scores [.., NG] (-inf for a
    group not before the query's own, no -0.0: ``_dsa_scores``), own [..]
    -> [.., NG] bool, true for the ``k_pick`` best groups (every group
    before, where there are fewer) and the query's own. EXACTLY ``lax.top_k``'s set: with ``thr`` the
    k-th best score, every score above it and, of those that equal it, the
    lowest indices that fill the k. ``thr`` comes from the counted search
    the sampling plane ships (32 compare-and-count passes, no sort); the
    ranks among the equal ones (a prefix count over the table's width) are
    taken only where some row's k-th score is tied beyond the k."""
    from ..kernels.sampling import _search_threshold

    n_groups = scores.shape[-1]
    at_own = jnp.arange(n_groups, dtype=jnp.int32) == own[..., None]
    if k_pick <= 0:
        return at_own
    thr = _search_threshold(scores.reshape(-1, n_groups), jnp.int32(1),
                            jnp.int32(k_pick)).reshape(own.shape)[..., None]
    above = scores > thr
    # a group not before the query sits at -inf: it may equal ``thr`` (fewer
    # than k groups before) and is never picked
    equal = (scores == thr) & (scores > -jnp.inf)
    room = k_pick - jnp.sum(above, axis=-1, keepdims=True)

    def lowest_equal():
        rank = jnp.cumsum(equal, axis=-1, dtype=jnp.int32)  # 1-based
        return equal & (rank <= room)

    crowded = jnp.any(jnp.sum(equal, axis=-1, keepdims=True) > room)
    return above | jax.lax.cond(crowded, lowest_equal, lambda: equal) | at_own


def _dsa_pick(blk, q_i, w_i, ci, l, tbl, pos):
    """A sparse latent layer's pick as a GROUP mask for the page walks
    (``kernels/paged_attention``: ``group_mask``): [b, t, NG] int8 over the
    table's groups in logical order, 1 for the groups ``_dsa_attend`` would
    gather for the query (``_picked_groups``). No index list, no page ids,
    no gathered row: the walk reads the pages the row holds in the pool as
    it lies and masks what was not picked out of the softmax. A chunk runs
    in query tiles whose per-head scores stay under ``_DSA_TILE_BYTES``."""
    b, t = pos.shape
    G = blk.index_pool
    n_groups = tbl.shape[1] * ci.shape[2]
    k_pick = min(blk.index_topk // G - 1, n_groups)
    keys = ci[l, tbl].reshape(b, n_groups, -1)          # [b, NG, Di]

    def pick(args):
        qi, wi, qpos = args
        return _picked_groups(*_dsa_scores(keys, qi, wi, qpos, G),
                              k_pick).astype(jnp.int8)

    tile = _dsa_tile(t, b * blk.index_heads * n_groups * 4)
    if tile == t:
        return pick((q_i, w_i, pos))
    picked = jax.lax.map(pick, _query_tiles(t, tile, q_i, w_i, pos))
    return picked.swapaxes(0, 1).reshape(b, t, n_groups)


def _mla_paged_step(blk, b, t, project, mask, finish):
    """``_paged_layer_step`` for a latent block: ONE pool ``ck`` [L, N,
    ps, W] whose row is a token's [c_kv | k_rope | zero pad to W];
    ``project(layer_p, h)`` is ``_mla_latent``. Absorbed attention never
    expands the cache: q~[h] = q_nope[h] W_UK[h]^T scores the latent
    directly, every head reads ONE shared key row whose first r columns
    are also the value, and o[h] = (sum_j p_j c_kv[j]) W_UV[h]. The softmax
    scale rides the queries (sm_scale 1 in the attention). On a chip a
    decode tick and a prefill chunk each walk the pages in a kernel
    (``paged_mla_decode``; ``paged_mla_prefill`` where
    ``paged_attention.chunk_supported`` takes the operands: row and latent
    of whole lane rows); everything else (the CPU, unaligned widths)
    gathers them and attends absorbed too: rebuilding every head's keys
    and values from the gathered rows was 25.5 ms a 256-token unit over a
    16k context where this is 20.7 (my chip run, PR 35, PERF.md section
    6). ``index_topk``: learned sparse attention: ``cv`` is then the pool
    of the indexer's pooled keys, written beside the latent rows; on a chip
    the pick rides into the SAME two walks as a group mask (``_dsa_pick``;
    ``paged_attention.mask_supported``: the table no wider than the walk
    wins at), everywhere else the attention gathers the picked groups' rows
    alone (``_dsa_attend``). ``profiler.global_stat`` counts which a traced
    layer took (``dsa/walk_calls`` / ``dsa/gather_calls``)."""
    from .. import profiler
    from ..kernels import paged_attention
    from ..kernels.flash_attention import reference_attention

    r, rope_d = blk.kv_lora_rank, blk.qk_rope_head_dim
    scale = _sm_scale(blk)

    def attend(h, ck, cv, l, layer_p, x_l, tbl, ix_page, ix_row, **_kw):
        q_nope, q_rope, c_kv, k_rope = project(layer_p, h)
        W = ck.shape[-1]
        row = jnp.concatenate([c_kv, k_rope], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0), (0, W - row.shape[-1])))
        ck = ck.at[l, ix_page, ix_row].set(row.astype(ck.dtype))
        w_uk, w_uv = _mla_up(blk, layer_p)
        q_abs = _mm(blk, "bhtn,rhn->bhtr", q_nope, w_uk)
        q_lat = jnp.concatenate([q_abs, q_rope], axis=-1) * scale
        q_lat = jnp.pad(q_lat, ((0, 0),) * 3 + ((0, W - r - rope_d),))
        if blk.index_topk:
            # selection: ``cv`` is the indexer's pool; the reads follow the
            # pick, in the tick and in a chunk alike
            if "lengths" in mask:
                pos = (mask["lengths"] - 1)[:, None]
                valid = jnp.ones_like(pos, bool)
            else:
                steps = jnp.arange(t, dtype=jnp.int32)[None, :]
                pos = mask["q_pos0"][:, None] + steps
                valid = steps < mask["q_len"][:, None]
            q_i, k_i, w_i = _dsa_project(blk, layer_p, h)
            cv = _index_write(blk, cv, l, k_i, ix_page, ix_row, pos, valid)
            # on a chip the pick travels as a group mask into the walks of
            # the unselected layer (a tick; a chunk); every call they cannot
            # take (the CPU, unaligned widths, a table too wide to read
            # whole) gathers what it picked
            tick = t == 1 and set(mask) == {"lengths"} \
                and paged_attention.supported(W, ck, t)
            walks = paged_attention.mask_supported(
                ck, tbl.shape[1], blk.index_pool, chunk=not tick) and (
                    tick or paged_attention.chunk_supported(
                        q_lat.shape, ck, mask, r))
            if walks:
                picked = _dsa_pick(blk, q_i, w_i, cv, l, tbl, pos)
            if walks and tick:
                o_lat = paged_attention.paged_attention_decode(
                    q_lat[:, :, 0], ck, None, l, tbl, mask["lengths"],
                    sm_scale=1.0, name=paged_attention.MLA_KERNEL,
                    group_mask=picked[:, 0], group_rows=blk.index_pool)
                o_lat = o_lat.reshape(b, -1, 1, W)
            elif walks:
                o_lat = paged_attention.paged_attention_prefill(
                    q_lat, ck, None, l, tbl, mask["q_pos0"], mask["q_len"],
                    sm_scale=1.0, value_width=r, group_mask=picked,
                    group_rows=blk.index_pool)
                o_lat = o_lat.reshape(b, t, -1, r).transpose(0, 2, 1, 3)
            else:
                o_lat = _dsa_attend(blk, q_lat, q_i, w_i, ck, cv, l, tbl,
                                    pos)
            profiler.global_stat.add_count(
                "dsa/walk_calls" if walks else "dsa/gather_calls", 1)
        elif t == 1 and set(mask) == {"lengths"} \
                and paged_attention.supported(W, ck, t):
            o_lat = paged_attention.paged_attention_decode(
                q_lat[:, :, 0], ck, None, l, tbl, mask["lengths"],
                sm_scale=1.0, name=paged_attention.MLA_KERNEL)
            o_lat = o_lat.reshape(b, -1, 1, W)
        elif paged_attention.chunk_supported(q_lat.shape, ck, mask, r):
            # a prefill chunk: the pages its queries reach, never the table
            o_lat = paged_attention.paged_attention_prefill(
                q_lat, ck, None, l, tbl, mask["q_pos0"], mask["q_len"],
                sm_scale=1.0, value_width=r)
            o_lat = o_lat.reshape(b, t, -1, r).transpose(0, 2, 1, 3)
        else:
            lat = ck[l, tbl].reshape(b, 1, tbl.shape[1] * ck.shape[2], W)
            o_lat = reference_attention(q_lat.astype(ck.dtype), lat,
                                        lat[..., :r], sm_scale=1.0,
                                        **_gathered_mask(mask))
        ctx = _mm(blk, "bhtr,rhv->bthv", o_lat[..., :r].astype(h.dtype),
                  w_uv).reshape(b, t, -1)
        h, stats = finish(layer_p, h, ctx, x_l)
        return h, ck, cv, stats

    return attend


def chunk_mask(start, lengths):
    """A prefill chunk's ``mask``: block-causal from each row's ``start``
    [b], the first ``lengths`` [b] queries of a row real (what
    ``paged_attention.chunk_supported`` knows a chunk by)."""
    return dict(causal=True, q_pos0=start, q_len=lengths)


def _gathered_mask(mask):
    """``mask`` as ``reference_attention`` takes it: the gathered form
    attends a chunk's padding queries too (their rows are never read), so
    the chunk's length stays behind."""
    return {k: v for k, v in mask.items() if k != "q_len"}


def _paged_layer_step(b, t, ps, project, mask, finish, mla=None,
                      sparse=None):
    """The per-layer step of the paged loop (``_scan_paged_layers`` says
    what it does): ``attend(h, ck, cv, l, layer_p, x_l, table, ix_page,
    ix_row, window=None, rope=None)`` -> (h, ck, cv, stats) against the
    pools (ck, cv) of the layer's KIND at its index l within the kind:
    the layer's rows written, then its attention by whichever of
    ``kernels/paged_attention``'s three calls the operands allow (a decode
    step, a verify tick, a prefill chunk: all on a chip only), else the
    table gathered (a window layer: its span) under
    ``reference_attention``. ``mla``: a latent block (``_mla_paged_step``:
    one pool, cv None; the same rule over its one pool). ``sparse``: a K/V
    block with learned sparse attention (``Block.sparse_kv``): ``cv`` is then
    the pair (V pool, the indexer's pool [L, N, ps, Di]: one key a token,
    written beside the K and V rows); on a chip the pick rides into the SAME
    two walks as a mask of ``group_rows`` 1 (``_dsa_pick``;
    ``paged_attention.mask_supported``), everywhere else the attention
    gathers the picked tokens' rows alone (``_dsa_attend_kv``)."""
    from .. import profiler
    from ..kernels import paged_attention
    from ..kernels.flash_attention import reference_attention

    if mla is not None:
        return _mla_paged_step(mla, b, t, project, mask, finish)

    def token_rows(a):  # [b, Hkv, t, dh] -> [b, t, Hkv*dh]
        return a.transpose(0, 2, 1, 3).reshape(b, t, -1)

    def attend(h, ck, cv, l, layer_p, x_l, tbl, ix_page, ix_row,
               window=None, rope=None):
        """One layer against ITS kind's pools (ck, cv) at index l."""
        q, k, v = (project(layer_p, h) if rope is None
                   else project(layer_p, h, rope))
        hkv = k.shape[1]
        if sparse is not None:
            cv, ci = cv
        ck = ck.at[l, ix_page, ix_row].set(token_rows(k).astype(ck.dtype))
        cv = cv.at[l, ix_page, ix_row].set(token_rows(v).astype(cv.dtype))
        # on a chip a decode step (one query token a row, keys j <
        # lengths), a verify tick and a prefill chunk each walk the block
        # table in one kernel; every call the kernels cannot take gathers
        on_walk = paged_attention.supported(q.shape[1] * q.shape[3], ck, t)
        if sparse is not None:
            # selection: the reads follow the pick, tick and chunk alike
            if "lengths" in mask:
                pos = (mask["lengths"] - 1)[:, None]
                valid = jnp.ones_like(pos, bool)
            else:
                steps = jnp.arange(t, dtype=jnp.int32)[None, :]
                pos = mask["q_pos0"][:, None] + steps
                valid = steps < mask["q_len"][:, None]
            q_i, k_i, w_i = _dsa_project(sparse, layer_p, h)
            ci = _index_write(sparse, ci, l, k_i, ix_page, ix_row, pos, valid)
            tick = t == 1 and set(mask) == {"lengths"} and on_walk
            walks = paged_attention.mask_supported(
                ck, tbl.shape[1], 1, chunk=not tick) and (
                    tick or paged_attention.chunk_supported(q.shape, ck,
                                                            mask))
            if walks:
                picked = _dsa_pick(sparse, q_i, w_i, ci, l, tbl, pos)
            if walks and tick:
                ctx = paged_attention.paged_attention_decode(
                    q[:, :, 0], ck, cv, l, tbl, mask["lengths"],
                    group_mask=picked[:, 0], group_rows=1)[:, None]
            elif walks:
                ctx = paged_attention.paged_attention_prefill(
                    q, ck, cv, l, tbl, mask["q_pos0"], mask["q_len"],
                    group_mask=picked, group_rows=1)
            else:
                ctx = _dsa_attend_kv(sparse, q.astype(ck.dtype), q_i, w_i, ck,
                                     cv, ci, l, tbl, pos).astype(h.dtype)
            profiler.global_stat.add_count(
                "dsa/walk_calls" if walks else "dsa/gather_calls", 1)
            h, stats = finish(layer_p, h, ctx, x_l)
            return h, ck, (cv, ci), stats
        if t == 1 and set(mask) == {"lengths"} and on_walk:
            ctx = paged_attention.paged_attention_decode(
                q[:, :, 0], ck, cv, l, tbl, mask["lengths"],
                window=window)[:, None]
        elif set(mask) == {"first_len"} and on_walk:
            # a verify tick: t positions a row on the page walk
            ctx = paged_attention.paged_attention_verify(
                q, ck, cv, l, tbl, mask["first_len"], window=window)
        elif paged_attention.chunk_supported(q.shape, ck, mask):
            # a prefill chunk: the pages its queries reach, never the table
            ctx = paged_attention.paged_attention_prefill(
                q, ck, cv, l, tbl, mask["q_pos0"], mask["q_len"],
                window=window)
        else:
            m = _gathered_mask(mask)
            if set(mask) == {"first_len"}:
                # a verify tick off the chip: query j of a row sits at
                # position first_len - 1 + j and sees the keys up to itself
                m = dict(causal=True, q_pos0=mask["first_len"] - 1)
            if window is not None:
                tbl, k_pos0 = _window_span(tbl, m, t, ps, window)
                m = dict(m, k_pos0=k_pos0, window=window)
            ctx = reference_attention(q.astype(ck.dtype),
                                      _gather_pages(ck, l, tbl, hkv),
                                      _gather_pages(cv, l, tbl, hkv), **m)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, -1)
        h, stats = finish(layer_p, h, ctx, x_l)
        return h, ck, cv, stats

    return attend


# ---------------------------------------------------------------------------
# A stack held BY ATTENTION KIND (``Block.attn_kinds``): ``kda`` layers, whose
# memory of the sequence is a fixed-size recurrent state a SLOT (no pages),
# beside ``mla`` layers over the latent page pool; the leading
# ``first_dense`` layers a dense SwiGLU, the rest experts. Every group of
# planes leads with the number of ITS layers (``Block.group_index``).
# ---------------------------------------------------------------------------
_L2_EPS = 1e-6


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _kda_low_rank(blk, p, hn, key):
    """hn [b, t, d] through the decay's (``kda_a``) or the output gate's
    (``kda_gate``) projection to [b, t, HK]: one matrix, or the rank-
    ``kda_proj_rank`` pair ``<key>_down_w`` [d, r], ``<key>_up_w`` [r, HK]."""
    if not blk.kda_proj_rank:
        return _mm(blk, "btd,de->bte", hn, p[key + "_w"])
    low = _mm(blk, "btd,dr->btr", hn, p[key + "_down_w"])
    return _mm(blk, "btr,re->bte", low, p[key + "_up_w"])


def _short_conv(conv, u, w, n_valid, bias=None):
    """The causal depthwise convolution of a recurrent layer: u [b, t, C]
    (float32) behind the row's history conv [b, taps - 1, C] (its last
    tokens' u), taps w [taps, C] oldest first (+ ``bias`` [C]) -> (y [b, t,
    C] float32, before its activation; the history after the row's
    ``n_valid`` [b] tokens of this call, in conv's dtype)."""
    t, taps = u.shape[1], w.shape[0]
    full = jnp.concatenate([conv.astype(jnp.float32), u], axis=1)
    w = w.astype(jnp.float32)
    y = sum(full[:, i:i + t] * w[i] for i in range(taps))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    at = n_valid[:, None] + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
    return y, jnp.take_along_axis(full, at[..., None],
                                  axis=1).astype(conv.dtype)


def _kda_project(blk, p, hn, conv, n_valid):
    """The inputs of the delta rule from the normed stream hn [b, t, d]
    and the row's convolution history conv [b, taps - 1, 3HK] (the q | k |
    v projections of its last tokens): -> q, k, v, g [b, t, H, K] float32
    (q, k L2-normalised, q scaled by K^-1/2; g the log-decay, in
    (lower_bound, 0) or, ``kda_decay="softplus"``, -exp(A_log) softplus(a)),
    beta [b, t, H] (doubled under ``kda_neg_eigval``), and the history
    after the row's ``n_valid`` [b] tokens of this call. Tokens beyond
    ``n_valid`` get g = 0 and beta = 0: they leave the state as it is."""
    b, t, _ = hn.shape
    H, K = blk.num_heads, blk.kda_head_dim
    f32 = jnp.float32
    qkv = _mm(blk, "btd,de->bte", hn, p["kda_qkv_w"]).astype(f32)
    y, conv = _short_conv(conv, qkv, p["kda_conv_w"], n_valid)  # [taps, 3HK]
    y = jax.nn.silu(y)
    q, k, v = (y[..., i * H * K:(i + 1) * H * K].reshape(b, t, H, K)
               for i in range(3))
    q, k = _l2norm(q) * K ** -0.5, _l2norm(k)
    a = _kda_low_rank(blk, p, hn, "kda_a").astype(f32) \
        + p["kda_dt_bias"].astype(f32)
    rate = jnp.exp(p["kda_a_log"].astype(f32))[:, None]  # [H, 1]
    a = a.reshape(b, t, H, K)
    if blk.kda_decay == "softplus":
        g = -rate * jax.nn.softplus(a)
    else:
        g = blk.kda_lower_bound * jax.nn.sigmoid(a * rate)
    beta = jax.nn.sigmoid(
        _mm(blk, "btd,dh->bth", hn, p["kda_beta_w"]).astype(f32))
    if blk.kda_neg_eigval:
        beta = 2.0 * beta
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None]
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta, conv


def _kda_layer(blk, p, h, state, conv, l, rows, snaps=None):
    """One ``kda`` layer's attention half against the state arrays, in
    place: h [b, t, d], ``state`` [Lk, slots, H, K, V] float32, ``conv``
    [Lk, slots, taps - 1, 3HK], l the layer's index among the kda layers,
    ``rows`` = (slot [b] or None: row i IS slot i, start [b], n_valid
    [b]). -> (ctx [b, t, H*V]: RMSNorm of each head's read-out times the
    sigmoid output gate, state, conv, snaps). A row whose call starts at
    position 0 reads a ZERO state and history whatever its slot held; a row
    with no valid token (a vacant or prefilling slot of a decode tick, a
    padding row) leaves both as they were. t == 1 is the recurrent step (on
    a chip the ``kda_decode_step`` kernel over the whole state array), a
    chunk the chunked form.

    ``snaps`` (a prefill call of an engine with a snapshot pool) =
    (state_snap [Lk, n, H, K, V], conv_snap [Lk, n, taps - 1, 3HK], from
    [b], take [b]): a row whose ``from`` < n starts from that snapshot row
    instead of its slot's state, and a row whose ``take`` < n leaves a copy
    of the state and history it ends the chunk with in that row — bit for
    bit what its slot holds, so a later row that starts from it computes
    what this row's next chunk does."""
    from ..kernels import kda

    slot, start, n_valid = rows
    b, t, _ = h.shape
    H, K = blk.num_heads, blk.kda_head_dim
    live = n_valid > 0
    fresh = live & (start == 0)
    hn = _norm(blk, h, p["ln1_s"])
    ix = jnp.arange(b) if slot is None else slot
    conv0 = jnp.where(fresh[:, None, None], 0, conv[l, ix])
    if snaps is not None:
        s_snap, c_snap, s_from, s_take = snaps
        n_snap = s_snap.shape[1]
        restore = live & (s_from < n_snap)
        at = jnp.minimum(s_from, n_snap - 1)
        conv0 = jnp.where(restore[:, None, None], c_snap[l, at], conv0)
    q, k, v, g, beta, conv1 = _kda_project(blk, p, hn, conv0, n_valid)
    conv1 = jnp.where(live[:, None, None], conv1, conv0)
    conv = conv.at[l, ix].set(conv1, mode="drop")
    if slot is None and kda.supported(state, t):
        # (a live decode row never sits at position 0: nothing is fresh)
        o, state = kda.kda_decode_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                       beta[:, 0], state, l, live)
        o = o[:, None]
    else:
        s_old = state[l, ix]
        s0 = jnp.where(fresh[:, None, None, None], 0.0, s_old)
        if snaps is not None:
            s0 = jnp.where(restore[:, None, None, None], s_snap[l, at], s0)
        o, s1 = (kda.kda_recurrent if t == 1 else kda.kda_chunked)(
            q, k, v, g, beta, s0)
        s1 = jnp.where(live[:, None, None, None], s1, s_old)
        state = state.at[l, ix].set(s1, mode="drop")
        if snaps is not None:
            snaps = (s_snap.at[l, s_take].set(s1, mode="drop"),
                     c_snap.at[l, s_take].set(conv1, mode="drop"),
                     s_from, s_take)
    o = _rms(o, p["kda_norm_s"], blk.norm_eps).reshape(b, t, H * K)
    gate = _kda_low_rank(blk, p, hn, "kda_gate")
    if blk.kda_proj_rank:
        gate = gate + p["kda_gate_b"].astype(gate.dtype)
    return (o * jax.nn.sigmoid(gate)).astype(h.dtype), state, conv, snaps


def _mamba_project(blk, p, hn, conv, n_valid):
    """The inputs of the Mamba-2 recurrence from the normed stream hn [b,
    t, d] and the row's convolution history conv [b, taps - 1, x | B | C
    columns] (its last tokens' projections): -> z [b, t, H*P] (the gate's
    input), x [b, t, H, P], B, C [b, t, G, N], dt [b, t, H] (softplus(. +
    dt_bias)), g [b, t, H] (the log-decay -exp(A_log) dt), all float32, and
    the history after the row's ``n_valid`` [b] tokens of this call. Tokens
    beyond ``n_valid`` get dt = 0 and g = 0: they leave the state as it
    is."""
    b, t, _ = hn.shape
    H, P, G, N = (blk.mamba_heads, blk.mamba_head_dim, blk.mamba_groups,
                  blk.mamba_state)
    d_in, cw = H * P, blk.mamba_conv_width
    f32 = jnp.float32
    zxbcdt = _mm(blk, "btd,de->bte", hn, p["mamba_in_w"]).astype(f32)
    z, xbc, dt = (zxbcdt[..., :d_in], zxbcdt[..., d_in:d_in + cw],
                  zxbcdt[..., d_in + cw:])
    y, conv = _short_conv(conv, xbc, p["mamba_conv_w"], n_valid,
                          bias=p["mamba_conv_b"])       # [taps, x | B | C]
    y = jax.nn.silu(y)
    x = y[..., :d_in].reshape(b, t, H, P)
    B = y[..., d_in:d_in + G * N].reshape(b, t, G, N)
    C = y[..., d_in + G * N:].reshape(b, t, G, N)
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < n_valid[:, None]
    dt = jnp.where(valid[..., None],
                   jax.nn.softplus(dt + p["mamba_dt_bias"].astype(f32)), 0.0)
    g = -jnp.exp(p["mamba_a_log"].astype(f32)) * dt
    return z, x, B, C, dt, g, conv


def _mamba_layer(blk, p, h, state, conv, l, rows):
    """One ``mamba2`` layer's mixer against the state arrays, in place: h
    [b, t, d], ``state`` [Lm, slots, H, P, N] float32, ``conv`` [Lm, slots,
    taps - 1, x | B | C columns], l the layer's index among the mamba2
    layers, ``rows`` as ``_kda_layer`` takes them. -> (ctx [b, t, H*P]: the
    read-out plus the D skip, gated by silu(z) and THEN RMSNorm'd over each
    group's channels, state, conv). A row whose call starts at position 0
    reads a ZERO state and history whatever its slot held; a row with no
    valid token leaves both as they were. t == 1 is the recurrent step (on
    a chip the ``mamba2_decode_step`` kernel over the whole state array), a
    chunk the chunked (SSD) form."""
    from ..kernels import mamba2

    slot, start, n_valid = rows
    b, t, _ = h.shape
    H, P, G = blk.mamba_heads, blk.mamba_head_dim, blk.mamba_groups
    live = n_valid > 0
    fresh = live & (start == 0)
    hn = _norm(blk, h, p["ln1_s"])
    ix = jnp.arange(b) if slot is None else slot
    conv0 = jnp.where(fresh[:, None, None], 0, conv[l, ix])
    z, x, B, C, dt, g, conv1 = _mamba_project(blk, p, hn, conv0, n_valid)
    conv1 = jnp.where(live[:, None, None], conv1, conv0)
    conv = conv.at[l, ix].set(conv1, mode="drop")
    if slot is None and mamba2.supported(state, t):
        # (a live decode row never sits at position 0: nothing is fresh)
        y, state = mamba2.mamba2_decode_step(
            x[:, 0] * dt[:, 0, :, None], jnp.exp(g[:, 0]), B[:, 0], C[:, 0],
            state, l, live)
        y = y[:, None]
    else:
        s_old = state[l, ix]
        s0 = jnp.where(fresh[:, None, None, None], 0.0, s_old)
        if t == 1:
            y, s1 = mamba2.mamba2_recurrent(x, dt, g, B, C, s0)
        else:
            y, s1 = mamba2.mamba2_chunked(x, dt, g, B, C, s0,
                                          block=blk.mamba_chunk)
        s1 = jnp.where(live[:, None, None, None], s1, s_old)
        state = state.at[l, ix].set(s1, mode="drop")
    y = y + p["mamba_d"].astype(jnp.float32)[:, None] * x
    y = y.reshape(b, t, H * P) * jax.nn.silu(z)         # the gate, THEN the norm
    y = _rms(y.reshape(b, t, G, -1),
             p["mamba_norm_s"].reshape(G, -1), blk.norm_eps)
    return y.reshape(b, t, H * P).astype(h.dtype), state, conv


def _scan_kind_layers(blk, params, h, pool, table, page_id, page_row, pos0,
                      mask, states, rows, pool_v=None, snaps=None):
    """``_scan_paged_layers`` for a stack held by attention kind: h [b, t,
    d] through the layers with the page pool(s) of the ONE kind that
    caches tokens (the latent layers' [Lmla, N, ps, W], or the ``gqa``
    layers' K and V pools [Lgqa, N, ps, Hkv*dh]: ``pool_v``) AND the
    slot-state arrays (``states``: name -> [Lk, slots, ..]) as the loop's
    in-place carry. Layer l runs its kind's attention half (``kda``:
    ``_kda_layer`` against the state; ``mamba2``: ``_mamba_layer`` against
    its own; ``mla``: ``_mla_paged_step`` against the pool; ``gqa``:
    ``_paged_layer_step``, the paged grouped-query step of every K/V stack,
    without rotation and with the channel gate) and its FFN kind (dense
    SwiGLU below ``first_dense``, experts after), each on the planes of
    ITS group at the layer's index within the group; a position that is
    HALF a block (``Block.layer_parts``: a mixer alone, a feed-forward
    alone) runs that half and its one norm. The
    periods that hold a dense layer are unrolled (a prologue: their
    positions differ from the later periods'), the whole periods after
    them run under ONE ``lax.scan`` with the period's positions unrolled
    in its body. ``snaps``: the snapshot rows of a prefill call
    (``_kda_layer``), carried like the states. -> (h, pool, pool_v, states,
    snaps, (counts [Lexp, E], router prob mean [Lexp, E]))."""
    b, t, _ = h.shape
    if blk.residual == "mhc":   # X^0: the embedding in every stream
        h = jnp.broadcast_to(h.astype(jnp.float32)[:, :, None, :],
                             (b, t, blk.hc_mult, h.shape[-1]))
    kinds = blk.layer_parts     # (mixer or None, has an FFN) a position
    P = len(kinds)
    # norm 1 leads with the layers that have a mixer
    n_layers = params["ln1_s"].shape[0] * P // sum(
        1 for mixer, _ in kinds if mixer)
    index = blk.group_index(n_layers)
    group_of = {key: Block.plane_group(key) for key in params}
    whole = {k: params[k] for k in _RESIDENT_PLANES if k in params}
    # (``halves`` = (dense, ffn): what the layer's feed-forward half is)
    if "mla" in blk.mixers:
        paged = _mla_paged_step(
            blk, b, t, lambda p, hh: _mla_latent(blk, p, hh, pos0), mask,
            lambda p, hh, ctx, halves: _attn_out_ffn(
                blk, p, halves[2], _head_gate(blk, p, hh, ctx),
                dense=halves[0], ffn=halves[1], mix=halves[3]))
    else:
        paged = _paged_layer_step(
            b, t, pool.shape[2],
            lambda p, hh: _attn_proj(blk, {**p, "qkv_w": p["gqa_qkv_w"]},
                                     hh, pos0=pos0, rope=False),
            mask, lambda p, hh, ctx, halves: _attn_out_ffn(
                blk, p, halves[2], _channel_gate(blk, p, hh, ctx),
                out_key="gqa_out_w", dense=halves[0], ffn=halves[1],
                mix=halves[3]))
    paged_kind = "mla" if "mla" in blk.mixers else "gqa"
    ix = (page_id.reshape(b, t), page_row.reshape(b, t))

    def layer(carry, p, kind, dense, at):
        """One layer; ``at``: group -> the layer's index in the group."""
        hh, pool, pool_v, st, sn = carry
        mixer, ffn = kind
        if whole and ffn and not dense:
            p = {**p, **whole, "layer": at["experts"]}
        # the mixer reads u off the carry and writes back through ``mix``
        u, mix = _res_read(blk, p, hh, "hc1") if mixer else (hh, None)
        if mixer == "kda":
            ctx, s_new, c_new, sn = _kda_layer(
                blk, p, u, st["KdaState"], st["KdaConv"], at["kda"], rows,
                sn)
            st = {**st, "KdaState": s_new, "KdaConv": c_new}
            hh, stats = _attn_out_ffn(blk, p, hh, ctx, out_key="kda_out_w",
                                      dense=dense, ffn=ffn, mix=mix)
        elif mixer == "mamba2":
            ctx, s_new, c_new = _mamba_layer(
                blk, p, u, st["MambaState"], st["MambaConv"], at["mamba2"],
                rows)
            st = {**st, "MambaState": s_new, "MambaConv": c_new}
            hh, stats = _attn_out_ffn(blk, p, hh, ctx, out_key="mamba_out_w",
                                      dense=dense, ffn=ffn, mix=mix)
        elif mixer is None:             # the feed-forward alone
            hh, stats = _attn_out_ffn(blk, p, hh, None, dense=dense,
                                      mixer=False)
        else:
            hh, pool, pool_v, stats = paged(u, pool, pool_v, at[paged_kind],
                                            p, (dense, ffn, hh, mix), table,
                                            *ix)
        return (hh, pool, pool_v, st, sn), stats

    def planes(l):      # layer l's own planes (python l)
        return {k: v[index[group_of[k]][l]] for k, v in params.items()
                if k not in whole and index[group_of[k]][l] is not None}

    head = min(-(-blk.first_dense // P) * P, n_layers)
    carry, stats = (h, pool, pool_v, states, snaps), []
    for l in range(head):
        carry, st_l = layer(carry, planes(l), kinds[l % P],
                            l < blk.first_dense,
                            {g: index[g][l] for g in index})
        if st_l is not None:
            stats.append(st_l)
    stats = [tuple(jnp.stack(a) for a in zip(*stats))] if stats else []
    periods = (n_layers - head) // P
    if periods:
        # each group's planes past the prologue, viewed [periods, a
        # period's layers of the group, ..]
        first = {g: next((i for i in index[g][head:] if i is not None), 0)
                 for g in index}
        per = {g: sum(1 for i in index[g][head:head + P] if i is not None)
               for g in index}
        xs = {k: v[first[group_of[k]]:].reshape(
            (periods, per[group_of[k]]) + v.shape[1:])
            for k, v in params.items()
            if k not in whole and per[group_of[k]]}

        def period(c, inp):
            x_p, n = inp
            ys = []
            for j in range(P):
                at_j = {g: index[g][head + j] for g in index}
                p_j = {k: v[at_j[group_of[k]] - first[group_of[k]]]
                       for k, v in x_p.items()
                       if at_j[group_of[k]] is not None}
                c, y = layer(c, p_j, kinds[j], False,
                             {g: None if at_j[g] is None
                              else at_j[g] + n * per[g] for g in index})
                if y is not None:       # (a mixer alone reports nothing)
                    ys.append(y)
            return c, tuple(jnp.stack(a) for a in zip(*ys))

        carry, ys = jax.lax.scan(period, carry, (
            xs, jnp.arange(periods, dtype=jnp.int32)))
        stats.append(tuple(a.reshape((-1,) + a.shape[2:]) for a in ys))
    h, pool, pool_v, states, snaps = carry
    if blk.residual == "mhc":   # the streams' sum goes to the final norm
        h = jnp.sum(h, axis=2)
    stats = tuple(jnp.concatenate(a) for a in zip(*stats))
    return h, pool, pool_v, states, snaps, stats


def _head_gate(blk, p, h, ctx):
    """The latent attention's head-wise output gate: head n's context
    times sigmoid(norm 1(h) . w_n) (``attn_gate="head"``)."""
    if blk.attn_gate != "head":
        return ctx
    b, t, _ = ctx.shape
    hn = _norm(blk, h, p["ln1_s"], p.get("ln1_b"))
    gate = jax.nn.sigmoid(_mm(blk, "btd,dh->bth", hn, p["attn_gate_w"]))
    return (ctx.reshape(b, t, blk.num_heads, -1)
            * gate[..., None].astype(ctx.dtype)).reshape(b, t, -1)


def _channel_gate(blk, p, h, ctx):
    """The ``gqa`` kind's output gate: every channel of the context times
    sigmoid(norm 1(h) W_g) (``attn_gate="channel"``, arXiv:2505.06708)."""
    if blk.attn_gate != "channel":
        return ctx
    hn = _norm(blk, h, p["ln1_s"], p.get("ln1_b"))
    gate = jax.nn.sigmoid(_mm(blk, "btd,de->bte", hn, p["gqa_gate_w"]))
    return ctx * gate.astype(ctx.dtype)


def _draft_block(blk, ins, h, t_next, pool, table, page_id, page_row, pos0,
                 mask):
    """The drafting block (``Block.draft_block``; lm_spec.py has its
    equations) over the t positions a row brings: h [b, t, d] the stack's
    output BEFORE the final norm, t_next [b, t] the token that follows each
    position -> (g [b, t, d] the block's output, the full-attention pools
    with its K/V rows written at (its layer, page_id, page_row), its expert
    layer's stats). Its layer is the LAST of the full-attention pools
    (``LMSpec.pool_layers``): same table, same targets as the stack's
    full-attention layers. ``mask``: the call's own (a chunk's block-causal
    one, a verify tick's ``first_len``), so a tick's two positions walk the
    pages as the stack's did."""
    mblk = blk.draft()
    b, t, _ = h.shape
    cache_k, cache_v = pool
    p = {key: single(ins, DRAFT_SLOT_PREFIX + slot)
         for slot, key in mblk.stack_slots().items()}
    # the block's planes are a stack of ONE layer: the resident expert
    # planes stay whole (``moe_topk`` addresses layer 0 of them)
    layer_p = {k: (v if k in _RESIDENT_PLANES else v[0])
               for k, v in p.items()}
    layer_p["layer"] = jnp.zeros((), jnp.int32)
    own = {key: single(ins, slot) for slot, key in DRAFT_PLANES.items()}
    emb = _embed_rows(single(ins, "TokEmb"), t_next)
    u = jnp.concatenate([_norm(mblk, h, own["norm_h_s"]),
                         _norm(mblk, emb, own["norm_e_s"])], axis=-1)
    u = _mm(mblk, "bte,ed->btd", u, own["proj_w"])
    attend = _paged_layer_step(
        b, t, cache_k.shape[2],
        lambda lp, hh: _attn_proj(mblk, lp, hh, pos0=pos0), mask,
        lambda lp, hh, ctx, _x: _attn_out_ffn(mblk, lp, hh, ctx))
    g, cache_k, cache_v, stats = attend(
        u, cache_k, cache_v, cache_k.shape[0] - 1, layer_p, None, table,
        page_id.reshape(b, t), page_row.reshape(b, t))
    return g, (cache_k, cache_v), stats


def _draft_logits(blk, ins, g):
    """The shared head over the drafting block's output rows g [n, d]."""
    return _logits_fn(single(ins, "MtpHeadNormS"), None,
                      single(ins, "HeadW"), blk.draft())(g)


def _with_draft_stats(stats, more):
    """The stack's expert stats ([Lexp, E] each) with the drafting
    block's layer appended (its rows count as one more layer's)."""
    return tuple(jnp.concatenate([a, m[None]]) for a, m in zip(stats, more))


def _paged_outs(blk, stats, win, **outs):
    """The paged ops' outputs; an expert block adds ExpertCounts [L, E]
    int32 (rows each expert took in each layer of THIS call) so the
    engine's counters ride the tick's existing fetch; a spec with window
    layers adds their pools."""
    if blk.is_moe:
        outs["ExpertCounts"] = stats[0]
    if win is not None:
        outs["CacheKW"], outs["CacheVW"] = win
    for slot in _POOL_SLOTS:    # a latent block's one pool; no indexer
        if outs.get(slot, 0) is None:
            del outs[slot]
    return out(**outs)


def _state_ins(blk, ins):
    """The slot-state arrays the spec lists (``Block.slot_state``), by
    slot name: "KdaState" "KdaConv" | "MambaState" "MambaConv"; {} for a
    spec without any."""
    return {name: single(ins, name) for name, _, _ in blk.slot_state(0)}


#: the window kind's pools and table, beside CacheK / CacheV / BlockTable
#: (which a ``layer_pattern`` spec reads as its full-attention kind's)
_WINDOW_SLOTS = ("CacheKW", "CacheVW", "BlockTableW")
#: absent for a latent block, whose cache is the one pool under CacheK
_POOL_SLOTS = ("CacheV", "CacheIndex")


def _paged_project(blk, pos0):
    """``project`` of ``_scan_paged_layers`` for the spec's attention."""
    if blk.is_mla:
        return lambda p, h, rope=None: _mla_latent(blk, p, h, pos0)
    return lambda p, h, rope=None: _attn_proj(blk, p, h, pos0=pos0,
                                              rope=rope)


def _window_ins(blk, ins, targets):
    """``win`` of ``_scan_paged_layers`` for a spec with window layers:
    the kind's pools, its table and the (page, row) targets of the call's
    tokens under that table (``targets(table)``); None otherwise."""
    if not blk.has_window:
        return None
    table_w = single(ins, "BlockTableW").astype(jnp.int32)
    return (single(ins, "CacheKW"), single(ins, "CacheVW"), table_w,
            *targets(table_w))


@register_op("transformer_stack_paged_prefill",
             optional_inputs=(_LM_OPTIONAL + _SAMPLING_SLOTS + _WINDOW_SLOTS
                              + _POOL_SLOTS + STATE_SLOTS + ("StateSlot",)
                              + SNAPSHOT_SLOTS + DRAFT_SLOTS
                              + ("DraftNext", "PosIds", "MediaRow", "Pixels")
                              + VISION_SLOTS),
             needs_rng=lambda attrs: (attrs.get("temperature") or 0) > 0)
def transformer_stack_paged_prefill(attrs, ins, rng=None):
    """Prefill ONE CHUNK of each row's prompt into its block-table pages.

    Chunk [b, Tc] int (right-padded), StartPos [b] int32 (absolute
    sequence position of each row's first chunk token — 0 for a plain
    prefill, the shared-prefix length for a prefix-cache hit, k*chunk for
    the k-th chunk of a streaming long prompt), Lengths [b] int32 (valid
    tokens in THIS chunk, 0..Tc; 0 marks a padding row), BlockTable
    [b, P] int32 (the row's full logical->physical page map; padding
    entries 0), CacheK/CacheV [L, N, ps, Hkv*dh] page pools, plus the
    shared LM weights. attrs carry ``page_size`` next to the decode-op
    set. Returns NextTok [b] — argmax/sample from each row's LAST VALID
    chunk position (the first generated token when this chunk completes
    the prompt; garbage otherwise) — and the pools with the chunk's K/V
    scattered into rows StartPos..StartPos+Lengths-1 of each row's pages.

    The pools ride the layer loop as its in-place carry
    (``_scan_paged_layers``): a chunk writes b*Tc token rows per layer
    and pool and, on a chip, reads the pages each row's queries REACH in
    one kernel a layer (``paged_attention_prefill``: to the page of the
    chunk's last real key, on a window layer from the window's page; a
    latent pool: the same walk over its one pool, ``paged_mla_prefill``);
    elsewhere (the CPU, a head narrower than the lanes) it gathers b
    table-width contexts. Nothing it moves is proportional to
    N, and no page outside the written (layer, page, row) cells changes.

    Queries attend the row's WHOLE context block-causally (chunk token at
    absolute position p sees cached position j iff j <= p), so a later
    chunk attends every earlier chunk's pages and a shared-prefix row
    attends the shared pages it never prefilled — token-exact vs the
    dense one-shot prefill. Pages beyond a row's extent sit at positions
    > p: the walk never reads them, the gathered form masks them by the
    same rule. A padding query's context is unspecified (the walk leaves
    zeros, the gathered form attends it): nothing reads its row.

    Optional per-row sampling plane (Temperature/TopK/TopP/Seed/Step [b]
    + Mask [b, V]): when fed, NextTok comes from
    ``kernels.sampling.sample_rows`` — each row's policy and seed ride
    the request, the scope RNG is never consumed, and the token is a
    pure function of (request, seed, step). ``emit_topk`` > 0 adds
    TopV/TopI [b, emit_topk] (masked top-k log-probs of the last valid
    position) — the beam-search expansion plane.

    A spec with a drafting block (``draft_block``): the block runs over the
    chunk too, position i with the token that FOLLOWS it — the chunk's
    next one, after its last valid token DraftNext [b] (the prompt's next
    token; below 0: the token this call samples, the prompt ends here) —
    and writes its K/V rows into the last layer of the full-attention
    pools. NextTok is then [b, 2]: the sampled token and the block's
    draft of the one after it (argmax; meaningful where the prompt ends);
    TopV/TopI, where compiled in, [2 b, emit_topk]: the block's top-k
    log-probs at the same position below the stack's rows.
    """
    # per-row sampling slots, read via _row_sampling/_maybe_topk:
    # "Temperature", "TopK", "TopP", "Seed", "Step", "Mask"
    chunk = single(ins, "Chunk")
    start = single(ins, "StartPos").astype(jnp.int32)
    lengths = single(ins, "Lengths").astype(jnp.int32)
    table = single(ins, "BlockTable").astype(jnp.int32)
    cache_k = single(ins, "CacheK")
    cache_v = maybe(ins, "CacheV")      # None: a latent block's one pool
    # [L, N, ps / index_pool, index_dim]: a sparse latent layer's pooled
    # indexer keys, under the latent pool's page ids
    cache_i = maybe(ins, "CacheIndex")
    tok_emb = single(ins, "TokEmb")
    pos_emb = maybe(ins, "PosEmb")
    ln_s, ln_b = single(ins, "FinalLnS"), maybe(ins, "FinalLnB")
    head_w = single(ins, "HeadW")
    blk = Block.from_attrs(attrs)
    # optional stack slots (a block leaves out what it has no use for),
    # read via _stack_params: "Ln1B" "Ln2B" "QNormS" "KNormS" "FfW1"
    # "FfB1" "FfW2" "FfB2" "RouterW" "MoeGateW" "MoeUpW" "MoeDownW"
    # "QkvW" | "QaW" "QaNormS" "QbW" "KvaW" "KvaNormS" "KvbW";
    # "SharedGateW" "SharedUpW" "SharedDownW" and "FinalLnB"
    # a stack held by attention kind (``Block._slots_by_kind``): "OutW"
    # "QW" "AttnGateW" "RouterB" "KdaQkvW" "KdaConvW" "KdaAW" "KdaDtBias"
    # "KdaALog" "KdaBetaW" "KdaGateW" "KdaNormS" "KdaOutW" "DenseGateW"
    # "DenseUpW" "DenseDownW" "KdaADownW" "KdaAUpW" "KdaGateDownW"
    # "KdaGateUpW" "KdaGateB" "GqaQkvW" "GqaGateW" "GqaOutW"
    # "MambaInW" "MambaConvW" "MambaConvB" "MambaDtBias" "MambaALog" "MambaD"
    # "MambaNormS" "MambaOutW" "MoeLatentDownW" "MoeLatentUpW"
    # a sparse latent layer's indexer and the residual streams' mixes:
    # "IdxQW" "IdxKW" "IdxKNormS" "IdxKNormB" "IdxHeadW" "Hc1W" "Hc1Alpha"
    # "Hc1B" "Hc2W" "Hc2Alpha" "Hc2B"
    # and its slot-state arrays, via ``_state_ins``: "KdaState" "KdaConv"
    # "MambaState" "MambaConv"
    # (a prefill row's slot: "StateSlot")
    params = _stack_params(blk, ins)
    b, Tc = chunk.shape
    ps = cache_k.shape[2]
    P = table.shape[1]
    # absolute positions + per-token page targets (padding -> scrap 0)
    pos = start[:, None] + jnp.arange(Tc, dtype=jnp.int32)[None, :]
    valid = jnp.arange(Tc, dtype=jnp.int32)[None, :] < lengths[:, None]
    entry = jnp.clip(pos // ps, 0, P - 1)

    def page_of(table):
        return jnp.where(valid, jnp.take_along_axis(table, entry, axis=1), 0)

    page_id = page_of(table)
    page_row = jnp.where(valid, pos % ps, 0)
    x = _embed_rows(tok_emb, chunk)
    if pos_emb is not None:
        x = x + pos_emb[jnp.clip(pos, 0, pos_emb.shape[0] - 1)]
    if blk.vision_heads:
        # a tower in front of the stack: "Pixels" [b, Fc, S, S, 3] uint8 the
        # frames the chunk touches, "MediaRow" [b, Tc] the merged row of
        # those a placeholder position takes (-1: the embedding's), and the
        # tower's parameters (``vision_tower.VISION_SLOTS``): "VisPatchW"
        # "VisPatchB" "VisPosEmb" "VisLn1S" "VisLn1B" "VisQkvW" "VisQkvB"
        # "VisOutW" "VisOutB" "VisLn2S" "VisLn2B" "VisFc1W" "VisFc1B"
        # "VisFc2W" "VisFc2B" "VisPostLnS" "VisPostLnB" "VisMergeLnS"
        # "VisMergeLnB" "VisMergeW1" "VisMergeB1" "VisMergeW2" "VisMergeB2"
        x = splice_media(blk, functools.partial(_mm, blk), vision_params(ins),
                         x, single(ins, "Pixels"),
                         single(ins, "MediaRow").astype(jnp.int32))
    # "PosIds" [b, 3 Tc] int32: under ``rope="mrope"`` every token's
    # (temporal, height, width) id; pages, causality and the selection keep
    # the sequence index (``start``)
    rot_pos = (single(ins, "PosIds").astype(jnp.int32).reshape(b, Tc, 3)
               if blk.rope == "mrope" else start)
    if blk.sparse_kv:       # the indexer's pool rides beside the V pool
        cache_v = (cache_v, cache_i)
    states = {}
    if blk.attn_kinds:
        # "StateSlot" [b] int32: the slot whose state each row reads and
        # leaves advanced (a padding row: any index beyond the slots)
        # an engine with a snapshot pool: "KdaStateSnap" "KdaConvSnap"
        # [layers, n_snapshots, ..] and, a row, the snapshot row its state
        # starts from / is copied into ("SnapFrom" "SnapTake": a value
        # beyond the rows for neither)
        snaps = None
        if maybe(ins, "SnapFrom") is not None:
            snaps = (single(ins, "KdaStateSnap"), single(ins, "KdaConvSnap"),
                     single(ins, "SnapFrom").astype(jnp.int32),
                     single(ins, "SnapTake").astype(jnp.int32))
        # (beside the one paged kind's first pool: its V pool, or a sparse
        # latent layer's pooled indexer keys)
        h, cache_k, pool_v, states, snaps, stats = _scan_kind_layers(
            blk, params, x, cache_k, table, page_id, page_row, start,
            chunk_mask(start, lengths), _state_ins(blk, ins),
            (single(ins, "StateSlot").astype(jnp.int32), start, lengths),
            pool_v=cache_i if blk.index_topk else cache_v, snaps=snaps)
        cache_v, cache_i = (None, pool_v) if blk.index_topk else (pool_v,
                                                                  None)
        if snaps is not None:
            states = {**states, "KdaStateSnap": snaps[0],
                      "KdaConvSnap": snaps[1]}
        win = None
    else:
        # "CacheKW" "CacheVW" "BlockTableW": the window kind (_window_ins)
        h, cache_k, cache_v, stats, win = _scan_paged_layers(
            params, x, cache_k, cache_v, table, page_id, page_row,
            _paged_project(blk, rot_pos), chunk_mask(start, lengths),
            lambda p, h, ctx, _x_l: _attn_out_ffn(
                blk, p, h, ctx, dense="dense_gate_w" in p), blk=blk,
            win=_window_ins(blk, ins, lambda tw: (page_of(tw), page_row)))
        if blk.sparse_kv:
            cache_v, cache_i = cache_v
    at_last = jnp.clip(lengths, 1, Tc) - 1
    last = h[jnp.arange(b), at_last]  # [b, d]
    logits = _logits_fn(ln_s, ln_b, head_w, blk)(last)
    nxt = _pick_rows(attrs, ins, rng, head_w.shape[1], logits)
    nxt = nxt.astype(chunk.dtype)
    draft_logits = None
    if blk.draft_block:
        # "DraftNext": the token after the chunk's last
        # a drafting block's slots (``lm_spec.DRAFT_SLOTS``), read via
        # ``_draft_block`` / ``_draft_logits``: "MtpProjW" "MtpNormHS"
        # "MtpNormES" "MtpHeadNormS" and its one-layer stack's "MtpLn1S"
        # "MtpQkvW" "MtpQNormS" "MtpKNormS" "MtpOutW" "MtpLn2S" "MtpRouterW"
        # "MtpMoeGateW" "MtpMoeUpW" "MtpMoeDownW" "MtpSharedGateW"
        # "MtpSharedUpW" "MtpSharedDownW"
        after = single(ins, "DraftNext").astype(chunk.dtype)
        t_next = jnp.concatenate([chunk[:, 1:], chunk[:, :1]], axis=1)
        t_next = t_next.at[jnp.arange(b), at_last].set(
            jnp.where(after >= 0, after, nxt))
        g, (cache_k, cache_v), more = _draft_block(
            blk, ins, h, t_next, (cache_k, cache_v), table, page_id,
            page_row, start, chunk_mask(start, lengths))
        stats = _with_draft_stats(stats, more)
        draft_logits = _draft_logits(blk, ins, g[jnp.arange(b), at_last])
        draft = jnp.argmax(draft_logits, axis=-1)
        nxt = jnp.stack([nxt, draft.astype(nxt.dtype)], axis=1)
    outs = _paged_outs(blk, stats, win, NextTok=nxt, CacheK=cache_k,
                       CacheV=cache_v, CacheIndex=cache_i, **states)
    return _maybe_topk(attrs, ins, logits, outs, draft_logits)


@register_op("transformer_stack_paged_decode",
             optional_inputs=(_LM_OPTIONAL + _SAMPLING_SLOTS + _WINDOW_SLOTS
                              + _POOL_SLOTS + STATE_SLOTS + DRAFT_SLOTS
                              + ("Draft", "RopeOffset")),
             needs_rng=lambda attrs: (attrs.get("temperature") or 0) > 0)
def transformer_stack_paged_decode(attrs, ins, rng=None):
    """One decode step over every slot's paged context.

    Tok [S] int (the pending token per slot), Pos [S] int32 (its sequence
    position == rows already cached for the slot), BlockTable [S, P]
    int32 (per-slot page map; vacant slots feed all-zeros + Pos 0, so
    their write lands in the scrap page), CacheK/CacheV [L, N, ps,
    Hkv*dh] page pools, plus the shared LM weights. Returns NextTok [S]
    and the pools with each slot's token K/V written at page
    BlockTable[s, Pos//ps] row Pos%ps.

    The pools ride the layer loop as its in-place carry
    (``_scan_paged_layers``): a tick writes S token rows per layer and
    pool and, on a chip, reads the pages each slot HOLDS (``Pos // ps +
    1``; the scrap page for a vacant slot) in one paged-attention kernel
    a layer — the weights plus the K/V of the tokens in flight are what
    a tick moves. Elsewhere (CPU, a row not lane-aligned) it gathers S
    table-width contexts [P*ps, Hkv*dh]. No
    layer of the pool is sliced, re-laid out, restacked or copied.

    The slot axis is the batch axis and the table width is static, so the
    compiled shape never depends on occupancy or sequence lengths — the
    one-compile steady state of continuous batching, over a pool sized by
    TOKENS IN FLIGHT.

    Optional per-row sampling plane (Temperature/TopK/TopP/Seed/Step [S]
    + Mask [S, V]): per-REQUEST decode policy inside the one compiled
    step — greedy, temperature, top-k, top-p, and grammar-masked rows
    mix freely, and each row's token depends only on (its context, its
    policy, its seed, its step). ``emit_topk`` > 0 adds TopV/TopI
    [S, emit_topk] — beam hypotheses expand from these without a second
    model pass.

    A spec with a drafting block (``draft_block``) runs a VERIFY tick
    (``_verify_tick``): two positions a slot, one or two tokens out.
    """
    # per-row sampling slots, read via _row_sampling/_maybe_topk:
    # "Temperature", "TopK", "TopP", "Seed", "Step", "Mask"
    tok = single(ins, "Tok")
    pos = single(ins, "Pos").astype(jnp.int32)
    table = single(ins, "BlockTable").astype(jnp.int32)
    cache_k = single(ins, "CacheK")
    cache_v = maybe(ins, "CacheV")      # None: a latent block's one pool
    # [L, N, ps / index_pool, index_dim]: a sparse latent layer's pooled
    # indexer keys, under the latent pool's page ids
    cache_i = maybe(ins, "CacheIndex")
    tok_emb = single(ins, "TokEmb")
    pos_emb = maybe(ins, "PosEmb")
    ln_s, ln_b = single(ins, "FinalLnS"), maybe(ins, "FinalLnB")
    head_w = single(ins, "HeadW")
    blk = Block.from_attrs(attrs)
    # optional stack slots (a block leaves out what it has no use for),
    # read via _stack_params: "Ln1B" "Ln2B" "QNormS" "KNormS" "FfW1"
    # "FfB1" "FfW2" "FfB2" "RouterW" "MoeGateW" "MoeUpW" "MoeDownW"
    # "QkvW" | "QaW" "QaNormS" "QbW" "KvaW" "KvaNormS" "KvbW";
    # "SharedGateW" "SharedUpW" "SharedDownW" and "FinalLnB"
    # a stack held by attention kind (``Block._slots_by_kind``): "OutW"
    # "QW" "AttnGateW" "RouterB" "KdaQkvW" "KdaConvW" "KdaAW" "KdaDtBias"
    # "KdaALog" "KdaBetaW" "KdaGateW" "KdaNormS" "KdaOutW" "DenseGateW"
    # "DenseUpW" "DenseDownW" "KdaADownW" "KdaAUpW" "KdaGateDownW"
    # "KdaGateUpW" "KdaGateB" "GqaQkvW" "GqaGateW" "GqaOutW"
    # "MambaInW" "MambaConvW" "MambaConvB" "MambaDtBias" "MambaALog" "MambaD"
    # "MambaNormS" "MambaOutW" "MoeLatentDownW" "MoeLatentUpW"
    # a sparse latent layer's indexer and the residual streams' mixes:
    # "IdxQW" "IdxKW" "IdxKNormS" "IdxKNormB" "IdxHeadW" "Hc1W" "Hc1Alpha"
    # "Hc1B" "Hc2W" "Hc2Alpha" "Hc2B"
    # and its slot-state arrays, via ``_state_ins``: "KdaState" "KdaConv"
    # "MambaState" "MambaConv"
    # (a prefill row's slot: "StateSlot")
    params = _stack_params(blk, ins)
    S = tok.shape[0]
    if S != table.shape[0]:
        raise ValueError(f"Tok has {S} slots but the block table holds "
                         f"{table.shape[0]}")
    ps = cache_k.shape[2]
    P = table.shape[1]
    if blk.draft_block:
        # "Draft": the tick's second position
        # a drafting block's slots (``lm_spec.DRAFT_SLOTS``), read via
        # ``_draft_block`` / ``_draft_logits``: "MtpProjW" "MtpNormHS"
        # "MtpNormES" "MtpHeadNormS" and its one-layer stack's "MtpLn1S"
        # "MtpQkvW" "MtpQNormS" "MtpKNormS" "MtpOutW" "MtpLn2S" "MtpRouterW"
        # "MtpMoeGateW" "MtpMoeUpW" "MtpMoeDownW" "MtpSharedGateW"
        # "MtpSharedUpW" "MtpSharedDownW"
        return _verify_tick(attrs, ins, blk, params, tok, pos, table,
                            cache_k, cache_v)
    pos = jnp.clip(pos, 0, P * ps - 1)
    x = _embed_rows(tok_emb, tok)
    if pos_emb is not None:
        x = x + pos_emb[jnp.clip(pos, 0, pos_emb.shape[0] - 1)]
    h1 = x[:, None, :]  # [S, 1, d]
    srange = jnp.arange(S)
    page_id = table[srange, pos // ps]  # [S]
    page_row = pos % ps
    states = {}
    if blk.attn_kinds:
        # row s IS slot s; a live row's token lands in a page of its own
        # (a vacant or still-prefilling slot rides on the scrap page and
        # leaves its state alone)
        h1, cache_k, pool_v, states, _, stats = _scan_kind_layers(
            blk, params, h1, cache_k, table, page_id, page_row, pos,
            dict(lengths=pos + 1), _state_ins(blk, ins),
            (None, pos, (page_id != 0).astype(jnp.int32)),
            pool_v=cache_i if blk.index_topk else cache_v)
        cache_v, cache_i = (None, pool_v) if blk.index_topk else (pool_v,
                                                                  None)
        win = None
    else:
        # "CacheKW" "CacheVW" "BlockTableW": the window kind (_window_ins)
        # "RopeOffset" [S] int32: under ``rope="mrope"`` a slot's three ids
        # are its position + this (last id + 1 - sequence length: what its
        # clips shortened the ids by); the cache index stays the position
        rot_pos = pos if blk.rope != "mrope" else jnp.broadcast_to(
            (pos + single(ins, "RopeOffset").astype(jnp.int32))[
                :, None, None], (S, 1, 3))
        h1, cache_k, cache_v, stats, win = _scan_paged_layers(
            params, h1, cache_k,
            (cache_v, cache_i) if blk.sparse_kv else cache_v, table, page_id,
            page_row, _paged_project(blk, rot_pos), dict(lengths=pos + 1),
            lambda p, h, ctx, _x_l: _attn_out_ffn(
                blk, p, h, ctx, dense="dense_gate_w" in p), blk=blk,
            win=_window_ins(blk, ins,
                            lambda tw: (tw[srange, pos // ps], page_row)))
        if blk.sparse_kv:
            cache_v, cache_i = cache_v
    logits = _logits_fn(ln_s, ln_b, head_w, blk)(h1[:, 0])
    nxt = _pick_rows(attrs, ins, rng, head_w.shape[1], logits)
    outs = _paged_outs(blk, stats, win, NextTok=nxt.astype(tok.dtype),
                       CacheK=cache_k, CacheV=cache_v, CacheIndex=cache_i,
                       **states)
    return _maybe_topk(attrs, ins, logits, outs)


def _verify_tick(attrs, ins, blk, params, tok, pos, table, cache_k,
                 cache_v):
    """The decode tick of a spec with a drafting block: every slot brings
    TWO positions, its last committed token Tok [S] at Pos [S] and the
    block's draft of the next one, Draft [S] (below 0: none, the second
    position then writes the scrap page and is never accepted), at Pos + 1.
    The stack runs both (K/V rows of both written, each position seeing the
    keys up to itself: on a chip the page walk, ``paged_attention_verify``),
    the token after Tok is drawn from row 0 exactly as a one-position tick
    draws it (``sample_rows``: a pure function of logits, policy, seed and
    step), and where it EQUALS the draft the token after that is drawn from
    row 1 at step + 1: acceptance by exact match, so the slot emits the
    tokens the same engine without the block would. The drafting block
    then runs over the same two positions with the drawn tokens as their
    successors and leaves the next draft: from row 1 where the draft was
    accepted, from row 0 otherwise (row 1's K/V rows, the stack's and the
    block's, are then overwritten by the next tick, which starts there).

    -> NextTok [S, 3]: the token after Tok, the one after that (-1 unless
    the draft was accepted) and the next draft; TopV / TopI (the beam
    plane, where compiled in) [4 S, k]: rows 2 s and 2 s + 1 the two
    positions of slot s, rows 2 S + 2 s and 2 S + 2 s + 1 the drafting
    block's at the same two."""
    S = tok.shape[0]
    ps, P = cache_k.shape[2], table.shape[1]
    head_w = single(ins, "HeadW")
    plane = _row_sampling(ins)
    if plane is None:
        raise ValueError("a verify tick draws by the per-row sampling "
                         "plane (Temperature .. Step)")
    draft = single(ins, "Draft").astype(jnp.int32)
    has = draft >= 0
    pos = jnp.clip(pos, 0, P * ps - 2)
    poss = pos[:, None] + jnp.arange(2, dtype=jnp.int32)[None, :]   # [S, 2]
    toks = jnp.stack([tok.astype(jnp.int32), jnp.maximum(draft, 0)], axis=1)
    live2 = jnp.stack([jnp.ones_like(has), has], axis=1)

    def targets(tbl):
        page = jnp.take_along_axis(tbl, poss // ps, axis=1)
        return jnp.where(live2, page, 0), poss % ps

    page_id, page_row = targets(table)
    x = _embed_rows(single(ins, "TokEmb"), toks)                    # [S, 2, d]
    # query j of a slot sees the keys below first_len + j
    mask = dict(first_len=pos + 1)
    # "CacheKW" "CacheVW" "BlockTableW": the window kind (_window_ins)
    h, cache_k, cache_v, stats, win = _scan_paged_layers(
        params, x, cache_k, cache_v, table, page_id, page_row,
        _paged_project(blk, pos), mask,
        lambda p, hh, ctx, _x_l: _attn_out_ffn(
            blk, p, hh, ctx, dense="dense_gate_w" in p), blk=blk,
        win=_window_ins(blk, ins, targets))
    logits = _logits_fn(single(ins, "FinalLnS"), maybe(ins, "FinalLnB"),
                        head_w, blk)(h.reshape(2 * S, -1))
    from ..kernels.sampling import sample_rows

    temp, top_k, top_p, seed, step, row_mask = plane
    def twice(a):       # a slot's policy, for both of its rows
        return jnp.repeat(a, 2, axis=0)

    step2 = (step.astype(jnp.int32)[:, None]
             + jnp.arange(2, dtype=jnp.int32)[None, :]).reshape(-1)
    y = sample_rows(logits, twice(temp), twice(top_k), twice(top_p),
                    twice(seed), step2,
                    None if row_mask is None else twice(row_mask))
    y = y.reshape(S, 2).astype(jnp.int32)
    accept = has & (y[:, 0] == draft)
    g, (cache_k, cache_v), more = _draft_block(
        blk, ins, h, y, (cache_k, cache_v), table, page_id, page_row, pos,
        mask)
    draft_logits = _draft_logits(blk, ins, g.reshape(2 * S, -1))
    cand = jnp.argmax(draft_logits, axis=-1).reshape(S, 2).astype(jnp.int32)
    nxt = jnp.stack([y[:, 0], jnp.where(accept, y[:, 1], -1),
                     jnp.where(accept, cand[:, 1], cand[:, 0])], axis=1)
    outs = _paged_outs(blk, _with_draft_stats(stats, more), win,
                       NextTok=nxt.astype(tok.dtype), CacheK=cache_k,
                       CacheV=cache_v)
    return _maybe_topk(attrs, {**ins, "Mask": [twice(row_mask)]}
                       if row_mask is not None else ins, logits, outs,
                       draft_logits)


@register_op("kv_cache_page_copy", optional_inputs=_POOL_SLOTS)
def kv_cache_page_copy(attrs, ins):
    """Copy whole KV pages inside the pools: the copy-on-write step.

    Src [n] int32, Dst [n] int32 (distinct destination pages),
    CacheK/CacheV [L, N, ps, Hkv*dh]. Writes pool[:, Dst[i]] =
    pool[:, Src[i]] for both pools and echoes Dst as Ok [n] (a fetchable
    witness — the real outputs are the donated pool updates). The serving
    engine runs this when a sequence is about to write into a page whose
    refcount > 1 (a shared prefix page it is diverging from)."""
    src = single(ins, "Src").astype(jnp.int32)
    dst = single(ins, "Dst").astype(jnp.int32)
    cache_k = single(ins, "CacheK")
    cache_v = maybe(ins, "CacheV")      # None: a latent block's one pool
    # (a sparse latent layer's pooled indexer keys go with their page)
    pools = {"CacheK": cache_k, "CacheV": cache_v,
             "CacheIndex": maybe(ins, "CacheIndex")}
    return out(Ok=dst, **{slot: pool.at[:, dst].set(pool[:, src])
                          for slot, pool in pools.items()
                          if pool is not None})
