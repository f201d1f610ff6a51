"""Encoder-decoder (seq2seq / NMT) ops on the paged decode plane.

The encoder-decoder split maps cleanly onto serving phases: the ENCODER
runs exactly once per request (admission time), so its product — the
per-layer cross-attention K/V of the source sentence — is computed once
and parked in a slot-resident cache ``[L, S+1, Hkv, Ts, dh]`` alongside
the self-attention page pool (row ``S`` is the scrap row padding and
vacant slots address). The DECODER is the familiar paged continuous-
batching loop plus one cross-attention block per layer that READS the
parked rows; decode never re-touches the encoder. Because the cross
cache is read-only after admission, a beam fork shares its parent's
cross row by refcount — K hypotheses of one translation carry ONE copy
of the source K/V.

Weight layout: the decoder reuses the stacked-LM contract (tok_emb /
pos_emb / lm_stack.* / final_ln.* / lm_head.w — the target-side "LM")
extended with per-layer cross weights (ln/q/out projections, slots
XLnS/XLnB/XQW/XOutW), while the encoder carries its own stack
(enc_stack.*, src_emb, src_pos_emb, enc_ln.*) plus the cross K/V
projection ``xattn.stack_kv_w [L, d, 2·Hkv·dh]`` applied to the encoder
memory at encode time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..lm_spec import Block
from .common import maybe, out, single
from .pipeline_ops import (_SAMPLING_SLOTS, _STACK_SLOTS, _attn_out_ffn,
                           _attn_proj, _expand_kv, _logits_fn, _ln,
                           _maybe_topk, _pick_rows, _scan_paged_layers,
                           chunk_mask)

# encoder stack slots: the same 10-weight block layout, Enc-prefixed
_ENC_SLOTS = {f"Enc{slot}": key for slot, key in _STACK_SLOTS.items()}
# decoder cross-attention slots (per-layer, stacked [L, ...])
_CROSS_SLOTS = ("XLnS", "XLnB", "XQW", "XOutW")


def _unpack_cross(ins):
    return {k.lower(): single(ins, k) for k in _CROSS_SLOTS}


def _cross_attend(h1, xw, ck_x, cv_x, src_len, num_heads):
    """One-token (or window) cross-attention block: pre-LN query
    projection against the parked encoder K/V rows. h1 [b, t, d];
    ck_x/cv_x [b, Hkv, Ts, dh]; src_len [b]."""
    from ..kernels.flash_attention import reference_attention

    b, t, d = h1.shape
    head_d = d // num_heads
    hx = _ln(h1, xw["xlns"], xw["xlnb"])
    q = jnp.einsum("btd,de->bte", hx, xw["xqw"])
    q = q.reshape(b, t, num_heads, head_d).transpose(0, 2, 1, 3)
    ctx = reference_attention(q, ck_x, cv_x, lengths=src_len)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
    return h1 + jnp.einsum("btd,de->bte", ctx, xw["xoutw"])


def _cross_block(xslot, src_len, num_heads):
    """The ``finish`` of _scan_paged_layers for a decoder layer: self-attn
    out-projection + residual, the cross-attention block over the layer's
    parked encoder rows (x_l = its CrossK/CrossV [S+1, Hkv, Ts, dh] and
    cross weights), then the FFN."""

    def finish(layer_p, h, ctx, x_l):
        xk_l, xv_l, xw = x_l
        h = h + jnp.einsum("btd,de->bte", ctx, layer_p["out_w"])
        h = _cross_attend(h, xw, xk_l[xslot], xv_l[xslot], src_len,
                          num_heads)
        h2 = _ln(h, layer_p["ln2_s"], layer_p["ln2_b"])
        ff = jax.nn.gelu(jnp.einsum("btd,df->btf", h2, layer_p["ff_w1"])
                         + layer_p["ff_b1"])
        return h + jnp.einsum("btf,fd->btd", ff, layer_p["ff_w2"]) \
            + layer_p["ff_b2"], None

    return finish


def _encode_memory(ins, attrs, src, src_len):
    """Shared encoder forward: embedded source through the Enc stack
    (bidirectional, length-masked) + final LN -> memory [b, Ts, d]."""
    from ..kernels.flash_attention import reference_attention

    params = {key: single(ins, slot) for slot, key in _ENC_SLOTS.items()}
    tok_emb = single(ins, "SrcTokEmb")
    pos_emb = maybe(ins, "SrcPosEmb")
    num_heads = attrs["num_heads"]
    num_kv_heads = attrs.get("num_kv_heads") or num_heads
    blk = Block(num_heads=num_heads, num_kv_heads=num_kv_heads)
    b, Ts = src.shape
    d = params["ln1_s"].shape[1]
    x = tok_emb[src]
    if pos_emb is not None:
        x = x + pos_emb[None, :Ts]

    def block(h, layer_p):
        q, k, v = _attn_proj(blk, layer_p, h)
        kx, vx = _expand_kv(k, v, num_heads)
        ctx = reference_attention(q, kx, vx, lengths=src_len)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, Ts, d)
        return _attn_out_ffn(blk, layer_p, h, ctx)[0], None

    h, _ = jax.lax.scan(block, x, params)
    return _ln(h, single(ins, "EncLnS"), single(ins, "EncLnB"))


def _project_cross_kv(memory, xkv_w, num_kv_heads):
    """memory [b, Ts, d] x xkv_w [L, d, 2·Hkv·dh] -> per-layer cross
    K/V [L, b, Hkv, Ts, dh]."""
    b, Ts, d = memory.shape
    L = xkv_w.shape[0]
    d_kv = xkv_w.shape[2] // 2
    dh = d_kv // num_kv_heads
    kv = jnp.einsum("btd,lde->lbte", memory, xkv_w)
    k, v = kv[..., :d_kv], kv[..., d_kv:]

    def heads(a):
        return a.reshape(L, b, Ts, num_kv_heads, dh).transpose(
            0, 1, 3, 2, 4)

    return heads(k), heads(v)


@register_op("transformer_encdec_encode", optional_inputs=("SrcPosEmb",))
def transformer_encdec_encode(attrs, ins):
    """Run the encoder ONCE for a batch of admitted sources and park
    their cross-attention K/V in the slot cache.

    SrcIds [b, Ts] int (right-padded), SrcLen [b] int32, SlotIds [b]
    int32 (cross-cache row per request; padding rows target the scrap
    row S), SrcTokEmb [Vs, d], SrcPosEmb [Tsmax, d] (optional), the
    Enc-prefixed stacked encoder weights + EncLnS/EncLnB [d], XKvW
    [L, d, 2·Hkv·dh] (the DECODER's cross K/V projection — applied here
    so decode never touches the encoder memory), CrossK/CrossV
    [L, S+1, Hkv, Tsmax, dh]. Returns Ok [b] (echoed slot ids — the
    fetchable witness) and the cross caches with rows 0..Ts-1 of each
    target row overwritten (donated in place).
    """
    src = single(ins, "SrcIds")
    src_len = single(ins, "SrcLen").astype(jnp.int32)
    slot_ids = single(ins, "SlotIds").astype(jnp.int32)
    cross_k = single(ins, "CrossK")
    cross_v = single(ins, "CrossV")
    num_heads = attrs["num_heads"]
    num_kv_heads = attrs.get("num_kv_heads") or num_heads
    Ts = src.shape[1]
    if Ts > cross_k.shape[3]:
        raise ValueError(f"source bucket {Ts} exceeds the cross cache "
                         f"length {cross_k.shape[3]}")
    memory = _encode_memory(ins, attrs, src, src_len)
    k, v = _project_cross_kv(memory, single(ins, "XKvW"), num_kv_heads)
    # [L, b, Hkv, Ts, dh] -> scatter rows into their slots
    cross_k = cross_k.at[:, slot_ids, :, :Ts, :].set(k)
    cross_v = cross_v.at[:, slot_ids, :, :Ts, :].set(v)
    return out(Ok=slot_ids, CrossK=cross_k, CrossV=cross_v)


@register_op("transformer_stack_cross_prefill",
             optional_inputs=("PosEmb",) + _SAMPLING_SLOTS)
def transformer_stack_cross_prefill(attrs, ins, rng=None):
    """Paged chunk prefill of the TARGET prefix with cross-attention.

    The paged-prefill contract (Chunk/StartPos/Lengths/BlockTable +
    CacheK/CacheV page pools + the stacked-LM decoder weights) extended
    per layer with a cross-attention block over the parked encoder rows:
    XSlot [b] int32 (each row's cross-cache row), SrcLen [b] int32,
    CrossK/CrossV [L, S+1, Hkv, Tsmax, dh] (read-only here), and the
    XLnS/XLnB/XQW/XOutW stacked cross weights. Per-row sampling plane
    and ``emit_topk`` behave exactly like transformer_stack_paged_prefill.
    """
    # per-row sampling slots, read via _row_sampling/_maybe_topk:
    # "Temperature", "TopK", "TopP", "Seed", "Step", "Mask"
    chunk = single(ins, "Chunk")
    start = single(ins, "StartPos").astype(jnp.int32)
    lengths = single(ins, "Lengths").astype(jnp.int32)
    table = single(ins, "BlockTable").astype(jnp.int32)
    xslot = single(ins, "XSlot").astype(jnp.int32)
    src_len = single(ins, "SrcLen").astype(jnp.int32)
    cache_k, cache_v = single(ins, "CacheK"), single(ins, "CacheV")
    cross_k, cross_v = single(ins, "CrossK"), single(ins, "CrossV")
    tok_emb = single(ins, "TokEmb")
    pos_emb = maybe(ins, "PosEmb")
    ln_s, ln_b = single(ins, "FinalLnS"), single(ins, "FinalLnB")
    head_w = single(ins, "HeadW")
    params = {key: single(ins, slot) for slot, key in _STACK_SLOTS.items()}
    xparams = _unpack_cross(ins)
    num_heads = attrs["num_heads"]
    num_kv_heads = attrs.get("num_kv_heads") or num_heads
    # (the engine states the weights' dtype where it binds AMP operand
    # copies of the decoder's: ``ops.pipeline_ops._mm``)
    blk = Block(num_heads=num_heads, num_kv_heads=num_kv_heads,
                param_dtype=attrs.get("param_dtype"))
    b, Tc = chunk.shape
    ps = cache_k.shape[2]
    P = table.shape[1]
    pos = start[:, None] + jnp.arange(Tc, dtype=jnp.int32)[None, :]
    valid = jnp.arange(Tc, dtype=jnp.int32)[None, :] < lengths[:, None]
    entry = jnp.clip(pos // ps, 0, P - 1)
    page_id = jnp.where(
        valid, jnp.take_along_axis(table, entry, axis=1), 0)
    page_row = jnp.where(valid, pos % ps, 0)
    x = tok_emb[chunk]
    if pos_emb is not None:
        x = x + pos_emb[jnp.clip(pos, 0, pos_emb.shape[0] - 1)]
    h, cache_k, cache_v, _, _ = _scan_paged_layers(
        params, x, cache_k, cache_v, table, page_id, page_row,
        lambda p, h: _attn_proj(blk, p, h, pos0=start),
        chunk_mask(start, lengths),
        finish=_cross_block(xslot, src_len, num_heads),
        xs=(cross_k, cross_v, xparams))
    last = h[jnp.arange(b), jnp.clip(lengths, 1, Tc) - 1]
    logits = _logits_fn(ln_s, ln_b, head_w, blk)(last)
    nxt = _pick_rows(attrs, ins, rng, head_w.shape[1], logits)
    outs = out(NextTok=nxt.astype(chunk.dtype),
               CacheK=cache_k, CacheV=cache_v)
    return _maybe_topk(attrs, ins, logits, outs)


@register_op("transformer_stack_cross_decode",
             optional_inputs=("PosEmb",) + _SAMPLING_SLOTS)
def transformer_stack_cross_decode(attrs, ins, rng=None):
    """One decode step over every slot's paged target context PLUS a
    cross-attention read of its parked encoder rows.

    The transformer_stack_paged_decode contract extended with XSlot [S]
    int32 (cross-cache row per slot; vacant slots address the scrap
    row), SrcLen [S] int32, CrossK/CrossV [L, S+1, Hkv, Tsmax, dh]
    (READ-ONLY — written once by transformer_encdec_encode), and the
    stacked cross weights. Same per-row sampling plane and ``emit_topk``
    beam plane; same one-compile steady state.
    """
    # per-row sampling slots, read via _row_sampling/_maybe_topk:
    # "Temperature", "TopK", "TopP", "Seed", "Step", "Mask"
    tok = single(ins, "Tok")
    pos = single(ins, "Pos").astype(jnp.int32)
    table = single(ins, "BlockTable").astype(jnp.int32)
    xslot = single(ins, "XSlot").astype(jnp.int32)
    src_len = single(ins, "SrcLen").astype(jnp.int32)
    cache_k, cache_v = single(ins, "CacheK"), single(ins, "CacheV")
    cross_k, cross_v = single(ins, "CrossK"), single(ins, "CrossV")
    tok_emb = single(ins, "TokEmb")
    pos_emb = maybe(ins, "PosEmb")
    ln_s, ln_b = single(ins, "FinalLnS"), single(ins, "FinalLnB")
    head_w = single(ins, "HeadW")
    params = {key: single(ins, slot) for slot, key in _STACK_SLOTS.items()}
    xparams = _unpack_cross(ins)
    num_heads = attrs["num_heads"]
    num_kv_heads = attrs.get("num_kv_heads") or num_heads
    # (the engine states the weights' dtype where it binds AMP operand
    # copies of the decoder's: ``ops.pipeline_ops._mm``)
    blk = Block(num_heads=num_heads, num_kv_heads=num_kv_heads,
                param_dtype=attrs.get("param_dtype"))
    S = tok.shape[0]
    ps = cache_k.shape[2]
    P = table.shape[1]
    pos = jnp.clip(pos, 0, P * ps - 1)
    x = tok_emb[tok]
    if pos_emb is not None:
        x = x + pos_emb[jnp.clip(pos, 0, pos_emb.shape[0] - 1)]
    h1 = x[:, None, :]
    srange = jnp.arange(S)
    page_id = table[srange, pos // ps]
    page_row = pos % ps
    h1, cache_k, cache_v, _, _ = _scan_paged_layers(
        params, h1, cache_k, cache_v, table, page_id, page_row,
        lambda p, h: _attn_proj(blk, p, h, pos0=pos),
        dict(lengths=pos + 1),
        finish=_cross_block(xslot, src_len, num_heads),
        xs=(cross_k, cross_v, xparams))
    logits = _logits_fn(ln_s, ln_b, head_w, blk)(h1[:, 0])
    nxt = _pick_rows(attrs, ins, rng, head_w.shape[1], logits)
    outs = out(NextTok=nxt.astype(tok.dtype),
               CacheK=cache_k, CacheV=cache_v)
    return _maybe_topk(attrs, ins, logits, outs)


@register_op("transformer_encdec_teacher", has_loop=True,
             optional_inputs=("SrcPosEmb", "PosEmb"))
def transformer_encdec_teacher(attrs, ins):
    """Teacher-forced encoder-decoder forward: the NMT TRAINING (and
    reference-decode) path.

    SrcIds [b, Ts] + SrcLen [b] + the encoder/cross inputs of
    transformer_encdec_encode, TgtIn [b, Tt] + the stacked-LM decoder
    weights + cross weights -> Logits [b, Tt, V]: decoder position t
    attends target positions <= t (causal) and every valid source
    position (cross). Differentiable end to end through the generic
    grad machinery — this op IS the training graph; the paged
    cross-decode ops serve what it learns, token-exact.
    """
    from ..kernels.flash_attention import flash_attention

    src = single(ins, "SrcIds")
    src_len = single(ins, "SrcLen").astype(jnp.int32)
    tgt_in = single(ins, "TgtIn")
    tok_emb = single(ins, "TokEmb")
    pos_emb = maybe(ins, "PosEmb")
    ln_s, ln_b = single(ins, "FinalLnS"), single(ins, "FinalLnB")
    head_w = single(ins, "HeadW")
    params = {key: single(ins, slot) for slot, key in _STACK_SLOTS.items()}
    xparams = _unpack_cross(ins)
    num_heads = attrs["num_heads"]
    num_kv_heads = attrs.get("num_kv_heads") or num_heads
    blk = Block(num_heads=num_heads, num_kv_heads=num_kv_heads)
    b, Tt = tgt_in.shape
    d = params["ln1_s"].shape[1]
    memory = _encode_memory(ins, attrs, src, src_len)
    xk, xv = _project_cross_kv(memory, single(ins, "XKvW"),
                               num_kv_heads)  # [L, b, Hkv, Ts, dh]
    x = tok_emb[tgt_in]
    if pos_emb is not None:
        x = x + pos_emb[None, :Tt]

    def layer(h, inp):
        (layer_p, xk_l, xv_l, xlns, xlnb, xqw, xoutw) = inp
        xw = {"xlns": xlns, "xlnb": xlnb, "xqw": xqw, "xoutw": xoutw}
        q, k, v = _attn_proj(blk, layer_p, h)
        kx, vx = _expand_kv(k, v, num_heads)
        ctx = flash_attention(q, kx, vx, causal=True)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, Tt, d)
        h = h + jnp.einsum("btd,de->bte", ctx, layer_p["out_w"])
        h = _cross_attend(h, xw, xk_l, xv_l, src_len, num_heads)
        h2 = _ln(h, layer_p["ln2_s"], layer_p["ln2_b"])
        ff = jax.nn.gelu(jnp.einsum("btd,df->btf", h2, layer_p["ff_w1"])
                         + layer_p["ff_b1"])
        h = h + jnp.einsum("btf,fd->btd", ff, layer_p["ff_w2"]) \
            + layer_p["ff_b2"]
        return h, None

    h, _ = jax.lax.scan(
        layer, x,
        (params, xk, xv, xparams["xlns"], xparams["xlnb"],
         xparams["xqw"], xparams["xoutw"]))
    hn = _ln(h, ln_s, ln_b)
    logits = jnp.einsum("btd,dv->btv", hn, head_w).astype(jnp.float32)
    return out(Logits=logits)
