"""The vision tower of a stacked LM (``lm_spec.VisionSpec`` has the
equations): frames in, prompt rows out. Plain ``jax.numpy`` under the paged
prefill op (``transformer_stack_paged_prefill``): no kernel of its own, no
cache, one frame at a time (attention is within a frame), a frame the chunk
does not use skipped (``lax.cond``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..lm_spec import VisionSpec
from .common import maybe

#: the prefill op's slots for the tower's and the merger's parameters
VISION_SLOTS = tuple(p[0] for p in VisionSpec().planes(1))


def vision_params(ins):
    """The tower's parameters by key (``VisionSpec.planes``) from the op's
    input slots; {} for a program without a tower."""
    return {key: maybe(ins, slot)
            for slot, key, _, _, _ in VisionSpec().planes(1)
            if maybe(ins, slot) is not None}


def _ln(x, s, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * s.astype(jnp.float32)
            + b.astype(jnp.float32))


def patches_of(pixels, patch: int):
    """pixels [F, S, S, 3] uint8 -> [F, (S / patch)^2, patch^2 3] float32
    in [-1, 1]: patches in row-major order, a patch's values in (row,
    column, channel) order."""
    F, S = pixels.shape[:2]
    g = S // patch
    x = pixels.astype(jnp.float32) / 127.5 - 1.0
    x = x.reshape(F, g, patch, g, patch, 3).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(F, g * g, patch * patch * 3)


def _rot2d(x, grid: int, theta: float):
    """x [.., n = grid^2, H, dh]: half-split rotary; of the dh / 2 pairs the
    first half turn by the patch's row, the rest by its column."""
    dh = x.shape[-1]
    half, quarter = dh // 2, dh // 4
    inv = theta ** (-jnp.arange(quarter, dtype=jnp.float32) / quarter)
    at = jnp.arange(grid * grid)
    ang = jnp.concatenate([(at // grid)[:, None] * inv[None, :],
                           (at % grid)[:, None] * inv[None, :]], axis=-1)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def encode_frame(blk, mm, p, pixels):
    """ONE frame [S, S, 3] uint8 -> its merged rows [tokens a frame, d_out]
    float32. ``mm(eq, a, w)``: the block's matmul (``_mm``: bf16 weights as
    stored, float32 accumulation, the result in a's dtype)."""
    H, eps = blk.vision_heads, blk.vision_eps
    x = patches_of(pixels[None], blk.vision_patch)[0]           # [n, pv]
    n = x.shape[0]
    grid = int(round(n ** 0.5))
    d = p["patch_w"].shape[1]
    pos = p["pos_emb"].astype(jnp.float32)
    pg = int(round(pos.shape[0] ** 0.5))
    pos = jax.image.resize(pos.reshape(pg, pg, d), (grid, grid, d),
                           "bilinear").reshape(n, d)
    x = mm("nv,vd->nd", x, p["patch_w"]) + p["patch_b"].astype(
        jnp.float32) + pos

    def layer(x, lp):
        h = _ln(x, lp["stack_ln1_s"], lp["stack_ln1_b"], eps)
        qkv = mm("nd,de->ne", h, lp["stack_qkv_w"]) + lp[
            "stack_qkv_b"].astype(jnp.float32)
        q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(n, H, d // H)
                   for i in range(3))
        q = _rot2d(q, grid, blk.vision_theta)
        k = _rot2d(k, grid, blk.vision_theta)
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       preferred_element_type=jnp.float32) * (d // H) ** -0.5
        ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("nd,de->ne", ctx.reshape(n, d), lp["stack_out_w"]) + lp[
            "stack_out_b"].astype(jnp.float32)
        h = _ln(x, lp["stack_ln2_s"], lp["stack_ln2_b"], eps)
        h = jax.nn.gelu(mm("nd,df->nf", h, lp["stack_fc1_w"]) + lp[
            "stack_fc1_b"].astype(jnp.float32), approximate=True)
        return x + mm("nf,fd->nd", h, lp["stack_fc2_w"]) + lp[
            "stack_fc2_b"].astype(jnp.float32), None

    x, _ = jax.lax.scan(layer, x, {k: v for k, v in p.items()
                                   if k.startswith("stack_")})
    x = _ln(x, p["post_ln_s"], p["post_ln_b"], eps)
    m = blk.vision_merge
    side = grid // m
    x = x.reshape(side, m, side, m, d).transpose(0, 2, 1, 3, 4).reshape(
        side * side, m * m * d)
    h = _ln(x, p["merge_ln_s"], p["merge_ln_b"], eps)
    h = jax.nn.gelu(mm("nm,mk->nk", h, p["merge_w1"]) + p[
        "merge_b1"].astype(jnp.float32), approximate=False)
    return mm("nk,kd->nd", h, p["merge_w2"]) + p["merge_b2"].astype(
        jnp.float32)


def splice_media(blk, mm, p, x, pixels, media_row):
    """The chunk's stream x [b, t, d] with the merged rows of its frames at
    its placeholder positions: ``pixels`` [b, Fc, S, S, 3] uint8 the frames
    the chunk touches (a row's in order), ``media_row`` [b, t] int32 the row
    of THOSE frames' merged rows a position takes (frame x tokens a frame +
    its place in the frame; -1: the embedding's row stays). A frame no
    position of its row names is not encoded."""
    b, Fc = pixels.shape[:2]
    tpf = (pixels.shape[2] // blk.vision_patch // blk.vision_merge) ** 2
    d = x.shape[-1]
    used = jnp.any((media_row[:, None, :] // tpf == jnp.arange(Fc)[
        None, :, None]) & (media_row[:, None, :] >= 0), axis=-1)  # [b, Fc]

    def frame(args):
        px, on = args
        return jax.lax.cond(on, lambda: encode_frame(blk, mm, p, px),
                            lambda: jnp.zeros((tpf, d), jnp.float32))

    rows = jax.lax.map(frame, (pixels.reshape((b * Fc,) + pixels.shape[2:]),
                               used.reshape(-1)))       # [b Fc, tpf, d]
    rows = rows.reshape(b, Fc * tpf, d)
    at = jnp.clip(media_row, 0, Fc * tpf - 1)
    took = jnp.take_along_axis(rows, at[..., None], axis=1)
    return jnp.where((media_row >= 0)[..., None], took, x)
