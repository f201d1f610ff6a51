"""Family ``dsa_gqa_moe_vl``: a Keye-VL-2.0-shaped vision-language decoder — a
SigLIP-class vision tower and a 2 x 2 merger whose rows stand at a clip's
placeholder tokens, then rotating grouped-query layers with per-head QK-norm
and THREE-AXIS rotary (M-RoPE) that read only what a learned indexer picks
(one indexer key a TOKEN), and whole softmax-routed SwiGLU experts — served by
``serving.GenerationEngine(spec, .., media_resolver=)`` from ONE
``paddle_tpu.lm_spec.LMSpec`` (``spec_of``), with the yardstick's own pieces:
what the selection and the tower have to move (``dsa_cost`` /
``vision_cost``), which device op belongs to which mechanism (``dsa_op`` /
``moe_op`` / ``vision_op``) and a plain float32 ``jax.numpy`` reference of the
equations (one sequence of T tokens; RMSNorm(u) = u rsqrt(mean(u^2) + eps) w):

  tower, a frame S x S x 3 (uint8; x / 127.5 - 1), g = S / patch:
    P [g g, 3 patch^2] (patches row-major, a patch's values (row, column,
    channel)); z = P W_p + b_p + bilinear(E_pos [pg, pg, dv] -> [g, g, dv])
    per block: a = LN_1(z); [q | k | v] = a W_qkv + b; heads of dv / Hv;
      q, k turn by the patch's (row, column): half-split pairs, the first
      half of them by the row, the rest by the column, theta_v
      z += softmax(q k^T / sqrt(dv / Hv)) v W_o + b_o       (within a frame)
      z += gelu_tanh(LN_2(z) W_1 + b_1) W_2 + b_2
    z = LN_post(z); merged (r, c) = [z(2r, 2c) | z(2r, 2c+1) | z(2r+1, 2c) |
      z(2r+1, 2c+1)]; row = gelu(LN_m(merged) W_m1 + b_m1) W_m2 + b_m2
  prompt: ``vision_start``, F x (g / 2)^2 x ``video_pad``, ``vision_end``;
    the rows at pad positions are the merger's, every other the embedding's
  positions (Qwen2-VL): text t = h = w = the running id; a clip of F frames
    starting at id b gives merged patch (f, r, c) the ids (b + f, b + r, b +
    c); the text after it resumes at b + max(F, g / 2)
  layer, h = RMSNorm_1(x):
    q = h W_q [H, dh]; k = h W_k [Hkv, dh]; v = h W_v [Hkv, dh]
    q, k <- RMSNorm over each head (one scale of dh each), then M-RoPE:
      half-split pairs, pair i turning by the id of its axis (the first
      section[0] pairs the temporal id, ..) times theta^(-2i / dh)
    indexer: qI = h W_Iq [Hi, Di]; kI_s = LayerNorm(h_s W_Ik) [Di];
      wI = (h W_Iw) Hi^-1/2 Di^-1/2
      I[t, s] = sum_j wI[t, j] relu(qI[t, j] . kI_s)   for s < t
      picked(t) = t itself + the topk - 1 positions s < t of highest I (all
      of them while t < topk; ties to the lower index)
    x += [softmax over picked(t) of q . k dh^-1/2] v W_o   (all H heads over
      the same set; causality and the pick by the SEQUENCE index)
  experts, h2 = RMSNorm_2(x): p = softmax(h2 W_r) over ALL experts (float32);
    S = top-k of p; w_e = p_e / sum_S p (``norm_topk_prob``)
    x += sum_{e in S} w_e (silu(h2 W_g^e) * h2 W_u^e) W_d^e        (dropless)
  logits = RMSNorm_f(x) W_head

The reference has no cache, no pool, no kernel, no chunked form, no gather,
no top-k primitive and no grouped matmul: the tower runs whole frames under
``lax.map``, the indexer scores every position and picks by a FULL SORT,
attention is a masked softmax over the keys and values of ALL positions
(mask = picked), every expert is applied densely and masked by the top-k
set. It reads the SAME stored weights as the program and runs under
``jax.default_matmul_precision("highest")``. Where the configuration's
``page_dtype`` is bfloat16 a token's K and V rows and its indexer key are
rounded to bfloat16 as they are computed (what a page STORES is part of the
configuration: ``assumed.page_dtype``); every product over them is float32.
"""
from __future__ import annotations

import hashlib
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.dsa_kda_moe_lm import _bf16, picked_groups
from benchmark.families.moe_lm import grouped_matmul_cost  # noqa: F401
from benchmark.families.stacked_lm import draw_ids
from benchmark.families.window_moe_lm import _f32, _head, _rms

ITEM = "tokens"
_EXPERT_BLOCK = 8       # experts upcast to float32 at a time
_TOKEN_BLOCK = 1024     # tokens that go through the experts together
_QUERY_BLOCK = 128      # queries that score and attend together
_PAD = 2048             # the reference runs long sequences at whole multiples
                        # of this: few compiled programs
_PREAMBLE = 24          # text ids before a drawn prompt's clip
_MIN_QUESTION = 16      # .. and after it, at least
#: frames of the bank a run's resolver draws a clip's frames from
BANK_FRAMES = 256
#: WRONG models, one fault each, that the check and the tier-1 tests must
#: tell from the right one: ``reference_logits(.., variant=name)``
VARIANTS = {
    "recent_pick": "the most RECENT index_topk tokens instead of the "
                   "indexer's pick (a sliding window)",
    "no_selection": "every cached token attended: the layer without its "
                    "indexer",
    "no_mrope": "one position axis: every token turns by its sequence index",
    "no_vision": "the placeholder ids embedded as tokens: no tower",
    "no_qk_norm": "q and k without their per-head RMSNorm",
    "bf16_stated_f32": "the residual stream, norms, router scores, softmax "
                       "weights and the indexer's scores rounded to "
                       "bfloat16 where the configuration says float32",
    "fp8_operands": "the stack's matmul OPERANDS (weights and activations) "
                    "rounded to float8 e4m3 where the configuration says "
                    "bfloat16: the nearest precision below the stated one",
    "bf16_results": "bf16_stated_f32 and every product's RESULT rounded to "
                    "bfloat16 too (projections, scores, read-outs, the "
                    "experts' products): no float32 accumulator survives a "
                    "product",
}

_LOSSY = ("bf16_stated_f32", "bf16_results")


def _operand(variant: str):
    """How the reference reads a matmul operand of the stack: as float32, or
    (``fp8_operands``) rounded to float8 e4m3 first."""
    import jax.numpy as jnp

    if variant != "fp8_operands":
        return _f32
    return lambda a: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


#: THE LIMIT on the served top-8 log-prob error, the ``CHECK_LOGPROB_QUANTILE``
#: -th percentile over the served positions (``reference_logit_gaps``; the
#: mix's ``check.logit_gap_tol`` IS this number). Every limit below lies
#: between two readings taken on the check's OWN line (the schedule's four
#: checked requests through ``check_readings``; ``tools/keye2_chip_check.py
#: controls``, three seeds, and the cell's whole runs; my chip runs, PR 60;
#: PERF.md section 6 and the mix's ``logit_gap_tol_why`` give them all):
#: right 0.0232-0.0375; float8 operands 0.0605-0.0607, no selection
#: 0.094-0.118, the most recent 2048 0.149-0.176
CHECK_LOGPROB_QUANTILE = 90
CHECK_LOGPROB_TOL = 0.05
#: ... and on the LARGEST of them (a router or a pick near-tie is a discrete
#: step, not rounding: the tail has a limit of its own, for a fault that
#: reaches few positions): right 0.042-0.130; other pixels (no tower)
#: 1.07-1.20
CHECK_LOGPROB_MAX_TOL = 0.4
#: the tokens of a checked request's answer that the check replays
CHECK_REPLAY_TOKENS = 96
#: how far below its position's best the reference may put a token the TIMED
#: engine emitted (a request answered with another's tokens, or pixels):
#: right 0.023-0.115; other pixels 1.01-1.28
CHECK_EMITTED_GAP_TOL = 0.3
#: the mean share of a served query's picked positions that scoring the
#: indexer keys the engine's pages HOLD picks differently from scoring the
#: reference's own (the worst layer a query): right 0.0215-0.0296; float8
#: operands 0.0994-0.1037. THE reading that tells the stated precision from
#: the nearest below it: the limit stands 1.86 x over the one and 1.8 x
#: under the other
CHECK_PICK_MISS_TOL = 0.055
CHECK_TOPK = 8


def vision_of(config: dict):
    from paddle_tpu.lm_spec import VisionSpec

    v, t = config["assumed"]["vision"], config["assumed"]["prompt_format"]
    return VisionSpec(
        image_size=v["image_size"], patch_size=v["patch_size"],
        d_model=v["hidden_size"], n_layers=v["num_hidden_layers"],
        num_heads=v["num_attention_heads"], d_ff=v["intermediate_size"],
        pos_grid=v["position_grid"], merge=v["spatial_merge_size"],
        norm_eps=v["layer_norm_eps"], rope_theta=float(v["rope_theta"]),
        vision_start_id=t["vision_start_id"], video_pad_id=t["video_pad_id"],
        vision_end_id=t["vision_end_id"])


def spec_of(config: dict):
    """The program's model spec for this configuration: a tree whose spec
    lacks the tower, M-RoPE or the selection on K/V pages fails here, at
    once, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a, sa = config["assumed"], config["sa_config"]
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], use_rope=True, max_len=a["max_len"],
        norm="rms_norm", norm_eps=config["rms_norm_eps"], qk_norm=True,
        qk_norm_heads=True, rope_theta=float(config["rope_theta"]),
        rope_pairing="half", rope="mrope",
        mrope_section=tuple(config["rope_scaling"]["mrope_section"]),
        ffn="swiglu_moe", num_experts=config["num_experts"],
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"], bias=False,
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], index_pool=1, vision=vision_of(config),
        param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def seeded_vectors(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """The planes a startup program leaves at a constant and a checkpoint
    does not (``assumed.qk_norm_values``): the per-head q / k RMSNorm scales
    ~ U(lo, hi), so that a layer's scores q . k dh^-1/2 have a standard
    deviation near their product (a few dozen rows carry a softmax over
    2048: a wrong pick reads a wrong answer), and the indexer key's
    LayerNorm scale and bias."""
    spec = spec_of(config)
    rng = np.random.default_rng([int(seed), 0x4b455945])
    lo, hi = config["assumed"]["qk_norm_values"]["scale_range"]
    L, dh, Di = spec.n_layers, spec.head_dim, spec.index_dim
    return {"q_norm_s": rng.uniform(lo, hi, (L, dh)),
            "k_norm_s": rng.uniform(lo, hi, (L, dh)),
            "idx_k_norm_s": rng.uniform(0.5, 1.5, (L, Di)),
            "idx_k_norm_b": rng.normal(0.0, 0.1, (L, Di))}


def frame_bank(config: dict, seed: int) -> np.ndarray:
    """The ``BANK_FRAMES`` seeded frames a run's clips are drawn from
    [BANK_FRAMES, S, S, 3] uint8: built once at set-up, so that resolving a
    clip is a gather and not megabytes of random numbers on the engine's
    thread."""
    shape = vision_of(config).frame_shape
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0x434c4950])
    return rng.integers(0, 256, (BANK_FRAMES,) + shape, dtype=np.uint8)


def clip_frames(bank: np.ndarray, prompt, span) -> np.ndarray:
    """The frames of the vision span ``span`` = (first pad position, frames)
    of ``prompt``: indices into the bank seeded from the digest of the ids
    BEFORE the span (the text stands for the clip's reference, as a URL
    would): the same ids give the same pixels, to the engine's resolver and
    to the reference alike."""
    first, frames = span
    digest = hashlib.blake2b(
        np.asarray(prompt[:first], np.int64).tobytes(), digest_size=8)
    rng = np.random.default_rng(int.from_bytes(digest.digest(), "little"))
    return bank[rng.integers(0, bank.shape[0], frames)]


def build_engine(config: dict, mix: dict, seed: int, **engine_kw):
    """-> (engine, executors). Weights come from ONE run of the parameter
    program's startup block on the device, seeded, in the configuration's
    stored dtype; then the embedding is scaled and the seeded vectors set
    (``seeded_vectors``). The engine's ``media_resolver`` draws a clip's
    frames from the run's bank (``clip_frames``)."""
    spec = spec_of(config)      # first: a tree without the spec stops here
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu import models

    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        models.lm_parameters(spec)
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    scope.set("tok_emb", (scope.get("tok_emb") * config["assumed"][
        "embedding_scale"]).block_until_ready())
    for key, value in seeded_vectors(config, seed).items():
        name = f"lm_stack.stack_{key}"
        scope.set(name, jnp.asarray(value, scope.get(name).dtype))
    bank = frame_bank(config, seed)
    e = mix["engine"]
    from paddle_tpu.serving import GenerationEngine

    eng = GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], max_seq_len=e["max_len"],
        prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None,
        mask_plane=bool(e.get("mask_plane", 1)),
        media_resolver=lambda prompt, span: clip_frames(bank, prompt, span),
        **{"beam_width": e.get("beam_width", 0), **engine_kw})
    _ENGINES[id(config)] = (eng, bank)
    return eng, [exe, eng.executor]


#: id(configuration) -> (the engine the last ``build_engine`` built, its
#: frame bank): the check replays its requests through THAT engine after the
#: drain (its beam plane is how logits leave it) and makes the same pixels
_ENGINES: dict = {}


def draw_prompt_ids(rng, n: int, config: dict) -> np.ndarray:
    """``n`` ids: a text preamble of ``_PREAMBLE`` ids, ONE clip of F = (n -
    26 - 16) // tokens a frame frames between its start and end ids, and a
    question of the rest (16 ids and the remainder). Text ids are
    log-uniform over the vocabulary, never one of the three vision ids."""
    v = vision_of(config)
    tpf = v.tokens_per_frame
    frames = (n - _PREAMBLE - 2 - _MIN_QUESTION) // tpf
    if frames < 1:
        return _text_ids(rng, n, config, v)
    question = n - _PREAMBLE - 2 - frames * tpf
    return np.concatenate([
        _text_ids(rng, _PREAMBLE, config, v), [v.vision_start_id],
        np.full(frames * tpf, v.video_pad_id, np.int64), [v.vision_end_id],
        _text_ids(rng, question, config, v)]).astype(np.int64)


def _text_ids(rng, n, config, v):
    ids = draw_ids(rng, (n,), config, "log_uniform")
    special = np.isin(ids, (v.vision_start_id, v.video_pad_id,
                            v.vision_end_id))
    return np.where(special, 0, ids)


def weights_of(program, scope) -> Dict[str, object]:
    """The model's parameters by the fixed names the layout gives them, as
    stored (nothing is copied or cast)."""
    names = ["tok_emb", "final_ln.scale", "lm_head.w"] + sorted(
        n for n in scope.keys()
        if n.startswith(("lm_stack.stack_", "vision.")))
    return {name: scope.get(name) for name in names}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _ln(u, s, b, eps):
    import jax
    import jax.numpy as jnp

    mu = jnp.mean(u, axis=-1, keepdims=True)
    var = jnp.mean((u - mu) ** 2, axis=-1, keepdims=True)
    return (u - mu) * jax.lax.rsqrt(var + eps) * _f32(s) + _f32(b)


def _bilinear(table, grid: int):
    """table [pg, pg, d] -> [grid, grid, d], bilinear at half-pixel centres,
    the edge held beyond it."""
    import jax.numpy as jnp

    pg = table.shape[0]
    at = np.clip((np.arange(grid) + 0.5) * pg / grid - 0.5, 0.0, pg - 1.0)
    lo = np.floor(at).astype(np.int64)
    hi = np.minimum(lo + 1, pg - 1)
    w = at - lo
    w = jnp.asarray(w, jnp.float32)
    rows = table[lo] * (1 - w)[:, None, None] + table[hi] * w[:, None, None]
    return (rows[:, lo] * (1 - w)[None, :, None]
            + rows[:, hi] * w[None, :, None])


def tower_rows(config: dict, w: dict, frames):
    """frames [F, S, S, 3] uint8 -> the merger's rows [F x tokens a frame, d]
    float32 (frame-major, a frame's merged patches row-major)."""
    import jax
    import jax.numpy as jnp

    v = vision_of(config)
    g, pz, dv, Hv = v.grid, v.patch_size, v.d_model, v.num_heads
    dh, eps, m = dv // Hv, v.norm_eps, v.merge
    p = {k[len("vision."):]: a for k, a in w.items()
         if k.startswith("vision.")}
    pos = _bilinear(_f32(p["pos_emb"]).reshape(v.pos_grid, v.pos_grid, dv),
                    g).reshape(g * g, dv)
    quarter = dh // 4
    inv = v.rope_theta ** (-jnp.arange(quarter, dtype=jnp.float32) / quarter)
    at = jnp.arange(g * g)
    ang = jnp.concatenate([(at // g)[:, None] * inv, (at % g)[:, None] * inv],
                          axis=-1)                          # [n, dh / 2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rot(x):                                             # [n, Hv, dh]
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    stack = {k: a for k, a in p.items() if k.startswith("stack_")}

    def frame(px):
        x = px.astype(jnp.float32) / 127.5 - 1.0
        x = x.reshape(g, pz, g, pz, 3).transpose(0, 2, 1, 3, 4).reshape(
            g * g, pz * pz * 3)
        z = x @ _f32(p["patch_w"]) + _f32(p["patch_b"]) + pos

        def block(z, lp):
            a = _ln(z, lp["stack_ln1_s"], lp["stack_ln1_b"], eps)
            qkv = a @ _f32(lp["stack_qkv_w"]) + _f32(lp["stack_qkv_b"])
            q, k, val = (qkv[:, i * dv:(i + 1) * dv].reshape(-1, Hv, dh)
                         for i in range(3))
            s = jnp.einsum("qhd,khd->hqk", rot(q), rot(k)) * dh ** -0.5
            ctx = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), val)
            z = z + ctx.reshape(-1, dv) @ _f32(lp["stack_out_w"]) + _f32(
                lp["stack_out_b"])
            a = _ln(z, lp["stack_ln2_s"], lp["stack_ln2_b"], eps)
            a = jax.nn.gelu(a @ _f32(lp["stack_fc1_w"])
                            + _f32(lp["stack_fc1_b"]), approximate=True)
            return z + a @ _f32(lp["stack_fc2_w"]) + _f32(
                lp["stack_fc2_b"]), None

        z, _ = jax.lax.scan(block, z, stack)
        z = _ln(z, p["post_ln_s"], p["post_ln_b"], eps)
        side = g // m
        z = z.reshape(side, m, side, m, dv).transpose(0, 2, 1, 3, 4).reshape(
            side * side, m * m * dv)
        a = _ln(z, p["merge_ln_s"], p["merge_ln_b"], eps)
        a = jax.nn.gelu(a @ _f32(p["merge_w1"]) + _f32(p["merge_b1"]),
                        approximate=False)
        return a @ _f32(p["merge_w2"]) + _f32(p["merge_b2"])

    rows = jax.lax.map(frame, frames)
    return rows.reshape(-1, rows.shape[-1])


def _mrope(x, ids, section, theta):
    """x [T, n, dh] by the tokens' ids [T, 3]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    axis = np.repeat(np.arange(3), section)
    ang = ids.astype(jnp.float32)[:, axis] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def expert_layer(config: dict, p: dict, h2, variant: str = ""):
    """The expert half of a layer on h2 [T, d] (float32) with per-layer
    weights ``p``: every expert applied densely, masked by the top-k set."""
    import jax
    import jax.numpy as jnp

    T = h2.shape[0]
    E, k = config["num_experts"], config["num_experts_per_tok"]
    lossy = variant in _LOSSY

    def low(t):
        return _bf16(t) if variant == "bf16_results" else t

    op = _operand(variant)
    logits = h2 @ _f32(p["router_w"])       # (the router reads float32)
    h2 = op(h2)
    prob = jax.nn.softmax(_bf16(logits) if lossy else logits, axis=-1)
    if lossy:
        prob = _bf16(prob)
    kth = jax.lax.top_k(prob, k)[0][:, -1:]
    gate = jnp.where(prob >= kth, prob, 0.0)
    if config["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    eb = next(b for b in (_EXPERT_BLOCK, 4, 2, 1) if E % b == 0)
    Bt = next(b for b in (_TOKEN_BLOCK, 512, 256, 128, T) if T % b == 0)

    def token_block(blk):
        b_b, gate_b = blk

        def expert_block(y, e0):
            wg, wu, wd = (jax.lax.dynamic_slice_in_dim(p[name], e0, eb, 0)
                          for name in ("moe_gate_w", "moe_up_w",
                                       "moe_down_w"))
            g_blk = jax.lax.dynamic_slice_in_dim(gate_b, e0, eb, 1)
            g = low(jnp.einsum("td,edf->tef", b_b, op(wg)))
            u = low(jnp.einsum("td,edf->tef", b_b, op(wu)))
            a = op(jax.nn.silu(g) * u) * g_blk[..., None]
            return y + low(jnp.einsum("tef,efd->td", a, op(wd))), None

        return jax.lax.scan(expert_block, jnp.zeros_like(b_b),
                            jnp.arange(0, E, eb))[0]

    return jax.lax.map(token_block, (
        h2.reshape(T // Bt, Bt, -1), gate.reshape(T // Bt, Bt, E))
    ).reshape(T, -1)


def sparse_layer(config: dict, p: dict, h, ids3, variant: str = "",
                 cached=None):
    """One layer's mixer on h [T, d] (normed; T whole query blocks) with the
    tokens' rotary ids ``ids3`` [T, 3] -> (its output [T, d], a query's
    share of its picked positions that scoring the indexer keys ``cached``
    [T, Di] picks too [T]; ones without them)."""
    import jax
    import jax.numpy as jnp

    lossy = variant in _LOSSY

    def squash(t):
        return _bf16(t) if lossy else t

    def low(t):         # a product's result under ``bf16_results``
        return _bf16(t) if variant == "bf16_results" else t

    T = h.shape[0]
    H, Hkv, dh = (config["num_attention_heads"],
                  config["num_key_value_heads"], config["head_dim"])
    sa, eps = config["sa_config"], config["rms_norm_eps"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    k_pick = sa["topk"] - 1
    B = min(_QUERY_BLOCK, T)
    paged = config["assumed"]["page_dtype"] == "bfloat16"
    op = _operand(variant)
    h_op = op(h)
    qkv = low(h_op @ op(p["qkv_w"]))
    q = qkv[:, :H * dh].reshape(T, H, dh)
    k = qkv[:, H * dh:(H + Hkv) * dh].reshape(T, Hkv, dh)
    v = qkv[:, (H + Hkv) * dh:].reshape(T, Hkv, dh)
    if variant != "no_qk_norm":
        q = _rms(q, p["q_norm_s"], eps, lossy)
        k = _rms(k, p["k_norm_s"], eps, lossy)
    section = config["rope_scaling"]["mrope_section"]
    theta = float(config["rope_theta"])
    q, k = _mrope(q, ids3, section, theta), _mrope(k, ids3, section, theta)
    k_i = squash(_ln(h_op @ op(p["idx_k_w"]), p["idx_k_norm_s"],
                     p["idx_k_norm_b"], eps))
    if paged:
        # what the configuration says a page STORES: a token's K and V rows
        # and its indexer key are bfloat16 values; every product over them
        # is float32 (the indexer's queries meet the keys in their dtype)
        k, v, k_i = _bf16(k), _bf16(v), _bf16(k_i)
    theirs = None if cached is None else _f32(cached)
    w_iq, w_iw = op(p["idx_q_w"]), op(p["idx_head_w"])
    pos = jnp.arange(T)

    def score(q_i, w_i, keys):
        s = jnp.einsum("bhd,td->bht", q_i, keys)
        return squash(jnp.einsum("bht,bh->bt", jax.nn.relu(s), w_i))

    def block(args):
        h_b, q_b, pos_b = args
        q_i = low(h_b @ w_iq).reshape(B, Hi, Di)
        q_i = _bf16(q_i) if paged else q_i
        w_i = low(h_b @ w_iw) * (Hi * Di) ** -0.5
        pick = picked_groups(score(q_i, w_i, k_i), pos_b, k_pick, variant)
        agree = jnp.ones((B,), jnp.float32)
        if theirs is not None:
            other = picked_groups(score(q_i, w_i, theirs), pos_b, k_pick,
                                  variant)
            agree = jnp.sum(pick & other, axis=-1) / jnp.sum(pick, axis=-1)
        seen = pick
        if variant == "no_selection":
            seen = jnp.ones_like(seen)
        seen = seen & (pos_b[:, None] >= pos[None, :])
        qg = q_b.reshape(B, Hkv, H // Hkv, dh)
        s = low(jnp.einsum("bgrd,tgd->grbt", qg, k)) * dh ** -0.5
        s = jnp.where(seen[None, None], s, -jnp.inf)
        ctx = low(jnp.einsum("grbt,tgd->bgrd",
                             squash(jax.nn.softmax(s, axis=-1)), v))
        return ctx.reshape(B, H * dh), agree

    ctx, agree = jax.lax.map(block, (
        h_op.reshape(T // B, B, -1), q.reshape(T // B, B, H, dh),
        pos.reshape(T // B, B)))
    return low(op(ctx.reshape(T, H * dh)) @ op(p["out_w"])), agree.reshape(T)


def _hidden(config: dict, w: dict, ids, ids3, row, frames, variant: str = "",
            cached=None):
    """ids [T] (T whole query blocks) with the tokens' rotary ids ``ids3``
    [T, 3], the merged row each takes ``row`` [T] (-1: a text token) and the
    clip's ``frames`` [F, S, S, 3] -> (final-norm hidden [T, d] float32, the
    worst layer's pick agreement with the indexer keys ``cached`` [L, T, Di]
    a query [T])."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {sorted(VARIANTS)}")
    lossy = variant in _LOSSY
    eps = config["rms_norm_eps"]
    T = ids.shape[0]
    x = _f32(w["tok_emb"][ids])
    if variant != "no_vision" and frames.shape[0]:
        rows = tower_rows(config, w, frames)
        x = jnp.where((row >= 0)[:, None],
                      rows[jnp.clip(row, 0, rows.shape[0] - 1)], x)
    if variant == "no_mrope":
        ids3 = jnp.broadcast_to(jnp.arange(T)[:, None], (T, 3))
    stack = {k[len("lm_stack.stack_"):]: a for k, a in w.items()
             if k.startswith("lm_stack.stack_")}

    def body(carry, layer):
        x, agree = carry
        p, c = layer
        y, a = sparse_layer(config, p, _rms(x, p["ln1_s"], eps, lossy), ids3,
                            variant, c)
        x = x + y
        x = _bf16(x) if lossy else x
        x = x + expert_layer(config, p, _rms(x, p["ln2_s"], eps, lossy),
                             variant)
        x = _bf16(x) if lossy else x
        return (x, jnp.minimum(agree, a)), None

    (x, agree), _ = jax.lax.scan(
        body, (x, jnp.ones((T,), jnp.float32)), (stack, cached))
    return _rms(x, w["final_ln.scale"], eps, lossy), agree


def _padded(n: int) -> int:
    """The length the reference runs ``n`` tokens at: whole multiples of
    ``_PAD`` (few distinct compiled programs) beyond one query block."""
    if n <= _QUERY_BLOCK:
        return -(-n // 4) * 4 if n > 4 else n
    q = _PAD if n > _PAD else _QUERY_BLOCK
    return -(-n // q) * q


_HIDDEN_JITS: dict = {}


def _jit_hidden(config: dict, variant: str = "", cached: bool = False):
    import jax

    key = (id(config), variant, cached)
    if key not in _HIDDEN_JITS:
        _HIDDEN_JITS[key] = jax.jit(
            (lambda w, ids, ids3, row, frames, c: _hidden(
                config, w, ids, ids3, row, frames, variant, c)) if cached
            else (lambda w, ids, ids3, row, frames: _hidden(
                config, w, ids, ids3, row, frames, variant)))
    return _HIDDEN_JITS[key]


def _frames_of(config: dict, seq: np.ndarray, media=None, bank=None):
    """The layout and the pixels of ``seq``'s clips: -> (ids3 [n, 3], row
    [n], frames [F, S, S, 3] uint8, F padded to a whole multiple of 8: few
    compiled shapes). ``media``: the clips' frames, one entry a span; else
    drawn from ``bank`` as the benchmark's resolver draws them."""
    v = vision_of(config)
    spans, ids3, row = v.media_layout(seq)
    if media is None:
        media = [clip_frames(bank, seq, span) for span in spans]
    frames = (np.concatenate(media) if media
              else np.zeros((0,) + v.frame_shape, np.uint8))
    pad = -frames.shape[0] % 8 if frames.shape[0] else 0
    frames = np.concatenate([frames, np.zeros((pad,) + v.frame_shape,
                                              np.uint8)])
    return ids3, row, frames


def _rows_logits(config: dict, w: dict, seq: np.ndarray, rows,
                 variant: str = "", cached=None, media=None, bank=None):
    """Teacher-forced reference logits [len(rows), V] at positions ``rows``
    of ``seq`` (the head over those rows only) and the pick agreement
    there."""
    import jax
    import jax.numpy as jnp

    n, T = seq.size, _padded(seq.size)
    ids = np.zeros(T, np.int32)
    ids[:n] = seq
    ids3, row, frames = _frames_of(config, seq, media, bank)
    ids3 = np.concatenate([ids3, np.repeat(
        np.arange(n, T, dtype=np.int32)[:, None], 3, axis=1)])
    row = np.concatenate([row, np.full(T - n, -1, np.int32)])
    if cached is not None:
        held = np.zeros((cached.shape[0], T, cached.shape[-1]), np.float32)
        held[:, :min(cached.shape[1], T)] = cached[:, :T]
        cached = held
    rows = np.asarray(rows)
    whole = np.concatenate([rows, np.repeat(rows[-1:],
                                            -rows.size % _QUERY_BLOCK)])
    with jax.default_matmul_precision("highest"):
        fn = _jit_hidden(config, variant, cached is not None)
        args = (w, jnp.asarray(ids), jnp.asarray(ids3), jnp.asarray(row),
                jnp.asarray(frames)) + (() if cached is None else (cached,))
        hidden, agree = fn(*args)
        logits = np.concatenate([
            np.asarray(_head(hidden[jnp.asarray(whole[i:i + _QUERY_BLOCK])],
                             w["lm_head.w"]))
            for i in range(0, whole.size, _QUERY_BLOCK)])[:rows.size]
    return logits, np.asarray(agree)[rows]


def reference_logits(config: dict, w: dict, ids, rows=None,
                     variant: str = "", media=None, bank=None):
    """ids [T] -> logits [len(rows), V] float32 at positions ``rows`` (all
    T when None: small models only). ``media``: the clips' frames (one
    entry a vision span), else ``bank``'s as the resolver draws them."""
    ids = np.asarray(ids)
    rows = np.arange(ids.size) if rows is None else np.asarray(rows)
    return _rows_logits(config, w, ids, rows, variant, media=media,
                        bank=bank)[0]


_INDEX_POOL, _TABLE = "serving.paged_cache_index", "serving.block_table"


def served(eng, prompt, new_tokens: int, media=None):
    """One request through ``eng`` (beam plane on), chunked prefill then
    decode: -> ([(position, top-k log-probs, their ids)] of every chunk end
    and decode step, the emitted sequence, the indexer keys its pages hold
    [L, tokens, Di])."""
    calls, tables = [], []
    run = eng.executor.run
    prompt = np.asarray(prompt)

    def capture(prog, feed=None, fetch_list=None, scope=None, **kw):
        res = run(prog, feed=feed, fetch_list=fetch_list, scope=scope, **kw)
        if not feed or _TABLE not in feed:
            return res
        if "serving.chunk" in feed:
            # THIS request's chunks alone: told by their tokens
            start = int(feed["serving.start"][0])
            n = int(feed["serving.chunk_len"][0])
            if not n or not np.array_equal(
                    np.asarray(feed["serving.chunk"])[0, :n],
                    prompt[start:start + n]):
                return res
            tables.append(np.asarray(feed[_TABLE])[0].copy())
            pos, row = start + n - 1, 0
        else:
            # (a prompt the index holds whole prefills nothing: its first
            # tick re-feeds the last prompt token and is told by its position)
            tbl = np.asarray(feed[_TABLE])
            live = feed["serving.pos"] >= prompt.size - 1
            mine = np.flatnonzero(live & (
                (tbl[:, 0] == tables[-1][0]) if tables else (tbl[:, 0] != 0)))
            if not mine.size:       # not decoding yet
                return res
            row = int(mine[0])
            if not tables:
                tables.append(tbl[row].copy())
            pos = int(feed["serving.pos"][row])
        calls.append((pos, np.asarray(res[1])[row], np.asarray(res[2])[row]))
        return res

    eng.executor.run = capture
    try:
        payload = {"prompt": prompt}
        if media is not None:
            payload["media"] = media
        out = np.asarray(eng.generate_all([payload],
                                          max_new_tokens=new_tokens)[0])
    finally:
        eng.executor.run = run
    pages = tables[-1][:-(-out.size // eng.page_size)]
    pool = eng.scope.get(_INDEX_POOL)[:, pages]         # [L, pages, ps, Di]
    cached = np.asarray(pool, np.float32).reshape(
        pool.shape[0], -1, pool.shape[-1])
    return calls, out, cached


def _logprobs(logits):
    ref = logits - logits.max(axis=-1, keepdims=True)
    return ref - np.log(np.exp(ref).sum(axis=-1, keepdims=True))


def served_errors(config: dict, w: dict, eng, prompt, new_tokens: int,
                  variants=("",), media=None, bank=None):
    """One request through ``eng``: -> ({variant: the errors of the served
    top-k log-probs a served position against that reference}, the emitted
    sequence, the served positions, {variant: 1 - the pick agreement a
    served position})."""
    calls, again, cached = served(eng, prompt, new_tokens, media)
    at = np.asarray([p for p, _, _ in calls])
    errs, miss = {}, {}
    for variant in variants:
        logits, agree = _rows_logits(
            config, w, again[:-1], at, variant,
            cached=cached[:, :again.size - 1], media=media, bank=bank)
        ref = _logprobs(logits)
        errs[variant] = [float(np.abs(v - ref[j][i]).max())
                         for j, (_, v, i) in enumerate(calls)]
        miss[variant] = (1.0 - agree).tolist()
    return errs, again, at, miss


def check_readings(config: dict, w: dict,
                   results: List[Tuple[int, np.ndarray]],
                   variants=("",)) -> Dict[str, dict]:
    """The check's readings on the checked requests ``results`` =
    [(prompt_len, ids the TIMED engine returned)], against the reference
    (``""``) and, for ``tools/keye2_chip_check.py controls``, against each
    WRONG model of ``variants``: the requests are replayed ONCE through the
    engine ``build_engine`` built and every variant's reference reads the
    same served rows. -> {variant: the readings and, under ``"scaled"``, the
    four numbers ``reference_logit_gaps`` returns}."""
    import time

    entry = _ENGINES.get(id(config))
    if entry is None or entry[0].beam_width != CHECK_TOPK:
        raise ValueError("reference_logit_gaps replays the checked requests "
                         "through the engine build_engine built, with the "
                         "beam plane on (engine.beam_width in the mix)")
    eng, bank = entry
    acc = {v: {"errs": [], "gaps": [], "miss": []} for v in variants}
    same = 0
    t0, spent = time.monotonic(), {"replay_s": 0.0, "reference_s": 0.0}
    for prompt_len, out in results:
        out = np.asarray(out)[:prompt_len + CHECK_REPLAY_TOKENS]
        if eng.prefix_index is not None:
            eng.prefix_index.clear()        # a COLD replay: every unit runs
        calls, again, cached = served(eng, out[:prompt_len],
                                      out.size - prompt_len)
        spent["replay_s"] -= t0 - (t0 := time.monotonic())
        at = np.asarray([p for p, _, _ in calls])
        emitted = np.arange(prompt_len - 1, out.size - 1)
        equal = np.array_equal(again, out)
        same += equal
        for variant, a in acc.items():
            logits, agree = _rows_logits(
                config, w, again[:-1],
                np.concatenate([at, emitted]) if equal else at, variant,
                cached=cached[:, :again.size - 1], bank=bank)
            mine = logits[at.size:] if equal else _rows_logits(
                config, w, out[:-1], emitted, variant, bank=bank)[0]
            ref = _logprobs(logits[:at.size])
            a["errs"].extend(float(np.abs(v - ref[j][i]).max())
                             for j, (_, v, i) in enumerate(calls))
            a["miss"].extend((1.0 - agree[:at.size]).tolist())
            a["gaps"].extend((mine.max(axis=-1) - mine[
                np.arange(emitted.size), out[emitted + 1]]).tolist())
        spent["reference_s"] -= t0 - (t0 := time.monotonic())
    lines = {}
    for variant, a in acc.items():
        errs, gaps, miss = a["errs"], a["gaps"], a["miss"]
        held = float(np.percentile(errs, CHECK_LOGPROB_QUANTILE))
        worst, largest = float(max(gaps)), float(max(errs))
        missed = float(np.mean(miss))
        lines[variant] = {
            "quantile": CHECK_LOGPROB_QUANTILE, "limit": CHECK_LOGPROB_TOL,
            **{f"served_logprob_err_p{q}": float(np.percentile(errs, q))
               for q in (50, 80, 90, 95, 99)},
            "served_logprob_err_max": largest,
            "served_logprob_err_max_limit": CHECK_LOGPROB_MAX_TOL,
            "served_positions": len(errs), "emitted_gap_max": worst,
            "emitted_gap_limit": CHECK_EMITTED_GAP_TOL,
            "emitted_positions": len(gaps),
            "pick_miss_mean": missed, "pick_miss_max": float(max(miss)),
            "pick_miss_limit": CHECK_PICK_MISS_TOL,
            "requests": len(results),
            "contexts": [int(min(np.asarray(o).size,
                                 p + CHECK_REPLAY_TOKENS))
                         for p, o in results],
            "replays_equal_to_timed": int(same), **spent,
            "scaled": [held,
                       largest * CHECK_LOGPROB_TOL / CHECK_LOGPROB_MAX_TOL,
                       worst * CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL,
                       missed * CHECK_LOGPROB_TOL / CHECK_PICK_MISS_TOL]}
    return lines


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """What the serve driver holds a run to: it compares the LARGEST value
    returned with the mix's ``check.logit_gap_tol`` = ``CHECK_LOGPROB_TOL``.
    Four readings, each scaled into that limit's terms:

    1. the ``CHECK_LOGPROB_QUANTILE``-th percentile of the SERVED top-8
       log-prob error: every checked request ``(prompt_len, ids)``, its
       prompt and the first ``CHECK_REPLAY_TOKENS`` tokens of its answer, is
       replayed, after the drain, through the TIMED engine itself with its
       prefix index emptied first (the resolver's pixels, the tower in every
       unit that holds placeholder rows, chunked prefill with selection in
       every chunk past the second, then the tick's selection through the
       cache) and what it serves at every chunk end and decode step is
       compared with the reference's teacher-forced full forward of the
       replayed sequence, pixels included;
    2. the LARGEST such error (limit ``CHECK_LOGPROB_MAX_TOL``);
    3. on the tokens the TIMED engine emitted: how far below its position's
       best the reference puts each (the largest; limit
       ``CHECK_EMITTED_GAP_TOL``);
    4. the mean share of a served query's picked positions that the
       reference's queries, scoring the indexer keys the engine's pages
       HOLD, pick differently from scoring the reference's own keys (the
       worst layer a query; limit ``CHECK_PICK_MISS_TOL``): keys computed
       or stored in a lower precision, a pool written wrong. The reading
       that tells the stated precision from the one below it.

    The readings go to stderr as one JSON line."""
    import json
    import sys

    if not results:
        return np.zeros((0,), np.float32)
    line = check_readings(config, w, results)[""]
    print(json.dumps({"dsa_gqa_moe_vl.check": line}), file=sys.stderr,
          flush=True)
    return np.asarray(line["scaled"], np.float32)


# ---------------------------------------------------------------------------
# what the mechanisms have to move, and which device event belongs to which
# ---------------------------------------------------------------------------
def dsa_cost(config: dict, groups_scored: float,
             rows_attended: float) -> Dict[str, float]:
    """The least work of ONE layer's selection and attention over
    ``groups_scored`` indexer keys (one a token: ``index_pool`` 1) and
    ``rows_attended`` picked tokens (the engine's counters of the same
    names), whatever implements it: each indexer key and each picked token's
    K and V row read ONCE in the page dtype; the indexer's Hi dot products of
    Di a key and the attention's H scores and H read-outs of dh a row."""
    sa = config["sa_config"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    H, dh = config["num_attention_heads"], config["head_dim"]
    width = config["num_key_value_heads"] * dh
    itemsize = 2 if config["assumed"]["page_dtype"] == "bfloat16" else 4
    return {"bytes": (groups_scored * Di + rows_attended * 2 * width)
            * itemsize,
            "flops": 2.0 * groups_scored * Hi * Di
            + 2.0 * rows_attended * H * 2 * dh}


def _geometry(config: dict):
    a = config["assumed"]
    table = -(-a["max_len"] // a["page_size"])
    return {"table": table, "keys": table * a["page_size"],
            "ps": a["page_size"]}


_WALKS = ("paged_attention_decode", "paged_attention_prefill")


def dsa_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of a layer's selection a device event belongs to, by
    the shapes it reads or writes: ``"score"`` (the indexer's products, relu
    and head sum over the table's keys, the cached keys' gather), ``"pick"``
    (the k-th score's search and the flags over them), ``"attend"`` (the page
    walk under the pick: the calls ``paged_attention_decode`` /
    ``paged_attention_prefill``, which every layer of this family runs
    masked), ``"pool"`` (the indexer keys' pool: its write), ``"project"``
    (the indexer's projections). None for everything else."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    g, sa = _geometry(config), config["sa_config"]
    d, L = config["hidden_size"], config["num_hidden_layers"]
    Hi, Di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    if opcode == "custom-call" and name.split(".")[0] in _WALKS:
        return "attend"
    if re.search(rf"[\[,]{g['keys']}[,\]]", text):
        if re.search(rf"\[(\d+,)*{Hi},{g['keys']}\]", text) or re.search(
                rf"\[(\d+,)*{g['keys']},{Di}\]", text) \
                or re.search(rf"\[(\d+,)*{g['keys']},{Hi}\]", text):
            return "score"
        return "pick"
    if re.search(rf"\[{L},\d+,{g['ps']},{Di}\]", text):
        return "score" if opcode == "gather" else "pool"
    if f"[{d},{Hi * Di}]" in text or f"[{d},{Di}]" in text \
            or f"[{d},{Hi}]" in text:
        return "project"
    return None


def dsa_tick_op(hlo_text: str, config: dict, slots: int) -> bool:
    """Whether a device event ``dsa_op`` names belongs to the decode TICK:
    its result leads with the slot count (a chunk's leads with its one row
    or its query tile)."""
    from benchmark.trace_reduce import strip_layouts

    m = re.match(r"^%\S+ = \(?[a-z]+\d*\[(\d+)[,\]]",
                 strip_layouts(hlo_text))
    return bool(m) and int(m.group(1)) == slots


def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """As ``moe_lm.moe_op`` at this configuration's keys:
    ``"grouped_matmul"`` | ``"route"`` | None, by the expert stacks' shapes
    [.., d, f] / [.., f, d] (f = ``moe_intermediate_size``)."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    E, d, f = (config["num_experts"], config["hidden_size"],
               config["moe_intermediate_size"])
    if name.startswith("ragged-dot") or re.search(
            rf"\[(\d+,)+({d},{f}|{f},{d})\]", text):
        return "grouped_matmul"
    if f"[{d},{E}]" in text or (
            opcode in ("sort", "topk") and f",{E}]" in text):
        return None if f",{config['vocab_size']}]" in text else "route"
    return None


def vision_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of the tower a device event belongs to, by the shapes
    it reads or writes: ``"patch"`` (pixels to patches, the patch embedding,
    the position table's resize), ``"attn"`` (a block's qkv / out
    projections, its 2-D rotary, scores and read-out), ``"mlp"`` (a block's
    feed-forward), ``"merge"`` (the final norm, the 2 x 2 concatenation, the
    merger's two products). None for everything else."""
    from benchmark.trace_reduce import strip_layouts

    text = strip_layouts(hlo_text)
    v = vision_of(config)
    dv, f, m = v.d_model, v.d_ff, v.merge ** 2 * v.d_model
    n, S, Hv = v.grid ** 2, v.image_size, v.num_heads
    if re.search(rf"[\[,]{m}[,\]]", text):
        return "merge"
    if re.search(rf"[\[,]{f}[,\]]", text):
        return "mlp"
    if re.search(rf"[\[,]{v.patch_values}[,\]]", text) \
            or re.search(rf"\[(\d+,)*{S},{S},3\]", text) \
            or re.search(rf"\[{v.pos_grid ** 2},{dv}\]", text) \
            or re.search(rf"\[{v.pos_grid},{v.pos_grid},{dv}\]", text):
        return "patch"
    if re.search(rf"[\[,]{3 * dv}[,\]]", text) \
            or re.search(rf"\[{Hv},{n},{n}\]", text) \
            or re.search(rf"\[{n},{Hv},{dv // Hv}\]", text) \
            or re.search(rf"\[{n},{Hv},{dv // Hv // 2}\]", text) \
            or re.search(rf"\[(\d+,)?{n},{dv}\]", text) \
            or f"[{dv},{dv}]" in text:
        return "attn"
    return None


def vision_cost(config: dict, frames: float) -> Dict[str, float]:
    """The tower's and the merger's operations for ``frames`` frames (the
    engine's ``vision_frames_encoded``): 2 x (patches x the blocks' matrix
    parameters + the attention's scores and read-out within a frame) + the
    patch embedding and the merger."""
    v = vision_of(config)
    n, dv, m = v.grid ** 2, v.d_model, v.merge ** 2 * v.d_model
    block = 4 * dv * dv + 2 * dv * v.d_ff
    per_frame = 2.0 * n * (v.patch_values * dv + v.n_layers * block) \
        + v.n_layers * 4.0 * n * n * dv \
        + 2.0 * v.tokens_per_frame * (m * m + m * config["hidden_size"])
    return {"flops": frames * per_frame}
