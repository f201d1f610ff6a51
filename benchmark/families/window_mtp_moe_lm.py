"""Family ``window_mtp_moe_lm``: a K-EXAONE-shaped decoder — window and
full attention layers in one stack (``layer_types``: three
``sliding_attention`` layers of ``sliding_window`` keys, one
``full_attention``), grouped-query attention at an explicit ``head_dim``,
RoPE on every layer, pre-norm RMSNorm, no biases; a dense SwiGLU first layer
(``first_k_dense_replace``), then a sigmoid-scored top-k router over ALL
``router_outputs`` experts of which THIS chip holds a share, beside one
always-on shared expert; an untied head; and a DRAFTING
(multi-token-prediction) block behind the stack — served by
``serving.GenerationEngine(spec, ...)`` from ONE ``paddle_tpu.lm_spec.LMSpec``
(``spec_of``), with the yardstick's own pieces: the attention walk's and the
held experts' bytes and operations, and a plain float32 ``jax.numpy``
reference of the equations (x [T, d], position i, layer l):

    a = RMSNorm_1(x)                     RMSNorm(u) = u rsqrt(mean(u^2) + eps) w
    q = a W_q (H x dh)  k = a W_k (Hkv x dh)  v = a W_v (Hkv x dh)
    q, k <- RoPE(theta, pairing (i, i + dh/2))            on EVERY layer
    s_ij = q_i . k_j / sqrt(dh), j <= i; sliding_attention: only i - j < window
    h = x + softmax(s) v W_o             query head n reads KV head n // (H / Hkv)
    b = RMSNorm_2(h)
    l < first_k_dense_replace:  y = h + (silu(b W_g) * (b W_u)) W_d      (width intermediate_size)
    else: s = sigmoid(b W_r) over ALL router outputs (float32); S = top-k of s
          w_e = s_e / sum_S s;  E(u) = (silu(u W_g,e) * (u W_u,e)) W_d,e
          y = h + routed_scaling_factor * sum_{e in S, e HELD} w_e E_e(b) + E_shared(b)
    logits_i = RMSNorm_f(y_L,i) W_head

    the drafting block (DeepSeek-V3's form, arXiv:2412.19437 section 2.2),
    with r_i = y_L,i BEFORE RMSNorm_f and t_{i+1} the next token:
    u_i = [RMSNorm_h(r_i) ; RMSNorm_e(Emb(t_{i+1}))] M    M [2d, d]: rows 0..d-1 the hidden half
    g = Block_mtp(u)   one full-attention layer as above (K/V of its own over u_0..u_i,
                       RoPE at position i, the expert FFN with its own held experts)
    draft_{i+2} = argmax RMSNorm_m(g_i) W_head             embedding and head shared

What the absent experts would add is left out (program and reference alike)
and the partial result goes on: the chip's share of an expert-parallel
deployment, computed without its exchange (the ``model-configs`` guide,
section 4). ``expert_layer(.., held=)`` gives any share, so a test can add
the shares up to the uncut layer.

The reference has no cache, no kernel, no sort and no grouped matmul: every
HELD expert is applied densely to every token and masked by the top-k set,
the window is a mask over full scores. It reads the SAME stored weights as
the program (bfloat16 in the benchmark's configuration), upcasts a layer at
a time, runs the attention in QUERY BLOCKS, and makes logits only for the
rows asked for.

Departures from the published model, all under ``assumed`` in the
configuration file too: pre-norm placement, no QK-norm, no projection bias
and RoPE on every layer (the published config has no key for any of them);
no selection bias (no ``e_score_correction`` key); the drafting block's form
and the order of M's halves; q, k and v are one fused [d, (H + 2 Hkv) dh]
matrix (columns q | k | v): a layout. ``build_engine`` multiplies the seeded
token embedding by ``assumed.embedding_scale`` (as every serve configuration
does) and gives the drafting block's OWN planes the start-up of
``assumed.mtp_init`` (the reference reads the same stored weights).
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.moe_lm import (  # noqa: F401 - the family's surface
    draw_prompt_ids, grouped_matmul_cost)
from benchmark.families.paged_attention import decode_cost
from benchmark.families.window_moe_lm import (  # the same plain pieces
    _QUERY_BLOCK, _f32, _head, _rms, _rope_half)

ITEM = "tokens"
_ALL = ("ln1_s", "qkv_w", "out_w", "ln2_s")
_DENSE = ("dense_gate_w", "dense_up_w", "dense_down_w")
_EXPERTS = ("router_w", "moe_gate_w", "moe_up_w", "moe_down_w",
            "shared_gate_w", "shared_up_w", "shared_down_w")
_MTP_OWN = ("proj_w", "norm_h_s", "norm_e_s", "head_norm_s")
_EXPERT_BLOCK = 4       # experts upcast to float32 at a time
_TOKEN_BLOCK = 1024     # tokens that go through an FFN together
#: WRONG models, one fault each, that the cell's check
#: (``reference_logit_gaps``; ``tools/kexaone_chip_check.py variants``) and
#: the tier-1 tests must tell from the right one
VARIANTS = {
    "no_window": "the sliding_attention layers attend every earlier key",
    "window_127": "a window one key short (i - j < window - 1)",
    "nope_full": "the full_attention layers do not rotate q and k",
    "softmax_router": "softmax over the router's outputs for the sigmoid",
    "no_shared_expert": "the always-on expert left out",
    "no_routed_scale": "routed_scaling_factor left out",
    "mtp_normed_h": "the drafting block fed RMSNorm_f(r_i), not r_i",
    "mtp_halves_swapped": "[embedding ; hidden] into M for [hidden ; "
                          "embedding]",
    "mtp_windowed": "the drafting block attends sliding_window keys, as a "
                    "sliding_attention layer does (K/V rows of its own "
                    "that it never reads)",
    "mtp_no_shared_expert": "the drafting block's always-on expert left "
                            "out",
    "bf16_stated_f32": "norms, router scores, softmax and the residual "
                       "stream between blocks rounded to bfloat16 where "
                       "the configuration says float32",
}
#: variants that touch the drafting block alone: the stack's logits cannot
#: see them (by construction: a draft decides how MANY tokens a tick
#: emits, never which), the draft reading has to
DRAFT_VARIANTS = ("mtp_normed_h", "mtp_halves_swapped", "mtp_windowed",
                  "mtp_no_shared_expert")

#: THE LIMIT on the served top-8 log-prob error (the mix's
#: ``check.logit_gap_tol`` IS this number): the ``CHECK_LOGPROB_QUANTILE``-th
#: percentile over the served positions. PERF.md section 6 (PR 49) has the
#: readings it lies between.
CHECK_LOGPROB_QUANTILE = 90
CHECK_LOGPROB_TOL = 0.004
#: how far below its position's best the reference may put a token the
#: TIMED engine emitted (catches another block function, or a request
#: answered with another's tokens)
CHECK_EMITTED_GAP_TOL = 0.5
#: the share of checked positions at which the engine's draft may differ
#: from the reference drafting block's argmax
CHECK_DRAFT_UNEQUAL_TOL = 0.1
#: THE LIMIT on the drafting block's served top-8 log-prob error (the same
#: percentile, over the positions a draft was left at): an argmax tells
#: which HALF of M the block reads and little else (the embedding half
#: decides most drafts), its log-probs move with the block's attention, its
#: K/V rows and its experts. PERF.md section 6 (PR 49, review round) has the
#: readings: right 0.0037-0.0041, the block windowed 0.0129 (a computation
#: one precision lower reads as right here: reading 1 is the one it fails)
CHECK_DRAFT_LOGPROB_TOL = 0.007
#: the beam plane's width in the check's replay: top-8 log-probs a position
CHECK_TOPK = 8
#: answer tokens of a checked request that the check replays and reads (a
#: verify tick of the twin costs what the timed engine's does, and one whole
#: run of the cell, cold, has 300 s: ROADMAP S11; the mix checks FOUR
#: requests, so it is the stretch of each that is cut)
CHECK_REPLAY_TOKENS = 96
#: the lengths the reference runs at (one compiled program each; the
#: longest prompt of the cell's mix, 4096, and its replayed stretch fit the
#: second)
_REFERENCE_LENGTHS = (2048, 4352)


def pattern_of(config: dict) -> Tuple[str, ...]:
    """One period of layer kinds from ``layer_types`` (every layer
    rotates), which must repeat it down the whole (cut) stack."""
    L = config["num_hidden_layers"]
    kinds = [("window" if t == "sliding_attention" else "full") + "+rope"
             for t in config["layer_types"][:L]]
    for p in range(1, L + 1):
        if L % p == 0 and kinds == kinds[:p] * (L // p):
            return tuple(kinds[:p])
    raise ValueError(f"no period in {kinds}")


def held_of(config: dict) -> Tuple[int, int]:
    """(first, count): the routed experts this chip holds."""
    return config["assumed"]["experts_first"], config["num_experts"]


def spec_of(config: dict):
    """The program's model spec for this configuration: a tree whose spec
    lacks a dense head under layer kinds or the drafting block fails here,
    at once, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a = config["assumed"]
    first, count = held_of(config)
    E = config["router_outputs"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    if config["mtp_layer_types"] != ["full_attention"] \
            or config["num_nextn_predict_layers"] != 1:
        raise ValueError("one full-attention drafting block is what the "
                         "spec has")
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], num_heads=H,
        num_kv_heads=None if Hkv == H else Hkv, head_dim=config["head_dim"],
        use_rope=True, layer_pattern=pattern_of(config),
        window=config["sliding_window"], max_len=a["max_len"],
        norm="rms_norm", norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        rope_pairing="half", ffn="swiglu_moe", num_experts=E,
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["num_shared_experts"]
        * config["moe_intermediate_size"],
        d_ff=config["intermediate_size"],
        first_dense=config["first_k_dense_replace"],
        experts_held=None if (first, count) == (0, E) else (first, count),
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        router_score=config["scoring_func"], n_group=config["n_group"],
        topk_group=config["topk_group"], bias=False, draft_block=True,
        param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def build_engine(config: dict, mix: dict, seed: int, **engine_kw):
    """-> (engine, executors). Weights come from ONE run of the start-up
    program of ``models.lm_parameters`` on the device, seeded, in the
    configuration's stored dtype; then the embedding's scale and the
    drafting block's own start-up (``assumed.mtp_init``), BEFORE the pools
    are allocated. ``engine_kw``: further engine keywords."""
    spec = spec_of(config)      # first: a tree without the spec stops here
    import paddle_tpu as pt
    from paddle_tpu import models

    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        models.lm_parameters(spec)
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    a = config["assumed"]
    scope.set("tok_emb", (scope.get("tok_emb")
                          * a["embedding_scale"]).block_until_ready())
    mtp_start_up(scope, a["mtp_init"])
    eng = _engine(spec, scope, mix["engine"], **engine_kw)
    _ENGINES[id(config)] = (mix["engine"], eng)
    return eng, [exe, eng.executor]


def mtp_start_up(scope, init: dict) -> None:
    """The drafting block's OWN planes as ``assumed.mtp_init`` starts them
    (nothing of the stack, the embedding or the head is touched): M's
    embedding half = ``embedding_pass`` x the identity (the block sees the
    token it drafts after), its hidden half the seeded values x
    ``hidden_scale``, and the block's three output planes (attention,
    routed experts, shared expert) x ``block_out_scale``."""
    import jax.numpy as jnp

    m = scope.get("mtp.proj_w")
    d = m.shape[1]
    eye = jnp.eye(d, dtype=jnp.float32) * init["embedding_pass"]
    scope.set("mtp.proj_w", jnp.concatenate(
        [_f32(m[:d]) * init["hidden_scale"], eye]).astype(
            m.dtype).block_until_ready())
    for key in ("out_w", "moe_down_w", "shared_down_w"):
        name = f"mtp_stack.stack_{key}"
        v = scope.get(name)
        scope.set(name, (_f32(v) * init["block_out_scale"]).astype(
            v.dtype).block_until_ready())


def _engine(spec, scope, e: dict, **engine_kw):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], n_pages_window=e["n_pages_window"],
        max_seq_len=e["max_len"], prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None,
        mask_plane=bool(e.get("mask_plane", 1)), **engine_kw)


#: id(configuration) -> (the mix's ``engine`` section, the engine) of the
#: last ``build_engine``: the check's replay engine is its twin
_ENGINES: dict = {}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The model's parameters by the fixed names the layout gives them, as
    stored (nothing is copied or cast): the stack, and the drafting
    block's (``mtp.*``, ``mtp_stack.stack_*``)."""
    names = (["tok_emb", "final_ln.scale", "lm_head.w"]
             + [f"lm_stack.stack_{k}" for k in _ALL + _DENSE + _EXPERTS]
             + [f"mtp.{k}" for k in _MTP_OWN]
             + [f"mtp_stack.stack_{k}" for k in _ALL + _EXPERTS])
    return {name: scope.get(name) for name in names}


def _squash(lossy):
    import jax.numpy as jnp

    return (lambda t: _f32(t.astype(jnp.bfloat16))) if lossy else (lambda t: t)


def expert_layer(config: dict, p: dict, h2, held=None, variant: str = "",
                 parts: bool = False):
    """The expert half of a layer on h2 [T, d] (float32) with per-layer
    weights ``p`` (``moe_*_w`` [count, ..] holding the ``held`` = (first,
    count) experts; None: the configuration's share): shared + scale *
    routed, or (routed, shared) under ``parts``. The router scores ALL
    ``router_outputs`` experts with a sigmoid."""
    import jax
    import jax.numpy as jnp

    squash = _squash(variant == "bf16_stated_f32")
    first, count = held or held_of(config)
    k = config["num_experts_per_tok"]
    T = h2.shape[0]
    logits = squash(h2 @ _f32(p["router_w"]))
    score = (jax.nn.softmax(logits, axis=-1) if variant == "softmax_router"
             else jax.nn.sigmoid(logits))
    score = squash(score)                                       # [T, E]
    kth = jax.lax.top_k(score, k)[0][:, -1:]
    gate = jnp.where(score >= kth, score, 0.0)
    if config["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate[:, first:first + count]                         # the held
    eb = next(b for b in (_EXPERT_BLOCK, 2, 1) if count % b == 0)
    Bt = next(b for b in (_TOKEN_BLOCK, 512, 256, 128, T) if T % b == 0)

    def token_block(blk):
        b_b, gate_b = blk                       # [Bt, d], [Bt, count]

        def expert_block(y, e0):
            wg, wu, wd = (jax.lax.dynamic_slice_in_dim(p[name], e0, eb, 0)
                          for name in ("moe_gate_w", "moe_up_w",
                                       "moe_down_w"))
            g_blk = jax.lax.dynamic_slice_in_dim(gate_b, e0, eb, 1)
            gated = (jax.nn.silu(jnp.einsum("td,edf->tef", b_b, _f32(wg)))
                     * jnp.einsum("td,edf->tef", b_b, _f32(wu)))
            return y + jnp.einsum("tef,efd,te->td", gated, _f32(wd),
                                  g_blk), None

        return jax.lax.scan(expert_block, jnp.zeros_like(b_b),
                            jnp.arange(0, count, eb))[0]

    routed = jax.lax.map(token_block, (
        h2.reshape(T // Bt, Bt, -1), gate.reshape(T // Bt, Bt, count))
    ).reshape(T, -1)
    if variant != "no_routed_scale":
        routed = routed * config["routed_scaling_factor"]
    shared = jnp.zeros_like(h2)
    if variant != "no_shared_expert":
        shared = _swiglu(h2, p["shared_gate_w"], p["shared_up_w"],
                         p["shared_down_w"])
    return (routed, shared) if parts else routed + shared


def _swiglu(b, wg, wu, wd):
    """(silu(b W_g) * (b W_u)) W_d over token blocks (the dense layer's
    three planes are 1.4 GB in float32 at the published width)."""
    import jax

    T = b.shape[0]
    Bt = next(n for n in (_TOKEN_BLOCK, 512, 256, 128, T) if T % n == 0)
    wg, wu, wd = _f32(wg), _f32(wu), _f32(wd)
    return jax.lax.map(
        lambda x: (jax.nn.silu(x @ wg) * (x @ wu)) @ wd,
        b.reshape(T // Bt, Bt, -1)).reshape(T, -1)


def _attention(config: dict, p: dict, x, windowed, rotates, variant: str):
    """x [T, d] -> (x + attention, RMSNorm_2 of it), one layer's planes
    ``p``; ``windowed`` / ``rotates``: traced booleans of the layer."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"
    squash = _squash(lossy)
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, eps = config["head_dim"], config["rms_norm_eps"]
    G, dq, dkv = H // Hkv, H * dh, Hkv * dh
    theta = float(config["rope_parameters"]["rope_theta"])
    window = config["sliding_window"] - (variant == "window_127")
    T = x.shape[0]
    B = min(_QUERY_BLOCK, T)
    if T % B:
        raise ValueError(f"{T} tokens are not whole blocks of {B}")
    pos = jnp.arange(T)
    a = _rms(x, p["ln1_s"], eps, lossy)
    wqkv, wo = _f32(p["qkv_w"]), _f32(p["out_w"])
    kk = (a @ wqkv[:, dq:dq + dkv]).reshape(T, Hkv, dh)
    v = (a @ wqkv[:, dq + dkv:]).reshape(T, Hkv, dh)
    kk = jnp.where(rotates, _rope_half(kk, pos, theta), kk)

    def query_block(blk):
        x_b, a_b, pos_b = blk
        q = (a_b @ wqkv[:, :dq]).reshape(B, H, dh)
        q = jnp.where(rotates, _rope_half(q, pos_b, theta), q)
        q = q.reshape(B, Hkv, G, dh)                    # head n = g * G + r
        s = jnp.einsum("bgrd,tgd->grbt", q, kk) / np.sqrt(dh)
        dist = pos_b[:, None] - pos[None, :]
        seen = (dist >= 0) & (~windowed | (dist < window))
        s = jnp.where(seen[None, None], s, -jnp.inf)
        ctx = jnp.einsum("grbt,tgd->bgrd",
                         squash(jax.nn.softmax(s, axis=-1)), v)
        return x_b + ctx.reshape(B, dq) @ wo

    h = squash(jax.lax.map(query_block, (
        x.reshape(T // B, B, -1), a.reshape(T // B, B, -1),
        pos.reshape(T // B, B))).reshape(T, -1))
    return h, _rms(h, p["ln2_s"], eps, lossy)


def _padded(n: int) -> int:
    """The length the reference runs a sequence of ``n`` tokens at: whole
    query blocks, in FEW distinct sizes (the mask is causal, so the pad
    cannot reach back)."""
    if n <= _QUERY_BLOCK:
        return n
    fits = [size for size in _REFERENCE_LENGTHS if size >= n]
    return fits[0] if fits else -(-n // _QUERY_BLOCK) * _QUERY_BLOCK


def _stack(config: dict, w: dict, ids, variant: str = ""):
    """ids [T] -> r [T, d]: the stack's output BEFORE the final norm."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {sorted(VARIANTS)}")
    L, fd = config["num_hidden_layers"], config["first_k_dense_replace"]
    types = config["layer_types"][:L]
    windowed = jnp.asarray([t == "sliding_attention" for t in types])
    if variant == "no_window":
        windowed = jnp.zeros_like(windowed)
    rotates = jnp.ones((L,), bool)
    if variant == "nope_full":
        rotates = windowed
    stack = {k: w[f"lm_stack.stack_{k}"] for k in _ALL + _DENSE + _EXPERTS}
    squash = _squash(variant == "bf16_stated_f32")  # the stream, lossy
    x = _f32(w["tok_emb"][ids])
    for l in range(fd):                                 # the dense head
        p = {k: stack[k][l] for k in _ALL + _DENSE}
        h, b = _attention(config, p, x, windowed[l], rotates[l], variant)
        x = squash(h + _swiglu(b, p["dense_gate_w"], p["dense_up_w"],
                               p["dense_down_w"]))

    def layer(x, inp):
        p, win, rot = inp
        h, b = _attention(config, p, x, win, rot, variant)
        return squash(h + expert_layer(config, p, b, variant=variant)), None

    rest = {**{k: stack[k][fd:] for k in _ALL},
            **{k: stack[k] for k in _EXPERTS}}
    x, _ = jax.lax.scan(layer, x, (rest, windowed[fd:], rotates[fd:]))
    return x


def _drafts(config: dict, w: dict, r, ids_next, variant: str = ""):
    """r [T, d] (``_stack``), ids_next [T] (the token after each position)
    -> RMSNorm_m(g) [T, d]: the drafting block's output, normed for the
    shared head."""
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"
    eps = config["rms_norm_eps"]
    if variant == "mtp_normed_h":
        r = _rms(r, w["final_ln.scale"], eps, lossy)
    halves = [_rms(r, w["mtp.norm_h_s"], eps, lossy),
              _rms(_f32(w["tok_emb"][ids_next]), w["mtp.norm_e_s"], eps,
                   lossy)]
    if variant == "mtp_halves_swapped":
        halves = halves[::-1]
    u = jnp.concatenate(halves, axis=-1) @ _f32(w["mtp.proj_w"])
    p = {k: w[f"mtp_stack.stack_{k}"][0] for k in _ALL + _EXPERTS}
    h, b = _attention(config, p, u, jnp.asarray(variant == "mtp_windowed"),
                      jnp.ones((), bool), variant)
    g = h + expert_layer(config, p, b, variant=variant.removeprefix("mtp_"))
    return _rms(g, w["mtp.head_norm_s"], eps, lossy)


def _hidden(config: dict, w: dict, ids, variant: str = "",
            draft: bool = False):
    """ids [T] -> the final-norm hidden [T, d]; with ``draft`` also the
    drafting block's normed output [T, d], position i fed ids[i + 1] (the
    last position a pad: its row means nothing)."""
    import jax.numpy as jnp

    r = _stack(config, w, ids, "" if variant in DRAFT_VARIANTS else variant)
    hidden = _rms(r, w["final_ln.scale"], config["rms_norm_eps"],
                  variant == "bf16_stated_f32")
    if not draft:
        return hidden
    return hidden, _drafts(config, w, r, jnp.roll(ids, -1), variant)


_HIDDEN_JITS: dict = {}


def _jit_hidden(config: dict, variant: str = "", draft: bool = False):
    import jax

    key = (id(config), variant, draft)
    if key not in _HIDDEN_JITS:
        _HIDDEN_JITS[key] = jax.jit(
            lambda w, ids: _hidden(config, w, ids, variant, draft))
    return _HIDDEN_JITS[key]


def reference_logits(config: dict, w: dict, ids, rows=None,
                     variant: str = "", draft: bool = False):
    """ids [T] -> logits [len(rows), V] float32 at positions ``rows`` (all
    T when None: small models only); with ``draft`` also the drafting
    block's logits there (row i: over the token at i + 2, the block having
    been fed ids[i + 1]; a row T - 1 means nothing)."""
    import jax
    import jax.numpy as jnp

    ids = np.asarray(ids)
    n = ids.size
    padded = np.zeros(_padded(n), np.int32)
    padded[:n] = ids
    rows = jnp.asarray(np.arange(n) if rows is None else np.asarray(rows))
    with jax.default_matmul_precision("highest"):
        out = _jit_hidden(config, variant, draft)(w, jnp.asarray(padded))
        if not draft:
            return _head(out[rows], w["lm_head.w"])
        return tuple(_head(o[rows], w["lm_head.w"]) for o in out)


def _rows_logits(config: dict, w: dict, seq: np.ndarray, rows,
                 draft_rows=None, variant: str = ""):
    """Teacher-forced reference logits [len(rows), V] at ``rows`` of
    ``seq`` and, with ``draft_rows``, the drafting block's logits at those
    (None otherwise); the head runs over the rows alone, a block at a
    time."""
    import jax
    import jax.numpy as jnp

    ids = np.zeros(_padded(seq.size), np.int32)
    ids[:seq.size] = seq
    rows = np.asarray(rows)

    def head(hidden, at):
        # whole blocks (the last row repeated): one compiled head
        whole = np.pad(at, (0, -at.size % _QUERY_BLOCK), mode="edge")
        return np.concatenate([
            np.asarray(_head(hidden[jnp.asarray(whole[i:i + _QUERY_BLOCK])],
                             w["lm_head.w"]))
            for i in range(0, whole.size, _QUERY_BLOCK)])[:at.size]

    with jax.default_matmul_precision("highest"):
        out = _jit_hidden(config, variant, draft_rows is not None)(
            w, jnp.asarray(ids))
        if draft_rows is None:
            return head(out, rows), None
        return head(out[0], rows), head(out[1], np.asarray(draft_rows))


def served(eng, prompt, new_tokens: int):
    """ONE request driven through the engine's own ticks (chunked prefill,
    then VERIFY ticks), with the beam plane of every call captured ->
    (calls [(position, values [k], ids [k])]: the stack's top-k log-probs
    at the last token of every prefill chunk and at every position a tick
    COMMITTED — the second of a tick only where the draft was accepted —,
    drafts [(i, token, values [k], ids [k])]: what each call left as the
    next draft, i the last position whose successor was known when the
    drafting block ran, with the block's own top-k log-probs there (its
    rows lie below the stack's in the beam plane), the emitted sequence)."""
    calls, drafts = [], []
    run = eng.executor.run

    def capture(prog, feed=None, fetch_list=None, scope=None, **kw):
        res = run(prog, feed=feed, fetch_list=fetch_list, scope=scope, **kw)
        if feed and "serving.block_table" in feed:
            nxt, tv, ti = (np.asarray(r) for r in res[:3])
            if "serving.chunk" in feed:
                pos = int(feed["serving.start"][0]
                          + feed["serving.chunk_len"][0]) - 1
                calls.append((pos, tv[0], ti[0]))
                if feed["serving.draft_next"][0] < 0:
                    at = tv.shape[0] // 2
                    drafts.append((pos, int(nxt[0, 1]), tv[at], ti[at]))
            else:
                pos = int(feed["serving.pos"][0])
                took = int(nxt[0, 1] >= 0)
                for j in range(1 + took):
                    calls.append((pos + j, tv[j], ti[j]))
                at = tv.shape[0] // 2 + took
                drafts.append((pos + took, int(nxt[0, 2]), tv[at], ti[at]))
        return res

    eng.executor.run = capture
    try:
        out = eng.generate_all([prompt], max_new_tokens=new_tokens)[0]
    finally:
        eng.executor.run = run
    return calls, drafts, np.asarray(out)


def _replay_engine(config: dict, w: dict):
    """A twin of the engine ``build_engine`` last built for ``config``
    (its slots, page and chunk sizes, table width: the timed programs'
    shapes) on the SAME weight arrays, with the beam plane on (how logits
    leave an engine), pools of one table's pages and the chunk's own prompt
    bucket alone: a prompt's tail runs in the bucket its other chunks do,
    which spares a cold process the compile of one more prefill program
    (~15 s of the 300 it has)."""
    import paddle_tpu as pt

    if id(config) not in _ENGINES:
        raise ValueError("reference_logit_gaps replays the checked requests "
                         "through a twin of the engine: build_engine first")
    e = dict(_ENGINES[id(config)][0])
    e["n_pages"] = -(-e["max_len"] // e["page_size"]) + 2
    e["n_pages_window"] = min(e["n_pages"], e["n_pages_window"])
    e["prompt_buckets"] = [max(e["prompt_buckets"])]
    scope = pt.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return _engine(spec_of(config), scope, e, beam_width=CHECK_TOPK)


def free_pools(eng) -> None:
    """Let go of an engine's page pools (the run is over: the check's
    reference needs their room on the chip)."""
    import jax.numpy as jnp

    for cache in eng._caches:
        for name in cache.scope_names:
            eng.scope.set(name, jnp.zeros((1,), jnp.float32))


def read_replay(config: dict, w: dict, calls, drafts, out,
                variant: str = "") -> dict:
    """What ``served`` captured of one request against the reference
    (``variant``: a wrong one) teacher-forced over the replay's own tokens:
    -> ``errs`` (the served top-k log-prob error at every served position),
    ``draft_equal`` / ``draft_n`` (positions whose draft the reference's
    drafting block also ranks first; a draft made at the replay's last
    position has no successor to be teacher-forced with and is left out),
    ``draft_errs`` (the drafting block's top-k log-prob error at those),
    ``logits_at`` (position -> the reference's logits there)."""
    import jax

    rows = np.asarray(sorted({p for p, _, _ in calls}))
    kept = [d for d in drafts if d[0] + 1 < out.size]
    logits, ref_draft = _rows_logits(
        config, w, out, rows,
        np.asarray([d[0] for d in kept]) if kept else None, variant)
    at = {int(r): j for j, r in enumerate(rows)}
    ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    equal, draft_errs = 0, []
    if kept:
        equal = int((np.asarray([d[1] for d in kept])
                     == ref_draft.argmax(axis=-1)).sum())
        ref_d = np.asarray(jax.nn.log_softmax(ref_draft, axis=-1))
        draft_errs = [float(np.abs(v - ref_d[j][i]).max())
                      for j, (_, _, v, i) in enumerate(kept)]
    return {"errs": [float(np.abs(v - ref[at[p]][i]).max())
                     for p, v, i in calls],
            "draft_equal": equal, "draft_n": len(kept),
            "draft_errs": draft_errs,
            "logits_at": {int(r): logits[j] for r, j in at.items()}}


def check_request(config: dict, w: dict, eng, prompt, new_tokens: int,
                  variant: str = "") -> dict:
    """Replay one request through ``eng`` (beam plane on) and read it
    against the reference (``read_replay``); ``again``: the replay's
    tokens."""
    calls, drafts, again = served(eng, prompt, new_tokens)
    return {**read_replay(config, w, calls, drafts, again, variant),
            "again": again}


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """What the serve driver holds a run to: it compares the LARGEST value
    returned with the mix's ``check.logit_gap_tol``, which for this family
    is ``CHECK_LOGPROB_TOL``. Four readings, each in that limit's terms:

    1. the ``CHECK_LOGPROB_QUANTILE``-th percentile of the SERVED top-8
       log-prob error: every checked request ``(prompt_len, ids)`` is
       replayed (its prompt and the first ``CHECK_REPLAY_TOKENS`` of its
       answer), after the drain, through ``_replay_engine`` (chunked
       prefill, then verify ticks through the pages), and the log-probs it
       serves at every chunk end and every committed position of a tick
       (both rows of one whose draft was accepted) are compared with the
       reference's teacher-forced full forward of the replayed sequence.
       This is the number a computation one precision lower fails;
    2. on the tokens the TIMED engine emitted: how far below its
       position's best the reference puts each (the largest, scaled by
       ``CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL``);
    3. the DRAFT: the share of positions at which the draft the replay's
       drafting block left differs from the reference block's argmax,
       scaled by ``CHECK_LOGPROB_TOL / CHECK_DRAFT_UNEQUAL_TOL`` — a wrong
       drafting block is invisible in the emitted tokens by construction;
    4. the drafting block's LOG-PROBS: the same percentile of its served
       top-8 log-prob error at the positions of 3 (scaled by
       ``CHECK_LOGPROB_TOL / CHECK_DRAFT_LOGPROB_TOL``): what a fault in
       the block's attention, K/V rows or experts moves where its argmax,
       which the embedding half of M mostly decides, stays.

    The timed engine's pools are let go first (the float32 reference needs
    their room). The readings go to stderr as one JSON line."""
    import json
    import sys

    if not results:
        return np.zeros((0,), np.float32)
    import time

    t0 = time.monotonic()
    if id(config) in _ENGINES:
        free_pools(_ENGINES[id(config)][1])
    eng = _replay_engine(config, w)
    t1 = time.monotonic()
    errs: List[float] = []
    draft_errs: List[float] = []
    gaps: List[float] = []
    same = equal = drafted = 0
    for prompt_len, out in results:
        # (the first CHECK_REPLAY_TOKENS of the answer: the stretch read)
        out = np.asarray(out)[:prompt_len + CHECK_REPLAY_TOKENS]
        r = check_request(config, w, eng, out[:prompt_len],
                          out.size - prompt_len)
        errs.extend(r["errs"])
        draft_errs.extend(r["draft_errs"])
        equal += r["draft_equal"]
        drafted += r["draft_n"]
        emitted = np.arange(prompt_len - 1, out.size - 1)
        if np.array_equal(r["again"], out) and all(
                int(p) in r["logits_at"] for p in emitted):
            same += 1
            mine = np.stack([r["logits_at"][int(p)] for p in emitted])
        else:   # a near-tie, or a fault under load: a forward of its own
            mine = _rows_logits(config, w, out[:-1], emitted)[0]
        gaps.extend((mine.max(axis=-1) - mine[np.arange(emitted.size),
                                              out[emitted + 1]]).tolist())
    t2 = time.monotonic()
    held = float(np.percentile(errs, CHECK_LOGPROB_QUANTILE))
    worst = float(max(gaps))
    unequal = 1.0 - equal / max(drafted, 1)
    draft_held = float(np.percentile(draft_errs, CHECK_LOGPROB_QUANTILE)) \
        if draft_errs else 0.0
    counted = eng.metrics.snapshot()["counters"]
    print(json.dumps({"window_mtp_moe_lm.check": {
        "quantile": CHECK_LOGPROB_QUANTILE, "limit": CHECK_LOGPROB_TOL,
        **{f"served_logprob_err_p{q}": float(np.percentile(errs, q))
           for q in (50, 80, 90, 95, 99)},
        "served_logprob_err_max": float(max(errs)),
        "served_positions": len(errs), "emitted_gap_max": worst,
        "emitted_gap_limit": CHECK_EMITTED_GAP_TOL,
        "emitted_positions": len(gaps),
        "draft_unequal_share": unequal, "draft_positions": int(drafted),
        "draft_unequal_limit": CHECK_DRAFT_UNEQUAL_TOL,
        **{f"draft_logprob_err_p{q}": float(np.percentile(draft_errs, q))
           for q in (50, 90, 99) if draft_errs},
        "draft_logprob_err_max": max(draft_errs, default=0.0),
        "draft_logprob_limit": CHECK_DRAFT_LOGPROB_TOL,
        "replay_mtp_drafted": counted.get("mtp_drafted", 0),
        "replay_mtp_accepted": counted.get("mtp_accepted", 0),
        "requests": len(results), "replays_equal_to_timed": int(same),
        "twin_build_s": round(t1 - t0, 1),
        "replay_and_reference_s": round(t2 - t1, 1)}}),
        file=sys.stderr, flush=True)
    return np.asarray(
        [held, worst * CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL,
         unequal * CHECK_LOGPROB_TOL / CHECK_DRAFT_UNEQUAL_TOL,
         draft_held * CHECK_LOGPROB_TOL / CHECK_DRAFT_LOGPROB_TOL],
        np.float32)


# ---------------------------------------------------------------------------
# the kernels: which device event is a call, and what a call has to move
# ---------------------------------------------------------------------------
def _pool_layers(config: dict) -> Dict[int, str]:
    L = config["num_hidden_layers"]
    n_window = sum(t == "sliding_attention"
                   for t in config["layer_types"][:L])
    # the drafting block's K/V is the LAST layer of the full-attention pools
    kinds = {L - n_window + config["num_nextn_predict_layers"]: "global",
             n_window: "window"}
    return kinds if len(kinds) == 2 else {}


def attention_call_kind(call_layers: int, config: dict) -> Optional[str]:
    """Which kind of layer a ``paged_attention_decode`` call serves, told
    by the layer count of its pool operand ([L_kind, N, ps, Hkv*dh]):
    ``"global"`` (the stack's full-attention layers AND the drafting
    block's) | ``"window"``; None when it matches neither."""
    return _pool_layers(config).get(call_layers)


def pool_layer_counts(config: dict) -> Dict[str, int]:
    """kind -> the layers of its pools (= its attention calls a tick)."""
    return {kind: n for n, kind in _pool_layers(config).items()}


#: one call (one layer of one tick) that walks ``pages`` pages over all its
#: rows: the K and the V tile of each, ONLY the pages
mixed_attention_cost = decode_cost


def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of the expert layer a device event belongs to:
    ``"grouped_matmul"`` (a ragged-dot custom call, or an op with an
    operand shaped like the HELD expert stacks — the stack's [Lexp, held,
    d, f] / [Lexp * held, d, f], the drafting block's [1, held, d, f] /
    [held, d, f] — or their transposes), ``"shared_expert"`` (an operand
    shaped like the always-on expert's [.., d, f]), ``"route"`` (the
    router's [.., d] x [d, E] product over ALL E outputs, the sort / top-k
    over the assignments). None for everything else (the dense layer's
    planes are [.., d, intermediate_size]; the sampling plane's sorts run
    over the vocabulary)."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    Lx = config["num_hidden_layers"] - config["first_k_dense_replace"]
    held, E = config["num_experts"], config["router_outputs"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    pair = rf"({d},{f}|{f},{d})"
    if name.startswith("ragged-dot") or re.search(
            rf"\[({Lx},{held}|{Lx * held}|1,{held}|{held}),{pair}\]", text):
        return "grouped_matmul"
    if re.search(rf"\[((1|{Lx}),)?{pair}\]", text):
        return "shared_expert"
    if f"[{d},{E}]" in text or opcode in ("sort", "topk") \
            or name.startswith(("sort", "top-k", "topk")):
        return None if f",{config['vocab_size']}]" in text else "route"
    return None
