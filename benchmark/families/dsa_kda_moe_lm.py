"""Family ``dsa_kda_moe_lm``: a GLM-5.3-Flash-shaped hybrid decoder — Kimi
Delta Attention layers (a recurrent state a sequence) beside latent attention
layers WITHOUT a rotary key that read only what a learned indexer picks, a
four-stream manifold-constrained residual (mHC) round every half block, and
clamped SwiGLU feed-forwards (a dense head, then a sigmoid router with a
selection bias over experts of which THIS chip holds a share, plus a shared
expert) — served by ``serving.GenerationEngine(spec, ...)`` from ONE
``paddle_tpu.lm_spec.LMSpec`` (``spec_of``), with the yardstick's own pieces:
what the selection has to move (``dsa_cost``), which device op belongs to
which mechanism (``dsa_op`` / ``mhc_op`` / ``kda_op`` / ``moe_op``) and a plain
float32 ``jax.numpy`` reference of the equations (one sequence, T tokens, n
streams of width d; RMSNorm(u) = u rsqrt(mean(u^2) + eps) w):

  residual (mHC, arXiv:2512.24880), every HALF block (a mixer, a feed-forward)
  with its own Phi [n d, n | n | n n], alpha [3], b [n | n | n n]:
    X^0 = (e, e, .., e);  x~ = vec(X) rsqrt(mean(vec(X)^2) + hc_eps)
    H_pre = sigmoid(alpha_0 x~ Phi_pre + b_pre);  H_post = 2 sigmoid(alpha_1 x~ Phi_post + b_post)
    H_res = SK(exp(alpha_2 mat(x~ Phi_res) + b_res)), SK = hc_sinkhorn_iters x
            (rows / (row sum + hc_eps), then columns / (column sum + hc_eps))
    u = sum_i H_pre[i] X[i];  y = F(RMSNorm_l(u));  X[i] <- sum_j H_res[i, j] X[j] + H_post[i] y
    logits = RMSNorm_f(sum_i X[i]) W_head
  KDA layer (arXiv:2510.26692), H heads of K = V = head_dim, h = RMSNorm_1(u):
    [q~ | k~ | v~] = h W_qkv;  c(u)_t = silu(sum_i w_i u_{t-3+i})   (4 taps, zero history)
    q = l2norm(c(q~)) K^-1/2;  k = l2norm(c(k~));  v = c(v~)
    g = gate_lower_bound * sigmoid(exp(A_log_h) (h W_fa W_fb + dt_bias)), in (-5, 0)
    S' = diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    F = [RMSNorm_head(o_t) * sigmoid(h W_ga W_gb + b_g)] W_o;  beta = sigmoid(h w_beta)
  sparse latent layer (DeepSeek-V3.2's DSA over DeepSeek-V2's MLA, no rotation):
    c_q = RMSNorm(h W_qa);  q_n = c_q W_qb [H, nope];  c_t = RMSNorm(h W_kva) [r]
    k_n = c W_uk[n];  v_n = c W_uv[n];  scale nope^-1/2
    indexer: qI = c_q W_Iq [Hi, Di];  kI_s = LayerNorm(h_s W_Ik) [Di];  w = (h W_Iw) Hi^-1/2 Di^-1/2
    group g = positions G g .. G g + G - 1;  kbar_g = mean_s kI_s
    I[t, g] = sum_j w[t, j] relu(qI[t, j] . kbar_g)   for groups wholly before t's own
    picked(t) = t's own group (positions <= t) + the index_topk / G - 1 best others
    F = [softmax over the picked tokens of q_n . k_n scale] v_n W_o
  feed-forward, h2 = RMSNorm_2(u), L = swiglu_limit:
    G(a; Wg, Wu, Wd) = (silu(min(a Wg, L)) * clip(a Wu, -L, L)) Wd
    dense layers: G(h2);  else s = sigmoid(h2 W_r) over ALL experts (float32);
      S = top-k of s + b;  w_e = s_e / sum_S s * routed_scaling_factor
      F = G_shared(h2) + sum_{e in S, e HELD} w_e G_e(h2)

What the absent experts would add is left out, program and reference alike;
``expert_layer(.., held=)`` gives any share, so a test can add the shares up.

The reference has no cache, no pool, no state array, no kernel, no chunked
form, no absorbed attention, no gather, no top-k primitive and no grouped
matmul: KDA is the token-by-token recurrence under ``lax.scan``, the indexer
scores every group and picks by a FULL SORT, attention is a masked softmax
over the expanded keys and values of ALL positions (mask = picked), every HELD
expert is applied densely and masked by the top-k set. It reads the SAME
stored weights as the program and runs under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.kda_gqa_moe_lm import _low_rank
from benchmark.families.kda_mla_moe_lm import (  # noqa: F401 - the same kernel
    KDA_KERNEL, _bf16, kda_decode_call, kda_decode_cost, kda_scan,
    mantissa_bits)
from benchmark.families.moe_lm import (  # noqa: F401 - the family's surface
    draw_prompt_ids, grouped_matmul_cost)
from benchmark.families.window_moe_lm import _f32, _head, _rms

ITEM = "tokens"
_EXPERT_BLOCK = 4       # experts upcast to float32 at a time
_TOKEN_BLOCK = 1024     # tokens that go through the experts together
_KDA_TOKENS = 1024      # tokens whose q | k | v | g a KDA layer holds at once
_QUERY_BLOCK = 128      # queries that score and attend together
_HEAD_BLOCK = 4         # heads whose keys and values are expanded at a time
_PAD = 2048             # the reference runs long sequences at whole multiples
                        # of this: ONE compiled program for the check's
                        # contexts of 8193 to 10240 tokens
_L2_EPS = 1e-6
#: WRONG models, one fault each, that the check and the tier-1 tests must
#: tell from the right one: ``reference_logits(.., variant=name)``
VARIANTS = {
    "recent_pick": "the most RECENT index_topk tokens instead of the "
                   "indexer's pick (a sliding window)",
    "no_selection": "every cached token attended: the latent layer without "
                    "its indexer",
    "no_tail": "the query's own group not forced into the pick (of it the "
               "query sees itself alone)",
    "no_mhc": "one residual stream, x + F(norm(x))",
    "uniform_mix": "H_res uniform (1 / n): the streams averaged every half",
    "no_clamp": "SwiGLU without swiglu_limit",
    "no_decay": "g = 0: the KDA state never forgets",
    "no_shared_expert": "the always-on expert left out",
    "bf16_stated_f32": "norms, router scores, gates, the stream mixes and "
                       "the indexer's scores rounded to bfloat16 where the "
                       "configuration says float32",
    "bf16_state": "the recurrent state kept in bfloat16 between tokens",
}

#: THE LIMIT on the served top-8 log-prob error, the ``CHECK_LOGPROB_QUANTILE``
#: -th percentile over the served positions (``reference_logit_gaps``; the
#: mix's ``check.logit_gap_tol`` IS this number). The percentile lies between
#: the ~3% of positions where the right model flips an expert on a router
#: near-tie and the 10-15% where the reference computed one precision lower
#: does; the limit between the largest right reading at the published widths
#: (0.0088) and that reference's (0.056): PERF.md section 6 and the mix's
#: ``logit_gap_tol_why`` give the readings
CHECK_LOGPROB_QUANTILE = 95
CHECK_LOGPROB_TOL = 0.02
#: ... and on the LARGEST of them. Some 3% of the positions sit on a near-tie
#: of the router (the 8th against the 9th biased score inside the bfloat16
#: step of the engine's input): there the engine and the float32 reference
#: hold different experts, a discrete step and not rounding, so the tail
#: (0.058-0.163 in twenty right readings) has a limit of its own
CHECK_LOGPROB_MAX_TOL = 0.5
#: the tokens of a checked request's answer that the check replays and
#: compares (Part R's rule (1): the whole run cold and traced inside 300 s,
#: the check's replayed stretch cut first)
CHECK_REPLAY_TOKENS = 320
#: the limit on how far below its position's best the reference puts a token
#: the TIMED engine emitted (a request answered with another's tokens)
CHECK_EMITTED_GAP_TOL = 0.5
#: |mantissa bits the engine's held KDA state uses - the reference's|
CHECK_STATE_BITS_TOL = 8
#: the limit on the share of the reference's picked groups that scoring the
#: ENGINE's cached pooled keys does NOT pick, over the served positions
CHECK_PICK_MISS_TOL = 0.05
CHECK_TOPK = 8


def layers_from(config: dict) -> int:
    """The published layer this program's layer 0 is."""
    return config["reduced_from"]["layers_from"]


def pattern_of(config: dict) -> Tuple[str, ...]:
    """The attention kind of every layer the program holds (published
    positions ``layers_from`` ..): one period of the spec's pattern."""
    first, n = layers_from(config), config["num_hidden_layers"]
    kind = {"linear_attention": "kda", "deepseek_sparse_attention": "mla"}
    return tuple(kind[t] for t in config["layer_types"][first:first + n])


def first_dense_of(config: dict) -> int:
    first, n = layers_from(config), config["num_hidden_layers"]
    kinds = config["mlp_layer_types"][first:first + n]
    dense = sum(1 for k in kinds if k == "dense")
    if kinds != ["dense"] * dense + ["sparse"] * (n - dense):
        raise ValueError(f"dense layers do not lead: {kinds}")
    return dense


def held_of(config: dict) -> Tuple[int, int]:
    """(first, count): the routed experts this chip holds."""
    return config["assumed"]["experts_first"], config["n_routed_experts"]


def spec_of(config: dict):
    """The program's model spec for this configuration: a tree whose spec
    lacks the selection, the residual streams or the clamp fails here, at
    once, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a, lin = config["assumed"], config["linear_attn_config"]
    first, count = held_of(config)
    E = config["router_outputs"]
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"], use_rope=True,
        max_len=a["max_len"], norm="rms_norm",
        norm_eps=config["rms_norm_eps"], attn="mla",
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        index_heads=config["index_n_heads"],
        index_dim=config["index_head_dim"], index_topk=config["index_topk"],
        index_pool=config["index_kpool"],
        residual="mhc" if config["mhc"] else "add",
        hc_mult=config["hc_mult"], hc_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"], ffn_limit=float(config["swiglu_limit"]),
        layer_pattern=pattern_of(config), kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_lower_bound=float(lin["gate_lower_bound"]),
        kda_decay="bounded", kda_proj_rank=a["kda_proj_rank"],
        first_dense=first_dense_of(config), d_ff=config["intermediate_size"],
        ffn="swiglu_moe", num_experts=E,
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        experts_held=None if (first, count) == (0, E) else (first, count),
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        router_score=config["scoring_func"],
        router_bias=config["topk_method"] == "noaux_tc",
        n_group=config["n_group"], topk_group=config["topk_group"],
        bias=False, param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def seeded_vectors(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """The planes a startup program leaves at a constant and a checkpoint
    does not (``assumed``: ``router_bias_std``, ``kda_gate_values``,
    ``hc_values``): the router's selection bias, the KDA decay's A_log and
    dt_bias, and every half block's mHC scalars — alpha ~ U(0.5, 1), b_pre,
    b_post ~ N(0, 0.5^2), b_res = 1.5 I + N(0, 0.5^2): H_res leans to the
    identity without being it, and every mix moves with the token."""
    spec = spec_of(config)
    rng = np.random.default_rng([int(seed), 0x474c4d])
    H, K, n = spec.num_heads, spec.kda_head_dim, spec.hc_mult
    Lk, Le = spec.plane_layers("kda_a_log"), spec.plane_layers("router_b")
    out = {
        "router_b": rng.normal(0.0, 1.0, (Le, spec.num_experts))
        * config["assumed"]["router_bias_std"],
        "kda_a_log": np.log(rng.uniform(0.5, 1.5, (Lk, H))),
        "kda_dt_bias": rng.uniform(-5.0, -1.0, (Lk, H * K)),
    }
    if spec.residual == "mhc":
        for half in ("hc1", "hc2"):
            L = spec.plane_layers(half + "_b")
            b = rng.normal(0.0, 0.5, (L, n * n + 2 * n))
            b[:, 2 * n:] += 1.5 * np.eye(n).reshape(-1)
            out[half + "_b"] = b
            out[half + "_alpha"] = rng.uniform(0.5, 1.0, (L, 3))
    return out


def build_engine(config: dict, mix: dict, seed: int, **engine_kw):
    """-> (engine, executors). Weights come from ONE run of the parameter
    program's startup block on the device, seeded, in the configuration's
    stored dtype; then the embedding is scaled and the seeded vectors set
    (``seeded_vectors``)."""
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
    eng = _engine(spec, scope, mix["engine"], **engine_kw)
    _ENGINES[id(config)] = eng
    return eng, [exe, eng.executor]


def _engine(spec, scope, e: dict, **engine_kw):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], max_seq_len=e["max_len"],
        prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None,
        mask_plane=bool(e.get("mask_plane", 1)),
        **{"beam_width": e.get("beam_width", 0), **engine_kw})


#: id(configuration) -> the engine the last ``build_engine`` built: the
#: check replays its requests through THAT engine after the drain (its beam
#: plane, ``engine.beam_width`` in the mix, is how logits leave it): no twin
#: is compiled, and none holds a second state beside the reference
_ENGINES: dict = {}


def weights_of(program, scope) -> Dict[str, object]:
    """The model's parameters by the fixed names the layout gives them, as
    stored (nothing is copied or cast)."""
    names = ["tok_emb", "final_ln.scale", "lm_head.w"] + sorted(
        n for n in scope.keys() if n.startswith("lm_stack.stack_"))
    return {name: scope.get(name) for name in names}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds of (rows / (row sum + eps), columns / (column sum +
    eps)) over the last two axes of a positive m."""
    import jax.numpy as jnp

    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def hc_mixes(config: dict, p: dict, X, half: str, variant: str = ""):
    """X [T, n, d] float32 -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])
    of one half block (``half``: ``hc1`` the mixer's, ``hc2`` the
    feed-forward's)."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"
    T, n, d = X.shape
    eps = config["hc_eps"]
    v = X.reshape(T, n * d)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    z = v @ _f32(p[half + "_w"])
    if lossy:
        z = _bf16(z)
    alpha, b = _f32(p[half + "_alpha"]), _f32(p[half + "_b"])
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(alpha[2] * z[:, 2 * n:].reshape(T, n, n)
                  + b[2 * n:].reshape(n, n))
    res = sinkhorn(res, config["hc_sinkhorn_iters"], eps)
    if variant == "uniform_mix":
        res = jnp.full_like(res, 1.0 / n)
    if lossy:
        pre, post, res = _bf16(pre), _bf16(post), _bf16(res)
    return pre, post, res


def clamped_glu(config: dict, a, wg, wu, wd, variant: str = ""):
    """(silu(min(a Wg, L)) * clip(a Wu, -L, L)) Wd."""
    import jax
    import jax.numpy as jnp

    L = config["swiglu_limit"]
    g, u = a @ _f32(wg), a @ _f32(wu)
    if L and variant != "no_clamp":
        g, u = jnp.minimum(g, L), jnp.clip(u, -L, L)
    return (jax.nn.silu(g) * u) @ _f32(wd)


def router_choice(config: dict, h2, router_w, router_b, variant: str = ""):
    """h2 [T, d] float32 -> (scores s [T, E], chosen [T, E] bool): the
    router of one layer over ALL its outputs (``n_group`` 1: no groups)."""
    import jax

    def squash(t):
        return _bf16(t) if variant == "bf16_stated_f32" else t

    s = squash(jax.nn.sigmoid(squash(h2 @ _f32(router_w))))
    c = s + _f32(router_b)
    kth = jax.lax.top_k(c, config["num_experts_per_tok"])[0][:, -1:]
    return s, c >= kth


def expert_layer(config: dict, p: dict, h2, held=None, variant: str = "",
                 parts: bool = False, offset=0):
    """The expert half of a layer on h2 [T, d] (float32) with per-layer
    weights ``p`` (``moe_*_w`` holding the ``held`` = (first, count)
    experts; None: the configuration's share): shared + routed, or
    (routed, shared) under ``parts``. ``offset``: where the held experts
    start in ``moe_*_w`` (a layer's window of a flattened stack)."""
    import jax
    import jax.numpy as jnp

    first, count = held or held_of(config)
    T = h2.shape[0]
    L = 0 if variant == "no_clamp" else config["swiglu_limit"]
    s, chosen = router_choice(config, h2, p["router_w"], p["router_b"],
                              variant)
    gate = jnp.where(chosen, s, 0.0)
    if config["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate[:, first:first + count] * config["routed_scaling_factor"]
    eb = next(b for b in (_EXPERT_BLOCK, 2, 1) if count % b == 0)
    Bt = next(b for b in (_TOKEN_BLOCK, 512, 256, 128, T) if T % b == 0)

    def token_block(blk):
        b_b, gate_b = blk

        def expert_block(y, e0):
            wg, wu, wd = (jax.lax.dynamic_slice_in_dim(p[name], offset + e0,
                                                       eb, 0)
                          for name in ("moe_gate_w", "moe_up_w",
                                       "moe_down_w"))
            g_blk = jax.lax.dynamic_slice_in_dim(gate_b, e0, eb, 1)
            g = jnp.einsum("td,edf->tef", b_b, _f32(wg))
            u = jnp.einsum("td,edf->tef", b_b, _f32(wu))
            if L:
                g, u = jnp.minimum(g, L), jnp.clip(u, -L, L)
            a = jax.nn.silu(g) * u * g_blk[..., None]
            return y + jnp.einsum("tef,efd->td", a, _f32(wd)), None

        return jax.lax.scan(expert_block, jnp.zeros_like(b_b),
                            jnp.arange(0, count, eb))[0]

    routed = jax.lax.map(token_block, (
        h2.reshape(T // Bt, Bt, -1), gate.reshape(T // Bt, Bt, count))
    ).reshape(T, -1)
    shared = jnp.zeros_like(h2)
    if variant != "no_shared_expert":
        shared = clamped_glu(config, h2, p["shared_gate_w"],
                             p["shared_up_w"], p["shared_down_w"], variant)
    return (routed, shared) if parts else routed + shared


def kda_inputs(config: dict, p: dict, h, variant: str = "", history=None):
    """h [T, d] (normed, float32) -> q, k, v, g [T, H, K], beta [T, H] of
    one KDA layer and the convolutions' history after these tokens;
    ``history`` None: a zero history (the sequence starts here)."""
    import jax
    import jax.numpy as jnp

    def squash(t):
        return _bf16(t) if variant == "bf16_stated_f32" else t

    T = h.shape[0]
    lin = config["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    u = h @ _f32(p["kda_qkv_w"])
    u = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), u.dtype)
                         if history is None else history, u])
    w = _f32(p["kda_conv_w"])                                   # [taps, 3HK]
    y = jax.nn.silu(sum(u[i:i + T] * w[i] for i in range(taps)))
    q, k, v = (y[:, i * H * K:(i + 1) * H * K].reshape(T, H, K)
               for i in range(3))

    def l2(t):
        return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)

    a = (_low_rank(p, h, "kda_a") + _f32(p["kda_dt_bias"])).reshape(T, H, K)
    rate = jnp.exp(_f32(p["kda_a_log"]))[None, :, None]
    g = jnp.zeros_like(a) if variant == "no_decay" else \
        lin["gate_lower_bound"] * squash(jax.nn.sigmoid(rate * a))
    beta = squash(jax.nn.sigmoid(h @ _f32(p["kda_beta_w"])))
    return l2(q) * K ** -0.5, l2(k), v, g, beta, u[T:]


def kda_layer(config: dict, p: dict, h, real, variant: str = ""):
    """One KDA layer's mixer on h [T, d] (normed): -> (its output [T, d],
    the state after the last ``real`` token [H, K, V]). Token by token
    (``kda_scan``); the float32 q | k | v | g of ``_KDA_TOKENS`` tokens
    exist at a time, state and history carried between stretches."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"
    lin = config["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], lin[
        "short_conv_kernel_size"]
    T = h.shape[0]
    Bk = next(b for b in (_KDA_TOKENS, T) if T % b == 0)

    def stretch(carry, inp):
        S, hist = carry
        h_b, real_b = inp
        q, k, v, g, beta, hist = kda_inputs(config, p, h_b, variant, hist)
        o, S = kda_scan(q, k, v, jnp.where(real_b[..., None], g, 0.0),
                        jnp.where(real_b, beta, 0.0), variant, state=S)
        o = _rms(o, p["kda_norm_s"], config["rms_norm_eps"],
                 lossy).reshape(Bk, H * K)
        gate = jax.nn.sigmoid(_low_rank(p, h_b, "kda_gate")
                              + _f32(p["kda_gate_b"]))
        return (S, hist), (o * (_bf16(gate) if lossy else gate)) @ _f32(
            p["kda_out_w"])

    (S, _), y = jax.lax.scan(
        stretch, (jnp.zeros((H, K, K), jnp.float32),
                  jnp.zeros((taps - 1, 3 * H * K), jnp.float32)),
        (h.reshape(T // Bk, Bk, -1), real.reshape(T // Bk, Bk, 1)))
    return y.reshape(T, -1), S


def picked_groups(score, own, k: int, variant: str = ""):
    """score [B, NG] float32 and each query's own group ``own`` [B] -> the
    picked groups [B, NG] bool: the ``k`` best of the groups wholly before
    the query's own (a FULL SORT, ties to the lower index; all of them
    while there are fewer) and the query's own."""
    import jax.numpy as jnp

    NG = score.shape[1]
    g = jnp.arange(NG)[None, :]
    before = g < own[:, None]
    if variant == "recent_pick":
        best = before & (g >= own[:, None] - k)
    else:
        # the k-th best score by a full sort; of the groups that tie with
        # it, the lowest indices fill what the better ones leave
        s = jnp.where(before, score, -jnp.inf)
        kth = jnp.sort(s, axis=-1)[:, max(NG - k, 0)][:, None]
        tie = before & (s == kth)
        room = k - jnp.sum(s > kth, axis=-1, keepdims=True)
        best = before & ((s > kth) | (tie & (jnp.cumsum(tie, axis=-1)
                                             <= room)))
    return best if variant == "no_tail" else best | (g == own[:, None])


def dsa_layer(config: dict, p: dict, h, variant: str = "", pooled=None):
    """One sparse latent layer's mixer on h [T, d] (normed; T whole groups
    and whole query blocks) -> (its output [T, d], a query's share of its
    picked groups that scoring the pooled keys ``pooled`` [T / G, Di] picks
    too [T]; ones without them)."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"

    def squash(t):
        return _bf16(t) if lossy else t

    T = h.shape[0]
    H, eps = config["num_attention_heads"], config["rms_norm_eps"]
    nope, dv = config["qk_nope_head_dim"], config["v_head_dim"]
    Hi, Di = config["index_n_heads"], config["index_head_dim"]
    G = config["index_kpool"]
    k_pick = config["index_topk"] // G - 1
    B = min(_QUERY_BLOCK, T)
    NG = T // G
    c_q = _rms(h @ _f32(p["q_a_w"]), p["q_a_norm_s"], eps, lossy)
    c = _rms(h @ _f32(p["kv_a_w"]), p["kv_a_norm_s"], eps, lossy)
    k_i = h @ _f32(p["idx_k_w"])
    mu = jnp.mean(k_i, axis=-1, keepdims=True)
    var = jnp.mean((k_i - mu) ** 2, axis=-1, keepdims=True)
    k_i = squash((k_i - mu) * jax.lax.rsqrt(var + eps)
                 * _f32(p["idx_k_norm_s"]) + _f32(p["idx_k_norm_b"]))
    kbar = jnp.mean(k_i.reshape(NG, G, Di), axis=1)
    theirs = None
    if pooled is not None:
        theirs = _f32(pooled)
    w_iq, w_iw = _f32(p["idx_q_w"]), _f32(p["idx_head_w"])
    pos = jnp.arange(T)

    def score(q_i, w_i, keys):
        s = jnp.einsum("bhd,gd->bhg", q_i, keys)
        return squash(jnp.einsum("bhg,bh->bg", jax.nn.relu(s), w_i))

    def pick_block(blk_in):
        h_b, cq_b, pos_b = blk_in
        q_i = (cq_b @ w_iq).reshape(B, Hi, Di)
        w_i = (h_b @ w_iw) * (Hi * Di) ** -0.5
        own = pos_b // G
        pick = picked_groups(score(q_i, w_i, kbar), own, k_pick, variant)
        agree = jnp.ones((B,), jnp.float32)
        if theirs is not None:
            other = picked_groups(score(q_i, w_i, theirs), own, k_pick,
                                  variant)
            agree = jnp.sum(pick & other, axis=-1) / jnp.sum(pick, axis=-1)
        return pick, agree

    blocks = (h.reshape(T // B, B, -1), c_q.reshape(T // B, B, -1),
              pos.reshape(T // B, B))
    pick, agree = jax.lax.map(pick_block, blocks)       # [T / B, B, NG]

    # attention over the expanded keys and values of ALL positions, the
    # heads ``Hc`` at a time (every head's at once are 2 GB at 16k tokens)
    Hc = next(c for c in (_HEAD_BLOCK, H) if H % c == 0)
    w_q = _f32(p["q_b_w"]).reshape(-1, H // Hc, Hc, nope)
    w_kv = _f32(p["kv_b_w"]).reshape(-1, H // Hc, Hc, nope + dv)
    wo = _f32(p["out_w"]).reshape(H // Hc, Hc * dv, -1)

    def head_block(y, ws):
        wq_c, wkv_c, wo_c = ws
        kv = jnp.einsum("tr,rhe->the", c, wkv_c)
        k_n, v = kv[..., :nope], kv[..., nope:]

        def query_block(blk_in):
            cq_b, pos_b, pick_b = blk_in
            q = jnp.einsum("br,rhn->bhn", cq_b, wq_c)
            seen = jnp.repeat(pick_b, G, axis=-1)
            if variant == "no_selection":
                seen = jnp.ones_like(seen)
            elif variant == "no_tail":      # (the query still sees itself)
                seen = seen | (pos_b[:, None] == pos[None, :])
            seen = seen & (pos_b[:, None] >= pos[None, :])
            s = jnp.einsum("bhn,thn->hbt", q, k_n) * nope ** -0.5
            s = jnp.where(seen[None], s, -jnp.inf)
            ctx = jnp.einsum("hbt,thv->bhv",
                             squash(jax.nn.softmax(s, axis=-1)), v)
            return ctx.reshape(B, Hc * dv) @ wo_c

        return y + jax.lax.map(query_block, (blocks[1], blocks[2], pick)
                               ).reshape(T, -1), None

    y = jax.lax.scan(head_block, jnp.zeros_like(h), (
        w_q.transpose(1, 0, 2, 3), w_kv.transpose(1, 0, 2, 3), wo))[0]
    return y, agree.reshape(T)


def _hidden(config: dict, w: dict, ids, n, variant: str = "", pooled=None):
    """ids [T] (T whole query blocks and groups), of which the first ``n``
    are the sequence -> (final-norm hidden [T, d] float32, every KDA
    layer's state after token n - 1 [layers, H, K, V], the worst sparse
    layer's pick agreement with ``pooled`` [Ls, T / G, Di] a query [T])."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {sorted(VARIANTS)}")
    lossy = variant == "bf16_stated_f32"
    spec = spec_of(config)
    blk = spec.block
    eps, L = config["rms_norm_eps"], config["num_hidden_layers"]
    n_str = 1 if variant == "no_mhc" or not config["mhc"] else \
        config["hc_mult"]
    kinds = pattern_of(config)
    index = blk.group_index(L)
    T = ids.shape[0]
    real = (jnp.arange(T) < n)[:, None]
    stack = {key: w[f"lm_stack.stack_{key}"]
             for key in blk.stack_slots().values()}
    n_here = stack["moe_gate_w"].shape[1]
    experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:])
               for k in stack if k.startswith("moe_")}

    def planes(at, *groups):
        """Layer ``at``'s planes of ``groups`` (``at``: the layer's index in
        every stack group, traced)."""
        return {k: v[at[blk.plane_group(k)]] for k, v in stack.items()
                if not k.startswith("moe_") and blk.plane_group(k) in groups}

    Bt = next(b for b in (_TOKEN_BLOCK, T) if T % b == 0)

    def by_tokens(fn, *xs):
        """A token-wise ``fn`` over blocks of ``Bt`` tokens (the flattened
        streams of 16384 tokens, and a dense layer's 12288-wide products of
        them, are a GB each)."""
        ys = jax.lax.map(lambda a: fn(*a), tuple(
            x.reshape((T // Bt, Bt) + x.shape[1:]) for x in xs))
        return jax.tree_util.tree_map(
            lambda y: y.reshape((T,) + y.shape[2:]), ys)

    def half(p, X, which, F, tokenwise=False):
        """(X <- the half block F over the streams, what F returns beside
        its output)."""
        if n_str == 1:
            y, aux = F(X[:, 0])
            return X + y[:, None], aux

        def read(X_b):
            pre, post, res = hc_mixes(config, p, X_b, which, variant)
            return jnp.einsum("tn,tnd->td", pre, X_b), post, res

        def write(X_b, y_b, post, res):
            return (jnp.einsum("tij,tjd->tid", res, X_b)
                    + post[..., None] * y_b[:, None])

        if tokenwise:
            return by_tokens(lambda X_b: (
                lambda u, post, res: write(X_b, F(u)[0], post, res))(
                    *read(X_b)), X), None
        u, post, res = by_tokens(read, X)
        y, aux = F(u)
        return by_tokens(write, X, y, post, res), aux

    H_k, K_k = (config["linear_attn_config"][k]
                for k in ("num_heads", "head_dim"))

    # a mixer -> (its output, its KDA state after token n - 1 or zeros, its
    # pick agreement or ones); a feed-forward -> its output
    def kda_mixer(at, u):
        p = planes(at, "mixers", "kda")
        y, S = kda_layer(config, p, _rms(u, p["ln1_s"], eps, lossy), real,
                         variant)
        return y, (S, jnp.ones((T,), jnp.float32))

    def dsa_mixer(at, u):
        p = planes(at, "mixers", "mla")
        y, a = dsa_layer(config, p, _rms(u, p["ln1_s"], eps, lossy), variant,
                         None if pooled is None else pooled[at["mla"]])
        return y, (jnp.zeros((H_k, K_k, K_k), jnp.float32), a)

    def dense_ffn(at, u):
        p = planes(at, "ffns", "dense")
        return clamped_glu(config, _rms(u, p["ln2_s"], eps, lossy),
                           p["dense_gate_w"], p["dense_up_w"],
                           p["dense_down_w"], variant), None

    def expert_ffn(at, u):
        p = planes(at, "ffns", "experts")
        return expert_layer(config, {**p, **experts},
                            _rms(u, p["ln2_s"], eps, lossy), variant=variant,
                            offset=at["experts"] * n_here), None

    # ONE scanned body over the layers: every kind of mixer and of
    # feed-forward is a branch of it, traced (and compiled) once however
    # many layers have it
    mixers = [{"kda": kda_mixer, "mla": dsa_mixer}[k]
              for k in sorted(set(kinds))]
    ffns = [dense_ffn] * bool(first_dense_of(config)) + [expert_ffn] * (
        first_dense_of(config) < L)

    def body(carry, layer):
        X, agree = carry
        at, mixer, ffn = layer
        X, (S, a) = half(planes(at, "mixers"), X, "hc1", lambda u:
                         jax.lax.switch(mixer, mixers, at, u))
        X, _ = half(planes(at, "ffns"), X, "hc2", lambda u:
                    jax.lax.switch(ffn, ffns, at, u), tokenwise=True)
        return (X, jnp.minimum(agree, a)), S

    X = jnp.broadcast_to(_f32(w["tok_emb"][ids])[:, None],
                         (T, n_str, config["hidden_size"]))
    (X, agree), S = jax.lax.scan(
        body, (X, jnp.ones((T,), jnp.float32)), (
            {g: jnp.asarray([i or 0 for i in ix], jnp.int32)
             for g, ix in index.items()},
            jnp.asarray([sorted(set(kinds)).index(k) for k in kinds]),
            jnp.asarray([len(ffns) - 1 if l >= first_dense_of(config) else 0
                         for l in range(L)])))
    states = [S[l] for l in range(L) if kinds[l] == "kda"]
    x = jnp.sum(X, axis=1)
    return (_rms(x, w["final_ln.scale"], eps, lossy), jnp.stack(states),
            agree)


def _padded(n: int) -> int:
    """The length the reference runs ``n`` tokens at: whole multiples of
    ``_PAD`` (few distinct compiled programs) beyond one query block."""
    if n <= _QUERY_BLOCK:
        return -(-n // 4) * 4 if n > 4 else n
    q = _PAD if n > _PAD else _QUERY_BLOCK
    return -(-n // q) * q


_HIDDEN_JITS: dict = {}


def _jit_hidden(config: dict, variant: str = "", pooled: bool = False):
    import jax

    key = (id(config), variant, pooled)
    if key not in _HIDDEN_JITS:
        _HIDDEN_JITS[key] = jax.jit(
            (lambda w, ids, n, pool: _hidden(config, w, ids, n, variant,
                                             pool)) if pooled else
            (lambda w, ids, n: _hidden(config, w, ids, n, variant)))
    return _HIDDEN_JITS[key]


def _rows_logits(config: dict, w: dict, seq: np.ndarray, rows,
                 variant: str = "", pooled=None):
    """Teacher-forced reference logits [len(rows), V] at positions ``rows``
    of ``seq`` (the head over those rows only), every KDA layer's state
    after the last token and the pick agreement at ``rows``."""
    import jax
    import jax.numpy as jnp

    ids = np.zeros(_padded(seq.size), np.int32)
    ids[:seq.size] = seq
    if pooled is not None:
        # (a row a group of the PADDED length: one compiled shape whatever
        # the sequence's own length)
        held = np.zeros((pooled.shape[0], ids.size // config["index_kpool"],
                         pooled.shape[-1]), np.float32)
        held[:, :pooled.shape[1]] = pooled[:, :held.shape[1]]
        pooled = held
    rows = np.asarray(rows)
    # (whole query blocks of rows: ONE compiled shape of the head however
    # many positions a request was served at)
    whole = np.concatenate([rows, np.repeat(rows[-1:],
                                            -rows.size % _QUERY_BLOCK)])
    with jax.default_matmul_precision("highest"):
        fn = _jit_hidden(config, variant, pooled is not None)
        args = (w, jnp.asarray(ids), seq.size) + (
            () if pooled is None else (pooled,))
        hidden, S, agree = fn(*args)
        logits = np.concatenate([
            np.asarray(_head(hidden[jnp.asarray(whole[i:i + _QUERY_BLOCK])],
                             w["lm_head.w"]))
            for i in range(0, whole.size, _QUERY_BLOCK)])[:rows.size]
    return logits, np.asarray(S), np.asarray(agree)[rows]


def reference_logits(config: dict, w: dict, ids, rows=None,
                     variant: str = ""):
    """ids [T] -> logits [len(rows), V] float32 at positions ``rows`` (all
    T when None: small models only)."""
    ids = np.asarray(ids)
    rows = np.arange(ids.size) if rows is None else np.asarray(rows)
    return _rows_logits(config, w, ids, rows, variant)[0]


def _replay_engine(config: dict):
    """The engine ``build_engine`` last built for ``config`` — the TIMED
    one, idle after the drain — with its beam plane of ``CHECK_TOPK``
    log-probs on (how logits leave an engine)."""
    eng = _ENGINES.get(id(config))
    if eng is None or eng.beam_width != CHECK_TOPK:
        raise ValueError("reference_logit_gaps replays the checked requests "
                         "through the engine build_engine built, with the "
                         "beam plane on (engine.beam_width in the mix)")
    return eng


#: the engine's scope arrays of the KDA states [layers, slots, H, K, V] and
#: of the pooled indexer keys [layers, pages, groups a page, Di], and the
#: prefill feeds that name a row's slot and its pages
_STATE_ARRAY, _STATE_SLOT = "serving.state.KdaState", "serving.state_slot"
_INDEX_POOL, _TABLE = "serving.paged_cache_index", "serving.block_table"


def served(eng, prompt, new_tokens: int):
    """One request through ``eng`` (beam plane on), chunked prefill then
    decode: -> ([(position, top-k log-probs, their ids)] of every chunk end
    and decode step, the emitted sequence, the state its slot holds at the
    end [layers, H, K, V], the pooled indexer keys its pages hold [Ls,
    groups, Di])."""
    calls, slots, tables = [], [], []
    run = eng.executor.run
    prompt = np.asarray(prompt)

    def capture(prog, feed=None, fetch_list=None, scope=None, **kw):
        res = run(prog, feed=feed, fetch_list=fetch_list, scope=scope, **kw)
        if not feed or _TABLE not in feed:
            return res
        if "serving.chunk" in feed:
            # THIS request's chunks alone (a request the timed window left
            # in the engine may still be prefilling): told by their tokens
            start = int(feed["serving.start"][0])
            n = int(feed["serving.chunk_len"][0])
            if not np.array_equal(np.asarray(feed["serving.chunk"])[0, :n],
                                  prompt[start:start + n]):
                return res
            slots.append(int(np.asarray(feed[_STATE_SLOT])[0]))
            tables.append(np.asarray(feed[_TABLE])[0].copy())
            pos, row = start + n - 1, 0
        else:
            if not slots:
                return res
            pos, row = int(feed["serving.pos"][slots[0]]), slots[0]
            if pos < prompt.size:       # not decoding yet
                return res
        calls.append((pos, np.asarray(res[1])[row], np.asarray(res[2])[row]))
        return res

    eng.executor.run = capture
    try:
        out = np.asarray(eng.generate_all([prompt],
                                          max_new_tokens=new_tokens)[0])
    finally:
        eng.executor.run = run
    state = np.asarray(eng.scope.get(_STATE_ARRAY)[:, slots[0]], np.float32)
    pages = tables[0][:-(-out.size // eng.page_size)]
    pool = eng.scope.get(_INDEX_POOL)[:, pages]     # [Ls, pages, gp, Di]
    pooled = np.asarray(pool, np.float32).reshape(
        pool.shape[0], -1, pool.shape[-1])
    return calls, out, state, pooled


def _readings(config: dict, w: dict, calls, again, held, pooled,
              variant: str = "", emitted=None):
    """The reference's teacher-forced forward of the replayed sequence
    ``again`` against what the replay served: -> (the largest error of the
    served top-k log-probs a served position, {"rel_err", "bits": the slot's
    KDA state against the reference's a layer, "pick_miss": 1 - the share of
    the reference's picked groups that the ENGINE's pooled keys pick, a
    served position}, the reference's logits at ``emitted`` positions)."""

    at = np.asarray([p for p, _, _ in calls])
    rows = at if emitted is None else np.concatenate([at, emitted])
    # (the last token is emitted, never fed: its group is not pooled)
    n_groups = (again.size - 1) // config["index_kpool"]
    logits, S, agree = _rows_logits(config, w, again[:-1], rows, variant,
                                    pooled=pooled[:, :n_groups])
    ref = logits[:at.size] - logits[:at.size].max(axis=-1, keepdims=True)
    ref = ref - np.log(np.exp(ref).sum(axis=-1, keepdims=True))
    errs = [float(np.abs(v - ref[j][i]).max())
            for j, (_, v, i) in enumerate(calls)]
    more = {"rel_err": [float(np.linalg.norm(a - b) / np.linalg.norm(b))
                        for a, b in zip(held, S)],
            "bits": [abs(a - b) for a, b in zip(mantissa_bits(held),
                                                mantissa_bits(S))],
            "pick_miss": (1.0 - agree[:at.size]).tolist()}
    return errs, more, logits[at.size:]


def served_errors(config: dict, w: dict, eng, prompt, new_tokens: int,
                  variants=("",)):
    """One request through ``eng``: -> ({variant: the errors of
    ``_readings`` against that reference}, the emitted sequence, the served
    positions, {variant: its ``more``})."""
    calls, again, held, pooled = served(eng, prompt, new_tokens)
    errs, more = {}, {}
    for variant in variants:
        errs[variant], more[variant], _ = _readings(
            config, w, calls, again, held, pooled, variant)
    return errs, again, np.asarray([p for p, _, _ in calls]), more


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """What the serve driver holds a run to: it compares the LARGEST value
    returned with the mix's ``check.logit_gap_tol`` = ``CHECK_LOGPROB_TOL``.
    Five readings, each scaled into that limit's terms:

    1. the ``CHECK_LOGPROB_QUANTILE``-th percentile of the SERVED top-8
       log-prob error: every checked request ``(prompt_len, ids)``, its
       prompt and the first ``CHECK_REPLAY_TOKENS`` tokens of its answer, is
       replayed, after the drain, through the TIMED engine itself (chunked
       prefill with selection in every chunk past the second, then the
       tick's selection and the KDA kernel from the slot's state) and what
       it serves at every chunk end and decode step is compared with the
       reference's teacher-forced full forward of the replayed sequence;
    2. the LARGEST such error over the served positions (limit
       ``CHECK_LOGPROB_MAX_TOL``);
    3. on the tokens the TIMED engine emitted: how far below its position's
       best the reference puts each (the largest; limit
       ``CHECK_EMITTED_GAP_TOL``; ONE forward serves these readings where
       the replay emitted the timed engine's tokens, as a greedy one does);
    4. the precision of the KDA state each replay leaves in its slot
       (mantissa bits; limit ``CHECK_STATE_BITS_TOL``);
    5. the mean share, over the served positions, of the reference's picked
       groups that scoring the pooled keys the ENGINE cached does not pick
       (limit ``CHECK_PICK_MISS_TOL``): a pool written wrong, a group
       averaged over the wrong tokens, reads far above it.

    The readings go to stderr as one JSON line."""
    import json
    import sys

    import time

    eng = _replay_engine(config) if results else None
    errs: List[float] = []
    gaps: List[float] = []
    miss: List[float] = []
    bits = same = 0
    t0, spent = time.monotonic(), {"replay_s": 0.0, "reference_s": 0.0}
    for prompt_len, out in results:
        out = np.asarray(out)[:prompt_len + CHECK_REPLAY_TOKENS]
        calls, again, held, pooled = served(eng, out[:prompt_len],
                                            out.size - prompt_len)
        spent["replay_s"] -= t0 - (t0 := time.monotonic())
        emitted = np.arange(prompt_len - 1, out.size - 1)
        equal = np.array_equal(again, out)
        e, more, mine = _readings(config, w, calls, again, held, pooled,
                                  emitted=emitted if equal else None)
        if not equal:
            mine = _rows_logits(config, w, out[:-1], emitted,
                                pooled=pooled[:, :1])[0]
        spent["reference_s"] -= t0 - (t0 := time.monotonic())
        errs.extend(e)
        miss.extend(more["pick_miss"])
        bits = max(bits, *more["bits"])
        same += equal
        gaps.extend((mine.max(axis=-1) - mine[np.arange(emitted.size),
                                              out[emitted + 1]]).tolist())
    if not errs:
        return np.zeros((0,), np.float32)
    held = float(np.percentile(errs, CHECK_LOGPROB_QUANTILE))
    worst, missed = float(max(gaps)), float(np.mean(miss))
    largest = float(max(errs))
    print(json.dumps({"dsa_kda_moe_lm.check": {
        "quantile": CHECK_LOGPROB_QUANTILE, "limit": CHECK_LOGPROB_TOL,
        **{f"served_logprob_err_p{q}": float(np.percentile(errs, q))
           for q in (50, 80, 90, 95, 99)},
        "served_logprob_err_max": largest,
        "served_logprob_err_max_limit": CHECK_LOGPROB_MAX_TOL,
        "served_positions": len(errs), "emitted_gap_max": worst,
        "emitted_gap_limit": CHECK_EMITTED_GAP_TOL,
        "emitted_positions": len(gaps),
        "state_bits_differ_max": int(bits),
        "state_bits_limit": CHECK_STATE_BITS_TOL,
        "pick_miss_mean": missed, "pick_miss_max": float(max(miss)),
        "pick_miss_limit": CHECK_PICK_MISS_TOL,
        "requests": len(results),
        "contexts": [int(np.asarray(o).size) for _, o in results],
        "replays_equal_to_timed": int(same), **spent}}),
        file=sys.stderr, flush=True)
    return np.asarray(
        [held, largest * CHECK_LOGPROB_TOL / CHECK_LOGPROB_MAX_TOL,
         worst * CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL,
         bits * CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL,
         missed * CHECK_LOGPROB_TOL / CHECK_PICK_MISS_TOL], np.float32)


# ---------------------------------------------------------------------------
# what the mechanisms have to move, and which device event belongs to which
# ---------------------------------------------------------------------------
def dsa_cost(config: dict, groups_scored: float,
             rows_attended: float) -> Dict[str, float]:
    """The least work of ONE sparse layer's selection and attention over
    ``groups_scored`` pooled keys and ``rows_attended`` picked latent rows
    (the engine's counters of the same names), whatever implements it: each
    pooled key and each picked row read ONCE in the page dtype; the
    indexer's Hi dot products of Di a group and the absorbed attention's H
    scores over the row and H read-outs over the latent a row."""
    Hi, Di = config["index_n_heads"], config["index_head_dim"]
    H, r = config["num_attention_heads"], config["kv_lora_rank"]
    W = r + config["qk_rope_head_dim"]
    itemsize = 2 if config["assumed"]["page_dtype"] == "bfloat16" else 4
    return {"bytes": (groups_scored * Di + rows_attended * W) * itemsize,
            "flops": 2.0 * groups_scored * Hi * Di
            + 2.0 * rows_attended * H * (W + r)}


def _geometry(config: dict):
    a = config["assumed"]
    G = config["index_kpool"]
    gp = a["page_size"] // G
    table = -(-a["max_len"] // a["page_size"])
    return {"G": G, "gp": gp, "k1": config["index_topk"] // G,
            "table": table, "NG": table * gp,
            "layers": pattern_of(config).count("mla"),
            "W": config["kv_lora_rank"] + config["qk_rope_head_dim"]}


def dsa_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of a sparse latent layer's selection a device event
    belongs to, by the shapes it reads or writes: ``"score"`` (the
    indexer's products, relu and head sum over the table's groups, the
    pooled keys' gather), ``"pick"`` (the top-k over them), ``"gather"``
    (the picked groups' latent rows), ``"attend"`` (scores, softmax and
    read-out over the picked rows), ``"pool"`` (the pooled keys' pool:
    its write), ``"project"`` (the indexer's query projection). None for
    everything else."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    g = _geometry(config)
    H, Di = config["num_attention_heads"], config["index_head_dim"]
    Hi, rq = config["index_n_heads"], config["q_lora_rank"]
    rows = g["k1"] * g["G"]
    if re.search(rf",{g['NG']}\]", text) or re.search(
            rf"\[(\d+,)*{g['NG']},{Di}\]", text):
        if opcode in ("sort", "topk") or name.startswith(
                ("sort", "top-k", "topk")) or "TopK" in text:
            return "pick"
        return "score"
    # the picked groups' rows out of the pool viewed [Ls, N groups, G W]
    # (the compiler flattens the result to [queries x picks, G W]), and their
    # page ids out of the table
    if re.search(rf"\[{g['layers']},\d+,{g['G'] * g['W']}\]", text) \
            or re.search(rf"s32\[{g['table']}\]", text):
        return "gather"
    if re.search(rf",{g['k1']},{g['G'] * g['W']}\]", text) or re.search(
            rf"\[(\d+,)+{rows},{g['W']}\]", text):
        return "gather" if opcode in ("gather", "dynamic-slice") else "attend"
    if re.search(rf"\[(\d+,)*{H},(\d+,)?{rows}\]", text):
        return "attend"
    if re.search(rf"\[\d+,\d+,{g['gp']},{Di}\]", text):
        return "pool"
    if f"[{rq},{Hi * Di}]" in text:
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


def mhc_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of the manifold-constrained residual a device event
    belongs to: ``"stream"`` (anything shaped like the n streams [.., n, d]
    or their flattening [.., n d]: the read, the write-back, the norm),
    ``"mix"`` (the mixes' projection [n d, n n + 2 n] and the Sinkhorn
    rounds over [.., n, n]). None for everything else."""
    from benchmark.trace_reduce import strip_layouts

    text = strip_layouts(hlo_text)
    n, d = config["hc_mult"], config["hidden_size"]
    H, rq = config["num_attention_heads"], config["q_lora_rank"]
    # (the sparse layer's heads are as wide together as the streams: its
    # query and out-projections are told by their weights)
    if f"[{rq},{H * config['qk_nope_head_dim']}]" in text \
            or f"[{H * config['v_head_dim']},{d}]" in text:
        return None
    if f"[{n * d},{n * n + 2 * n}]" in text:
        return "mix"
    if re.search(rf"\[(\d+,)+{n},{d}\]", text) or re.search(
            rf"\[(\d+,)+{n * d}\]", text):
        return "stream"
    if re.search(rf"\[(\d+,)+{n},{n}\]", text) or re.search(
            rf"\[(\d+,)+{n * n + 2 * n}\]", text):
        return "mix"
    return None


_KDA_BLOCK = 64


def kda_op(hlo_text: str, config: dict) -> Optional[str]:
    """As ``kda_mla_moe_lm.kda_op``: ``"step"`` (the decode kernel),
    ``"state"`` (the chunked form, the state's gather and scatter),
    ``"project"`` (q | k | v, the low-rank gates, the convolution). None
    for everything else."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, _ = parse_op(hlo_text)
    if name.split(".")[0] == KDA_KERNEL:
        return "step"
    lin = config["linear_attn_config"]
    H, K, d = lin["num_heads"], lin["head_dim"], config["hidden_size"]
    rank = config["assumed"]["kda_proj_rank"]
    if re.search(rf"\[(\d+,)*{H},{K},{K}\]", text) \
            or re.search(rf"\[(\d+,)*{H},{_KDA_BLOCK},{_KDA_BLOCK}(,{K})?\]",
                         text):
        return "state"
    if re.search(rf"\[(\d+,)*({d},)?{3 * H * K}\]", text) \
            or f"[{rank},{H * K}]" in text or f"[{d},{rank}]" in text:
        return "project"
    return None


def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """As ``kda_mla_moe_lm.moe_op``: ``"grouped_matmul"`` |
    ``"shared_expert"`` | ``"route"`` | None, by the expert stacks' shapes."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    L = config["num_hidden_layers"] - first_dense_of(config)
    held = config["n_routed_experts"]
    E, d, f = (config["router_outputs"], config["hidden_size"],
               config["moe_intermediate_size"])
    pair = rf"({d},{f}|{f},{d})"
    if name.startswith("ragged-dot") or re.search(
            rf"\[({L},{held}|{L * held}|{held}),{pair}\]", text):
        return "grouped_matmul"
    if re.search(rf"\[({L},)?{pair}\]", text):
        return "shared_expert"
    if f"[{d},{E}]" in text or (
            opcode in ("sort", "topk") and f",{E}]" in text):
        return None if f",{config['vocab_size']}]" in text else "route"
    return None
