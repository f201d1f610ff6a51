"""Family ``kda_mla_moe_lm``: a Ling-3.0 / Kimi-Linear-shaped hybrid decoder —
periods of ``layer_group_size`` layers, all but the last Kimi Delta Attention
(KDA: the gated delta rule with a per-channel decay, linear in the context, a
fixed-size recurrent state a sequence), the last latent attention (MLA, full
-rank query, head-wise output gate); the leading ``first_k_dense_replace``
layers a dense SwiGLU, the rest a sigmoid group-limited router with a
selection bias over SwiGLU experts of which THIS chip holds a share, plus one
shared expert; pre-norm RMSNorm, no biases, untied head — served by
``serving.GenerationEngine(spec, ...)`` from ONE ``paddle_tpu.lm_spec.LMSpec``
(``spec_of``), with the yardstick's own pieces: the KDA decode kernel's and
the held experts' bytes, which device op belongs to which layer, and a plain
float32 ``jax.numpy`` reference of the equations (x [T, d], one sequence):

    h = RMSNorm_1(x)                      RMSNorm(u) = u rsqrt(mean(u^2) + eps) w
  KDA layer (arXiv:2510.26692), H heads of K = V = head_dim:
    [q~ | k~ | v~] = h W_qkv;  c(u)_t = silu(sum_i w_i u_{t-3+i})  (4 taps, zero history)
    q = l2norm(c(q~)) K^-1/2;  k = l2norm(c(k~));  v = c(v~)       per head
    g = kda_lower_bound * sigmoid(exp(A_log_h) (h W_a + dt_bias))  per key channel, in (-5, 0)
    beta = sigmoid(h w_beta_h)
    S' = diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    x' = x + [RMSNorm_head(o_t) * sigmoid(h W_g)] W_o              S float32, S_0 = 0
  MLA layer (DeepSeek-V2), no query bottleneck:
    q = h W_q -> [T, H, nope | rope];  [c | k_r] = h W_kva;  c = RMSNorm(c)
    [k_nope | v] = c W_kvb;  RoPE (interleaved pairs, theta) on q_rope and the ONE k_r a token
    s[h,i,j] = (q_nope.k_nope + q_rope.k_rope) (nope + rope)^-1/2,  j <= i
    x' = x + [softmax_j(s) v * sigmoid(h w_g_h)] W_o               gate head-wise
  FFN, h2 = RMSNorm_2(x'):
    l < first_k_dense_replace:  x'' = x' + (silu(h2 W_g) * (h2 W_u)) W_d
    else: s = sigmoid(h2 W_r) over ALL experts (float32); c = s + b
      groups of E / n_group; a group's score = its two largest c summed; keep topk_group groups
      S = top-k of c inside them;  w_e = s_e / sum_S s * routed_scaling_factor
      x'' = x' + E_shared(h2) + sum_{e in S, e HELD} w_e E_e(h2)
    logits = RMSNorm_f(x_L) W_head

What the absent experts would add is left out, program and reference alike
(the ``model-configs`` guide, section 4); ``expert_layer(.., held=)`` gives
any share, so a test can add the shares up to the uncut layer.

The reference has no cache, no state array, no kernel, no chunked form, no
absorbed attention, no sort and no grouped matmul: KDA is the token-by-token
recurrence under ``lax.scan``, MLA expands every head's keys and values, every
HELD expert is applied densely and masked by the top-k set. It reads the SAME
stored weights as the program and runs under
``jax.default_matmul_precision("highest")``.

Every reading the published keys do not settle is under ``assumed`` in the
configuration file, with the key it rests on.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.mla_moe_lm import (  # noqa: F401 - the same kernel
    mla_decode_call, mla_decode_cost)   # the ONE latent layer's page walk
from benchmark.families.moe_lm import (  # noqa: F401 - the family's surface
    draw_prompt_ids, grouped_matmul_cost, served_logprobs)
from benchmark.families.window_moe_lm import (  # the same plain pieces
    _QUERY_BLOCK, _f32, _head, _padded, _rms)

ITEM = "tokens"
_EXPERT_BLOCK = 4       # experts upcast to float32 at a time
_TOKEN_BLOCK = 1024     # tokens that go through the experts together
_L2_EPS = 1e-6
#: WRONG models, one fault each, that the check and the tier-1 tests must
#: tell from the right one: ``reference_logits(.., variant=name)``
VARIANTS = {
    "no_decay": "g = 0: the state never forgets",
    "no_lower_bound": "g = -exp(A_log) softplus(a): the decay without "
                      "kda_lower_bound's squash",
    "no_delta": "S = S' + beta k v^T: no (v - S'^T k) correction",
    "softmax_router": "softmax over the logits instead of sigmoid",
    "no_router_bias": "the top-k taken on s, not s + b",
    "no_group_limit": "top-k over all experts, no group selection",
    "no_shared_expert": "the always-on expert left out",
    "bf16_stated_f32": "norms, router scores and the KDA / output gates "
                       "rounded to bfloat16 where the configuration says "
                       "float32",
    "bf16_state": "the recurrent state kept in bfloat16 between tokens",
}

#: THE LIMIT on the served top-8 log-prob error (``reference_logit_gaps``;
#: the mix's ``check.logit_gap_tol`` IS this number), the 80th percentile
#: over the positions. Readings (my chip runs, PR 42, PERF.md section 6;
#: ``tools/ling3_chip_check.py variants`` at seeds 2147483659 / 71 / 99,
#: 217 positions each, and the cell's own checked requests, 823-4699
#: positions a run): against the right reference p80 0.0021-0.0028 (p50
#: 0.0016-0.0019; the cell's runs p90 0.0027-0.0030); with norms, router
#: scores and gates rounded to bfloat16 (one precision below what the
#: configuration states) p80 0.0063 / 0.0287 (p90 0.033-0.046); every
#: fault of the mathematics p80 >= 0.077 (no group limit; no decay 0.37).
#: 0.0045 = 1.6 x the largest right reading and 1.4 x under the smallest
#: wrong one (those with ``router_bias_std`` 0.05; at the final 0.005: right
#: 0.0026 and 0.00226-0.00236 in ten runs of the cell, one precision lower
#: 0.0343, no router bias 0.059). Why the 80th percentile: a sound engine sits at 0.03-0.10 in
#: 2-8% of positions (an upstream bf16 product flips a near-tie of the
#: router's GROUP choice, and with groups 0 and 1 of 8 held that swaps
#: whether a held expert answers at all), so p95 read 0.0032 / 0.0035 /
#: 0.0317 at the three seeds; the bfloat16 model flips in over 20%.
CHECK_LOGPROB_QUANTILE = 80
CHECK_LOGPROB_TOL = 0.0045
#: THE LIMIT on the recurrent state's precision: |mantissa bits the
#: engine's held state uses - those the reference's uses|, the worst KDA
#: layer of a checked request (``served_errors``). The configuration states
#: a float32 state; the right engine reads 0 (23 against 23), a state kept
#: in bfloat16, the nearest precision below, reads 16 (the ``bf16_state``
#: model's 7 against the engine's 23; an engine that lowered its state
#: reads the same against the right model): 8 lies half way. Why bits and
#: not a distance: the state's relative error against the float32
#: recurrence separates the two by 1.2-1.4 x only (tiny size under AMP, the
#: CPU: 0.0030-0.0067 right, 0.0035-0.0088 with a bfloat16 state), as the
#: served log-probs did on the chip (p80 0.0021-0.0028 against 0.0027-
#: 0.0035): the engine's bfloat16 q | k | v products put as much into S as
#: rounding S does. The distance still goes to stderr, a layer: on the
#: chip the right engine reads 0.0032-0.0036 in the three layers no router
#: precedes and 0.016-0.023 in the two after the first expert layers (a
#: flipped group choice upstream), bits 0, in three runs of the cell (my
#: chip runs, PR 42; a bfloat16 state's distance on the chip: not measured).
CHECK_STATE_BITS_TOL = 8
#: ... and the limit on how far below its position's best the reference
#: puts a token the TIMED engine emitted: the right engine reads 0.089 /
#: 0.103 (823 / 4682 positions: the same flips), a precision fault no
#: more (0.054-0.080 over 200), the mathematics' faults 0.09-0.60 over 200
#: positions: this one catches a request answered with another's tokens
#: (several units), nothing finer.
CHECK_EMITTED_GAP_TOL = 0.5
CHECK_TOPK = 8


def _bf16(t):
    """t rounded to bfloat16's 8 exponent and 7 mantissa bits, kept in
    float32. ``reduce_precision`` and not a pair of casts: the TPU compiler
    may drop a float32 -> bfloat16 -> float32 pair (excess precision is
    allowed by default), and did, inside the recurrence's loop (my chip
    run, PR 42: the bf16-state variant read the right model's numbers to
    the last digit)."""
    import jax

    return jax.lax.reduce_precision(t, exponent_bits=8, mantissa_bits=7)


def pattern_of(config: dict) -> Tuple[str, ...]:
    p = config["layer_group_size"]
    return ("kda",) * (p - 1) + ("mla",)


def held_of(config: dict) -> Tuple[int, int]:
    """(first, count): the routed experts this chip holds."""
    return config["assumed"]["experts_first"], config["num_experts"]


def spec_of(config: dict):
    """The program's model spec for this configuration: a tree whose spec
    lacks the attention kinds, the slot state or the router's fields fails
    here, at once, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a = config["assumed"]
    first, count = held_of(config)
    E = config["router_outputs"]
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"], use_rope=True,
        max_len=a["max_len"], norm="rms_norm",
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        rope_pairing="interleaved" if config["rope_interleave"] else "half",
        attn="mla", q_lora_rank=config["q_lora_rank"] or 0,
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        attn_gate={"head_wise": "head"}[
            config["gated_attention_proj_granularity_type"]],
        layer_pattern=pattern_of(config), kda_head_dim=config["head_dim"],
        kda_conv=config["short_conv_kernel_size"],
        kda_lower_bound=float(config["kda_lower_bound"]),
        first_dense=config["first_k_dense_replace"],
        d_ff=config["intermediate_size"], ffn="swiglu_moe", num_experts=E,
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=(config["num_shared_experts"]
                  * config["moe_shared_expert_intermediate_size"]),
        experts_held=None if (first, count) == (0, E) else (first, count),
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        router_score=config["score_function"],
        router_bias=config["moe_router_enable_expert_bias"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        bias=False, param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def seeded_vectors(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """The planes a startup program leaves at a constant and a checkpoint
    does not (``assumed``: ``router_bias_std``, ``kda_gate_values``): the
    router's selection bias b ~ N(0, ``router_bias_std``^2), A_log = ln
    U(0.5, 1.5), dt_bias ~ U(-5, -1) — a spread of slow and fast
    channels: a token keeps exp(g) between 0.26 and 0.99 of a channel."""
    spec = spec_of(config)
    rng = np.random.default_rng([int(seed), 0x4b4441])
    H, K = spec.num_heads, spec.kda_head_dim
    Lk, Le = spec.plane_layers("kda_a_log"), spec.plane_layers("router_b")
    std = config["assumed"].get("router_bias_std", 0.05)
    return {
        "router_b": rng.normal(0.0, 1.0, (Le, spec.num_experts)) * std,
        "kda_a_log": np.log(rng.uniform(0.5, 1.5, (Lk, H))),
        "kda_dt_bias": rng.uniform(-5.0, -1.0, (Lk, H * K)),
    }


def build_engine(config: dict, mix: dict, seed: int, **engine_kw):
    """-> (engine, executors). Weights come from ONE run of the generation
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
    _ENGINES[id(config)] = mix["engine"]
    eng = _engine(spec, scope, mix["engine"], **engine_kw)
    return eng, [exe, eng.executor]


def _engine(spec, scope, e: dict, **engine_kw):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], max_seq_len=e["max_len"],
        prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None,
        # a [slots, vocabulary] float32 mask a tick is 40 MB of host feed
        # at 256 slots: off where no request constrains its decoding
        mask_plane=bool(e.get("mask_plane", 1)), **engine_kw)


#: id(configuration) -> the mix's ``engine`` section the last
#: ``build_engine`` used: the check's replay engine is its twin
_ENGINES: dict = {}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The model's parameters by the fixed names the layout gives them, as
    stored (nothing is copied or cast). The configuration is not at hand
    here: every ``lm_stack.stack_*`` tensor of the scope is taken."""
    names = ["tok_emb", "final_ln.scale", "lm_head.w"] + sorted(
        n for n in scope.keys() if n.startswith("lm_stack.stack_"))
    return {name: scope.get(name) for name in names}


def _rope(x, pos, theta: float):
    """x [T, ..., rope] at positions pos [T]: pair (2i, 2i+1) rotates by
    pos * theta^(-2i/rope)."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def router_choice(config: dict, h2, router_w, router_b, variant: str = ""):
    """h2 [T, d] float32 -> (scores s [T, E], chosen [T, E] bool): the
    router of one layer over ALL its outputs."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"

    def squash(t):
        return _bf16(t) if lossy else t

    k, E = config["num_experts_per_tok"], router_w.shape[-1]
    logits = squash(h2 @ _f32(router_w))
    if variant == "softmax_router":
        s = squash(jax.nn.softmax(logits, axis=-1))
    else:
        s = squash(jax.nn.sigmoid(logits))
    c = s if variant == "no_router_bias" else s + _f32(router_b)
    n_group = 1 if variant == "no_group_limit" else config["n_group"]
    if n_group > 1:
        per = c.reshape(-1, n_group, E // n_group)
        score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)     # [T, groups]
        kth = jax.lax.top_k(score, config["topk_group"])[0][:, -1:]
        c = jnp.where((score >= kth)[..., None], per, -jnp.inf).reshape(c.shape)
    kth = jax.lax.top_k(c, k)[0][:, -1:]
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
            gated = (jax.nn.silu(jnp.einsum("td,edf->tef", b_b, _f32(wg)))
                     * jnp.einsum("td,edf->tef", b_b, _f32(wu)))
            return y + jnp.einsum("tef,efd,te->td", gated, _f32(wd),
                                  g_blk), None

        return jax.lax.scan(expert_block, jnp.zeros_like(b_b),
                            jnp.arange(0, count, eb))[0]

    routed = jax.lax.map(token_block, (
        h2.reshape(T // Bt, Bt, -1), gate.reshape(T // Bt, Bt, count))
    ).reshape(T, -1)
    shared = jnp.zeros_like(h2)
    if variant != "no_shared_expert":
        shared = (jax.nn.silu(h2 @ _f32(p["shared_gate_w"]))
                  * (h2 @ _f32(p["shared_up_w"]))) @ _f32(p["shared_down_w"])
    return (routed, shared) if parts else routed + shared


def kda_inputs(config: dict, p: dict, h, variant: str = ""):
    """h [T, d] (normed, float32) -> q, k, v, g [T, H, K], beta [T, H] of
    one KDA layer, the convolutions from a zero history."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"

    def squash(t):
        return _bf16(t) if lossy else t

    T = h.shape[0]
    H, K = config["num_attention_heads"], config["head_dim"]
    taps = config["short_conv_kernel_size"]
    u = jnp.pad(h @ _f32(p["kda_qkv_w"]), ((taps - 1, 0), (0, 0)))
    w = _f32(p["kda_conv_w"])                                   # [taps, 3HK]
    y = jax.nn.silu(sum(u[i:i + T] * w[i] for i in range(taps)))
    q, k, v = (y[:, i * H * K:(i + 1) * H * K].reshape(T, H, K)
               for i in range(3))

    def l2(t):
        return t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _L2_EPS)

    a = (h @ _f32(p["kda_a_w"]) + _f32(p["kda_dt_bias"])).reshape(T, H, K)
    rate = jnp.exp(_f32(p["kda_a_log"]))[None, :, None]
    if variant == "no_decay":
        g = jnp.zeros_like(a)
    elif variant == "no_lower_bound":
        g = -rate * jax.nn.softplus(a)
    else:
        g = config["kda_lower_bound"] * squash(jax.nn.sigmoid(rate * a))
    beta = squash(jax.nn.sigmoid(h @ _f32(p["kda_beta_w"])))
    return l2(q) * K ** -0.5, l2(k), v, g, beta


def kda_scan(q, k, v, g, beta, variant: str = "", state=None):
    """The recurrence, token by token -> (o [T, H, V], S after the last
    token [H, K, V])."""
    import jax
    import jax.numpy as jnp

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None]
        err = v_t if variant == "no_delta" \
            else v_t - jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + (b_t[:, None] * k_t)[..., None] * err[:, None, :]
        if variant == "bf16_state":
            S = _bf16(S)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    H, K = q.shape[1:]
    S0 = jnp.zeros((H, K, v.shape[-1]), jnp.float32) if state is None \
        else state
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def _hidden(config: dict, w: dict, ids, n, variant: str = ""):
    """ids [T] (T a multiple of the query block, or shorter than one), of
    which the first ``n`` are the sequence -> (final-norm hidden [T, d]
    float32, every KDA layer's state S after token n - 1 [layers, H, K, V]:
    the padding's tokens decay nothing and write nothing). ``variant``:
    one of ``VARIANTS``."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {sorted(VARIANTS)}")
    lossy = variant == "bf16_stated_f32"

    def squash(t):
        return _bf16(t) if lossy else t

    spec = spec_of(config)
    blk = spec.block
    H, eps = config["num_attention_heads"], config["rms_norm_eps"]
    r, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rope, dv = config["qk_rope_head_dim"], config["v_head_dim"]
    K, L = config["head_dim"], config["num_hidden_layers"]
    theta = float(config["rope_theta"])
    kinds = pattern_of(config)
    index = blk.group_index(L)
    T = ids.shape[0]
    B = min(_QUERY_BLOCK, T)
    if T % B:
        raise ValueError(f"{T} tokens are not whole blocks of {B}")
    pos = jnp.arange(T)
    real = (pos < n)[:, None]
    states = []
    scale = (nope + rope) ** -0.5
    stack = {key: w[f"lm_stack.stack_{key}"]
             for key in blk.stack_slots().values()}
    n_here = stack["moe_gate_w"].shape[1]
    experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:])
               for k in stack if k.startswith("moe_")}

    def planes(l):
        return {k: v[index[blk.plane_group(k)][l]] for k, v in stack.items()
                if not k.startswith("moe_")
                and index[blk.plane_group(k)][l] is not None}

    x = _f32(w["tok_emb"][ids])
    for l in range(L):
        p = planes(l)
        h = _rms(x, p["ln1_s"], eps, lossy)
        if kinds[l % len(kinds)] == "kda":
            q, k, v, g, beta = kda_inputs(config, p, h, variant)
            o, S = kda_scan(q, k, v, jnp.where(real[..., None], g, 0.0),
                            jnp.where(real, beta, 0.0), variant)
            states.append(S)
            o = _rms(o, p["kda_norm_s"], eps, lossy).reshape(T, H * K)
            gate = squash(jax.nn.sigmoid(h @ _f32(p["kda_gate_w"])))
            x1 = x + (o * gate) @ _f32(p["kda_out_w"])
        else:
            kv_a = h @ _f32(p["kv_a_w"])
            c_kv = _rms(kv_a[:, :r], p["kv_a_norm_s"], eps, lossy)
            k_rope = _rope(kv_a[:, r:], pos, theta)             # [T, rope]
            kv = (c_kv @ _f32(p["kv_b_w"])).reshape(T, H, nope + dv)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            w_q, wo = _f32(p["q_w"]), _f32(p["out_w"])
            w_gate = _f32(p["attn_gate_w"])

            def query_block(blk_in, k_nope=k_nope, v=v, k_rope=k_rope,
                            w_q=w_q, wo=wo, w_gate=w_gate):
                x_b, h_b, pos_b = blk_in
                q = (h_b @ w_q).reshape(B, H, nope + rope)
                q_rope = _rope(q[..., nope:], pos_b, theta)
                s = (jnp.einsum("bhn,thn->hbt", q[..., :nope], k_nope)
                     + jnp.einsum("bhr,tr->hbt", q_rope, k_rope)) * scale
                s = jnp.where((pos_b[:, None] >= pos[None, :])[None], s,
                              -jnp.inf)
                ctx = jnp.einsum("hbt,thv->bhv",
                                 squash(jax.nn.softmax(s, axis=-1)), v)
                gate = squash(jax.nn.sigmoid(h_b @ w_gate))     # [B, H]
                return x_b + (ctx * gate[..., None]).reshape(B, H * dv) @ wo

            x1 = jax.lax.map(query_block, (
                x.reshape(T // B, B, -1), h.reshape(T // B, B, -1),
                pos.reshape(T // B, B))).reshape(T, -1)
        h2 = _rms(x1, p["ln2_s"], eps, lossy)
        if l < config["first_k_dense_replace"]:
            x = x1 + (jax.nn.silu(h2 @ _f32(p["dense_gate_w"]))
                      * (h2 @ _f32(p["dense_up_w"]))) @ _f32(p["dense_down_w"])
        else:
            x = x1 + expert_layer(config, {**p, **experts}, h2,
                                  variant=variant,
                                  offset=index["experts"][l] * n_here)
    return _rms(x, w["final_ln.scale"], eps, lossy), jnp.stack(states)


def reference_logits(config: dict, w: dict, ids, rows=None,
                     variant: str = ""):
    """ids [T] -> logits [len(rows), V] float32 at positions ``rows`` (all
    T when None: small models only): one sequence through the whole
    model (``variant``: one of ``VARIANTS``, a wrong one)."""
    import jax
    import jax.numpy as jnp

    ids = np.asarray(ids)
    n = ids.size
    padded = np.zeros(_padded(n), np.int32)
    padded[:n] = ids
    rows = np.arange(n) if rows is None else np.asarray(rows)
    with jax.default_matmul_precision("highest"):
        hidden, _ = _jit_hidden(config, variant)(w, jnp.asarray(padded), n)
        return _head(hidden[jnp.asarray(rows)], w["lm_head.w"])


_HIDDEN_JITS: dict = {}


def _jit_hidden(config: dict, variant: str = ""):
    import jax

    key = (id(config), variant)
    if key not in _HIDDEN_JITS:
        _HIDDEN_JITS[key] = jax.jit(
            lambda w, ids, n: _hidden(config, w, ids, n, variant))
    return _HIDDEN_JITS[key]


def _rows_logits(config: dict, w: dict, seq: np.ndarray, rows,
                 variant: str = "", states: bool = False):
    """Teacher-forced reference logits [len(rows), V] at positions ``rows``
    of ``seq``; the head runs over those rows only, a block at a time.
    With ``states``: -> (logits, every KDA layer's state after the last
    token of ``seq`` [layers, H, K, V])."""
    import jax
    import jax.numpy as jnp

    ids = np.zeros(_padded(seq.size), np.int32)
    ids[:seq.size] = seq
    rows = np.asarray(rows)
    with jax.default_matmul_precision("highest"):
        hidden, S = _jit_hidden(config, variant)(w, jnp.asarray(ids),
                                                 seq.size)
        logits = np.concatenate([
            np.asarray(_head(hidden[jnp.asarray(rows[i:i + _QUERY_BLOCK])],
                             w["lm_head.w"]))
            for i in range(0, rows.size, _QUERY_BLOCK)])
    return (logits, np.asarray(S)) if states else logits


def _replay_engine(config: dict, w: dict):
    """A twin of the engine ``build_engine`` last built for ``config``
    (its slots, page and chunk sizes, table width: the timed programs'
    shapes) on the SAME weight arrays, with the beam plane on (how logits
    leave an engine) and a pool of one table's pages."""
    import paddle_tpu as pt

    if id(config) not in _ENGINES:
        raise ValueError("reference_logit_gaps replays the checked requests "
                         "through a twin of the engine: build_engine first")
    e = dict(_ENGINES[id(config)])
    e["n_pages"] = -(-e["max_len"] // e["page_size"]) + 2
    scope = pt.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return _engine(spec_of(config), scope, e, beam_width=CHECK_TOPK)


#: the engine's scope array of the KDA states [layers, slots, H, K, V] and
#: the prefill feed that names a row's slot (``serving/generation.py``)
_STATE_ARRAY, _STATE_SLOT = "serving.state.KdaState", "serving.state_slot"


def mantissa_bits(S) -> List[int]:
    """S [layers, ...] float32 -> the explicit mantissa bits a layer's
    values USE (23 less the low bits that are zero in every one of them):
    23 for a state computed and kept in float32, 7 for one that passed
    through bfloat16, whatever the array's own dtype says."""
    bits = np.ascontiguousarray(S, np.float32).view(np.uint32)
    used = np.bitwise_or.reduce(bits.reshape(bits.shape[0], -1), axis=1)
    return [23 - min(int(u & -u).bit_length() - 1, 23) if u else 0
            for u in used.tolist()]


def served_errors(config: dict, w: dict, eng, prompt, new_tokens: int,
                  variants=("",)):
    """One request through ``eng`` (beam plane on): -> ({variant: [the
    largest error of the served top-k log-probs against that reference, a
    served position]}, the emitted sequence, the served positions,
    {variant: the state the request's SLOT holds when it ends (after the
    last token that was fed) against that reference's recurrence, a KDA
    layer: ``rel_err`` |S_engine - S| / |S| (Frobenius) and ``bits``
    |mantissa_bits(S_engine) - mantissa_bits(S)|})."""
    import jax

    slots = []
    run = eng.executor.run

    def note_slot(prog, feed=None, **kw):
        if feed and _STATE_SLOT in feed:
            slots.append(int(np.asarray(feed[_STATE_SLOT])[0]))
        return run(prog, feed=feed, **kw)

    eng.executor.run = note_slot
    try:
        calls, again = served_logprobs(eng, np.asarray(prompt), new_tokens)
    finally:
        eng.executor.run = run
    held = np.asarray(eng.scope.get(_STATE_ARRAY)[:, slots[0]], np.float32)
    served = np.asarray([p for p, _, _ in calls])
    errs, state = {}, {}
    for variant in variants:
        logits, S = _rows_logits(config, w, again[:-1], served, variant,
                                 states=True)
        ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        errs[variant] = [float(np.abs(v - ref[j][i]).max())
                         for j, (_, v, i) in enumerate(calls)]
        state[variant] = {
            "rel_err": [float(np.linalg.norm(a - b) / np.linalg.norm(b))
                        for a, b in zip(held, S)],
            "bits": [abs(a - b) for a, b in zip(mantissa_bits(held),
                                                mantissa_bits(S))]}
    return errs, again, served, state


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """What the serve driver holds a run to: it compares the LARGEST value
    returned with the mix's ``check.logit_gap_tol``, which for this family
    is ``CHECK_LOGPROB_TOL``. Three readings, each in that limit's terms
    (the first two as ``mla_moe_lm.reference_logit_gaps`` has them):

    1. the ``CHECK_LOGPROB_QUANTILE``-th percentile of the SERVED top-8
       log-prob error: every checked request ``(prompt_len, ids)`` is
       replayed, after the drain, through ``_replay_engine`` (chunked
       prefill through the chunked KDA form and the latent pages, then the
       decode kernel's recurrence from the slot's state), and the log-probs
       it serves at every chunk end and decode step are compared with the
       reference's teacher-forced full forward of the replayed sequence;
    2. on the tokens the TIMED engine emitted: how far below its
       position's best the reference puts each (the largest, scaled by
       ``CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL``);
    3. the precision of the STATE each replay leaves in its slot against
       the reference recurrence's (the largest difference in mantissa
       bits, scaled by ``CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL``).

    The readings go to stderr as one JSON line."""
    import json
    import sys

    eng = _replay_engine(config, w) if results else None
    errs: List[float] = []
    gaps: List[float] = []
    state_errs: List[List[float]] = []
    bits = same = 0
    for prompt_len, out in results:
        out = np.asarray(out)
        by, again, _, state = served_errors(config, w, eng, out[:prompt_len],
                                            out.size - prompt_len)
        errs.extend(by[""])
        state_errs.append(state[""]["rel_err"])
        bits = max(bits, *state[""]["bits"])
        same += np.array_equal(again, out)
        emitted = np.arange(prompt_len - 1, out.size - 1)
        mine = _rows_logits(config, w, out[:-1], emitted)
        gaps.extend((mine.max(axis=-1) - mine[np.arange(emitted.size),
                                              out[emitted + 1]]).tolist())
    if not errs:
        return np.zeros((0,), np.float32)
    held = float(np.percentile(errs, CHECK_LOGPROB_QUANTILE))
    worst = float(max(gaps))
    print(json.dumps({"kda_mla_moe_lm.check": {
        "quantile": CHECK_LOGPROB_QUANTILE, "limit": CHECK_LOGPROB_TOL,
        **{f"served_logprob_err_p{q}": float(np.percentile(errs, q))
           for q in (50, 80, 90, 95, 97, 99)},
        "served_logprob_err_max": float(max(errs)),
        "served_positions": len(errs), "emitted_gap_max": worst,
        "emitted_gap_limit": CHECK_EMITTED_GAP_TOL,
        "emitted_positions": len(gaps),
        "state_bits_differ_max": int(bits),
        "state_bits_limit": CHECK_STATE_BITS_TOL,
        "state_rel_err_by_layer_max": np.max(state_errs, axis=0).tolist(),
        "requests": len(results),
        "replays_equal_to_timed": int(same)}}), file=sys.stderr, flush=True)
    return np.asarray(
        [held, worst * CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL,
         bits * CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL], np.float32)


# ---------------------------------------------------------------------------
# the kernels: which device event is a call, and what a call has to move
# ---------------------------------------------------------------------------
#: ``pallas_call(name=...)`` of the KDA decode step
#: (``paddle_tpu/kernels/kda.KERNEL``)
KDA_KERNEL = "kda_decode_step"
#: tokens of one block of the chunked form (``kernels/kda.BLOCK``)
_KDA_BLOCK = 64
_STATE = re.compile(r"\bf32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")


def kda_decode_call(hlo_text: str) -> Optional[Dict[str, int]]:
    """None unless the device event is a call of the KDA decode kernel
    (told by its NAME); else the geometry off its state operand f32[L,
    slots, H, K, V]: ``slots``, ``heads``, ``k``, ``v``."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    name, opcode = parse_op(hlo_text)
    if opcode != "custom-call" or name.split(".")[0] != KDA_KERNEL:
        return None
    m = _STATE.search(strip_layouts(hlo_text).split("custom-call(", 1)[1])
    if m is None:
        return None
    return {"slots": int(m.group(2)), "heads": int(m.group(3)),
            "k": int(m.group(4)), "v": int(m.group(5))}


def kda_decode_cost(config: dict, slots: int, heads: int, k: int,
                    v: int) -> Dict[str, float]:
    """One call (one layer of one tick): every row's state tiles read once
    and written once, float32. ONLY those: the columns, the values and the
    read-outs (a thousandth of it) are left out, so a share computed from
    this cannot read above the truth."""
    return {"bytes": 2.0 * slots * heads * k * v * 4}


def kda_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of a KDA layer a device event belongs to: ``"step"``
    (the decode kernel), ``"state"`` (any other op with an operand or
    result shaped like the state [.., H, K, V] or a chunk's pairwise
    tensors [.., H, C, C(, K)]: the chunked form, the state's gather and
    scatter), ``"project"`` (the q | k | v, decay-gate and output-gate
    products and the convolution over [.., 3HK] / [.., HK] columns). None
    for everything else."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, _ = parse_op(hlo_text)
    if name.split(".")[0] == KDA_KERNEL:
        return "step"
    H, K = config["num_attention_heads"], config["head_dim"]
    d = config["hidden_size"]
    if re.search(rf"\[(\d+,)*{H},{K},{K}\]", text) \
            or re.search(rf"\[(\d+,)*{H},{_KDA_BLOCK},{_KDA_BLOCK}(,{K})?\]",
                         text):
        return "state"
    if re.search(rf"\[(\d+,)*({d},)?{3 * H * K}\]", text) \
            or f"[{d},{H * K}]" in text:
        return "project"
    return None


def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """As ``mla_moe_lm.moe_op``: ``"grouped_matmul"`` | ``"shared_expert"``
    | ``"route"`` | None, by the expert stacks' shapes (the expert layers
    are ``num_hidden_layers - first_k_dense_replace``)."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    L = config["num_hidden_layers"] - config["first_k_dense_replace"]
    held = config["num_experts"]
    E, d, f = (config["router_outputs"], config["hidden_size"],
               config["moe_intermediate_size"])
    pair = rf"({d},{f}|{f},{d})"
    if name.startswith("ragged-dot") or re.search(
            rf"\[({L},{held}|{L * held}|{held}),{pair}\]", text):
        return "grouped_matmul"
    if re.search(rf"\[({L},)?{pair}\]", text):
        return "shared_expert"
    if f"[{d},{E}]" in text or opcode in ("sort", "topk") \
            or name.startswith(("sort", "top-k", "topk")):
        return None if f",{config['vocab_size']}]" in text else "route"
    return None
