"""Family ``kda_gqa_moe_lm``: a Solar-Open2-shaped hybrid decoder — periods of
``gqa_interval + 1`` layers, the first softmax grouped-query attention WITHOUT
positions and with an elementwise sigmoid output gate (its K and V rows live
in page pools), the rest Kimi Delta Attention (KDA: the gated delta rule with
a per-channel decay, linear in the context, a fixed-size recurrent state a
sequence) in Kimi Linear's own form (unbounded softplus decay, low-rank decay
and gate projections) with beta in (0, 2); every layer a sigmoid router with a
selection bias over SwiGLU experts of which THIS chip holds a share, plus one
shared expert; pre-norm RMSNorm, no biases, untied head — served by
``serving.GenerationEngine(spec, ...)`` from ONE ``paddle_tpu.lm_spec.LMSpec``
(``spec_of``), with the yardstick's own pieces: the kernels' bytes, which
device op belongs to which layer, and a plain float32 ``jax.numpy`` reference
of the equations (x [T, d], one sequence):

    h = RMSNorm_1(x)                      RMSNorm(u) = u rsqrt(mean(u^2) + eps) w
  GQA layer (layers 0, 4, ..: ``gqa_layers``), H query / Hkv cached heads of dh:
    q = h W_q [T, H, dh];  k = h W_k, v = h W_v [T, Hkv, dh]     no rotation (``use_rope`` false), no QK-norm
    s[n,i,j] = q[i,n] . k[j, n // (H / Hkv)] / sqrt(dh),  j <= i
    x' = x + [softmax_j(s) v * sigmoid(h W_g)] W_o               gate per CHANNEL [d, H dh] (``use_gqa_gate``)
  KDA layer (arXiv:2510.26692), H heads of K = V = head_dim:
    [q~ | k~ | v~] = h W_qkv;  c(u)_t = silu(sum_i w_i u_{t-3+i})  (4 taps, zero history)
    q = l2norm(c(q~)) K^-1/2;  k = l2norm(c(k~));  v = c(v~)       per head
    g = -exp(A_log_h) softplus(h W_fa W_fb + dt_bias)              per key channel, rank 128 (``kda_use_full_proj`` false)
    beta = 2 sigmoid(h w_beta_h)                                   (``kda_allow_neg_eigval``)
    S' = diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t
    x' = x + [RMSNorm_head(o_t) * sigmoid(h W_ga W_gb + b_g)] W_o  S float32, S_0 = 0
  FFN (every layer: ``first_k_dense_replace`` 0), h2 = RMSNorm_2(x'):
    s = sigmoid(h2 W_r) over ALL experts (float32); S = top-k of s + b;  w_e = s_e / sum_S s * routed_scaling_factor
    x'' = x' + E_shared(h2) + sum_{e in S, e HELD} w_e E_e(h2)    E(u) = (silu(u W_g) * (u W_u)) W_d
    logits = RMSNorm_f(x_L) W_head

What the absent experts would add is left out, program and reference alike
(the ``model-configs`` guide, section 4); ``expert_layer(.., held=)`` gives
any share, so a test can add the shares up to the uncut layer.

The reference has no cache, no state array, no snapshot, no kernel, no
chunked form, no sort and no grouped matmul: KDA is the token-by-token
recurrence under ``lax.scan``, the softmax layer scores every key in query
blocks, every HELD expert is applied densely and masked by the top-k set. It
reads the SAME stored weights as the program and runs under
``jax.default_matmul_precision("highest")``.

Every reading the published keys do not settle is under ``assumed`` in the
configuration file, with the key it rests on.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.kda_mla_moe_lm import (  # noqa: F401 - same pieces
    KDA_KERNEL, _bf16, kda_decode_call, kda_decode_cost, kda_scan,
    mantissa_bits)
from benchmark.families.moe_lm import (  # noqa: F401 - the family's surface
    draw_prompt_ids, grouped_matmul_cost, served_logprobs)
from benchmark.families.window_moe_lm import (  # the same plain pieces
    _QUERY_BLOCK, _f32, _head, _padded, _rms)

ITEM = "tokens"
_EXPERT_BLOCK = 4       # experts upcast to float32 at a time
_TOKEN_BLOCK = 1024     # tokens that go through the experts together
_KDA_TOKENS = 2048      # tokens whose q | k | v | g a KDA layer holds at once
_L2_EPS = 1e-6
#: WRONG models, one fault each, that the check and the tier-1 tests must
#: tell from the right one: ``reference_logits(.., variant=name)``
VARIANTS = {
    "no_gqa_gate": "the softmax layer's output gate left out",
    "rope_on_gqa": "q and k of the softmax layer rotated (theta, half "
                   "pairing) where the model has no positions",
    "beta_not_doubled": "beta = sigmoid(.), in (0, 1): no negative "
                        "eigenvalue",
    "bounded_decay": "g = -5 sigmoid(exp(A_log) a): the squashed decay of "
                     "another family",
    "no_decay": "g = 0: the state never forgets",
    "no_delta": "S = S' + beta k v^T: no (v - S'^T k) correction",
    "no_gate_bias": "the KDA output gate without its bias",
    "softmax_router": "softmax over the logits instead of sigmoid",
    "no_router_bias": "the top-k taken on s, not s + b",
    "no_shared_expert": "the always-on expert left out",
    "bf16_stated_f32": "norms, router scores and the gates rounded to "
                       "bfloat16 where the configuration says float32",
    "bf16_state": "the recurrent state kept in bfloat16 between tokens",
}

#: THE LIMIT on the served top-8 log-prob error (``reference_logit_gaps``;
#: the mix's ``check.logit_gap_tol`` IS this number), the
#: ``CHECK_LOGPROB_QUANTILE``-th percentile over the positions of BOTH
#: replays of every checked request (cold, and from a snapshot). The
#: readings it was set from are in the mix's ``logit_gap_tol_why`` and
#: PERF.md section 6 (my chip runs, PR 45).
CHECK_LOGPROB_QUANTILE = 95
CHECK_LOGPROB_TOL = 0.008
#: ... on the recurrent state's precision, in mantissa bits the slot's
#: state USES against the reference recurrence's (23 for float32, 7 for a
#: state that passed through bfloat16; ``kda_mla_moe_lm`` says why bits and
#: not a distance): the right engine reads 0, a bfloat16 state 16
CHECK_STATE_BITS_TOL = 8
#: ... and on how far below its position's best the reference puts a token
#: the TIMED engine emitted (a request answered with another's tokens, or
#: from another prefix's state, reads several units)
CHECK_EMITTED_GAP_TOL = 0.5
#: ... and on the RESTORE: |S_hit - S_cold| / |S_cold| (Frobenius, the
#: worst KDA layer) between the state a request's slot holds when it
#: entered at a snapshot and when the SAME engine served it cold: in the
#: TIMED engine after the drain (``restore_under_traffic``: its slots, its
#: index as the window left it, the rows it took under traffic; the state
#: at the prompt's end) and in the twin (``replay_twice``: at the
#: request's end). A snapshot row is a copy and both are chunked alike, so
#: the right engine reads exactly 0 on the chip as on the CPU (my chip
#: runs, PR 45: the two states equal to the last bit); a snapshot one
#: boundary off (the state of 4096 tokens earlier in the same preamble)
#: reads 0.004-0.05 after the 350-750 tokens that follow the restore: 1e-3
#: is 4 x under the smallest.
#: The served log-probs cannot see it: a state one boundary off moved
#: their p80 from 0.0024 to 0.0027 (random weights forget: most channels
#: keep under a thousandth of a state over 4096 tokens)
CHECK_RESTORE_STATE_TOL = 1e-3
CHECK_TOPK = 8


def pattern_of(config: dict) -> Tuple[str, ...]:
    """One period: ``gqa_layers`` are 0, p, 2p, .. with p = gqa_interval +
    1; the layers between are KDA."""
    p = config["gqa_interval"] + 1
    L = config["num_hidden_layers"]
    if [l for l in config["gqa_layers"] if l < L] != list(range(0, L, p)):
        raise ValueError("gqa_layers is not every (gqa_interval + 1)-th "
                         "layer from 0")
    return ("gqa",) + ("kda",) * (p - 1)


def held_of(config: dict) -> Tuple[int, int]:
    """(first, count): the routed experts this chip holds."""
    return config["assumed"]["experts_first"], config["n_routed_experts"]


def spec_of(config: dict):
    """The program's model spec for this configuration: a tree whose spec
    lacks the ``gqa`` kind, the KDA variants or the channel gate fails
    here, at once, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a, lin = config["assumed"], config["linear_attn_config"]
    first, count = held_of(config)
    E = config["router_outputs"]
    if config["use_rope"] or config["first_k_dense_replace"] \
            or lin["num_heads"] != config["num_attention_heads"]:
        raise ValueError("kda_gqa_moe_lm: a softmax layer without positions, "
                         "no leading dense layer, one head count")
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], use_rope=True,     # = no learned table
        max_len=a["max_len"], norm="rms_norm",
        norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), rope_pairing="half",
        layer_pattern=pattern_of(config),
        attn_gate="channel" if config["use_gqa_gate"] else "none",
        kda_head_dim=lin["head_dim"], kda_conv=lin["short_conv_kernel_size"],
        kda_decay="softplus", kda_neg_eigval=config["kda_allow_neg_eigval"],
        kda_proj_rank=0 if config["kda_use_full_proj"]
        else a["kda_proj_rank"],
        ffn="swiglu_moe", num_experts=E,
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["n_shared_experts"] * config["moe_intermediate_size"],
        experts_held=None if (first, count) == (0, E) else (first, count),
        norm_topk_prob=config["norm_topk_prob"],
        routed_scale=float(config["routed_scaling_factor"]),
        router_score="sigmoid", router_bias=True,
        bias=False, param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def seeded_vectors(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """The planes a startup program leaves at a constant and a checkpoint
    does not (``assumed``: ``router_bias_std``, ``kda_gate_values``): the
    router's selection bias b ~ N(0, ``router_bias_std``^2); A_log = ln
    U(1, 16) a head and dt_bias = softplus^-1(dt), dt log-uniform in
    (0.001, 0.1), a channel (the gated-delta-rule families' own
    initialisation: a token keeps exp(-A dt) between 0.2 and 0.999 of a
    channel); the output gate's bias ~ U(-1, 1)."""
    spec = spec_of(config)
    rng = np.random.default_rng([int(seed), 0x4b4441])
    H, K = spec.num_heads, spec.kda_head_dim
    Lk, Le = spec.plane_layers("kda_a_log"), spec.plane_layers("router_b")
    std = config["assumed"]["router_bias_std"]
    dt = np.exp(rng.uniform(np.log(0.001), np.log(0.1), (Lk, H * K)))
    out = {
        "router_b": rng.normal(0.0, 1.0, (Le, spec.num_experts)) * std,
        "kda_a_log": np.log(rng.uniform(1.0, 16.0, (Lk, H))),
        "kda_dt_bias": dt + np.log(-np.expm1(-dt)),     # softplus^-1(dt)
    }
    if spec.kda_proj_rank:
        out["kda_gate_b"] = rng.uniform(-1.0, 1.0, (Lk, H * K))
    return out


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
    eng = _engine(spec, scope, mix["engine"], **engine_kw)
    _ENGINES[id(config)] = (mix["engine"], eng)
    return eng, [exe, eng.executor]


def _engine(spec, scope, e: dict, **engine_kw):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], max_seq_len=e["max_len"],
        prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None,
        snapshot_stride=e.get("snapshot_stride", 0),
        n_snapshots=e.get("n_snapshots", 0),
        mask_plane=bool(e.get("mask_plane", 1)), **engine_kw)


#: id(configuration) -> (the mix's ``engine`` section, the engine) of the
#: last ``build_engine``: the check replays the checked requests through
#: THAT engine (the timed one) and, for the logits it cannot emit, through
#: a twin built from the section
_ENGINES: dict = {}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The model's parameters by the fixed names the layout gives them, as
    stored (nothing is copied or cast)."""
    names = ["tok_emb", "final_ln.scale", "lm_head.w"] + sorted(
        n for n in scope.keys() if n.startswith("lm_stack.stack_"))
    return {name: scope.get(name) for name in names}


def _rope_half(x, pos, theta: float):
    """x [T, H, dh] at positions pos [T]: pair (i, i + dh/2) rotates by
    pos * theta^(-2i/dh) (only the ``rope_on_gqa`` variant calls it)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def router_choice(config: dict, h2, router_w, router_b, variant: str = ""):
    """h2 [T, d] float32 -> (scores s [T, E], chosen [T, E] bool): the
    router of one layer over ALL its outputs, one group."""
    import jax

    def squash(t):
        return _bf16(t) if variant == "bf16_stated_f32" else t

    logits = squash(h2 @ _f32(router_w))
    s = squash(jax.nn.softmax(logits, axis=-1)
               if variant == "softmax_router" else jax.nn.sigmoid(logits))
    c = s if variant == "no_router_bias" else s + _f32(router_b)
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


def _low_rank(p: dict, h, key: str):
    """h through the decay's / the gate's projection: one [d, HK] matrix
    (``kda_use_full_proj``) or the rank-r pair."""
    if key + "_w" in p:
        return h @ _f32(p[key + "_w"])
    return (h @ _f32(p[key + "_down_w"])) @ _f32(p[key + "_up_w"])


def kda_inputs(config: dict, p: dict, h, variant: str = "", history=None):
    """h [T, d] (normed, float32) -> q, k, v, g [T, H, K], beta [T, H] of
    one KDA layer and the convolutions' history after these tokens (the
    last taps - 1 rows of [q~ | k~ | v~]); ``history`` None: a zero
    history (the sequence starts here)."""
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
    if variant == "no_decay":
        g = jnp.zeros_like(a)
    elif variant == "bounded_decay":
        g = -5.0 * jax.nn.sigmoid(rate * a)
    else:
        g = -rate * squash(jax.nn.softplus(a))
    beta = squash(jax.nn.sigmoid(h @ _f32(p["kda_beta_w"])))
    if config["kda_allow_neg_eigval"] and variant != "beta_not_doubled":
        beta = 2.0 * beta
    return l2(q) * K ** -0.5, l2(k), v, g, beta, u[T:]


def kda_layer(config: dict, p: dict, h, real, variant: str = ""):
    """One KDA layer's attention half on h [T, d] (normed): -> (what it
    adds to the stream [T, d], the state after the last ``real`` token [H,
    K, V]). The recurrence runs token by token (``kda_scan``); only the
    float32 q | k | v | g of ``_KDA_TOKENS`` tokens exist at a time (16384
    tokens of them are 2.7 GB), the state and the convolutions' history
    carried from one stretch to the next."""
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
        gate = _low_rank(p, h_b, "kda_gate")
        if "kda_gate_b" in p and variant != "no_gate_bias":
            gate = gate + _f32(p["kda_gate_b"])
        gate = jax.nn.sigmoid(gate)
        return (S, hist), (o * (_bf16(gate) if lossy else gate)) @ _f32(
            p["kda_out_w"])

    (S, _), y = jax.lax.scan(
        stretch, (jnp.zeros((H, K, K), jnp.float32),
                  jnp.zeros((taps - 1, 3 * H * K), jnp.float32)),
        (h.reshape(T // Bk, Bk, -1), real.reshape(T // Bk, Bk, 1)))
    return y.reshape(T, -1), S


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

    blk = spec_of(config).block
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, eps = config["head_dim"], config["rms_norm_eps"]
    L = config["num_hidden_layers"]
    kinds = pattern_of(config)
    index = blk.group_index(L)
    T = ids.shape[0]
    B = min(_QUERY_BLOCK, T)
    if T % B:
        raise ValueError(f"{T} tokens are not whole blocks of {B}")
    pos = jnp.arange(T)
    real = (pos < n)[:, None]
    states = []
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
            y, S = kda_layer(config, p, h, real, variant)
            states.append(S)
            x1 = x + y
        else:
            kv = h @ _f32(p["gqa_qkv_w"][:, H * dh:])   # columns q | k | v
            k = kv[:, :Hkv * dh].reshape(T, Hkv, dh)
            v = kv[:, Hkv * dh:].reshape(T, Hkv, dh)
            if variant == "rope_on_gqa":
                k = _rope_half(k, pos, float(config["rope_theta"]))
            w_q, wo = _f32(p["gqa_qkv_w"][:, :H * dh]), _f32(p["gqa_out_w"])
            w_gate = None if variant == "no_gqa_gate" or "gqa_gate_w" \
                not in p else _f32(p["gqa_gate_w"])

            def query_block(blk_in, k=k, v=v, w_q=w_q, wo=wo, w_gate=w_gate):
                x_b, h_b, pos_b = blk_in
                q = (h_b @ w_q).reshape(B, H, dh)
                if variant == "rope_on_gqa":
                    q = _rope_half(q, pos_b, float(config["rope_theta"]))
                q = q.reshape(B, Hkv, H // Hkv, dh)     # head n = (n // G, n % G)
                s = jnp.einsum("bngd,tnd->ngbt", q, k) * dh ** -0.5
                s = jnp.where((pos_b[:, None] >= pos[None, :])[None, None],
                              s, -jnp.inf)
                ctx = jnp.einsum("ngbt,tnd->bngd",
                                 squash(jax.nn.softmax(s, axis=-1)),
                                 v).reshape(B, H * dh)
                if w_gate is not None:
                    ctx = ctx * squash(jax.nn.sigmoid(h_b @ w_gate))
                return x_b + ctx @ wo

            x1 = jax.lax.map(query_block, (
                x.reshape(T // B, B, -1), h.reshape(T // B, B, -1),
                pos.reshape(T // B, B))).reshape(T, -1)
        h2 = _rms(x1, p["ln2_s"], eps, lossy)
        x = x1 + expert_layer(config, {**p, **experts}, h2, variant=variant,
                              offset=index["experts"][l] * n_here)
    return _rms(x, w["final_ln.scale"], eps, lossy), jnp.stack(states)


def reference_logits(config: dict, w: dict, ids, rows=None,
                     variant: str = ""):
    """ids [T] -> logits [len(rows), V] float32 at positions ``rows`` (all
    T when None: small models only): one sequence through the whole model
    (``variant``: one of ``VARIANTS``, a wrong one)."""
    return _rows_logits(config, w, np.asarray(ids),
                        np.arange(len(ids)) if rows is None else rows,
                        variant)


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
    (its page, chunk and table sizes, its snapshot stride: the timed
    prefill program's shapes) on the SAME weight arrays, with the beam
    plane on (how logits leave an engine), 8 slots at most (a slot's state
    is 13 MB beside a timed engine that fills the chip), pages for two
    tables (a cold replay's, which the index keeps, and the hit's own) and
    a snapshot row a boundary of one table."""
    import paddle_tpu as pt

    e = dict(_ENGINES[id(config)][0])
    table = -(-e["max_len"] // e["page_size"])
    e["slots"] = min(e["slots"], 8)
    e["n_pages"] = 2 * table + 2
    if e.get("n_snapshots"):
        e["n_snapshots"] = max(table // e["snapshot_stride"], 1)
    scope = pt.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return _engine(spec_of(config), scope, e, beam_width=CHECK_TOPK)


#: the engine's scope array of the KDA states [layers, slots, H, K, V] and
#: the prefill feed that names a row's slot (``serving/generation.py``)
_STATE_ARRAY, _STATE_SLOT = "serving.state.KdaState", "serving.state_slot"


def served(eng, prompt, new_tokens: int, logprobs: bool = True):
    """One request through ``eng``: -> (the served top-k log-probs
    [(position, values, ids)] at every chunk end and decode step (an engine
    with the beam plane on; ``logprobs`` False: none, any engine), the
    emitted sequence, the state the request's SLOT holds when it ends
    [layers, H, K, V])."""
    slots = []
    run = eng.executor.run

    def note_slot(prog, feed=None, **kw):
        if feed and _STATE_SLOT in feed:
            slots.append(int(np.asarray(feed[_STATE_SLOT])[0]))
        return run(prog, feed=feed, **kw)

    eng.executor.run = note_slot
    try:
        if logprobs:
            calls, again = served_logprobs(eng, np.asarray(prompt),
                                           new_tokens)
        else:
            calls, again = [], np.asarray(eng.generate_all(
                [np.asarray(prompt)], max_new_tokens=new_tokens)[0])
    finally:
        eng.executor.run = run
    held = np.asarray(eng.scope.get(_STATE_ARRAY)[:, slots[0]], np.float32)
    return calls, again, held


def misplace_snapshots(eng) -> None:
    """THE FAULT the restore's reading must catch, planted by the tools
    and tests that show it does: every snapshot row moved by one, so that
    a hit starts from another boundary's state."""
    import jax.numpy as jnp

    for _, name, _, _ in eng._snapshots:
        eng.scope.set(name, jnp.roll(eng.scope.get(name), 1, axis=1))


_MOVED = ("state_snapshots_restored", "prefix_hit_tokens",
          "state_snapshot_cutback_tokens")


def restore_under_traffic(eng, prompts) -> List[dict]:
    """The restore in ``eng`` ITSELF: the timed engine after the drain,
    with its slot count, its index as the window left it and the snapshot
    rows it took under traffic (by whichever slot came first to a
    boundary, waited for or adopted). Every prompt is prefilled to its
    first answer token TWICE: with the index as it stands (a prompt whose
    prefix the engine still holds enters at a snapshot row of the
    window's), then, once all have been, COLD (the index emptied before
    each). -> a prompt: what the first moved (``_MOVED``), the state its
    slot held at the prompt's end against the cold one's
    (``state_vs_cold``: |S - S_cold| / |S_cold| a KDA layer; a row that is
    stale, misplaced or overwritten under its pin does not read 0), the
    mantissa bits that state uses, and whether both gave the same first
    token. An engine without a snapshot pool: nothing."""
    if eng.prefix_index is None or not eng._snapshots:
        return []

    def counted():
        return dict(eng.metrics.snapshot()["counters"])

    warm = []
    for prompt in prompts:
        c0 = counted()
        _, again, held = served(eng, prompt, 1, logprobs=False)
        c1 = counted()
        warm.append((again, held,
                     {k: c1.get(k, 0) - c0.get(k, 0) for k in _MOVED}))
    out = []
    for prompt, (again, held, moved) in zip(prompts, warm):
        eng.prefix_index.clear()
        _, cold_again, cold = served(eng, prompt, 1, logprobs=False)
        out.append({
            **moved, "bits": mantissa_bits(held),
            "state_vs_cold": [float(np.linalg.norm(a - b)
                                    / np.linalg.norm(b))
                              for a, b in zip(held, cold)],
            "first_token_equal": bool(again[-1] == cold_again[-1])})
    return out


def errors_of(calls, logits_at: Dict[int, np.ndarray]) -> List[float]:
    """The largest error of each served position's top-k log-probs against
    the reference's log-softmax row at that position."""
    out = []
    for pos, values, ids in calls:
        row = logits_at[pos]
        ref = row - np.logaddexp.reduce(row)
        out.append(float(np.abs(values - ref[ids]).max()))
    return out


def state_errors(held, S) -> Dict[str, list]:
    """The slot's state against the reference recurrence's, a KDA layer:
    relative error (Frobenius) and the difference in mantissa bits used."""
    return {"rel_err": [float(np.linalg.norm(a - b) / np.linalg.norm(b))
                        for a, b in zip(held, S)],
            "bits": [abs(a - b) for a, b in zip(mantissa_bits(held),
                                                mantissa_bits(S))]}


def replay_twice(config: dict, w: dict, eng, prompt, new_tokens: int,
                 variants=("",), between=None):
    """One request through ``eng`` TWICE: cold (the index emptied first),
    then again while the index holds the first replay's pages and
    snapshots, so that the second enters at a snapshot boundary and its
    first chunk starts from the snapshot row (``between``: a fault to
    plant between the two, for the tools and tests that must see a wrong
    restore caught). Both are compared with ONE teacher-forced float32
    forward of the sequence from token 0 a model of ``variants``. ->
    {"cold" | "hit": {"errs": {variant: [..]}, "state": {variant: ..},
    "restored", "hit_tokens", "again", "logits_at": {position: the first
    variant's reference logits there}, "ref_bits": the mantissa bits that
    reference's states use}; "hit" also holds
    ``state_vs_cold`` (|S_hit - S_cold| / |S_cold| a KDA layer) and
    ``logprob_vs_cold`` (the largest difference of a served log-prob)."""
    out, ref, seq, helds, served_at = {}, {}, None, {}, {}
    for name in ("cold", "hit"):
        if name == "cold" and eng.prefix_index is not None:
            eng.prefix_index.clear()
        if name == "hit" and between is not None:
            between(eng)
        c0 = dict(eng.metrics.snapshot()["counters"])
        calls, again, held = served(eng, prompt, new_tokens)
        c1 = eng.metrics.snapshot()["counters"]
        helds[name] = held
        served_at[name] = {p: v for p, v, _ in calls}
        if seq is None or not np.array_equal(again, seq):
            seq = again
            rows = np.asarray(sorted({p for p, _, _ in calls}))
            for variant in variants:
                lg, S = _rows_logits(config, w, seq[:-1], rows, variant,
                                     states=True)
                ref[variant] = (dict(zip(rows.tolist(), lg)), S)
        out[name] = {
            "errs": {v: errors_of(calls, ref[v][0]) for v in variants},
            "state": {v: state_errors(held, ref[v][1]) for v in variants},
            "again": again, "logits_at": ref[variants[0]][0],
            "ref_bits": mantissa_bits(ref[variants[0]][1]),
            "restored": c1.get("state_snapshots_restored", 0)
            - c0.get("state_snapshots_restored", 0),
            "hit_tokens": c1.get("prefix_hit_tokens", 0)
            - c0.get("prefix_hit_tokens", 0)}
    # the restore itself: what the hit left in the slot, and what it
    # served, against the cold replay of the same request
    out["hit"]["state_vs_cold"] = [
        float(np.linalg.norm(a - b) / np.linalg.norm(b))
        for a, b in zip(helds["hit"], helds["cold"])]
    out["hit"]["logprob_vs_cold"] = max(
        (float(np.abs(v - served_at["cold"][p]).max())
         for p, v in served_at["hit"].items() if p in served_at["cold"]),
        default=0.0)
    return out


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """What the serve driver holds a run to: it compares the LARGEST value
    returned with the mix's ``check.logit_gap_tol``, which for this family
    is ``CHECK_LOGPROB_TOL``. Four readings, each in that limit's terms;
    (2), (3) and (4) are taken on the TIMED engine (``build_engine``'s
    last), (1) on a twin, since the timed engine emits no logits:

    1. the ``CHECK_LOGPROB_QUANTILE``-th percentile of the SERVED top-8
       log-prob error: every checked request ``(prompt_len, ids)`` is
       replayed, after the drain, through ``_replay_engine`` TWICE
       (``replay_twice``: cold, then from the snapshot the cold replay
       left, so that restore lies on the compared path), and the
       log-probs both serve at every chunk end and decode step are
       compared with the reference's teacher-forced full forward from
       token 0;
    2. on the tokens the timed engine EMITTED in the window: how far below
       its position's best the reference puts each (the largest, scaled by
       ``CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL``);
    3. the precision of the STATE: what each checked prompt leaves in a
       slot of the timed engine, and each replay in the twin's, against
       the reference recurrence's (the largest difference in mantissa
       bits, scaled by ``CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL``);
    4. the RESTORE: the state a checked prompt leaves in the timed engine
       when it enters at a snapshot the window took against the same
       prompt served cold by that engine (``restore_under_traffic``), and
       the twin's snapshot replay against its cold one
       (``CHECK_RESTORE_STATE_TOL``: the right engine reads 0, a stale or
       misplaced snapshot does not), scaled by ``CHECK_LOGPROB_TOL /
       CHECK_RESTORE_STATE_TOL``.

    The readings go to stderr as one JSON line, with what the timed
    engine counted of its snapshots up to the drain's end."""
    import json
    import sys
    import time

    if not results:
        return np.zeros((0,), np.float32)
    if id(config) not in _ENGINES:
        raise ValueError("reference_logit_gaps replays the checked requests "
                         "through the timed engine and a twin of it: "
                         "build_engine first")
    timed = _ENGINES[id(config)][1]
    run_counted = {k: v for k, v in timed.metrics.snapshot()[
        "counters"].items() if k.startswith(("state_snapshot",
                                             "state_prefix"))}
    t0 = time.monotonic()
    under_traffic = restore_under_traffic(
        timed, [np.asarray(out)[:n] for n, out in results])
    t1 = time.monotonic()
    eng = _replay_engine(config, w)
    errs = {"cold": [], "hit": []}
    gaps: List[float] = []
    state_errs: List[List[float]] = []
    bits = same = restored = hit_equal = 0
    restore = logprob_restore = 0.0
    for (prompt_len, out), timed_r in zip(
            results, under_traffic or [None] * len(results)):
        out = np.asarray(out)
        twice = replay_twice(config, w, eng, out[:prompt_len],
                             out.size - prompt_len)
        for name, r in twice.items():
            errs[name].extend(r["errs"][""])
            state_errs.append(r["state"][""]["rel_err"])
            bits = max(bits, *r["state"][""]["bits"])
        if timed_r is not None:
            bits = max(bits, *(abs(a - b) for a, b in zip(
                timed_r["bits"], twice["cold"]["ref_bits"])))
        restored += twice["hit"]["restored"]
        restore = max(restore, *twice["hit"]["state_vs_cold"])
        logprob_restore = max(logprob_restore,
                              twice["hit"]["logprob_vs_cold"])
        same += np.array_equal(twice["cold"]["again"], out)
        hit_equal += np.array_equal(twice["cold"]["again"],
                                    twice["hit"]["again"])
        emitted = np.arange(prompt_len - 1, out.size - 1)
        cold = twice["cold"]
        if np.array_equal(cold["again"], out):
            # the replay emitted the timed tokens: its served positions
            # (the last chunk's end and every decode step) ARE these rows
            mine = np.stack([cold["logits_at"][p] for p in emitted.tolist()])
        else:
            mine = _rows_logits(config, w, out[:-1], emitted)
        gaps.extend((mine.max(axis=-1) - mine[np.arange(emitted.size),
                                              out[emitted + 1]]).tolist())
    both = errs["cold"] + errs["hit"]
    held = float(np.percentile(both, CHECK_LOGPROB_QUANTILE))
    worst = float(max(gaps))
    timed_restore = max((v for r in under_traffic
                         for v in r["state_vs_cold"]), default=0.0)
    print(json.dumps({"kda_gqa_moe_lm.check": {
        "quantile": CHECK_LOGPROB_QUANTILE, "limit": CHECK_LOGPROB_TOL,
        **{f"served_logprob_err_{name}_p{q}": float(np.percentile(e, q))
           for name, e in errs.items() for q in (50, 80, 90, 95, 99)},
        **{f"served_logprob_err_p{q}": float(np.percentile(both, q))
           for q in (90, 95)},
        "served_logprob_err_max": float(max(both)),
        "served_positions": len(both), "emitted_gap_max": worst,
        "emitted_gap_limit": CHECK_EMITTED_GAP_TOL,
        "emitted_positions": len(gaps),
        "state_bits_differ_max": int(bits),
        "state_bits_limit": CHECK_STATE_BITS_TOL,
        "state_rel_err_by_layer_max": np.max(state_errs, axis=0).tolist(),
        "timed_engine_slots": timed.slots,
        "timed_restore_state_vs_cold_max": timed_restore,
        "timed_prompts_from_a_snapshot": sum(
            r["state_snapshots_restored"] > 0 for r in under_traffic),
        "timed_prefix_hit_tokens": sum(
            r["prefix_hit_tokens"] for r in under_traffic),
        "timed_cutback_tokens": sum(
            r["state_snapshot_cutback_tokens"] for r in under_traffic),
        "timed_first_tokens_equal_to_cold": sum(
            r["first_token_equal"] for r in under_traffic),
        "timed_engine_counted_to_the_drain": run_counted,
        "restore_state_vs_cold_max": restore,
        "restore_state_limit": CHECK_RESTORE_STATE_TOL,
        "restore_logprob_vs_cold_max": logprob_restore,
        "requests": len(results), "replays_from_a_snapshot": int(restored),
        "hit_replays_equal_to_cold": int(hit_equal),
        "replays_equal_to_timed": int(same),
        "timed_engine_s": round(t1 - t0, 1),
        "twin_and_reference_s": round(time.monotonic() - t1, 1)}}),
        file=sys.stderr, flush=True)
    return np.asarray(
        [held, worst * CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL,
         bits * CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL,
         max(restore, timed_restore) * CHECK_LOGPROB_TOL
         / CHECK_RESTORE_STATE_TOL], np.float32)


# ---------------------------------------------------------------------------
# the kernels: which device event is a call, and what a call has to move
# (``kda_decode_call`` / ``kda_decode_cost``: the KDA decode kernel's, as
# ``kda_mla_moe_lm`` has them: told by name, the geometry off the state
# operand; the softmax layer's ``paged_attention_decode`` call is
# ``families/paged_attention``'s, the one-kind cells' reader reads it)
# ---------------------------------------------------------------------------
#: tokens of one block of the chunked form (``kernels/kda.BLOCK``)
_KDA_BLOCK = 64


def kda_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of a KDA layer a device event belongs to: ``"step"``
    (the decode kernel), ``"state"`` (any other op with an operand or
    result shaped like the state or a snapshot row [.., H, K, V] or a
    chunk's pairwise tensors [.., H, C, C(, K)]: the chunked form, the
    state's gather and scatter, a snapshot's copy), ``"project"`` (the q |
    k | v product, the convolution and its history over 3HK columns, and
    the rank-r decay / gate pairs [.., d, r] [.., r, HK]). None for
    everything else. Two shapes of this configuration are NOT KDA's alone:
    [d, HK] is also the softmax layer's query and gate projection (never
    told), and 3HK = 24576 is also this chip's slice of the VOCABULARY:
    the head's [d, V] weight and the logits / sampling plane's [rows, V]
    are 2-D float32 / int32 where KDA's tensors of that width carry the
    stack's or the taps' axis ([Lk, d, 3HK], [rows, taps, 3HK]) or are
    the bf16 history rows [rows * (taps - 1), 3HK] (read off the cell's
    traced ops, my chip run, PR 45: told the other way, the head and the
    sampling search were a sixth of ``project``)."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, _ = parse_op(hlo_text)
    if name.split(".")[0] == KDA_KERNEL:
        return "step"
    lin = config["linear_attn_config"]
    H, K, d = lin["num_heads"], lin["head_dim"], config["hidden_size"]
    r, W = config["assumed"]["kda_proj_rank"], 3 * H * K
    if re.search(rf"\[(\d+,)*{H},{K},{K}\]", text) \
            or re.search(rf"\[(\d+,)*{H},{_KDA_BLOCK},{_KDA_BLOCK}(,{K})?\]",
                         text):
        return "state"
    if re.search(rf"\[(\d+,){{2,}}{W}\]", text) \
            or re.search(rf"bf16\[(?!{d},)\d+,{W}\]", text) \
            or re.search(rf"\[(\d+,)*({d},{r}|{r},{H * K})\]", text):
        return "project"
    return None


def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """As ``mla_moe_lm.moe_op``: ``"grouped_matmul"`` | ``"shared_expert"``
    | ``"route"`` | None, by the expert stacks' shapes (every layer is an
    expert layer; ``n_routed_experts`` counts the experts HELD)."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    L, held = config["num_hidden_layers"], config["n_routed_experts"]
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
