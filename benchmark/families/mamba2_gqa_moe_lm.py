"""Family ``mamba2_gqa_moe_lm``: a Nemotron-H-shaped hybrid decoder — every
layer is ONE residual step ``x <- x + F(RMSNorm_l(x))`` and F is a mixer OR a
feed-forward alone, by the letter of ``hybrid_override_pattern``: ``M`` a
Mamba-2 state-space layer (a fixed-size recurrent state a sequence), ``*``
softmax grouped-query attention without positions (its K and V rows live in
page pools), ``E`` a sigmoid router with a selection bias over UNGATED relu^2
experts that work in a LATENT (one down-projection before them, one
up-projection after them, shared by all) of which THIS chip holds a share,
beside one shared expert at the model's width; RMSNorm, no biases, untied head
— served by ``serving.GenerationEngine(spec, ...)`` from ONE
``paddle_tpu.lm_spec.LMSpec`` (``spec_of``), with the yardstick's own pieces:
the kernels' bytes, which device op belongs to which layer, and a plain float32
``jax.numpy`` reference of the equations (x [T, d], one sequence; h =
RMSNorm_l(x), RMSNorm(u) = u rsqrt(mean(u^2) + eps) w):

  M, Mamba-2 (H heads of P, G groups, N state dimensions, ``conv_kernel`` taps):
    [z | xBC | dt] = h W_in                                        (H P | H P + 2 G N | H, no bias)
    xBC_t <- silu(sum_i w_i xBC_{t-3+i} + b)                       depthwise, zero history (``use_conv_bias``)
    x [H, P], B, C [G, N] = split(xBC)                             head n reads group n // (H / G)
    dt = softplus(dt + dt_bias_n);  a_t = exp(-exp(A_log_n) dt_t)
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D_n x_t   S [P, N] a head, float32, S_0 = 0
    F = GroupRMSNorm(y * silu(z)) W_out                            gate BEFORE the norm, groups of H P / G channels
  *, attention, H query / Hkv cached heads of dh, no rotation:
    s[n,i,j] = q[i,n] . k[j, n // (H / Hkv)] / sqrt(dh),  j <= i;  F = softmax_j(s) v W_o
  E, latent experts:
    s = sigmoid(h W_r) over ALL experts (float32);  S = top-k of s + b
    w_e = s_e / sum_S s * routed_scaling_factor;  u = h W_down [latent]
    F = (sum_{e in S, e HELD} w_e relu(u W1_e)^2 W2_e) W_up + relu(h Ws1)^2 Ws2
  logits = RMSNorm_f(x_L) W_head

What the absent experts would add is left out, program and reference alike
(the ``model-configs`` guide, section 4); ``expert_layer(.., held=)`` gives
any share, so a test can add the shares up to the uncut layer.

The reference has no cache, no state array, no kernel, no chunked form, no
sort and no grouped matmul: Mamba-2 is the token-by-token recurrence under
``lax.scan``, the softmax layer scores every key in query blocks, every HELD
expert is applied densely and masked by the top-k set. It reads the SAME
stored weights as the program and runs under
``jax.default_matmul_precision("highest")``.

Every reading the published keys do not settle is under ``assumed`` in the
configuration file, with the key it rests on.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.kda_gqa_moe_lm import _rope_half  # (one variant's)
from benchmark.families.kda_mla_moe_lm import (  # noqa: F401 - same pieces
    _bf16, mantissa_bits)
from benchmark.families.moe_lm import (  # noqa: F401 - the family's surface
    draw_prompt_ids, grouped_matmul_cost, served_logprobs)
from benchmark.families.window_moe_lm import (  # the same plain pieces
    _QUERY_BLOCK, _f32, _head, _rms)

ITEM = "tokens"
_EXPERT_BLOCK = 8       # experts upcast to float32 at a time
_TOKEN_BLOCK = 1024     # tokens that go through the experts together
#: WRONG models, one fault each, that the check and the tier-1 tests must
#: tell from the right one: ``reference_logits(.., variant=name)``
VARIANTS = {
    "no_d_skip": "y = S C: the D x skip left out",
    "gate_after_norm": "GroupRMSNorm(y) * silu(z): the gate after the norm",
    "no_conv_bias": "the convolution without its bias",
    "no_dt_bias": "dt = softplus(dt): dt_bias left out",
    "no_routed_scale": "routed_scaling_factor left out",
    "no_latent": "the experts fed the first latent-many channels of h, "
                 "their sum padded with zeros: no W_down, no W_up",
    "gated_experts": "silu(u W1) * (u W1) W2: a gated form on the one plane",
    "no_router_bias": "the top-k taken on s, not s + b",
    "no_shared_expert": "the always-on expert left out",
    "rope_on_gqa": "q and k of the softmax layer rotated (theta, half "
                   "pairing) where the model has no positions",
    "bf16_stated_f32": "norms, router scores, dt and the decay rounded to "
                       "bfloat16 where the configuration says float32",
    "bf16_state": "the recurrent state kept in bfloat16 between tokens",
}

#: THE LIMIT on the served top-8 log-prob error (``reference_logit_gaps``;
#: the mix's ``check.logit_gap_tol`` IS this number), the MEDIAN over the
#: served positions. Readings (my chip runs, PR 54, PERF.md section 6;
#: ``tools/nemotron3s_chip_check.py variants`` at seeds 2147483659 / 671, 217
#: positions each, and the cell's own checked requests in thirteen runs,
#: 715-1211 positions each): against the right reference p50 0.00231-0.00253;
#: with norms, router scores, dt and the decay rounded to bfloat16 (one
#: precision below what the configuration states) p50 0.0184 / 0.0207; every
#: fault of the mathematics p50 >= 0.0107 (rope on the attention; no shared
#: expert 1.36). 0.007 = 2.8 x the largest right reading and 2.6 x under the
#: smallest wrong one.
#: Why the median and not ling3's 80th percentile: a sound engine sits at
#: 0.01-0.06 in 10-20% of positions (an upstream bf16 product flips a
#: near-tie of the router's top-22 among 512 scores, and with 128 of the 512
#: held that swaps whether a held expert answers at all), so p80 read 0.0036-
#: 0.0045 and p90 0.007-0.024 over the first eight right runs: p80 stands ON the
#: knee of the distribution, the median on the flat stretch below it.
CHECK_LOGPROB_QUANTILE = 50
CHECK_LOGPROB_TOL = 0.007
#: ... on the recurrent state's precision, in mantissa bits the slot's
#: state USES against the reference recurrence's (23 for float32, 7 for a
#: state that passed through bfloat16; ``kda_mla_moe_lm`` says why bits and
#: not a distance): the right engine reads 0, a bfloat16 state 16
CHECK_STATE_BITS_TOL = 8
#: ... and on how far below its position's best the reference puts a token
#: the TIMED engine emitted (a request answered with another's tokens, or
#: from another slot's state, reads several units)
CHECK_EMITTED_GAP_TOL = 0.5
CHECK_TOPK = 8

_KINDS = {"M": "mamba2+none", "*": "gqa+none", "E": "none+ffn"}


def letters_of(config: dict) -> str:
    """The layers this configuration runs: the first ``num_hidden_layers``
    letters of the published pattern."""
    letters = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    if len(letters) != config["num_hidden_layers"] or set(letters) - set(
            _KINDS):
        raise ValueError(f"hybrid_override_pattern {letters!r}: "
                         f"{config['num_hidden_layers']} letters of M * E")
    return letters


def pattern_of(config: dict) -> Tuple[str, ...]:
    return tuple(_KINDS[c] for c in letters_of(config))


def held_of(config: dict) -> Tuple[int, int]:
    """(first, count): the routed experts this chip holds."""
    return config["assumed"]["experts_first"], config["n_routed_experts"]


def spec_of(config: dict):
    """The program's model spec for this configuration: a tree whose spec
    lacks the half-block positions, the ``mamba2`` kind or the latent
    ungated experts fails here, at once, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a = config["assumed"]
    first, count = held_of(config)
    E = config["router_outputs"]
    if config["mlp_hidden_act"] != "relu2" or config["n_group"] != 1 \
            or config["mamba_hidden_act"] != "silu" \
            or not config["use_conv_bias"] or config["mamba_proj_bias"] \
            or config["expand"] * config["hidden_size"] \
            != config["mamba_num_heads"] * config["mamba_head_dim"]:
        raise ValueError("mamba2_gqa_moe_lm: relu2 experts in one group, a "
                         "silu convolution with a bias, no projection bias, "
                         "expand x hidden_size = heads x head_dim")
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], use_rope=True,     # = no learned table
        max_len=a["max_len"], norm="rms_norm", norm_eps=config["norm_eps"],
        rope_theta=float(config["rope_theta"]), rope_pairing="half",
        layer_pattern=pattern_of(config),
        mamba_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        mamba_groups=config["n_groups"], mamba_state=config["ssm_state_size"],
        mamba_conv=config["conv_kernel"], mamba_chunk=config["chunk_size"],
        ffn="swiglu_moe", num_experts=E,
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["moe_intermediate_size"], expert_act="relu2",
        expert_latent=config["moe_latent_size"],
        d_shared=(config["n_shared_experts"]
                  * config["moe_shared_expert_intermediate_size"]),
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
    does not (``assumed``: ``router_bias_std``, ``mamba_values``): the
    router's selection bias b ~ N(0, ``router_bias_std``^2); Mamba-2's own
    start: A_log = ln U(1, 16) a head, dt_bias = softplus^-1(dt) with dt
    log-uniform in (``time_step_min``, ``time_step_max``) a head (a token
    keeps exp(-A dt) between 0.2 and 0.999 of a head's state), D = 1; the
    convolution's bias ~ U(-1/2, 1/2) (a depthwise convolution of 4 taps as
    its framework starts it)."""
    spec = spec_of(config)
    rng = np.random.default_rng([int(seed), 0x4d414d42])
    H = spec.mamba_heads
    Lm, Le = spec.plane_layers("mamba_a_log"), spec.plane_layers("router_b")
    std = config["assumed"]["router_bias_std"]
    dt = np.exp(rng.uniform(np.log(config["time_step_min"]),
                            np.log(config["time_step_max"]), (Lm, H)))
    dt = np.maximum(dt, config["time_step_floor"])
    return {
        "router_b": rng.normal(0.0, 1.0, (Le, spec.num_experts)) * std,
        "mamba_a_log": np.log(rng.uniform(1.0, 16.0, (Lm, H))),
        "mamba_dt_bias": dt + np.log(-np.expm1(-dt)),   # softplus^-1(dt)
        "mamba_d": np.ones((Lm, H)),
        "mamba_conv_b": rng.uniform(-0.5, 0.5,
                                    (Lm, spec.block.mamba_conv_width)),
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
        mask_plane=bool(e.get("mask_plane", 1)), **engine_kw)


#: id(configuration) -> (the mix's ``engine`` section, the engine) of the
#: last ``build_engine``: the check's replay engine is its twin
_ENGINES: dict = {}


def _release(eng) -> None:
    """The TIMED engine is done when the check starts (the server is
    stopped, its counters and the memory peak are read): its state arrays
    and page pools (3.4 GB at the cell's size, on a chip the weights and
    they fill to 77%) leave its scope, so that the twin's own state and the
    float32 reference's temporaries have the room."""
    for name in [n for n in eng.scope.keys() if n.startswith("serving.")]:
        eng.scope.delete(name)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The model's parameters by the fixed names the layout gives them, as
    stored (nothing is copied or cast)."""
    names = ["tok_emb", "final_ln.scale", "lm_head.w"] + sorted(
        n for n in scope.keys() if n.startswith("lm_stack.stack_"))
    return {name: scope.get(name) for name in names}


def router_choice(config: dict, h, router_w, router_b, variant: str = ""):
    """h [T, d] float32 -> (scores s [T, E], chosen [T, E] bool): the
    router of one layer over ALL its outputs, one group."""
    import jax

    def squash(t):
        return _bf16(t) if variant == "bf16_stated_f32" else t

    s = squash(jax.nn.sigmoid(squash(h @ _f32(router_w))))
    c = s if variant == "no_router_bias" else s + _f32(router_b)
    kth = jax.lax.top_k(c, config["num_experts_per_tok"])[0][:, -1:]
    return s, c >= kth


def _relu2(t):
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(t))


def expert_layer(config: dict, p: dict, h, held=None, variant: str = "",
                 parts: bool = False, offset=0):
    """The expert layer on h [T, d] (float32, normed) with per-layer
    weights ``p`` (``moe_up_w`` / ``moe_down_w`` holding the ``held`` =
    (first, count) experts; None: the configuration's share): shared +
    routed, or (routed, shared) under ``parts``. ``offset``: where the held
    experts start in ``moe_*_w`` (a layer's window of a flattened stack)."""
    import jax
    import jax.numpy as jnp

    first, count = held or held_of(config)
    T = h.shape[0]
    dl = config["moe_latent_size"]
    s, chosen = router_choice(config, h, p["router_w"], p["router_b"],
                              variant)
    gate = jnp.where(chosen, s, 0.0)
    if config["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    gate = gate[:, first:first + count]
    if variant != "no_routed_scale":
        gate = gate * config["routed_scaling_factor"]
    u = h[:, :dl] if variant == "no_latent" \
        else h @ _f32(p["moe_latent_down_w"])
    eb = next(b for b in (_EXPERT_BLOCK, 4, 2, 1) if count % b == 0)
    Bt = next(b for b in (_TOKEN_BLOCK, 512, 256, 128, T) if T % b == 0)

    def token_block(blk):
        u_b, gate_b = blk

        def expert_block(y, e0):
            w1, w2 = (jax.lax.dynamic_slice_in_dim(p[name], offset + e0, eb,
                                                   0)
                      for name in ("moe_up_w", "moe_down_w"))
            g_blk = jax.lax.dynamic_slice_in_dim(gate_b, e0, eb, 1)
            a = jnp.einsum("td,edf->tef", u_b, _f32(w1))
            a = jax.nn.silu(a) * a if variant == "gated_experts" \
                else _relu2(a)
            return y + jnp.einsum("tef,efd,te->td", a, _f32(w2), g_blk), None

        return jax.lax.scan(expert_block, jnp.zeros_like(u_b),
                            jnp.arange(0, count, eb))[0]

    r = jax.lax.map(token_block, (
        u.reshape(T // Bt, Bt, -1), gate.reshape(T // Bt, Bt, count))
    ).reshape(T, -1)
    routed = jnp.pad(r, ((0, 0), (0, h.shape[1] - dl))) \
        if variant == "no_latent" else r @ _f32(p["moe_latent_up_w"])
    shared = jnp.zeros_like(h)
    if variant != "no_shared_expert":
        shared = _relu2(h @ _f32(p["shared_up_w"])) @ _f32(p["shared_down_w"])
    return (routed, shared) if parts else routed + shared


def mamba_inputs(config: dict, p: dict, h, variant: str = "", history=None):
    """h [T, d] (normed, float32) -> z [T, H P], x [T, H, P], B, C [T, G,
    N], dt [T, H], g [T, H] (the log-decay) of one Mamba-2 layer and the
    convolution's history after these tokens (the last taps - 1 rows of
    xBC); ``history`` None: a zero history (the sequence starts here)."""
    import jax
    import jax.numpy as jnp

    def squash(t):
        return _bf16(t) if variant == "bf16_stated_f32" else t

    T = h.shape[0]
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, taps = (config["n_groups"], config["ssm_state_size"],
                  config["conv_kernel"])
    d_in, cw = H * P, H * P + 2 * G * N
    proj = h @ _f32(p["mamba_in_w"])
    z, xbc, dt = proj[:, :d_in], proj[:, d_in:d_in + cw], proj[:, d_in + cw:]
    u = jnp.concatenate([jnp.zeros((taps - 1, cw), xbc.dtype)
                         if history is None else history, xbc])
    w = _f32(p["mamba_conv_w"])                                 # [taps, cw]
    y = sum(u[i:i + T] * w[i] for i in range(taps))
    if variant != "no_conv_bias":
        y = y + _f32(p["mamba_conv_b"])
    y = jax.nn.silu(y)
    x = y[:, :d_in].reshape(T, H, P)
    B = y[:, d_in:d_in + G * N].reshape(T, G, N)
    C = y[:, d_in + G * N:].reshape(T, G, N)
    if variant != "no_dt_bias":
        dt = dt + _f32(p["mamba_dt_bias"])
    dt = squash(jax.nn.softplus(dt))
    g = squash(-jnp.exp(_f32(p["mamba_a_log"])) * dt)
    return z, x, B, C, dt, g, u[T:]


def mamba_scan(x, B, C, dt, g, variant: str = "", state=None):
    """The recurrence, token by token -> (y [T, H, P] = S_t C_t, S after
    the last token [H, P, N])."""
    import jax
    import jax.numpy as jnp

    T, H, P = x.shape
    G, N = B.shape[1:]

    def step(S, inp):
        x_t, B_t, C_t, dt_t, g_t = inp
        S = S * jnp.exp(g_t)[:, None, None] \
            + (x_t * dt_t[:, None])[..., None] * jnp.repeat(
                B_t, H // G, axis=0)[:, None, :]
        if variant == "bf16_state":
            S = _bf16(S)
        return S, jnp.einsum("hpn,hn->hp", S, jnp.repeat(C_t, H // G,
                                                         axis=0))

    S0 = jnp.zeros((H, P, N), jnp.float32) if state is None else state
    S, y = jax.lax.scan(step, S0, (x, B, C, dt, g))
    return y, S


def mamba_layer(config: dict, p: dict, h, real, variant: str = ""):
    """One Mamba-2 layer on h [T, d] (normed): -> (what it adds to the
    stream [T, d], the state after the last ``real`` token [H, P, N])."""
    import jax
    import jax.numpy as jnp

    lossy = variant == "bf16_stated_f32"
    H, P, G = (config["mamba_num_heads"], config["mamba_head_dim"],
               config["n_groups"])
    T = h.shape[0]
    z, x, B, C, dt, g, _ = mamba_inputs(config, p, h, variant)
    y, S = mamba_scan(x, B, C, jnp.where(real, dt, 0.0),
                      jnp.where(real, g, 0.0), variant)
    if variant != "no_d_skip":
        y = y + _f32(p["mamba_d"])[:, None] * x
    y = y.reshape(T, H * P)
    scale = p["mamba_norm_s"].reshape(G, -1)
    if variant == "gate_after_norm":
        y = _rms(y.reshape(T, G, -1), scale, config["norm_eps"],
                 lossy).reshape(T, -1) * jax.nn.silu(z)
    else:
        y = _rms((y * jax.nn.silu(z)).reshape(T, G, -1), scale,
                 config["norm_eps"], lossy).reshape(T, -1)
    return y @ _f32(p["mamba_out_w"]), S


def _hidden(config: dict, w: dict, ids, n, variant: str = ""):
    """ids [T] (T a multiple of the query block, or shorter than one), of
    which the first ``n`` are the sequence -> (final-norm hidden [T, d]
    float32, every Mamba-2 layer's state S after token n - 1 [layers, H, P,
    N]: the padding's tokens decay nothing and write nothing).
    ``variant``: one of ``VARIANTS``."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {sorted(VARIANTS)}")
    lossy = variant == "bf16_stated_f32"

    def squash(t):
        return _bf16(t) if lossy else t

    blk = spec_of(config).block
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, eps = config["head_dim"], config["norm_eps"]
    letters = letters_of(config)
    index = blk.group_index(len(letters))
    T = ids.shape[0]
    Bq = min(_QUERY_BLOCK, T)
    if T % Bq:
        raise ValueError(f"{T} tokens are not whole blocks of {Bq}")
    pos = jnp.arange(T)
    real = (pos < n)[:, None]
    states = []
    stack = {key: w[f"lm_stack.stack_{key}"]
             for key in blk.stack_slots().values()}
    n_here = stack["moe_up_w"].shape[1]
    experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:])
               for k in ("moe_up_w", "moe_down_w")}

    def planes(l):
        return {k: v[index[blk.plane_group(k)][l]] for k, v in stack.items()
                if k not in experts
                and index[blk.plane_group(k)][l] is not None}

    x = _f32(w["tok_emb"][ids])
    for l, letter in enumerate(letters):
        p = planes(l)
        if letter == "E":
            h = _rms(x, p["ln2_s"], eps, lossy)
            x = x + expert_layer(config, {**p, **experts}, h, variant=variant,
                                 offset=index["experts"][l] * n_here)
            continue
        h = _rms(x, p["ln1_s"], eps, lossy)
        if letter == "M":
            y, S = mamba_layer(config, p, h, real, variant)
            states.append(S)
            x = x + y
            continue
        kv = h @ _f32(p["gqa_qkv_w"][:, H * dh:])       # columns q | k | v
        k = kv[:, :Hkv * dh].reshape(T, Hkv, dh)
        v = kv[:, Hkv * dh:].reshape(T, Hkv, dh)
        if variant == "rope_on_gqa":
            k = _rope_half(k, pos, float(config["rope_theta"]))
        w_q, wo = _f32(p["gqa_qkv_w"][:, :H * dh]), _f32(p["gqa_out_w"])

        def query_block(blk_in, k=k, v=v, w_q=w_q, wo=wo):
            x_b, h_b, pos_b = blk_in
            q = (h_b @ w_q).reshape(Bq, H, dh)
            if variant == "rope_on_gqa":
                q = _rope_half(q, pos_b, float(config["rope_theta"]))
            q = q.reshape(Bq, Hkv, H // Hkv, dh)    # head n = (n // G, n % G)
            s = jnp.einsum("bngd,tnd->ngbt", q, k) * dh ** -0.5
            s = jnp.where((pos_b[:, None] >= pos[None, :])[None, None], s,
                          -jnp.inf)
            ctx = jnp.einsum("ngbt,tnd->bngd",
                             squash(jax.nn.softmax(s, axis=-1)),
                             v).reshape(Bq, H * dh)
            return x_b + ctx @ wo

        x = jax.lax.map(query_block, (
            x.reshape(T // Bq, Bq, -1), h.reshape(T // Bq, Bq, -1),
            pos.reshape(T // Bq, Bq))).reshape(T, -1)
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


def _padded_len(n: int) -> int:
    """The length the reference runs a sequence of ``n`` tokens at: one
    query block or less as it is, else 512, else whole thousands (1024 ..),
    so that a run's checked requests share two or three compiled programs
    (the mask is causal and the padding's tokens neither decay nor write a
    state, so the pad cannot reach back)."""
    if n <= _QUERY_BLOCK:
        return n
    return 512 if n <= 512 else -(-n // 1024) * 1024


def _rows_logits(config: dict, w: dict, seq: np.ndarray, rows,
                 variant: str = "", states: bool = False):
    """Teacher-forced reference logits [len(rows), V] at positions ``rows``
    of ``seq``; the head runs over those rows only, a block at a time.
    With ``states``: -> (logits, every Mamba-2 layer's state after the last
    token of ``seq`` [layers, H, P, N])."""
    import jax
    import jax.numpy as jnp

    ids = np.zeros(_padded_len(seq.size), np.int32)
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
    """A twin of the engine ``build_engine`` last built for ``config`` (its
    page and chunk sizes, table width: the timed programs' shapes; that
    engine's own state and pools are released first) on the SAME weight
    arrays, with the beam plane on (how logits leave an engine),
    8 slots at most (a slot's state is 21 MB beside a timed engine that
    fills the chip) and a pool of one table's pages."""
    import paddle_tpu as pt

    if id(config) not in _ENGINES:
        raise ValueError("reference_logit_gaps replays the checked requests "
                         "through a twin of the engine: build_engine first")
    section, timed = _ENGINES[id(config)]
    _release(timed)
    e = dict(section)
    e["slots"] = min(e["slots"], 8)
    e["n_pages"] = -(-e["max_len"] // e["page_size"]) + 2
    scope = pt.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return _engine(spec_of(config), scope, e, beam_width=CHECK_TOPK)


#: the engine's scope array of the Mamba-2 states [layers, slots, H, P, N]
#: and the prefill feed that names a row's slot (``serving/generation.py``)
_STATE_ARRAY, _STATE_SLOT = "serving.state.MambaState", "serving.state_slot"


def served_errors(config: dict, w: dict, eng, prompt, new_tokens: int,
                  variants=("",)):
    """One request through ``eng`` (beam plane on): -> ({variant: [the
    largest error of the served top-k log-probs against that reference, a
    served position]}, the emitted sequence, the served positions,
    {variant: the state the request's SLOT holds when it ends (after the
    last token that was fed) against that reference's recurrence, a Mamba-2
    layer: ``rel_err`` |S_engine - S| / |S| (Frobenius) and ``bits``
    |mantissa_bits(S_engine) - mantissa_bits(S)|}, {position: the FIRST
    variant's reference logits there})."""
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
    errs, state, logits_at = {}, {}, None
    for variant in variants:
        logits, S = _rows_logits(config, w, again[:-1], served, variant,
                                 states=True)
        if logits_at is None:
            logits_at = dict(zip(served.tolist(), logits))
        ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        errs[variant] = [float(np.abs(v - ref[j][i]).max())
                         for j, (_, v, i) in enumerate(calls)]
        state[variant] = {
            "rel_err": [float(np.linalg.norm(a - b) / np.linalg.norm(b))
                        for a, b in zip(held, S)],
            "bits": [abs(a - b) for a, b in zip(mantissa_bits(held),
                                                mantissa_bits(S))]}
    return errs, again, served, state, logits_at


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """What the serve driver holds a run to: it compares the LARGEST value
    returned with the mix's ``check.logit_gap_tol``, which for this family
    is ``CHECK_LOGPROB_TOL``. Three readings, each in that limit's terms (as
    ``kda_mla_moe_lm.reference_logit_gaps`` has them):

    1. the ``CHECK_LOGPROB_QUANTILE``-th percentile of the SERVED top-8
       log-prob error: every checked request ``(prompt_len, ids)`` is
       replayed, after the drain, through ``_replay_engine`` (chunked
       prefill through the chunked SSD form and the K/V pages, then the
       decode kernel's recurrence from the slot's state), and the log-probs
       it serves at every chunk end and decode step are compared with the
       reference's teacher-forced full forward of the replayed sequence;
    2. on the tokens the TIMED engine emitted: how far below its position's
       best the reference puts each (the largest, scaled by
       ``CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL``);
    3. the precision of the STATE each replay leaves in its slot against
       the reference recurrence's (the largest difference in mantissa bits,
       scaled by ``CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL``).

    The readings go to stderr as one JSON line."""
    import json
    import sys
    import time

    t0 = time.monotonic()
    eng = _replay_engine(config, w) if results else None
    errs: List[float] = []
    gaps: List[float] = []
    state_errs: List[List[float]] = []
    bits = same = 0
    for prompt_len, out in results:
        out = np.asarray(out)
        by, again, served, state, logits_at = served_errors(
            config, w, eng, out[:prompt_len], out.size - prompt_len)
        errs.extend(by[""])
        state_errs.append(state[""]["rel_err"])
        bits = max(bits, *state[""]["bits"])
        same += np.array_equal(again, out)
        emitted = np.arange(prompt_len - 1, out.size - 1)
        if np.array_equal(again, out):
            # the replay emitted the timed tokens: its served positions
            # (the last chunk's end and every decode step) ARE these rows
            mine = np.stack([logits_at[p] for p in emitted.tolist()])
        else:
            mine = _rows_logits(config, w, out[:-1], emitted)
        gaps.extend((mine.max(axis=-1) - mine[np.arange(emitted.size),
                                              out[emitted + 1]]).tolist())
    if not errs:
        return np.zeros((0,), np.float32)
    held = float(np.percentile(errs, CHECK_LOGPROB_QUANTILE))
    worst = float(max(gaps))
    print(json.dumps({"mamba2_gqa_moe_lm.check": {
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
        "replays_equal_to_timed": int(same),
        "twin_and_reference_s": round(time.monotonic() - t0, 1)}}),
        file=sys.stderr, flush=True)
    return np.asarray(
        [held, worst * CHECK_LOGPROB_TOL / CHECK_EMITTED_GAP_TOL,
         bits * CHECK_LOGPROB_TOL / CHECK_STATE_BITS_TOL], np.float32)


# ---------------------------------------------------------------------------
# the kernels: which device event is a call, and what a call has to move
# ---------------------------------------------------------------------------
#: ``pallas_call(name=...)`` of the Mamba-2 decode step
#: (``paddle_tpu/kernels/mamba2.KERNEL``)
MAMBA_KERNEL = "mamba2_decode_step"
_STATE = re.compile(r"\bf32\[(\d+),(\d+),(\d+),(\d+),(\d+)\]")
_GROUP_ROWS = re.compile(r"\bf32\[(\d+),(\d+),1,(\d+)\]")


def mamba_decode_call(hlo_text: str) -> Optional[Dict[str, int]]:
    """None unless the device event is a call of the Mamba-2 decode kernel
    (told by its NAME); else the geometry off its operands: the state f32[L,
    slots, H, P, N] and the groups' rows f32[slots, G, 1, N]: ``slots``,
    ``heads``, ``p``, ``n``, ``groups``."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    name, opcode = parse_op(hlo_text)
    if opcode != "custom-call" or name.split(".")[0] != MAMBA_KERNEL:
        return None
    operands = strip_layouts(hlo_text).split("custom-call(", 1)[1]
    m, g = _STATE.search(operands), _GROUP_ROWS.search(operands)
    if m is None or g is None:
        return None
    return {"slots": int(m.group(2)), "heads": int(m.group(3)),
            "p": int(m.group(4)), "n": int(m.group(5)),
            "groups": int(g.group(2))}


def mamba_decode_cost(config: dict, slots: int, heads: int, p: int, n: int,
                      groups: int) -> Dict[str, float]:
    """One call (one layer of one tick), float32: every row's state tiles
    read once and written once, plus what the step reads beside them (the
    decay and dt x columns [slots, H, P], the groups' B and C rows) and the
    read-out it writes [slots, H, P]: all of it is moved whatever
    implements the step, and all of it together is 1.6% over the state's
    own bytes at the published sizes."""
    state = 2.0 * slots * heads * p * n * 4
    beside = slots * (3 * heads * p + 2 * groups * n) * 4
    return {"bytes": state + beside}


def mamba_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of a Mamba-2 layer a device event belongs to:
    ``"step"`` (the decode kernel), ``"scan"`` (any other op with an
    operand or result shaped like the state [.., H, P, N] / [.., G, H/G, P,
    N] or a block's pairwise tensors [.., H, C, C] / [.., G, H/G, C, C]: the
    chunked form, the state's gather and scatter), ``"conv"`` (the
    convolution and its history: anything x | B | C = H P + 2 G N columns
    wide), ``"project"`` (the in- and out-projection, told by their
    weights' and result's widths, and the gate and group norm over H P
    channels). None for everything else."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, _ = parse_op(hlo_text)
    if name.split(".")[0] == MAMBA_KERNEL:
        return "step"
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    G, N, C = config["n_groups"], config["ssm_state_size"], config[
        "chunk_size"]
    d, d_in = config["hidden_size"], H * P
    cw = d_in + 2 * G * N
    heads = rf"({H}|{G},{H // G})"
    if re.search(rf"\[(\d+,)*{heads},{P},{N}\]", text) \
            or re.search(rf"\[(\d+,)*{heads},{C},{C}\]", text):
        return "scan"
    if re.search(rf"\[(\d+,)*{cw}\]", text):
        return "conv"
    if re.search(rf"\[(\d+,)*({d},)?{d_in + cw + H}\]", text) \
            or re.search(rf"\[(\d+,)*{d_in},{d}\]", text) \
            or re.search(rf"\[(\d+,)+({d_in}|{G},{d_in // G})\]", text):
        return "project"
    return None


def expert_widths(config: dict) -> Tuple[int, int]:
    """(what a routed expert reads and writes, its inner width): the
    LATENT's width, not ``hidden_size``."""
    return config["moe_latent_size"], config["moe_intermediate_size"]


def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """``"grouped_matmul"`` (the Pallas call by its name, ``ragged-dot``,
    anything reading the stacked expert planes [.., held, latent, f] /
    [.., held, f, latent]) | ``"latent"`` (the down- and up-projection, by
    their weights [d, latent] / [latent, d]) | ``"shared_expert"`` ([d, fs]
    / [fs, d]) | ``"route"`` (the router's product, top-k and sorts over
    the experts or the assignment vector) | None."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    L = letters_of(config).count("E")
    held, E = config["n_routed_experts"], config["router_outputs"]
    d, fs = config["hidden_size"], (
        config["n_shared_experts"]
        * config["moe_shared_expert_intermediate_size"])
    dl, f = expert_widths(config)
    pair = rf"({dl},{f}|{f},{dl})"
    if name.startswith(("ragged-dot", "grouped_matmul")) or re.search(
            rf"\[({L},{held}|{L * held}|{held}),{pair}\]", text):
        return "grouped_matmul"
    if re.search(rf"\[({L},)?({d},{dl}|{dl},{d})\]", text):
        return "latent"
    if re.search(rf"\[({L},)?({d},{fs}|{fs},{d})\]", text):
        return "shared_expert"
    if f"[{d},{E}]" in text or opcode in ("sort", "topk") \
            or name.startswith(("sort", "top-k", "topk")):
        return None if f",{config['vocab_size']}]" in text else "route"
    return None
