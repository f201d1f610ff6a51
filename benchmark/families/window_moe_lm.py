"""Family ``window_moe_lm``: a SmallThinker-shaped decoder — layers of two
kinds in one stack (full attention WITHOUT positions / a sliding window
WITH RoPE, by ``sliding_window_layout`` and ``rope_layout``), grouped-query
attention at an explicit ``head_dim``, pre-norm RMSNorm, no biases, dropless
top-k ReGLU experts routed from the ATTENTION's input, untied head — served
by ``serving.GenerationEngine(spec, ...)`` from ONE ``paddle_tpu.lm_spec.
LMSpec`` (``spec_of``), with the yardstick's own pieces: the expert layer's
and the decode kernel's operations and bytes, and a plain float32
``jax.numpy`` reference of the equations of layer l (HF
``modeling_smallthinker``):

    a = RMSNorm_1(x)                    RMSNorm(u) = u rsqrt(mean(u^2) + eps) w
    r = a W_r                           router logits from the attention's input
    q = a W_q (H x dh)  k = a W_k (Hkv x dh)  v = a W_v (Hkv x dh)   no biases
    window layer: q, k <- RoPE(theta, pairing (i, i + dh/2));  full layer: as they are
    s_ij = q_i . k_j / sqrt(dh) for j <= i, on a window layer only while i - j < window
    h = x + softmax(s) v W_o            query head n reads KV head n // (H / Hkv)
    b = RMSNorm_2(h)
    S = top-k of r;  w = softmax(r_S)   (= softmax over all E, renormalised over S)
    y = h + sum_{e in S} w_e (relu(b W_gate,e) * (b W_up,e)) W_down,e
    logits = RMSNorm_f(y_L) W_head

The reference applies EVERY expert densely to every token and masks by the
top-k set, and masks the window over full scores: no sort, no grouped
matmul, no cache, no kernel, no batching. It reads the SAME stored weights
as the program (bfloat16 in the benchmark's configuration), upcasts a layer
at a time (the experts in blocks), and runs everything after the keys and
values of a layer in QUERY BLOCKS, so a 12k-token context fits beside an
engine that holds 14 GB; logits are made only for the rows asked for (a
whole [T, V] at this vocabulary is 7.5 GB).

Departures from the published model, all under ``assumed`` in the
configuration file too: no projection biases and no QK-norm (the published
``config.json`` has no key for either); the window is ``0 <= i - j <
window`` (HF's sliding-window mask); the "secondary experts" of the model
card — an inference-time predictor of which rows of an expert are zero after
``relu`` — change no result and are left out; q, k and v are one fused [d,
(H + 2 Hkv) dh] matrix (columns q | k | v): a layout; ``build_engine``
multiplies the seeded token embedding by ``assumed.embedding_scale`` so the
router sees the token, as a trained model's does (the reference reads the
same stored weights). The training-only load-balance loss is ``moe_lm``'s
(per layer E * sum_e f_e P_e).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmark.families.moe_lm import (  # noqa: F401 - the family's surface
    draw_prompt_ids, grouped_matmul_cost, moe_op, served_logprobs)
from benchmark.families.paged_attention import decode_cost

ITEM = "tokens"
_STACK = ("ln1_s", "qkv_w", "out_w", "ln2_s", "router_w", "moe_gate_w",
          "moe_up_w", "moe_down_w")
_EXPERT_BLOCK = 8       # experts upcast to float32 at a time
_QUERY_BLOCK = 128      # queries that attend together ([H, 128, T] scores)
_TOKEN_BLOCK = 1024     # tokens that go through the experts together
#: WRONG models, one fault each, that the tight check
#: (``tools/olmoe_chip_check.py --cell .. serve``) must tell from the right
#: one: ``reference_logits(.., variant=name)``
VARIANTS = {
    "no_window": "the window layers attend every earlier key",
    "rope_everywhere": "the global layers rotate q and k too",
    "silu_experts": "silu for relu in the experts' gate",
    "late_router": "the router reads RMSNorm_2(h), after the attention",
    "kv_head_mod": "query head n reads KV head n % Hkv, not n // (H/Hkv)",
    "bf16_stated_f32": "norms, router logits and softmax rounded to "
                       "bfloat16 where the configuration says float32",
}


def layer_pattern(config: dict) -> Tuple[str, ...]:
    """One period of layer kinds from the published per-layer lists
    (``sliding_window_layout[l]`` 1 = window, ``rope_layout[l]`` 1 = RoPE),
    which must repeat it down the whole (cut) stack."""
    L = config["num_hidden_layers"]
    kinds = [("window" if w else "full") + ("+rope" if r else "+nope")
             for w, r in zip(config["sliding_window_layout"][:L],
                             config["rope_layout"][:L])]
    for p in range(1, L + 1):
        if L % p == 0 and kinds == kinds[:p] * (L // p):
            return tuple(kinds[:p])
    raise ValueError(f"no period in {kinds}")


def spec_of(config: dict):
    """The program's model spec for this configuration. Importing it and
    building it is the first thing ``build_engine`` does:
    a tree whose spec lacks layer kinds, ``head_dim`` or the expert
    options fails here, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a = config["assumed"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], num_heads=H,
        num_kv_heads=None if Hkv == H else Hkv,
        head_dim=config["head_dim"], use_rope=True,
        layer_pattern=layer_pattern(config),
        window=config["sliding_window_size"],
        max_len=config["max_position_embeddings"], norm="rms_norm",
        norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]),
        rope_pairing="half", ffn="swiglu_moe",
        num_experts=config["moe_num_primary_experts"],
        experts_per_tok=config["moe_num_active_primary_experts"],
        d_expert=config["moe_ffn_hidden_size"],
        norm_topk_prob=config["norm_topk_prob"], expert_act="relu",
        router_input="attn_input",
        router_aux_loss_coef=a["router_aux_loss_coef"], bias=False,
        param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def build_engine(config: dict, mix: dict, seed: int, **engine_kw):
    """-> (engine, executors). Weights come from ONE run of the generation
    program's startup block on the device, seeded, in the configuration's
    stored dtype. ``engine_kw``: further engine keywords (``beam_width=8``
    switches on the plane ``served_logprobs`` reads)."""
    spec = spec_of(config)      # first: a tree without the spec stops here
    import paddle_tpu as pt
    from paddle_tpu import layers, models
    from paddle_tpu.serving import GenerationEngine

    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p_init", shape=[8], dtype="int64")
        models.transformer_lm_generate(p, spec=spec, max_new_tokens=1)
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    # done, and the unscaled table let go, BEFORE the pools are allocated:
    # dispatched behind it they would sit beside both tables (0.78 GB of
    # live peak that no deployment holds)
    scope.set("tok_emb", (scope.get("tok_emb") * config["assumed"][
        "embedding_scale"]).block_until_ready())
    e = mix["engine"]
    eng = GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], n_pages_window=e["n_pages_window"],
        max_seq_len=e["max_len"],
        prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None, **engine_kw)
    return eng, [exe, eng.executor]


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The stacked LM's parameters by the fixed names the layout gives
    them, as stored (nothing is copied or cast)."""
    names = (["tok_emb", "final_ln.scale", "lm_head.w"]
             + [f"lm_stack.stack_{k}" for k in _STACK])
    return {name: scope.get(name) for name in names}


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(u, w, eps, lossy=False):
    import jax
    import jax.numpy as jnp

    y = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                          + eps) * _f32(w)
    return _f32(y.astype(jnp.bfloat16)) if lossy else y


def _rope_half(x, pos, theta):
    """x [T, n, dh] at positions pos [T]: pair (i, i + dh/2) rotates by
    pos * theta^(-2i/dh)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _hidden(config: dict, w: dict, ids, variant: str = ""):
    """ids [T] (T a multiple of the query block, or shorter than one) ->
    (final-norm hidden [T, d] float32, chosen [L, T, E] bool, prob_sum
    [L, E]: the router's statistics for the training loss). ``variant``:
    one of ``VARIANTS``, a deliberately wrong model."""
    import jax
    import jax.numpy as jnp

    if variant and variant not in VARIANTS:
        raise ValueError(f"variant {variant!r} not in {sorted(VARIANTS)}")
    lossy = variant == "bf16_stated_f32"

    def squash(t):      # a float32-stated value kept in bfloat16
        return _f32(t.astype(jnp.bfloat16)) if lossy else t

    act = jax.nn.silu if variant == "silu_experts" else jax.nn.relu

    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    dh, eps = config["head_dim"], config["rms_norm_eps"]
    E = config["moe_num_primary_experts"]
    k = config["moe_num_active_primary_experts"]
    G, dq, dkv = H // Hkv, H * dh, Hkv * dh
    theta, window = float(config["rope_theta"]), config["sliding_window_size"]
    L = config["num_hidden_layers"]
    T = ids.shape[0]
    B = min(_QUERY_BLOCK, T)
    if T % B:
        raise ValueError(f"{T} tokens are not whole blocks of {B}")
    pos = jnp.arange(T)
    blocks = max(E // _EXPERT_BLOCK, 1)
    # the experts take larger token blocks than the attention's queries:
    # a [128, d] x [d, f] product reloads an expert's weights every 128 rows
    Bt = next(b for b in (_TOKEN_BLOCK, 512, 256, B) if T % b == 0)

    def split(t):
        return t.reshape((blocks, E // blocks) + t.shape[1:])

    def layer(x, inp):
        p, windowed, rotates = inp
        a = _rms(x, p["ln1_s"], eps, lossy)
        wqkv = _f32(p["qkv_w"])
        if variant == "no_window":
            windowed = jnp.zeros((), bool)
        if variant == "rope_everywhere":
            rotates = jnp.ones((), bool)
        kk = (a @ wqkv[:, dq:dq + dkv]).reshape(T, Hkv, dh)
        v = (a @ wqkv[:, dq + dkv:]).reshape(T, Hkv, dh)
        kk = jnp.where(rotates, _rope_half(kk, pos, theta), kk)
        wo, wr = _f32(p["out_w"]), _f32(p["router_w"])

        def query_block(blk):
            x_b, a_b, pos_b = blk                       # [B, d] x2, [B]
            q = (a_b @ wqkv[:, :dq]).reshape(B, H, dh)
            q = jnp.where(rotates, _rope_half(q, pos_b, theta), q)
            if variant == "kv_head_mod":                # head n = r * Hkv + g
                q = q.reshape(B, G, Hkv, dh).transpose(0, 2, 1, 3)
            else:                                       # head n = g * G + r
                q = q.reshape(B, Hkv, G, dh)
            s = jnp.einsum("bgrd,tgd->grbt", q, kk) / math.sqrt(dh)
            dist = pos_b[:, None] - pos[None, :]
            seen = (dist >= 0) & (~windowed | (dist < window))
            s = jnp.where(seen[None, None], s, -jnp.inf)
            ctx = jnp.einsum("grbt,tgd->bgrd",
                             squash(jax.nn.softmax(s, axis=-1)), v)
            if variant == "kv_head_mod":
                ctx = ctx.transpose(0, 2, 1, 3)
            return x_b + ctx.reshape(B, dq) @ wo

        h = jax.lax.map(query_block, (
            x.reshape(T // B, B, -1), a.reshape(T // B, B, -1),
            pos.reshape(T // B, B))).reshape(T, -1)
        b_ = _rms(h, p["ln2_s"], eps, lossy)
        # the EARLY router: from the attention's input
        logits = squash((b_ if variant == "late_router" else a) @ wr)
        prob = squash(jax.nn.softmax(logits, axis=-1))      # [T, E]
        kth = jax.lax.top_k(logits, k)[0][:, -1:]
        chosen = logits >= kth
        gate = jnp.where(chosen, prob, 0.0)
        if config["norm_topk_prob"]:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        experts = (split(p["moe_gate_w"]), split(p["moe_up_w"]),
                   split(p["moe_down_w"]))

        def token_block(blk):
            b_b, gate_b = blk                           # [Bt, d], [Bt, E]

            def expert_block(y, eb):
                wg, wu, wd, g_blk = eb
                gated = (act(jnp.einsum("td,edf->tef", b_b, _f32(wg)))
                         * jnp.einsum("td,edf->tef", b_b, _f32(wu)))
                return y + jnp.einsum("tef,efd,te->td", gated, _f32(wd),
                                      g_blk), None

            return jax.lax.scan(
                expert_block, jnp.zeros_like(b_b),
                (*experts, gate_b.reshape(Bt, blocks, E // blocks).transpose(
                    1, 0, 2)))[0]

        y = jax.lax.map(token_block, (b_.reshape(T // Bt, Bt, -1),
                                      gate.reshape(T // Bt, Bt, E)))
        return h + y.reshape(T, -1), (chosen, jnp.sum(prob, axis=0))

    windowed = jnp.asarray(config["sliding_window_layout"][:L], bool)
    rotates = jnp.asarray(config["rope_layout"][:L], bool)
    stack = {key: w[f"lm_stack.stack_{key}"] for key in _STACK}
    x, (chosen, prob_sum) = jax.lax.scan(
        layer, _f32(w["tok_emb"][ids]), (stack, windowed, rotates))
    return _rms(x, w["final_ln.scale"], eps, lossy), chosen, prob_sum


def _padded(n: int) -> int:
    """The length the reference runs a sequence of ``n`` tokens at: whole
    query blocks, in few distinct sizes (one compiled program each: 128 x
    2^k or 128 x 3 x 2^k); the mask is causal, so the pad cannot reach
    back."""
    if n <= _QUERY_BLOCK:
        return n
    size = _QUERY_BLOCK
    while size < n:
        if size // 2 * 3 >= n and size > _QUERY_BLOCK:
            return size // 2 * 3
        size *= 2
    return size


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
        hidden = _jit_hidden(config, variant)(w, jnp.asarray(padded))
        return _head(hidden[jnp.asarray(rows)], w["lm_head.w"])


_HIDDEN_JITS: dict = {}


def _jit_hidden(config: dict, variant: str = ""):
    import jax

    key = (id(config), variant)
    if key not in _HIDDEN_JITS:
        _HIDDEN_JITS[key] = jax.jit(
            lambda w, ids: _hidden(config, w, ids, variant)[0])
    return _HIDDEN_JITS[key]


def _head_block(h, head_w):
    return h @ _f32(head_w)


def _head(hidden, head_w):
    """Rows of logits, a query block at a time (the weight is an
    ARGUMENT of the compiled block, never a constant inside it)."""
    import jax
    import jax.numpy as jnp

    global _HEAD_JIT
    if _HEAD_JIT is None:
        _HEAD_JIT = jax.jit(_head_block)
    out = [_HEAD_JIT(hidden[i:i + _QUERY_BLOCK], head_w)
           for i in range(0, hidden.shape[0], _QUERY_BLOCK)]
    return jnp.concatenate(out, axis=0) if len(out) > 1 else out[0]


_HEAD_JIT = None


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """For every generated position of every ``(prompt_len, ids)``: (the
    reference's max logit there) - (its logit of the token the engine
    emitted), under one teacher-forced forward of the whole sequence; the
    head runs over the generated rows only, a query block at a time."""
    import jax
    import jax.numpy as jnp

    gaps: List[float] = []
    with jax.default_matmul_precision("highest"):
        for prompt_len, out in results:
            out = np.asarray(out)
            rows = np.arange(prompt_len - 1, out.size - 1)
            ids = np.zeros(_padded(out.size - 1), np.int32)
            ids[:out.size - 1] = out[:-1]
            hidden = _jit_hidden(config)(w, jnp.asarray(ids))
            for i in range(0, rows.size, _QUERY_BLOCK):
                r = rows[i:i + _QUERY_BLOCK]
                logits = _head(hidden[jnp.asarray(r)], w["lm_head.w"])
                tok = jnp.take_along_axis(
                    logits, jnp.asarray(out[r + 1])[:, None], axis=-1)[:, 0]
                gaps.extend(np.asarray(jnp.max(logits, axis=-1)
                                       - tok).tolist())
    return np.asarray(gaps, np.float32)


def _loss_fn(config: dict):
    import jax
    import jax.numpy as jnp

    E = config["moe_num_primary_experts"]
    k = config["moe_num_active_primary_experts"]
    coef = config["assumed"]["router_aux_loss_coef"]

    def loss_of(w, ids, tgt):
        def one(pair):
            hidden, chosen, prob_sum = _hidden(config, w, pair[0])
            logits = hidden @ _f32(w["lm_head.w"])
            lse = jax.nn.logsumexp(logits, axis=-1)
            tok = jnp.take_along_axis(logits, pair[1][:, None], axis=-1)
            return (jnp.sum(lse - tok[:, 0]),
                    jnp.sum(chosen.astype(jnp.float32), axis=1), prob_sum)

        ce, counts, prob_sum = jax.lax.map(one, (ids, tgt))
        n = ids.size
        f = jnp.sum(counts, axis=0) / (n * k)          # [L, E]
        P = jnp.sum(prob_sum, axis=0) / n
        aux = E * jnp.sum(jax.lax.stop_gradient(f) * P)
        return jnp.sum(ce) / n + coef * aux

    return loss_of


def reference_grads(config: dict, w: dict,
                    feed: Dict[str, np.ndarray]) -> Tuple[float, dict]:
    """(loss, d loss / d every weight): mean next-token cross entropy of
    the batch plus ``coef`` x the load-balance loss, through ``jax.grad``
    — what the tests hold the train op to."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(_loss_fn(config)))(
            w, jnp.asarray(feed["ids"], jnp.int32),
            jnp.asarray(feed["tgt"], jnp.int32))
    return float(loss), grads


# ---------------------------------------------------------------------------
# the decode kernel's calls, by the kind of layer they serve
# ---------------------------------------------------------------------------
def attention_call_kind(call_layers: int, config: dict) -> Optional[str]:
    """Which kind of layer a ``paged_attention_decode`` call serves, told
    by the layer count of its pool operand ([L_kind, N, ps, Hkv*dh]):
    ``"global"`` | ``"window"``; None when it matches neither (or both:
    the kinds of this configuration then cannot be told apart)."""
    L = config["num_hidden_layers"]
    n_window = sum(config["sliding_window_layout"][:L])
    kinds = {L - n_window: "global", n_window: "window"}
    return kinds.get(call_layers) if len(kinds) == 2 else None


#: one call (one layer of one tick) that walks ``pages`` pages over all
#: rows: the K and the V tile of each, ONLY the pages — what the one-kind
#: cells' ``paged_attn_roofline`` counts, per kind here
mixed_attention_cost = decode_cost
