"""Family ``moe_lm``: an OLMoE-shaped decoder — pre-norm RMSNorm, QK-norm,
half-split RoPE, no biases, dropless token-choice top-k SwiGLU experts,
untied head — in the repo's stacked layout:
``models.transformer_lm(spec=, pipeline_stack=True)`` to train,
``serving.GenerationEngine(spec, ...)`` to serve, both built from ONE
``paddle_tpu.lm_spec.LMSpec`` (``spec_of``). With the yardstick's own
pieces: FLOPs per token at the ACTIVE parameters, the expert layer's
operations and bytes, and a plain float32 ``jax.numpy`` reference of the
same equations (HF ``modeling_olmoe``):

    a = RMSNorm_1(x)          RMSNorm(u) = u * rsqrt(mean(u^2) + eps) * w
    q = RMSNorm_q(a W_q)  k = RMSNorm_k(a W_k)  v = a W_v   (whole vectors)
    q, k -> H heads x dh, RoPE theta on all dh dims, pairing (i, i + dh/2)
    h = x + (softmax(q k^T / sqrt(dh), causal) v) W_o
    b = RMSNorm_2(h);  p = softmax(b W_r);  S = top-k of p, weights p_e
    y = h + sum_{e in S} p_e (silu(b W_gate,e) * (b W_up,e)) W_down,e
    logits = RMSNorm_f(y_L) W_head
    loss = CE + coef * sum_layers E * sum_e f_e P_e

The reference applies EVERY expert densely to every token and masks by
the top-k set: no sort, no grouped matmul, no cache, no batching. It
reads the SAME stored weights as the program (bfloat16 in the benchmark's
configuration) and upcasts them one layer at a time inside its scan, the
experts in blocks, so it fits beside an engine that holds ~11 GB.

Departures from the published model, all listed in the configuration
file under ``assumed`` too: the aux loss is summed per layer with f_e the
share of the layer's assignments (HF concatenates the layers before its
two means and does not divide the counts by top-k); the OLMo trainer's
router z-loss is left out (it is not in the published modeling code); q,
k and v are one fused [d, 3d] matrix (columns q | k | v); no dropout;
``build_engine`` multiplies the seeded token embedding by ``assumed``'s
``embedding_scale`` so that the router sees the token, as a trained
model's does (the reference reads the same stored weights).
Nothing is padded or tied; the RoPE pairing is computed as published, not
by permuting columns.
"""
from __future__ import annotations

import math
import re
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmark.families.stacked_lm import draw_ids
from benchmark.harness import TrainProgram

ITEM = "tokens"
_STACK = ("ln1_s", "qkv_w", "q_norm_s", "k_norm_s", "out_w", "ln2_s",
          "router_w", "moe_gate_w", "moe_up_w", "moe_down_w")
_EXPERT_BLOCK = 8      # experts upcast to float32 at a time


def spec_of(config: dict):
    """The program's model spec for this configuration. Importing it is
    the first thing ``build_engine`` / ``build_train`` do: a tree without
    the spec fails here, before anything is allocated."""
    from paddle_tpu.lm_spec import LMSpec

    a = config["assumed"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return LMSpec(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"], num_heads=H,
        num_kv_heads=None if Hkv == H else Hkv, use_rope=True,
        max_len=config["max_position_embeddings"], norm="rms_norm",
        norm_eps=config["rms_norm_eps"], qk_norm=True,
        rope_theta=float(config["rope_theta"]), rope_pairing="half",
        ffn="swiglu_moe", num_experts=config["num_experts"],
        experts_per_tok=config["num_experts_per_tok"],
        d_expert=config["intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        router_aux_loss_coef=a["router_aux_loss_coef"], bias=False,
        param_dtype=a["param_dtype"], page_dtype=a["page_dtype"])


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def build_train(config: dict, mix: dict, seed: int, plan=None) -> TrainProgram:
    spec = spec_of(config)
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    T, V = mix["seq"], spec.vocab_size
    scope = pt.Scope()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        logits, aux = models.transformer_lm(
            ids, spec=spec, pipeline_stack=True,
            remat=mix.get("remat", True))
        ce = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, V]),
            layers.reshape(tgt, shape=[-1, 1])))
        loss = layers.elementwise_add(
            ce, layers.scale(aux, scale=spec.router_aux_loss_coef))
        opt = mix["optimizer"]
        if opt["name"] != "adam":
            raise ValueError(f"moe_lm trains with adam, not {opt}")
        sgd = pt.trainer.SGD(
            loss, pt.optimizer.AdamOptimizer(learning_rate=opt["lr"]),
            [ids, tgt], scope=scope, plan=plan)
    return TrainProgram(sgd=sgd, scope=scope, main=main)


def batches(config: dict, mix: dict, seed: int) -> Iterator[list]:
    rng = np.random.RandomState(seed)
    while True:
        seq = draw_ids(rng, (mix["batch"], mix["seq"] + 1), config,
                       mix["ids"])
        yield [(row[:-1], row[1:]) for row in seq]


def items_per_step(mix: dict) -> int:
    return mix["batch"] * mix["seq"]


def flops_per_item(config: dict, mix: dict) -> float:
    """Model FLOPs per trained token at the ACTIVE parameters: forward +
    backward (3x forward), 2 FLOPs a multiply-add; k of E experts, the
    router, attention at its CAUSAL cost, the untied head; recompute not
    counted."""
    d, L, T = config["hidden_size"], config["num_hidden_layers"], mix["seq"]
    dkv = d // config["num_attention_heads"] * config["num_key_value_heads"]
    f, k, E = (config["intermediate_size"], config["num_experts_per_tok"],
               config["num_experts"])
    dense = L * 2 * (d * (d + 2 * dkv) + d * d + d * E + k * 3 * d * f)
    attn = L * 2 * T * d
    head = 2 * d * config["vocab_size"]
    return 3.0 * (dense + attn + head)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def build_engine(config: dict, mix: dict, seed: int, **engine_kw):
    """-> (engine, executors). Weights come from ONE run of the
    generation program's startup block on the device, seeded, in the
    configuration's stored dtype (no float32 copy is ever made).
    ``engine_kw``: further engine keywords (``beam_width=8`` switches on
    the plane ``served_logprobs`` reads)."""
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
    # the seeded embedding is 1/100 of a block's output: see ``assumed``
    scope.set("tok_emb", scope.get("tok_emb")
              * config["assumed"]["embedding_scale"])
    e = mix["engine"]
    eng = GenerationEngine(
        spec, scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], max_seq_len=e["max_len"],
        prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None, **engine_kw)
    return eng, [exe, eng.executor]


def served_logprobs(eng, prompt, new_tokens: int):
    """How logits leave the engine for a comparison with the reference:
    ONE request driven through the engine's own ticks (chunked prefill,
    then decode), with the beam plane (``beam_width=k``: the top-k
    log-probs of each row's last position) of every call captured.
    -> ([(position, values [k], ids [k])] for the last token of every
    prefill chunk and every decode step, the emitted sequence)."""
    calls = []
    run = eng.executor.run

    def capture(prog, feed=None, fetch_list=None, scope=None, **kw):
        res = run(prog, feed=feed, fetch_list=fetch_list, scope=scope, **kw)
        if feed and "serving.block_table" in feed:
            if "serving.chunk" in feed:
                pos = int(feed["serving.start"][0]
                          + feed["serving.chunk_len"][0]) - 1
            else:
                pos = int(feed["serving.pos"][0])
            calls.append((pos, np.asarray(res[1])[0], np.asarray(res[2])[0]))
        return res

    eng.executor.run = capture
    try:
        out = eng.generate_all([prompt], max_new_tokens=new_tokens)[0]
    finally:
        eng.executor.run = run
    return calls, np.asarray(out)


def draw_prompt_ids(rng, n: int, config: dict) -> np.ndarray:
    return draw_ids(rng, (n,), config, "log_uniform")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The stacked LM's parameters by the fixed names the layout gives
    them, as stored (nothing is copied or cast)."""
    names = (["tok_emb", "final_ln.scale", "lm_head.w"]
             + [f"lm_stack.stack_{k}" for k in _STACK])
    out = {}
    for name in names:
        arr = scope.get(name)
        shards = getattr(arr, "addressable_shards", None)
        if shards and len(shards) > 1:
            if not arr.is_fully_replicated:
                raise ValueError(f"{name} is sharded; the reference "
                                 "needs it whole on one device")
            arr = shards[0].data
        out[name] = arr
    return out


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms(u, w, eps):
    import jax
    import jax.numpy as jnp

    return u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                             + eps) * _f32(w)


def _rope_half(x, theta):
    """x [H, T, dh]: pair (i, i + dh/2) rotates by pos * theta^(-2i/dh)."""
    import jax.numpy as jnp

    T, dh = x.shape[1], x.shape[2]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None], jnp.sin(ang)[None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _forward(config: dict, w: dict, ids):
    """ids [T] -> (logits [T, V] float32, chosen [L, T, E] bool: the
    top-k set of every token in every layer, prob_sum [L, E])."""
    import jax
    import jax.numpy as jnp

    H = config["num_attention_heads"]
    Hkv = config["num_key_value_heads"]
    d, eps = config["hidden_size"], config["rms_norm_eps"]
    E, k = config["num_experts"], config["num_experts_per_tok"]
    dh = d // H
    dkv = dh * Hkv
    theta = float(config["rope_theta"])
    T = ids.shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))
    blocks = max(E // _EXPERT_BLOCK, 1)

    def layer(x, p):
        a = _rms(x, p["ln1_s"], eps)
        qkv = a @ _f32(p["qkv_w"])
        q = _rms(qkv[:, :d], p["q_norm_s"], eps)
        kk = _rms(qkv[:, d:d + dkv], p["k_norm_s"], eps)
        v = qkv[:, d + dkv:]
        q = _rope_half(q.reshape(T, H, dh).transpose(1, 0, 2), theta)
        kk = _rope_half(kk.reshape(T, Hkv, dh).transpose(1, 0, 2), theta)
        v = v.reshape(T, Hkv, dh).transpose(1, 0, 2)
        kk, v = (jnp.repeat(t, H // Hkv, axis=0) for t in (kk, v))
        s = jnp.einsum("hqd,hkd->hqk", q, kk) / math.sqrt(dh)
        s = jnp.where(causal[None], s, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)
        h = x + ctx.transpose(1, 0, 2).reshape(T, d) @ _f32(p["out_w"])
        b = _rms(h, p["ln2_s"], eps)
        prob = jax.nn.softmax(b @ _f32(p["router_w"]), axis=-1)   # [T, E]
        kth = jax.lax.top_k(prob, k)[0][:, -1:]
        chosen = prob >= kth
        gate = jnp.where(chosen, prob, 0.0)
        if config["norm_topk_prob"]:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

        def expert_block(y, blk):
            wg, wu, wd, g_blk = blk     # [B, d, f] x2, [B, f, d], [T, B]
            act = (jax.nn.silu(jnp.einsum("td,edf->tef", b, _f32(wg)))
                   * jnp.einsum("td,edf->tef", b, _f32(wu)))
            return y + jnp.einsum("tef,efd,te->td", act, _f32(wd),
                                  g_blk), None

        def split(t):
            return t.reshape((blocks, E // blocks) + t.shape[1:])

        y, _ = jax.lax.scan(
            expert_block, jnp.zeros_like(h),
            (split(p["moe_gate_w"]), split(p["moe_up_w"]),
             split(p["moe_down_w"]),
             gate.reshape(T, blocks, E // blocks).transpose(1, 0, 2)))
        return h + y, (chosen, jnp.sum(prob, axis=0))

    stack = {key: w[f"lm_stack.stack_{key}"] for key in _STACK}
    x, (chosen, prob_sum) = jax.lax.scan(
        layer, _f32(w["tok_emb"][ids]), stack)
    x = _rms(x, w["final_ln.scale"], eps)
    return x @ _f32(w["lm_head.w"]), chosen, prob_sum


def reference_logits(config: dict, w: dict, ids):
    """ids [T] -> logits [T, V] float32: one sequence through the whole
    model, no kernel, no cache, no batching, no sort."""
    return _forward(config, w, ids)[0]


def _loss_fn(config: dict):
    import jax
    import jax.numpy as jnp

    E, k = config["num_experts"], config["num_experts_per_tok"]
    coef = config["assumed"]["router_aux_loss_coef"]

    def loss_of(w, ids, tgt):
        def one(pair):
            logits, chosen, prob_sum = _forward(config, w, pair[0])
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


def reference_loss(config: dict, w: dict, feed: Dict[str, np.ndarray]) -> float:
    """Mean next-token cross entropy of the batch plus ``coef`` x the
    load-balance loss (per layer, over the whole batch), one sequence at
    a time."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return float(jax.jit(_loss_fn(config))(
            w, jnp.asarray(feed["ids"], jnp.int32),
            jnp.asarray(feed["tgt"], jnp.int32)))


def reference_grads(config: dict, w: dict,
                    feed: Dict[str, np.ndarray]) -> Tuple[float, dict]:
    """(loss, d loss / d every weight) of ``reference_loss`` through
    ``jax.grad`` — what the tests hold the train op's gradients to."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(_loss_fn(config)))(
            w, jnp.asarray(feed["ids"], jnp.int32),
            jnp.asarray(feed["tgt"], jnp.int32))
    return float(loss), grads


def _padded(n: int, quantum: int = 256) -> int:
    return -(-n // quantum) * quantum


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """For every generated position of every ``(prompt_len, ids)``:
    (the reference's max logit there) - (its logit of the token the
    engine emitted), under one teacher-forced forward of the whole
    sequence (see ``stacked_lm.reference_logit_gaps``). Sequences are
    padded to ONE length, the longest rounded up to 256, so one program
    serves all (causal: the pad cannot reach back)."""
    import jax
    import jax.numpy as jnp

    if not results:
        return np.zeros(0, np.float32)
    T = _padded(max(out.size for _, out in results))

    @jax.jit
    def gap_of(w, ids):
        logits = reference_logits(config, w, ids[:-1])
        tok = jnp.take_along_axis(logits, ids[1:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - tok

    gaps = []
    with jax.default_matmul_precision("highest"):
        for prompt_len, out in results:
            ids = np.zeros(T, np.int32)
            ids[:out.size] = out
            gap = np.asarray(gap_of(w, jnp.asarray(ids)))
            gaps.extend(gap[prompt_len - 1:out.size - 1].tolist())
    return np.asarray(gaps, np.float32)


# ---------------------------------------------------------------------------
# the expert layer: which device op is part of it, and what a call needs
# ---------------------------------------------------------------------------
def moe_op(hlo_text: str, config: dict) -> Optional[str]:
    """Name the part of the expert layer a device event belongs to, told
    by its signature as ``stacked_lm.mosaic_kernel`` tells the flash
    calls: ``"grouped_matmul"`` (a ragged-dot custom call, or any op with
    an operand shaped like the stacked expert weights [.., d, f] / [..,
    f, d] under one or two leading axes), ``"route"`` (the router's [.., d] x [d, E] product, the softmax
    / top-k / sort / count / gather ops over [rows, E] or the [rows * k]
    assignment vector). None for everything else."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    text = strip_layouts(hlo_text)
    name, opcode = parse_op(hlo_text)
    E, d, f = (config["num_experts"], config["hidden_size"],
               config["intermediate_size"])
    if name.startswith("ragged-dot") or re.search(
            rf"\[(\d+,)+({d},{f}|{f},{d})\]", text):
        return "grouped_matmul"
    if f"[{d},{E}]" in text or opcode in ("sort", "topk") \
            or name.startswith(("sort", "top-k", "topk")):
        # the sampling plane's sorts run over the vocabulary; the
        # expert layer's over E columns or the assignment vector
        return None if f",{config['vocab_size']}]" in text else "route"
    return None


def grouped_matmul_cost(config: dict, rows: int, cols_in: int,
                        cols_out: int, touched: float) -> Dict[str, float]:
    """One grouped matmul of ``rows`` sorted assignment rows [rows,
    cols_in] with the stacked expert weights [.., cols_in, cols_out]:
    2 * rows * cols_in * cols_out FLOPs; bytes = the weights of the
    ``touched`` experts that took a row (bf16; the engine counts them:
    vacant slots and padding all route alike, so a call touches fewer
    experts than even routing would) + the rows in (bf16) and out
    (float32)."""
    return {"flops": 2.0 * rows * cols_in * cols_out,
            "bytes": touched * cols_in * cols_out * 2
            + rows * (cols_in * 2 + cols_out * 4)}
