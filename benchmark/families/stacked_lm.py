"""Family ``stacked_lm``: a GPT-2-shaped decoder (pre-LN LayerNorm,
tanh-GELU, 4x FFN with biases, learned positions) in the repo's stacked
layout — ``models.transformer_lm(pipeline_stack=True)`` to train,
``serving.GenerationEngine`` to serve — with the yardstick's own pieces:
FLOPs per token, the Mosaic kernels' operations and bytes, and a plain
float32 ``jax.numpy`` reference of the same block.

Departures of the reference from the published GPT-2 (they follow the
repo's block, and the configuration file lists them under ``assumed``):
the vocabulary is padded (the pad columns take part in the softmax), the
output head is its own matrix (not tied to the embedding), and the qkv
and attention-output projections have no bias.
"""
from __future__ import annotations

import math
import re
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmark.harness import TrainProgram

ITEM = "tokens"
_STACK = ("ln1_s", "ln1_b", "qkv_w", "out_w", "ln2_s", "ln2_b",
          "ff_w1", "ff_b1", "ff_w2", "ff_b2")


def sizes(config: dict) -> dict:
    d = config["n_embd"]
    return dict(vocab_size=config["assumed"]["padded_vocab_size"],
                d_model=d, n_layers=config["n_layer"],
                num_heads=config["n_head"], max_len=config["n_positions"],
                d_ff=config.get("n_inner") or 4 * d)


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def build_train(config: dict, mix: dict, seed: int, plan=None) -> TrainProgram:
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    sz = sizes(config)
    T, V = mix["seq"], sz["vocab_size"]
    scope = pt.Scope()
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = seed
    with pt.program_guard(main, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        logits = models.transformer_lm(
            ids, pipeline_stack=True, use_rope=False,
            remat=mix.get("remat", True), **sz)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, V]),
            layers.reshape(tgt, shape=[-1, 1])))
        opt = mix["optimizer"]
        if opt["name"] != "adam":
            raise ValueError(f"stacked_lm trains with adam, not {opt}")
        sgd = pt.trainer.SGD(
            loss, pt.optimizer.AdamOptimizer(learning_rate=opt["lr"]),
            [ids, tgt], scope=scope, plan=plan)
    return TrainProgram(sgd=sgd, scope=scope, main=main)


def draw_ids(rng: np.random.RandomState, shape, config: dict,
             dist: str) -> np.ndarray:
    """Token ids below the PUBLISHED vocabulary size. ``log_uniform``:
    p(i) ~ 1/(i+1), the Zipf shape of real token streams, so that there
    is a unigram distribution to learn; ``uniform``: nothing to learn."""
    V = config["vocab_size"]
    if dist == "uniform":
        return rng.randint(0, V, size=shape).astype(np.int64)
    if dist == "log_uniform":
        ids = np.exp(rng.uniform(0.0, math.log(V + 1), size=shape)) - 1.0
        return np.minimum(ids.astype(np.int64), V - 1)
    raise ValueError(f"unknown token distribution {dist!r}")


def batches(config: dict, mix: dict, seed: int) -> Iterator[list]:
    """Endless stream of batches, each made on the host when asked for:
    ``batch`` rows of (ids[:-1], ids[1:])."""
    rng = np.random.RandomState(seed)
    while True:
        seq = draw_ids(rng, (mix["batch"], mix["seq"] + 1), config,
                       mix["ids"])
        yield [(row[:-1], row[1:]) for row in seq]


def items_per_step(mix: dict) -> int:
    return mix["batch"] * mix["seq"]


def flops_per_item(config: dict, mix: dict) -> float:
    """Model FLOPs per trained token: forward + backward (3x forward),
    2 FLOPs a multiply-add, attention at its CAUSAL cost (half the T^2
    square), head at the published vocabulary, recompute not counted.
    Copied from bench.py ``transformer_train_flops`` (PR 22)."""
    sz = sizes(config)
    d, L, T = sz["d_model"], sz["n_layers"], mix["seq"]
    dense = L * (2 * d * 4 * d + 2 * d * 2 * sz["d_ff"])
    attn = L * 2 * T * d
    head = 2 * d * config["vocab_size"]
    return 3.0 * (dense + attn + head)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def build_engine(config: dict, mix: dict, seed: int):
    """-> (engine, executors). Weights come from ONE run of the
    generation program's startup block on the device (no train -> save ->
    load), seeded."""
    import paddle_tpu as pt
    from paddle_tpu import layers, models
    from paddle_tpu.serving import GenerationEngine, LMSpec

    sz = sizes(config)
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace(0))
    prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(prog, startup):
        p = layers.data("p_init", shape=[8], dtype="int64")
        models.transformer_lm_generate(p, max_new_tokens=1, **sz)
    startup.random_seed = seed
    exe.run(startup, scope=scope)
    e = mix["engine"]
    eng = GenerationEngine(
        LMSpec(vocab_size=sz["vocab_size"], d_model=sz["d_model"],
               n_layers=sz["n_layers"], num_heads=sz["num_heads"],
               max_len=sz["max_len"], d_ff=sz["d_ff"]),
        scope, slots=e["slots"], page_size=e["page_size"],
        n_pages=e["n_pages"], prompt_buckets=tuple(e["prompt_buckets"]),
        prefill_batch_buckets=tuple(e["prefill_batch_buckets"]),
        prefill_chunk=e["prefill_chunk"], eos_id=None)
    return eng, [exe, eng.executor]


def draw_prompt_ids(rng, n: int, config: dict) -> np.ndarray:
    return draw_ids(rng, (n,), config, "log_uniform")


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def weights_of(program, scope) -> Dict[str, object]:
    """The stacked LM's parameters by the fixed names the layout gives
    them, each as ONE single-device array (a replicated array's first
    shard; nothing is copied)."""
    names = (["tok_emb", "pos_emb", "final_ln.scale", "final_ln.bias",
              "lm_head.w"] + [f"lm_stack.stack_{k}" for k in _STACK])
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


def _ln(x, scale, bias, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def reference_logits(config: dict, w: dict, ids):
    """ids [T] -> logits [T, V] float32: one sequence through the whole
    model, no kernel, no cache, no batching."""
    import jax
    import jax.numpy as jnp

    sz = sizes(config)
    H, d = sz["num_heads"], sz["d_model"]
    eps = config["layer_norm_epsilon"]
    T = ids.shape[0]
    x = w["tok_emb"][ids] + w["pos_emb"][:T]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        h = _ln(x, p["ln1_s"], p["ln1_b"], eps)
        q, k, v = jnp.split(h @ p["qkv_w"], 3, axis=-1)
        q, k, v = (a.reshape(T, H, d // H).transpose(1, 0, 2)
                   for a in (q, k, v))
        s = jnp.einsum("hqd,hkd->hqk", q, k) / math.sqrt(d // H)
        s = jnp.where(causal[None], s, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, axis=-1), v)
        x = x + ctx.transpose(1, 0, 2).reshape(T, d) @ p["out_w"]
        h2 = _ln(x, p["ln2_s"], p["ln2_b"], eps)
        ff = _gelu_new(h2 @ p["ff_w1"] + p["ff_b1"])
        return x + ff @ p["ff_w2"] + p["ff_b2"], None

    stack = {k: w[f"lm_stack.stack_{k}"] for k in _STACK}
    x, _ = jax.lax.scan(layer, x.astype(jnp.float32), stack)
    x = _ln(x, w["final_ln.scale"], w["final_ln.bias"], eps)
    return x @ w["lm_head.w"]


def reference_loss(config: dict, w: dict, feed: Dict[str, np.ndarray]) -> float:
    """Mean next-token cross entropy of the batch, one sequence at a
    time (so the [tokens, vocab] plane of the whole batch never
    exists)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loss_of(w, ids, tgt):
        def one(pair):
            logits = reference_logits(config, w, pair[0])
            lse = jax.nn.logsumexp(logits, axis=-1)
            tok = jnp.take_along_axis(logits, pair[1][:, None], axis=-1)
            return jnp.sum(lse - tok[:, 0])

        return jnp.sum(jax.lax.map(one, (ids, tgt))) / ids.size

    with jax.default_matmul_precision("highest"):
        return float(loss_of(w, jnp.asarray(feed["ids"], jnp.int32),
                             jnp.asarray(feed["tgt"], jnp.int32)))


def reference_logit_gaps(config: dict, w: dict,
                         results: List[Tuple[int, np.ndarray]]) -> np.ndarray:
    """For every generated position of every ``(prompt_len, ids)``:
    (the reference's max logit there) - (its logit of the token the
    engine emitted), under one teacher-forced forward of the whole
    sequence. 0 where engine and reference agree on the argmax, small
    where the engine's lower precision broke a near-tie the other way,
    large if the engine computes another function. Sequences are padded
    to ONE length so one program serves all (causal: the pad cannot reach
    back)."""
    import jax
    import jax.numpy as jnp

    T = sizes(config)["max_len"]

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
            # logits[t] predicts token t+1
            gaps.extend(gap[prompt_len - 1:out.size - 1].tolist())
    return np.asarray(gaps, np.float32)


# ---------------------------------------------------------------------------
# the Mosaic kernels: which call is which, and what each needs
# ---------------------------------------------------------------------------
_CALL = re.compile(r"^%\S+ = (\(.*?\)|\S+) custom-call\((.*?)\), custom_call")


def mosaic_kernel(hlo_text: str) -> Optional[str]:
    """Name the flash kernel behind a ``tpu_custom_call`` event. The
    trace does not carry the kernel's name (PERF.md §7), so the call is
    told by its signature (kernels/flash_attention.py): forward =
    (lengths, q, k, v) -> (o, lse); dq = 7 operands -> one tensor; dkv =
    7 operands -> (dk, dv)."""
    from benchmark.trace_reduce import strip_layouts

    m = _CALL.match(strip_layouts(hlo_text))
    if not m:
        return None
    outs = m.group(1).count("[")
    ins = m.group(2).count("[")
    if ins == 4 and outs == 2:
        return "_flash_kernel"
    if ins == 7:
        return "_flash_dq_kernel" if outs == 1 else "_flash_dkv_kernel"
    return None


def mosaic_costs(config: dict, mix: dict, chips: int) -> Dict[str, dict]:
    """Per CALL of each flash kernel on one chip: the FLOPs the algorithm
    needs (causal: half the T^2 square; 2 FLOPs a multiply-add) and the
    bytes it has to move at the configuration's compute type (bf16, 2 B:
    what AMP would hand it; the program hands it float32 today, PERF.md
    §6). forward: QK^T, PV. dq: QK^T, dO V^T, dS K. dkv: QK^T, dO V^T,
    P^T dO, dS^T Q."""
    sz = sizes(config)
    T, H = mix["seq"], sz["num_heads"]
    dh = sz["d_model"] // H
    rows = mix["batch"] // chips * H          # batch*heads on this chip
    square = rows * T * T * dh                # multiply-adds of one T^2 dot
    tensor = rows * T * dh * 2                # one [rows, T, dh] in bf16
    vec = rows * T * 4                        # lse / delta, float32
    return {
        "_flash_kernel": {"flops": 2 * 2 * square / 2,
                          "bytes": 4 * tensor + vec},
        "_flash_dq_kernel": {"flops": 2 * 3 * square / 2,
                             "bytes": 6 * tensor + 2 * vec},
        "_flash_dkv_kernel": {"flops": 2 * 4 * square / 2,
                              "bytes": 7 * tensor + 2 * vec},
    }
