"""Train a small causal transformer LM (fluid-style API) and sample from
it — the long-context flagship path (flash attention, PERF.md). Beyond the
reference's capability set (it predates Transformers); shown here as the
idiomatic way to train one with this framework.

Run:  python demos/transformer_lm.py  (PADDLE_TPU_DEMO_FAST=1 to smoke)
"""
import os

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers, models

FAST = bool(os.environ.get("PADDLE_TPU_DEMO_FAST"))


def synthetic_corpus(rng, vocab, n, T):
    """A learnable language: token t+1 = (3*t + noise) % vocab."""
    x = np.zeros((n, T + 1), np.int64)
    x[:, 0] = rng.randint(0, vocab, size=n)
    for t in range(T):
        noise = rng.randint(0, 2, size=n)
        x[:, t + 1] = (3 * x[:, t] + noise) % vocab
    return x


def main():
    vocab, T = 97, 32 if FAST else 64
    d_model, n_layers = 64, 2
    main_prog, startup = pt.Program(), pt.Program()
    with pt.program_guard(main_prog, startup):
        ids = layers.data("ids", shape=[T], dtype="int64")
        tgt = layers.data("tgt", shape=[T], dtype="int64")
        # pipeline_stack: stacked [L, ...] weights (scan over layers; the
        # same tensors pipeline over a 'pp' mesh) — also what the KV-cache
        # generation program rejoins by name below
        logits = models.transformer_lm(ids, vocab_size=vocab,
                                       d_model=d_model, n_layers=n_layers,
                                       num_heads=4, max_len=2 * T,
                                       pipeline_stack=True)
        loss = layers.mean(layers.softmax_with_cross_entropy(
            layers.reshape(logits, shape=[-1, vocab]),
            layers.reshape(tgt, shape=[-1, 1])))
        pt.optimizer.AdamOptimizer(learning_rate=3e-3).minimize(
            loss, startup_program=startup)

    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)

    rng = np.random.RandomState(0)
    steps = 10 if FAST else 120
    for step in range(steps):
        seq = synthetic_corpus(rng, vocab, n=32, T=T)
        lo, = exe.run(main_prog,
                      feed={"ids": seq[:, :-1], "tgt": seq[:, 1:]},
                      fetch_list=[loss], scope=scope)
        if step % 20 == 0 or step == steps - 1:
            print(f"step {step}: loss {float(lo):.4f}")

    # greedy generation through the KV-cache decode path: a sibling
    # program that rejoins the trained weights by name (startup never run)
    n_new = 8
    gen_prog, gen_startup = pt.Program(), pt.Program()
    with pt.program_guard(gen_prog, gen_startup):
        prompt = layers.data("prompt", shape=[T], dtype="int64")
        out_ids = models.transformer_lm_generate(
            prompt, vocab_size=vocab, d_model=d_model, n_layers=n_layers,
            num_heads=4, max_len=2 * T, max_new_tokens=n_new)
    ctx = synthetic_corpus(rng, vocab, n=1, T=T)[:, :-1]
    gen, = exe.run(gen_prog, feed={"prompt": ctx}, fetch_list=[out_ids],
                   scope=scope)
    gen = np.asarray(gen)[0]
    tail = gen[-(n_new + 1):]
    # the language allows next in {3t, 3t+1} mod vocab: judge each
    # generated step against the rule applied to ITS OWN predecessor
    # (an independent chain would diverge at the first +1 branch)
    ok = [int(tail[i + 1]) in {(3 * int(tail[i])) % vocab,
                               (3 * int(tail[i]) + 1) % vocab}
          for i in range(n_new)]
    print("generated continuation:", gen[-n_new:].tolist())
    print(f"rule-consistent steps: {sum(ok)}/{n_new}")

    # the other decoder over the same trained weights: beam search
    # (best-first with scores)
    alt_prog, alt_startup = pt.Program(), pt.Program()
    with pt.program_guard(alt_prog, alt_startup):
        prompt2 = layers.data("prompt2", shape=[T], dtype="int64")
        beams, scores = models.transformer_lm_beam_search(
            prompt2, vocab_size=vocab, d_model=d_model,
            n_layers=n_layers, num_heads=4, max_len=2 * T,
            max_new_tokens=n_new, beam_size=3)
    # alt_startup is never run: it would re-initialize the trained weights
    bm, sc_ = exe.run(alt_prog, feed={"prompt2": ctx},
                      fetch_list=[beams, scores], scope=scope)
    bm, sc_ = np.asarray(bm), np.asarray(sc_)
    print("beam best :", bm[0, 0, -n_new:].tolist(),
          f"(score {sc_[0, 0]:.2f})")
    print("beam 2nd  :", bm[0, 1, -n_new:].tolist(),
          f"(score {sc_[0, 1]:.2f})")


if __name__ == "__main__":
    main()
