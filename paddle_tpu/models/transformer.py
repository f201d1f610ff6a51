"""Transformer language model (decoder-only, causal).

Capability extension beyond the reference (which predates Transformers);
the flagship long-context model: flash attention on one chip,
ring-attention sequence parallelism across chips
(parallel/ring_attention.py) when T outgrows a single device.
"""
from __future__ import annotations

import numpy as np

from .. import layers
from ..layers.layer_helper import LayerHelper
from ..param_attr import ParamAttr


def transformer_lm(ids, vocab_size, d_model=256, n_layers=4, num_heads=8,
                   d_ff=None, num_kv_heads=None, use_rope=False,
                   max_len=2048, norm_type="layer_norm",
                   pipeline_stack=False, n_microbatches=None, remat=False,
                   include_head=True,
                   main_program=None, startup_program=None):
    """ids [b, T] int64 -> logits [b, T, vocab]. Pre-LN GPT-style blocks,
    learned positional embedding, weight-tied-free output head.

    ``pipeline_stack=True`` builds the blocks as one stacked-weight layer
    (scan over layers; pipeline-parallel under a 'pp' mesh axis with
    ``parallel.pipeline_plan`` — see layers.pipelined_transformer_stack).
    ``include_head=False`` returns the final-norm hidden states [b, T, d]
    instead of logits, for use with
    ``layers.fused_head_cross_entropy`` (chunked large-vocab loss that
    never materializes the logits)."""
    # validate BEFORE building anything: a raise must not leave orphan
    # embedding ops/parameters in the caller's program
    if norm_type != "layer_norm" and pipeline_stack:
        raise ValueError(
            "pipeline_stack=True supports norm_type='layer_norm' only "
            "(the stacked-weight layout and its generation/serving "
            "siblings share fixed LN parameter planes)")
    if not include_head and pipeline_stack:
        raise ValueError(
            "pipeline_stack=True requires include_head=True: the "
            "generation/serving siblings rejoin the trained head by its "
            "fixed name (lm_head.w), which only the built-in head "
            "creates — a fused_head_cross_entropy head would train "
            "under a different parameter name and serving would "
            "silently run an untrained head")
    kw = dict(main_program=main_program, startup_program=startup_program)
    d_ff = d_ff or 4 * d_model
    tok = layers.embedding(ids, size=[vocab_size, d_model],
                           param_attr=ParamAttr(name="tok_emb"), **kw)
    tok.seq_len = getattr(ids, "seq_len", None)
    T = ids.shape[1]
    helper = LayerHelper("transformer_lm", **kw)
    if use_rope:
        # positions live in the attention rotation — no learned table
        x = tok
    else:
        pos_table = helper.create_parameter(
            ParamAttr(name="pos_emb"), shape=[max_len, d_model],
            dtype="float32")
        # slice the first T rows; T is static under the whole-block compile
        pos = helper.simple_op("slice", {"X": [pos_table]},
                               {"axes": [0], "starts": [0], "ends": [T]})
        x = helper.simple_op("elementwise_add", {"X": [tok], "Y": [pos]})
        x.seq_len = tok.seq_len
    ln_attr = ln_bias = head_attr = None
    if pipeline_stack:
        # stable parameter names so a generation program (which rebuilds
        # these layers) shares the trained weights by name; one stacked
        # LM per program — the fixed names would otherwise silently alias
        if "lm_stack.stack_qkv_w" in helper.main_program.global_block.vars:
            raise ValueError(
                "transformer_lm(pipeline_stack=True) may be built only "
                "once per program: its parameter names (lm_stack.*, "
                "final_ln.*, lm_head.w) are fixed so generation programs "
                "can rejoin them, and a second stacked LM in the same "
                "program would silently share weights")
        x = layers.pipelined_transformer_stack(
            x, n_layers=n_layers, num_heads=num_heads, d_ff=d_ff,
            num_kv_heads=num_kv_heads, use_rope=use_rope, causal=True,
            n_microbatches=n_microbatches, remat=remat,
            param_attr=ParamAttr(name="lm_stack"), **kw)
        ln_attr = ParamAttr(name="final_ln.scale")
        ln_bias = ParamAttr(name="final_ln.bias")
        head_attr = ParamAttr(name="lm_head.w")
    else:
        from ..core.program import maybe_recompute

        for _ in range(n_layers):
            # remat: each block becomes one recompute segment — only its
            # matmul outputs survive to the backward (the norms'
            # grad_fn_is_optimization keeps them segment-eligible), the
            # deep-stack activation-memory lever for the per-layer path
            with maybe_recompute(remat, main_program):
                x = layers.transformer_encoder_layer(
                    x, num_heads=num_heads, d_ff=d_ff,
                    num_kv_heads=num_kv_heads, use_rope=use_rope,
                    causal=True, norm_type=norm_type, **kw)
    if norm_type == "rms_norm":
        x = layers.rms_norm(x, begin_norm_axis=2, **kw)
    else:
        x = layers.layer_norm(x, begin_norm_axis=2, param_attr=ln_attr,
                              bias_attr=ln_bias, **kw)
    if not include_head:
        return x
    logits = layers.fc(x, size=vocab_size, num_flatten_dims=2,
                       param_attr=head_attr, bias_attr=False, **kw)
    return logits


def _shared_lm_params(helper, vocab_size, d_model, d_ff, max_len,
                      n_layers, num_heads=None, num_kv_heads=None,
                      use_rope=False):
    """The weights-shared-by-name contract with transformer_lm
    (pipeline_stack=True), in ONE place: rebuild tok_emb/pos_emb/
    final_ln/lm_head/lm_stack.* so a generation-family program rejoins
    the trained tensors. Returns the op-input dict (minus Prompt)."""
    from ..initializer import ConstantInitializer
    from ..layers.attention import make_stack_params

    if num_heads and num_kv_heads and num_heads % num_kv_heads:
        raise ValueError(f"num_heads {num_heads} not a multiple of "
                         f"num_kv_heads {num_kv_heads}")
    tok = helper.create_parameter(ParamAttr(name="tok_emb"),
                                  shape=[vocab_size, d_model],
                                  dtype="float32")
    pos = None if use_rope else helper.create_parameter(
        ParamAttr(name="pos_emb"), shape=[max_len, d_model],
        dtype="float32")
    ln_s = helper.create_parameter(
        ParamAttr(name="final_ln.scale"), shape=[d_model], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    ln_b = helper.create_parameter(ParamAttr(name="final_ln.bias"),
                                   shape=[d_model], dtype="float32",
                                   is_bias=True)
    head_w = helper.create_parameter(ParamAttr(name="lm_head.w"),
                                     shape=[d_model, vocab_size],
                                     dtype="float32")
    ins = {"TokEmb": [tok], "FinalLnS": [ln_s],
           "FinalLnB": [ln_b], "HeadW": [head_w]}
    if pos is not None:
        ins["PosEmb"] = [pos]
    ins.update(make_stack_params(helper, "lm_stack", n_layers, d_model,
                                 d_ff, num_heads=num_heads,
                                 num_kv_heads=num_kv_heads))
    return ins


def transformer_lm_generate(prompt, vocab_size, d_model=256, n_layers=4,
                            num_heads=8, d_ff=None, num_kv_heads=None,
                            use_rope=False, max_len=2048,
                            max_new_tokens=32, temperature=0.0, top_k=0,
                            main_program=None, startup_program=None):
    """Generation program for a ``transformer_lm(pipeline_stack=True)``
    model: KV-cache incremental decoding
    (ops/pipeline_ops.transformer_stack_generate) — greedy by default,
    temperature/top-k sampling through the RNG plane when
    ``temperature`` > 0.

    Rebuilds the SAME named parameters (tok_emb, pos_emb, lm_stack.*,
    final_ln.*, lm_head.w) so running this program in the training scope
    serves the trained weights — do not run its startup program (that
    would re-initialize them; the pattern is the GAN demo's shared-weight
    sibling programs). prompt: [b, Tp] int64 -> [b, Tp + max_new_tokens].
    """
    kw = dict(main_program=main_program, startup_program=startup_program)
    d_ff = d_ff or 4 * d_model
    helper = LayerHelper("transformer_lm_generate", **kw)
    ins = {"Prompt": [prompt]}
    ins.update(_shared_lm_params(helper, vocab_size, d_model, d_ff,
                                 max_len, n_layers, num_heads,
                                 num_kv_heads, use_rope))
    o = helper.simple_op("transformer_stack_generate", ins,
                         {"num_heads": num_heads,
                          "num_kv_heads": num_kv_heads,
                          "use_rope": use_rope,
                          "max_new_tokens": max_new_tokens,
                          "temperature": float(temperature),
                          "top_k": int(top_k)})
    o.stop_gradient = True
    return o


def transformer_lm_beam_search(prompt, vocab_size, d_model=256, n_layers=4,
                               num_heads=8, d_ff=None, num_kv_heads=None,
                               use_rope=False, max_len=2048,
                               max_new_tokens=32, beam_size=4,
                               length_penalty=0.0, eos_id=None,
                               main_program=None, startup_program=None):
    """Beam-search generation for a ``transformer_lm(pipeline_stack=True)``
    model (ops/pipeline_ops.transformer_stack_beam_search). Same
    shared-parameter contract as ``transformer_lm_generate``. Returns
    (ids [b, K, Tp+N] best-first, scores [b, K])."""
    kw = dict(main_program=main_program, startup_program=startup_program)
    d_ff = d_ff or 4 * d_model
    helper = LayerHelper("transformer_lm_beam_search", **kw)
    ins = {"Prompt": [prompt]}
    ins.update(_shared_lm_params(helper, vocab_size, d_model, d_ff,
                                 max_len, n_layers, num_heads,
                                 num_kv_heads, use_rope))
    outs, _ = helper.append_op(
        "transformer_stack_beam_search", ins, ["Out", "Scores"],
        {"num_heads": num_heads, "num_kv_heads": num_kv_heads,
         "use_rope": use_rope,
         "max_new_tokens": max_new_tokens,
         "beam_size": beam_size, "length_penalty": float(length_penalty),
         "eos_id": -1 if eos_id is None else int(eos_id)})
    ids = outs["Out"][0]
    scores = outs["Scores"][0]
    ids.stop_gradient = True
    scores.stop_gradient = True
    return ids, scores


def transformer_lm_speculative_generate(prompt, vocab_size, d_model=256,
                                        n_layers=4, num_heads=8, d_ff=None,
                                        num_kv_heads=None, use_rope=False,
                                        max_len=2048, max_new_tokens=32,
                                        draft_layers=None, gamma=4,
                                        main_program=None,
                                        startup_program=None):
    """Self-speculative greedy decoding for a
    ``transformer_lm(pipeline_stack=True)`` model: the first
    ``draft_layers`` of the SAME stack plus a small draft head
    (draft_ln.*, draft_head.w — train it separately, e.g. on the frozen
    stack) propose ``gamma`` tokens per round, and the full stack verifies
    them in one block-causal pass. Output is EXACTLY the plain greedy
    decode (acceptance keeps only tokens the full stack argmaxes); the
    draft only buys fewer full-stack passes. Returns (ids [b, Tp+N],
    rounds [1] — plain decode would take N).

    EXPERIMENTAL (status, PERF.md "speculative decoding"): correctness is
    pinned (tests/test_generate.py) and a trained draft head cuts verify
    rounds well below N on the CPU mesh, but the only wall-clock A/B on
    record (r3 chip, UNtrained model — zero acceptance) was a 2.4x
    slowdown. Until a trained-model A/B on the chip records a speedup
    > 1 (ROADMAP D4), prefer plain ``transformer_lm_generate`` in
    production."""
    from ..initializer import ConstantInitializer

    kw = dict(main_program=main_program, startup_program=startup_program)
    d_ff = d_ff or 4 * d_model
    draft_layers = draft_layers or max(1, n_layers // 2)
    helper = LayerHelper("transformer_lm_speculative_generate", **kw)
    ins = {"Prompt": [prompt]}
    ins.update(_shared_lm_params(helper, vocab_size, d_model, d_ff,
                                 max_len, n_layers, num_heads,
                                 num_kv_heads, use_rope))
    ins["DraftLnS"] = [helper.create_parameter(
        ParamAttr(name="draft_ln.scale"), shape=[d_model],
        dtype="float32", default_initializer=ConstantInitializer(1.0))]
    ins["DraftLnB"] = [helper.create_parameter(
        ParamAttr(name="draft_ln.bias"), shape=[d_model], dtype="float32",
        is_bias=True)]
    ins["DraftHeadW"] = [helper.create_parameter(
        ParamAttr(name="draft_head.w"), shape=[d_model, vocab_size],
        dtype="float32")]
    outs, _ = helper.append_op(
        "transformer_stack_speculative_generate", ins, ["Out", "Rounds"],
        {"num_heads": num_heads, "num_kv_heads": num_kv_heads,
         "use_rope": use_rope, "max_new_tokens": max_new_tokens,
         "draft_layers": int(draft_layers), "gamma": int(gamma)})
    ids = outs["Out"][0]
    rounds = outs["Rounds"][0]
    ids.stop_gradient = True
    rounds.stop_gradient = True
    return ids, rounds
