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


def _legacy_spec(spec, vocab_size, d_model, n_layers, num_heads, d_ff,
                 num_kv_heads, use_rope, max_len):
    """The ``LMSpec`` of a call: the one given, or the GPT-2 block at the
    size keywords (the form every call had before there was a spec)."""
    from ..lm_spec import LMSpec

    if spec is not None:
        return spec
    if vocab_size is None:
        raise ValueError("pass spec= (an LMSpec) or vocab_size and sizes")
    return LMSpec(vocab_size=vocab_size, d_model=d_model, n_layers=n_layers,
                  num_heads=num_heads, num_kv_heads=num_kv_heads,
                  use_rope=use_rope, max_len=max_len, d_ff=d_ff)


def _lm_param(helper, spec, name):
    """One of the stacked LM's five fixed-name parameters outside the
    stack, in the spec's stored dtype. ``create_parameter`` rejoins a
    name that exists, so a layer called right after with the same name
    (embedding, layer_norm / rms_norm, fc) uses THIS parameter."""
    from ..initializer import ConstantInitializer

    d, V = spec.d_model, spec.vocab_size
    shape, init, is_bias = {
        "tok_emb": ([V, d], None, False),
        "pos_emb": ([spec.max_len, d], None, False),
        "final_ln.scale": ([d], ConstantInitializer(1.0), False),
        "final_ln.bias": ([d], None, True),
        "lm_head.w": ([d, V], None, False),
    }[name]
    return helper.create_parameter(
        ParamAttr(name=name), shape=shape, dtype=spec.param_dtype,
        is_bias=is_bias, default_initializer=init, stored_dtype=True)


def transformer_lm(ids, vocab_size=None, d_model=256, n_layers=4,
                   num_heads=8, d_ff=None, num_kv_heads=None, use_rope=False,
                   max_len=2048, norm_type="layer_norm",
                   pipeline_stack=False, n_microbatches=None, remat=False,
                   include_head=True, spec=None,
                   main_program=None, startup_program=None):
    """ids [b, T] int64 -> logits [b, T, vocab]. Pre-norm GPT-style
    blocks, weight-tied-free output head.

    ``pipeline_stack=True`` builds the blocks as one stacked-weight layer
    (scan over layers; pipeline-parallel under a 'pp' mesh axis with
    ``parallel.pipeline_plan`` — see layers.pipelined_transformer_stack).
    The stacked model is described by ``spec`` (an ``LMSpec``: norm,
    QK-norm, positions, FFN kind, dtypes — the same object the serving
    engines take); without one the size keywords give the GPT-2 block. A
    ``swiglu_moe`` spec returns ``(logits, aux_loss)``: add
    ``spec.router_aux_loss_coef * aux_loss`` to the objective.
    ``include_head=False`` returns the final-norm hidden states [b, T, d]
    instead of logits, for use with
    ``layers.fused_head_cross_entropy`` (chunked large-vocab loss that
    never materializes the logits)."""
    # validate BEFORE building anything: a raise must not leave orphan
    # embedding ops/parameters in the caller's program
    if spec is not None and not pipeline_stack:
        raise ValueError("spec= describes the stacked model: pass "
                         "pipeline_stack=True")
    if norm_type != "layer_norm" and pipeline_stack:
        raise ValueError(
            "pipeline_stack=True takes its norm from spec= "
            "(LMSpec(norm='rms_norm', ...)), not from norm_type")
    if not include_head and pipeline_stack:
        raise ValueError(
            "pipeline_stack=True requires include_head=True: the "
            "generation/serving siblings rejoin the trained head by its "
            "fixed name (lm_head.w), which only the built-in head "
            "creates — a fused_head_cross_entropy head would train "
            "under a different parameter name and serving would "
            "silently run an untrained head")
    kw = dict(main_program=main_program, startup_program=startup_program)
    helper = LayerHelper("transformer_lm", **kw)
    if pipeline_stack:
        return _stacked_lm(helper, ids, _legacy_spec(
            spec, vocab_size, d_model, n_layers, num_heads, d_ff,
            num_kv_heads, use_rope, max_len), n_microbatches, remat, kw)
    d_ff = d_ff or 4 * d_model
    tok = layers.embedding(ids, size=[vocab_size, d_model],
                           param_attr=ParamAttr(name="tok_emb"), **kw)
    tok.seq_len = getattr(ids, "seq_len", None)
    T = ids.shape[1]
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
        x = layers.layer_norm(x, begin_norm_axis=2, **kw)
    if not include_head:
        return x
    return layers.fc(x, size=vocab_size, num_flatten_dims=2,
                     bias_attr=False, **kw)


def _stacked_lm(helper, ids, spec, n_microbatches, remat, kw):
    """The stacked form of ``transformer_lm``: embedding (+ learned
    positions), ``pipelined_transformer_stack(spec=)``, final norm, untied
    head — every parameter under its fixed name (tok_emb, pos_emb,
    lm_stack.*, final_ln.*, lm_head.w) and in the spec's stored dtype, so
    a generation program (which rebuilds them from the same spec) shares
    the trained weights by name."""
    # one stacked LM per program — the fixed names would otherwise
    # silently alias
    if "lm_stack.stack_ln1_s" in helper.main_program.global_block.vars:
        raise ValueError(
            "transformer_lm(pipeline_stack=True) may be built only "
            "once per program: its parameter names (lm_stack.*, "
            "final_ln.*, lm_head.w) are fixed so generation programs "
            "can rejoin them, and a second stacked LM in the same "
            "program would silently share weights")
    _lm_param(helper, spec, "tok_emb")
    tok = layers.embedding(ids, size=[spec.vocab_size, spec.d_model],
                           param_attr=ParamAttr(name="tok_emb"), **kw)
    if spec.param_dtype != "float32":
        tok = layers.cast(tok, "float32", **kw)   # float32 residual stream
    tok.seq_len = getattr(ids, "seq_len", None)
    T = ids.shape[1]
    if spec.use_rope:
        # positions live in the attention rotation — no learned table
        x = tok
    else:
        pos_table = _lm_param(helper, spec, "pos_emb")
        # slice the first T rows; T is static under the whole-block compile
        pos = helper.simple_op("slice", {"X": [pos_table]},
                               {"axes": [0], "starts": [0], "ends": [T]})
        x = helper.simple_op("elementwise_add", {"X": [tok], "Y": [pos]})
        x.seq_len = tok.seq_len
    x = layers.pipelined_transformer_stack(
        x, spec=spec, causal=True, n_microbatches=n_microbatches,
        remat=remat, param_attr=ParamAttr(name="lm_stack"), **kw)
    aux = None
    if spec.block.is_moe:
        x, aux = x
    _lm_param(helper, spec, "final_ln.scale")
    if spec.norm == "rms_norm":
        x = layers.rms_norm(x, begin_norm_axis=2, epsilon=spec.norm_eps,
                            param_attr=ParamAttr(name="final_ln.scale"),
                            **kw)
    else:
        _lm_param(helper, spec, "final_ln.bias")
        x = layers.layer_norm(x, begin_norm_axis=2, epsilon=spec.norm_eps,
                              param_attr=ParamAttr(name="final_ln.scale"),
                              bias_attr=ParamAttr(name="final_ln.bias"),
                              **kw)
    _lm_param(helper, spec, "lm_head.w")
    logits = layers.fc(x, size=spec.vocab_size, num_flatten_dims=2,
                       param_attr=ParamAttr(name="lm_head.w"),
                       bias_attr=False, **kw)
    return logits if aux is None else (logits, aux)


def _shared_lm_params(helper, spec):
    """The weights-shared-by-name contract with transformer_lm
    (pipeline_stack=True), in ONE place: rebuild tok_emb/pos_emb/
    final_ln/lm_head/lm_stack.* of ``spec`` so a generation-family
    program rejoins the trained tensors. Returns the op-input dict (minus
    Prompt)."""
    from ..layers.attention import make_stack_params

    ins = {"TokEmb": [_lm_param(helper, spec, "tok_emb")]}
    if not spec.use_rope:
        ins["PosEmb"] = [_lm_param(helper, spec, "pos_emb")]
    ins["FinalLnS"] = [_lm_param(helper, spec, "final_ln.scale")]
    if spec.norm == "layer_norm":
        ins["FinalLnB"] = [_lm_param(helper, spec, "final_ln.bias")]
    ins["HeadW"] = [_lm_param(helper, spec, "lm_head.w")]
    ins.update(make_stack_params(helper, "lm_stack", spec))
    return ins


def draft_params(helper, spec):
    """The drafting block's parameters (``LMSpec(draft_block=True)``)
    under their fixed names — ``mtp.<key>`` and its one-layer stack
    ``mtp_stack.stack_<key>`` — as the paged ops' input slots; {} for a
    spec without one."""
    from ..initializer import ConstantInitializer, XavierInitializer
    from ..layers.attention import make_stack_params
    from ..lm_spec import DRAFT_SLOT_PREFIX

    if not spec.draft_block:
        return {}
    ins = {}
    for slot, key, shape, fan in spec.draft_planes():
        init = (XavierInitializer(fan_in=fan[0], fan_out=fan[1])
                if fan is not None else ConstantInitializer(1.0))
        ins[slot] = [helper.create_parameter(
            ParamAttr(name=f"mtp.{key}"), shape=shape,
            dtype=spec.param_dtype, is_bias=fan is None,
            default_initializer=init, stored_dtype=True)]
    block = make_stack_params(helper, "mtp_stack", spec.draft_spec())
    ins.update({DRAFT_SLOT_PREFIX + slot: v for slot, v in block.items()})
    return ins


def vision_params(helper, spec):
    """The vision tower's and the merger's parameters (``LMSpec(vision=)``)
    under their fixed names ``vision.<key>`` as the paged prefill op's input
    slots; {} for a spec without a tower."""
    from ..initializer import ConstantInitializer, XavierInitializer

    ins = {}
    for slot, key, shape, fan, _ in spec.vision_planes():
        init = (XavierInitializer(fan_in=fan[0], fan_out=fan[1])
                if fan is not None
                else ConstantInitializer(1.0 if key.endswith("_s") else 0.0))
        ins[slot] = [helper.create_parameter(
            ParamAttr(name=f"vision.{key}"), shape=shape,
            dtype=spec.param_dtype, is_bias=fan is None,
            default_initializer=init, stored_dtype=True)]
    return ins


def lm_parameters(spec, main_program=None, startup_program=None):
    """Declare the parameters of ``spec``'s stacked LM under their fixed
    names (tok_emb, final_ln.*, lm_head.w, lm_stack.stack_*; a drafting
    block's mtp.* and mtp_stack.stack_*) and nothing else: running the
    startup program then initialises a scope that ``GenerationEngine(spec,
    scope)`` serves. For a spec only the paged ops run (a stack held by
    attention kind, a dense head under layer kinds), whose weights no train
    or one-shot generation program can declare. Returns the op-input
    dict."""
    helper = LayerHelper("lm_parameters", main_program=main_program,
                         startup_program=startup_program)
    return {**_shared_lm_params(helper, spec), **draft_params(helper, spec),
            **vision_params(helper, spec)}


def transformer_lm_generate(prompt, vocab_size=None, d_model=256,
                            n_layers=4, num_heads=8, d_ff=None,
                            num_kv_heads=None, use_rope=False, max_len=2048,
                            max_new_tokens=32, temperature=0.0, top_k=0,
                            spec=None,
                            main_program=None, startup_program=None):
    """Generation program for a ``transformer_lm(pipeline_stack=True)``
    model: KV-cache incremental decoding
    (ops/pipeline_ops.transformer_stack_generate) — greedy by default,
    temperature/top-k sampling through the RNG plane when
    ``temperature`` > 0. ``spec`` (an ``LMSpec``) describes the model;
    without one the size keywords give the GPT-2 block.

    Rebuilds the SAME named parameters (tok_emb, pos_emb, lm_stack.*,
    final_ln.*, lm_head.w) so running this program in the training scope
    serves the trained weights — do not run its startup program (that
    would re-initialize them; the pattern is the GAN demo's shared-weight
    sibling programs). prompt: [b, Tp] int64 -> [b, Tp + max_new_tokens].
    """
    kw = dict(main_program=main_program, startup_program=startup_program)
    spec = _legacy_spec(spec, vocab_size, d_model, n_layers, num_heads,
                        d_ff, num_kv_heads, use_rope, max_len)
    helper = LayerHelper("transformer_lm_generate", **kw)
    ins = {"Prompt": [prompt]}
    ins.update(_shared_lm_params(helper, spec))
    # a drafting block is declared (so the startup program seeds it) and
    # not run: the one-shot op emits the stack's own tokens
    draft_params(helper, spec)
    o = helper.simple_op("transformer_stack_generate", ins,
                         {**spec.block.attrs(),
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
    helper = LayerHelper("transformer_lm_beam_search", **kw)
    ins = {"Prompt": [prompt]}
    ins.update(_shared_lm_params(helper, _legacy_spec(
        None, vocab_size, d_model, n_layers, num_heads, d_ff, num_kv_heads,
        use_rope, max_len)))
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
