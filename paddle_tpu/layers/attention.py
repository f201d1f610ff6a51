"""Attention & transformer layers (capability extension beyond the
reference — SURVEY.md §5.7; the reference's sequence modelling tops out at
LSTM/GRU + RecurrentGradientMachine).

TPU-first design notes: weights are fused (one qkv projection = one MXU
matmul), heads live in a [B, H, T, D] layout whose last dim maps to lanes,
attention is the flash kernel, and everything between the matmuls fuses
under the whole-block XLA compile.
"""
from __future__ import annotations

from ..initializer import NormalInitializer, XavierInitializer
from .layer_helper import LayerHelper
from .sequence import get_seq_len


def multi_head_attention(queries, keys=None, values=None, d_model=None,
                         num_heads=8, num_kv_heads=None, causal=False,
                         use_rope=False, sequence_parallel=False,
                         param_attr=None,
                         main_program=None, startup_program=None):
    """Multi-head attention over [b, T, d_model] sequences; self-attention
    when keys/values are omitted. Returns [b, T, d_model].

    ``num_kv_heads`` < num_heads gives grouped-query / multi-query
    attention (smaller KV projections and caches — the long-context
    serving trade); ``use_rope`` applies rotary position embedding to
    q/k heads in place of learned positions."""
    from . import tensor as T

    helper = LayerHelper("multi_head_attention", main_program=main_program,
                         startup_program=startup_program)
    keys = queries if keys is None else keys
    values = keys if values is None else values
    d_model = d_model or queries.shape[-1]
    if d_model % num_heads:
        raise ValueError(f"d_model {d_model} not divisible by heads "
                         f"{num_heads}")
    num_kv_heads = num_kv_heads or num_heads
    if num_heads % num_kv_heads:
        raise ValueError(f"num_heads {num_heads} not a multiple of "
                         f"num_kv_heads {num_kv_heads}")
    head_d = d_model // num_heads
    d_kv = head_d * num_kv_heads
    self_attn = keys is queries

    def proj(x, width, name):
        # each projection gets its own parameter: suffix a user-provided
        # name so qkv/out never collapse onto one shared weight
        from ..param_attr import ParamAttr

        attr = ParamAttr.to_attr(param_attr)
        if attr is not None and attr.name:
            import copy

            attr = copy.copy(attr)
            attr.name = f"{attr.name}.{name}"
        w = helper.create_parameter(
            attr, shape=[x.shape[-1], width], dtype=x.dtype,
            default_initializer=XavierInitializer())
        return helper.simple_op("mul", {"X": [x], "Y": [w]},
                                {"x_num_col_dims": 2})

    mp, sp = helper.main_program, helper.startup_program
    if self_attn:
        qkv = proj(queries, d_model + 2 * d_kv, "qkv")  # ONE fused matmul
        q, k, v = T.split(qkv, [d_model, d_kv, d_kv], dim=2,
                          main_program=mp, startup_program=sp)
    else:
        q = proj(queries, d_model, "q")
        k = proj(keys, d_kv, "k")
        v = proj(values, d_kv, "v")

    def heads(x, Tlen, n):
        x = T.reshape(x, [-1, Tlen, n, head_d], main_program=mp,
                      startup_program=sp)
        return T.transpose(x, [0, 2, 1, 3], main_program=mp,
                           startup_program=sp)

    tq, tk = queries.shape[1], keys.shape[1]
    qh = heads(q, tq, num_heads)
    kh = heads(k, tk, num_kv_heads)
    vh = heads(v, tk, num_kv_heads)
    if use_rope:
        qh = helper.simple_op("rotary_embed", {"X": [qh]})
        kh = helper.simple_op("rotary_embed", {"X": [kh]})
    ins = {"Q": [qh], "K": [kh], "V": [vh]}
    sl = get_seq_len(keys)
    if sl is not None:
        ins["Length"] = [sl]
    ctx = helper.simple_op("scaled_dot_product_attention", ins,
                           {"causal": causal,
                            "sequence_parallel": sequence_parallel})
    ctx = T.transpose(ctx, [0, 2, 1, 3], main_program=mp, startup_program=sp)
    ctx = T.reshape(ctx, [-1, tq, d_model], main_program=mp,
                    startup_program=sp)
    o = proj(ctx, d_model, "out")
    o.seq_len = get_seq_len(queries)
    return o


def transformer_encoder_layer(x, num_heads, d_ff, causal=False,
                              num_kv_heads=None, use_rope=False,
                              dropout_prob=0.0, sequence_parallel=False,
                              moe_experts=0, norm_type="layer_norm",
                              main_program=None,
                              startup_program=None):
    """Pre-LN transformer block: x + MHA(LN(x)); x + FFN(LN(x)).
    ``sequence_parallel`` routes attention through the ring kernel when the
    executor mesh has an 'sp' axis; ``moe_experts`` > 0 swaps the dense FFN
    for a Switch MoE (returns (out, aux_loss) in that case);
    ``norm_type="rms_norm"`` swaps both pre-norms for RMSNorm (single
    reduction, no shift — the modern LM convention)."""
    from . import nn as N

    if norm_type not in ("layer_norm", "rms_norm"):
        raise ValueError(f"norm_type must be 'layer_norm' or 'rms_norm', "
                         f"got {norm_type!r}")

    def pre_norm(t, **kw2):
        if norm_type == "rms_norm":
            return N.rms_norm(t, begin_norm_axis=2, **kw2)
        return N.layer_norm(t, begin_norm_axis=2, **kw2)

    kw = dict(main_program=main_program, startup_program=startup_program)
    d_model = x.shape[-1]
    h = pre_norm(x, **kw)
    h.seq_len = get_seq_len(x)
    attn = multi_head_attention(h, num_heads=num_heads, causal=causal,
                                num_kv_heads=num_kv_heads,
                                use_rope=use_rope,
                                sequence_parallel=sequence_parallel, **kw)
    helper = LayerHelper("transformer", **kw)
    x = helper.simple_op("elementwise_add", {"X": [x], "Y": [attn]})
    h2 = pre_norm(x, **kw)
    if moe_experts:
        ff, aux = switch_moe(h2, num_experts=moe_experts, d_ff=d_ff, **kw)
        o = helper.simple_op("elementwise_add", {"X": [x], "Y": [ff]})
        o.seq_len = get_seq_len(x)
        return o, aux
    ff = N.fc(h2, size=d_ff, num_flatten_dims=2, act="gelu", **kw)
    if dropout_prob:
        ff = N.dropout(ff, dropout_prob, **kw)
    ff = N.fc(ff, size=d_model, num_flatten_dims=2, **kw)
    o = helper.simple_op("elementwise_add", {"X": [x], "Y": [ff]})
    o.seq_len = get_seq_len(x)
    return o


def make_stack_params(helper, base, spec, param_attr=None,
                      stored_dtype=True):
    """Create (or rejoin by name) the stacked [L, ...] block weights of
    ``spec`` (an ``LMSpec``) for ``pipelined_transformer_stack`` and the
    decode ops: returns the op-input dict keyed by slot name. Names
    follow ``{base}.stack_{key}`` so sharding plans and sibling programs
    (training vs generation) address the same tensors; planes, shapes and
    dtype all come from ``spec.stack_planes()`` / ``spec.param_dtype``
    (``stored_dtype=False``: the dtype was inherited from an activation,
    so AMP's float32-master rule of ``create_parameter`` applies)."""
    import copy

    from ..initializer import ConstantInitializer
    from ..param_attr import ParamAttr

    def mk(key, shape, fan):
        attr = (ParamAttr.to_attr(param_attr) if param_attr is not None
                else ParamAttr())
        attr = copy.copy(attr)
        attr.name = f"{base}.stack_{key}"
        if fan is not None:
            init = XavierInitializer(fan_in=fan[0], fan_out=fan[1])
        else:   # vectors: norm scales start at 1, biases at 0
            init = (ConstantInitializer(1.0) if key.endswith("_s")
                    else None)
        return helper.create_parameter(
            attr, shape=[spec.plane_layers(key)] + shape,
            dtype=spec.param_dtype,
            is_bias=fan is None, default_initializer=init,
            stored_dtype=stored_dtype)

    return {slot: [mk(key, shape, fan)]
            for slot, key, shape, fan in spec.stack_planes()}


def pipelined_transformer_stack(x, n_layers=None, num_heads=None, d_ff=None,
                                num_kv_heads=None, use_rope=False,
                                causal=True, n_microbatches=None,
                                pipe_axis="pp", data_axis="dp", remat=False,
                                param_attr=None, spec=None,
                                main_program=None, startup_program=None):
    """L transformer blocks with stacked [L, ...] weights — the
    scan-over-layers form of ``transformer_encoder_layer``. One compiled
    block body regardless of depth, and the layer axis doubles as the
    pipeline-stage axis: under a mesh with a ``pp`` axis (see
    ``parallel.pipeline_plan``) the stack runs the GPipe microbatch
    schedule across stages. Names carry a ``.stack_`` marker so the plan
    can shard every stacked tensor's leading dim on ``pp``.

    The block comes from ``spec`` (an ``LMSpec``); without one the size
    keywords describe the GPT-2 block (pre-LN LayerNorm, GELU 4x FFN). A
    ``swiglu_moe`` spec returns ``(out, aux_loss)``: the load-balance
    loss summed over the layers (add ``spec.router_aux_loss_coef *
    aux_loss`` to the objective).

    ``remat``: what the layer scan keeps for its backward. ``False``:
    every interior of every layer; ``True``: the stream, the attention
    call's own residuals and the out-projection's result, in the matmuls'
    operand dtype (5 d a token a layer, bf16 under AMP: the backward
    rebuilds the attention half elementwise and runs the FFN's first
    matmul, or the expert layer, again); ``"full"``: the stream alone,
    the backward runs each layer's forward again."""
    from ..lm_spec import LMSpec
    from ..param_attr import ParamAttr

    if get_seq_len(x) is not None:
        raise NotImplementedError(
            "pipelined_transformer_stack assumes full-length sequences; "
            "padded variable-length batches should use the per-layer "
            "transformer_encoder_layer path (which masks via Length)")
    helper = LayerHelper("pipelined_transformer_stack",
                         main_program=main_program,
                         startup_program=startup_program)
    d_model = x.shape[-1]
    stated = spec is not None
    if spec is None:
        if d_model % num_heads:
            raise ValueError(f"d_model {d_model} not divisible by heads "
                             f"{num_heads}")
        if num_kv_heads and num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not a multiple of "
                             f"num_kv_heads {num_kv_heads}")
        spec = LMSpec(vocab_size=0, d_model=d_model, n_layers=n_layers,
                      num_heads=num_heads, num_kv_heads=num_kv_heads,
                      use_rope=use_rope, d_ff=d_ff, param_dtype=x.dtype)
    elif spec.d_model != d_model:
        raise ValueError(f"x is {d_model} wide, the spec {spec.d_model}")

    _given = ParamAttr.to_attr(param_attr)
    base = (_given.name if _given is not None and _given.name
            else helper.main_program.unique_name("pipe"))
    ins = {"X": [x]}
    ins.update(make_stack_params(helper, base, spec, param_attr=param_attr,
                                 stored_dtype=stated))
    attrs = {**spec.block.attrs(), "causal": causal,
             "n_microbatches": n_microbatches, "pipe_axis": pipe_axis,
             "data_axis": data_axis, "remat": remat}
    if spec.block.is_moe:
        outs, _ = helper.append_op("pipelined_transformer_stack", ins,
                                   ["Out", "AuxLoss"], attrs)
        return outs["Out"][0], outs["AuxLoss"][0]
    return helper.simple_op("pipelined_transformer_stack", ins, attrs)


def switch_moe(x, num_experts, d_ff=None, capacity_factor=1.25,
               param_attr=None, main_program=None, startup_program=None):
    """Switch-Transformer MoE FFN (top-1 routing, capacity-dropped tokens).
    Expert weights are [E, ...]-major so an 'ep' mesh axis shards experts
    (see ops/moe_ops.py). Returns (out, aux_loss) — add
    ``alpha * aux_loss`` to the training objective for load balance."""
    from ..param_attr import ParamAttr

    helper = LayerHelper("switch_moe", main_program=main_program,
                         startup_program=startup_program)
    d_model = x.shape[-1]
    d_ff = d_ff or 4 * d_model
    E = num_experts
    base = helper.main_program.unique_name("moe")

    def mk(suffix, shape, bias=False):
        # explicit names: ".expert_" marks [E, ...]-major tensors so
        # expert_parallel_plan can shard dim 0 on the 'ep' mesh axis
        attr = (ParamAttr.to_attr(param_attr) if param_attr is not None
                else ParamAttr())
        import copy

        attr = copy.copy(attr)
        attr.name = f"{base}.{suffix}"
        return helper.create_parameter(
            attr, shape=shape, dtype=x.dtype, is_bias=bias,
            default_initializer=None if bias else XavierInitializer())

    wg = mk("gate", [d_model, E])
    w1 = mk("expert_w1", [E, d_model, d_ff])
    b1 = mk("expert_b1", [E, d_ff], bias=True)
    w2 = mk("expert_w2", [E, d_ff, d_model])
    b2 = mk("expert_b2", [E, d_model], bias=True)
    outs, _ = helper.append_op(
        "switch_moe",
        {"X": [x], "Gate": [wg], "W1": [w1], "B1": [b1], "W2": [w2],
         "B2": [b2]},
        ["Out", "AuxLoss"], {"capacity_factor": capacity_factor})
    y = outs["Out"][0]
    y.seq_len = get_seq_len(x)
    return y, outs["AuxLoss"][0]
