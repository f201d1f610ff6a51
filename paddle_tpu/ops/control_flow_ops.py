"""Control-flow ops: static_rnn (scan), while (while_loop), tensor arrays,
and a fused beam-search decoder.

TPU-native replacement for the reference's control-flow machinery:
- recurrent_op.cc:222 (StepScopes per-timestep sub-scope execution)
- while_op.cc (sub-block interpreted until a cond var flips)
- lod_tensor_to_array / array ops (LoDTensorArray plumbing for dynamic RNN)
- beam_search_op.cc + beam_search_decode_op.cc, and the legacy
  RecurrentGradientMachine::generateSequence/beamSearch
  (gserver/gradientmachines/RecurrentGradientMachine.h:307-309)

The reference executes sub-blocks with a per-op interpreter inside step
scopes. Here a sub-block is *data*: the layer builders (layers/control_flow.py)
serialize the body's ops (type/inputs/outputs/attrs — all plain values) into
the parent op's attrs, and the kernel re-materialises the body as a pure JAX
function evaluated under ``lax.scan`` / ``lax.while_loop``. That keeps these
ops ordinary pure kernels — so ``static_rnn`` is reverse-differentiable
through ``lax.scan`` and the generic vjp backward works unchanged, with no
executor special-casing and no StepScope state.

Body-op environment contract (shared by static_rnn/while):
  x_names     — per-step values (sliced from time axis / loop-carried)
  mem_names   — loop-carried state, seeded from MemInit
  param_names — external reads (weights etc.), constant across steps
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from ..core.registry import get_op, register_op
from .common import maybe, out
from .sequence_ops import time_mask


def run_body(body_ops: List[dict], env: Dict[str, jax.Array]) -> Dict:
    """Execute serialized body ops over an env dict (pure; traceable)."""
    for od in body_ops:
        opdef = get_op(od["type"])
        if opdef.needs_rng or opdef.special:
            raise NotImplementedError(
                f"op {od['type']!r} cannot run inside a control-flow body")
        ins = {slot: [env[n] for n in names]
               for slot, names in od["inputs"].items() if names}
        outs = opdef.fn(od["attrs"], ins)
        for slot, names in od["outputs"].items():
            vals = outs.get(slot, [])
            for n, v in zip(names, vals):
                env[n] = v
    return env


@register_op("static_rnn", has_loop=True,
             optional_inputs=("X", "MemInit", "Param", "Length"))
def static_rnn(attrs, ins):
    """User-defined recurrence over the time axis (recurrent_op.cc:222).

    Sequence inputs [b, T, ...] are sliced per step; memories carry across
    steps; per-step outputs are re-stacked to [b, T, ...]. With Length,
    finished rows freeze their memories and zero their outputs (LoD
    semantics, same masking as the lstm/gru kernels).
    """
    xs = ins.get("X", [])
    mem_init = ins.get("MemInit", [])
    params = ins.get("Param", [])
    lengths = maybe(ins, "Length")
    body_ops = attrs["body_ops"]
    x_names = attrs["x_names"]
    mem_names = attrs["mem_names"]
    mem_out_names = attrs["mem_out_names"]
    out_names = attrs["out_names"]
    param_names = attrs["param_names"]

    T = xs[0].shape[1] if xs else attrs["seq_len_static"]
    base_env = dict(zip(param_names, params))
    xs_tm = [jnp.swapaxes(x, 0, 1) for x in xs]  # time-major
    mask_tm = (jnp.swapaxes(time_mask(lengths, T, mem_init[0].dtype
                                      if mem_init else jnp.float32), 0, 1)
               if lengths is not None else None)

    def step(carry, slices):
        if mask_tm is not None:
            xt, m = slices
        else:
            xt, m = (slices if slices is not None else ()), None
        env = dict(base_env)
        env.update(zip(x_names, xt))
        env.update(zip(mem_names, carry))
        env = run_body(body_ops, env)
        new_carry = []
        for old, name in zip(carry, mem_out_names):
            new = env[name]
            if m is not None:
                mm = m.reshape(m.shape + (1,) * (new.ndim - 1))
                new = mm * new + (1 - mm) * old
            new_carry.append(new)
        step_outs = []
        for name in out_names:
            y = env[name]
            if m is not None:
                mm = m.reshape(m.shape + (1,) * (y.ndim - 1))
                y = y * mm.astype(y.dtype)
            step_outs.append(y)
        return tuple(new_carry), tuple(step_outs)

    if mask_tm is None:
        seq = tuple(xs_tm) if xs_tm else None
        carry, ys = jax.lax.scan(step, tuple(mem_init), seq,
                                 length=None if xs_tm else T)
    else:
        carry, ys = jax.lax.scan(step, tuple(mem_init),
                                 (tuple(xs_tm), mask_tm))
    outputs = [jnp.swapaxes(y, 0, 1) for y in ys]
    return {"Out": outputs, "LastMem": list(carry)}


@register_op("while", has_loop=True, optional_inputs=("Param",))
def while_op(attrs, ins):
    """Functional while (while_op.cc): body runs until the carried cond var
    is false. Carried vars are the loop state; the body must reassign each
    (typically via ``assign``/arithmetic writing the same name).

    Two lowerings:
    - ``max_iters`` set -> a fixed-trip ``lax.scan`` where steps whose cond
      has gone false pass the carry through unchanged. This is
      reverse-differentiable, so while-graphs TRAIN — the TPU answer to the
      reference differentiating while sub-blocks
      (/root/reference/paddle/framework/backward.cc:415 MakeBlockBackward).
      The trip count is static (compiler-friendly); inactive tail steps are
      masked no-ops.
    - otherwise -> ``lax.while_loop`` with true early exit (decode-side
      loops: beam search, generation). Not reverse-differentiable; pass
      max_iters if the loop must be trained through.
    """
    carried_in = ins["Carried"]
    params = ins.get("Param", [])
    body_ops = attrs["body_ops"]
    carried_names = attrs["carried_names"]
    param_names = attrs["param_names"]
    cond_name = attrs["cond_name"]
    max_iters = attrs.get("max_iters")
    base_env = dict(zip(param_names, params))
    cond_pos = carried_names.index(cond_name)

    def body_fn(carry):
        env = dict(base_env)
        env.update(zip(carried_names, carry))
        env = run_body(body_ops, env)
        return tuple(env[n] for n in carried_names)

    if max_iters is not None:
        def step(carry, _):
            active = jnp.reshape(carry[cond_pos], ()).astype(bool)
            new = body_fn(carry)
            merged = tuple(
                jnp.where(active, n, o) for n, o in zip(new, carry))
            return merged, None

        final, _ = jax.lax.scan(step, tuple(carried_in), None,
                                length=int(max_iters))
        return {"Out": list(final)}

    def cond_fn(carry):
        return jnp.reshape(carry[cond_pos], ())

    final = jax.lax.while_loop(cond_fn, body_fn, tuple(carried_in))
    return {"Out": list(final)}


@register_op("array_write")
def array_write(attrs, ins):
    """Write X into Array (a [max_len, ...] buffer) at scalar Index
    (functional LoDTensorArray write, tensor_array_read_write ops)."""
    x = ins["X"][0]
    i = jnp.reshape(ins["I"][0], ()).astype(jnp.int32)
    arr = ins["Array"][0]
    return out(Out=jax.lax.dynamic_update_index_in_dim(arr, x, i, axis=0))


@register_op("array_read")
def array_read(attrs, ins):
    i = jnp.reshape(ins["I"][0], ()).astype(jnp.int32)
    arr = ins["Array"][0]
    return out(Out=jax.lax.dynamic_index_in_dim(arr, i, axis=0,
                                                keepdims=False))


@register_op("beam_search_decoder",
             optional_inputs=("InitCell", "Bias", "OutBias"))
def beam_search_decoder(attrs, ins):
    """Fused in-graph beam-search generation with a GRU or LSTM cell.

    The TPU-native fusion of the reference's decode loop — while_op +
    beam_search_op (top-k prune) + beam_search_decode_op (backtrack), and the
    legacy RecurrentGradientMachine::beamSearch — into one op: a
    lax.while_loop over at most max_len steps with the whole beam resident
    on-chip; each step is one [b*beam, h] x [h, gates] MXU matmul + top-k.
    Early exit when every beam has emitted EOS (the reference's
    eos-pruning, RecurrentGradientMachine.cpp:98-117).

    Inputs:
      InitState [b, h]   — decoder initial hidden state
      InitCell  [b, h]   — (LSTM only) initial cell state
      Embedding [V, e]   — target-side embedding table
      WeightX   [e, G*h] — input->gates projection (G=3 GRU, G=4 LSTM)
      WeightH   [h, G*h] — hidden->gates recurrence
      Bias      [1, G*h]
      WeightOut [h, V], OutBias [V] — output projection to vocab logits

    Outputs: Ids [b, beam, max_len] int32 (post-BOS tokens, padded with
    eos_id), SeqScores [b, beam] total log-prob (best first), SeqLen
    [b, beam] int32 generated lengths (excluding EOS).
    """
    init_h = ins["InitState"][0]
    init_c = maybe(ins, "InitCell")
    emb = ins["Embedding"][0]
    wx = ins["WeightX"][0]
    wh = ins["WeightH"][0]
    bias = maybe(ins, "Bias")
    w_out = ins["WeightOut"][0]
    b_out = maybe(ins, "OutBias")

    beam = int(attrs.get("beam_size", 4))
    max_len = int(attrs.get("max_len", 32))
    bos = int(attrs.get("bos_id", 0))
    eos = int(attrs.get("eos_id", 1))
    cell_kind = attrs.get("cell", "gru")
    b, h = init_h.shape
    V = emb.shape[0]
    neg_inf = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)

    def cell_step(tok, hc):
        """One decoder cell step over flattened [b*beam] rows."""
        x = emb[tok]  # [N, e]
        hs, cs = hc
        gates_x = jnp.dot(x, wx)
        if bias is not None:
            gates_x = gates_x + bias
        if cell_kind == "gru":
            gx, cx = gates_x[..., : 2 * h], gates_x[..., 2 * h:]
            g = jax.nn.sigmoid(gx + jnp.dot(hs, wh[:, : 2 * h]))
            u, r = g[..., :h], g[..., h:]
            cand = jnp.tanh(cx + jnp.dot(r * hs, wh[:, 2 * h:]))
            new_h = (1.0 - u) * hs + u * cand
            return new_h, (new_h, cs)
        gates = gates_x + jnp.dot(hs, wh)
        gc, gi, gf, go = jnp.split(gates, 4, axis=-1)
        c_new = jax.nn.sigmoid(gf) * cs + jax.nn.sigmoid(gi) * jnp.tanh(gc)
        new_h = jax.nn.sigmoid(go) * jnp.tanh(c_new)
        return new_h, (new_h, c_new)

    # State over [b, beam] lattices.
    hs0 = jnp.broadcast_to(init_h[:, None], (b, beam, h))
    cs0 = (jnp.broadcast_to(init_c[:, None], (b, beam, h))
           if init_c is not None else jnp.zeros_like(hs0))
    # Only beam 0 is live at t=0 (all beams start identical).
    scores0 = jnp.where(jnp.arange(beam)[None, :] == 0, 0.0, neg_inf)
    scores0 = jnp.broadcast_to(scores0, (b, beam)).astype(jnp.float32)
    state0 = (
        jnp.zeros((b, beam), jnp.bool_),             # finished
        scores0,                                     # cumulative log-prob
        jnp.full((b, beam), bos, jnp.int32),         # last token
        (hs0, cs0),                                  # cell state
        jnp.full((b, beam, max_len), eos, jnp.int32),  # emitted ids
        jnp.zeros((b, beam), jnp.int32),             # lengths
        jnp.asarray(0, jnp.int32),                   # t
    )

    def cond(state):
        finished, _, _, _, _, _, t = state
        return jnp.logical_and(t < max_len, ~jnp.all(finished))

    def step(state):
        finished, scores, last, (hs, cs), ids, lens, t = state
        flat = lambda a: a.reshape((b * beam,) + a.shape[2:])
        logit_h, (new_h, new_c) = cell_step(flat(last), (flat(hs), flat(cs)))
        logits = jnp.dot(logit_h, w_out)
        if b_out is not None:
            logits = logits + b_out
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(b, beam, V)
        # Finished beams may only "emit" EOS at zero cost — keeps exactly one
        # live continuation per finished beam (beam_search_op.cc prune).
        eos_only = jnp.full((V,), neg_inf).at[eos].set(0.0)
        logp = jnp.where(finished[..., None], eos_only[None, None, :], logp)
        cand = scores[..., None] + logp  # [b, beam, V]
        top_scores, top_idx = jax.lax.top_k(cand.reshape(b, beam * V), beam)
        src_beam = top_idx // V  # [b, beam]
        tok = (top_idx % V).astype(jnp.int32)

        take = lambda a: jnp.take_along_axis(
            a, src_beam.reshape((b, beam) + (1,) * (a.ndim - 2)), axis=1)
        new_h = take(new_h.reshape(b, beam, h))
        new_c = take(new_c.reshape(b, beam, h))
        ids = take(ids)
        lens = jnp.take_along_axis(lens, src_beam, axis=1)
        was_fin = jnp.take_along_axis(finished, src_beam, axis=1)
        ids = jnp.where((jnp.arange(max_len) == t)[None, None, :]
                        & ~was_fin[..., None], tok[..., None], ids)
        now_fin = was_fin | (tok == eos)
        lens = jnp.where(~was_fin & (tok != eos), lens + 1, lens)
        return (now_fin, top_scores, tok, (new_h, new_c), ids, lens, t + 1)

    finished, scores, _, _, ids, lens, _ = jax.lax.while_loop(
        cond, step, state0)
    return out(Ids=ids, SeqScores=scores, SeqLen=lens)


@register_op("cond", optional_inputs=("Param",))
def cond_op(attrs, ins):
    """Functional two-branch conditional (cond_op.cc / if_else design doc):
    scalar Cond picks which serialized branch runs under lax.cond. Both
    branches must write the same output names (attrs out_names); inputs are
    the union of branch reads (Param slot)."""
    pred = jnp.reshape(ins["Cond"][0], ()).astype(bool)
    params = ins.get("Param", [])
    param_names = attrs["param_names"]
    out_names = attrs["out_names"]
    base_env = dict(zip(param_names, params))

    def branch(body_ops):
        def fn(env):
            env = dict(env)
            env = run_body(body_ops, env)
            return tuple(env[n] for n in out_names)
        return fn

    outs = jax.lax.cond(pred, branch(attrs["true_ops"]),
                        branch(attrs["false_ops"]), base_env)
    return {"Out": list(outs)}
