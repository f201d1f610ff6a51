"""Mixture-of-Experts op: Switch-style top-1 routing with capacity.

Capability extension beyond the reference (no MoE exists there; the closest
analogue is the sparse-parameter pserver path this replaces — SelectedRows
updates touching only some rows, /root/reference/paddle/framework/
selected_rows.h). Expert-parallel scaling: the expert-major weight tensors
[E, ...] shard their leading dim over the mesh's 'ep' axis, so each device
holds E/n experts and the dispatch/combine einsums become all-to-alls that
XLA GSPMD inserts — the TPU-native version of what a CUDA framework builds
from NCCL all-to-all.

Formulation (Switch Transformer): token -> top-1 expert via gate softmax;
per-expert capacity C = ceil(tokens/E * capacity_factor); tokens beyond an
expert's capacity are dropped (pass through the residual); dispatch and
combine are one-hot einsums, keeping everything dense/static for XLA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..kernels import grouped_matmul
from .common import amp_cast, amp_enabled, mxu_precision, out, single


__all__ = ["moe_topk", "switch_moe"]


@register_op("switch_moe", optional_inputs=("GateBias",))
def switch_moe(attrs, ins):
    """X [b, T, d]; Gate [d, E]; W1 [E, d, ff]; B1 [E, ff]; W2 [E, ff, d];
    B2 [E, d] -> Out [b, T, d] plus AuxLoss [1] (load-balance loss)."""
    x = single(ins, "X")
    wg = single(ins, "Gate")
    w1 = single(ins, "W1")
    b1 = single(ins, "B1")
    w2 = single(ins, "W2")
    b2 = single(ins, "B2")
    capacity_factor = attrs.get("capacity_factor", 1.25)
    b, T, d = x.shape
    E = wg.shape[1]
    n_tok = b * T
    cap = int(max(1, round(n_tok / E * capacity_factor)))

    xt = x.reshape(n_tok, d)
    logits = jnp.dot(xt, wg, precision=mxu_precision()).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [N, E]
    expert = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.max(probs, axis=-1)  # [N] routing weight

    # position of each token within its expert's queue (0-based)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # [N, E]
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot  # 1-based at slot
    pos = jnp.sum(pos_in_expert, axis=-1) - 1  # [N]
    keep = pos < cap

    # dispatch one-hot [N, E, C]
    dispatch = (jax.nn.one_hot(expert, E, dtype=x.dtype)[:, :, None]
                * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                                 dtype=x.dtype)[:, None, :cap])
    xe = jnp.einsum("nec,nd->ecd", dispatch, xt)  # [E, C, d]
    xe_c, w1_c = amp_cast(xe, w1)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", xe_c, w1_c,
                   precision=mxu_precision()).astype(xe.dtype)
        + b1[:, None, :])
    h_c, w2_c = amp_cast(h, w2)
    ye = jnp.einsum("ecf,efd->ecd", h_c, w2_c,
                    precision=mxu_precision()).astype(xe.dtype) \
        + b2[:, None, :]
    combine = dispatch * gate[:, None, None].astype(x.dtype)
    y = jnp.einsum("nec,ecd->nd", combine, ye)  # dropped tokens -> 0

    # Switch load-balance auxiliary loss: E * sum_e f_e * p_e
    frac_tokens = jnp.mean(onehot.astype(jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out(Out=y.reshape(b, T, d).astype(x.dtype),
               AuxLoss=aux.reshape(1))


_EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu,
                "relu2": lambda u: jnp.square(jax.nn.relu(u))}


def experts_on_kernel(n_rows: int, d: int, f: int, layer) -> bool:
    """Whether ``moe_topk`` multiplies ``n_rows`` assignment rows by [d, f]
    / [f, d] expert planes under ``layer`` with the Pallas kernel. ONE
    algorithm, two implementations by what the call can observe: on a TPU
    the serving form (``layer``: a decode tick's rows and a prefill unit's
    alike) goes to ``kernels/grouped_matmul`` (it visits only the (row
    tile, expert) pairs that hold rows; its docstring has the per-shape
    table), the train op (``layer`` None: a gradient is taken), a call of
    a few rows and the CPU keep XLA's ``ragged_dot``. The serving engine
    asks the same question of the programs it builds
    (``moe_kernel_layer_calls``)."""
    dtype = jnp.bfloat16 if amp_enabled() else jnp.float32
    return all(grouped_matmul.grouped_supported(n_rows, a, b, dtype, layer)
               for a, b in ((d, f), (f, d)))


def moe_topk(x, router_w, gate_w, up_w, down_w, k, norm_topk_prob=False,
             layer=None, act="silu", router_x=None, shared=None, held=None,
             routed_scale=1.0, score="softmax", bias=None, n_group=1,
             topk_group=1, latent=None, limit=None):
    """Dropless token-choice top-``k`` gated experts — the expert layer
    of the ``swiglu_moe`` block (ops/pipeline_ops.py calls it from the
    block's FFN half; it is not a program op of its own).

    x [N, d] (a float32 residual-stream row per token), router_w [d, E],
    gate_w / up_w [E, d, f], down_w [E, f, d] ->
    (y [N, d] in x.dtype, counts [E] int32 rows sent to each expert,
    prob_mean [E] float32 mean router probability).

    Router logits, softmax and top-k are float32. The N*k (token, choice)
    assignments are sorted by expert (stable), the sorted rows go through
    three grouped matmuls (``jax.lax.ragged_dot``: the TPU compiler turns
    it into one grouped-matmul custom call over the rows, so the cost
    grows with assignments, never with E x capacity), and the weighted
    rows are gathered back by the inverse permutation and summed over k.
    There is no capacity and no drop; every shape is static (always N*k
    rows); ``jax.grad`` goes through (the sort indices are constants of
    the backward pass). Under AMP the grouped matmuls take bf16 operands
    and accumulate in float32; weights stored in bf16 are used as they
    are, never upcast on the device.

    ``layer`` (a traced index): gate_w / up_w / down_w are then the WHOLE
    stacks [L, E, ...] and the call uses layer ``layer``'s experts —
    addressed as groups layer*E .. layer*E+E-1 of L*E (every other group
    empty), so no layer of the stack is sliced out. A scan that hands the
    per-layer slice [E, d, f] to the grouped-matmul custom call makes XLA
    COPY it first: 3 x 268 MB a layer at OLMoE's widths, 19.6 of an 82 ms
    decode tick (my chip run, PR 26). The serving ops pass ``layer``; the
    train op scans slices (its weight gradient must be one layer's). On a
    TPU the ``layer`` form multiplies on ``kernels/grouped_matmul`` instead
    (``experts_on_kernel``): the same operands and accumulation, the layer
    a prefetched scalar of the kernel's weight index, only the experts
    that took a row ever read.

    ``act``: the gate's activation (``silu``: SwiGLU, ``relu``: ReGLU).
    ``router_x`` [N, d]: what the router reads when that is not ``x`` (a
    block whose router sees the attention's input routes from norm 1's
    output while the experts take norm 2's).

    ``held`` = (first, count): gate_w / up_w / down_w hold experts ``first
    .. first + count - 1`` of the router's E only ([count, d, f], or
    [L, count, ..] under ``layer``): expert parallelism's share of the
    layer, computed without its exchange. The router still scores all E
    and picks its top-k among them; an assignment to an absent expert
    sorts behind the held ones, belongs to no group of the grouped matmul
    and adds EXACTLY zero (its rows are masked after the matmuls, whatever
    the custom call leaves beyond its groups); ``counts`` stays [E] wide,
    so the router's statistics and the engine's ``moe_dropped_tokens`` (an
    absent expert is not a drop) read as before. Nothing stands in for
    the absent chips. ``held=None`` is the call it always was.
    ``shared`` = (gate_w [d, fs], up_w [d, fs], down_w [fs, d]): an
    always-on expert of the same activation, added to every row once.
    ``routed_scale`` multiplies the routed sum (``routed_scaling_factor``).

    ``score``: ``softmax`` over the E logits, or ``sigmoid`` of each
    (DeepSeek-V3's ``noaux_tc``). ``bias`` [E] float32: the top-k is taken
    on score + bias while the weights stay the bare scores of the chosen.
    ``n_group`` > 1: the E experts are ``n_group`` equal consecutive
    groups, a group's score the sum of its two largest (biased) scores;
    only the experts of the ``topk_group`` best groups can be chosen. The
    defaults are the call it always was, bit for bit.

    ``gate_w`` None: UNGATED experts, ``act(x W_up) W_down`` (``act``
    ``relu2``: the squared ReLU), two grouped matmuls and not three; the
    shared expert is ungated alike (``shared[0]`` None). ``latent`` =
    (down_w [d, dl], up_w [dl, d]): the routed experts work in a latent of
    width dl (up_w / down_w [E, dl, f] / [E, f, dl]): ONE projection of the
    N token rows down before the sort and one of the combined rows up
    after it, shared by all experts; the router and the shared expert read
    ``x`` at the model's width.

    ``limit`` L > 0: the clamped gated form ``act(min(x W_gate, L)) *
    clip(x W_up, -L, L)``, applied between the grouped products, in the
    routed experts and the shared one alike.
    """
    N, d = x.shape
    E = router_w.shape[-1]
    x32 = x.astype(jnp.float32)
    r32 = x32 if router_x is None else router_x.astype(jnp.float32)
    logits = jnp.dot(r32, router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "sigmoid":
        probs = jax.nn.sigmoid(logits)                        # [N, E]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    if bias is None and n_group == 1:
        top_p, top_e = jax.lax.top_k(probs, k)                # [N, k]
    else:
        choice = probs if bias is None else probs + bias.astype(jnp.float32)
        if n_group > 1:
            per = choice.reshape(N, n_group, E // n_group)
            group_score = jnp.sum(jax.lax.top_k(per, 2)[0], axis=-1)
            kth = jax.lax.top_k(group_score, topk_group)[0][:, -1:]
            choice = jnp.where((group_score >= kth)[..., None], per,
                               -jnp.inf).reshape(N, E)
        top_e = jax.lax.top_k(choice, k)[1]
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    flat_e = top_e.reshape(N * k)
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    sizes, n_here, present = counts, E, None
    if held is not None:
        first, n_here = held
        present = (flat_e >= first) & (flat_e < first + n_here)
        # absent experts sort behind every held group
        flat_e = jnp.where(present, flat_e - first, n_here)
        sizes = jax.lax.dynamic_slice(counts, (first,), (n_here,))
    order = jnp.argsort(flat_e, stable=True)                  # by expert

    def dense(a, w):
        if w.dtype != a.dtype:
            w = w.astype(a.dtype)
        return jnp.dot(a, w, precision=mxu_precision(),
                       preferred_element_type=jnp.float32)

    def operand(a):     # a matmul's left operand under the AMP rule
        return a.astype(jnp.bfloat16) if amp_enabled() else a

    src = x32 if latent is None else dense(operand(x32), latent[0])
    on_kernel = experts_on_kernel(N * k, src.shape[-1], up_w.shape[-1], layer)
    if layer is not None:
        n_layers = up_w.shape[0]
        if not on_kernel:
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n_layers * n_here,), jnp.int32), sizes,
                (layer * n_here,))
        gate_w, up_w, down_w = (
            None if w is None
            else w.reshape((n_layers * n_here,) + w.shape[2:])
            for w in (gate_w, up_w, down_w))
    rows = src[order // k]                                    # [N*k, d | dl]
    if amp_enabled():
        rows = rows.astype(jnp.bfloat16)

    def grouped(a, w):
        if on_kernel:
            return grouped_matmul.grouped_matmul(
                a, w, sizes, layer=layer, precision=mxu_precision())
        if w.dtype != a.dtype:      # bf16 weights under float32 compute
            w = w.astype(a.dtype)
        return jax.lax.ragged_dot(a, w, sizes, precision=mxu_precision(),
                                  preferred_element_type=jnp.float32)

    def gated(gate, up):
        """act(gate()) * up(), each clamped under ``limit``."""
        g = gate()
        a = _EXPERT_ACTS[act](jnp.minimum(g, limit) if limit else g)
        u = up()
        return a * (jnp.clip(u, -limit, limit) if limit else u)

    if gate_w is None:
        h = _EXPERT_ACTS[act](grouped(rows, up_w))
    else:
        h = gated(lambda: grouped(rows, gate_w), lambda: grouped(rows, up_w))
    o = grouped(h.astype(rows.dtype), down_w)                 # [N*k, d] f32
    if present is not None:
        o = jnp.where(present[order][:, None], o, 0.0)
    inv = jnp.argsort(order)                                  # unsort
    y = jnp.sum(o[inv].reshape(N, k, -1) * top_p[..., None], axis=1)
    if routed_scale != 1.0:
        y = y * routed_scale
    if latent is not None:
        y = dense(operand(y), latent[1])
    if shared is not None:
        xs = operand(x32)
        s_gate, s_up, s_down = shared
        if s_gate is None:
            hs = _EXPERT_ACTS[act](dense(xs, s_up))
        else:
            hs = gated(lambda: dense(xs, s_gate), lambda: dense(xs, s_up))
        y = y + dense(hs.astype(xs.dtype), s_down)
    return y.astype(x.dtype), counts, jnp.mean(probs, axis=0)
