"""Symbolic backward-pass construction over the Program IR.

The TPU-native analogue of the reference's AppendBackward
(/root/reference/paddle/framework/backward.cc:523) and the python
append_backward_ops (/root/reference/python/paddle/v2/fluid/backward.py):
walks the block in reverse from the loss, emits one gradient op per forward
op, and sum-accumulates fan-out gradients, naming grad variables
``<var>@GRAD`` exactly like the reference.

Where the reference needs a hand-written GradOpDescMaker + grad kernel per op
(grad_op_desc_maker.h), we emit a generic ``grad`` op whose kernel computes
``jax.vjp`` of the registered forward. For a straight-line kernel the forward
subexpressions the vjp traces again are CSE'd by XLA inside the single fused
block computation, so this is free at run time and guarantees
analytically-consistent gradients for every op. XLA does not merge two
loops: the forward op's ``while`` (no residuals) and the vjp's forward
``while`` (stacks residuals) would both run. So a forward op whose definition
says ``has_loop`` shares a ``__vjp_key__`` with its ``grad`` op, and the
executor traces it once (``traced_once``: ``jax.vjp``, closure kept in the
trace environment) and has the grad op apply the kept closure (``grad_kept``)
— the pairing ``seg_fwd``/``grad_seg`` have, without their checkpoint. A
program that holds the forward op and not its grad op (inference clones,
pruned exports, serving) runs the plain kernel. Ops with randomness or custom
sparse grads register an explicit ``grad_fn`` and get a ``grad_custom`` op
instead.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp

from .program import (GRAD_SUFFIX, Block, Operator, Program, Variable,
                      grad_var_name)
from .registry import get_op, register_op, op_uses_rng
from .types import is_floating

# Ops after which there is nothing to differentiate.
NON_DIFFERENTIABLE = {
    "fill_constant", "gaussian_random", "uniform_random", "feed", "fetch",
    "accuracy", "top_k", "assign_value", "fill_constant_batch_size_like",
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "ftrl", "proximal_gd", "proximal_adagrad",
    "equal", "not_equal", "less_than", "less_equal", "greater_than",
    "greater_equal", "logical_and", "logical_or", "logical_not", "logical_xor",
    "argmax", "one_hot", "truncated_gaussian_random",
    "gaussian_random_batch_size_like",
    # decode-side: generation is not trained through
    "beam_search_decoder",
}
# NOTE: "while" IS differentiable when built with max_iters (fixed-trip
# scan lowering); unbounded whiles on a loss path raise jax's
# while_loop-not-differentiable error at compile time.


# --------------------------------------------------------------------------
# Generic grad kernels
# --------------------------------------------------------------------------
def _rebuild_ins(attrs, ins):
    """Reconstruct the forward op's input dict from the grad op's I: slots."""
    return {slot: ins["I:" + slot] for slot in attrs["in_slots"] if "I:" + slot in ins}


def _vjp_of_forward(opdef, fwd_attrs, primal, diff_mask, out_slots):
    """ONE trace of a forward op under ``jax.vjp`` w.r.t. the inputs
    ``diff_mask`` marks -> (outs, float_pos, leaves, vjp): the op's whole
    output dict, the (slot, index) of each floating output among
    ``out_slots``, those outputs' values, and the closure over them."""
    # Split inputs into differentiated leaves and fixed leaves.
    diff_ins = {
        slot: [a for a, d in zip(primal[slot], diff_mask[slot]) if d]
        for slot in diff_mask
        if any(diff_mask[slot])
    }

    def merge(d_ins):
        full = {}
        for slot, arrs in primal.items():
            mask = diff_mask.get(slot)
            if not mask or not any(mask):
                full[slot] = list(arrs)
                continue
            it = iter(d_ins[slot])
            full[slot] = [next(it) if d else a for a, d in zip(arrs, mask)]
        return full

    float_pos: List[Tuple[str, int]] = []

    def f(d_ins):
        o = opdef.fn(fwd_attrs, merge(d_ins))
        float_pos[:] = [
            (slot, i)
            for slot in out_slots
            for i in range(len(o.get(slot, [])))
            if is_floating(o[slot][i].dtype)
        ]
        return [o[s][i] for (s, i) in float_pos], o

    leaves, vjp, outs = jax.vjp(f, diff_ins, has_aux=True)
    return outs, float_pos, leaves, vjp


def _input_grads(attrs, ins, float_pos, leaves, vjp):
    """Apply ``vjp`` to the grad op's OG: inputs -> its IG: outputs."""
    # Build cotangents aligned with float_pos; missing grads are zeros.
    og_arrays: Dict[str, List] = {}
    for slot, mask in attrs["og"].items():
        arrs = iter(ins.get("OG:" + slot, []))
        og_arrays[slot] = [next(arrs) if m else None for m in mask]
    cts = []
    for (slot, i), leaf in zip(float_pos, leaves):
        g = og_arrays.get(slot, [None] * (i + 1))[i] if slot in og_arrays else None
        cts.append(g.astype(leaf.dtype) if g is not None else jnp.zeros_like(leaf))
    (gins,) = vjp(cts)
    return {"IG:" + slot: list(arrs) for slot, arrs in gins.items()}


@register_op("grad")
def generic_grad(attrs, ins):
    """vjp-of-forward gradient kernel.

    attrs:
      fwd_type, fwd_attrs — the forward op
      in_slots  — {slot: n_inputs} of the forward op
      out_slots — [slot, ...] deterministic output slot order
      og        — {slot: [bool per output]} which outputs have incoming grads
      diff      — {slot: [bool per input]} which inputs need gradients
    """
    _, float_pos, leaves, vjp = _vjp_of_forward(
        get_op(attrs["fwd_type"]), attrs["fwd_attrs"],
        _rebuild_ins(attrs, ins), attrs["diff"], attrs["out_slots"])
    return _input_grads(attrs, ins, float_pos, leaves, vjp)


# A forward op whose kernel holds a loop (OpDef.has_loop) and the ``grad``
# op append_backward emitted for it carry the same value under this attr.
VJP_KEY_ATTR = "__vjp_key__"
_OP_VJP_PREFIX = "@OPVJP@"


def vjp_pairs(ops) -> Dict[str, dict]:
    """{pair key: the grad op's attrs} for every forward op of ``ops``
    that is followed, in ``ops``, by the ``grad`` op sharing its key —
    the ops the executor traces once. A forward op whose grad op is not
    in the block (an inference clone, a pruned export) is not in it."""
    fwd_type: Dict[str, str] = {}
    pairs: Dict[str, dict] = {}
    for op in ops:
        key = op.attrs.get(VJP_KEY_ATTR)
        if key is None:
            continue
        if op.type != "grad":
            fwd_type[key] = op.type
        elif fwd_type.get(key) == op.attrs["fwd_type"]:
            pairs[key] = op.attrs
    return pairs


def drop_unpaired_keys(ops) -> bool:
    """Take the pair key off every op of ``ops`` whose partner is not in
    it (what a prune leaves of a train program) -> whether any went."""
    pairs = vjp_pairs(ops)
    dangling = [op for op in ops if VJP_KEY_ATTR in op.attrs
                and op.attrs[VJP_KEY_ATTR] not in pairs]
    for op in dangling:
        op.attrs = {k: v for k, v in op.attrs.items() if k != VJP_KEY_ATTR}
    return bool(dangling)


def traced_once(op, ins, grad_attrs, env):
    """Run a paired forward op as the primal half of ``jax.vjp`` — the
    same kernel on the same inputs, so the same outputs — and keep the
    closure in the trace environment for ``grad_kept``. The op's own
    attrs (a stack's ``remat``) still decide what its loop saves."""
    outs, float_pos, leaves, vjp = _vjp_of_forward(
        get_op(op.type), op.attrs, ins, grad_attrs["diff"],
        grad_attrs["out_slots"])
    env[_OP_VJP_PREFIX + op.attrs[VJP_KEY_ATTR]] = (float_pos, leaves, vjp)
    return outs


def grad_kept(attrs, ins, env):
    """The paired ``grad`` op: applies the closure ``traced_once`` kept
    instead of tracing the forward (and its loop) a second time."""
    return _input_grads(attrs, ins, *env[_OP_VJP_PREFIX + attrs[VJP_KEY_ATTR]])


# Outputs of these op types are saved across forward->backward inside a
# recompute segment (program.recompute_guard); everything else — BN applies,
# activations, residual adds — is rematerialized in the backward, where XLA
# fuses the recompute into the consuming kernels instead of round-tripping
# the intermediate through HBM. MXU ops are saved because recomputing them
# costs real FLOPs; tiny (ndim<=1) tensors are saved because storing them is
# free and recomputing them needs a full reduction over a big operand.
SEGMENT_SAVE_OPS = {
    "conv2d", "conv2d_transpose", "conv3d", "conv3d_transpose",
    "depthwise_conv2d", "mul", "matmul", "pool2d", "pool3d",
    "max_pool2d_with_index", "max_pool3d_with_index", "sequence_conv",
    "lstm", "gru",
}

_SEG_RESIDUAL = "seg_saved"
_SEG_VJP_PREFIX = "@SEGVJP@"


@register_op("seg_fwd", special=True)
def segment_forward(attrs, ins, *, executor=None, env=None, op=None,
                    program=None, scope=None):
    """Forward of a whole recompute segment as ONE composite call.

    Emitted by append_backward in place of the segment's individual forward
    ops (program.recompute_guard). Runs ``jax.vjp`` of the composite under
    ``jax.checkpoint`` with a save-only-named-residuals policy: matmul/conv
    outputs and tiny (ndim<=1) stats are the only values that survive to the
    backward; every other intermediate (BN applies, activations, residual
    adds) dies as soon as the forward consumes it and is rematerialized —
    fused into the consuming kernels — inside the paired ``grad_seg`` op.
    The vjp closure is stashed in the trace environment under a key only the
    paired grad op knows, so the forward is computed exactly once.

    attrs:
      seg_ops   — [{type, attrs, ins, outs}] the original forward ops
      ext_in    — external input names, aligned with the I slot
      diff      — bool per ext_in: which inputs receive gradients
      all_outs  — every segment output name, aligned with the O slot
      vjp_key   — env key for the vjp closure
    """
    from jax.ad_checkpoint import checkpoint_name

    ext = attrs["ext_in"]
    diff = attrs["diff"]
    vals = ins["I"]
    fixed = {n: v for n, v, d in zip(ext, vals, diff) if not d}
    dvals = {n: v for n, v, d in zip(ext, vals, diff) if d}

    def f(dins):
        local = dict(fixed)
        local.update(dins)
        for sop in attrs["seg_ops"]:
            opdef = get_op(sop["type"])
            op_ins = {slot: [local[n] for n in names]
                      for slot, names in sop["ins"].items() if names}
            outs = opdef.fn(sop["attrs"], op_ins)
            save_all = sop["type"] in SEGMENT_SAVE_OPS
            for slot, names in sop["outs"].items():
                for name, v in zip(names, outs.get(slot, [])):
                    if save_all or getattr(v, "ndim", 2) <= 1:
                        v = checkpoint_name(v, _SEG_RESIDUAL)
                    local[name] = v
        return [local[n] for n in attrs["all_outs"]]

    f_ck = jax.checkpoint(
        f, policy=jax.checkpoint_policies.save_only_these_names(_SEG_RESIDUAL))
    outs, vjp_fn = jax.vjp(f_ck, dvals)
    env[_SEG_VJP_PREFIX + attrs["vjp_key"]] = (vjp_fn, outs)
    return {"O": outs}


@register_op("grad_seg", special=True)
def segment_grad(attrs, ins, *, executor=None, env=None, op=None,
                 program=None, scope=None):
    """Backward of a recompute segment: applies the vjp closure stashed by
    the paired ``seg_fwd`` op.

    attrs:
      vjp_key   — env key of the closure
      ext_in / diff — as in seg_fwd (IG slot order = diff'ed ext_in order)
      og_outs   — names (subset of seg_fwd's all_outs) aligned with OG
      all_outs  — seg_fwd's output order, to place cotangents
    """
    vjp_fn, outs = env[_SEG_VJP_PREFIX + attrs["vjp_key"]]
    og_map = dict(zip(attrs["og_outs"], ins["OG"]))
    cts = []
    for name, o in zip(attrs["all_outs"], outs):
        g = og_map.get(name)
        cts.append(g.astype(o.dtype) if g is not None else jnp.zeros_like(o))
    (gins,) = vjp_fn(cts)
    dnames = [n for n, d in zip(attrs["ext_in"], attrs["diff"]) if d]
    return {"IG": [gins[n] for n in dnames]}


@register_op("grad_custom")
def custom_grad(attrs, ins):
    """Dispatch to an op's registered grad_fn (ops with rng/sparse grads)."""
    opdef = get_op(attrs["fwd_type"])
    fwd_attrs = attrs["fwd_attrs"]
    primal = _rebuild_ins(attrs, ins)
    outs = {slot: ins["O:" + slot] for slot in attrs["out_slots"] if "O:" + slot in ins}
    og_mask = attrs["og"]
    ogs = {}
    for slot, mask in og_mask.items():
        arrs = iter(ins.get("OG:" + slot, []))
        vals = [next(arrs) if m else None for m in mask]
        if any(m for m in mask):
            ogs[slot] = vals
    grads = opdef.grad_fn(fwd_attrs, primal, outs, ogs)
    result = {}
    diff_mask = attrs["diff"]
    for slot, mask in diff_mask.items():
        if not any(mask):
            continue
        vals = grads.get(slot, [None] * len(mask))
        picked = []
        for idx, (v, d) in enumerate(zip(vals, mask)):
            if not d:
                continue
            if v is None:  # grad_fn declined: zero gradient
                v = jnp.zeros_like(primal[slot][idx])
            picked.append(v)
        result["IG:" + slot] = picked
    return result


# --------------------------------------------------------------------------
# append_backward
# --------------------------------------------------------------------------
def _is_float_var(block: Block, name: str) -> bool:
    if not block.has_var(name):
        return True  # unknown vars: assume float tensors
    return is_floating(block.var(name).dtype)


def append_backward(
    loss: Variable,
    parameter_list: Optional[Sequence[str]] = None,
    no_grad_set: Optional[Set[str]] = None,
) -> List[Tuple[Variable, Variable]]:
    """Append gradient ops for ``loss`` to its program's global block.

    Returns [(param, grad_var)] pairs, matching fluid's contract used by
    Optimizer.minimize (reference optimizer.py / backward.py).
    """
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())

    # 1. Find ops on the path to the loss (forward ops only — grad ops are
    # appended below and must not be revisited).
    n_fwd = len(block.ops)
    relevant: Set[str] = {loss.name}
    op_needed = [False] * n_fwd
    for i in range(n_fwd - 1, -1, -1):
        op = block.ops[i]
        if any(n in relevant for n in op.output_names()):
            if op.type in NON_DIFFERENTIABLE:
                continue
            op_needed[i] = True
            for name in op.input_names():
                if _is_float_var(block, name) and name not in no_grad:
                    var = block.var(name) if block.has_var(name) else None
                    if var is not None and var.stop_gradient and not var.is_parameter:
                        continue
                    relevant.add(name)

    # 2. Count grad contributions per var (outputs consumed by needed ops).
    contributions: Dict[str, List[str]] = {}

    # 3. Seed: d loss / d loss = 1.
    loss_grad_name = grad_var_name(loss.name)
    # declared shape must match the fill_constant below exactly — a ()
    # loss declares a () seed, not (1,) (the whole-program checker pins
    # declared-vs-inferred agreement)
    block.create_var(name=loss_grad_name,
                     shape=loss.shape if loss.shape is not None else (),
                     dtype=loss.dtype, stop_gradient=True)
    block.append_op(
        "fill_constant",
        outputs={"Out": [loss_grad_name]},
        attrs={"shape": list(loss.shape or ()), "value": 1.0,
               "dtype": str(loss.dtype)},
    )
    contributions[loss.name] = [loss_grad_name]
    finalized: Dict[str, Optional[str]] = {}

    def finalize_grad(name: str) -> Optional[str]:
        """Emit accumulation op if needed; returns grad var name or None."""
        if name in finalized:
            return finalized[name]
        contribs = contributions.get(name, [])
        gname = grad_var_name(name)
        if not contribs:
            result = None
        elif len(contribs) == 1:
            result = contribs[0]
        else:
            block.create_var(name=gname, stop_gradient=True)
            block.append_op("sum", inputs={"X": contribs}, outputs={"Out": [gname]})
            result = gname
        finalized[name] = result
        return result

    def add_contribution(name: str, gname: str):
        contributions.setdefault(name, []).append(gname)

    # The program is not SSA: in-place patterns (assign-into, the while
    # op's carried write-back) re-write existing names. Two consequences
    # for the reverse walk (the reference sidesteps both by renaming in
    # AppendBackward, /root/reference/paddle/framework/backward.cc:523):
    #
    # (a) gradient accounting is per-VERSION: once the writing op's output
    #     grads are taken, the name reverts to its previous definition, so
    #     its contribution/finalize state must be cleared (kill_versions);
    # (b) grad ops execute after ALL forward ops, so any primal value a
    #     grad op reads must be snapshotted before the overwrite if some
    #     op at/after the forward op's position re-writes that name
    #     (last_write + @PRE snapshots below).
    last_write: Dict[str, int] = {}
    for pos in range(n_fwd):
        for names in block.ops[pos].outputs.values():
            for name in names:
                last_write[name] = pos

    canonical_first: Dict[str, str] = {}

    def kill_versions(op):
        for names in op.outputs.values():
            for name in names:
                # Keep the latest version's grad for the canonical
                # ``<var>@GRAD`` alias (step 5): in the reverse walk the
                # first kill of a name belongs to its last write.
                g = finalized.get(name)
                if g is not None and name not in canonical_first:
                    canonical_first[name] = g
                contributions.pop(name, None)
                finalized.pop(name, None)

    def _seg_eligible(op) -> bool:
        """May this op be folded into a composite recompute-segment grad?"""
        if op.type in NON_DIFFERENTIABLE:
            return False
        opdef = get_op(op.type)
        return not (opdef.special or opdef.needs_rng
                    or (opdef.grad_fn is not None
                        and not opdef.grad_fn_is_optimization))

    def _diffable_input(name: str) -> bool:
        ok = (name in relevant and _is_float_var(block, name)
              and name not in no_grad)
        if ok and block.has_var(name):
            v = block.var(name)
            if v.stop_gradient and not v.is_parameter:
                ok = False
        return ok

    def _emit_segment_grad(j: int, i: int) -> None:
        """Differentiate block.ops[j..i] (one recompute segment): replace the
        forward run with one composite ``seg_fwd`` op and append the paired
        ``grad_seg``. No primal snapshots are needed — the vjp closure
        captures the segment inputs at their forward position, before any
        later in-place overwrite."""
        run = block.ops[j:i + 1]
        seg_ops_desc = []
        written: Set[str] = set()
        ext_in: List[str] = []
        ext_set: Set[str] = set()
        all_outs: List[str] = []
        for op2 in run:
            for names in op2.inputs.values():
                for name in names:
                    if name not in written and name not in ext_set:
                        ext_set.add(name)
                        ext_in.append(name)
            for name in op2.output_names():
                written.add(name)
                all_outs.append(name)
            seg_ops_desc.append({
                "type": op2.type,
                "attrs": dict(op2.attrs),
                "ins": {s: list(v) for s, v in op2.inputs.items()},
                "outs": {s: list(v) for s, v in op2.outputs.items()},
            })
        # Keep only the final version of names written more than once: that
        # is the version visible outside the segment.
        seen: Set[str] = set()
        dedup: List[str] = []
        for name in reversed(all_outs):
            if name not in seen:
                seen.add(name)
                dedup.append(name)
        all_outs = list(reversed(dedup))
        # OG for segment outputs (grads contributed by already-processed
        # later ops).
        og_outs, og_vars = [], []
        for name in all_outs:
            g = finalize_grad(name)
            if g is not None:
                og_outs.append(name)
                og_vars.append(g)
        for op2 in reversed(run):
            kill_versions(op2)
        diff = [_diffable_input(n) for n in ext_in]
        vjp_key = program.unique_name("seg")
        seg_attrs = {"seg_ops": seg_ops_desc, "ext_in": list(ext_in),
                     "diff": list(diff), "all_outs": all_outs,
                     "vjp_key": vjp_key}
        fwd_op = Operator(block, "seg_fwd",
                          inputs={"I": list(ext_in)},
                          outputs={"O": list(all_outs)},
                          attrs=seg_attrs)
        block.ops[j:i + 1] = [fwd_op]
        program._bump()
        if not og_outs or not any(diff):
            return
        ig_vars = []
        for name, d in zip(ext_in, diff):
            if not d:
                continue
            gvar = program.unique_name(grad_var_name(name) + "@R")
            block.create_var(name=gvar, stop_gradient=True)
            add_contribution(name, gvar)
            ig_vars.append(gvar)
        block.append_op(
            "grad_seg",
            inputs={"OG": og_vars},
            outputs={"IG": ig_vars},
            attrs={"vjp_key": vjp_key, "ext_in": list(ext_in),
                   "diff": list(diff), "og_outs": og_outs,
                   "all_outs": all_outs},
        )

    # 4. Walk forward ops in reverse, emitting grad ops. Contiguous runs of
    # ops tagged by program.recompute_guard collapse into one grad_seg op.
    i = n_fwd - 1
    while i >= 0:
        op = block.ops[i]
        if not op_needed[i]:
            kill_versions(op)
            i -= 1
            continue
        if op.type == "seg_fwd":
            raise NotImplementedError(
                "append_backward over a program that already contains a "
                "compiled recompute segment (seg_fwd): differentiate each "
                "loss from its own program build (clone before the first "
                "minimize), or disable recompute_guard for multi-loss "
                "programs")
        seg = op.attrs.get("__recompute_seg__")
        if seg is not None and _seg_eligible(op):
            j = i
            while j > 0 and (
                    block.ops[j - 1].attrs.get("__recompute_seg__") == seg
                    and op_needed[j - 1]
                    and _seg_eligible(block.ops[j - 1])):
                j -= 1
            _emit_segment_grad(j, i)
            i = j - 1
            continue
        opdef = get_op(op.type)

        out_slots = sorted(op.outputs)
        og_mask = {}
        og_inputs = {}
        any_og = False
        for slot in out_slots:
            mask = []
            arrs = []
            for name in op.outputs[slot]:
                g = finalize_grad(name)
                mask.append(g is not None)
                if g is not None:
                    arrs.append(g)
                    any_og = True
            og_mask[slot] = mask
            if arrs:
                og_inputs["OG:" + slot] = arrs
        kill_versions(op)
        if not any_og:
            i -= 1
            continue

        diff_mask = {}
        ig_outputs = {}
        for slot, names in op.inputs.items():
            mask = []
            outs_for_slot = []
            for name in names:
                ok = _diffable_input(name)
                mask.append(ok)
                if ok:
                    g = program.unique_name(grad_var_name(name) + "@R")
                    # Single-contribution grads keep the canonical name.
                    outs_for_slot.append((name, g))
            diff_mask[slot] = mask
            if outs_for_slot:
                ig_outputs[slot] = outs_for_slot
        if not ig_outputs:
            i -= 1
            continue

        use_custom = opdef.grad_fn is not None
        if op_uses_rng(opdef, op.attrs) and not use_custom:
            raise NotImplementedError(
                f"op {op.type!r} uses randomness and has no custom grad_fn"
            )

        # (b) above: snapshot primal INPUTS whose name is re-written by
        # this or any later op (the grad op would otherwise read the
        # post-overwrite value), and — for custom grads that take O: slots
        # — primal OUTPUTS overwritten strictly later. Snapshots are
        # assigns inserted at the op's position (inputs) / right after it
        # (outputs); XLA elides the copies.
        in_names = {n for names in op.inputs.values() for n in names}
        snap = {}
        for name in sorted(in_names):
            if last_write.get(name, -1) >= i:
                sname = program.unique_name(name + "@PRE")
                block.create_var(name=sname, stop_gradient=True)
                block.insert_op(i, "assign", inputs={"X": [name]},
                                outputs={"Out": [sname]})
                snap[name] = sname
        osnap = {}
        if use_custom:
            out_names = {n for names in op.outputs.values() for n in names}
            for name in sorted(out_names):
                if last_write.get(name, -1) > i:
                    sname = program.unique_name(name + "@POST")
                    block.create_var(name=sname, stop_gradient=True)
                    block.insert_op(i + 1 + len(snap), "assign",
                                    inputs={"X": [name]},
                                    outputs={"Out": [sname]})
                    osnap[name] = sname

        grad_inputs = {("I:" + slot): [snap.get(n, n) for n in names]
                       for slot, names in op.inputs.items() if names}
        if use_custom:
            for slot, names in op.outputs.items():
                if names:
                    grad_inputs["O:" + slot] = [osnap.get(n, n)
                                                for n in names]
        grad_inputs.update(og_inputs)

        grad_outputs = {}
        for slot, pairs in ig_outputs.items():
            slot_outs = []
            for name, gvar in pairs:
                block.create_var(name=gvar, stop_gradient=True)
                slot_outs.append(gvar)
                add_contribution(name, gvar)
            grad_outputs["IG:" + slot] = slot_outs

        grad_attrs = {
            "fwd_type": op.type,
            "fwd_attrs": dict(op.attrs),
            "in_slots": {slot: len(names) for slot, names in op.inputs.items()},
            "out_slots": out_slots,
            "og": og_mask,
            "diff": diff_mask,
        }
        if (opdef.has_loop and not use_custom
                and VJP_KEY_ATTR not in op.attrs):
            # XLA would run the forward's loop and the vjp's forward loop
            # both: pair the two ops so the executor traces the forward
            # once (traced_once / grad_kept). A second loss over the same
            # forward op keeps the generic grad.
            key = program.unique_name("vjp")
            op.attrs = dict(op.attrs, **{VJP_KEY_ATTR: key})
            grad_attrs[VJP_KEY_ATTR] = key
        block.append_op(
            "grad_custom" if use_custom else "grad",
            inputs=grad_inputs,
            outputs=grad_outputs,
            attrs=grad_attrs,
        )
        i -= 1

    # 5. Finalize remaining contributions (producer-less vars: feeds/params)
    # and give every finalized grad its canonical ``<var>@GRAD`` alias so
    # users and transforms can fetch it by name. Unfetched grads are DCE'd by
    # XLA, so unused aliases cost nothing.
    for name in list(contributions):
        g = finalize_grad(name)
        canonical_first.setdefault(name, g)
    # Multi-version names resolve to the LATEST version's grad (recorded at
    # its first kill in the reverse walk) — the value the loss consumed.
    for name, g in canonical_first.items():
        canonical = grad_var_name(name)
        if g is not None and g != canonical and not block.has_var(canonical):
            src = block.var(name) if block.has_var(name) else None
            block.create_var(name=canonical,
                             shape=src.shape if src is not None else None,
                             dtype=src.dtype if src is not None else "float32",
                             stop_gradient=True)
            block.append_op("assign", inputs={"X": [g]},
                            outputs={"Out": [canonical]})

    # 6. Collect (param, grad) pairs.
    params = (
        [block.var(n) for n in parameter_list]
        if parameter_list
        else block.all_parameters()
    )
    result = []
    for p in params:
        g = finalize_grad(p.name)
        if g is None:
            continue
        canonical = grad_var_name(p.name)
        if not block.has_var(canonical):  # single direct contribution
            block.create_var(name=canonical, shape=p.shape, dtype=p.dtype,
                             stop_gradient=True)
            block.append_op("assign", inputs={"X": [g]},
                            outputs={"Out": [canonical]})
        result.append((p, block.var(canonical)))
    return result
