"""Op registry: op type name -> pure JAX kernel + metadata.

The TPU-native replacement for the reference's OpRegistry / kernel-registry
pair (/root/reference/paddle/framework/op_registry.h:148,
/root/reference/paddle/framework/operator.cc:463-556). There is no per-device
kernel selection: every op is a pure JAX function; XLA picks the TPU lowering
and fuses across op boundaries because the executor compiles whole blocks.

Shape inference (the reference's InferShape, shape_inference.h) is derived
from the kernel itself via ``jax.eval_shape`` — one source of truth.

Gradients: ops normally do NOT register hand-written grad kernels. The
backward pass (core/backward.py) emits generic ``grad`` ops whose kernel
computes ``jax.vjp`` of the registered forward. For a straight-line kernel
the forward subexpressions that the vjp traces again are CSE'd by XLA inside
the single fused computation, so this costs nothing relative to hand-written
grad ops. XLA does not merge two loops, so an op whose kernel holds one
registers ``has_loop=True``: the executor then traces it once, under
``jax.vjp``, and its grad op applies the kept closure. Ops may still register
a custom ``grad_fn`` when vjp-of-forward is wrong or wasteful (e.g. ops with
integer inputs that need SelectedRows-style sparse grads).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

Arrays = Dict[str, List[jax.Array]]  # slot -> list of arrays


@dataclasses.dataclass
class OpDef:
    type: str
    fn: Callable  # fn(attrs, ins: Arrays, [rng]) -> Arrays
    # True, False, or a predicate over the op's attrs (for ops that only
    # sometimes draw randomness, e.g. sampling vs greedy decode). When not
    # strictly False the kernel fn must accept an ``rng`` kwarg (None when
    # the predicate says this instance draws nothing).
    needs_rng: object = False
    # Custom vjp: grad_fn(attrs, ins, outs, out_grads) -> dict varslot->grads
    grad_fn: Optional[Callable] = None
    # True when grad_fn is a pure HBM/FLOP optimization and vjp-of-forward
    # is STILL mathematically valid (batch_norm/layer_norm). Such ops stay
    # eligible for recompute segments, whose composite jax.vjp ignores
    # grad_fn; ops whose grad_fn exists for correctness (rng, sparse
    # grads) must keep this False so segments never swallow them.
    grad_fn_is_optimization: bool = False
    # Ops whose semantics are stateful/structural and are handled specially by
    # the executor trace (feed/fetch/control-flow) rather than called as fns.
    special: bool = False
    # The kernel holds a lax.scan / while_loop / fori_loop. XLA cannot CSE
    # a second trace of a loop, so when append_backward pairs this op with
    # a generic ``grad`` op the executor traces it once under jax.vjp and
    # the grad op applies the kept closure (backward.traced_once).
    has_loop: bool = False
    # Input slots that may legally be absent/empty (e.g. optional Bias).
    optional_inputs: tuple = ()
    # If set, only these input slots get gradients even if others are float.
    stop_gradient_inputs: tuple = ()
    # Analytical cost handler fn(attrs, ins, outs) -> analysis.costmodel
    # OpCost, attached post-registration by paddle_tpu.analysis.costmodel
    # (register_cost) — the FLOP/HBM-byte twin of infer_outputs. Ops whose
    # cost is structurally meaningless (feed/fetch/unbounded decode loops)
    # set cost_exempt instead; the registry conformance audit requires one
    # of the two for every op.
    cost_fn: Optional[Callable] = None
    cost_exempt: bool = False


_REGISTRY: Dict[str, OpDef] = {}


def register_op(
    type: str,
    fn: Callable = None,
    *,
    needs_rng: bool = False,
    grad_fn: Callable = None,
    grad_fn_is_optimization: bool = False,
    special: bool = False,
    has_loop: bool = False,
    optional_inputs=(),
    stop_gradient_inputs=(),
):
    """Register an op kernel. Usable as decorator or direct call."""

    def _do(f):
        if type in _REGISTRY:
            raise ValueError(f"op {type!r} already registered")
        _REGISTRY[type] = OpDef(
            type=type,
            fn=f,
            needs_rng=needs_rng,
            grad_fn=grad_fn,
            grad_fn_is_optimization=grad_fn_is_optimization,
            special=special,
            has_loop=has_loop,
            optional_inputs=tuple(optional_inputs),
            stop_gradient_inputs=tuple(stop_gradient_inputs),
        )
        return f

    if fn is None:
        return _do
    return _do(fn)


def get_op(type: str) -> OpDef:
    opdef = _REGISTRY.get(type)
    if opdef is None:
        import difflib

        known = sorted(_REGISTRY)
        close = difflib.get_close_matches(type, known, n=3, cutoff=0.6)
        hint = ("; did you mean " + " / ".join(repr(c) for c in close) + "?"
                if close else "")
        sample = ", ".join(known[:8])
        raise KeyError(
            f"op {type!r} is not registered{hint} "
            f"({len(known)} ops registered, e.g. {sample}, ...; "
            f"see registry.registered_ops() for the full list)")
    return opdef


def op_uses_rng(opdef: OpDef, attrs) -> bool:
    """Does THIS op instance consume randomness? Attr-dependent ops
    declare needs_rng as a predicate; plain ops as a bool."""
    nr = opdef.needs_rng
    return bool(nr(attrs or {})) if callable(nr) else bool(nr)


def has_op(type: str) -> bool:
    return type in _REGISTRY


def registered_ops() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# infer_outputs memoization
#
# Whole-program analysis (paddle_tpu.analysis) and repeated layer_helper
# build-time calls evaluate identical (op_type, attrs, input-signature)
# triples over and over — a ResNet block stamps the same conv/BN/relu
# signatures dozens of times, and the pass-sandwich verifier re-checks a
# mostly-unchanged program after every pass. jax.eval_shape is pure in
# those inputs (plus the process-global AMP policy, which changes kernel
# compute dtypes), so the result is cached. Hit/miss counters land in the
# profiler StatSet as registry/infer_cache/{hit,miss}.
# ---------------------------------------------------------------------------
_INFER_CACHE: Dict[tuple, object] = {}
_INFER_CACHE_MAX = 8192
_INFER_HITS = 0
_INFER_MISSES = 0


class _Unfreezable(Exception):
    """Attr value with no stable hashable form; skip memoization."""


def _freeze(x):
    """Stable hashable digest of an attr value. Keys starting with '_'
    (``_callsite``, ``__fused_from__`` provenance, recompute-segment
    tags) are metadata no kernel reads — excluding them is what lets two
    ops built at different source lines share a cache entry."""
    if isinstance(x, dict):
        return tuple(sorted(
            (k, _freeze(v)) for k, v in x.items()
            if not (isinstance(k, str) and k.startswith("_"))))
    if isinstance(x, (list, tuple)):
        return tuple(_freeze(v) for v in x)
    if isinstance(x, (set, frozenset)):
        return ("<set>",) + tuple(sorted(repr(_freeze(v)) for v in x))
    if isinstance(x, np.ndarray):
        return ("<ndarray>", x.shape, str(x.dtype), hash(x.tobytes()))
    if isinstance(x, (str, int, float, bool, bytes, type(None))):
        return x
    raise _Unfreezable(repr(type(x)))


def _signature_key(op_type: str, attrs, in_shapes) -> Optional[tuple]:
    """Cache key, or None when any part has no stable digest."""
    try:
        frozen_attrs = _freeze(attrs or {})
    except _Unfreezable:
        return None
    leaves, treedef = jax.tree_util.tree_flatten(in_shapes)
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return None
        sig.append((tuple(shape), str(dtype)))
    from ..ops import common as ops_common

    return (op_type, frozen_attrs, tuple(sig), treedef,
            ops_common.amp_enabled())


def _copy_inferred(result):
    """Callers consume the result as {slot: [ShapeDtypeStruct]}; hand each
    one its own containers so a mutating caller can't poison the cache."""
    if isinstance(result, dict):
        return {k: (list(v) if isinstance(v, (list, tuple)) else v)
                for k, v in result.items()}
    return result


def infer_cache_stats() -> Dict[str, int]:
    """{'hits', 'misses', 'entries'} of the infer_outputs memo table."""
    return {"hits": _INFER_HITS, "misses": _INFER_MISSES,
            "entries": len(_INFER_CACHE)}


def clear_infer_cache() -> None:
    global _INFER_HITS, _INFER_MISSES
    _INFER_CACHE.clear()
    _INFER_HITS = 0
    _INFER_MISSES = 0


def _count_infer(kind: str) -> None:
    from .. import profiler

    profiler.global_stat.add_count(f"registry/infer_cache/{kind}", 1)


def infer_outputs(op_type: str, attrs, in_shapes: Arrays) -> Dict[str, List[jax.ShapeDtypeStruct]]:
    """Abstractly evaluate an op to get output shapes/dtypes.

    ``in_shapes`` maps slot -> list of ShapeDtypeStruct (concrete arrays
    are accepted too — only shape/dtype are read). Replaces the
    reference's per-op InferShape implementations. Results are memoized
    on (op_type, attrs digest, input signature, AMP policy); see
    ``infer_cache_stats``.
    """
    global _INFER_HITS, _INFER_MISSES
    key = _signature_key(op_type, attrs, in_shapes)
    if key is not None:
        cached = _INFER_CACHE.get(key)
        if cached is not None:
            _INFER_HITS += 1
            _count_infer("hit")
            return _copy_inferred(cached)
    result = _infer_outputs_uncached(op_type, attrs, in_shapes)
    if key is not None:
        _INFER_MISSES += 1
        _count_infer("miss")
        if len(_INFER_CACHE) >= _INFER_CACHE_MAX:
            _INFER_CACHE.clear()  # whole-table reset beats LRU bookkeeping
        _INFER_CACHE[key] = _copy_inferred(result)
    return result


def _infer_outputs_uncached(op_type: str, attrs, in_shapes: Arrays):
    opdef = get_op(op_type)
    if op_uses_rng(opdef, attrs):
        def f(ins, rng):
            return opdef.fn(attrs, ins, rng=rng)

        return jax.eval_shape(f, in_shapes,
                              jax.ShapeDtypeStruct((2,), jnp.uint32))
    if callable(opdef.needs_rng):
        return jax.eval_shape(lambda ins: opdef.fn(attrs, ins, rng=None),
                              in_shapes)
    return jax.eval_shape(lambda ins: opdef.fn(attrs, ins), in_shapes)
