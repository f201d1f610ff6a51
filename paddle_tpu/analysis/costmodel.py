"""Per-op analytical cost model: FLOPs, HBM bytes, arithmetic intensity.

The static half of PERF.md's roofline methodology: every registered
kernel gets a cost handler — registered on the OpDef exactly like
``infer_outputs`` derives shapes from the kernel — that maps the op's
abstract input/output ``ShapeDtypeStruct``s to an :class:`OpCost`
(FLOPs + HBM bytes touched). ``registry conformance`` (audit_op) makes
the coverage a contract: a newly registered op without a handler or an
explicit ``cost_exempt`` marker fails ``tests/test_registry_conformance``
at registration quality.

Two deliberate modeling choices, both calibrated against PERF.md's
measured ResNet-50 bs256 step (78.4 GB by ``cost_analysis``):

- **fusion discount**: XLA fuses elementwise chains into their
  producers, so a unary elementwise op charges only its output write
  (the read rides the producer's epilogue), binaries charge one operand
  stream + the write, and assign/reshape-class aliases are free (XLA
  elides the copies — the @PRE snapshots and @GRAD canonical aliases).
  Counting full in+out bytes per op over-estimates a conv/BN/ReLU stack
  by ~2x.
- **backward stream accounting**: a generic ``grad`` op emits one XLA
  kernel per LARGE gradient (dX and dW), each re-streaming the incoming
  cotangent (the round-3 profile: backward dots carry ~4x the forward's
  bytes), plus its gradient writes and one saved-primal re-read.

Handlers are approximations with ~20% honesty, not instruction counts;
the ``bench_memplan`` bench records estimated-vs-``cost_analysis`` drift
per release so the model cannot rot silently.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np

from ..core import registry
from ..core.registry import get_op, has_op

# Published per-chip peaks, keyed by the ``device_kind`` jax reports:
# (dense bf16 FLOP/s, HBM bytes/s). Source: Google Cloud documentation,
# "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s; ridge ~240 FLOP/byte. THE one
# table: the live MFU gauge (trace/goodput.py) and bench.py resolve the
# running device through it, and a kind that is not here has no peak —
# add the row with its source rather than defaulting.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
}
# the static analyzer models a NAMED v5e whatever device runs the analysis
V5E_PEAK_FLOPS, V5E_HBM_BW = DEVICE_PEAKS["TPU v5 lite"]


def device_peaks(device_kind: str):
    """(peak bf16 FLOP/s, HBM bytes/s) of ``device_kind``; KeyError naming
    the kind when it is not in :data:`DEVICE_PEAKS`."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to "
            f"analysis.costmodel.DEVICE_PEAKS with its source") from None


@dataclasses.dataclass
class OpCost:
    """One op's analytic cost: FLOPs executed and HBM bytes touched
    (reads + writes, post fusion discount). ``residual_bytes`` is the
    forward->backward residual footprint kernels keep *internally*
    (scan-over-layers activation stacks) — invisible to name-level
    liveness, added by the memory analyzer from fwd op to paired grad."""

    flops: float = 0.0
    bytes: float = 0.0
    residual_bytes: float = 0.0

    @property
    def intensity(self) -> float:
        """Arithmetic intensity in FLOP/byte (inf for zero-byte ops)."""
        if self.bytes <= 0:
            return float("inf") if self.flops > 0 else 0.0
        return self.flops / self.bytes

    def step_seconds(self, peak_flops: float = V5E_PEAK_FLOPS,
                     hbm_bw: float = V5E_HBM_BW) -> float:
        """Roofline time: bound by compute or bandwidth, whichever binds."""
        return max(self.flops / peak_flops, self.bytes / hbm_bw)

    def __add__(self, other: "OpCost") -> "OpCost":
        return OpCost(self.flops + other.flops, self.bytes + other.bytes,
                      self.residual_bytes + other.residual_bytes)


# --------------------------------------------------------------------------
# Registration plane (mirrors infer_outputs: handlers live on the OpDef)
# --------------------------------------------------------------------------
def register_cost(op_type: str, fn: Callable = None):
    """Attach a cost handler ``fn(attrs, ins, outs) -> OpCost`` to a
    registered op (``ins``/``outs`` map slot -> [ShapeDtypeStruct] with
    batch dims already concrete). Decorator or direct call."""

    def _do(f):
        opdef = get_op(op_type)
        if opdef.cost_fn is not None:
            raise ValueError(f"op {op_type!r} already has a cost handler")
        opdef.cost_fn = f
        opdef.cost_exempt = False
        return f

    if fn is None:
        return _do
    return _do(fn)


def cost_exempt(*op_types: str) -> None:
    """Mark ops as deliberately outside the cost model (structural ops
    the executor interprets, unbounded decode loops). The conformance
    audit accepts the marker in place of a handler."""
    for t in op_types:
        get_op(t).cost_exempt = True


def has_cost(op_type: str) -> bool:
    ensure_registered()
    return has_op(op_type) and get_op(op_type).cost_fn is not None


def is_cost_exempt(op_type: str) -> bool:
    ensure_registered()
    return has_op(op_type) and get_op(op_type).cost_exempt


def op_cost(op_type: str, attrs, ins, outs) -> Optional[OpCost]:
    """Evaluate the registered handler; None for exempt/uncovered ops.
    A handler crash degrades to None — the cost plane must never turn a
    lintable program into an exception."""
    if not has_cost(op_type):
        return None
    try:
        return get_op(op_type).cost_fn(attrs or {}, ins, outs)
    except Exception:
        return None


# --------------------------------------------------------------------------
# Shape helpers
# --------------------------------------------------------------------------
def _nbytes(sds) -> float:
    leaves = _leaves(sds)
    return sum(float(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
               for s in leaves)


def _leaves(sds) -> List:
    """ShapeDtypeStruct leaves of a possibly-pytree value (SelectedRows)."""
    import jax

    return [l for l in jax.tree_util.tree_leaves(sds)
            if hasattr(l, "shape") and hasattr(l, "dtype")]


def _elems(sds) -> float:
    return sum(float(np.prod(s.shape)) for s in _leaves(sds))


def _slot_bytes(d: Dict[str, list]) -> float:
    return sum(_nbytes(s) for arrs in (d or {}).values() for s in arrs)


def _slot_elems(d: Dict[str, list]) -> float:
    return sum(_elems(s) for arrs in (d or {}).values() for s in arrs)


def _first(d: Dict[str, list], slot: str):
    arrs = (d or {}).get(slot) or []
    return arrs[0] if arrs else None


def _io_cost(flops: float, ins, outs) -> OpCost:
    return OpCost(flops=flops, bytes=_slot_bytes(ins) + _slot_bytes(outs))


# --------------------------------------------------------------------------
# Generic handler families
# --------------------------------------------------------------------------
def _elementwise(k: float = 1.0, fused_reads: bool = True):
    """k FLOPs per output element. With ``fused_reads`` (the default),
    charge the output write plus ONE operand stream — the XLA-fusion
    model: the other operands ride the producing kernels' epilogues."""

    def h(attrs, ins, outs):
        ob = _slot_bytes(outs)
        if fused_reads:
            # unary chains fuse into their producer: the read rides the
            # producer's epilogue and only the (replacing) write counts
            return OpCost(flops=k * _slot_elems(outs), bytes=ob)
        biggest = max((_nbytes(s) for arrs in (ins or {}).values()
                       for s in arrs), default=0.0)
        return OpCost(flops=k * _slot_elems(outs), bytes=biggest + ob)

    return h


def _alias(attrs, ins, outs):
    """assign/reshape-class ops are pure aliases: XLA elides the copy
    (the @PRE snapshots and @GRAD canonical aliases cost nothing)."""
    return OpCost(flops=0.0, bytes=0.0)


def _movement(attrs, ins, outs):
    """Pure data movement (reshape/transpose/concat/...): zero FLOPs,
    one read + one write stream."""
    return OpCost(flops=0.0, bytes=_slot_bytes(ins) + _slot_bytes(outs))


def _fill(attrs, ins, outs):
    """Generators (fill/random): write-only."""
    return OpCost(flops=_slot_elems(outs), bytes=_slot_bytes(outs))


def _reduction(k: float = 1.0):
    """k FLOPs per INPUT element (reductions stream the operand once)."""

    def h(attrs, ins, outs):
        return OpCost(flops=k * _slot_elems(ins),
                      bytes=_slot_bytes(ins) + _slot_bytes(outs))

    return h


def _memory_bound(attrs, ins, outs):
    """The honest default for the long tail (metrics, decode utilities):
    a few FLOPs per element, full operand streams."""
    return _io_cost(_slot_elems(ins) + _slot_elems(outs), ins, outs)


def _optimizer(attrs, ins, outs):
    """Parameter updates: ~4 FLOPs/element, every state read + written
    (no fusion discount — accumulators genuinely stream)."""
    return _io_cost(4.0 * _slot_elems(outs), ins, outs)


# --------------------------------------------------------------------------
# Compute-op handlers
# --------------------------------------------------------------------------
def _mul_cost(attrs, ins, outs):
    x = _first(ins, "X")
    y = _first(ins, "Y")
    o = _first(outs, "Out")
    if x is None or y is None or o is None:
        return _memory_bound(attrs, ins, outs)
    yd = attrs.get("y_num_col_dims", 1)
    k = float(np.prod(y.shape[:yd]))  # contracted dim
    return _io_cost(2.0 * _elems(o) * k, ins, outs)


def _matmul_cost(attrs, ins, outs):
    x = _first(ins, "X")
    o = _first(outs, "Out")
    if x is None or o is None:
        return _memory_bound(attrs, ins, outs)
    k = float(x.shape[-2] if attrs.get("transpose_X", False)
              else x.shape[-1]) if len(x.shape) else 1.0
    return _io_cost(2.0 * _elems(o) * k, ins, outs)


def _conv_cost(attrs, ins, outs):
    w = _first(ins, "Filter")
    o = _first(outs, "Output") or _first(outs, "Out")
    if w is None or o is None:
        return _memory_bound(attrs, ins, outs)
    fmt = attrs.get("data_format", "NCHW")
    wsh = tuple(w.shape)
    if fmt == "NHWC":  # HWIO (2-D) / DHWIO (3-D)
        k_spatial = float(np.prod(wsh[:-2]))
        cin_per_group = float(wsh[-2])
    else:  # OIHW / OIDHW
        k_spatial = float(np.prod(wsh[2:]))
        cin_per_group = float(wsh[1])
    flops = 2.0 * _elems(o) * k_spatial * cin_per_group
    return _io_cost(flops, ins, outs)


def _pool_cost(attrs, ins, outs):
    ksize = attrs.get("ksize") or attrs.get("pool_size") or [2, 2]
    try:
        window = float(np.prod([int(k) for k in np.atleast_1d(ksize)]))
    except Exception:
        window = 4.0
    return _io_cost(window * _slot_elems(outs), ins, outs)


def _norm_cost(attrs, ins, outs):
    # normalize + stats: ~8 FLOPs per element; activation streamed in+out,
    # stats/affine params are noise
    x = _first(ins, "X") or _first(ins, "Input")
    xb = _nbytes(x) if x is not None else _slot_bytes(ins)
    main_out = max((_nbytes(s) for arrs in (outs or {}).values()
                    for s in arrs), default=0.0)
    return OpCost(flops=8.0 * (_elems(x) if x is not None else 0.0),
                  bytes=xb + main_out)


def _sdpa_cost(attrs, ins, outs):
    q = _first(ins, "Q") or _first(ins, "X")
    o = _first(outs, "Out")
    if q is None or o is None:
        return _memory_bound(attrs, ins, outs)
    # q: [..., T, dh] (possibly [b, h, T, dh]); two T x T contractions.
    t = float(q.shape[-2])
    flops = 4.0 * _elems(q) * t
    if attrs.get("causal", False):
        flops *= 0.5
    # flash form: the [T, T] score plane never reaches HBM
    return _io_cost(flops, ins, outs)


def _fused_head_ce_cost(attrs, ins, outs):
    x = _first(ins, "X")
    w = _first(ins, "W")
    if x is None or w is None:
        return _memory_bound(attrs, ins, outs)
    n = float(np.prod(x.shape[:-1]))
    d = float(x.shape[-1])
    v = float(w.shape[-1])
    # chunked online-logsumexp scan: logits NEVER materialize — bytes are
    # the activation + weight streams only (PERF.md "chunked fused head")
    return OpCost(flops=2.0 * n * d * v,
                  bytes=_nbytes(x) + _nbytes(w) + _slot_bytes(outs))


def _embedding_cost(attrs, ins, outs):
    # O(batch) random gathers: touched table rows = output bytes. The
    # [V, D] table is NOT a stream operand — a V=1e6 lookup costs its
    # rows-touched bytes (id stream + row reads + output write), which
    # is what the chip actually DMAs.
    ids = _first(ins, "Ids")
    return OpCost(flops=0.0,
                  bytes=(_nbytes(ids) if ids is not None else 0.0)
                  + 2.0 * _slot_bytes(outs))


def _sparse_optimizer(attrs, ins, outs):
    """Row-granular scatter-apply updates (sparse_sgd/sparse_adagrad):
    price by ROWS TOUCHED — the SelectedRows grad's (ids + row values)
    stream plus a read+write of the touched rows per dense state tensor
    — never the [V, D] table (rows-touched bytes are what the update
    DMAs; the table only pays for rows it owns in the batch)."""
    g = _first(ins, "Grad")
    if g is None:
        return _optimizer(attrs, ins, outs)
    grad_bytes = _nbytes(g)  # id stream + row grads (dense on fan-in)
    row_bytes = max((_nbytes(l) for l in _leaves(g)), default=0.0)
    n_state = sum(1 for slot in ("Param", "Moment") if (ins or {}).get(slot))
    return OpCost(flops=6.0 * _elems(g),  # dedup sort + update arithmetic
                  bytes=grad_bytes + 2.0 * n_state * row_bytes)


def _rnn_cost(attrs, ins, outs):
    # per-step gate matmuls: hidden x hidden contractions dominate.
    # Input carries [b, T, G*H] pre-projected gates; recurrent weight is
    # [H, G*H] -> 2*b*T*H*(G*H) FLOPs == 2 * in_elems * H.
    w = _first(ins, "Weight") or _first(ins, "W")
    h = float(w.shape[0]) if w is not None and len(w.shape) else 1.0
    return _io_cost(2.0 * _slot_elems(ins) * h, ins, outs)


def _conv1x1_bn_act_cost(attrs, ins, outs):
    x = _first(ins, "Input")
    w = _first(ins, "Filter")
    o = _first(outs, "Output")
    if x is None or w is None or o is None:
        return _memory_bound(attrs, ins, outs)
    flops = 2.0 * _elems(o) * float(w.shape[-2])
    # the fused epilogue's point: the raw conv output never streams — one
    # input read, one weight read, one fused output write
    return OpCost(flops=flops,
                  bytes=_nbytes(x) + _nbytes(w) + _nbytes(o))


# --------------------------------------------------------------------------
# Gradient ops: derive from the forward op's cost
# --------------------------------------------------------------------------
def _rebuilt_fwd_ins(attrs, ins):
    return {slot: ins["I:" + slot] for slot in attrs.get("in_slots", {})
            if "I:" + slot in ins}


def _grad_cost(attrs, ins, outs):
    fwd_type = attrs.get("fwd_type")
    fwd_ins = _rebuilt_fwd_ins(attrs, ins)
    fwd = None
    if fwd_type and fwd_ins and has_cost(fwd_type):
        try:
            fwd_outs = registry.infer_outputs(fwd_type,
                                              attrs.get("fwd_attrs"),
                                              fwd_ins)
            fwd = op_cost(fwd_type, attrs.get("fwd_attrs"), fwd_ins,
                          fwd_outs)
        except Exception:
            fwd = None
    og_bytes = sum(_nbytes(s) for slot, arrs in (ins or {}).items()
                   if slot.startswith("OG:") for s in arrs)
    ig_bytes = _slot_bytes(outs)
    if fwd is None:
        return OpCost(flops=2.0 * _slot_elems(ins),
                      bytes=_slot_bytes(ins) + ig_bytes)
    # Explicit stream accounting (round-3 profile): each LARGE gradient
    # (ndim>=2 — dX, dW; vector grads ride along) is its own kernel that
    # re-streams the cotangent once, writes its output, and re-reads the
    # largest saved primal once (dW reads X; recomputed subexpressions
    # are CSE'd with the forward, not re-streamed).
    n_big = max(1, sum(
        1 for arrs in (outs or {}).values() for s in arrs
        if len(getattr(s, "shape", ())) >= 2))
    primal = max((_nbytes(s) for slot, arrs in (ins or {}).items()
                  if slot.startswith("I:") for s in arrs), default=0.0)
    return OpCost(flops=2.0 * fwd.flops,
                  bytes=ig_bytes + n_big * og_bytes + primal)


def _seg_ops_cost(seg_ops, resolve) -> OpCost:
    """Walk a recompute segment's serialized interior ops, accumulating
    their costs with a local shape environment (checker's seg handler)."""
    total = OpCost()
    local: Dict[str, object] = {}

    def get(name):
        return local[name] if name in local else resolve(name)

    for sop in seg_ops:
        op_ins = {slot: [get(n) for n in names]
                  for slot, names in sop["ins"].items() if names}
        op_outs = registry.infer_outputs(sop["type"], sop["attrs"], op_ins)
        c = op_cost(sop["type"], sop["attrs"], op_ins, op_outs)
        if c is not None:
            total = total + c
        for slot, names in sop["outs"].items():
            for n, sds in zip(names, (op_outs or {}).get(slot, [])):
                local[n] = sds
    return total


def _seg_fwd_cost(attrs, ins, outs):
    env = dict(zip(attrs["ext_in"], ins.get("I", [])))
    inner = _seg_ops_cost(attrs["seg_ops"], env.__getitem__)
    return OpCost(flops=inner.flops, bytes=inner.bytes)


def _grad_seg_cost(attrs, ins, outs):
    # the round-3 lesson as analysis: the barriered backward re-RUNS the
    # segment (recompute FLOPs) and re-streams its interior as separate
    # kernels — roughly the forward's traffic twice, plus the grads
    og_bytes = _slot_bytes({"OG": ins.get("OG", [])})
    ig_bytes = _slot_bytes(outs)
    return OpCost(flops=2.0 * _slot_elems(ins),
                  bytes=2.0 * og_bytes + ig_bytes + _slot_bytes(ins))


def _stack_cost(attrs, ins, outs):
    """pipelined_transformer_stack: scan-over-layers. FLOPs from the
    stacked [L, in, out] weights (each is one token-plane contraction per
    layer); residual_bytes models what the scan keeps resident forward->
    backward under the remat policy — the [L, ...] activation planes
    PERF.md's stacked-scan A/Bs are about."""
    x = _first(ins, "X")
    if x is None:
        return _memory_bound(attrs, ins, outs)
    b_t = float(np.prod(x.shape[:-1]))  # tokens
    d = float(x.shape[-1])
    itemsize = np.dtype(x.dtype).itemsize
    flops = 0.0
    weight_bytes = 0.0
    L = 1.0
    for slot, arrs in (ins or {}).items():
        if slot == "X":     # (the activation is [b, T, d], no weight plane)
            continue
        for w in arrs:
            weight_bytes += _nbytes(w)
            if len(w.shape) == 3:  # [L, in, out] matmul plane
                L = float(w.shape[0])
                flops += 2.0 * b_t * float(w.shape[1]) * float(w.shape[2])
    t = float(x.shape[-2]) if len(x.shape) >= 2 else 1.0
    flops += L * 2.0 * b_t * t * d  # attention score+context contractions
    remat = attrs.get("remat", False)
    # saved per token per layer (see ops/pipeline_ops.py): "full" the
    # stream alone (1 d); True the stream and ``_STACK_SAVED``: the flash
    # call's q, k, v (expanded to the query heads) and result and the
    # out-projection's result, each in the matmuls' operand dtype (5 d of
    # bf16 under AMP for the GPT-2 block); False every interior, ~14 d.
    if remat == "full":
        per_tok = d * itemsize
    elif remat:
        from ..ops.common import amp_enabled

        out_w = _first(ins, "OutW")                         # [L, H*dh, d]
        planes = 4.0 * out_w.shape[-2] + out_w.shape[-1]
        per_tok = d * itemsize + planes * (2 if amp_enabled() else itemsize)
    else:
        per_tok = 14.0 * d * itemsize
    residual = L * b_t * per_tok
    return OpCost(flops=flops,
                  bytes=_nbytes(x) + weight_bytes + _slot_bytes(outs),
                  residual_bytes=residual)


def _stacked_pass_cost(attrs, ins, outs):
    """One stacked-weight pass of a decode op over its tokens: FLOPs from
    every [L, in, out] weight plane, bytes from the full I/O stream (the
    cache planes included)."""
    x = (_first(ins, "Prompt") or _first(ins, "Tok")
         or _first(ins, "X") or _first(ins, "Ids"))
    toks = float(np.prod(x.shape)) if x is not None else 1.0
    flops = 0.0
    for slot, arrs in (ins or {}).items():
        for w in arrs:
            if len(w.shape) == 3:  # [L, in, out]
                flops += 2.0 * toks * float(w.shape[1]) * float(w.shape[2])
    return _io_cost(flops, ins, outs)


def _encdec_cost(attrs, ins, outs):
    """transformer_encdec_* family (seq2seq): stacked encoder/decoder
    passes — FLOPs from every [L, in, out] weight plane applied to the
    op's token count (source tokens for encode, source + target for the
    teacher, slot rows for the cross decode), bytes from the full I/O
    stream, which prices the cross-KV planes ``[L, S+1, Hkv, Ts, dh]``
    as read state — the memplan gate sees the encoder-decoder config's
    extra resident bytes."""
    toks = 0.0
    for slot in ("SrcIds", "TgtIn", "Chunk", "Tok"):
        x = _first(ins, slot)
        if x is not None:
            toks += float(np.prod(x.shape))
    flops = 0.0
    for arrs in (ins or {}).values():
        for w in arrs:
            if len(w.shape) == 3:
                flops += 2.0 * toks * float(w.shape[1]) * float(w.shape[2])
    return _io_cost(flops, ins, outs)


def _paged_cache_cost(attrs, ins, outs):
    """transformer_stack_paged_prefill/decode: the stacked pass plus
    the K/V context the attention reads per layer (x2 for K and V).

    Prefill (``Chunk``): every row gathers its table-width [P*ps, Hkv*dh]
    block — the chunk path keeps the gather. Decode (``Tok``/``Pos``): the
    paged-attention kernel walks only the pages a row HOLDS, ``Pos // ps +
    1`` of them (a vacant slot reads the scrap page once) — counted from
    ``Pos`` where it carries values (a concrete array); where only its
    shape is known, rows x P pages: the table width, an UPPER bound, and
    what the gathered fallback (no TPU, grouped-query heads, a row not
    lane-aligned) really moves."""
    base = _stacked_pass_cost(attrs, ins, outs)
    table = _first(ins, "BlockTable")
    pool = _first(ins, "CacheK")
    context = 0.0
    if table is not None and pool is not None and len(pool.shape) == 4:
        L, _, ps, width = pool.shape  # width = Hkv*dh
        rows, P = table.shape
        pages = float(rows) * float(P)
        pos = _first(ins, "Pos")
        if _first(ins, "Chunk") is None and isinstance(pos, np.ndarray):
            pages = float(np.sum(np.clip(pos // ps, 0, P - 1) + 1))
        context = 2.0 * float(L) * pages * float(ps) * float(width) \
            * np.dtype(pool.dtype).itemsize
    return OpCost(flops=base.flops, bytes=base.bytes + context)


# --------------------------------------------------------------------------
# Coverage: every registered op gets a handler or an exempt marker.
# (tests/test_registry_conformance.py pins the audit clean — a new op
# registered without either fails there, naming the op.)
# --------------------------------------------------------------------------
_ELEMENTWISE_1 = (
    "abs", "brelu", "ceil", "clip", "cos", "equal", "exp",
    "floor", "greater_equal", "greater_than", "hard_shrink",
    "hard_sigmoid", "increment", "leaky_relu", "less_equal", "less_than",
    "log", "logical_and", "logical_not", "logical_or", "logical_xor",
    "not_equal", "prelu", "reciprocal", "relu", "relu6", "round",
    "rsqrt", "scale", "sin", "sqrt",
    "square", "fill_zeros_like", "cast", "scale_shift",
    "slope_intercept", "l1_decay_sign", "interpolation", "linear_comb",
    "scaling", "multiplex", "sequence_mask", "power", "pow",
)
_ELEMENTWISE_4 = (
    "elu", "gelu", "logsigmoid", "sigmoid", "soft_relu", "softplus",
    "softshrink", "softsign", "stanh", "swish", "tanh", "tanh_shrink",
    "thresholded_relu", "dropout", "clip_by_norm",
    "clip_by_global_norm", "lrn", "rotary_embed", "maxout",
    "sum_to_one_norm", "row_l2_norm", "static_prune_mask",
)
_ELEMENTWISE_BIN = (
    "elementwise_add", "elementwise_div", "elementwise_max",
    "elementwise_min", "elementwise_mul", "elementwise_pow",
    "elementwise_sub", "addto", "sum",
)
_MOVEMENT = (
    "transpose", "concat", "split", "slice", "pad", "squeeze",
    "unsqueeze", "stack", "expand", "repeat", "gather", "scatter",
    "crop", "resize", "rotate", "switch_order", "sequence_concat",
    "sequence_expand", "sequence_reshape", "sequence_reverse",
    "sequence_slice", "sequence_enumerate", "sub_nested_seq", "sub_seq",
    "array_read", "array_write", "assign_value", "one_hot",
    "im2sequence", "unpool", "scale_sub_region", "unpack_plane",
)
_ALIAS = (
    "assign", "reshape", "squeeze", "unsqueeze", "lod_reset",
)
_FILL = (
    "fill_constant", "fill_constant_batch_size_like", "gaussian_random",
    "gaussian_random_batch_size_like", "uniform_random",
    "truncated_gaussian_random", "sampling_id",
)
_REDUCTION = (
    "mean", "reduce_max", "reduce_mean", "reduce_min", "reduce_prod",
    "reduce_sum", "l1_norm", "squared_l2_norm", "norm", "l2_distance",
    "squared_l2_distance", "cos_sim", "dot_prod", "sequence_pool",
    "kmax_seq_score",
)
_SOFTMAXISH = (
    "softmax", "log_softmax", "sequence_softmax",
    "softmax_with_cross_entropy", "cross_entropy",
    "cross_entropy_with_selfnorm", "bce_loss",
    "sigmoid_cross_entropy_with_logits", "log_loss", "huber_loss",
    "modified_huber_loss", "smooth_l1_loss", "square_error_cost",
    "hinge_loss", "margin_rank_loss", "rank_loss", "lambda_cost",
)
_OPTIMIZER = (
    "sgd", "momentum", "adam", "adamax", "adagrad", "decayed_adagrad",
    "adadelta", "rmsprop", "ftrl", "proximal_gd", "proximal_adagrad",
    "model_average_update", "lr_schedule", "lr_warmup",
)
_MATMUL_LIKE = {
    "mul": _mul_cost, "matmul": _matmul_cost,
}
_CONV = (
    "conv2d", "conv2d_cudnn", "conv2d_transpose",
    "conv2d_transpose_cudnn", "conv3d", "conv3d_cudnn",
    "conv3d_transpose", "conv3d_transpose_cudnn", "depthwise_conv2d",
    "sequence_conv", "row_conv", "conv_shift", "context_project",
)
_POOL = (
    "pool2d", "pool2d_cudnn", "pool3d", "pool3d_cudnn",
    "max_pool2d_with_index", "max_pool3d_with_index", "spp", "roi_pool",
)
_NORM = ("batch_norm", "layer_norm", "rms_norm")
_RNN = ("lstm", "gru", "gru_unit", "lstm_unit", "simple_rnn",
        "gated_unit")
# metrics / decode / detection utilities: memory-bound default
_MEMORY_BOUND = (
    "accuracy", "auc", "auc_histogram", "precision_recall",
    "confusion_counts", "pnpair_counts", "positive_negative_pair",
    "rank_auc", "detection_map_counts", "chunk_eval", "edit_distance",
    "top_k", "argmax", "iou_similarity", "prior_box", "box_coder",
    "detection_output", "multibox_loss", "linear_chain_crf",
    "crf_decoding", "warpctc", "ctc_greedy_decode", "beam_search",
    "is_empty", "nce", "hsigmoid", "bilinear_interp",
    "bilinear_tensor_product", "tensor_product", "out_prod", "dot",
    "factorization_machine", "switch_moe",
)
# structural / executor-interpreted / unbounded-loop ops: exempt
_EXEMPT = (
    "feed", "fetch", "while", "cond", "static_rnn", "beam_search_decoder",
    "transformer_stack_generate", "transformer_stack_beam_search",
)


def _register_all() -> None:
    def reg(names, handler):
        for n in names:
            if has_op(n) and not has_cost(n) and not is_cost_exempt(n):
                register_cost(n, handler)

    reg(_ALIAS, _alias)
    reg(_ELEMENTWISE_1, _elementwise(1.0))
    reg(_ELEMENTWISE_4, _elementwise(4.0))
    reg(_ELEMENTWISE_BIN, _elementwise(1.0, fused_reads=False))
    reg(_MOVEMENT, _movement)
    reg(_FILL, _fill)
    reg(_REDUCTION, _reduction(1.0))
    reg(_SOFTMAXISH, _reduction(6.0))
    reg(_OPTIMIZER, _optimizer)
    reg(_CONV, _conv_cost)
    reg(_POOL, _pool_cost)
    reg(_NORM, _norm_cost)
    reg(_RNN, _rnn_cost)
    reg(_MEMORY_BOUND, _memory_bound)
    for name, h in _MATMUL_LIKE.items():
        reg((name,), h)
    reg(("conv1x1_bn_act",), _conv1x1_bn_act_cost)
    reg(("scaled_dot_product_attention",), _sdpa_cost)
    reg(("fused_head_cross_entropy",), _fused_head_ce_cost)
    reg(("lookup_table",), _embedding_cost)
    reg(("sparse_sgd", "sparse_adagrad"), _sparse_optimizer)
    reg(("grad", "grad_custom"), _grad_cost)
    reg(("seg_fwd",), _seg_fwd_cost)
    reg(("grad_seg",), _grad_seg_cost)
    reg(("pipelined_transformer_stack",), _stack_cost)
    reg(("transformer_stack_paged_prefill", "transformer_stack_paged_decode"),
        _paged_cache_cost)
    reg(("transformer_encdec_encode", "transformer_encdec_teacher",
         "transformer_stack_cross_prefill",
         "transformer_stack_cross_decode"), _encdec_cost)
    reg(("kv_cache_page_copy",), _movement)
    cost_exempt(*[n for n in _EXEMPT if has_op(n)])


_registered = False


def ensure_registered() -> None:
    """Idempotently attach the standard handler set. Registration is
    lazy because paddle_tpu/__init__ imports the analysis package BEFORE
    the ops modules — at that point the registry is still empty; the
    first cost query after the ops plane loads does the real work."""
    global _registered
    if _registered or not has_op("relu"):
        return
    _registered = True
    _register_all()


ensure_registered()
