"""Liveness & peak-HBM analysis over program blocks.

The static answer to "will this program fit, and if not, which tensors
are holding the watermark" — computed at BUILD time from the checker's
inferred ``ShapeDtypeStruct``s (PR 6), before XLA ever sees the program
or a chip OOMs. The model follows the executor's actual residency rules:

- **resident** values — persistable vars (parameters, optimizer slots),
  scope state (KV caches), and the feeds — occupy HBM for the whole
  step;
- **transient** values live from their producing op to their last
  consumer (fetches live to the end of the block);
- **donation/aliasing**: the liveness map is keyed by NAME, so an op
  writing onto its own input (momentum's in-place param update,
  batch_norm's MeanOut onto Mean) replaces the buffer instead of
  double-counting it — exactly what ``donate_argnums`` buys at run time;
- **recompute segments** (``seg_fwd``/``grad_seg``): interior
  activations are freed as soon as the forward consumes them; only the
  checkpoint-policy residuals (matmul/conv outputs + ndim<=1 stats, the
  ``backward.SEGMENT_SAVE_OPS`` contract) stay live until the paired
  ``grad_seg``;
- **stacked scans** (``pipelined_transformer_stack``): the scan body's
  saved activation planes are ``[L, ...]``-shaped and invisible to
  name-level liveness — the op's cost handler sizes them per its
  ``remat`` policy (``residual_bytes``) and they are held live from the
  forward op to its paired grad op.

``check_memory_budget`` turns the analysis into a gate:
``SGD.train(mem_budget=...)`` and the serving engines raise a located
:class:`MemoryBudgetError` naming the peak set and the remat advisor's
suggestions instead of letting XLA OOM at compile.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from ..core.enforce import EnforceError
from ..core.program import (BATCH_DIM_SENTINEL, GRAD_SUFFIX, Block,
                            Operator, Program)
from ..core.registry import get_op, has_op, infer_outputs
from ..core.scope import Scope
from . import costmodel
from .checker import infer_program
from .costmodel import OpCost, V5E_HBM_BW, V5E_PEAK_FLOPS, op_cost


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024.0 or unit == "TB":
            return f"{n:.2f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0
    return f"{n:.2f} TB"


@dataclasses.dataclass
class LiveTensor:
    """One entry of the live set at the peak: what it is, how big, and
    which op (and user line) produced it."""

    name: str
    bytes: float
    shape: tuple
    dtype: str
    kind: str  # "resident" | "activation" | "residual"
    producer_index: Optional[int] = None
    producer_type: Optional[str] = None
    callsite: Optional[str] = None

    def format(self) -> str:
        where = ""
        if self.producer_type is not None:
            where = f"  <- op #{self.producer_index} {self.producer_type!r}"
            if self.callsite:
                where += f" (created at {self.callsite})"
        return (f"{_fmt_bytes(self.bytes):>12}  {self.name}  "
                f"{tuple(self.shape)} {self.dtype} [{self.kind}]{where}")


@dataclasses.dataclass
class RematAdvice:
    """One candidate ``recompute_guard`` span, ranked by the peak bytes
    it would free against the extra HBM traffic + FLOPs the barriered
    backward recompute would re-stream (the PERF.md round-3 lesson:
    remat is a memory lever, NOT a bandwidth lever — the advisor prices
    both sides instead of leaving it folklore)."""

    start: int
    end: int
    op_types: List[str]
    bytes_saved: float
    extra_traffic_bytes: float
    extra_flops: float
    callsite: Optional[str] = None

    @property
    def net_memory_per_traffic(self) -> float:
        return self.bytes_saved / max(self.extra_traffic_bytes, 1.0)

    def format(self) -> str:
        kinds = ", ".join(self.op_types[:5])
        if len(self.op_types) > 5:
            kinds += ", ..."
        site = f" (around {self.callsite})" if self.callsite else ""
        return (f"recompute_guard ops #{self.start}..#{self.end} "
                f"[{kinds}]{site}: frees ~{_fmt_bytes(self.bytes_saved)} "
                f"of peak at +{_fmt_bytes(self.extra_traffic_bytes)} HBM "
                f"traffic / +{self.extra_flops / 1e9:.1f} GFLOP recompute")


class MemoryBudgetError(EnforceError):
    """The static peak-HBM estimate exceeds the configured budget.
    Raised at build time — before XLA compiles, allocates, or OOMs —
    with the peak live set and remat advice attached."""

    def __init__(self, message: str, *, peak_bytes: float,
                 budget_bytes: float, top: Sequence[LiveTensor] = (),
                 advice: Sequence[RematAdvice] = ()):
        super().__init__(message)
        self.peak_bytes = peak_bytes
        self.budget_bytes = budget_bytes
        self.top = list(top)
        self.advice = list(advice)


class MemoryAnalysis:
    """Result of :func:`analyze_memory`.

    With a sharding plan (``analyze_memory(plan=...)`` or a
    ShardProgram-annotated program) every byte figure is PER DEVICE:
    sharded dims divide each tensor by its mesh-axis product, and
    ``collectives`` prices the in-graph psum/all-gather traffic the plan
    implies (``mesh_axes`` records the mesh; both are None single-chip).
    """

    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self.mesh_axes = None
        self.collectives = None  # analysis.sharding.ShardingCost
        self.resident_bytes: float = 0.0
        self.peak_bytes: float = 0.0
        self.peak_op_index: Optional[int] = None
        self.peak_op_type: Optional[str] = None
        self.peak_live: List[LiveTensor] = []
        # live bytes DURING each op (outputs allocated, dead inputs not
        # yet freed) — the watermark curve
        self.live_at_op: List[float] = []
        self.op_costs: List[Optional[OpCost]] = []
        self.op_types: List[str] = []
        self.total_cost: OpCost = OpCost()
        self.uncosted_ops: List[str] = []

    # -- summary -----------------------------------------------------------
    def top(self, n: int = 10) -> List[LiveTensor]:
        return sorted(self.peak_live, key=lambda t: -t.bytes)[:n]

    @property
    def total_flops(self) -> float:
        return self.total_cost.flops

    @property
    def total_hbm_bytes(self) -> float:
        return self.total_cost.bytes

    @property
    def intensity(self) -> float:
        return self.total_cost.intensity

    def estimated_step_seconds(self, peak_flops: float = V5E_PEAK_FLOPS,
                               hbm_bw: float = V5E_HBM_BW) -> float:
        """Sum of per-op roofline times — each op bound by compute or
        bandwidth, whichever binds it (PERF.md's per-op-group method)."""
        return sum(c.step_seconds(peak_flops, hbm_bw)
                   for c in self.op_costs if c is not None)

    def roofline_rows(self) -> List[dict]:
        """Per-op-type aggregate: FLOPs, bytes, intensity, bound, est ms
        — the shape of PERF.md's round-3 table, derived statically."""
        agg: Dict[str, OpCost] = {}
        counts: Dict[str, int] = {}
        for t, c in zip(self.op_types, self.op_costs):
            if c is None:
                continue
            agg[t] = agg.get(t, OpCost()) + c
            counts[t] = counts.get(t, 0) + 1
        rows = []
        for t, c in agg.items():
            rows.append({
                "op": t, "count": counts[t], "flops": c.flops,
                "bytes": c.bytes, "intensity": round(c.intensity, 2),
                "bound": ("compute" if c.intensity >= (
                    V5E_PEAK_FLOPS / V5E_HBM_BW) else "HBM"),
                "est_ms": round(c.step_seconds() * 1e3, 3)})
        rows.sort(key=lambda r: -r["est_ms"])
        return rows

    @property
    def collective_bytes(self) -> float:
        return self.collectives.total_bytes if self.collectives else 0.0

    def format_report(self, top_n: int = 10) -> str:
        scope_note = ""
        if self.mesh_axes:
            axes = "x".join(f"{a}={s}" for a, s in self.mesh_axes.items())
            scope_note = f" PER DEVICE over mesh [{axes}]"
        lines = [
            f"peak HBM watermark: {_fmt_bytes(self.peak_bytes)}{scope_note}"
            f" at op #{self.peak_op_index} {self.peak_op_type!r} "
            f"(batch={self.batch_size})",
            f"  resident (params/state/feeds): "
            f"{_fmt_bytes(self.resident_bytes)}",
            f"  transient at peak: "
            f"{_fmt_bytes(self.peak_bytes - self.resident_bytes)}",
            f"top {top_n} live tensors at the peak:",
        ]
        lines += ["  " + t.format() for t in self.top(top_n)]
        lines += [
            f"roofline: {self.total_flops / 1e9:.1f} GFLOP, "
            f"{_fmt_bytes(self.total_hbm_bytes)} HBM, intensity "
            f"{self.intensity:.1f} F/B vs ridge "
            f"{V5E_PEAK_FLOPS / V5E_HBM_BW:.0f} -> est "
            f"{self.estimated_step_seconds() * 1e3:.2f} ms/step (v5e)",
        ]
        if self.uncosted_ops:
            lines.append(
                f"  (no cost model for: "
                f"{sorted(set(self.uncosted_ops))[:8]})")
        if self.collectives is not None:
            lines.append(self.collectives.format_report())
        return "\n".join(lines)


# --------------------------------------------------------------------------
def _concrete(sds, batch_size: int):
    """Replace the batch sentinel with the given batch in a ShapeDtype
    tree (shapes from infer_program carry BATCH_DIM_SENTINEL). The
    sentinel is PRIME, so dims the program derived by flattening or
    concatenating the batch axis (``reshape([-1, V])`` -> tokens =
    sentinel * T) are recovered by divisibility: any multiple of the
    sentinel rescales by batch/sentinel."""
    def dim(d):
        d = int(d)
        if d == BATCH_DIM_SENTINEL:
            return batch_size
        if d and d % BATCH_DIM_SENTINEL == 0:
            return (d // BATCH_DIM_SENTINEL) * batch_size
        return d

    def leaf(s):
        if not hasattr(s, "shape"):
            return s
        return jax.ShapeDtypeStruct(tuple(dim(d) for d in s.shape),
                                    s.dtype)

    return jax.tree_util.tree_map(leaf, sds)


def _lookup_var(block: Block, name: str):
    b = block
    while b is not None:
        if name in b.vars:
            return b.vars[name]
        b = b.parent
    return None


def _segment_residual_bytes(op: Operator, env: Dict[str, object]) -> float:
    """Bytes the checkpoint policy keeps live across a recompute segment
    (seg_fwd -> grad_seg): outputs of SEGMENT_SAVE_OPS plus ndim<=1
    stats — backward.segment_forward's save-only-named-residuals set."""
    from ..core.backward import SEGMENT_SAVE_OPS

    local: Dict[str, object] = {}
    for name in op.attrs["ext_in"]:
        if name in env:
            local[name] = env[name]
    saved = 0.0
    for sop in op.attrs["seg_ops"]:
        try:
            ins = {slot: [local[n] for n in names]
                   for slot, names in sop["ins"].items() if names}
            outs = infer_outputs(sop["type"], sop["attrs"], ins)
        except Exception:
            continue
        save_all = sop["type"] in SEGMENT_SAVE_OPS
        for slot, names in sop["outs"].items():
            for n, sds in zip(names, (outs or {}).get(slot, [])):
                local[n] = sds
                nd = len(getattr(sds, "shape", ()))
                if save_all or nd <= 1:
                    saved += costmodel._nbytes(sds)
    return saved


def _paired_grad_index(block: Block, i: int, op: Operator) -> Optional[int]:
    """Index of the grad op that consumes op's forward residuals: for
    seg_fwd the grad_seg sharing its vjp_key; for a loop-bearing op
    traced once (backward.traced_once) the grad op sharing its pair key;
    for other plain ops the first later grad/grad_custom with fwd_type ==
    op.type reading one of op's outputs (or their @PRE snapshots)."""
    from ..core.backward import VJP_KEY_ATTR

    if op.type == "seg_fwd" or VJP_KEY_ATTR in op.attrs:
        kind, attr = (("grad_seg", "vjp_key") if op.type == "seg_fwd"
                      else ("grad", VJP_KEY_ATTR))
        key = op.attrs.get(attr)
        for j in range(i + 1, len(block.ops)):
            o = block.ops[j]
            if o.type == kind and o.attrs.get(attr) == key:
                return j
        return None
    out_names = set(op.output_names())
    for j in range(i + 1, len(block.ops)):
        o = block.ops[j]
        if o.type not in ("grad", "grad_custom"):
            continue
        if o.attrs.get("fwd_type") != op.type:
            continue
        reads = set(o.input_names())
        if out_names & reads:
            return j
    return None


# --------------------------------------------------------------------------
# elementwise-class ops that keep their input's last-dim sharding (the
# mini GSPMD propagation below); contractions and everything else stop
# the chain — conservative, in the cost model's ~20% honesty class
_TP_INHERIT_OPS = frozenset((
    "gelu", "relu", "sigmoid", "tanh", "elementwise_add",
    "elementwise_mul", "elementwise_sub", "dropout", "scale",
    "layer_norm", "softmax", "addto", "cast"))


def _tp_activation_divisors(block, plan, axis_sizes, data_axis):
    """Megatron's column-parallel activations, statically: an op
    contracting a weight sharded on its LAST (output) dim produces an
    activation sharded the same way, and elementwise consumers inherit
    — until the next contraction combines the partials. Returns
    name -> tp divisor for those activations (1 implied elsewhere)."""
    from ..parallel.plan import spec_axes
    from .sharding import _contract_like

    div: Dict[str, int] = {}
    for op in block.ops:
        d = 1
        if _contract_like(op):
            for name in op.input_names():
                v = _lookup_var(block, name)
                if v is None or not v.persistable:
                    continue
                spec = getattr(v, "sharding", None)
                if spec is None and v.shape is not None:
                    spec = plan.spec_for_state(name, len(v.shape),
                                               shape=v.shape)
                if spec is None or not tuple(spec):
                    continue
                last = tuple(spec)[-1]
                axes = last if isinstance(last, tuple) else (last,)
                for ax in axes:
                    if ax is not None and ax != data_axis:
                        d *= axis_sizes.get(ax, 1)
        elif op.type in _TP_INHERIT_OPS:
            d = max((div.get(n, 1) for n in op.input_names()), default=1)
        if d > 1:
            for out in op.output_names():
                div[out] = d
    return div


def _make_shard_divisor(plan, block, types, feeds, batch_size):
    """name -> how many ways that tensor's bytes split per device under
    the plan: state/feeds by their resolved PartitionSpec (ShardProgram
    annotations win), transient activations by the ``dp`` axis when
    their leading dim is batch-derived (the sharding GSPMD propagates);
    1 without a plan."""
    if plan is None:
        return lambda name: 1
    from ..parallel.plan import spec_axes

    axis_sizes = plan.mesh_axes()
    n_dp = axis_sizes.get(plan.data_axis, 1) if plan.data_axis else 1
    tp_div = _tp_activation_divisors(block, plan, axis_sizes,
                                     plan.data_axis)
    cache: Dict[str, int] = {}

    def leaf_shape(name):
        sds = types.get(name)
        leaves = costmodel._leaves(sds) if sds is not None else []
        return tuple(leaves[0].shape) if leaves else ()

    def div(name: str) -> int:
        if name in cache:
            return cache[name]
        base = name
        if GRAD_SUFFIX in name:
            # a weight's gradient shards exactly like the weight (GSPMD
            # propagates the spec through the cotangent)
            cand = name.split(GRAD_SUFFIX, 1)[0]
            cv = _lookup_var(block, cand)
            if cv is not None and cv.persistable:
                base = cand
        v = _lookup_var(block, base)
        ann = getattr(v, "sharding", None) if v is not None else None
        shape = leaf_shape(base if base != name else name)
        spec = None
        if ann is not None:
            spec = ann
        elif base in feeds:
            spec = plan.spec_for_feed(base, len(shape))
        elif v is not None and (v.persistable or v.is_data):
            spec = plan.spec_for_state(base, len(shape), shape=shape)
        if spec is None:
            d = n_dp if (n_dp > 1 and shape
                         and (shape[0] == batch_size
                              or (batch_size > 1
                                  and shape[0] % batch_size == 0))) else 1
            # column-parallel tp sharding composes with the dp split; an
            # activation's GRADIENT mirrors the forward activation
            act = name.split(GRAD_SUFFIX, 1)[0] \
                if GRAD_SUFFIX in name else name
            d *= tp_div.get(act, 1)
        else:
            d = 1
            for ax in spec_axes(spec):
                d *= axis_sizes.get(ax, 1)
        cache[name] = max(int(d), 1)
        return cache[name]

    return div


def analyze_memory(program: Program, feed_names: Sequence[str] = (),
                   fetch_names: Sequence[str] = (),
                   scope: Optional[Scope] = None,
                   batch_size: int = 1,
                   include_costs: bool = True,
                   plan=None) -> MemoryAnalysis:
    """Compute per-op live-byte sets, the peak-HBM watermark, and (with
    ``include_costs``) the per-op roofline costs for the global block.

    ``batch_size`` concretises every ``-1`` batch dim. Shapes come from
    :func:`~paddle_tpu.analysis.checker.infer_program`, so anything that
    fails whole-program inference raises the same located
    ``ProgramCheckError`` this plane is built on.

    ``plan`` (a :class:`paddle_tpu.parallel.ShardingPlan`; defaults to a
    ShardProgram-annotated program's own plan) switches the analysis to
    PER-DEVICE accounting: state/feed tensors divide by the mesh-axis
    product of their plan-resolved spec, batch-led activations divide by
    the ``dp`` axis (the sharding GSPMD propagates), and
    ``mem.collectives`` prices the plan's psum/all-to-all wire bytes.
    """
    costmodel.ensure_registered()
    if plan is None:
        plan = getattr(program, "sharding_plan", None)
    analysis = infer_program(program, feed_names, fetch_names, scope=scope,
                             annotate=False)
    block = program.global_block
    ops = list(block.ops)
    mem = MemoryAnalysis(batch_size)

    types: Dict[str, object] = {
        name: _concrete(sds, batch_size)
        for name, sds in analysis.types.items()}

    shard_div = _make_shard_divisor(plan, block, types, set(feed_names),
                                    batch_size)
    if plan is not None:
        mem.mesh_axes = plan.mesh_axes()

    def bytes_of(name: str) -> float:
        sds = types.get(name)
        if sds is None:
            return 0.0
        return costmodel._nbytes(sds) / shard_div(name)

    # ---- residency classification ------------------------------------
    feeds = set(feed_names)
    fetches = set(fetch_names)
    resident: Set[str] = set()
    scope_names: Set[str] = set()
    if scope is not None:
        s = scope
        while s is not None:
            scope_names.update(s.keys())
            s = s.parent
    for name in types:
        v = _lookup_var(block, name)
        if (name in feeds or name in scope_names
                or (v is not None and (v.persistable or v.is_data))):
            resident.add(name)
    mem.resident_bytes = sum(bytes_of(n) for n in resident)

    # ---- last-use map -------------------------------------------------
    last_use: Dict[str, int] = {}
    producer: Dict[str, Tuple[int, Operator]] = {}
    for i, op in enumerate(ops):
        for name in op.input_names():
            last_use[name] = i
        for name in op.output_names():
            producer[name] = (i, op)
    horizon = len(ops)
    for name in fetches:
        last_use[name] = horizon  # fetches survive the block

    # ---- residual intervals (fwd->bwd footprints liveness can't see) --
    # [(start, end, bytes, label)]
    residuals: List[Tuple[int, int, float, str]] = []

    # ---- the walk -----------------------------------------------------
    live: Dict[str, float] = {}
    peak = mem.resident_bytes
    peak_i: Optional[int] = None
    peak_live_names: List[str] = []
    peak_residuals: List[Tuple[float, str, int]] = []
    active_residuals: List[Tuple[int, float, str, int]] = []  # (end, b, lbl, i)

    dp_div = 1
    if plan is not None and plan.data_axis:
        dp_div = plan.mesh_axes().get(plan.data_axis, 1)

    for i, op in enumerate(ops):
        cost = None
        if include_costs and has_op(op.type):
            opdef = get_op(op.type)
            if opdef.cost_fn is not None:
                ins = {slot: [types[n] for n in names if n in types]
                       for slot, names in op.inputs.items() if names}
                outs = {slot: [types[n] for n in names if n in types]
                        for slot, names in op.outputs.items() if names}
                cost = op_cost(op.type, op.attrs, ins, outs)
                if cost is not None and plan is not None:
                    # per-device roofline: this op computes 1/d of the
                    # global work (its output's shard count)
                    out_names = op.output_names()
                    d = shard_div(out_names[0]) if out_names else 1
                    if d > 1:
                        cost = OpCost(cost.flops / d, cost.bytes / d,
                                      cost.residual_bytes / d)
            elif not opdef.cost_exempt:
                mem.uncosted_ops.append(op.type)
        mem.op_costs.append(cost)
        mem.op_types.append(op.type)
        if cost is not None:
            mem.total_cost = mem.total_cost + cost

        # residual footprint: seg_fwd's checkpoint saves, or the cost
        # handler's declared residual (stacked-scan activation planes;
        # batch-carried, so per-device they divide by dp)
        res_bytes = 0.0
        if op.type == "seg_fwd":
            res_bytes = _segment_residual_bytes(op, types) / dp_div
        elif cost is not None and cost.residual_bytes:
            res_bytes = cost.residual_bytes
            if plan is not None and shard_div(op.output_names()[0]
                                              if op.output_names()
                                              else "") <= 1:
                res_bytes /= dp_div
        if res_bytes:
            j = _paired_grad_index(block, i, op)
            if j is not None:
                residuals.append((i, j, res_bytes, op.type))
                active_residuals.append((j, res_bytes, op.type, i))

        # allocate outputs (name-keyed: an in-place rewrite of a live
        # name — donated state, aliased BN stats — replaces, not adds)
        for name in op.output_names():
            if name in resident:
                continue
            live[name] = bytes_of(name)
        running_transient = sum(live.values())
        res_active = sum(b for (end, b, _, _) in active_residuals
                         if end >= i)
        now = mem.resident_bytes + running_transient + res_active
        mem.live_at_op.append(now)
        if now > peak:
            peak = now
            peak_i = i
            peak_live_names = list(live)
            peak_residuals = [(b, lbl, src) for (end, b, lbl, src)
                              in active_residuals if end >= i]

        # free transients whose last consumer this was
        for name in list(live):
            if last_use.get(name, -1) <= i and name not in fetches:
                del live[name]
        active_residuals = [(end, b, lbl, src)
                            for (end, b, lbl, src) in active_residuals
                            if end > i]

    mem.peak_bytes = peak
    mem.peak_op_index = peak_i
    mem.peak_op_type = ops[peak_i].type if peak_i is not None else None

    # ---- the peak's named live set ------------------------------------
    peak_set: List[LiveTensor] = []
    for name in resident:
        sds = types.get(name)
        if sds is None:
            continue
        leaves = costmodel._leaves(sds)
        shape = tuple(leaves[0].shape) if leaves else ()
        dt = str(leaves[0].dtype) if leaves else "?"
        pi, pop = producer.get(name, (None, None))
        peak_set.append(LiveTensor(
            name=name, bytes=bytes_of(name), shape=shape, dtype=dt,
            kind="resident", producer_index=pi,
            producer_type=pop.type if pop is not None else None,
            callsite=(pop.attrs.get("_callsite")
                      if pop is not None else None)))
    for name in peak_live_names:
        sds = types.get(name)
        if sds is None:
            continue
        leaves = costmodel._leaves(sds)
        shape = tuple(leaves[0].shape) if leaves else ()
        dt = str(leaves[0].dtype) if leaves else "?"
        pi, pop = producer.get(name, (None, None))
        peak_set.append(LiveTensor(
            name=name, bytes=bytes_of(name), shape=shape, dtype=dt,
            kind="activation", producer_index=pi,
            producer_type=pop.type if pop is not None else None,
            callsite=(pop.attrs.get("_callsite")
                      if pop is not None else None)))
    for b, lbl, src in peak_residuals:
        sop = ops[src]
        peak_set.append(LiveTensor(
            name=f"<{lbl} residuals #{src}>", bytes=b, shape=(),
            dtype="-", kind="residual", producer_index=src,
            producer_type=lbl, callsite=sop.attrs.get("_callsite")))
    mem.peak_live = peak_set

    # ---- the plan's collective wire bytes (psum/all-reduce/all-to-all) -
    if plan is not None and include_costs:
        from .sharding import estimate_collectives

        try:
            mem.collectives = estimate_collectives(
                program, feed_names, fetch_names, plan=plan, scope=scope,
                batch_size=batch_size, types=types)
        except Exception:  # noqa: BLE001 - pricing must never break lint
            mem.collectives = None
    return mem


# --------------------------------------------------------------------------
# Remat advisor
# --------------------------------------------------------------------------
def advise_recompute(program: Program, mem: MemoryAnalysis,
                     min_ops: int = 3,
                     top_n: int = 5) -> List[RematAdvice]:
    """Rank candidate ``recompute_guard`` spans in the forward region by
    peak bytes they would free vs the traffic/FLOPs their barriered
    backward recompute would add. Only meaningful for training programs
    (a program with no grad ops holds no fwd->bwd activations — advice
    is empty there, remat cannot help inference)."""
    from ..core.backward import NON_DIFFERENTIABLE, SEGMENT_SAVE_OPS

    block = program.global_block
    ops = list(block.ops)
    first_grad = next(
        (i for i, op in enumerate(ops)
         if op.type in ("grad", "grad_custom", "grad_seg")), None)
    if first_grad is None:
        return []

    # names the backward actually reads (these are the pinned activations)
    bwd_reads: Set[str] = set()
    for op in ops[first_grad:]:
        bwd_reads.update(op.input_names())

    def eligible(op: Operator) -> bool:
        if not has_op(op.type) or op.type in NON_DIFFERENTIABLE:
            return False
        opdef = get_op(op.type)
        return not (opdef.special or opdef.needs_rng is True
                    or op.attrs.get("__recompute_seg__") is not None)

    types_cache: Dict[str, object] = {}

    def _bytes(name: str) -> float:
        # sizes via the recorded peak set / live curve are name-free;
        # re-derive from the op costs' slot shapes is overkill — use the
        # analysis peak tensors where available, else 0
        if not types_cache:
            for t in mem.peak_live:
                types_cache[t.name] = t.bytes
        return types_cache.get(name, 0.0)

    advice: List[RematAdvice] = []
    i = 0
    while i < first_grad:
        if not eligible(ops[i]):
            i += 1
            continue
        j = i
        while j + 1 < first_grad and eligible(ops[j + 1]):
            j += 1
        if j - i + 1 >= min_ops:
            saved = 0.0
            traffic = 0.0
            flops = 0.0
            op_types: List[str] = []
            site = None
            for k in range(i, j + 1):
                op = ops[k]
                op_types.append(op.type)
                if site is None:
                    site = op.attrs.get("_callsite")
                c = mem.op_costs[k] if k < len(mem.op_costs) else None
                if c is not None:
                    traffic += c.bytes
                    flops += c.flops
                save_all = op.type in SEGMENT_SAVE_OPS
                for name in op.output_names():
                    if save_all:
                        continue  # checkpoint policy keeps these anyway
                    if name in bwd_reads:
                        saved += _bytes(name)
            if saved > 0:
                advice.append(RematAdvice(
                    start=i, end=j, op_types=op_types, bytes_saved=saved,
                    extra_traffic_bytes=traffic, extra_flops=flops,
                    callsite=site))
        i = j + 1
    advice.sort(key=lambda a: -a.bytes_saved)
    return advice[:top_n]


# --------------------------------------------------------------------------
# Budget gating
# --------------------------------------------------------------------------
def check_memory_budget(program: Program, feed_names: Sequence[str],
                        fetch_names: Sequence[str], budget_bytes: float,
                        scope: Optional[Scope] = None,
                        batch_size: int = 1,
                        what: str = "program",
                        plan=None) -> MemoryAnalysis:
    """Raise :class:`MemoryBudgetError` when the static peak-HBM
    watermark exceeds ``budget_bytes``; returns the analysis otherwise.
    With a plan (argument or ShardProgram-annotated program) the budget
    gates the PER-DEVICE watermark — sharding state IS the remedy the
    advisor can't suggest, so it is priced in before the gate fires."""
    mem = analyze_memory(program, feed_names, fetch_names, scope=scope,
                         batch_size=batch_size, plan=plan)
    if mem.peak_bytes <= budget_bytes:
        return mem
    top = mem.top(8)
    advice = advise_recompute(program, mem)
    lines = [
        f"{what}: static peak-HBM estimate "
        f"{_fmt_bytes(mem.peak_bytes)} exceeds mem_budget "
        f"{_fmt_bytes(budget_bytes)} (batch={batch_size}; "
        f"resident {_fmt_bytes(mem.resident_bytes)} + transient "
        f"{_fmt_bytes(mem.peak_bytes - mem.resident_bytes)} at op "
        f"#{mem.peak_op_index} {mem.peak_op_type!r})",
        "top live tensors at the peak:",
    ]
    lines += ["  " + t.format() for t in top]
    if advice:
        lines.append("remat advisor (bytes-saved vs bytes-re-streamed):")
        lines += ["  " + a.format() for a in advice]
        lines.append(
            "  (remat trades HBM traffic for peak memory — PERF.md "
            "round 3: a memory lever, not a bandwidth lever)")
    else:
        lines.append("no recompute_guard candidates found (inference "
                     "program or segments already in place) — reduce "
                     "batch, shard state, or raise the budget")
    raise MemoryBudgetError("\n".join(lines), peak_bytes=mem.peak_bytes,
                            budget_bytes=budget_bytes, top=top,
                            advice=advice)
