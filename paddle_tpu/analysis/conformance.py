"""Op-registry conformance audit.

A statically-checkable metadata contract for every registered op: when a
new kernel is registered inconsistently (an ``optional_inputs`` slot the
kernel never reads, a ``needs_rng`` predicate that isn't callable-safe,
``grad_fn_is_optimization`` without a ``grad_fn``), the audit — run by
``tests/test_registry_conformance.py`` and ``tools/proglint.py
--audit`` — fails with the op named, instead of the inconsistency
surfacing as a runtime crash in whatever program first exercises it.
"""
from __future__ import annotations

import inspect
from typing import List, Optional

from ..core.registry import OpDef, get_op, registered_ops
from .lint import ERROR, LintIssue


def _kernel_source(opdef: OpDef) -> Optional[str]:
    try:
        return inspect.getsource(opdef.fn)
    except (OSError, TypeError):
        return None


def _accepts_rng(opdef: OpDef) -> bool:
    try:
        sig = inspect.signature(opdef.fn)
    except (ValueError, TypeError):
        return True  # unsignaturable callables: give the benefit of doubt
    params = sig.parameters
    return "rng" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values())


def _slot_mentioned(source: Optional[str], slot: str) -> bool:
    """Kernels address slots as string literals (``ins["Bias"]``,
    ``maybe(ins, "Bias")``); a declared slot whose name never appears in
    the kernel source is a stale declaration."""
    if source is None:
        return True  # source unavailable (C-accelerated, exec'd): skip
    return f'"{slot}"' in source or f"'{slot}'" in source


def _op_issue(op_type: str, severity: str, message: str) -> LintIssue:
    return LintIssue(rule="registry-conformance", severity=severity,
                     message=f"op {op_type!r}: {message}", op_type=op_type)


def audit_op(op_type: str) -> List[LintIssue]:
    """Audit one op's registry metadata; returns issues (empty = clean)."""
    opdef = get_op(op_type)
    issues: List[LintIssue] = []

    for field in ("optional_inputs", "stop_gradient_inputs"):
        slots = getattr(opdef, field)
        if not isinstance(slots, tuple):
            issues.append(_op_issue(
                op_type, ERROR, f"{field} must be a tuple, got "
                                f"{type(slots).__name__}"))
            continue
        for slot in slots:
            if not isinstance(slot, str) or not slot:
                issues.append(_op_issue(
                    op_type, ERROR,
                    f"{field} entry {slot!r} is not a slot name"))

    # needs_rng: strictly False, strictly True, or a predicate over attrs
    nr = opdef.needs_rng
    if not isinstance(nr, bool):
        if not callable(nr):
            issues.append(_op_issue(
                op_type, ERROR,
                f"needs_rng must be a bool or a predicate over attrs, "
                f"got {type(nr).__name__}"))
        else:
            try:
                verdict = nr({})
                bool(verdict)
            except Exception as exc:
                issues.append(_op_issue(
                    op_type, ERROR,
                    f"needs_rng predicate must accept an attrs dict and "
                    f"return a truth value; calling it with {{}} raised "
                    f"{type(exc).__name__}: {exc}"))

    if opdef.grad_fn_is_optimization and opdef.grad_fn is None:
        issues.append(_op_issue(
            op_type, ERROR,
            "grad_fn_is_optimization=True requires a grad_fn (the flag "
            "asserts vjp-of-forward is still valid ALONGSIDE a custom "
            "grad — with no grad_fn it is meaningless)"))

    if opdef.has_loop and (opdef.grad_fn is not None or opdef.special
                           or opdef.needs_rng):
        issues.append(_op_issue(
            op_type, ERROR,
            "has_loop=True pairs the op with the generic grad op (traced "
            "once under jax.vjp): an op with a grad_fn, a special op or "
            "one that draws randomness never gets that grad op"))

    # cost-model coverage contract (applies to special ops too): every
    # op carries an analytical cost handler (costmodel.register_cost) or
    # an explicit cost_exempt marker — the roofline/memory plane must
    # never meet an op it silently cannot price
    from . import costmodel

    costmodel.ensure_registered()
    if opdef.cost_fn is None and not opdef.cost_exempt:
        issues.append(_op_issue(
            op_type, ERROR,
            "no cost-model handler registered and not cost_exempt: add "
            "a handler via analysis.costmodel.register_cost (FLOPs + "
            "HBM bytes from the abstract input/output shapes) or mark "
            "it analysis.costmodel.cost_exempt with a reason"))
    if opdef.cost_fn is not None and not callable(opdef.cost_fn):
        issues.append(_op_issue(op_type, ERROR, "cost_fn is not callable"))

    if opdef.special:
        return issues  # executor-trace calling convention: nothing below
    # applies (special kernels take executor/env/op kwargs)

    if (opdef.needs_rng is not False) and not _accepts_rng(opdef):
        issues.append(_op_issue(
            op_type, ERROR,
            "needs_rng is not strictly False, so the kernel must accept "
            "an ``rng`` keyword (None when this instance draws nothing)"))

    source = _kernel_source(opdef)
    for field in ("optional_inputs", "stop_gradient_inputs"):
        slots = getattr(opdef, field)
        if not isinstance(slots, tuple):
            continue
        for slot in slots:
            if isinstance(slot, str) and not _slot_mentioned(source, slot):
                issues.append(_op_issue(
                    op_type, ERROR,
                    f"{field} declares slot {slot!r} but the kernel "
                    f"source never references it — stale or misspelled "
                    f"slot declaration"))
    if opdef.grad_fn is not None and not callable(opdef.grad_fn):
        issues.append(_op_issue(op_type, ERROR, "grad_fn is not callable"))
    return issues


def audit_op_registry() -> List[LintIssue]:
    """Audit every registered op. Returns all findings; a clean registry
    returns []."""
    issues: List[LintIssue] = []
    for op_type in registered_ops():
        issues.extend(audit_op(op_type))
    return issues
