"""Op-registry conformance audit: every registered op's metadata must be
internally consistent. This test fails the moment a new op is registered
with a stale optional_inputs slot, a broken needs_rng predicate, or a
grad_fn_is_optimization flag without a grad_fn — at registration
quality, not first-use runtime."""
import pytest

import paddle_tpu  # noqa: F401 — registers every op
from paddle_tpu import analysis
from paddle_tpu.core import registry


def test_every_registered_op_conforms():
    issues = analysis.audit_op_registry()
    assert not issues, "registry conformance violations:\n" + "\n".join(
        i.format() for i in issues)


def test_audit_is_exhaustive():
    # sanity: the audit actually walked the full registry
    assert len(registry.registered_ops()) > 200


def _identity_kernel(attrs, ins):
    return {"Out": [ins["X"][0]]}


def test_audit_catches_bad_metadata():
    """Seed a deliberately-inconsistent op; the audit must flag it."""
    registry.register_op(
        "conformance_test_bad_op", _identity_kernel,
        optional_inputs=("NoSuch" + "Slot",))
    try:
        issues = analysis.audit_op("conformance_test_bad_op")
        assert issues
        assert any("NoSuchSlot" in i.message for i in issues)
        assert all(i.severity == analysis.ERROR for i in issues)
    finally:
        registry._REGISTRY.pop("conformance_test_bad_op", None)


def test_audit_catches_optimization_flag_without_grad_fn():
    registry.register_op(
        "conformance_test_optflag_op", _identity_kernel,
        grad_fn_is_optimization=True)
    try:
        issues = analysis.audit_op("conformance_test_optflag_op")
        assert any("grad_fn_is_optimization" in i.message for i in issues)
    finally:
        registry._REGISTRY.pop("conformance_test_optflag_op", None)


def test_audit_catches_rng_kernel_without_rng_kwarg():
    registry.register_op(
        "conformance_test_rng_op", _identity_kernel, needs_rng=True)
    try:
        issues = analysis.audit_op("conformance_test_rng_op")
        assert any("rng" in i.message for i in issues)
    finally:
        registry._REGISTRY.pop("conformance_test_rng_op", None)


# --------------------------------------------------------------------------
# cost-model coverage contract
# --------------------------------------------------------------------------
def test_every_op_has_cost_handler_or_exempt_marker():
    """Every registered op is priced by the roofline cost model or
    explicitly exempted — audited over the full registry (the audit
    itself is pinned clean by test_every_registered_op_conforms)."""
    from paddle_tpu.analysis import costmodel

    for op_type in registry.registered_ops():
        assert costmodel.has_cost(op_type) or costmodel.is_cost_exempt(
            op_type), f"op {op_type!r} has no cost handler and no " \
                      f"cost_exempt marker"


def test_audit_catches_op_without_cost_handler():
    registry.register_op("conformance_test_uncosted_op", _identity_kernel)
    try:
        issues = analysis.audit_op("conformance_test_uncosted_op")
        assert any("cost-model handler" in i.message for i in issues)
        assert all(i.severity == analysis.ERROR for i in issues)
        # either remedy clears the finding: a handler ...
        from paddle_tpu.analysis import costmodel

        costmodel.register_cost(
            "conformance_test_uncosted_op",
            lambda attrs, ins, outs: costmodel.OpCost())
        assert not analysis.audit_op("conformance_test_uncosted_op")
    finally:
        registry._REGISTRY.pop("conformance_test_uncosted_op", None)


def test_paged_cache_ops_conform():
    """The paged-KV serving ops carry the full registry contract:
    optional-input declarations, cost handlers, and working
    infer_outputs (shape inference straight off the kernel)."""
    import jax
    import numpy as np

    from paddle_tpu.analysis import costmodel

    for op in ("transformer_stack_paged_prefill",
               "transformer_stack_paged_decode", "kv_cache_page_copy"):
        assert not analysis.audit_op(op), op
        assert costmodel.has_cost(op), op
    for op in ("transformer_stack_paged_prefill",
               "transformer_stack_paged_decode"):
        assert "PosEmb" in registry.get_op(op).optional_inputs

    L, Hkv, dh, d, V, ps, N, P, S = 2, 1, 8, 16, 32, 4, 6, 3, 2
    sds = jax.ShapeDtypeStruct
    stack = {
        "Ln1S": (L, d), "Ln1B": (L, d), "QkvW": (L, d, d + 2 * Hkv * dh),
        "OutW": (L, d, d), "Ln2S": (L, d), "Ln2B": (L, d),
        "FfW1": (L, d, 4 * d), "FfB1": (L, 4 * d),
        "FfW2": (L, 4 * d, d), "FfB2": (L, d),
        "TokEmb": (V, d), "FinalLnS": (d,), "FinalLnB": (d,),
        "HeadW": (d, V),
    }
    ins = {k: [sds(s, np.float32)] for k, s in stack.items()}
    ins.update({
        "Tok": [sds((S,), np.int64)], "Pos": [sds((S,), np.int32)],
        "BlockTable": [sds((S, P), np.int32)],
        "CacheK": [sds((L, N, ps, Hkv * dh), np.float32)],
        "CacheV": [sds((L, N, ps, Hkv * dh), np.float32)],
    })
    attrs = {"num_heads": 2, "num_kv_heads": Hkv, "page_size": ps}
    outs = registry.infer_outputs("transformer_stack_paged_decode",
                                  attrs, ins)
    assert tuple(outs["NextTok"][0].shape) == (S,)
    assert tuple(outs["CacheK"][0].shape) == (L, N, ps, Hkv * dh)
    cost = registry.get_op("transformer_stack_paged_decode").cost_fn(
        attrs, ins, outs)
    assert cost.flops > 0 and cost.bytes > 0
    # the decode op's K/V term is the pages the rows HOLD where Pos
    # carries values, else rows x table width as an upper bound
    page = 2 * L * ps * Hkv * dh * 4                  # K and V, all layers
    cost_fn = registry.get_op("transformer_stack_paged_decode").cost_fn
    held = dict(ins, Pos=[np.array([0, 2 * ps + 1], np.int32)])
    assert cost.bytes - cost_fn(attrs, held, outs).bytes == (S * P - 4) * page
    full = dict(ins, Pos=[np.array([P * ps - 1, P * ps + 7], np.int32)])
    assert cost_fn(attrs, full, outs).bytes == cost.bytes


def test_audit_accepts_cost_exempt_marker():
    registry.register_op("conformance_test_exempt_op", _identity_kernel)
    try:
        from paddle_tpu.analysis import costmodel

        costmodel.cost_exempt("conformance_test_exempt_op")
        assert not analysis.audit_op("conformance_test_exempt_op")
    finally:
        registry._REGISTRY.pop("conformance_test_exempt_op", None)
