"""Kernels: the share of the window's expert-layer calls whose grouped
matmuls ran on the Pallas kernel (``kernels/grouped_matmul``: a prefill
unit's rows, and a tick's where its shape is over the kernel's threshold)
instead of XLA's ``ragged_dot``: the engine's ``moe_kernel_layer_calls``
over ``moe_layer_calls`` (after - before over the window; the engine knows
a program's shape when it builds it, and counts a call's layers under
both). None where the engine does not count it (a program without the
kernel, a model without experts). Source: program counter."""


def read(trace, spans, counters, cell):
    calls = counters.get("moe_layer_calls")
    on_kernel = counters.get("moe_kernel_layer_calls")
    if not calls or on_kernel is None:
        return None
    return 100.0 * on_kernel / calls
