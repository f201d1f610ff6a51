"""Current-mesh context: lets op kernels opt into mesh-aware lowering.

Op kernels are pure functions; they cannot take a Mesh argument through the
Program IR. The executor publishes its mesh here while tracing/compiling a
block, so ops with a distributed formulation (sequence-parallel attention,
expert-parallel MoE) can pick it up — the analogue of the reference's
global DeviceContextPool (/root/reference/paddle/platform/
device_context.h:161) giving kernels their device handles.
"""
from __future__ import annotations

import contextlib
from typing import Optional

_CURRENT_MESH = None
_CURRENT_DATA_AXIS = None


@contextlib.contextmanager
def mesh_context(mesh, data_axis: Optional[str] = None):
    """Publish the executor's mesh (and the plan's batch axis, when it
    has one) for the duration of a block trace."""
    global _CURRENT_MESH, _CURRENT_DATA_AXIS
    prev = _CURRENT_MESH, _CURRENT_DATA_AXIS
    _CURRENT_MESH, _CURRENT_DATA_AXIS = mesh, data_axis
    try:
        yield
    finally:
        _CURRENT_MESH, _CURRENT_DATA_AXIS = prev


def current_mesh():
    return _CURRENT_MESH


def current_data_axis() -> Optional[str]:
    """Mesh axis the batch dim of feeds shards on (the plan's
    ``data_axis``), None without a mesh or a data axis."""
    return _CURRENT_DATA_AXIS


def mesh_axis(name: str) -> int:
    """Size of axis ``name`` on the current mesh (1 if absent/no mesh)."""
    m = _CURRENT_MESH
    if m is None or name not in m.axis_names:
        return 1
    return m.shape[name]
