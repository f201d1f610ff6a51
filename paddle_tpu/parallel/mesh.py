"""Device-mesh construction helpers.

Replaces the reference's device-topology knobs (--trainer_count,
--num_gradient_servers, --ports_num; /root/reference/paddle/utils/Flags.h:19-44)
with a single declarative object: a jax.sharding.Mesh whose named axes are the
parallelism dimensions (dp = data, mp = tensor/model, pp = pipeline,
sp = sequence, ep = expert). Collectives ride ICI within a slice and DCN
across slices; XLA picks the routing from the mesh's device order.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a named device mesh.

    ``axes`` maps axis name -> size, in major-to-minor order; a size of -1
    means "all remaining devices". Defaults to a pure data-parallel mesh over
    every visible device.

    Device order comes from ``jax.experimental.mesh_utils``: on a TPU it
    follows the physical topology (so mesh neighbours are ICI neighbours),
    on the virtual CPU mesh it is a plain reshape. A topology it cannot
    lay out raises — a silently chosen device order is not a fallback.
    """
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"dp": len(devices)}
    axes = dict(axes)
    known = 1
    wild = None
    for name, size in axes.items():
        if size == -1:
            if wild is not None:
                raise ValueError("only one mesh axis may be -1")
            wild = name
        else:
            known *= size
    if wild is not None:
        if len(devices) % known:
            raise ValueError(
                f"{len(devices)} devices not divisible by fixed axes {axes}")
        axes[wild] = len(devices) // known
        known *= axes[wild]
    if known != len(devices):
        raise ValueError(
            f"mesh axes {axes} require {known} devices, have {len(devices)}")
    if len(devices) > 1:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            tuple(axes.values()), devices=devices)
    else:
        dev_array = np.array(devices).reshape(tuple(axes.values()))
    return Mesh(dev_array, tuple(axes.keys()))


def mesh_axis_size(mesh: Mesh, axis: str) -> int:
    """Size of a named axis, 1 if the axis is absent."""
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)


def make_abstract_mesh(axes: Dict[str, int]):
    """A devices-free mesh skeleton (jax.sharding.AbstractMesh) for the
    analysis plane: ShardingPlans built over it resolve specs, divide
    per-device bytes, and price collectives without the process owning
    ``dp*mp*...`` real devices — how ``tools/proglint.py --mesh dp=4,mp=2``
    lints a sharded program on a 1-device box. Not executable: hand the
    executor a plan over a real :func:`make_mesh` mesh instead."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(int(v) for v in axes.values()),
                        tuple(str(k) for k in axes))
