"""Multi-host (multi-slice) bring-up: the DCN plane.

The reference scales across hosts with an etcd-discovered pserver fleet and
trainer processes wired by flags (--trainer_id, --pservers,
--num_gradient_servers; /root/reference/doc/design/cluster_train/README.md).
The TPU-native equivalent is radically smaller: every host runs the SAME
SPMD program, jax.distributed provides the rendezvous, and the global
device mesh spans all slices — gradient exchange is the same in-graph
all-reduce, now routed over ICI within a slice and DCN across slices by
XLA. No parameter server exists to fail over; the data plane's master
(paddle_tpu.master) remains the only stateful coordinator.

Axis placement follows the scaling-book recipe: put the
communication-light axis (dp, or ZeRO's data axis) on DCN and the
communication-heavy axes (mp/sp/ep) on ICI — ``make_hybrid_mesh`` encodes
exactly that split.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the multi-host rendezvous (idempotent).

    Arguments default from the standard env (COORDINATOR_ADDRESS,
    NUM_PROCESSES, PROCESS_ID) — the analogue of the reference's etcd
    discovery (/root/reference/go/pserver/etcd_client.go), with the
    rendezvous service standing in for etcd. Without a coordinator the
    call is a single-process no-op, so the same training script runs
    unchanged on one host. (Launchers relying on cloud auto-detection can
    call jax.distributed.initialize() directly before importing models.)
    """
    global _initialized
    if _initialized:
        return
    kwargs = {}
    addr = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if addr:
        kwargs["coordinator_address"] = addr
        kwargs["num_processes"] = int(
            num_processes if num_processes is not None
            else os.environ.get("NUM_PROCESSES", 1))
        kwargs["process_id"] = int(
            process_id if process_id is not None
            else os.environ.get("PROCESS_ID", 0))
    if not kwargs:
        # Single-process no-op — deliberately NOT latched: a later call
        # that does carry a coordinator (e.g. after flag parsing) must
        # still be able to join the rendezvous.
        return
    # jax.distributed must run before ANY backend use; detect via the
    # same probe xla_env uses rather than calling jax.process_count()
    # (which would itself initialise the backend).
    from ..xla_env import backend_initialized

    if backend_initialized():
        raise RuntimeError(
            "initialize_multihost() must run before any JAX computation "
            "(the XLA backend is already initialised in this process)")
    jax.distributed.initialize(**kwargs)
    _initialized = True


def process_info() -> Dict[str, int]:
    """(process_id, process_count, local/global device counts) — the
    --trainer_id / --num_gradient_servers analogue."""
    return {
        "process_id": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }


def make_hybrid_mesh(dcn_axes: Dict[str, int],
                     ici_axes: Dict[str, int],
                     devices: Optional[Sequence] = None) -> Mesh:
    """Mesh spanning slices: ``dcn_axes`` (major) are laid out ACROSS
    slices (host/DCN boundaries), ``ici_axes`` (minor) within a slice.

    Example — 4 slices of 8 chips, data parallel across slices, tensor x
    sequence parallel within: ``make_hybrid_mesh({"dp": 4}, {"mp": 4,
    "sp": 2})``. Uses mesh_utils.create_hybrid_device_mesh on real
    multi-slice topologies; on a single host/slice (including the virtual
    CPU mesh) it degrades to the plain ICI-ordered mesh with the same axis
    names, so programs written against the hybrid mesh run anywhere.
    """
    from .mesh import make_mesh

    devices = list(devices if devices is not None else jax.devices())
    axes = dict(dcn_axes)
    axes.update(ici_axes)
    n_slices = 1
    try:  # devices expose slice_index on real multi-slice systems
        n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    except Exception:
        pass
    if n_slices > 1:
        from jax.experimental import mesh_utils

        # create_hybrid_device_mesh takes SAME-LENGTH per-axis shapes whose
        # elementwise product is the result shape: dcn axes get size 1 in
        # the ICI shape and vice versa, so the returned array is already
        # (dcn..., ici...)-ordered — no reshape (one would scramble which
        # axis crosses slices).
        nd, ni = len(dcn_axes), len(ici_axes)
        ici_shape = (1,) * nd + tuple(ici_axes.values())
        dcn_shape = tuple(dcn_axes.values()) + (1,) * ni
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
        return Mesh(dev_array, tuple(axes.keys()))
    return make_mesh(axes, devices=devices)


def local_batch_slice(global_batch: int) -> slice:
    """Each process feeds its shard of the global batch (the analogue of
    the reference's per-trainer data sharding): rows
    [process_id * per_host, (process_id + 1) * per_host). The global
    batch must divide evenly — silently dropping remainder rows would
    corrupt loss averaging."""
    n = max(jax.process_count(), 1)
    if global_batch % n:
        raise ValueError(
            f"global batch {global_batch} is not divisible by the "
            f"{n} processes; pad or resize the batch")
    per_host = global_batch // n
    start = jax.process_index() * per_host
    return slice(start, start + per_host)
