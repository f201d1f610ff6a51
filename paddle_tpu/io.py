"""Checkpoint & inference-model persistence.

Parity surface of /root/reference/python/paddle/v2/fluid/io.py:32-218
(save_vars/save_params/save_persistables, load_*, save_inference_model,
load_inference_model) and the save/load ops
(/root/reference/paddle/operators/save_op.cc, load_op.cc).

The TPU-native design difference: the reference emits save/load ops into a
program and runs them through the per-op executor; here persistence is a
host-side operation on the scope (device->host DMA + npz/pickle), since
serialisation is not compute and does not belong in an XLA computation.
Program serialisation uses a stable JSON-encodable dict (the analogue of the
ProgramDesc protobuf) so saved models are portable across processes.
"""
from __future__ import annotations

import json
import os
import pickle
from typing import Dict, List, Optional, Sequence

import numpy as np

from .core.program import (Block, Operator, Parameter, Program, Variable,
                           default_main_program)
from .core.scope import Scope, global_scope

__all__ = [
    "save_vars", "save_params", "save_persistables", "load_vars",
    "load_params", "load_persistables", "save_inference_model",
    "load_inference_model", "read_inference_model_meta",
    "program_to_dict", "program_from_dict", "prune_program",
    "transpile_saved_model", "quantize_inference_model",
]


# --------------------------------------------------------------------------
# Program (de)serialisation — ProgramDesc-protobuf equivalent
# --------------------------------------------------------------------------
def program_to_dict(program: Program) -> dict:
    blocks = []
    for b in program.blocks:
        blocks.append({
            "idx": b.idx,
            "parent_idx": b.parent_idx,
            "vars": [
                {
                    "name": v.name,
                    "shape": list(v.shape) if v.shape is not None else None,
                    "dtype": str(v.dtype),
                    "persistable": v.persistable,
                    "stop_gradient": v.stop_gradient,
                    "lod_level": v.lod_level,
                    "is_data": v.is_data,
                    "is_parameter": isinstance(v, Parameter),
                }
                for v in b.vars.values()
            ],
            "ops": [
                {"type": op.type, "inputs": op.inputs, "outputs": op.outputs,
                 "attrs": op.attrs}
                for op in b.ops
            ],
        })
    return {"blocks": blocks, "version": 1}


def program_from_dict(d: dict) -> Program:
    p = Program()
    p.blocks = []
    for bd in d["blocks"]:
        b = Block(p, bd["idx"], bd["parent_idx"])
        for vd in bd["vars"]:
            cls = Parameter if vd.get("is_parameter") else Variable
            v = cls(b, vd["name"], shape=vd["shape"], dtype=vd["dtype"],
                    persistable=vd["persistable"],
                    stop_gradient=vd["stop_gradient"],
                    lod_level=vd.get("lod_level", 0),
                    is_data=vd.get("is_data", False))
            b.vars[vd["name"]] = v
        for od in bd["ops"]:
            b.ops.append(Operator(b, od["type"], od["inputs"], od["outputs"],
                                  od["attrs"]))
        p.blocks.append(b)
    return p


# --------------------------------------------------------------------------
# Variable persistence
# --------------------------------------------------------------------------
def _is_persistable(v: Variable) -> bool:
    return v.persistable


def _is_parameter(v: Variable) -> bool:
    return isinstance(v, Parameter)


def save_vars(executor, dirname: str, main_program: Optional[Program] = None,
              vars: Optional[Sequence[Variable]] = None, predicate=None,
              scope: Optional[Scope] = None):
    """Save selected scope variables to ``dirname`` (one .npy per var +
    manifest), mirroring io.py save_vars semantics."""
    program = main_program or default_main_program()
    scope = scope or global_scope()
    if vars is None:
        vars = [v for v in program.list_vars() if predicate(v)]
    os.makedirs(dirname, exist_ok=True)
    manifest = []
    for v in vars:
        if not scope.has(v.name):
            continue
        arr = scope.get_numpy(v.name)
        fname = v.name.replace("/", "__")
        entry = {"name": v.name, "file": fname + ".npy"}
        if arr.dtype.kind == "V":
            # ml_dtypes (bf16/fp8) round-trip through np.save as raw void
            # ('|V2') and come back unreadable — store the integer bit
            # view and the logical dtype in the manifest instead.
            entry["dtype"] = str(arr.dtype)
            arr = arr.view(np.dtype(f"u{arr.dtype.itemsize}"))
        np.save(os.path.join(dirname, fname + ".npy"), arr)
        manifest.append(entry)
    with open(os.path.join(dirname, "MANIFEST.json"), "w") as f:
        json.dump(manifest, f, indent=1)


def save_params(executor, dirname, main_program=None, scope=None):
    return save_vars(executor, dirname, main_program, None, _is_parameter, scope)


def save_persistables(executor, dirname, main_program=None, scope=None):
    return save_vars(executor, dirname, main_program, None, _is_persistable, scope)


def load_vars(executor, dirname, main_program=None, vars=None, predicate=None,
              scope=None):
    program = main_program or default_main_program()
    scope = scope or global_scope()
    if vars is None:
        vars = [v for v in program.list_vars() if predicate(v)]
    with open(os.path.join(dirname, "MANIFEST.json")) as f:
        manifest = {e["name"]: e for e in json.load(f)}
    import jax.numpy as jnp

    for v in vars:
        if v.name not in manifest:
            continue
        entry = manifest[v.name]
        arr = np.load(os.path.join(dirname, entry["file"]))
        if entry.get("dtype"):
            import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names

            arr = arr.view(np.dtype(entry["dtype"]))
        # values land on the loading executor's device (mesh executors
        # reshard at the first step)
        with executor.device_ctx():
            scope.set(v.name, jnp.asarray(arr))


def load_params(executor, dirname, main_program=None, scope=None):
    return load_vars(executor, dirname, main_program, None, _is_parameter, scope)


def load_persistables(executor, dirname, main_program=None, scope=None):
    return load_vars(executor, dirname, main_program, None, _is_persistable, scope)


# --------------------------------------------------------------------------
# Inference model: program pruning + save
# --------------------------------------------------------------------------
def prune_program(program: Program, feed_names: List[str],
                  fetch_names: List[str], for_test: bool = True) -> Program:
    """Slice the program to the subgraph producing ``fetch_names`` from
    ``feed_names`` (the reference's prune.cc / inference_optimize).

    Runs the transpiler's ``prune_pipeline`` on a clone: composite
    ``seg_fwd`` recompute segments flatten back to plain forward ops
    (checkpointing only matters when training, and a flat op list keeps
    the saved artifact consumable by every backend including the native
    C machine), ``for_test`` canonicalizes every ``is_test`` attr, and
    dead-op elimination takes the backward slice from the fetches."""
    from .transpiler import prune_pipeline

    return prune_pipeline(for_test=for_test).run(
        program.clone(), feed_names, fetch_names)


def save_inference_model(dirname: str, feeded_var_names: List[str],
                         target_vars: List[Variable], executor,
                         main_program: Optional[Program] = None, scope=None,
                         transpile: bool = True):
    """Prune to the inference subgraph, run the transpiler's inference
    pipeline (dropout→scale, constant folding, BN folding, fused-kernel
    rewrites — ``transpile=False`` restores the plain prune), and persist
    program + params (reference io.py:165 save_inference_model).

    Weight-rewriting passes write NEW names into a child scope; the
    caller's scope is never mutated."""
    program = main_program or default_main_program()
    fetch_names = [v.name if hasattr(v, "name") else v for v in target_vars]
    pruned = prune_program(program, feeded_var_names, fetch_names)
    save_scope = scope or global_scope()
    if transpile:
        from .transpiler import inference_pipeline

        work_scope = Scope(parent=save_scope)
        pruned = inference_pipeline().run(
            pruned, feeded_var_names, fetch_names, scope=work_scope)
        save_scope = work_scope
    from .flags import FLAGS

    if FLAGS.verify_program:
        # never persist an artifact the verifier rejects: the saved model
        # is the contract every serving replica loads
        from . import analysis

        analysis.check_program(pruned, feeded_var_names, fetch_names,
                               scope=save_scope, annotate=False)
    os.makedirs(dirname, exist_ok=True)
    _drop_stale_manifest(dirname)
    with open(os.path.join(dirname, "__model__.json"), "w") as f:
        json.dump({
            "program": program_to_dict(pruned),
            "feed_names": feeded_var_names,
            "fetch_names": fetch_names,
        }, f)
    save_vars(executor, os.path.join(dirname, "params"),
              main_program=pruned, predicate=_is_persistable,
              scope=save_scope)


def _drop_stale_manifest(dirname: str) -> None:
    """Re-saving an artifact invalidates its warmup manifest: the old
    signatures reference the previous program's digest, and leaving them
    would make every future boot skip-replay (or merge-accumulate stale
    records forever). The next warmup writes a fresh one."""
    from .core.manifest import MANIFEST_NAME

    try:
        os.remove(os.path.join(dirname, MANIFEST_NAME))
    except OSError:
        pass


def _load_saved_params(dirname: str) -> Scope:
    """Load a saved model's params/ directory into a fresh host Scope
    (numpy arrays; no executor involved) for offline transpilation."""
    scope = Scope()
    with open(os.path.join(dirname, "params", "MANIFEST.json")) as f:
        manifest = json.load(f)
    for entry in manifest:
        arr = np.load(os.path.join(dirname, "params", entry["file"]))
        if entry.get("dtype"):
            import ml_dtypes  # noqa: F401 — registers bfloat16/fp8 names

            arr = arr.view(np.dtype(entry["dtype"]))
        scope.set(entry["name"], arr)
    return scope


def transpile_saved_model(dirname: str, out_dirname: str, pipeline=None):
    """Re-run a transpile pipeline over an already-saved inference model,
    writing a new saved-model directory. Defaults to the transpiler's
    ``deployment_pipeline`` — the portable form with fused ops lowered
    back to folded conv2d + bias add, which is what int8 weight
    quantization and the native C machine want. Returns the PassManager
    (``.stats()`` has the per-pass numbers)."""
    from .transpiler import deployment_pipeline

    with open(os.path.join(dirname, "__model__.json")) as f:
        payload = json.load(f)
    program = program_from_dict(payload["program"])
    scope = _load_saved_params(dirname)
    pm = pipeline or deployment_pipeline()
    program = pm.run(program, payload["feed_names"],
                     payload["fetch_names"], scope=scope)
    os.makedirs(out_dirname, exist_ok=True)
    with open(os.path.join(out_dirname, "__model__.json"), "w") as f:
        json.dump({
            "program": program_to_dict(program),
            "feed_names": payload["feed_names"],
            "fetch_names": payload["fetch_names"],
        }, f)
    save_vars(None, os.path.join(out_dirname, "params"),
              main_program=program, predicate=_is_persistable, scope=scope)
    return pm


def quantize_inference_model(dirname: str, out_dirname: str,
                             min_elems: int = 1024,
                             transpile: bool = True) -> List[str]:
    """Weight-only per-output-channel int8 quantization of a saved
    inference model, for the C machine (beyond-reference; the reference
    era predates int8 deployment).

    Eligible weights (>= ``min_elems`` f32 elements) are per-output-
    channel symmetric int8 (scale = max|w over channel| / 127), recorded
    in ``__quant__.json`` sidecars; everything else copies through:
    - 2-D params used EXCLUSIVELY as ``mul`` right-hand sides (fc / qkv
      / head projections, the bulk of LM bytes): the C machine keeps the
      int8 bytes resident and folds the scales into the matmul epilogue
      — ~4x serving memory AND artifact size;
    - 4-D params used exclusively as ``conv2d`` filters (one consistent
      data_format): int8 in the artifact, dequantized once at load
      (filters are small next to activations — the win is the shipped
      bytes).
    Weights with any other/shared use stay f32. The quantized directory
    is C-machine-only (the Python executor load path expects the f32
    manifest).

    ``transpile`` (default) first runs the transpiler's deployment
    pipeline over the saved model: batch_norm folds into the preceding
    conv/mul weights and fused ``conv1x1_bn_act`` ops lower to plain
    folded conv2d — so weights that were locked up in BN-adjacent or
    fused forms become int8-eligible (strictly more parameter bytes
    quantize on conv+BN models)."""
    import shutil
    import tempfile

    tmpdir = None
    if transpile:
        tmpdir = tempfile.mkdtemp(prefix="quant_transpile_")
        transpile_saved_model(dirname, tmpdir)
        dirname = tmpdir
    try:
        return _quantize_saved_model(dirname, out_dirname, min_elems)
    finally:
        if tmpdir is not None:
            shutil.rmtree(tmpdir, ignore_errors=True)


def _quantize_saved_model(dirname: str, out_dirname: str,
                          min_elems: int) -> List[str]:
    import shutil

    with open(os.path.join(dirname, "__model__.json")) as f:
        payload = json.load(f)
    # a param is eligible only if EVERY reference to it is mul's Y slot
    # (int8 stays resident) or conv2d's Filter slot with one consistent
    # data_format (int8 on disk, dequantized once at load)
    usage: dict = {}
    for op in payload["program"]["blocks"][0]["ops"]:
        for slot, names in op["inputs"].items():
            for n in names:
                if op["type"] == "mul" and slot == "Y":
                    kind = "mul"
                elif op["type"] == "conv2d" and slot == "Filter":
                    kind = "conv:" + op["attrs"].get("data_format",
                                                     "NCHW")
                else:
                    kind = "no"
                prev = usage.setdefault(n, kind)
                if prev != kind:
                    usage[n] = "no"
    os.makedirs(os.path.join(out_dirname, "params"), exist_ok=True)
    shutil.copyfile(os.path.join(dirname, "__model__.json"),
                    os.path.join(out_dirname, "__model__.json"))
    with open(os.path.join(dirname, "params", "MANIFEST.json")) as f:
        manifest = json.load(f)
    kept, quant, quantized = [], [], []
    for entry in manifest:
        arr = None
        kind = usage.get(entry["name"], "no")
        if "dtype" in entry or kind == "no":
            eligible = False  # bf16 bit-view / shared or unknown use
        else:
            arr = np.load(os.path.join(dirname, "params", entry["file"]))
            want_ndim = 2 if kind == "mul" else 4
            eligible = (arr.dtype == np.float32
                        and arr.ndim == want_ndim
                        and arr.size >= min_elems)
        if not eligible:
            shutil.copyfile(os.path.join(dirname, "params", entry["file"]),
                            os.path.join(out_dirname, "params",
                                         entry["file"]))
            kept.append(entry)
            continue
        if kind == "mul":
            reduce_axes, out_axis = (0,), 1
        else:  # conv filters: OIHW for NCHW, HWIO for NHWC
            out_axis = 0 if kind.endswith("NCHW") else 3
            reduce_axes = tuple(a for a in range(4) if a != out_axis)
        scales = np.maximum(np.abs(arr).max(axis=reduce_axes),
                            1e-12) / 127.0
        bshape = tuple(-1 if a == out_axis else 1 for a in range(arr.ndim))
        q = np.clip(np.round(arr / scales.reshape(bshape)), -127,
                    127).astype(np.int8)
        base = entry["file"][:-4]
        qfile, sfile = base + ".int8.bin", base + ".scale.bin"
        q.tofile(os.path.join(out_dirname, "params", qfile))
        scales.astype(np.float32).tofile(
            os.path.join(out_dirname, "params", sfile))
        rec = {"name": entry["name"], "qfile": qfile, "sfile": sfile,
               "kind": "mul" if kind == "mul" else "conv",
               "shape": [int(d) for d in arr.shape],
               "out_axis": out_axis}
        if kind == "mul":
            rec["rows"], rec["cols"] = int(arr.shape[0]), int(arr.shape[1])
        quant.append(rec)
        quantized.append(entry["name"])
    with open(os.path.join(out_dirname, "params", "MANIFEST.json"),
              "w") as f:
        json.dump(kept, f, indent=1)
    with open(os.path.join(out_dirname, "__quant__.json"), "w") as f:
        json.dump(quant, f, indent=1)
    return quantized


def read_inference_model_meta(dirname: str) -> dict:
    """Read a saved inference model's metadata WITHOUT loading parameters:
    returns ``{"program": <program dict>, "feed_names": [...],
    "fetch_names": [...]}``. The serving engines use this to derive
    shape buckets and decode hyperparameters (attrs + var shapes live in
    the program dict) before deciding how to place the weights."""
    with open(os.path.join(dirname, "__model__.json")) as f:
        return json.load(f)


def load_inference_model(dirname: str, executor, scope=None):
    """Returns (program, feed_names, fetch_names); parameters are loaded into
    the scope (reference io.py load_inference_model)."""
    with open(os.path.join(dirname, "__model__.json")) as f:
        payload = json.load(f)
    program = program_from_dict(payload["program"])
    load_vars(executor, os.path.join(dirname, "params"),
              main_program=program, predicate=_is_persistable, scope=scope)
    return program, payload["feed_names"], payload["fetch_names"]
