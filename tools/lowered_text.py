"""Hash the lowered (StableHLO) text of the paged prefill / decode ops and
the train stack of every tiny twin configuration under
``benchmark/tests/data/configs`` on the CPU, one line a program: run it in
two checkouts and ``diff`` the output to show that a change leaves the
programs of the configurations it does not touch as they were.

    JAX_PLATFORMS=cpu python tools/lowered_text.py [config.json ..]
"""
import glob
import hashlib
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, PAGE, PAGES, TABLE, CHUNK = 3, 8, 12, 4, 16


def op_shapes(spec, prefill):
    """name -> (shape, dtype) of every input of the paged prefill (one row)
    or decode op of ``spec`` under an engine of ``SLOTS`` slots, ``PAGES``
    pages of ``PAGE`` tokens, tables ``TABLE`` wide and chunks of ``CHUNK``."""
    dt = spec.param_dtype
    b = 1 if prefill else SLOTS
    rows = ({"Chunk": ((b, CHUNK), "int32"), "StartPos": ((b,), "int32"),
             "Lengths": ((b,), "int32")} if prefill else
            {"Tok": ((b,), "int32"), "Pos": ((b,), "int32")})
    rows.update({"BlockTable": ((b, TABLE), "int32"),
                 "Temperature": ((b,), "float32"), "TopK": ((b,), "int32"),
                 "TopP": ((b,), "float32"), "Seed": ((b,), "int32"),
                 "Step": ((b,), "int32")})
    row = (PAGE, spec.cache_row_width)
    pools = {n: ((spec.pool_layers(False), PAGES, *row), spec.page_dtype)
             for n in ("CacheK", "CacheV")[:spec.cache_pools]}
    if getattr(spec, "index_topk", 0):
        pools["CacheIndex"] = ((spec.pool_layers(False), PAGES,
                                PAGE // spec.index_pool, spec.index_dim),
                               spec.page_dtype)
    if spec.block.has_window:
        rows["BlockTableW"] = ((b, TABLE), "int32")
        pools.update({n: ((spec.pool_layers(True), PAGES, *row),
                          spec.page_dtype) for n in ("CacheKW", "CacheVW")})
    weights = {"TokEmb": ((spec.vocab_size, spec.d_model), dt),
               "FinalLnS": ((spec.d_model,), dt),
               "HeadW": ((spec.d_model, spec.vocab_size), dt)}
    if not spec.use_rope:
        weights["PosEmb"] = ((spec.max_len, spec.d_model), dt)
    if spec.norm == "layer_norm":
        weights["FinalLnB"] = ((spec.d_model,), dt)
    for slot, key, shape, _ in spec.stack_planes():
        weights[slot] = ((spec.plane_layers(key), *shape), dt)
    if spec.draft_block:
        from paddle_tpu.lm_spec import DRAFT_SLOT_PREFIX

        rows["DraftNext" if prefill else "Draft"] = ((b,), "int32")
        for slot, _, shape, _ in spec.draft_planes():
            weights[slot] = (tuple(shape), dt)
        for slot, _, shape, _ in spec.draft_spec().stack_planes():
            weights[DRAFT_SLOT_PREFIX + slot] = ((1, *shape), dt)
    state = {name: ((layers, SLOTS, *shape), dtype)
             for name, shape, dtype, layers in spec.slot_state()}
    if state and prefill:
        rows["StateSlot"] = ((b,), "int32")
    return {**rows, **pools, **weights, **state}


def texts_of(config):
    from paddle_tpu.ops import pipeline_ops

    family = importlib.import_module("benchmark.families." + config["family"])
    if hasattr(family, "spec_of"):
        spec = family.spec_of(config)
    else:                       # the GPT-2 block at the configuration's sizes
        from paddle_tpu.lm_spec import LMSpec

        sz = family.sizes(config)
        spec = LMSpec(**{k: sz[k] for k in (
            "vocab_size", "d_model", "n_layers", "num_heads", "max_len",
            "d_ff")})
    for what in ("prefill", "decode"):
        op = getattr(pipeline_ops, f"transformer_stack_paged_{what}")
        shapes = op_shapes(spec, what == "prefill")
        names = sorted(shapes)
        attrs = dict(spec.block.attrs(), page_size=PAGE, temperature=0.0,
                     top_k=0)

        def step(*args, op=op, names=names, attrs=attrs):
            outs = op(attrs, {k: [a] for k, a in zip(names, args)})
            return {k: v[0] for k, v in outs.items()}

        yield what, jax.jit(step).lower(*[
            jax.ShapeDtypeStruct(*shapes[n]) for n in names]).as_text()
    if not spec.block.attn_kinds and not spec.first_dense \
            and not spec.draft_block and spec.attn != "mla":
        import jax.numpy as jnp

        blk = spec.block
        p = {key: jnp.zeros(shape, spec.param_dtype)
             for _, key, shape, _ in spec.stack_planes()}
        x = jnp.zeros((2, 8, spec.d_model), jnp.float32)
        yield "train_block", jax.jit(
            lambda p, x: pipeline_ops._block(blk, p, x, True)).lower(
                p, x).as_text()


def main(paths):
    import paddle_tpu as pt

    for path in paths:
        with open(path) as f:
            config = json.load(f)
        if not str(config.get("family", "")).endswith("_lm"):
            continue
        pt.set_amp(config.get("amp") == "bfloat16")
        for what, text in texts_of(config):
            print(os.path.basename(path), what,
                  hashlib.sha256(text.encode()).hexdigest()[:16], len(text))


if __name__ == "__main__":
    main(sys.argv[1:] or sorted(glob.glob(os.path.join(
        ROOT, "benchmark", "tests", "data", "configs", "*.json"))))
