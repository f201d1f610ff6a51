"""Hash the lowered (StableHLO) text of the paged prefill / decode ops and
the train stack of every tiny twin configuration under
``benchmark/tests/data/configs`` on the CPU, one line a program: run it in
two checkouts and ``diff`` the output to show that a change leaves the
programs of the configurations it does not touch as they were.

    JAX_PLATFORMS=cpu python tools/lowered_text.py [config.json ..]

``--kernels`` hashes instead the traced program (the jaxpr, the kernel's
body and grid included, no source location in it) of the paged attention
kernels' UNMASKED calls at the serve cells' shapes (``KERNEL_CALLS``): what a
change to ``kernels/paged_attention.py`` that other cells must not feel has
to leave as it was (``tests/test_dsa_mask_walk.py`` holds the hashes).
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
    if spec.rope == "mrope":        # three-axis rotary ids / a slot's offset
        rows["PosIds" if prefill else "RopeOffset"] = (
            (b, 3 * CHUNK) if prefill else (b,), "int32")
    if spec.vision is not None and prefill:     # the tower in the unit
        v = spec.vision
        rows["MediaRow"] = ((b, CHUNK), "int32")
        rows["Pixels"] = ((b, -(-CHUNK // v.tokens_per_frame) + 1)
                          + v.frame_shape, "uint8")
        for slot, _, shape, _, _ in spec.vision_planes():
            weights[slot] = (tuple(shape), dt)
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
            and not spec.draft_block and spec.attn != "mla" \
            and spec.rope != "mrope" and not spec.index_topk:
        import jax.numpy as jnp

        blk = spec.block
        p = {key: jnp.zeros(shape, spec.param_dtype)
             for _, key, shape, _ in spec.stack_planes()}
        x = jnp.zeros((2, 8, spec.d_model), jnp.float32)
        yield "train_block", jax.jit(
            lambda p, x: pipeline_ops._block(blk, p, x, True)).lower(
                p, x).as_text()


#: cell -> (slots, heads, KV heads, d_head, page, pool layers, pages, table
#: width, prefill chunk, window) of its K/V kernels' calls
KV_CALLS = {
    "kexaone-serve-reason": (64, 64, 8, 128, 64, 2, 4096, 96, 256, None),
    "kexaone-serve-reason.window": (64, 64, 8, 128, 64, 6, 512, 96, 256, 128),
    "olmoe-serve-chat": (32, 16, 16, 128, 64, 8, 1024, 32, 128, None),
}
#: cell -> (slots, heads, pool row W, latent r, page, pool layers, pages,
#: table width, prefill chunk) of its latent kernels' calls
LATENT_CALLS = {
    "mistral4-serve-longdoc": (64, 32, 384, 256, 256, 6, 1536, 80, 256),
    "ling3-serve-reason": (128, 32, 640, 512, 256, 1, 4096, 48, 256),
}


def kernel_texts():
    """(call, jaxpr text) of every UNMASKED paged attention kernel call the
    cells of ``KV_CALLS`` / ``LATENT_CALLS`` make: a tick (kexaone's: a
    verify tick of two positions), a prefill chunk."""
    import jax.numpy as jnp

    from paddle_tpu.kernels import paged_attention as pa

    bf, i32 = jnp.bfloat16, jnp.int32

    def arg(shape, dt=bf):
        return jax.ShapeDtypeStruct(shape, dt)

    for cell, (slots, H, Hkv, dh, ps, L, N, P, tc, window) in KV_CALLS.items():
        pool = arg((L, N, ps, Hkv * dh))
        rows = (arg(()), arg((slots, P), i32), arg((slots,), i32))
        if cell.startswith("kexaone"):
            yield f"{cell} verify", jax.make_jaxpr(
                lambda q, k, v, l, t, n: pa.paged_attention_verify(
                    q, k, v, l, t, n, window=window))(
                        arg((slots, H, 2, dh)), pool, pool, *rows)
        else:
            yield f"{cell} decode", jax.make_jaxpr(
                lambda q, k, v, l, t, n: pa.paged_attention_decode(
                    q, k, v, l, t, n, window=window))(
                        arg((slots, H, dh)), pool, pool, *rows)
        yield f"{cell} prefill", jax.make_jaxpr(
            lambda q, k, v, l, t, s, n: pa.paged_attention_prefill(
                q, k, v, l, t, s, n, window=window))(
                    arg((1, H, tc, dh)), pool, pool, arg(()),
                    arg((1, P), i32), arg((1,), i32), arg((1,), i32))
    for cell, (slots, H, W, r, ps, L, N, P, tc) in LATENT_CALLS.items():
        pool = arg((L, N, ps, W))
        yield f"{cell} decode", jax.make_jaxpr(
            lambda q, k, l, t, n: pa.paged_attention_decode(
                q, k, None, l, t, n, sm_scale=1.0, name=pa.MLA_KERNEL))(
                    arg((slots, H, W)), pool, arg(()), arg((slots, P), i32),
                    arg((slots,), i32))
        yield f"{cell} prefill", jax.make_jaxpr(
            lambda q, k, l, t, s, n: pa.paged_attention_prefill(
                q, k, None, l, t, s, n, sm_scale=1.0, value_width=r))(
                    arg((1, H, tc, W)), pool, arg(()), arg((1, P), i32),
                    arg((1,), i32), arg((1,), i32))


def kernel_hashes():
    """call -> the first 16 hex digits of its traced program's sha256."""
    return {call: hashlib.sha256(str(text).encode()).hexdigest()[:16]
            for call, text in kernel_texts()}


def main(paths):
    import paddle_tpu as pt

    if paths[:1] == ["--kernels"]:
        for call, digest in kernel_hashes().items():
            print(call, digest)
        return

    for path in paths:
        with open(path) as f:
            config = json.load(f)
        if not str(config.get("family", "")).endswith(("_lm", "_vl")):
            continue
        pt.set_amp(config.get("amp") == "bfloat16")
        for what, text in texts_of(config):
            print(os.path.basename(path), what,
                  hashlib.sha256(text.encode()).hexdigest()[:16], len(text))


if __name__ == "__main__":
    main(sys.argv[1:] or sorted(glob.glob(os.path.join(
        ROOT, "benchmark", "tests", "data", "configs", "*.json"))))
