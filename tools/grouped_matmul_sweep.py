"""Time the expert layer's grouped matmuls by shape on the chip:
``jax.lax.ragged_dot`` as ``ops/moe_ops.moe_topk`` calls it under ``layer``
(``L * E`` groups, all but E empty) against ``kernels/grouped_matmul`` at
every (rows, K -> N) the six MoE serve cells run, tick and prefill unit,
one JSON row a reading on stdout. The table in
``kernels/grouped_matmul.py`` (and ``MIN_ROWS`` under it) is this tool's
output.

    python tools/grouped_matmul_sweep.py [--cells olmoe kexaone ..] [--seed N]

Group sizes are drawn as the cells draw them: ``even`` (every assignment
row picks one of the router's E experts uniformly; under ``held`` only the
held experts' rows sit in groups, the rest behind them) and ``alike``
(half the tokens are vacant slots and route to ONE set of top-k experts,
the rest evenly). Each reading is one jitted chain of ``--calls``
dependent calls walking the layers of the stack (a corner of the result is
written back into the rows, so nothing overlaps or is hoisted), best of
five by the host clock around ``block_until_ready``, divided by the calls.
``bytes`` = the touched experts' planes + the owned rows in (bf16) and out
(float32); ``share_pct`` = bytes / 819 GB/s over the time. ``--row-tile`` /
``--block-mb`` re-time the kernel at other tiles, ``--rows`` both at other
row counts (where the threshold sits). Refuses to run off a TPU;
``--compile-only`` lowers every variant for a described v5e instead (no
chip: the sandbox rehearsal) and prints what Mosaic refuses."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.kernels import grouped_matmul as gm

HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)

#: d, f, the router's experts, the held share, MoE layers of the stack,
#: top-k, and the assignment rows of the cell's tick and prefill units
CELLS = {
    "olmoe": dict(d=2048, f=1024, router=64, held=64, layers=8, k=8,
                  rows=(256, 512, 1024)),
    "smallthinker": dict(d=2560, f=768, router=64, held=64, layers=12, k=6,
                         rows=(192, 1536)),
    "mistral4": dict(d=4096, f=2048, router=128, held=32, layers=6, k=4,
                     rows=(256, 1024)),
    "ling3": dict(d=2560, f=768, router=512, held=128, layers=4, k=8,
                  rows=(1024,)),
    "solar2": dict(d=4096, f=1280, router=320, held=40, layers=4, k=8,
                   rows=(512, 2048)),
    "kexaone": dict(d=6144, f=2048, router=128, held=8, layers=7, k=8,
                    rows=(1024, 2048)),
}


def draw_sizes(rng, rows, k, router, held, draw):
    """[held] group sizes of ``rows`` assignment rows (``rows / k`` tokens)."""
    tokens = rows // k
    alike = tokens // 2 if draw == "alike" else 0
    counts = np.bincount(rng.integers(0, router, (tokens - alike) * k),
                         minlength=router)
    counts[rng.choice(router, k, replace=False)] += alike
    return counts[:held].astype(np.int32)


def chain(impl, n_experts, layers, calls):
    """rows, w [L * E, K, N], sizes [E] -> the rows after ``calls``
    dependent grouped matmuls, call i on layer ``i % layers``."""
    def run(rows, w, sizes):
        def body(i, rows):
            layer = i % layers
            if impl == "ragged_dot":
                wide = jax.lax.dynamic_update_slice(
                    jnp.zeros((layers * n_experts,), jnp.int32), sizes,
                    (layer * n_experts,))
                out = jax.lax.ragged_dot(rows, w, wide,
                                         preferred_element_type=jnp.float32)
            else:
                out = gm.grouped_matmul(rows, w, sizes, layer=layer)
            return jax.lax.dynamic_update_slice(
                rows, out[:8, :128].astype(rows.dtype), (0, 0))
        return jax.lax.fori_loop(0, calls, body, rows)
    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="+", default=list(CELLS))
    ap.add_argument("--rows", type=int, nargs="+",
                    help="assignment rows to time instead of the cells' own")
    ap.add_argument("--draws", nargs="+", default=["even", "alike"])
    ap.add_argument("--impls", nargs="+",
                    default=["ragged_dot", "grouped_matmul"])
    ap.add_argument("--row-tile", type=int, nargs="+", default=[gm.ROW_TILE])
    ap.add_argument("--block-mb", type=int, nargs="+",
                    default=[gm._BLOCK_BYTES >> 20])
    ap.add_argument("--calls", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args()

    where = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("grouped_matmul_sweep: needs a TPU (or --compile-only)")

    rng = np.random.default_rng(args.seed)
    for cell in args.cells:
        c = CELLS[cell]
        planes = c["layers"] * c["held"]
        for k_in, n_out in ((c["d"], c["f"]), (c["f"], c["d"])):
            if args.compile_only:
                w = jax.ShapeDtypeStruct((planes, k_in, n_out), jnp.bfloat16,
                                         sharding=where)
            else:
                # a value a plane (the MXU's time does not follow the
                # values; a wrong plane's product shows in ``max_err``)
                w = jnp.broadcast_to(
                    (0.01 * (1 + jnp.arange(planes) % 13))[:, None, None],
                    (planes, k_in, n_out)).astype(jnp.bfloat16)
            for rows_n in args.rows or c["rows"]:
                for draw in args.draws:
                    sizes = draw_sizes(rng, rows_n, c["k"], c["router"],
                                       c["held"], draw)
                    one(args, cell, c, k_in, n_out, rows_n, draw, sizes, w,
                        where)
            del w


def one(args, cell, c, k_in, n_out, rows_n, draw, sizes, w, where):
    """Time (or lower) every implementation of one shape under one draw."""
    touched, owned = int((sizes > 0).sum()), int(sizes.sum())
    nbytes = touched * k_in * n_out * 2 + owned * (k_in * 2 + n_out * 4)
    if where is not None:
        rows = jax.ShapeDtypeStruct((rows_n, k_in), jnp.bfloat16,
                                    sharding=where)
        sz = jax.ShapeDtypeStruct(sizes.shape, jnp.int32, sharding=where)
    else:
        rows = jax.random.normal(jax.random.PRNGKey(1), (rows_n, k_in),
                                 jnp.bfloat16)
        sz = jnp.asarray(sizes)
    for impl in args.impls:
        tiles = [(None, None)] if impl == "ragged_dot" else [
            (tm, mb) for tm in args.row_tile for mb in args.block_mb]
        for tm, mb in tiles:
            row = {"cell": cell, "rows": rows_n, "k_in": k_in,
                   "n_out": n_out, "draw": draw, "impl": impl,
                   "touched": touched, "held": c["held"], "owned": owned,
                   "mbytes": round(nbytes / 1e6, 1)}
            if tm is not None:
                gm.ROW_TILE, gm._BLOCK_BYTES = tm, mb << 20
                gm._visit.clear_cache()
                row.update(row_tile=gm._row_tile(rows_n, jnp.bfloat16),
                           col_tile=gm._col_tile(k_in, n_out, 2))
            fn = jax.jit(chain(impl, c["held"], c["layers"], args.calls))
            try:
                if where is not None:
                    text = fn.lower(rows, w, sz).compile().as_text()
                    row["mosaic_calls"] = text.count(
                        'custom_call_target="tpu_custom_call"')
                else:
                    jax.block_until_ready(fn(rows, w, sz))
                    best = float("inf")
                    for _ in range(5):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(rows, w, sz))
                        best = min(best, time.perf_counter() - t0)
                    ms = 1e3 * best / args.calls
                    row["ms_per_call"] = round(ms, 4)
                    row["share_pct"] = round(
                        100 * nbytes / HBM_BYTES_PER_S / (ms / 1e3), 1)
                    if impl != "ragged_dot":
                        row["max_err"] = float(jnp.max(jnp.abs(
                            gm.grouped_matmul(rows, w, sz, layer=1)[:owned]
                            - jax.lax.ragged_dot(
                                rows, w[c["held"]:2 * c["held"]], sz,
                                preferred_element_type=jnp.float32)[:owned])))
            except Exception as e:  # a refusal is a row of the table
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
