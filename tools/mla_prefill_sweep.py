"""Time a latent block's prefill-chunk attention on the chip: the gathered
form (``ck[l, tbl]`` + ``reference_attention``, what ``_mla_paged_step``
runs where the walk cannot) against the chunk walk ``paged_mla_prefill``
(``kernels/paged_attention.paged_attention_prefill(cache_v=None)``) at the
shapes the two latent cells run, one JSON row a reading on stdout. The table
in ``kernels/paged_attention.py`` is this tool's output.

    python tools/mla_prefill_sweep.py [--cells mistral4 ling3] [--seed N]
        [--tiles 4096:512:1024 2048:512:1024 ..] [--calls 12]

Each reading is one jitted chain of ``--calls`` dependent calls walking the
layers of the pool (a corner of the result is written back into the queries,
so nothing overlaps or is hoisted), best of five by the host clock around
``block_until_ready``, divided by the calls. ``gflop`` = 2 x heads x the
(query, key) pairs the mask lets through x (W + r); ``mxu_pct`` = that over
197 TFLOP/s over the time. ``err`` is the walk's largest distance from the
gathered form on the same operands (both round P and the result to bf16).
``--tiles`` re-times the walk at other (query rows a grid step : rows of a
score tile : keys a step) triples. Refuses to run off a TPU;
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

from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels.flash_attention import reference_attention

MXU_FLOP_PER_S = 197e12     # TPU v5e, bf16 (benchmark/peaks.json)

#: heads, the pool row's width, the latent's (value) width, page, layers and
#: pages of the pool, the table's pages, and the (chunk, context before it)
#: pairs timed: a question chunk behind a cached 16k document; a median, a
#: long and the longest prompt's last chunk
CELLS = {
    "mistral4": dict(heads=32, W=384, r=256, ps=256, layers=6, pages=1536,
                     table=80, calls=((256, 16896), (64, 16896), (256, 0))),
    "ling3": dict(heads=32, W=640, r=512, ps=256, layers=1, pages=4096,
                  table=48, calls=((256, 256), (64, 448), (256, 3840),
                                   (256, 12032))),
}


def gathered(q, ck, layer, table, start, lengths, r):
    """``_mla_paged_step``'s gathered branch -> [b, Tc, H * r]."""
    b = q.shape[0]
    lat = ck[layer, table].reshape(b, 1, table.shape[1] * ck.shape[2],
                                   ck.shape[3])
    o = reference_attention(q, lat, lat[..., :r], sm_scale=1.0, causal=True,
                            q_pos0=start)
    return o.transpose(0, 2, 1, 3).reshape(b, q.shape[2], -1)


def walk(q, ck, layer, table, start, lengths, r):
    return pa.paged_attention_prefill(q, ck, None, layer, table, start,
                                      lengths, sm_scale=1.0, value_width=r)


def chain(form, calls, layers, r):
    def run(q, ck, table, start, lengths):
        def body(i, q):
            o = form(q, ck, i % layers, table, start, lengths, r)
            return q.at[:, 0, 0, 0].add(o[:, 0, 0] * 1e-3)
        return jax.lax.fori_loop(0, calls, body, q)
    return jax.jit(run)


def set_tiles(rows, score_rows, keys):
    pa._CHUNK_ROWS, pa._LATENT_SCORE_ROWS, pa._CHUNK_KEYS = (rows,
                                                             score_rows, keys)
    jax.clear_caches()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="*", default=sorted(CELLS))
    ap.add_argument("--tiles", nargs="*", default=None,
                    help="rows:score_rows:keys triples (default: the module's)")
    ap.add_argument("--calls", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args()
    default = (pa._CHUNK_ROWS, pa._LATENT_SCORE_ROWS, pa._CHUNK_KEYS)
    tiles = ([tuple(int(x) for x in t.split(":")) for t in args.tiles]
             if args.tiles else [default])
    sharding = None
    if args.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        sharding = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("mla_prefill_sweep: needs a TPU (or --compile-only)")
    bf = jnp.bfloat16
    for cell in args.cells:
        c = CELLS[cell]
        H, W, r, ps = c["heads"], c["W"], c["r"], c["ps"]
        pool_shape = (c["layers"], c["pages"], ps, W)
        for tc, before in c["calls"]:
            row = dict(cell=cell, chunk=tc, keys_before=before)
            held = -(-(before + tc) // ps)
            q_pairs = sum(before + i + 1 for i in range(tc))
            gflop = 2 * H * q_pairs * (W + r) / 1e9
            forms = [("gathered", None, gathered)] + [
                ("walk", t, walk) for t in tiles]
            if args.compile_only:
                def arg(shape, dt):
                    return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
                ops = (arg((1, H, tc, W), bf), arg(pool_shape, bf),
                       arg((1, c["table"]), jnp.int32), arg((1,), jnp.int32),
                       arg((1,), jnp.int32))
            else:
                key = jax.random.split(jax.random.PRNGKey(args.seed), 2)
                rng = np.random.default_rng(args.seed)
                table = np.zeros((1, c["table"]), np.int32)
                table[0, :held] = rng.permutation(
                    np.arange(1, c["pages"]))[:held]
                ops = (0.3 * jax.random.normal(key[0], (1, H, tc, W), bf),
                       jax.random.normal(key[1], pool_shape, bf),
                       jnp.asarray(table), jnp.asarray([before], jnp.int32),
                       jnp.asarray([tc], jnp.int32))
            want = None
            for name, tile, form in forms:
                out = dict(row, form=name, tiles=tile, gflop=round(gflop, 2))
                if tile is not None:
                    set_tiles(*tile)
                try:
                    if args.compile_only:
                        t0 = time.perf_counter()
                        chain(form, args.calls, c["layers"], r).lower(
                            *ops).compile()
                        out["compile_s"] = round(time.perf_counter() - t0, 2)
                    else:
                        once = jax.jit(form, static_argnums=6)(
                            ops[0], ops[1], 0, *ops[2:], r)
                        once = np.asarray(once.astype(jnp.float32))
                        if want is None:
                            want = once
                        out["err"] = float(np.abs(once - want).max())
                        out["ref_max"] = float(np.abs(want).max())
                        fn = chain(form, args.calls, c["layers"], r)
                        jax.block_until_ready(fn(*ops))
                        best = float("inf")
                        for _ in range(5):
                            t0 = time.perf_counter()
                            jax.block_until_ready(fn(*ops))
                            best = min(best, time.perf_counter() - t0)
                        ms = best / args.calls * 1e3
                        out["ms"] = round(ms, 4)
                        out["mxu_pct"] = round(
                            100 * gflop * 1e9 / MXU_FLOP_PER_S / (ms / 1e3), 1)
                except Exception as exc:  # noqa: BLE001 - a refused variant
                    out["refused"] = f"{type(exc).__name__}: {exc}"[:400]
                print(json.dumps(out), flush=True)
    set_tiles(*default)


if __name__ == "__main__":
    main()
