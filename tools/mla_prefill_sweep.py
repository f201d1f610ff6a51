"""Time a latent block's prefill-chunk attention on the chip: the gathered
form (``ck[l, tbl]`` + ``reference_attention``, what ``_mla_paged_step``
runs where the walk cannot) against the chunk walk ``paged_mla_prefill``
(``kernels/paged_attention.paged_attention_prefill(cache_v=None)``) at the
shapes the latent cells run, one JSON row a reading on stdout. The tables
in ``kernels/paged_attention.py`` are this tool's output.

    python tools/mla_prefill_sweep.py [--cells mistral4 ling3 glm53f]
        [--seed N] [--tiles 4096:512:1024 2048:512:1024 ..] [--calls 12]

A cell with ``group`` (a SPARSE latent layer: glm53f) times the two forms of
its attention on ONE random pick a query (``topk / group - 1`` of the groups
before it, all of them where there are fewer, and its own): the gather of the
picked groups' rows in query tiles (``_dsa_attend``'s inner form) against the
chunk walk under the pick as a group mask; where the two cross sets
``paged_attention.MASK_WALK_KEYS``. Its ``pick`` rows time the threshold of
the k-th score over the table's width three ways (a sort: ``lax.top_k``; the
counted search; the whole of ``_picked_groups``) at a unit's tile and at the
tick's shape.

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
    # the sparse layer: a unit of 1024 behind nothing, the mix's median
    # context, its 73rd and 90th percentiles and the table's last unit
    "glm53f": dict(heads=64, W=512, r=512, ps=256, layers=1, pages=2560,
                   table=132, group=4, topk=2048, slots=32,
                   calls=((1024, 0), (1024, 4096), (1024, 8192),
                          (1024, 16384), (1024, 32768))),
}
#: queries a tile of the sparse layer's gathered form (``_dsa_attend``'s at
#: the cell's shapes: 268 MB of gathered rows)
GATHER_TILE = 128


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


def random_pick(rng, tc, before, n_groups, group, k):
    """-> (pick [1, tc, k + 1] group ids, ok [1, tc, k + 1], mask [1, tc,
    n_groups] int8): k random groups before each query's own (all of them
    where there are fewer: the rest not ok) and its own, last."""
    pick = np.zeros((1, tc, k + 1), np.int32)
    ok = np.zeros((1, tc, k + 1), bool)
    mask = np.zeros((1, tc, n_groups), np.int8)
    for i in range(tc):
        own = (before + i) // group
        n = min(own, k)
        pick[0, i, :n] = (rng.permutation(own)[:n] if own > k
                          else np.arange(n))
        pick[0, i, k], ok[0, i, :n], ok[0, i, k] = own, True, True
        mask[0, i, pick[0, i, :n]] = 1
        mask[0, i, own] = 1
    return pick, ok, mask


def sparse_gathered(q, ck, layer, table, start, lengths, r, pick, ok, mask,
                    group=4):
    """``_dsa_attend``'s form on a given pick (its own
    ``_attend_picked``: page ids, the picked groups' rows, a batched
    product) in query tiles of ``GATHER_TILE`` -> [b, Tc, H * r]."""
    from paddle_tpu.ops.pipeline_ops import _attend_picked, _query_tiles

    b, H, t, W = q.shape
    L, N, ps, _ = ck.shape
    gp = ps // group
    groups = ck.reshape(L, N * gp, group * W)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    n = t // GATHER_TILE
    o = jax.lax.map(
        lambda a: _attend_picked(a[0], groups, layer, table, *a[1:], gp, r),
        (q.reshape(b, H, n, GATHER_TILE, W).transpose(2, 0, 1, 3, 4),
         *_query_tiles(t, GATHER_TILE, pick, ok, pos)))
    # [n, b, H, tile, r] -> a token's heads side by side
    return o.transpose(1, 0, 3, 2, 4).reshape(b, t, H * r).astype(ck.dtype)


def sparse_walk(q, ck, layer, table, start, lengths, r, pick, ok, mask,
                group=4):
    return pa.paged_attention_prefill(
        q, ck, None, layer, table, start, lengths, sm_scale=1.0,
        value_width=r, group_mask=mask, group_rows=group)


def chain(form, calls, layers, r):
    def run(q, ck, table, start, lengths, *extra):
        def body(i, q):
            o = form(q, ck, i % layers, table, start, lengths, r, *extra)
            return q.at[:, 0, 0, 0].add(o[:, 0, 0] * 1e-3)
        return jax.lax.fori_loop(0, calls, body, q)
    return jax.jit(run)


def best_ms(fn, ops, calls=1):
    jax.block_until_ready(fn(*ops))
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ops))
        best = min(best, time.perf_counter() - t0)
    return best / calls * 1e3


def pick_rows(cell, c, seed):
    """The threshold of the k-th best score over the table's width, three
    ways, at a unit's tile and the tick's shape: ms a call (best of five),
    and that the mask is ``lax.top_k``'s set."""
    from paddle_tpu.kernels.sampling import _search_threshold
    from paddle_tpu.ops.pipeline_ops import _picked_groups

    n_groups = c["table"] * c["ps"] // c["group"]
    k = c["topk"] // c["group"] - 1
    rng = np.random.default_rng(seed)
    for what, rows in (("unit-tile", GATHER_TILE), ("tick", c["slots"])):
        own = rng.integers(k + 1, n_groups, size=rows).astype(np.int32)
        z = np.where(np.arange(n_groups)[None] < own[:, None],
                     rng.standard_normal((rows, n_groups)), -np.inf)
        z, own = jnp.asarray(z, jnp.float32), jnp.asarray(own)
        forms = {
            "top_k": lambda z, own: jax.lax.top_k(z, k)[0][..., -1],
            "search": lambda z, own: _search_threshold(
                z, jnp.int32(1), jnp.int32(k)),
            "mask": lambda z, own: _picked_groups(z, own, k)}
        got = {n: jax.jit(f)(z, own) for n, f in forms.items()}
        top = np.asarray(jax.lax.top_k(z, k)[1])
        want = np.zeros((rows, n_groups), bool)
        np.put_along_axis(want, top, True, axis=1)
        want[np.arange(rows), np.asarray(own)] = True
        for name, f in forms.items():
            yield dict(cell=cell, pick=what, rows=rows, groups=n_groups, k=k,
                       form=name, ms=round(best_ms(jax.jit(f), (z, own)), 4),
                       same_threshold=bool((np.asarray(got["top_k"])
                                            == np.asarray(got["search"])).all()),
                       same_set=bool((np.asarray(got["mask"]) == want).all()))


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
        sparse = "group" in c
        if sparse and not args.compile_only:
            for out in pick_rows(cell, c, args.seed):
                print(json.dumps(out), flush=True)
        for tc, before in c["calls"]:
            row = dict(cell=cell, chunk=tc, keys_before=before)
            held = -(-(before + tc) // ps)
            # (the masked walk multiplies every pair the causal rule lets
            # through, picked or not: the same count)
            q_pairs = sum(before + i + 1 for i in range(tc))
            gflop = 2 * H * q_pairs * (W + r) / 1e9
            forms = [("gathered", None,
                      sparse_gathered if sparse else gathered)] + [
                ("walk", t, sparse_walk if sparse else walk) for t in tiles]
            extra = ()
            if sparse:
                n_groups = c["table"] * ps // c["group"]
                k1 = c["topk"] // c["group"]
                extra = (((1, tc, k1), jnp.int32), ((1, tc, k1), jnp.bool_),
                         ((1, tc, n_groups), jnp.int8))
            if args.compile_only:
                def arg(shape, dt):
                    return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
                ops = (arg((1, H, tc, W), bf), arg(pool_shape, bf),
                       arg((1, c["table"]), jnp.int32), arg((1,), jnp.int32),
                       arg((1,), jnp.int32)) + tuple(
                           arg(*x) for x in extra)
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
                if sparse:
                    ops += tuple(jnp.asarray(a) for a in random_pick(
                        rng, tc, before, n_groups, c["group"], k1 - 1))
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
                            ops[0], ops[1], 0, *ops[2:5], r, *ops[5:])
                        once = np.asarray(once.astype(jnp.float32))
                        if want is None:
                            want = once
                        out["err"] = float(np.abs(once - want).max())
                        out["ref_max"] = float(np.abs(want).max())
                        ms = best_ms(chain(form, args.calls, c["layers"], r),
                                     ops, args.calls)
                        out["ms"] = round(ms, 4)
                        out["mxu_pct"] = round(
                            100 * gflop * 1e9 / MXU_FLOP_PER_S / (ms / 1e3), 1)
                except Exception as exc:  # noqa: BLE001 - a refused variant
                    out["refused"] = f"{type(exc).__name__}: {exc}"[:400]
                print(json.dumps(out), flush=True)
    set_tiles(*default)


if __name__ == "__main__":
    main()
