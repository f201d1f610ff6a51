"""Sweep the flash kernels' blocks on the chip: time a call of
``flash_fwd`` / ``flash_dq`` / ``flash_dkv`` over packed ``[b, T, H *
d_head]`` operands (``--layout rows``: the ``[b * H, T, d_head]`` view
of the ``[B, H, T, D]`` entry) for every (block_q, block_k) of
``--block-q`` x ``--block-k`` that fits T, causal, by operand type, one
JSON row a reading on stdout. The table in
``kernels/flash_attention.py`` is this tool's output.

    python tools/flash_block_sweep.py [--shapes 8,1024,16,64 8,2048,16,64 ..]

Each reading is one jitted chain of ``--calls`` dependent kernel calls
(the output feeds the next call's input, so nothing overlaps or is
hoisted), best of five by the host clock around ``block_until_ready``,
divided by the calls. The loop's carry is copied every turn, so a
reading stands 0.1-0.4 ms over the kernel's own device time (by operand
bytes, the same for every block pair): it ranks blocks, a traced run
times a kernel. Refuses to run off a TPU. ``--compile-only`` lowers
every variant for a described v5e instead (no chip: the sandbox
rehearsal) and prints what Mosaic refuses."""
import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa


def chain(kernel, blocks, d, heads, calls, causal=True):
    """q, k, v, do -> the last call's outputs, after ``calls`` dependent
    calls of ``kernel`` at ``blocks`` (``heads`` a row of d each)."""
    sm = d ** -0.5

    def run(q, k, v, do, lse, o):
        def body(_, c):
            q, k, v = c
            if kernel == "fwd":
                out, _ = fa._flash_forward(q, k, v, None, causal, sm,
                                           *blocks, interpret=False,
                                           num_heads=heads)
                return out, k, v
            dq, dk, dv = fa._flash_backward(
                q, k, v, o, lse, None, do, causal, sm, *blocks,
                interpret=False, num_heads=heads)
            # (the kernel whose outputs go unused is dead code to XLA)
            return (dq, k, v) if kernel == "dq" else (q, dk, dv)
        return jax.lax.fori_loop(0, calls, body, (q, k, v))

    return run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", metavar="B,T,H,D_HEAD",
                    default=["8,1024,16,64", "8,2048,16,64", "8,2048,8,128"])
    ap.add_argument("--layout", choices=["packed", "rows"], default="packed")
    ap.add_argument("--dtypes", nargs="+", default=["bfloat16", "float32"])
    ap.add_argument("--calls", type=int, default=24)
    ap.add_argument("--block-q", type=int, nargs="+",
                    default=[128, 256, 512])
    ap.add_argument("--block-k", type=int, nargs="+",
                    default=[128, 256, 512])
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args()

    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        where = SingleDeviceSharding(topo.devices[0])
    elif jax.default_backend() != "tpu":
        sys.exit("flash_block_sweep: needs a TPU (or --compile-only)")

    for shape4, dt in itertools.product(args.shapes, args.dtypes):
        b, T, H, d = (int(n) for n in shape4.split(","))
        shape, heads = (((b, T, H * d), H) if args.layout == "packed"
                        else ((b * H, T, d), 1))
        if args.compile_only:
            x = jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=where)
            operands = (x, x, x, x, jax.ShapeDtypeStruct(
                (b * H, 1, T), jnp.float32, sharding=where), x)
        else:
            q, k, v, do = (
                jax.random.normal(key, shape, jnp.float32).astype(dt)
                for key in jax.random.split(jax.random.PRNGKey(0), 4))
            o, lse = fa._flash_forward(q, k, v, None, True, d ** -0.5,
                                       512, 512, interpret=False,
                                       num_heads=heads)
            operands = (q, k, v, do, lse, o)
        for kernel, blocks in itertools.product(
                ("fwd", "dq", "dkv"),
                itertools.product(args.block_q, args.block_k)):
            if max(blocks) > T:
                continue
            fn = jax.jit(chain(kernel, blocks, d, heads, args.calls))
            row = {"shape": shape4, "layout": args.layout, "dtype": dt,
                   "kernel": kernel,
                   "block_q": blocks[0], "block_k": blocks[1]}
            try:
                if args.compile_only:
                    text = fn.lower(*operands).compile().as_text()
                    row["mosaic_calls"] = text.count(
                        'custom_call_target="tpu_custom_call"')
                else:
                    jax.block_until_ready(fn(*operands))
                    best = float("inf")
                    for _ in range(5):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(*operands))
                        best = min(best, time.perf_counter() - t0)
                    row["ms_per_call"] = round(1e3 * best / args.calls, 4)
            except Exception as e:  # a refusal is a row of the table
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
