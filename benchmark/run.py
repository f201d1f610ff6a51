"""One run of one cell of BENCHMARK.json, in a fresh process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for. Refuses to start without a TPU (exit 1, no result line; it sets
neither ``JAX_PLATFORMS`` nor a cache directory — the package resolves the
compile cache: ``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
Earlier lines of stdout are free-form JSON notes; the LAST line is the
contract's object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
number the driver compared beside its limit (also the last lines of
standard error).
"""
import time

T0 = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import paddle_tpu  # noqa: F401 - the system under test
    except ImportError as exc:
        print(f"benchmark: the system under test is not in this checkout "
              f"({exc}); nothing was run", file=sys.stderr)
        return 1
    import jax

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s), jax "
              f"found {len(devices)} x {devices[0].platform!r} "
              f"({devices[0].device_kind}); nothing was run",
              file=sys.stderr)
        return 1
    line = harness.run_cell(cell, args.seed, args.seconds,
                            bool(args.trace), devices[:cell.chips], T0)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
