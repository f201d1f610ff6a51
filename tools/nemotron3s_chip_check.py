"""Chip checks of the ``nemotron3s-serve-chat`` cell (``chiprun -- python3
tools/nemotron3s_chip_check.py <phase> ...``; each phase its own process, a
TPU only):

    variants [--seed N] [--slots 8] [--only NAME ...]
        the tight check: two sequences (a chat turn, and a long prompt in
        many chunks with a long answer) through a beam-plane twin of the
        cell's engine at the published widths; the served top-8 log-prob
        error, the emitted-token gap and the slot's final STATE (relative
        error and mantissa bits, a Mamba-2 layer) against the float32
        reference and against every entry of ``mamba2_gqa_moe_lm.VARIANTS``
        (or those of ``--only``) — the readings a tolerance is set from,
        and which of the cell's three limits each wrong model fails.
    kernel [--slots 128 64 32 16] [--reps 40] [--seed N]
        the decode kernel's microbenchmark down to the tick's rows: a
        chain of ``mamba2_decode_step`` calls over the state's 5 layers at
        the published sizes by the host clock, ms a call and the share of
        the chip's bandwidth its bytes (``mamba_decode_cost``) make of it,
        with half the rows live and with all of them. A reading holds the
        XLA ops that lay the step's columns out beside the kernel (3.8 ms a
        call at 128 slots where the cell's trace reads the kernel alone at
        2.12, my chip runs, PR 54): it ranks variants of the kernel, the
        traced run times it.
    sweep RATE [--slots N] [--seconds W] [--schedule S] [--greedy K]
            [--ramp R] [--seed N]
        ONE rate through ``benchmark/sweep.run_rate`` (the cell's own
        driver: a fresh engine, the mix's ramp), with the checked requests
        left out where asked (``--greedy 0``: a sweep judges the load, not
        the logits) and the slot count in the file's name under
        ``chiprun_out/sweep/``.
"""
import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "nemotron3s-serve-chat"
#: (prompt tokens, new tokens) of the tight check
CHECK_SEQUENCES = ((300, 48), (2300, 160))


def variants(args) -> int:
    import numpy as np

    import paddle_tpu as pt
    from benchmark import harness
    from benchmark.families import mamba2_gqa_moe_lm as fam

    cell = harness.load_cell(CELL)
    pt.set_amp(cell.config["amp"] == "bfloat16")
    mix = json.loads(json.dumps(cell.mix))
    mix["engine"].update(slots=args.slots, n_pages=64)
    eng, _ = fam.build_engine(cell.config, mix, args.seed,
                              beam_width=fam.CHECK_TOPK)
    w = fam.weights_of(None, eng.scope)
    rng = np.random.default_rng(args.seed)
    names = ("",) + tuple(args.only or fam.VARIANTS)
    errs = {n: [] for n in names}
    gaps = {n: 0.0 for n in names}
    state = {n: {"rel_err": [], "bits": 0} for n in names}
    for plen, new in CHECK_SEQUENCES:
        prompt = fam.draw_prompt_ids(rng, plen, cell.config)
        by, out, served, held, _ = fam.served_errors(
            cell.config, w, eng, prompt, new, variants=names)
        emitted = np.arange(plen - 1, out.size - 1)
        for n in names:
            errs[n].extend(by[n])
            state[n]["rel_err"].append(held[n]["rel_err"])
            state[n]["bits"] = max(state[n]["bits"], *held[n]["bits"])
            # how far below its position's best THIS model puts a token
            # the engine emitted (the check's second statistic)
            lg = fam._rows_logits(cell.config, w, out[:-1], emitted, n)
            gaps[n] = max(gaps[n], float((lg.max(-1) - lg[
                np.arange(emitted.size), out[emitted + 1]]).max()))
        print(json.dumps({"sequence": [plen, new], "positions": len(served),
                          "right_max": max(by[""])}), flush=True)
    res = {"seed": args.seed, "slots": args.slots,
           "positions": len(errs[""])}
    for n in names:
        e = np.asarray(errs[n])
        res[n or "right"] = {f"p{q}": float(np.percentile(e, q))
                             for q in (50, 80, 90, 95, 99)} | {
                                 "max": float(e.max()),
                                 "emitted_gap_max": gaps[n],
                                 "state_rel_err_by_layer": state[n]["rel_err"],
                                 "state_bits_differ": state[n]["bits"]}
        # the cell's three limits (``reference_logit_gaps``), by name
        res[n or "right"]["fails"] = [name for name, over in (
            ("logprob", np.percentile(e, fam.CHECK_LOGPROB_QUANTILE)
             > fam.CHECK_LOGPROB_TOL),
            ("emitted_gap", gaps[n] > fam.CHECK_EMITTED_GAP_TOL),
            ("state_bits", state[n]["bits"] > fam.CHECK_STATE_BITS_TOL))
            if over]
    print(json.dumps(res), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "nemotron3s"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron3s",
                           f"variants_{args.seed}.json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


def kernel(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.families import mamba2_gqa_moe_lm as fam
    from paddle_tpu.kernels import mamba2

    cell = harness.load_cell(CELL)
    cfg = cell.config
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, L = cfg["n_groups"], cfg["ssm_state_size"], fam.letters_of(
        cfg).count("M")
    bw = harness.peaks_for(cell, jax.devices()[0].device_kind)[
        "hbm_bytes_per_s"]
    rng = np.random.default_rng(args.seed)

    @jax.jit
    def chain(xdt, a, B, C, state, live):
        y = jnp.zeros_like(xdt)
        for l in range(L):
            o, state = mamba2.mamba2_decode_step(xdt + 1e-3 * y, a, B, C,
                                                 state, jnp.int32(l), live)
            y = o
        return y, state

    out = []
    for slots in args.slots:
        f = jnp.float32
        xdt = jnp.asarray(rng.normal(size=(slots, H, P)) * 0.01, f)
        a = jnp.asarray(rng.uniform(0.2, 0.999, size=(slots, H)), f)
        B, C = (jnp.asarray(rng.normal(size=(slots, G, N)), f)
                for _ in range(2))
        cost = fam.mamba_decode_cost(cfg, slots, H, P, N, G)["bytes"]
        for share in (1.0, 0.5):
            live = jnp.asarray(np.arange(slots) < slots * share)
            state = jnp.zeros((L, slots, H, P, N), f)
            y, state = chain(xdt, a, B, C, state, live)
            y.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(args.reps):
                y, state = chain(xdt, a, B, C, state, live)
            y.block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3 / (args.reps * L)
            out.append({"slots": slots, "live_share": share,
                        "ms_per_call": ms, "bytes_per_call": cost,
                        "bandwidth_share_pct": 100.0 * cost / bw
                        / (ms * 1e-3)})
            print(json.dumps(out[-1]), flush=True)
            del state
    os.makedirs(os.path.join(ROOT, "chiprun_out", "nemotron3s"),
                exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron3s",
                           "kernel.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


def sweep(args) -> int:
    import jax

    from benchmark import harness
    from benchmark import sweep as bench_sweep

    cell = harness.load_cell(CELL)
    if args.greedy is not None:
        cell.mix["check"]["greedy_requests"] = args.greedy
    if args.ramp is not None:
        cell.mix["ramp_s"] = args.ramp
    engine = {} if args.slots is None else {"slots": args.slots}
    line = bench_sweep.run_rate(cell, args.rate, args.schedule, engine,
                                args.seed, args.seconds, jax.devices()[:1],
                                T0)
    out = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out, exist_ok=True)
    name = (f"{CELL}_s{cell.mix['engine']['slots']}_{args.rate:g}_"
            f"{line['schedule_seed']}_{args.seed}.json")
    with open(os.path.join(out, name), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="phase", required=True)
    v = sub.add_parser("variants")
    v.add_argument("--seed", type=int, default=2**31 + 11)
    v.add_argument("--slots", type=int, default=8)
    v.add_argument("--only", nargs="*", metavar="VARIANT")
    k = sub.add_parser("kernel")
    k.add_argument("--slots", type=int, nargs="*", default=[128, 64, 32, 16])
    k.add_argument("--reps", type=int, default=40)
    k.add_argument("--seed", type=int, default=2**31 + 13)
    s = sub.add_parser("sweep")
    s.add_argument("rate", type=float)
    s.add_argument("--slots", type=int)
    s.add_argument("--seconds", type=float, default=60.0)
    s.add_argument("--schedule", type=int)
    s.add_argument("--greedy", type=int)
    s.add_argument("--ramp", type=float)
    s.add_argument("--seed", type=int, default=2**31 + 7)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("nemotron3s_chip_check: needs a TPU; nothing was run",
              file=sys.stderr)
        return 1
    return {"variants": variants, "sweep": sweep,
            "kernel": kernel}[args.phase](args)


if __name__ == "__main__":
    sys.exit(main())
