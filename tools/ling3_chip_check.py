"""Chip checks of the ``ling3-serve-reason`` cell (``chiprun -- python3
tools/ling3_chip_check.py <phase> ...``; each phase its own process, a TPU
only):

    variants [--seed N] [--slots 8] [--only NAME ...]
        the tight check: two sequences (a chat turn, and a long prompt in
        many chunks with a long answer) through a beam-plane twin of the
        cell's engine at the published widths; the served top-8 log-prob
        error, the emitted-token gap and the slot's final STATE (relative
        error and mantissa bits, a KDA layer) against the float32
        reference and against every entry of ``kda_mla_moe_lm.VARIANTS``
        (or those of ``--only``) — the readings a tolerance is set from,
        and which of the cell's three limits each wrong model fails.
    tick [--scale E] [--seed N] [--live 85] [--new 200]
        how the decode tick follows the SEED at an embedding scale: a fresh
        engine of the cell's shapes, ``--live`` requests of 64 prompt tokens
        decoded side by side for ``--new`` tokens; prints the tick's p50,
        the held experts a layer call touches and the distinct tokens a
        tick's rows emit (what ``assumed.embedding_scale`` was chosen by).
    sweep RATE [--slots N] [--seconds W] [--schedule S] [--greedy K]
            [--ramp R] [--seed N]
        ONE rate through ``benchmark/sweep.run_rate`` (the cell's own
        driver: a fresh engine, the mix's ramp). What ``benchmark/sweep.py
        --engine slots=N`` cannot do: leave out the checked requests
        (``--greedy 0``: the replay and the float32 reference are 100 s of
        a 350 s run, and a sweep judges the load, not the logits) and keep
        128 and 256 slots at one rate apart (the slot count is in the
        file's name under ``chiprun_out/sweep/``).
"""
import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "ling3-serve-reason"
#: (prompt tokens, new tokens) of the tight check
CHECK_SEQUENCES = ((300, 48), (2300, 160))


def variants(args) -> int:
    import numpy as np

    import paddle_tpu as pt
    from benchmark import harness
    from benchmark.families import kda_mla_moe_lm as fam

    cell = harness.load_cell(CELL)
    pt.set_amp(cell.config["amp"] == "bfloat16")
    mix = json.loads(json.dumps(cell.mix))
    mix["engine"].update(slots=args.slots, n_pages=128)
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
        by, out, served, held = fam.served_errors(
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
    os.makedirs(os.path.join(ROOT, "chiprun_out", "ling3"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ling3",
                           f"variants_{args.seed}.json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


def tick(args) -> int:
    import numpy as np

    import paddle_tpu as pt
    from benchmark import harness
    from benchmark.families import kda_mla_moe_lm as fam

    cell = harness.load_cell(CELL)
    pt.set_amp(cell.config["amp"] == "bfloat16")
    if args.scale is not None:
        cell.config["assumed"]["embedding_scale"] = args.scale
    if args.bias_std is not None:
        cell.config["assumed"]["router_bias_std"] = args.bias_std
    eng, _ = fam.build_engine(cell.config, cell.mix, args.seed)
    eng.warmup()
    rng = np.random.default_rng(args.seed)
    prompts = [fam.draw_prompt_ids(rng, 64, cell.config)
               for _ in range(args.live)]
    before = eng.metrics.snapshot()["counters"]
    outs = eng.generate_all(prompts, max_new_tokens=args.new)
    snap = eng.metrics.snapshot()
    c = {k: snap["counters"].get(k, 0) - before.get(k, 0)
         for k in ("moe_touched_experts", "moe_layer_calls",
                   "moe_held_assignments", "moe_assignments",
                   "decode_steps")}
    new = np.stack([o[64:] for o in outs])              # [live, new]
    res = {"scale": cell.config["assumed"]["embedding_scale"],
           "router_bias_std": cell.config["assumed"].get("router_bias_std"),
           "seed": args.seed, "live": args.live,
           "decode_step_p50_ms": snap["latency"]["decode_step_ms"]["p50"],
           "touched_held_experts_mean": c["moe_touched_experts"]
           / max(c["moe_layer_calls"], 1),
           "held_rows_pct": 100.0 * c["moe_held_assignments"]
           / max(c["moe_assignments"], 1),
           "distinct_tokens_a_tick_mean": float(np.mean(
               [np.unique(new[:, t]).size for t in range(new.shape[1])])),
           "decode_steps": c["decode_steps"]}
    print(json.dumps(res), flush=True)
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
    t = sub.add_parser("tick")
    t.add_argument("--scale", type=float)
    t.add_argument("--bias-std", type=float)
    t.add_argument("--seed", type=int, default=2**31 + 13)
    t.add_argument("--live", type=int, default=85)
    t.add_argument("--new", type=int, default=200)
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
        print("ling3_chip_check: needs a TPU; nothing was run",
              file=sys.stderr)
        return 1
    return {"variants": variants, "sweep": sweep,
            "tick": tick}[args.phase](args)


if __name__ == "__main__":
    sys.exit(main())
