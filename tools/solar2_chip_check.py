"""Checks of the ``solar2-serve-agent`` cell (``chiprun -- python3
tools/solar2_chip_check.py <phase> ...``; each phase its own process; all but
``compile`` need a TPU):

    compile [--slots 64] [--pages 4096] [--reference 0|1]
        no chip: the cell's prefill unit (snapshot feeds included) and
        decode tick lowered at the published widths and the cell's sizes
        and compiled for a DESCRIBED v5e with the chip's own compiler
        (Mosaic refusals, HBM fit: ``memory_analysis()`` of each), and with
        ``--reference 1`` the float32 reference's forward at 16384 tokens.
    check [--seed N] [--fault NAME ...] [--variants NAME ...]
        the timed path against the reference at the cell's sizes: a
        12288-token preamble + a turn served COLD, the same preamble under
        another turn served from the snapshot the first left, the first
        request again (a hit on its own pages) and an unshared prompt,
        through a beam-plane twin of the cell's engine; the served top-8
        log-prob error of each against the float32 reference's full
        forward from token 0 (and against ``--variants``, wrong models of
        ``kda_gqa_moe_lm.VARIANTS``), the slot's final state, and with
        ``--fault misplaced_snapshot`` the same with the snapshot rows
        moved by one before the hits (which must then read far over the
        cell's limit).
    cell --fault misplaced_snapshot [--seed N] [--seconds W]
        THE CONTROL of the cell's ``correct``, through the harness's own
        comparison: one whole run of the cell as ``benchmark/run.py`` makes
        it, with the TIMED engine's snapshot rows moved by one after the
        drain, before the family's check runs; its last line must read
        ``correct: false`` (the twin is sound: only the reading taken on
        the timed engine, ``timed_restore_state_vs_cold_max``, is over).
    tick [--scale E] [--seed N] [--live 40] [--new 100]
        how the decode tick follows the SEED at an embedding scale (what
        ``assumed.embedding_scale`` was chosen by): the tick's p50, the held
        experts a layer call touches, the held share of the assignments and
        the distinct tokens a tick's rows emit.
    sweep RATE [--seconds W] [--schedule S] [--greedy K] [--ramp R] [--seed N]
        ONE rate through ``benchmark/sweep.run_rate`` (the cell's own
        driver: a fresh engine, the mix's ramp); ``--greedy 0`` leaves the
        checked requests out (a sweep judges the load, not the logits).
"""
import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "solar2-serve-agent"
OUT = os.path.join(ROOT, "chiprun_out", "solar2")


def _cell():
    from benchmark import harness

    return harness.load_cell(CELL)


def compile_(args) -> int:
    """The serving programs (and the reference) for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as pt
    from paddle_tpu.ops import pipeline_ops

    cell = _cell()
    fam, e = cell.family, cell.mix["engine"]
    spec = fam.spec_of(cell.config)
    pt.set_amp(cell.config["amp"] == "bfloat16")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"     # the kernels' dispatch rule
    S, ps = args.slots, e["page_size"]
    P, N = e["max_len"] // ps, args.pages
    dt = spec.param_dtype
    pool = ((spec.layers_of(False), N, ps, spec.cache_row_width),
            spec.page_dtype)
    weights = {"TokEmb": ((spec.vocab_size, spec.d_model), dt),
               "FinalLnS": ((spec.d_model,), dt),
               "HeadW": ((spec.d_model, spec.vocab_size), dt)}
    for slot, key, shape, _ in spec.stack_planes():
        weights[slot] = ((spec.plane_layers(key), *shape), dt)
    state = {name: ((layers, S, *shape), dtype)
             for name, shape, dtype, layers in spec.slot_state()}
    snaps = {name + "Snap": ((layers, e["n_snapshots"], *shape), dtype)
             for name, shape, dtype, layers in spec.slot_state()}
    attrs = dict(spec.block.attrs(), page_size=ps, temperature=0.0, top_k=0)

    def plane(rows):
        return {"Temperature": ((rows,), "float32"),
                "TopK": ((rows,), "int32"), "TopP": ((rows,), "float32"),
                "Seed": ((rows,), "int32"), "Step": ((rows,), "int32")}

    progs = {
        "decode": (pipeline_ops.transformer_stack_paged_decode, {
            "Tok": ((S,), "int32"), "Pos": ((S,), "int32"),
            "BlockTable": ((S, P), "int32"), "CacheK": pool, "CacheV": pool,
            **plane(S), **weights, **state},
            ("CacheK", "CacheV", *state)),
        "prefill": (pipeline_ops.transformer_stack_paged_prefill, {
            "Chunk": ((1, e["prefill_chunk"]), "int32"),
            "StartPos": ((1,), "int32"), "Lengths": ((1,), "int32"),
            "BlockTable": ((1, P), "int32"), "StateSlot": ((1,), "int32"),
            "SnapFrom": ((1,), "int32"), "SnapTake": ((1,), "int32"),
            "CacheK": pool, "CacheV": pool, **plane(1), **weights, **state,
            **snaps}, ("CacheK", "CacheV", *state, *snaps)),
    }
    res = {"params": spec.n_params(), "slots": S, "pages": N}
    for name, (op, shapes, donated) in progs.items():
        names = sorted(shapes)

        def step(*a, op=op, names=names):
            outs = op(attrs, {k: [v] for k, v in zip(names, a)})
            return {k: v[0] for k, v in outs.items()}

        t = time.monotonic()
        compiled = jax.jit(step, donate_argnums=tuple(
            names.index(n) for n in donated)).lower(*[
                jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1],
                                     sharding=dev) for n in names]).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        res[name] = {
            "compile_s": round(time.monotonic() - t, 1),
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "kernels": sorted({k for k in ("paged_attention_decode",
                                           "kda_decode_step")
                               if f"%{k}" in text})}
        print(json.dumps({name: res[name]}), flush=True)
    if args.reference:
        w = {"tok_emb": weights["TokEmb"], "final_ln.scale":
             weights["FinalLnS"], "lm_head.w": weights["HeadW"]}
        for slot, key, shape, _ in spec.stack_planes():
            w[f"lm_stack.stack_{key}"] = weights[slot]
        w = {k: jax.ShapeDtypeStruct(s, d, sharding=dev)
             for k, (s, d) in w.items()}
        t = time.monotonic()
        with jax.default_matmul_precision("highest"):
            compiled = jax.jit(
                lambda w, ids, n: fam._hidden(cell.config, w, ids, n)).lower(
                w, jax.ShapeDtypeStruct((e["max_len"],), jnp.int32,
                                        sharding=dev),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=dev)).compile()
        mem = compiled.memory_analysis()
        res["reference"] = {
            "compile_s": round(time.monotonic() - t, 1),
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9}
        print(json.dumps({"reference": res["reference"]}), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "compile.json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


def check(args) -> int:
    import numpy as np

    import paddle_tpu as pt

    cell = _cell()
    fam = cell.family
    pt.set_amp(cell.config["amp"] == "bfloat16")
    mix = json.loads(json.dumps(cell.mix))
    table = mix["engine"]["max_len"] // mix["engine"]["page_size"]
    mix["engine"].update(slots=8, n_pages=3 * table + 2, n_snapshots=8)
    eng, _ = fam.build_engine(cell.config, mix, args.seed,
                              beam_width=fam.CHECK_TOPK)
    print(json.dumps({"built_s": round(time.monotonic() - T0, 1)}),
          flush=True)
    w = fam.weights_of(None, eng.scope)
    rng = np.random.default_rng(args.seed)
    pre = fam.draw_prompt_ids(rng, mix["prompt"]["shared_prefix"]["tokens"],
                              cell.config)
    turns = [fam.draw_prompt_ids(rng, n, cell.config) for n in (300, 700)]
    first = np.concatenate([pre, turns[0]])
    runs = [("cold_preamble", first, 48),
            ("hit_other_turn", np.concatenate([pre, turns[1]]), 48),
            ("hit_same_request", first, 48),
            ("unshared", fam.draw_prompt_ids(rng, 2300, cell.config), 96)]
    names = ("",) + tuple(args.variants or ())
    res = {"seed": args.seed, "fault": args.fault, "runs": {}}
    pooled = {n: [] for n in names}
    helds = {}
    for label, prompt, new in runs:
        if label == "hit_other_turn" and "misplaced_snapshot" in (
                args.fault or ()):
            fam.misplace_snapshots(eng)
        c0 = dict(eng.metrics.snapshot()["counters"])
        t = time.monotonic()
        calls, again, held = fam.served(eng, prompt, new)
        served_s = time.monotonic() - t
        helds[label] = held
        c1 = eng.metrics.snapshot()["counters"]
        rows = np.asarray(sorted({p for p, _, _ in calls}))
        line = {"prompt": int(prompt.size), "positions": len(calls),
                "served_s": round(served_s, 2),
                **{k: c1.get(k, 0) - c0.get(k, 0) for k in (
                    "prefix_hit_tokens", "state_snapshots_restored",
                    "state_snapshots_taken", "state_snapshot_cutback_tokens",
                    "prefill_chunks")}}
        for n in names:
            t = time.monotonic()
            lg, S = fam._rows_logits(cell.config, w, again[:-1], rows, n,
                                     states=True)
            errs = fam.errors_of(calls, dict(zip(rows.tolist(), lg)))
            pooled[n].extend(errs)
            st = fam.state_errors(held, S)
            line[n or "right"] = {
                "reference_s": round(time.monotonic() - t, 1),
                **{f"p{q}": float(np.percentile(errs, q))
                   for q in (50, 80, 95)}, "max": float(max(errs)),
                "state_rel_err": st["rel_err"], "state_bits": st["bits"]}
        if label == "hit_same_request":
            # the restore itself: the same request cold and from a snapshot
            line["state_vs_cold"] = [
                float(np.linalg.norm(a - b) / np.linalg.norm(b))
                for a, b in zip(held, helds["cold_preamble"])]
        res["runs"][label] = line
        print(json.dumps({label: line}), flush=True)
    for n in names:
        e = np.asarray(pooled[n])
        res[n or "right"] = {
            **{f"p{q}": float(np.percentile(e, q)) for q in (50, 80, 90, 95)},
            "max": float(e.max()), "positions": int(e.size),
            "over_the_limit": bool(np.percentile(
                e, fam.CHECK_LOGPROB_QUANTILE) > fam.CHECK_LOGPROB_TOL)}
    res["memory_peak_bytes"] = eng.executor.device().memory_stats().get(
        "peak_bytes_in_use")
    res["total_s"] = round(time.monotonic() - T0, 1)
    print(json.dumps(res), flush=True)
    os.makedirs(OUT, exist_ok=True)
    tag = "_".join(args.fault or ()) or "right"
    with open(os.path.join(OUT, f"check_{tag}_{args.seed}.json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


def cell_(args) -> int:
    from benchmark import run as bench_run
    from benchmark.families import kda_gqa_moe_lm as fam

    real = fam.reference_logit_gaps

    def planted(config, w, results):
        fam.misplace_snapshots(fam._ENGINES[id(config)][1])
        return real(config, w, results)

    if "misplaced_snapshot" in (args.fault or ()):
        fam.reference_logit_gaps = planted
    return bench_run.main(["--workload", CELL, "--seed", str(args.seed),
                           "--seconds", str(args.seconds), "--trace", "0"])


def tick(args) -> int:
    import numpy as np

    import paddle_tpu as pt

    cell = _cell()
    fam = cell.family
    pt.set_amp(cell.config["amp"] == "bfloat16")
    if args.scale is not None:
        cell.config["assumed"]["embedding_scale"] = args.scale
    if args.bias_std is not None:
        cell.config["assumed"]["router_bias_std"] = args.bias_std
    mix = json.loads(json.dumps(cell.mix))
    mix["engine"].update(n_pages=256, n_snapshots=2)
    eng, _ = fam.build_engine(cell.config, mix, args.seed)
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
           "router_bias_std": cell.config["assumed"]["router_bias_std"],
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

    from benchmark import sweep as bench_sweep

    cell = _cell()
    if args.greedy is not None:
        cell.mix["check"]["greedy_requests"] = args.greedy
    if args.ramp is not None:
        cell.mix["ramp_s"] = args.ramp
    line = bench_sweep.run_rate(cell, args.rate, args.schedule, {},
                                args.seed, args.seconds, jax.devices()[:1],
                                T0)
    line["wall_s"] = round(time.monotonic() - T0, 1)
    out = os.path.join(ROOT, "chiprun_out", "sweep")
    os.makedirs(out, exist_ok=True)
    name = (f"{CELL}_{args.rate:g}_{line['schedule_seed']}_"
            f"{args.seed}.json")
    with open(os.path.join(out, name), "w") as f:
        json.dump(line, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="phase", required=True)
    c = sub.add_parser("compile")
    c.add_argument("--slots", type=int, default=64)
    c.add_argument("--pages", type=int, default=4096)
    c.add_argument("--reference", type=int, default=0)
    k = sub.add_parser("check")
    k.add_argument("--seed", type=int, default=2**31 + 11)
    k.add_argument("--fault", nargs="*", choices=["misplaced_snapshot"])
    k.add_argument("--variants", nargs="*", metavar="VARIANT")
    f = sub.add_parser("cell")
    f.add_argument("--fault", nargs="*", choices=["misplaced_snapshot"])
    f.add_argument("--seed", type=int, default=2**31 + 17)
    f.add_argument("--seconds", type=float, default=51.0)
    t = sub.add_parser("tick")
    t.add_argument("--scale", type=float)
    t.add_argument("--bias-std", type=float)
    t.add_argument("--seed", type=int, default=2**31 + 13)
    t.add_argument("--live", type=int, default=40)
    t.add_argument("--new", type=int, default=100)
    s = sub.add_parser("sweep")
    s.add_argument("rate", type=float)
    s.add_argument("--seconds", type=float, default=60.0)
    s.add_argument("--schedule", type=int)
    s.add_argument("--greedy", type=int)
    s.add_argument("--ramp", type=float)
    s.add_argument("--seed", type=int, default=2**31 + 7)
    args = ap.parse_args(argv)
    if args.phase == "compile":
        return compile_(args)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("solar2_chip_check: needs a TPU; nothing was run",
              file=sys.stderr)
        return 1
    return {"check": check, "cell": cell_, "sweep": sweep,
            "tick": tick}[args.phase](args)


if __name__ == "__main__":
    sys.exit(main())
