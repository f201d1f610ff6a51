"""``kexaone-serve-reason`` by hand (family ``window_mtp_moe_lm``; all but
``compile`` need a TPU, each phase its own process):

    compile [--slots 64] [--pages 4096] [--pages-window 512] [--emit-topk 0]
        the cell's decode (VERIFY) tick and its 256-token prefill unit,
        lowered from the ops at the cell's shapes and compiled for a
        DESCRIBED v5e with the chip's own compiler, no chip needed:
        ``memory_analysis()`` and which kernels are in each program (the
        tick's attention AND the unit's must be the page walk, never a
        gathered table);
        ``--emit-topk 8``: the check's twin, the beam plane compiled in
    variants [--seed N] [--requests 2] [--only a,b] [--plain-init 0|1]
        the check's readings (served top-8 log-prob error of the stack and
        of the drafting block, draft against the reference block's argmax;
        the emitted gap needs a timed run) on the cell's
        engine shapes at full width, for the right reference and every
        wrong model of ``VARIANTS``; ``--plain-init 1`` leaves the drafting
        block as the start-up program seeds it (acceptance before the
        ``assumed.mtp_init`` choice)

Writes ``chiprun_out/kexaone/<phase>.json``.
"""
import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "kexaone")
CELL = "kexaone-serve-reason"


def _cell():
    from benchmark import harness

    return harness.load_cell(CELL)


def compile_(args) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as pt
    from paddle_tpu.lm_spec import DRAFT_SLOT_PREFIX
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
    P = e["max_len"] // ps
    dt, W = spec.param_dtype, spec.cache_row_width
    pool = ((spec.pool_layers(False), args.pages, ps, W), spec.page_dtype)
    pool_w = ((spec.pool_layers(True), args.pages_window, ps, W),
              spec.page_dtype)
    weights = {"TokEmb": ((spec.vocab_size, spec.d_model), dt),
               "FinalLnS": ((spec.d_model,), dt),
               "HeadW": ((spec.d_model, spec.vocab_size), dt)}
    for slot, key, shape, _ in spec.stack_planes():
        weights[slot] = ((spec.plane_layers(key), *shape), dt)
    for slot, _, shape, _ in spec.draft_planes():
        weights[slot] = (tuple(shape), dt)
    for slot, _, shape, _ in spec.draft_spec().stack_planes():
        weights[DRAFT_SLOT_PREFIX + slot] = ((1, *shape), dt)
    attrs = dict(spec.block.attrs(), page_size=ps, temperature=0.0, top_k=0,
                 emit_topk=args.emit_topk)
    pools = {"CacheK": pool, "CacheV": pool, "CacheKW": pool_w,
             "CacheVW": pool_w}

    def plane(rows):
        return {"Temperature": ((rows,), "float32"),
                "TopK": ((rows,), "int32"), "TopP": ((rows,), "float32"),
                "Seed": ((rows,), "int32"), "Step": ((rows,), "int32"),
                "BlockTable": ((rows, P), "int32"),
                "BlockTableW": ((rows, P), "int32")}

    progs = {
        "decode": (pipeline_ops.transformer_stack_paged_decode, {
            "Tok": ((S,), "int32"), "Pos": ((S,), "int32"),
            "Draft": ((S,), "int32"), **plane(S), **pools, **weights}),
        "prefill": (pipeline_ops.transformer_stack_paged_prefill, {
            "Chunk": ((1, e["prefill_chunk"]), "int32"),
            "StartPos": ((1,), "int32"), "Lengths": ((1,), "int32"),
            "DraftNext": ((1,), "int32"), **plane(1), **pools, **weights}),
    }
    res = {"params": spec.n_params(), "slots": S, "pages": args.pages,
           "pages_window": args.pages_window}
    for name, (op, shapes) in progs.items():
        names = sorted(shapes)

        def step(*a, op=op, names=names):
            outs = op(attrs, {k: [v] for k, v in zip(names, a)})
            return {k: v[0] for k, v in outs.items()}

        t = time.monotonic()
        compiled = jax.jit(step, donate_argnums=tuple(
            names.index(n) for n in pools)).lower(*[
                jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1],
                                     sharding=dev) for n in names]).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        res[name] = {
            "compile_s": round(time.monotonic() - t, 1),
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "attention_kernel_calls": len(re.findall(
                r"%paged_attention_decode[.\d]* = ", text)),
            # the chunk walk: one call a K/V layer of the prefill unit
            "prefill_kernel_calls": len(re.findall(
                r"%paged_attention_prefill[.\d]* = ", text)),
            # a gathered table-width context: pages [rows, P, ps, W]
            "gathered_tables": len(re.findall(
                rf"\[\d+,{P},{ps},{W}\]", text))}
        print(json.dumps({name: res[name]}), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "compile.json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


def variants(args) -> int:
    import jax
    import numpy as np

    import paddle_tpu as pt

    if jax.devices()[0].platform != "tpu":
        print("variants needs a TPU", file=sys.stderr)
        return 1
    cell = _cell()
    fam, config, mix = cell.family, cell.config, cell.mix
    pt.set_amp(config["amp"] == "bfloat16")
    e = dict(mix["engine"])
    e["n_pages"] = e["max_len"] // e["page_size"] * 2 + 2
    e["n_pages_window"] = 64
    if args.plain_init:
        fam.mtp_start_up = lambda scope, init: None
    if args.mtp_init:
        config["assumed"]["mtp_init"] = dict(zip(
            ("embedding_pass", "hidden_scale", "block_out_scale"),
            map(float, args.mtp_init.split(","))))
    if args.embedding_scale:
        config["assumed"]["embedding_scale"] = args.embedding_scale
    eng, _ = fam.build_engine(config, dict(mix, engine=e), args.seed,
                              beam_width=fam.CHECK_TOPK)
    w = fam.weights_of(None, eng.scope)
    rng = np.random.RandomState(args.seed % 2**31)
    # one short turn, one beyond the 128-key window by thousands
    shapes = [(300, 160), (2500, 224), (900, 192)][:args.requests]
    names = [""] + [v for v in fam.VARIANTS
                    if not args.only or v in args.only.split(",")]
    res = {"seed": args.seed, "plain_init": bool(args.plain_init),
           "shapes": shapes, "readings": {}}
    replays = []
    for n_prompt, n_new in shapes:
        prompt = fam.draw_prompt_ids(rng, n_prompt, config)
        replays.append(fam.served(eng, prompt, n_new))
    c = eng.metrics.snapshot()["counters"]
    res["mtp"] = {k: c.get(k, 0) for k in (
        "mtp_drafted", "mtp_accepted", "mtp_first_ticks", "decode_tokens",
        "decode_live_rows", "verify_rows_rejected")}
    res["mtp"]["accept_pct"] = 100.0 * c.get("mtp_accepted", 0) / max(
        c.get("mtp_drafted", 0), 1)
    print(json.dumps({"mtp": res["mtp"]}), flush=True)
    for variant in names:
        errs, derrs, equal, n = [], [], 0, 0
        t = time.monotonic()
        for calls, drafts, out in replays:
            r = fam.read_replay(config, w, calls, drafts, out, variant)
            errs += r["errs"]
            derrs += r["draft_errs"]
            equal += r["draft_equal"]
            n += r["draft_n"]
        res["readings"][variant or "right"] = {
            **{f"p{q}": float(np.percentile(errs, q))
               for q in (50, 80, 90, 95, 99)},
            "max": float(max(errs)), "positions": len(errs),
            "draft_unequal_share": 1.0 - equal / max(n, 1),
            "draft_positions": n,
            **{f"draft_p{q}": float(np.percentile(derrs, q))
               for q in (50, 90, 99)}, "draft_max": float(max(derrs)),
            "s": round(time.monotonic() - t, 1)}
        print(json.dumps({variant or "right":
                          res["readings"][variant or "right"]}), flush=True)
    os.makedirs(OUT, exist_ok=True)
    res["assumed"] = {k: config["assumed"][k]
                      for k in ("mtp_init", "embedding_scale")}
    tag = f"variants_{args.seed}_{int(args.plain_init)}{args.tag}"
    with open(os.path.join(OUT, tag + ".json"), "w") as f:
        json.dump(res, f, indent=1)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="phase", required=True)
    c = sub.add_parser("compile")
    c.add_argument("--slots", type=int, default=64)
    c.add_argument("--pages", type=int, default=4096)
    c.add_argument("--pages-window", type=int, default=512)
    c.add_argument("--emit-topk", type=int, default=0)
    v = sub.add_parser("variants")
    v.add_argument("--seed", type=int, default=2**31 + 11)
    v.add_argument("--requests", type=int, default=2)
    v.add_argument("--only", default="")
    v.add_argument("--plain-init", type=int, default=0)
    v.add_argument("--mtp-init", default="",
                   help="embedding_pass,hidden_scale,block_out_scale")
    v.add_argument("--embedding-scale", type=float, default=0.0)
    v.add_argument("--tag", default="")
    args = ap.parse_args(argv)
    return compile_(args) if args.phase == "compile" else variants(args)


if __name__ == "__main__":
    sys.exit(main())
