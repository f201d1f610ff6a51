"""keye2-serve-video's own checks, beside the benchmark.

    python3 tools/keye2_chip_check.py compile           (the sandbox: no chip)
    python3 tools/keye2_chip_check.py controls [--seed N] [--variants ..]
                                    [--contexts 1500 9000]  (the chip)
    python3 tools/keye2_chip_check.py kernel            (the chip)
    python3 tools/keye2_chip_check.py slice [--xplane PATH]
                                    (after a --trace 1 run, same checkout)

``compile``: the cell's prefill unit and decode tick, lowered from the paged
ops at the cell's own shapes (its spec with the tower, its pools and table,
Pixels / PosIds / MediaRow) and compiled for a described v5e: what Mosaic or
the HBM refuse here costs no chip time; prints the arguments' and the
temporaries' bytes and the custom calls by name.

``controls``: the cell's OWN check line (``dsa_gqa_moe_vl.check_readings``:
what ``reference_logit_gaps`` hands the serve driver) on the four greedy
requests the cell's schedule checks, served by the engine at ``--seed``,
against the reference AND against each wrong model: every limit's two
readings come from here, through the harness's own comparison (the largest
of the four scaled readings against the mix's ``check.logit_gap_tol``). One
row a variant, with ``correct`` as the driver would say it, to
``chiprun_out/keye2/controls_<seed>.json``. ``--contexts``: requests of
those lengths (a drawn prompt and 64 tokens of answer) in place of the
schedule's four: how the readings move inside ``topk`` and past 6144.

``slice``: what the last traced run's slice held (its ``xplane.pb`` under
``.bench_out/keye2-serve-video/``): the ticks and the prefill units by the
walks' device events (``paged_attention_decode`` / ``paged_attention_prefill``,
one a layer a call), their device seconds, the slice's length and busy time:
the numbers to state beside every device metric of the cell.

``kernel``: this shape's own row of ``kernels/paged_attention.py``'s table
(32 query heads over K and V pools of 4 x 128, bf16 pages of 256, a table of
25,600 keys, ``group_rows`` 1): a 1024-query unit's walk with and without the
pick's mask at 0 to 24,576 keys behind it, the tick's at 16 slots, the pick
itself (``_dsa_pick``: the scores over the table and the k-th score's search)
and the gathered form (``_dsa_attend_kv``) beside them, ms a call of ONE layer.
"""
import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
CELL = "keye2-serve-video"


def _shapes(spec, e, tc):
    """op slot -> (shape, dtype) of the prefill unit (``tc`` tokens) or the
    tick (``tc`` None) at the cell's engine settings."""
    ps, dt = e["page_size"], spec.param_dtype
    P = e["max_len"] // ps
    rows = 1 if tc is not None else e["slots"]
    v = spec.vision
    feeds = {"BlockTable": ((rows, P), "int32"),
             "Temperature": ((rows,), "float32"), "TopK": ((rows,), "int32"),
             "TopP": ((rows,), "float32"), "Seed": ((rows,), "int32"),
             "Step": ((rows,), "int32")}
    if tc is None:
        feeds.update(Tok=((rows,), "int32"), Pos=((rows,), "int32"),
                     RopeOffset=((rows,), "int32"))
    else:
        frames = -(-tc // v.tokens_per_frame) + 1
        feeds.update(Chunk=((1, tc), "int32"), StartPos=((1,), "int32"),
                     Lengths=((1,), "int32"), PosIds=((1, 3 * tc), "int32"),
                     MediaRow=((1, tc), "int32"),
                     Pixels=((1, frames) + v.frame_shape, "uint8"))
    L = spec.pool_layers(False)
    pools = {"CacheK": ((L, e["n_pages"], ps, spec.cache_row_width),
                        spec.page_dtype),
             "CacheV": ((L, e["n_pages"], ps, spec.cache_row_width),
                        spec.page_dtype),
             "CacheIndex": ((L, e["n_pages"], ps, spec.index_dim),
                            spec.page_dtype)}
    weights = {"TokEmb": ((spec.vocab_size, spec.d_model), dt),
               "FinalLnS": ((spec.d_model,), dt),
               "HeadW": ((spec.d_model, spec.vocab_size), dt)}
    for slot, key, shape, _ in spec.stack_planes():
        weights[slot] = ((spec.plane_layers(key), *shape), dt)
    if tc is not None:
        for slot, _, shape, _, _ in spec.vision_planes():
            weights[slot] = (tuple(shape), dt)
    return feeds, pools, weights


def compile_only() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as pt
    from benchmark import harness
    from paddle_tpu.ops import pipeline_ops

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    cell = harness.load_cell(CELL)
    e = cell.mix["engine"]
    spec = cell.family.spec_of(cell.config)
    pt.set_amp(True)
    jax.default_backend = lambda: "tpu"     # the kernels' dispatch rule
    out = {}
    for what, tc, op in (
            ("tick", None, pipeline_ops.transformer_stack_paged_decode),
            ("unit", e["prefill_chunk"],
             pipeline_ops.transformer_stack_paged_prefill)):
        feeds, pools, weights = _shapes(spec, e, tc)
        shapes = {**feeds, **pools, **weights}
        names = sorted(shapes)
        attrs = dict(spec.block.attrs(), page_size=e["page_size"],
                     temperature=0.0, top_k=0, emit_topk=e["beam_width"])

        def step(*args):
            outs = op(attrs, {k: [a] for k, a in zip(names, args)})
            return {k: v[0] for k, v in outs.items()}

        t0 = time.monotonic()
        compiled = jax.jit(step, donate_argnums=tuple(
            names.index(n) for n in pools)).lower(*[
                jax.ShapeDtypeStruct(shapes[n][0], shapes[n][1],
                                     sharding=one_chip)
                for n in names]).compile()
        mem = compiled.memory_analysis()
        text = re.sub(r"\{[^{}]*\}", "", compiled.as_text())
        calls = {}
        for name in re.findall(r"%([a-z_0-9]+?)(?:\.\d+)? = [^\n]*"
                               r"custom-call\([^\n]*tpu_custom_call", text):
            calls[name] = calls.get(name, 0) + 1
        # ops that move something as large as the indexer's pool (a copy or
        # a re-layout of a pool is work proportional to the POOL, not to the
        # tokens in flight)
        pool_elems = 1
        for n in pools["CacheIndex"][0]:
            pool_elems *= n
        big = {}
        for m in re.finditer(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\] "
                             r"(copy|transpose|reshape)\(", text, re.M):
            n = 1
            for d in m.group(2).split(","):
                n *= int(d)
            if n >= pool_elems:
                key = f"{m.group(3)} [{m.group(2)}]"
                big[key] = big.get(key, 0) + 1
        out[what] = {
            "pool_sized_copies": big,
            "compile_s": time.monotonic() - t0,
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "custom_calls": calls}
        print(json.dumps({what: out[what]}), flush=True)
    return 0


def controls(seed, which, contexts) -> int:
    import jax
    import numpy as np

    import paddle_tpu as pt
    from benchmark import harness, traffic

    cell = harness.load_cell(CELL)
    fam, config, mix = cell.family, cell.config, cell.mix
    if jax.devices()[0].platform != "tpu":
        print("keye2_chip_check controls needs the chip", file=sys.stderr)
        return 1
    pt.set_amp(config["amp"] == "bfloat16")
    t0 = time.monotonic()
    eng, _ = fam.build_engine(config, mix, seed)
    eng.warmup()
    w = fam.weights_of(None, eng.scope)
    if contexts:
        rng = np.random.RandomState(seed & 0x7FFFFFFF)
        prompts = [fam.draw_prompt_ids(rng, n - 64, config) for n in contexts]
        new = [64] * len(prompts)
    else:
        # the requests the serve driver checks: the first ``greedy_requests``
        # greedy ones due in the window (benchmark/drivers/serve.py)
        ramp, seconds = mix["ramp_s"], harness._read_json(
            harness.MANIFEST)["run_seconds"]
        planned = traffic.schedule(
            mix, seed, ramp, seconds,
            lambda rng, n: fam.draw_prompt_ids(rng, n, config))
        checked = [p for p in planned if ramp <= p.due < ramp + seconds
                   and p.sampling is None][:mix["check"]["greedy_requests"]]
        prompts = [p.prompt for p in checked]
        new = [min(p.max_new_tokens, fam.CHECK_REPLAY_TOKENS)
               for p in checked]
    results = [(p.size, np.asarray(eng.generate_all(
        [{"prompt": p}], max_new_tokens=n)[0])) for p, n in zip(prompts, new)]
    setup_s = time.monotonic() - t0
    lines = fam.check_readings(config, w, results, variants=which)
    tol = mix["check"]["logit_gap_tol"]
    rows = {"seed": seed, "setup_s": setup_s, "limit": tol, "variants": {}}
    for v, line in lines.items():
        line["correct"] = bool(max(line["scaled"]) <= tol)
        rows["variants"][v or "right"] = line
        print(json.dumps({v or "right": line}), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "keye2")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"controls_{seed}.json"), "w") as f:
        json.dump(rows, f)
    return 0


def slice_counts(xplane) -> int:
    from benchmark import harness, trace_reduce

    cell = harness.load_cell(CELL)
    xplane = xplane or harness.TraceSlice(CELL).dir
    if os.path.isdir(xplane):
        import glob
        found = sorted(glob.glob(os.path.join(
            xplane, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            print(f"no xplane.pb under {xplane}", file=sys.stderr)
            return 1
        xplane = found[-1]
    trace = trace_reduce.load(xplane)
    layers = cell.config["num_hidden_layers"]
    lo, hi = trace.window
    row = {"window_s": trace.window_s, "busy_s": trace.busy_s(0)}
    for what, call in (("ticks", "paged_attention_decode"),
                       ("units", "paged_attention_prefill")):
        events = [(s, e) for text, s, e in trace.device_ops.get(0, ())
                  if trace_reduce.parse_op(text)[0].split(".")[0] == call
                  and lo <= s < hi]
        row[what] = len(events) / layers
        row[f"{what}_walk_s"] = sum(e - s for s, e in events)
    print(json.dumps(row), flush=True)
    return 0


def kernel() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from paddle_tpu.kernels import paged_attention as pa
    from paddle_tpu.ops import pipeline_ops

    cell = harness.load_cell(CELL)
    e = cell.mix["engine"]
    spec = cell.family.spec_of(cell.config)
    blk = spec.block
    if jax.devices()[0].platform != "tpu":
        print("keye2_chip_check kernel needs the chip", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    L, N, ps, W = 2, e["n_pages"], e["page_size"], spec.cache_row_width
    P, H, dh = e["max_len"] // ps, spec.num_heads, spec.head_dim
    Hi, Di, tc, S = spec.index_heads, spec.index_dim, e["prefill_chunk"], \
        e["slots"]
    bf = jnp.bfloat16
    ck, cv = (jnp.asarray(rng.standard_normal((L, N, ps, W)), bf)
              for _ in range(2))
    ci = jnp.asarray(rng.standard_normal((L, N, ps, Di)), bf)

    def timed(fn, *args, reps=5):
        out = jax.block_until_ready(fn(*args))
        t0 = time.monotonic()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return 1e3 * (time.monotonic() - t0) / reps

    rows = []
    for what, b, t in (("unit", 1, tc), ("tick", S, 1)):
        table = jnp.asarray(np.stack([rng.permutation(np.arange(1, N))[:P]
                                      for _ in range(b)]).astype(np.int32))
        q = jnp.asarray(0.3 * rng.standard_normal((b, H, t, dh)), bf)
        q_i = jnp.asarray(rng.standard_normal((b, t, Hi, Di)), jnp.float32)
        w_i = jnp.asarray(rng.standard_normal((b, t, Hi)), jnp.float32)
        for behind in (0, 4096, 8192, 16384, P * ps - tc):
            start = jnp.full((b,), behind, jnp.int32)
            pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
            n = jnp.full((b,), t, jnp.int32)
            pick = jax.jit(lambda qi, wi, c, tb, p: pipeline_ops._dsa_pick(
                blk, qi, wi, c, 0, tb, p))
            picked = pick(q_i, w_i, ci, table, pos)
            if what == "unit":
                walk = jax.jit(lambda q, k, v, tb, s, n, m: pa.
                               paged_attention_prefill(
                                   q, k, v, 0, tb, s, n, group_mask=m,
                                   group_rows=None if m is None else 1))
                args = (q, ck, cv, table, start, n)
            else:
                walk = jax.jit(lambda q, k, v, tb, ln, m: pa.
                               paged_attention_decode(
                                   q[:, :, 0], k, v, 0, tb, ln,
                                   group_mask=None if m is None else m[:, 0],
                                   group_rows=None if m is None else 1))
                args = (q, ck, cv, table, start + 1)
            row = {"call": what, "keys_behind": behind,
                   "pick_ms": timed(pick, q_i, w_i, ci, table, pos),
                   "walk_masked_ms": timed(walk, *args, picked),
                   "walk_unmasked_ms": timed(walk, *args, None)}
            if behind in (8192,):
                gather = jax.jit(lambda q, qi, wi, k, v, c, tb, p:
                                 pipeline_ops._dsa_attend_kv(
                                     blk, q, qi, wi, k, v, c, 0, tb, p))
                row["gathered_ms"] = timed(gather, q, q_i, w_i, ck, cv, ci,
                                           table, pos, reps=2)
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out", "keye2")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kernel.json"), "w") as f:
        json.dump(rows, f)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("compile", "controls", "kernel",
                                     "slice"))
    ap.add_argument("--xplane", default=None)
    ap.add_argument("--contexts", type=int, nargs="*", default=[])
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--variants", nargs="*", default=[
        "", "recent_pick", "no_selection", "fp8_operands", "bf16_results",
        "bf16_stated_f32", "no_mrope", "no_vision"])
    args = ap.parse_args(argv)
    if args.what == "compile":
        return compile_only()
    if args.what == "kernel":
        return kernel()
    if args.what == "slice":
        return slice_counts(args.xplane)
    return controls(args.seed, tuple(args.variants), args.contexts)


if __name__ == "__main__":
    sys.exit(main())
