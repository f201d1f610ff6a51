"""chip_smoke.py — the standing proof that the main path starts on the chip.

One process, no arguments. Drives ``transformer_lm`` -> ``SGD.train`` ->
``save_inference_model`` -> ``GenerationEngine.from_saved`` -> ``Server``
at the full width of the chip-session LM (V16384 d1024 L8 H8 T2048, AMP
bf16, random weights from a seed), then three ResNet-50 steps, then — when
four devices are visible — the same train step under dp4 and dp2 x mp2 and
four one-chip serving replicas behind a ``Fleet``. Every phase checks what
came out by the repo's own means and the first failed check ends the run
with a non-zero exit code.

It measures nothing: wall seconds and compile seconds are printed as
set-up time, never as a rate. It refuses to start without a TPU, sets
neither ``JAX_PLATFORMS`` nor a cache directory (the package resolves the
cache: ``$JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache`` on a
TPU), and writes only under ``chip_smoke_out/`` and that cache.

    python chip_smoke.py          # on a machine with a TPU

Every phase prints one JSON line (the summary's ends with
``"claim": null``); the last line of stdout is the result the driver
parses, with exactly these keys:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import collections
import gc
import json
import os
import re
import shutil
import sys
import threading
import time

import numpy as np

OUT_DIR = "chip_smoke_out"
SEED = 2026

# The chip-session width (ROADMAP S1/S5/S7): the one LM configuration with
# chip history, in the stacked layout serving can rejoin. Depth and step
# counts are the only things a rehearsal may cut.
FULL = dict(
    vocab=16384, d_model=1024, n_layers=8, heads=8, max_len=2048,
    batch=8, seq=2048, sync_steps=10, async_steps=10, lr=1e-3,
    # the stream lives on the first ``stream_mod`` ids of the vocabulary:
    # 20 steps can learn that support and its bigram map (each id seen
    # ~64x a step), where a recurrence over all 16384 ids — each seen
    # once a step — cannot move the loss at all (my chip run, PR 21:
    # 9.77 -> 9.84). The model, head and embedding stay full width.
    stream_mod=256,
    # first loss ~ln(16384) = 9.7; learning the support alone takes it to
    # ln(256) = 5.5
    loss_margin=1.5,
    slots=8, prompt_buckets=(64, 256), prefill_batch_buckets=(1, 4),
    page=64,
    # wave: (prompt_len, new_tokens, sampling meta) — lengths spread over
    # 32..1024, 16..64 new tokens; 1024 chunk-prefills (8 x 128 chunks)
    wave=((64, 32, None), (64, 32, None), (64, 32, None), (64, 32, None),
          (32, 16, None), (200, 24, None), (500, 40, None),
          (1024, 64, None),
          (48, 32, dict(temperature=0.8, top_p=0.9, seed=1234)),
          (96, 48, dict(temperature=1.0, top_p=0.95, seed=7)),
          (300, 20, dict(temperature=0.7, top_p=0.9, seed=99)),
          (148, 24, None)),
    oneshot=(64, 32),            # (prompt_len, new_tokens) of the saved op
    prefix_len=128,              # shared page-aligned prefix (2 pages)
    sharer=(33, 16),             # (tail, new tokens) of its second user
    # bf16 tolerance: emitted token's teacher-forced logit vs that
    # position's max logit (0 when both programs agree on the argmax)
    logit_gap_tol=0.25,
    resnet=dict(batch=256, hw=224, classes=1000, steps=3),
    multichip=dict(steps=3, loss_tol=2e-2, replica_requests=16,
                   replica_prompt=48, replica_new=16),
)


def device_info():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices()), "jax": jax.__version__}


def emit(phase, t0, **counts):
    """One JSON line per phase, naming the device it ran on."""
    rec = {"phase": phase, **device_info(),
           "seconds": round(time.perf_counter() - t0, 1), **counts}
    print(json.dumps(rec), flush=True)
    return rec


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# data: the demos/serving_lm.py recurrence inside the full vocabulary
# ---------------------------------------------------------------------------
def lm_sequences(rng, n, length, mod):
    """next = (3*cur + noise) % mod, noise in {0, 1}: learnable (the
    bigram map is nearly deterministic), seeded, nothing read from disk."""
    seq = np.zeros((n, length), np.int64)
    seq[:, 0] = rng.randint(0, mod, size=n)
    for t in range(length - 1):
        seq[:, t + 1] = (3 * seq[:, t] + rng.randint(0, 2, size=n)) % mod
    return seq


def lm_reader(cfg, steps, seed):
    def reader():
        rng = np.random.RandomState(seed)
        for _ in range(steps):
            seq = lm_sequences(rng, cfg["batch"], cfg["seq"] + 1,
                               cfg["stream_mod"])
            yield [(row[:-1], row[1:]) for row in seq]
    return reader


# ---------------------------------------------------------------------------
# phase 1: LM training in the servable layout
# ---------------------------------------------------------------------------
class LMTrainer:
    """The stacked LM + Adam behind ``trainer.SGD`` — one builder for the
    one-chip run and the sharded legs, so their programs (and therefore
    their seeded initial weights) are identical."""

    def __init__(self, cfg, plan=None):
        import paddle_tpu as pt
        from paddle_tpu import layers, models

        self.cfg = cfg
        self.scope = pt.Scope()
        self.main, self.startup = pt.Program(), pt.Program()
        self.main.random_seed = self.startup.random_seed = SEED
        V = cfg["vocab"]
        with pt.program_guard(self.main, self.startup):
            ids = layers.data("ids", shape=[cfg["seq"]], dtype="int64")
            tgt = layers.data("tgt", shape=[cfg["seq"]], dtype="int64")
            self.logits = models.transformer_lm(
                ids, vocab_size=V, d_model=cfg["d_model"],
                n_layers=cfg["n_layers"], num_heads=cfg["heads"],
                max_len=cfg["max_len"], pipeline_stack=True, remat=True)
            self.loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.reshape(self.logits, shape=[-1, V]),
                layers.reshape(tgt, shape=[-1, 1])))
            self.sgd = pt.trainer.SGD(
                self.loss, pt.optimizer.AdamOptimizer(
                    learning_rate=cfg["lr"]),
                [ids, tgt], scope=self.scope, plan=plan)

    def train(self, steps, seed, async_depth=1):
        """-> (losses in EndIteration order, EndIteration batch ids)."""
        import paddle_tpu as pt

        losses, order = [], []

        def handler(e):
            if isinstance(e, pt.event.EndIteration):
                losses.append(float(e.cost))
                order.append(e.batch_id)

        self.sgd.train(lm_reader(self.cfg, steps, seed), num_passes=1,
                       event_handler=handler, async_depth=async_depth)
        return losses, order


_MOSAIC_CALL = re.compile(
    r'%(\w+?)\.\d+ = [^\n]*?custom_call_target="tpu_custom_call", '
    r'operand_layout_constraints=\{([^\n]*?)\}\}')


def mosaic_calls(hlo_text):
    """[(kernel, [operand type, ...])] of a compiled module's Mosaic call
    instructions, under the names its trace shows: ``%flash_fwd.16 = ...
    custom-call(...), custom_call_target="tpu_custom_call",
    operand_layout_constraints={s32[8]{0}, bf16[8,1024,1024]{2,1,0},
    ...}`` -> ("flash_fwd", ["s32[8]", "bf16[8,1024,1024]", ...])."""
    return [(name, re.findall(r"(\w+\[[\d,]*\])", operands))
            for name, operands in _MOSAIC_CALL.findall(hlo_text)]


def compiled_step_loops_and_kernels(tr):
    """(``while`` instructions, the Mosaic call instructions as
    ``mosaic_calls`` gives them) of the COMPILED train step: what the
    device runs."""
    loops, calls = 0, []
    for c in tr.sgd.exe._cache.values():
        text = c.aot.as_text()
        loops += len(re.findall(r"= .* while\(", text))
        calls += mosaic_calls(text)
    return loops, calls


def flash_operands_not_bf16(calls, batch, seq, width):
    """The flash calls among ``calls`` (``mosaic_calls``) whose packed
    ``[batch, seq, heads * d_head]`` operands (q, k, v and, in the
    backward, dO) are not all bf16, or are not all there: what AMP must
    leave empty."""
    want = f"bf16[{batch},{seq},{width}]"
    return [(name, types) for name, types in calls
            if name.startswith("flash_")
            and [t for t in types if t.endswith(f"[{batch},{seq},{width}]")]
            != [want] * (3 if name == "flash_fwd" else 4)]


def phase_train(cfg):
    """Sync then async_depth=2 on one trainer (one compiled step). Pass:
    every loss finite, the last below the first by ``loss_margin``,
    EndIteration in batch order, the stack is traced once for forward
    and backward (core/backward.py), and on a TPU the compiled step holds
    TWO loops (forward scan, backward scan) running the Mosaic flash
    kernels: ONE ``flash_fwd`` a layer (the layer checkpoint saves the
    call's residuals: no recompute), one ``flash_dq``, one ``flash_dkv``,
    each on bf16 operands (AMP is on)."""
    t0 = time.perf_counter()
    tr = LMTrainer(cfg)
    sync_losses, sync_order = tr.train(cfg["sync_steps"], seed=SEED)
    async_losses, async_order = tr.train(cfg["async_steps"], seed=SEED + 1,
                                         async_depth=2)
    losses = sync_losses + async_losses
    check(len(sync_losses) == cfg["sync_steps"]
          and len(async_losses) == cfg["async_steps"],
          f"expected {cfg['sync_steps']}+{cfg['async_steps']} "
          f"EndIteration events, got {len(sync_losses)}+{len(async_losses)}")
    check(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(sync_order == list(range(cfg["sync_steps"])),
          f"sync EndIteration out of order: {sync_order}")
    check(async_order == list(range(cfg["async_steps"])),
          f"async EndIteration out of order: {async_order}")
    check(losses[-1] < losses[0] - cfg["loss_margin"],
          f"loss did not fall by {cfg['loss_margin']}: "
          f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    loops, calls = compiled_step_loops_and_kernels(tr)
    kernels = collections.Counter(name for name, _ in calls)
    check(tr.sgd.exe.cache_stats()["paired_vjp_ops"] == 1,
          "the stack op was not paired with its grad op: the train step "
          "traces its forward scan twice")
    if device_info()["platform"] == "tpu":
        # (the CPU backend expands scatters and sorts into loops of its own)
        check(loops == 2, f"{loops} while loops in the compiled train "
              "step: one forward + one backward scan expected")
        # the names kernels/flash_attention.py gives its pallas_calls;
        # none at all means the kernels gave way to the jnp reference
        check(dict(kernels) == {"flash_fwd": 1, "flash_dq": 1,
                                "flash_dkv": 1},
              f"Mosaic calls of the compiled train step {dict(kernels)}: "
              "expected one flash_fwd a layer (the forward scan's; a "
              "second is remat running the kernel again), one flash_dq, "
              "one flash_dkv")
        f32_fed = flash_operands_not_bf16(
            calls, cfg["batch"], cfg["seq"], cfg["d_model"])
        check(not f32_fed, "under AMP every flash call takes bf16 "
              f"[batch, T, heads * d_head] operands; these do not: "
              f"{f32_fed}")
    emit("lm_train", t0, steps=len(losses),
         first_loss=round(losses[0], 4), last_loss=round(losses[-1], 4),
         loss_margin=cfg["loss_margin"],
         while_loops_in_compiled_step=loops,
         mosaic_calls_in_compiled_step=dict(kernels),
         same_losses_as_previous_run=same_as_previous_run(cfg, losses),
         **tr.sgd.exe.cache_stats())
    return tr, losses[0]


def same_as_previous_run(cfg, losses):
    """Seeds fix the run, so a second invocation on the same device must
    reproduce the first one's losses — the check that executables
    RESTORED from the compile cache (which donate their state like fresh
    ones) are sound. None on a first run; the record lives in OUT_DIR."""
    path = os.path.join(OUT_DIR, "lm_train_losses.json")
    info = device_info()
    key = [info["device_kind"], info["jax"],
           repr(sorted((k, v) for k, v in cfg.items()
                       if not isinstance(v, (dict, tuple))))]
    previous = None
    if os.path.exists(path):
        with open(path) as f:
            previous = json.load(f)
    with open(path, "w") as f:
        json.dump({"key": key, "losses": losses}, f)
    if previous is None or previous["key"] != key:
        return None
    check(np.allclose(previous["losses"], losses, rtol=1e-6, atol=0),
          f"same seeds, same device, different losses than the previous "
          f"run: {previous['losses']} vs {losses}")
    return True


# ---------------------------------------------------------------------------
# phase 2: save -> load -> serve
# ---------------------------------------------------------------------------
def save_generation_model(cfg, tr, model_dir):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    tp, n_new = cfg["oneshot"]
    gen, gen_startup = pt.Program(), pt.Program()
    with pt.program_guard(gen, gen_startup):
        prompt = layers.data("prompt", shape=[tp], dtype="int64")
        out_ids = models.transformer_lm_generate(
            prompt, vocab_size=cfg["vocab"], d_model=cfg["d_model"],
            n_layers=cfg["n_layers"], num_heads=cfg["heads"],
            max_len=cfg["max_len"], max_new_tokens=n_new)
    pt.io.save_inference_model(model_dir, ["prompt"], [out_ids],
                               tr.sgd.exe, main_program=gen,
                               scope=tr.scope)
    return gen, out_ids


def teacher_forced_gaps(tr, results):
    """For every greedy-generated position: (max logit at that position)
    - (logit of the token the engine emitted), under ONE full-sequence
    forward of the training graph on the same weights. 0 where the two
    programs agree on the argmax; small and positive where bf16 broke a
    near-tie differently; large if the engine computes another function.
    The [b, T, V] logits stay on the device — only [b, T] comes back."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap_of(logits, nxt):
        lg = logits[:, :-1].astype(jnp.float32)
        tok = jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
        return jnp.max(lg, axis=-1) - tok

    cfg = tr.cfg
    B, T = cfg["batch"], cfg["seq"]
    gaps = []
    for i in range(0, len(results), B):
        group = results[i:i + B]
        ids = np.zeros((B, T), np.int64)
        for r, (prompt, out) in enumerate(group):
            ids[r, :out.size] = out
        logits, = tr.sgd.exe.run(
            tr.sgd.test_program, feed={"ids": ids, "tgt": ids},
            fetch_list=[tr.logits], scope=tr.scope, return_numpy=False)
        gap = np.asarray(gap_of(logits, ids[:, 1:].astype(np.int32)))
        del logits
        for r, (prompt, out) in enumerate(group):
            # logits[t] predicts token t+1: generated tokens sit at
            # positions prompt.size .. out.size-1
            gaps.extend(gap[r, prompt.size - 1:out.size - 1].tolist())
    return np.asarray(gaps, np.float32)


def phase_serve(cfg, tr):
    from paddle_tpu.serving import GenerationEngine, Server

    t0 = time.perf_counter()
    V = cfg["vocab"]
    model_dir = os.path.join(OUT_DIR, "lm")
    gen, gen_out = save_generation_model(cfg, tr, model_dir)
    eng = GenerationEngine.from_saved(
        model_dir, slots=cfg["slots"], max_seq_len=cfg["max_len"],
        prompt_buckets=cfg["prompt_buckets"],
        prefill_batch_buckets=cfg["prefill_batch_buckets"],
        page_size=cfg["page"])
    shapes = eng.warmup()
    warm = eng.cache_stats()

    rng = np.random.RandomState(SEED + 2)
    mod = cfg["stream_mod"]
    wave = [(lm_sequences(rng, 1, n, mod)[0], new, meta)
            for n, new, meta in cfg["wave"]]
    # the last wave entry opens with the shared page-aligned prefix
    prefix = wave[-1][0][:cfg["prefix_len"]]
    tail, sharer_new = cfg["sharer"]
    sharer = (np.concatenate([prefix, lm_sequences(rng, 1, tail, mod)[0]]),
              sharer_new, None)
    seeded_i = next(i for i, w in enumerate(wave) if w[2] is not None)

    def submit(srv, entry):
        prompt, new, meta = entry
        return srv.submit({"prompt": prompt}, max_new_tokens=new,
                          **(meta or {}))

    srv = Server(eng, max_wait_ms=2, max_queue=64)
    try:
        srv.start()
        futs, lock = [None] * len(wave), threading.Lock()

        def client(idx):
            for i in idx:
                f = submit(srv, wave[i])
                with lock:
                    futs[i] = f

        threads = [threading.Thread(target=client,
                                    args=(range(k, len(wave), 4),))
                   for k in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        outs = [np.asarray(f.result(timeout=600)) for f in futs]
        # after the wave: a second request opening with the cached
        # prefix, then the seeded request again, ALONE
        shared_out = np.asarray(submit(srv, sharer).result(timeout=600))
        alone = np.asarray(submit(srv, wave[seeded_i]).result(timeout=600))
    finally:
        srv.stop()

    for (prompt, new, _), out in zip(wave + [sharer], outs + [shared_out]):
        check(out.size == prompt.size + new,
              f"asked {new} tokens after {prompt.size}, got "
              f"{out.size - prompt.size}")
        check(np.array_equal(out[:prompt.size], prompt),
              "prompt not echoed")
        check(out.min() >= 0 and out.max() < V, "token id outside vocab")
    after = eng.cache_stats()
    check(after["misses"] == warm["misses"],
          f"serving compiled after warm-up: {warm} -> {after}")
    hits = eng.metrics.counter("prefix_hit_tokens")
    check(hits > 0, "no prefix-cache hit for the shared prefix")
    # (request, seed, step) purity — what PR 20's resume-from-token
    # recovery rests on
    check(np.array_equal(alone, outs[seeded_i]),
          "seeded request differs alone vs inside the wave: "
          f"{alone[-8:]} vs {outs[seeded_i][-8:]}")

    greedy = [(w[0], o) for w, o in zip(wave, outs) if w[2] is None]
    gaps = teacher_forced_gaps(tr, greedy)
    check(gaps.size == sum(w[1] for w in wave if w[2] is None),
          "scored positions != greedy tokens generated")
    check(float(gaps.max()) <= cfg["logit_gap_tol"],
          f"engine and training graph disagree: emitted token's logit is "
          f"{gaps.max():.4f} below the max (tol {cfg['logit_gap_tol']})")

    # exact equality with the one-shot op is a COUNT, not a gate: two
    # compiled programs need not break bf16 argmax ties alike
    tp, n_new = cfg["oneshot"]
    same = [i for i, w in enumerate(wave)
            if w[2] is None and w[0].size == tp and w[1] == n_new]
    ref, = tr.sgd.exe.run(
        gen, feed={"prompt": np.stack([wave[i][0] for i in same])},
        fetch_list=[gen_out], scope=tr.scope)
    ref = np.asarray(ref)
    equal = int(sum((ref[r, tp:] == outs[i][tp:]).sum()
                    for r, i in enumerate(same)))
    emit("lm_serve", t0, warmup_shapes=shapes, requests=len(wave) + 2,
         tokens_generated=int(sum(w[1] for w in wave) + sharer_new
                              + wave[seeded_i][1]),
         prefix_hit_tokens=int(hits),
         cache_misses_after_warmup=after["misses"] - warm["misses"],
         seeded_alone_equals_in_wave=True,
         greedy_positions_scored=int(gaps.size),
         logit_gap_max=round(float(gaps.max()), 5),
         logit_gap_nonzero=int((gaps > 0).sum()),
         logit_gap_tol=cfg["logit_gap_tol"],
         oneshot_equal_tokens=equal, oneshot_tokens=len(same) * n_new,
         **after)
    return eng.executor, model_dir


# ---------------------------------------------------------------------------
# phase 3: ResNet-50, the paper's model on the same executor
# ---------------------------------------------------------------------------
def phase_resnet(cfg):
    import jax

    import paddle_tpu as pt
    from paddle_tpu import layers, models

    t0 = time.perf_counter()
    r = cfg["resnet"]
    main, startup = pt.Program(), pt.Program()
    main.random_seed = startup.random_seed = SEED
    with pt.program_guard(main, startup):
        images = layers.data("images", shape=[r["hw"], r["hw"], 3])
        label = layers.data("label", shape=[1], dtype="int64")
        logits = models.resnet_imagenet(images, num_classes=r["classes"],
                                        depth=50)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
        pt.optimizer.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9).minimize(
            loss, startup_program=startup)
    scope = pt.Scope()
    exe = pt.Executor(pt.TPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(SEED)
    feed = {
        "images": jax.device_put(rng.rand(
            r["batch"], r["hw"], r["hw"], 3).astype("float32")),
        "label": jax.device_put(rng.randint(
            0, r["classes"], size=(r["batch"], 1)).astype("int64")),
    }
    losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(r["steps"])]
    check(np.all(np.isfinite(losses)), f"non-finite ResNet loss: {losses}")
    emit("resnet50_train", t0, steps=len(losses), batch=r["batch"],
         image_size=r["hw"], losses=[round(v, 4) for v in losses],
         **exe.cache_stats())
    return [exe]


# ---------------------------------------------------------------------------
# phase 4: the same path on four chips
# ---------------------------------------------------------------------------
def _state_bytes_per_device(scope, devices):
    """[bytes of parameter/optimizer state resident on devices[i]]."""
    import jax

    per = {d: 0 for d in devices}
    for name in scope.keys():
        val = scope.get(name)
        if isinstance(val, jax.Array):
            for sh in val.addressable_shards:
                per[sh.device] += sh.data.nbytes
    return [per[d] for d in devices]


def _local_kernel_batches(tr):
    """Batch extent of every Mosaic call's first operand in the COMPILED
    (partitioned) train step: the kernel must see the local shard, not
    the all-gathered batch."""
    found = set()
    for c in tr.sgd.exe._cache.values():
        # every flash kernel's first operand is the [batch] lengths vector
        # the scalar prefetch reads
        found.update(int(n) for n in re.findall(
            r'custom_call_target="tpu_custom_call", '
            r'operand_layout_constraints=\{s32\[(\d+)\]',
            c.aot.as_text()))
    return sorted(found)


def phase_multichip(cfg, ref_first_loss, model_dir):
    import jax

    import paddle_tpu as pt
    from paddle_tpu.parallel import (data_parallel_plan, make_mesh,
                                     megatron_plan)
    from paddle_tpu.serving import Fleet, GenerationEngine, Server

    t0 = time.perf_counter()
    n = len(jax.devices())
    if n < 4:
        print(json.dumps({"phase": "multichip", **device_info(),
                          "skipped": f"{n} device"}), flush=True)
        return []
    m = cfg["multichip"]
    devices = jax.devices()[:4]
    on_tpu = device_info()["platform"] == "tpu"
    executors, legs = [], {}
    for name, axes, make_plan in (
            ("dp4", {"dp": 4}, data_parallel_plan),
            ("dp2xmp2", {"dp": 2, "mp": 2}, megatron_plan)):
        tr = LMTrainer(cfg, plan=make_plan(make_mesh(axes, devices=devices)))
        losses, _ = tr.train(m["steps"], seed=SEED)
        check(np.all(np.isfinite(losses)), f"{name}: non-finite {losses}")
        check(abs(losses[0] - ref_first_loss) <= m["loss_tol"],
              f"{name}: first-step loss {losses[0]:.5f} vs one chip "
              f"{ref_first_loss:.5f} (tol {m['loss_tol']})")
        qkv = tr.scope.get("lm_stack.stack_qkv_w")
        check({s.device for s in qkv.addressable_shards} == set(devices),
              f"{name}: parameters not on all four devices")
        legs[name] = leg = {
            "losses": [round(v, 4) for v in losses],
            "state_bytes_per_device": _state_bytes_per_device(
                tr.scope, devices)}
        if on_tpu:
            stats = [d.memory_stats() for d in devices]
            check(all(s and s["bytes_in_use"] > 0 for s in stats),
                  f"{name}: a chip holds no memory")
            bh = _local_kernel_batches(tr)
            local = cfg["batch"] // axes["dp"]
            check(bh and max(bh) <= local,
                  f"{name}: flash kernel runs on batch {bh}, local "
                  f"shard is {local} — GSPMD gathered the batch")
            leg["kernel_batch"] = bh
        executors.append(tr.sgd.exe)
        del tr
        gc.collect()
    # tensor parallelism cuts what each device holds; dp replicates it
    dp, tp = (legs[k]["state_bytes_per_device"][0]
              for k in ("dp4", "dp2xmp2"))
    check(tp < dp, f"mp did not cut per-device state: {tp} vs {dp}")

    # four one-chip replicas of the model phase 2 saved, each on its
    # own device
    engines = [GenerationEngine.from_saved(
        model_dir, slots=cfg["slots"], max_seq_len=cfg["max_len"],
        prompt_buckets=(cfg["prompt_buckets"][0],),
        prefill_batch_buckets=(1,), page_size=cfg["page"],
        place=pt.TPUPlace(i)) for i in range(4)]
    for eng in engines:
        eng.warmup()
    rng = np.random.RandomState(SEED + 3)
    prompts = lm_sequences(rng, m["replica_requests"], m["replica_prompt"],
                           cfg["stream_mod"])
    fleet = Fleet([Server(e, max_wait_ms=2) for e in engines], hedge=False)
    try:
        fleet.start()
        futs = [fleet.submit({"prompt": p}, max_new_tokens=m["replica_new"])
                for p in prompts]
        outs = [np.asarray(f.result(timeout=600)) for f in futs]
    finally:
        fleet.stop()
    check(all(o.size == m["replica_prompt"] + m["replica_new"]
              for o in outs), "replica returned a short generation")
    served = [int(e.metrics.counter("completed")) for e in engines]
    check(all(served), f"a replica answered nothing: {served}")
    for i, eng in enumerate(engines):
        want = {devices[i]}
        for var in ("tok_emb", "lm_stack.stack_qkv_w",
                    "serving.paged_cache_k", "serving.paged_cache_v"):
            got = eng.scope.get(var).devices()
            check(got == want, f"replica {i}: {var} on {got}, not {want}")
    executors.extend(e.executor for e in engines)
    emit("multichip", t0, **legs, replicas_served=served,
         replica_tokens=int(sum(o.size for o in outs) - prompts.size))
    return executors


# ---------------------------------------------------------------------------
def run(cfg):
    """All phases in order; returns the summary dict. A failed check
    raises — nothing is caught and carried past."""
    import jax

    import paddle_tpu as pt

    t0 = time.perf_counter()
    os.makedirs(OUT_DIR, exist_ok=True)
    pt.set_amp(True)
    executors = []
    try:
        tr, first_loss = phase_train(cfg)
        executors.append(tr.sgd.exe)
        serve_exe, model_dir = phase_serve(cfg, tr)
        executors.append(serve_exe)
        del tr, serve_exe
        gc.collect()
        executors += phase_resnet(cfg)
        gc.collect()
        executors += phase_multichip(cfg, first_loss, model_dir)
    finally:
        pt.set_amp(False)
        # the saved weights are ~0.5 GB at full width: never left behind
        shutil.rmtree(os.path.join(OUT_DIR, "lm"), ignore_errors=True)
    stats = [e.cache_stats() for e in executors]
    totals = {
        "fresh_compiles": sum(s["fresh_compiles"] for s in stats),
        "persistent_hits": sum(s["persistent_hits"] for s in stats),
        "entries": sum(s["entries"] for s in stats),
        "compile_seconds_setup": round(
            sum(e.compile_seconds for e in executors), 1),
    }
    mem = jax.devices()[0].memory_stats() or {}
    from paddle_tpu.xla_env import compilation_cache_dir

    return emit("summary", t0, **totals,
                peak_bytes_in_use=mem.get("peak_bytes_in_use"),
                compilation_cache_dir=compilation_cache_dir(),
                claim=None)


def result_line():
    """The last line of stdout, printed only when every phase passed:
    exactly ``ok`` and ``device``, the device as JAX reports it — the
    driver refuses any other key."""
    import jax

    dev = jax.devices()[0]
    return json.dumps({"ok": True,
                       "device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(jax.devices())}})


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 1
    run(FULL)
    print(result_line(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
