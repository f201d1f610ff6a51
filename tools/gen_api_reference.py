"""Generate API_REFERENCE.md from the package's public surface.

Walks paddle_tpu's public modules (respecting __all__ where defined),
collecting each public function/class with its signature and first
docstring line. Run from the repo root on the CPU backend:

    JAX_PLATFORMS=cpu PYTHONPATH=. python tools/gen_api_reference.py
"""
import importlib
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MODULES = [
    ("paddle_tpu", "Top level: Program/Executor/Scope, program_guard, AMP"),
    ("paddle_tpu.layers", "Graph-building layers (fluid-style)"),
    ("paddle_tpu.models", "Model zoo"),
    ("paddle_tpu.optimizer", "Optimizers"),
    ("paddle_tpu.initializer", "Parameter initializers"),
    ("paddle_tpu.regularizer", "Weight regularizers"),
    ("paddle_tpu.clip", "Gradient clipping"),
    ("paddle_tpu.learning_rate_decay", "LR schedules"),
    ("paddle_tpu.evaluator", "Evaluators / metrics"),
    ("paddle_tpu.io", "Save/load + inference models"),
    ("paddle_tpu.checkpoint", "Training checkpoint/resume"),
    ("paddle_tpu.core.manifest",
     "Cold-start plane: compile-signature manifests + AOT warmup replay"),
    ("paddle_tpu.trainer", "Training loop driver"),
    ("paddle_tpu.resilience",
     "Preemption-safe training: checkpoint manager, retry, fault plans"),
    ("paddle_tpu.transpiler",
     "Program-rewrite pass framework + standard passes"),
    ("paddle_tpu.analysis",
     "Static analysis: whole-program shape checker, verifier, lint rules"),
    ("paddle_tpu.checkgrad", "Numeric gradient checking"),
    ("paddle_tpu.profiler", "Timers, traces, per-op stats"),
    ("paddle_tpu.trace",
     "Span tracing, trace exporters, RunLog journals"),
    ("paddle_tpu.trace.goodput",
     "Training observatory: goodput/badput accounting + live MFU"),
    ("paddle_tpu.flags", "Runtime flag registry"),
    ("paddle_tpu.parallel", "Meshes, sharding plans, pipeline/ring"),
    ("paddle_tpu.reader", "Reader decorators and batching"),
    ("paddle_tpu.dataset", "Datasets"),
    ("paddle_tpu.data_feeder", "Batch -> feed-dict conversion"),
    ("paddle_tpu.master", "Fault-tolerant task dispatch (native)"),
    ("paddle_tpu.serving",
     "Model server: dynamic & continuous batching; GenerationEngine(spec, "
     "scope, slots=, page_size=, n_pages=, prefill_chunk=, ..; "
     "snapshot_stride=<pages>, n_snapshots=<rows>: state snapshots, the "
     "prefix index for a spec whose slots carry a recurrent state; "
     "media_resolver=fn: a spec with a vision tower takes payloads "
     "{prompt, media: [frames uint8 [F, S, S, 3], ..]} or resolves a "
     "vision span at admission; serving.media has the check submit makes "
     "and the plan admission makes)"),
    ("paddle_tpu.serving.fleet",
     "Multi-replica fleet: retries/hedging, breakers, load shedding, "
     "rolling weight updates"),
    ("paddle_tpu.serving.router",
     "Fleet routing policies + per-replica circuit breakers"),
    ("paddle_tpu.online",
     "Streaming online learning: endless-pass trainer + weight "
     "publisher"),
    ("paddle_tpu.feedback",
     "Closed feedback loop: impression log, outcome joiner, "
     "compactor feeding the master queue"),
    ("paddle_tpu.capi", "C inference ABI bindings"),
    ("paddle_tpu.v2", "The v2 user API facade"),
    ("paddle_tpu.v2.layer", "v2 keyword layer namespace"),
    ("paddle_tpu.v2.networks", "v2 composite networks"),
    ("paddle_tpu.lm_spec",
     "The stacked LM's model spec: Block (what a block computes), LMSpec "
     "(plus sizes), RopeScaling; attention mha | mla, a shared expert, a "
     "held share of the router's experts; a layer_pattern over attention "
     "kinds (kda | mla | gqa | mamba2, each a whole block, or HALF a "
     "block: '<kind>+none' the mixer alone, 'none+ffn' the feed-forward "
     "alone; planes by kind, recurrent kinds beside ONE kind that "
     "caches tokens; mamba2 = Mamba-2 (SSD) with mamba_heads / "
     "mamba_head_dim / mamba_groups / mamba_state / mamba_conv / "
     "mamba_chunk; gqa = grouped-query K/V pages without positions, "
     "attn_gate head | channel; kda_decay bounded | softplus, "
     "kda_neg_eigval, kda_proj_rank; slot_state = what a serving slot "
     "holds beside its pages, which GenerationEngine(snapshot_stride=, "
     "n_snapshots=) also keeps as snapshot rows for its prefix index; "
     "require_stateless still refuses beams, resume, the slot handoff and "
     "share_cache_with=), first_dense leading dense layers (of a stack "
     "held by attention kind, or of full / window K/V layers: "
     "plane_layers), the router's score / bias / groups, expert_act "
     "silu | relu | relu2 (relu2: UNGATED experts, no gate plane), "
     "expert_latent = routed experts in a latent behind one shared down- "
     "and up-projection; draft_block = a "
     "drafting (multi-token-prediction) block behind the stack "
     "(draft_planes / draft_spec / pool_layers: its K/V is one more "
     "full-attention layer of the pools; GenerationEngine then runs "
     "verify ticks of two positions a slot that emit one or two tokens; "
     "require_no_draft refuses beams, the slot handoff, "
     "share_cache_with=, DisaggEngine.build and a pp mesh); "
     "qk_rope_head_dim 0 = a latent row without a rotary key; index_topk "
     "/ index_heads / index_dim / index_pool = learned sparse attention "
     "inside the mla kind (an indexer over pooled keys in a second, "
     "narrow page pool picks the cached tokens a query attends); "
     "residual add | mhc with hc_mult / hc_iters / hc_eps = the "
     "multi-stream (manifold-constrained) residual round every half "
     "block; ffn_limit = the clamped SwiGLU; index_topk on a stack of "
     "full-attention K/V layers too (sparse_kv: index_pool 1, one indexer "
     "key a token in a third pool); rope mrope with mrope_section = "
     "three-axis rotary ids; qk_norm_heads = RMSNorm a head; vision = a "
     "VisionSpec (a tower and a merger in front of the stack, run inside "
     "the prefill unit; media_layout = where a prompt's clips lie and every "
     "token's three ids)"),
    ("paddle_tpu.ops.moe_ops",
     "The expert layer: moe_topk (dropless top-k; shared=, held=, "
     "routed_scale=; score= softmax | sigmoid, bias=, n_group=, "
     "topk_group=; gate_w None = ungated experts, act= relu2, latent= "
     "the latent's down- and up-projection; limit= the clamped SwiGLU) "
     "and the Switch op"),
    ("paddle_tpu.kernels.flash_attention", "Pallas flash attention"),
    ("paddle_tpu.kernels.paged_attention",
     "Pallas paged attention: walks the block table (one query position "
     "a row, a verify tick's two folded into one walk: "
     "paged_attention_verify, or a prefill chunk's queries over the pages "
     "they reach: paged_attention_prefill, K/V pools or a latent block's "
     "one pool, either under a sparse layer's pick as a group mask)"),
    ("paddle_tpu.kernels.grouped_matmul",
     "Pallas grouped matmul for sorted assignment rows: the expert "
     "layer's products in the serving programs, visiting only the (row "
     "tile, expert) pairs that hold rows; grouped_supported is "
     "moe_topk's dispatch rule"),
    ("paddle_tpu.kernels.kda",
     "Kimi Delta Attention: the gated delta rule with a per-channel decay "
     "token by token, chunked (prefill), and the kda_decode_step Pallas "
     "kernel over the whole slot-state array"),
    ("paddle_tpu.kernels.mamba2",
     "Mamba-2 (state-space duality, one scalar decay a head): the "
     "recurrence token by token, the chunked SSD form (prefill), and the "
     "mamba2_decode_step Pallas kernel over the whole slot-state array"),
    ("paddle_tpu.kernels.sampling",
     "Per-request sampling plane: each decode row's own temperature / "
     "top-k / top-p / seed; cut-offs by a counted search, run only when "
     "a live row asks"),
]


def public_names(mod):
    """(callables, submodules): submodules matter for packages like
    paddle_tpu.dataset whose public API IS its module list."""
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out, mods = [], []
    for n in sorted(set(names)):
        obj = getattr(mod, n, None)
        if obj is None:
            continue
        if inspect.ismodule(obj):
            mods.append((n, obj))
        elif inspect.isfunction(obj) or inspect.isclass(obj):
            out.append((n, obj))
    return out, mods


def first_line(obj):
    doc = inspect.getdoc(obj) or ""
    line = doc.strip().splitlines()[0] if doc.strip() else ""
    return line.rstrip(".")


def signature_of(obj):
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    if len(sig) <= 88:
        return sig
    cut = sig.rfind(",", 0, 85)  # truncate at a parameter boundary
    return sig[:cut if cut > 0 else 85] + ", ...)"


def main():
    lines = [
        "# API reference",
        "",
        "Generated by `tools/gen_api_reference.py` — the public surface,",
        "one line per callable. Regenerate after API changes.",
        "",
    ]
    total = 0
    sections = 0
    for mod_name, blurb in MODULES:
        try:
            mod = importlib.import_module(mod_name)
        except Exception as exc:  # noqa: BLE001
            print(f"skip {mod_name}: {exc!r}", file=sys.stderr)
            continue
        entries, submods = public_names(mod)
        if not entries and not submods:
            continue
        sections += 1
        lines.append(f"## `{mod_name}` — {blurb}")
        lines.append("")
        for n, sub in submods:
            desc = first_line(sub)
            lines.append(f"- `module {n}` — {desc}" if desc
                         else f"- `module {n}`")
            total += 1
        for n, obj in entries:
            kind = "class" if inspect.isclass(obj) else "def"
            desc = first_line(obj)
            sig = signature_of(obj) if kind == "def" else ""
            lines.append(f"- `{kind} {n}{sig}` — {desc}" if desc
                         else f"- `{kind} {n}{sig}`")
            total += 1
        lines.append("")
    lines.append(f"*{total} public names across {sections} modules.*")
    with open(os.path.join(REPO, "API_REFERENCE.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"API_REFERENCE.md: {total} entries")


if __name__ == "__main__":
    main()
