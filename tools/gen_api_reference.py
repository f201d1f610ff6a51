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
    ("paddle_tpu.serving", "Model server: dynamic & continuous batching"),
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
     "held share of the router's experts"),
    ("paddle_tpu.ops.moe_ops",
     "The expert layer: moe_topk (dropless top-k; shared=, held=, "
     "routed_scale=) and the Switch op"),
    ("paddle_tpu.kernels.flash_attention", "Pallas flash attention"),
    ("paddle_tpu.kernels.paged_attention",
     "Pallas paged decode attention: walks the block table"),
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
