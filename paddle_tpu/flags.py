"""Runtime flag registry — the gflags plane of the reference.

The reference centralizes runtime knobs as gflags
(/root/reference/paddle/utils/Flags.h:19-44: --use_gpu, --trainer_count,
--port, --log_period, ...; per-file DEFINE_* like executor.cc:25
--check_nan_inf), parsed in initMain / framework::InitGflags. The TPU-native
equivalent keeps the same three entry points:

- ``define_*`` at module scope registers a typed flag with a default;
- environment overrides: ``PADDLE_TPU_<NAME>`` is read at definition time
  (the cluster-launcher path — the reference reads gflags' FLAGS_* env);
- ``parse_flags(argv)`` consumes ``--name=value`` / ``--name value`` /
  ``--noname`` tokens (script path), returning unrecognized tokens.

Access is via the ``FLAGS`` namespace: ``flags.FLAGS.check_nan_inf``.
Components read their defaults from FLAGS so a flag flip affects every
instance created afterwards (constructor args still win).
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Optional

_ENV_PREFIX = "PADDLE_TPU_"


class FlagError(ValueError):
    pass


class _Flag:
    __slots__ = ("name", "default", "value", "help", "parser", "type_name")

    def __init__(self, name, default, help_str, parser, type_name):
        self.name = name
        self.default = default
        self.help = help_str
        self.parser = parser
        self.type_name = type_name
        self.value = default


class _Namespace:
    """Attribute view over the registry (gflags' FLAGS object)."""

    def __getattr__(self, name: str) -> Any:
        try:
            return _registry[name].value
        except KeyError:
            raise AttributeError(f"unknown flag {name!r}; defined flags: "
                                 f"{sorted(_registry)}") from None

    def __setattr__(self, name: str, value: Any) -> None:
        flag = _registry.get(name)
        if flag is None:
            raise FlagError(f"unknown flag {name!r}")
        flag.value = flag.parser(value)


_registry: Dict[str, _Flag] = {}
FLAGS = _Namespace()


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off"):
        return False
    raise FlagError(f"not a boolean: {v!r}")


def _define(name: str, default: Any, help_str: str,
            parser: Callable[[Any], Any], type_name: str) -> None:
    if name in _registry:
        raise FlagError(f"flag {name!r} already defined")
    flag = _Flag(name, default, help_str, parser, type_name)
    env = os.environ.get(_ENV_PREFIX + name.upper())
    if env is not None:
        flag.value = parser(env)
    _registry[name] = flag


def define_bool(name, default, help_str=""):
    _define(name, default, help_str, _parse_bool, "bool")


def define_int32(name, default, help_str=""):
    _define(name, default, help_str, lambda v: int(str(v), 0), "int32")


def define_float(name, default, help_str=""):
    _define(name, default, help_str, float, "float")


def define_string(name, default, help_str=""):
    _define(name, default, help_str, str, "string")


def get_flag(name: str) -> Any:
    return getattr(FLAGS, name)


def set_flags(values: Dict[str, Any]) -> None:
    """Bulk set, fluid's paddle.set_flags analogue."""
    for k, v in values.items():
        setattr(FLAGS, k, v)


def flags_registered() -> List[str]:
    return sorted(_registry)


def reset_flags() -> None:
    """Restore every flag to its registered default (tests)."""
    for flag in _registry.values():
        flag.value = flag.default


def parse_flags(argv: List[str]) -> List[str]:
    """Consume --name=value / --name value / --noname tokens from argv;
    returns the tokens that are not recognized flags (positional args and
    foreign options), matching gflags' remove_flags behaviour."""
    rest: List[str] = []
    i = 0
    while i < len(argv):
        tok = argv[i]
        if not tok.startswith("--"):
            rest.append(tok)
            i += 1
            continue
        body = tok[2:]
        name, eq, val = body.partition("=")
        if name in _registry:
            flag = _registry[name]
            if eq:
                flag.value = flag.parser(val)
            elif flag.type_name == "bool":
                flag.value = True
            elif i + 1 < len(argv):
                flag.value = flag.parser(argv[i + 1])
                i += 1
            else:
                raise FlagError(f"flag --{name} expects a value")
        elif name.startswith("no") and name[2:] in _registry \
                and _registry[name[2:]].type_name == "bool" and not eq:
            _registry[name[2:]].value = False
        else:
            rest.append(tok)
        i += 1
    return rest


def print_flags() -> str:
    lines = []
    for name in sorted(_registry):
        f = _registry[name]
        mark = "" if f.value == f.default else "  (set)"
        lines.append(f"--{name}={f.value!r}  [{f.type_name}] {f.help}{mark}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Core flags (the load-bearing subset of Flags.h:19-44 + per-file DEFINEs,
# translated to what exists on TPU).
# ---------------------------------------------------------------------------
define_bool("check_nan_inf", False,
            "scan fetched outputs and updated state for NaN/Inf each run "
            "(executor.cc:25 --check_nan_inf)")
define_bool("use_amp", False,
            "default bf16-compute/f32-master mixed precision for new "
            "programs (TPU analogue of the float16 plane)")
define_string("mxu_precision", "default",
              "MXU contraction precision: default | high | highest")
define_bool("fused_conv_epilogue", False,
            "lower NHWC 1x1/stride-1 conv+BN(+relu)(+residual) chains in "
            "models as the fused conv1x1_bn_act op (Pallas forward that "
            "computes BN stats in the conv pass and folds the epilogue "
            "into the output tile; ops/fusion_ops.py). Default off until "
            "the chip A/B lands (ROADMAP S6/D6). On a TPU a layer whose "
            "shape has no VMEM tile raises instead of running unfused")
define_string("compilation_cache_dir", "",
              "persist XLA compilations here (jax persistent cache): "
              "repeat runs of the same program skip the 20-40s "
              "first-compile. $JAX_COMPILATION_CACHE_DIR, when set, wins "
              "over this flag; with neither, a TPU process caches in "
              "<repo>/.jax_cache and a CPU process stays in-memory "
              "(xla_env.compilation_cache_dir). Pair with a warmup "
              "manifest (core.manifest / tools/warmup.py) for "
              "zero-fresh-compile boots")
define_int32("warmup_concurrency", 4,
             "thread-pool width for AOT manifest replay "
             "(core.manifest.replay): XLA compilation is host-side and "
             "releases the GIL, so boot-time signature compiles overlap")
define_int32("seed", 0,
             "global graph RNG seed used when a program sets no "
             "random_seed of its own (ThreadLocalRand analogue); runs "
             "are deterministic for a fixed seed")
define_int32("log_period", 100,
             "default trainer log cadence in batches (Flags.h --log_period)")
define_bool("op_callsite", True,
            "record user file:line on every appended op for error "
            "reports (CustomStackTrace analogue); disable to shave "
            "graph-build time")
define_int32("trace_level", 0,
             "span-tracing level seeding trace.get_tracer() at import: "
             "0 off, 1 executor/serving/trainer spans, 2 additionally "
             "per-op interpret-mode debug runs (Executor.run walks the "
             "block op-by-op, locating NaN/Inf producers). Runtime flips "
             "go through trace.enable(level)")
define_bool("verify_program", False,
            "run the paddle_tpu.analysis program verifier + whole-program "
            "shape/dtype checker around every transpiler pass "
            "(PassManager verify_each — the pass that breaks a program "
            "is named), and on the programs the trainer, "
            "save_inference_model, and the serving engines are about to "
            "compile. Build-time cost only; on in CI")
define_bool("reduce_peak_memory", False,
            "append the memory-aware op-scheduling pass "
            "(transpiler.ReducePeakMemory) to the inference/deployment "
            "pipelines: topologically reorders ops to shrink the static "
            "peak-HBM watermark (bit-exact outputs; analysis.memory "
            "computes the watermark)")
define_string("fault_plan", "",
              "deterministic chaos plan for manual resilience drills, "
              "e.g. 'preempt@5,torn_checkpoint@3': kind@step entries "
              "(resilience/faults.py FAULT_KINDS) injected once each "
              "into the next SGD.train run; empty = no injection")
define_float("trace_sample_rate", 1.0,
             "fraction of trace roots kept by the span tracer "
             "(deterministic counter-based sampling, no RNG)")
define_int32("trace_buffer", 16384,
             "span ring-buffer capacity; oldest completed spans fall "
             "off — bounds tracing memory on long-lived servers")
