"""paddle_tpu: a TPU-native deep-learning framework.

A from-scratch rebuild of PaddlePaddle's (~v0.11) capability set —
ProgramDesc-style graph capture, an op zoo with automatic backward,
optimizers-as-ops, feed/fetch execution, readers/datasets, checkpointing,
distributed data-parallel training — re-architected for JAX/XLA on TPU:
whole program blocks compile to single XLA computations, gradients come from
jax.vjp, and every distributed path is in-graph collectives over ICI/DCN
instead of parameter servers. See SURVEY.md at the repo root for the full
mapping onto the reference.
"""
from . import (analysis, checkpoint, clip, decoding, evaluator, event,
               initializer,
               layers, learning_rate_decay, master, models, nets, online,
               optimizer, parallel, profiler, regularizer, resilience,
               serving, trace, trainer, transpiler)
from . import flags
from .lm_spec import LMSpec
from .checkgrad import check_gradients
from .core.enforce import (EnforceError, enforce, enforce_eq, enforce_ge,
                           enforce_gt, enforce_le, enforce_lt, enforce_ne,
                           enforce_not_none)
from .flags import FLAGS, parse_flags, set_flags
from .data_feeder import DataFeeder
from .core import (CPUPlace, Executor, Program, RunHandle, Scope, TPUPlace,
                   recompute_guard,
                   default_main_program, default_startup_program, global_scope,
                   program_guard)
from .core.backward import append_backward
from .core.selected_rows import SelectedRows
from .param_attr import ParamAttr
from .ops.common import amp_enabled, set_amp, set_mxu_precision

# ops must be imported so kernels register before any program runs
from . import ops as _ops  # noqa: F401

__version__ = "0.1.0"
