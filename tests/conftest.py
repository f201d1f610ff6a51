"""Test configuration: run on a virtual 8-device CPU mesh.

Real multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's host platform with 8 virtual devices (the same GSPMD
partitioner TPUs use). This mirrors the reference's strategy of testing its
distributed paths in one process on localhost
(/root/reference/paddle/pserver/test/test_ParameterServer2.cpp:555-560).
"""
import os

# Force, not setdefault: whatever the ambient JAX_PLATFORMS says (a TPU
# host leaves it unset and jax picks the chip), unit tests run on the
# virtual CPU mesh — so this process never holds a chip, and the one test
# that needs one (test_tpu_tier.py) can hand it to a single child.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

# pytest plugins (jaxtyping) import jax before this conftest runs, so the env
# var alone can come too late — update the live config as well (backends
# initialise lazily, so this still takes effect).
import jax

jax.config.update("jax_platforms", "cpu")

# Persistent-cache note: with JAX_COMPILATION_CACHE_DIR unset a CPU process
# keeps its compilations in memory (xla_env.compilation_cache_dir), so the
# suite's wall does not depend on a disk cache. Opting in is safe:
# tests/test_cold_start.py pins that donating executables restored from
# disk are bit-exact on the installed jaxlib.

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def fresh_programs():
    """Give every test fresh default programs and a fresh global scope."""
    import paddle_tpu as pt
    from paddle_tpu.core import program as prog_mod
    from paddle_tpu.core import scope as scope_mod

    prog_mod._main_program = prog_mod.Program()
    prog_mod._startup_program = prog_mod.Program()
    scope_mod._global_scope = scope_mod.Scope()
    scope_mod._scope_stack[:] = [scope_mod._global_scope]
    np.random.seed(0)
    # flags leak across tests otherwise (e.g. paddle.v2.init(seed=...) sets
    # FLAGS.seed, changing a LATER test's parameter init and its
    # convergence) — every test starts from registered defaults
    pt.flags.reset_flags()
    # set_amp / set_mxu_precision PIN the policy over the flag (the
    # tri-state in ops/common.py): a test that ends with set_amp(False)
    # would make a later test on the same worker blind to --use_amp.
    # Which test runs before which is the scheduler's choice
    # (--dist loadfile), so every test starts AND ends unpinned. Handing
    # back what was found is not enough: pytest builds a module-scoped
    # fixture before this one, so a pin made there (test_kda_parity's
    # ``served``, test_kda_gqa_parity's ``twice``) was "found", handed
    # back after every test of that file and carried into the next file.
    from paddle_tpu.ops import common

    common._AMP = common._MXU_PRECISION = common._UNSET
    yield
    common._AMP = common._MXU_PRECISION = common._UNSET


@pytest.fixture
def pallas_path(monkeypatch):
    """``flash_attention``'s TPU branch on the CPU: the backend reads
    "tpu" and the three kernels run in interpret mode. -> the inner calls
    made, as (pass, operand dtypes, preferred blocks)."""
    from paddle_tpu.kernels import flash_attention as fa

    calls = []
    forward, backward = fa._flash_forward, fa._flash_backward

    def fwd(q, k, v, lengths, causal, sm_scale, block_q, block_k,
            interpret, **kw):
        calls.append(("fwd", {a.dtype for a in (q, k, v)},
                      (block_q, block_k)))
        return forward(q, k, v, lengths, causal, sm_scale, block_q, block_k,
                       interpret=True, **kw)

    def bwd(q, k, v, o, lse, lengths, g, causal, sm_scale, block_q,
            block_k, interpret, **kw):
        calls.append(("bwd", {a.dtype for a in (q, k, v, o, g)},
                      (block_q, block_k)))
        return backward(q, k, v, o, lse, lengths, g, causal, sm_scale,
                        block_q, block_k, interpret=True, **kw)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_flash_forward", fwd)
    monkeypatch.setattr(fa, "_flash_backward", bwd)
    return calls


# ---------------------------------------------------------------------------
# Shared virtual-mesh fixtures: ONE mesh object per session instead of a
# per-test rebuild — sharding tests that only need "the 8 CPU devices,
# named" share these (and skip with a known reason when the virtual
# device plane is absent, e.g. under a real single-chip backend).
# ---------------------------------------------------------------------------

def _mesh_or_skip(axes):
    import jax

    from paddle_tpu.parallel import make_mesh

    need = 1
    for s in axes.values():
        need *= s
    if len(jax.devices()) < need:
        pytest.skip("needs 8 virtual devices")
    return make_mesh(axes, devices=jax.devices()[:need])


@pytest.fixture(scope="session")
def cpu_mesh8():
    """The full 8-device data-parallel mesh: {'dp': 8}."""
    return _mesh_or_skip({"dp": 8})


@pytest.fixture(scope="session")
def cpu_mesh_dp_mp():
    """The hybrid dp x tp mesh: {'dp': 4, 'mp': 2}."""
    return _mesh_or_skip({"dp": 4, "mp": 2})


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: real-chip tier (runs in a child process owning "
        "the TPU; skips when no chip is reachable)")
    config.addinivalue_line(
        "markers", "slow: long-running sweeps excluded from tier-1 "
        "(crash matrix, chaos drills); run with -m slow")


# ---------------------------------------------------------------------------
# Skip visibility + budget: every skip must carry a KNOWN reason; the
# summary lists them; an unrecognized skip reason fails the session (so a
# typo'd marker or an accidentally-skipped test cannot hide in the log).
# ---------------------------------------------------------------------------

KNOWN_SKIP_REASONS = (
    "no TPU reachable",          # test_tpu_tier child-process tier
    "reference tree not present",  # as-is reference config tests
    "no C++ toolchain",          # capi / native builds
    "xprof converter unavailable",
    "needs 4 virtual devices",
    "needs 8 virtual devices",   # the shared cpu_mesh fixtures below
    # two-process DCN tests: the compiler itself rejects multi-process
    # CPU computations on this jaxlib line — true multi-process required
    "true multi-process unsupported on this jaxlib CPU backend",
)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    skipped = terminalreporter.stats.get("skipped", [])
    if not skipped:
        return
    tw = terminalreporter
    reasons = {}
    for rep in skipped:
        reason = rep.longrepr[2] if isinstance(rep.longrepr, tuple) \
            else str(rep.longrepr)
        reason = reason.replace("Skipped: ", "")
        reasons.setdefault(reason, []).append(rep.nodeid)
    tw.write_sep("-", "skip report")
    unknown = []
    for reason, nodes in sorted(reasons.items()):
        known = any(k in reason for k in KNOWN_SKIP_REASONS)
        tw.write_line(f"{'  ' if known else '! UNKNOWN '}"
                      f"{len(nodes):3d} x {reason}")
        if not known:
            unknown.extend(nodes)
    if unknown:
        tw.write_line(
            f"! {len(unknown)} test(s) skipped for reasons outside "
            f"KNOWN_SKIP_REASONS (tests/conftest.py) — add the reason "
            f"there or unskip:")
        for n in unknown:
            tw.write_line(f"!   {n}")
        config._unknown_skips = unknown


def pytest_sessionfinish(session, exitstatus):
    if getattr(session.config, "_unknown_skips", None) and exitstatus == 0:
        session.exitstatus = 1
