"""Real-chip test tier: launches tests/tpu_tier.py in a child process that
owns the TPU, and reports each chip-side check as a pytest test. Skips
cleanly when no TPU is reachable.

A chip belongs to one process at a time. This is sound only because the
suite process itself is pinned to the virtual CPU mesh (conftest.py) and so
never holds the chip: a short probe child looks for one and exits, then all
chip work happens in exactly one child, launched at most once per pytest
session. On the chip tool the same checks run directly:
``python tests/tpu_tier.py``.
"""
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.xla_env import tpu_env

_HERE = os.path.dirname(os.path.abspath(__file__))
_PROBE_TIMEOUT_S = 60   # backend discovery, with or without a chip
_TIER_TIMEOUT_S = 1800  # every check pays its first compile

# Chip-side check names, derived from tpu_tier.py's CHECKS registry by a
# jax-free file load (its top-level imports are stdlib+numpy only) so
# pytest can enumerate tests without initialising a backend — and the
# list can never drift from the registry.
def _load_check_names():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpu_tier_for_names", os.path.join(_HERE, "tpu_tier.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return [f.__name__ for f in mod.CHECKS]


CHECK_NAMES = _load_check_names()

_results = None


def _tpu_available():
    if os.environ.get("PADDLE_TPU_SKIP_TPU_TIER"):
        return False
    probe = ("import jax, sys; d = jax.devices()[0]; "
             "sys.exit(0 if d.platform == 'tpu' else 3)")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", probe], env=tpu_env(os.environ),
            capture_output=True, timeout=_PROBE_TIMEOUT_S)
        return proc.returncode == 0
    except (subprocess.TimeoutExpired, OSError):
        return False


def _run_tier():
    global _results
    if _results is not None:
        return _results
    if not _tpu_available():
        _results = {}
        return _results
    proc = subprocess.run(
        [sys.executable, os.path.join(_HERE, "tpu_tier.py")],
        env=tpu_env(os.environ), cwd=os.path.dirname(_HERE),
        capture_output=True, text=True, timeout=_TIER_TIMEOUT_S)
    results = {}
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            try:
                rec = json.loads(line)
                results[rec["check"]] = rec
            except (json.JSONDecodeError, KeyError):
                pass
    if not results:
        tail = (proc.stderr or "").strip().splitlines()[-5:]
        results["__launch__"] = {"ok": False, "detail": " | ".join(tail)}
    _results = results
    return _results


@pytest.mark.tpu
@pytest.mark.parametrize("name", CHECK_NAMES)
def test_tpu_tier(name):
    results = _run_tier()
    if not results:
        pytest.skip("no TPU reachable (or PADDLE_TPU_SKIP_TPU_TIER set)")
    if "__launch__" in results:
        pytest.fail(f"tier child failed: {results['__launch__']['detail']}")
    rec = results.get(name)
    assert rec is not None, f"check {name!r} produced no result"
    assert rec["ok"], rec["detail"]
