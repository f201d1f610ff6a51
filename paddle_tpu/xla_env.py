"""Helpers for steering which XLA backend a process (or child) uses, and
where its persistent compilation cache lives.

A TPU chip belongs to one process at a time: a parent that has touched JAX
holds it, and a child that needs it then fails or hangs. Multi-device work
without real chips runs on a virtual CPU mesh
(``--xla_force_host_platform_device_count``); a child pinned to that mesh
never asks for the chip, so it is safe to spawn from any parent. These
helpers centralize the env surgery so scripts (__graft_entry__.py) and
tests agree on it.
"""
from __future__ import annotations

import os
from typing import Optional

_FORCE_FLAG = "--xla_force_host_platform_device_count"

#: JAX reads this itself at import; when set it is the ONLY cache
#: directory and nothing in this package overrides it.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Where a TPU process caches when the environment names no directory:
#: one fixed path inside the checkout (the path is part of the cache key's
#: story — a directory that moves never hits).
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def compilation_cache_dir(platform: Optional[str] = None) -> Optional[str]:
    """The persistent-compilation-cache directory for this process, or
    None for in-memory only. One resolution every entry point shares:
    ``$JAX_COMPILATION_CACHE_DIR`` wins; else ``--compilation_cache_dir``;
    else ``<repo>/.jax_cache`` when ``platform`` is ``"tpu"``; else None
    (CPU runs stay in-memory unless asked). ``platform`` defaults to
    ``jax.default_backend()``, which initialises the backend — callers
    that must not do that pass it explicitly."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    from .flags import FLAGS

    if FLAGS.compilation_cache_dir:
        return FLAGS.compilation_cache_dir
    if platform is None:
        import jax

        platform = jax.default_backend()
    return REPO_CACHE_DIR if platform == "tpu" else None


def strip_host_device_flag(flags: str) -> str:
    """Remove any existing host-device-count flag (either '--flag=value' or
    '--flag value' spelling) from an XLA_FLAGS string."""
    toks = flags.split()
    kept, skip_next = [], False
    for i, t in enumerate(toks):
        if skip_next:
            skip_next = False
            continue
        if t.startswith(_FORCE_FLAG):
            # '--flag value' spelling: the bare flag followed by an integer
            if t == _FORCE_FLAG and i + 1 < len(toks) and toks[i + 1].isdigit():
                skip_next = True
            continue
        kept.append(t)
    return " ".join(kept)


def cpu_mesh_env(base_env: dict, n_devices: int) -> dict:
    """Child-process env for an n-device virtual CPU mesh."""
    env = dict(base_env)
    flags = strip_host_device_flag(env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + f" {_FORCE_FLAG}={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def tpu_env(base_env: dict) -> dict:
    """Child-process env cleaned for real-TPU use: drop any CPU pin or
    virtual-device-count leakage so JAX picks the chip. Only a parent
    that is itself pinned to CPU may spawn such a child."""
    env = dict(base_env)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = strip_host_device_flag(env.get("XLA_FLAGS", ""))
    return env


def claim_cpu_mesh(n_devices: int) -> None:
    """Commit THIS process's (not-yet-initialized) JAX backend to an
    n-device virtual CPU mesh. Must run before any backend initialization;
    sets both the env vars and the live config (the env var alone is
    ignored once jax is imported)."""
    os.environ.update(
        {k: v for k, v in cpu_mesh_env(os.environ, n_devices).items()
         if k in ("XLA_FLAGS", "JAX_PLATFORMS")})
    import jax

    jax.config.update("jax_platforms", "cpu")


def backend_initialized() -> bool:
    """Whether a JAX backend has already been committed in this process."""
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)
