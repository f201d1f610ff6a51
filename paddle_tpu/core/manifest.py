"""Signature manifests — the cold-start plane's compile record.

PERF.md measures first-compile at seconds per signature, and a bucketed
serving engine (or a resumed trainer) needs a dozen signatures before the
first token/step — so a new replica pays tens of seconds of dead time
unless it knows, ahead of traffic, exactly what to compile. The Executor
records every compiled ``(program digest, feed signature, fetch set)``
into a :class:`SignatureManifest`; engines and ``SGD.train`` persist it
next to the saved model / checkpoint as ``warmup_manifest.json``; a boot
replays it with :func:`replay` — AOT ``.lower().compile()`` of every
signature, concurrently (compilation is host-side work and releases the
GIL), WITHOUT executing anything. Combined with ``--compilation_cache_dir``
the replayed compiles are disk restores, and the first request/step after
replay is a pure in-process cache hit: zero fresh compiles.

The schema is versioned; an unknown version is rejected with an error
naming the file, so a manifest written by a future build degrades loudly
into execute-based warmup instead of silently half-warming a replica.
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

MANIFEST_VERSION = 1
MANIFEST_NAME = "warmup_manifest.json"
MANIFEST_SCHEMA = "paddle_tpu/warmup_manifest"

__all__ = ["ManifestError", "SignatureManifest", "program_digest",
           "load", "try_load", "replay", "MANIFEST_NAME",
           "MANIFEST_VERSION"]


class ManifestError(ValueError):
    """A manifest file that cannot be trusted: wrong schema/version or a
    malformed signature record. The message names the file."""


def program_digest(program) -> str:
    """Stable cross-process digest of a program's structure (the
    ``program_to_dict`` JSON) — how a manifest signature finds the right
    program on the next boot. Private op attrs (``_callsite`` etc.) are
    stripped first: they record WHERE the program was built (a warmup CLI
    vs a server boot construct identical programs from different call
    sites) and must not split the digest. Memoized per program version,
    so recording a compile is O(1) in the steady state."""
    cached = getattr(program, "_sig_digest", None)
    if cached is not None and cached[0] == program.version:
        return cached[1]
    from ..io import program_to_dict

    d = program_to_dict(program)
    for block in d.get("blocks", []):
        for op in block.get("ops", []):
            attrs = op.get("attrs")
            if attrs and any(k.startswith("_") for k in attrs):
                op["attrs"] = {k: v for k, v in attrs.items()
                               if not k.startswith("_")}
    blob = json.dumps(d, sort_keys=True, separators=(",", ":"), default=str)
    digest = hashlib.sha1(blob.encode()).hexdigest()[:16]
    try:
        program._sig_digest = (program.version, digest)
    except AttributeError:  # exotic program-like objects: skip the memo
        pass
    return digest


def _norm_feeds(feeds) -> tuple:
    """Feeds as a canonical sorted tuple of (name, shape, dtype)."""
    out = []
    for name, shape, dtype in feeds:
        out.append((str(name), tuple(int(d) for d in shape), str(dtype)))
    return tuple(sorted(out))


class SignatureManifest:
    """A deduplicated, thread-safe set of compiled signatures."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sigs: Dict[tuple, dict] = {}

    def record(self, digest: str, feeds, fetches: Sequence[str]) -> bool:
        """Record one compiled signature; returns True when new.
        ``feeds`` is an iterable of (name, shape, dtype)."""
        feeds_t = _norm_feeds(feeds)
        key = (digest, feeds_t, tuple(str(f) for f in fetches))
        with self._lock:
            if key in self._sigs:
                return False
            self._sigs[key] = {
                "program": str(digest),
                "feeds": [[n, list(s), dt] for n, s, dt in feeds_t],
                "fetches": [str(f) for f in fetches],
            }
            return True

    def signatures(self) -> List[dict]:
        with self._lock:
            return list(self._sigs.values())

    def merge(self, other: "SignatureManifest") -> int:
        """Absorb another manifest's signatures; returns how many were
        new."""
        added = 0
        for sig in other.signatures():
            if self.record(sig["program"],
                           [tuple(f) for f in sig["feeds"]],
                           sig["fetches"]):
                added += 1
        return added

    def __len__(self) -> int:
        with self._lock:
            return len(self._sigs)

    # -- (de)serialisation -------------------------------------------------
    def to_dict(self) -> dict:
        return {"schema": MANIFEST_SCHEMA, "version": MANIFEST_VERSION,
                "signatures": self.signatures()}

    @classmethod
    def from_dict(cls, d: dict, where: str = "<manifest>") -> "SignatureManifest":
        version = d.get("version")
        if version != MANIFEST_VERSION:
            raise ManifestError(
                f"{where}: unsupported warmup-manifest version {version!r} "
                f"(this build reads version {MANIFEST_VERSION}); regenerate "
                f"it with tools/warmup.py or delete the file to fall back "
                f"to execute-based warmup")
        m = cls()
        for i, sig in enumerate(d.get("signatures", [])):
            try:
                m.record(sig["program"],
                         [tuple(f) for f in sig["feeds"]], sig["fetches"])
            except (KeyError, TypeError, ValueError) as exc:
                raise ManifestError(
                    f"{where}: malformed signature #{i}: {exc}") from exc
        return m

    def save(self, dirname: str, name: str = MANIFEST_NAME,
             merge: bool = True) -> str:
        """Atomically write this manifest into ``dirname`` (next to the
        saved model / checkpoints). With ``merge`` (default) an existing
        readable manifest's signatures are folded in first, so incremental
        warmups (a second bucket set, a later trainer run) accumulate."""
        os.makedirs(dirname, exist_ok=True)
        path = os.path.join(dirname, name)
        out = SignatureManifest()
        out.merge(self)
        if merge and os.path.exists(path):
            try:
                out.merge(load(dirname, name))
            except (ManifestError, OSError, json.JSONDecodeError):
                pass  # unreadable/foreign file: overwrite with ours
        tmp = path + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(out.to_dict(), f, indent=1)
        os.replace(tmp, path)
        return path


def load(dirname: str, name: str = MANIFEST_NAME) -> SignatureManifest:
    """Read ``dirname/warmup_manifest.json``; raises FileNotFoundError
    when absent and :class:`ManifestError` (naming the path) when the
    version/schema is not one this build reads."""
    path = os.path.join(dirname, name)
    with open(path) as f:
        try:
            d = json.load(f)
        except json.JSONDecodeError as exc:
            raise ManifestError(f"{path}: not valid JSON: {exc}") from exc
    return SignatureManifest.from_dict(d, where=path)


def try_load(dirname: str,
             name: str = MANIFEST_NAME) -> Optional[SignatureManifest]:
    """:func:`load`, but an absent file returns None (the no-manifest
    boot path). Version/schema problems still raise — they must be loud."""
    try:
        return load(dirname, name)
    except FileNotFoundError:
        return None


def replay(executor, programs, scope=None, manifest=None,
           dirname: Optional[str] = None,
           max_workers: Optional[int] = None) -> dict:
    """AOT-compile every manifest signature that matches one of
    ``programs`` — ``Executor.warm_signature`` per record, fanned out over
    a thread pool (XLA compilation releases the GIL, so this is real
    concurrency). Nothing executes; state in ``scope`` is only read for
    shapes. Returns ``{"compiled", "already", "skipped", "seconds"}`` —
    ``skipped`` counts signatures whose program digest matched none of
    ``programs`` (an artifact from a different build: degrade, don't
    die)."""
    if manifest is None:
        if dirname is None:
            raise ValueError("replay needs a manifest or a dirname")
        manifest = load(dirname)
    by_digest = {}
    for p in programs:
        by_digest.setdefault(program_digest(p), p)
    jobs, skipped = [], 0
    for sig in manifest.signatures():
        prog = by_digest.get(sig["program"])
        if prog is None:
            skipped += 1
            continue
        jobs.append((prog, sig))
    if max_workers is None:
        try:
            from ..flags import FLAGS

            max_workers = max(int(FLAGS.warmup_concurrency), 1)
        except Exception:
            max_workers = 4

    def one(job):
        prog, sig = job
        feeds = {n: (tuple(s), dt) for n, s, dt in
                 (tuple(f) for f in sig["feeds"])}
        return executor.warm_signature(prog, feeds, sig["fetches"],
                                       scope=scope)

    t0 = time.perf_counter()
    if len(jobs) > 1 and max_workers > 1:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(
                max_workers=min(max_workers, len(jobs)),
                thread_name_prefix="paddle-tpu-warm") as pool:
            results = list(pool.map(one, jobs))
    else:
        results = [one(j) for j in jobs]
    compiled = sum(1 for r in results if r)
    return {"compiled": compiled, "already": len(jobs) - compiled,
            "skipped": skipped,
            "seconds": round(time.perf_counter() - t0, 6)}
