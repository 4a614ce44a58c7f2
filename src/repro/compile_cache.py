"""Persistent XLA compile cache for the repository's entry points.

Every script that compiles (``chip_smoke.py``, ``benchmarks/*.py``,
``examples/*.py``) calls :func:`enable` before its first compile, so a
second run of the same programs loads them instead of compiling again.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (and no
other), else ``.jax_cache`` at the root of this checkout. It is a fixed
path on purpose: a per-run temporary directory would never be hit.
Every compiled program is cached, including those JAX's default skips
for compiling in under a second: a chip run compiles many such programs,
and with the default a second run compiles them all again.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's own monitoring events: a program loaded from the cache, and a
# compiled program written to it
_EVENTS = {"/jax/compilation_cache/cache_hits": "loaded",
           "/jax/compilation_cache/cache_misses": "written"}
_counts = {"loaded": 0, "written": 0}
_listening = False


def _count(event: str, **_kw) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        _counts[name] += 1


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and
    start counting its hits; returns the directory. Call before the
    first compile of the process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    return path


def stats() -> dict:
    """Programs ``loaded`` from and ``written`` to the cache since
    :func:`enable` (programs too quick to compile to be cached count in
    neither)."""
    return dict(_counts)
