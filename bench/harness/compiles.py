"""Counts the programs a process compiles or loads, through
``jax.monitoring``: a backend compile, or a program loaded from the
persistent compilation cache, is a program new to the process. The
harness reads the count at the start and the end of the measured window;
any difference is a harness fault (a shape it did not warm up)."""
from __future__ import annotations

import jax

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_count = [0]
_installed = [False]


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        _count[0] += 1


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _count[0] += 1


def install() -> None:
    if not _installed[0]:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _installed[0] = True


def new_programs() -> int:
    """Programs compiled or loaded from the cache since ``install``."""
    return _count[0]
