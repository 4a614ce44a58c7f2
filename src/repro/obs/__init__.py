"""Runtime observability for the DIALS runtime.

``Telemetry`` is the facade the drivers and the multi-host stack talk
to: it owns a span :class:`~repro.obs.trace.Tracer` (``tracer``) and a
per-process JSONL sink (``telemetry-p{PID}.jsonl`` in a shared
directory — the ``fault.HostMonitor`` heartbeat-dir pattern). Every
emitted event gets an envelope — ``event`` kind, ``proc``, per-process
monotone ``seq``, unix ``t`` — so rank 0 can merge all processes' files
into one globally ordered ``telemetry.jsonl``
(:func:`repro.obs.sinks.merge_dir`).

The disabled instance is :data:`DISABLED` (also what
:func:`maybe` returns for a ``None`` directory): ``emit`` is a no-op,
its ``tracer`` is :data:`~repro.obs.trace.NULL_TRACER` (spans that are
bare profiler annotations), and **no files are created** — the drivers
keep their telemetry calls unconditionally and pay nothing when it is
off. Crucially, telemetry is host-side only: enabling it never changes
the traced round program, so the sharded driver's round sync contract
is untouched (the on-mesh scalars it reports — staleness stats, CE —
ride the round record the driver already fetches).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax

from repro.obs import metrics, sinks
from repro.obs.trace import (NULL_TRACER, NullTracer, Tracer, annotate,
                             profile)

__all__ = ["Telemetry", "DISABLED", "maybe", "Tracer", "NullTracer",
           "NULL_TRACER", "annotate", "profile", "metrics", "sinks"]


def _default_process_id() -> int:
    return jax.process_index()


class Telemetry:
    """Live telemetry: span tracer + per-process event sinks."""

    enabled = True

    def __init__(self, directory: str, *, process_id: int,
                 tracer: Tracer):
        self.directory = directory
        self.process_id = process_id
        self.tracer = tracer
        self._seq = 0
        self._sinks: List = [sinks.JsonlSink(
            sinks.proc_path(directory, process_id))]

    @classmethod
    def create(cls, directory: str, *, process_id: Optional[int] = None,
               fence: bool = False) -> "Telemetry":
        import os
        os.makedirs(directory, exist_ok=True)
        pid = process_id if process_id is not None \
            else _default_process_id()
        return cls(directory, process_id=pid, tracer=Tracer(fenced=fence))

    def emit(self, event: str, **fields) -> Dict:
        """Wrap ``fields`` in the envelope and write to every sink."""
        rec = {"event": event, "proc": self.process_id, "seq": self._seq,
               "t": time.time(), **fields}
        self._seq += 1
        for s in self._sinks:
            s.write(rec)
        return rec

    def emit_round(self, rec: Dict) -> Dict:
        """Emit a (already :func:`metrics.round_record`-typed) round
        record as a ``"round"`` event."""
        return self.emit("round", **rec)

    def merge(self) -> str:
        """Merge every process's event file in this directory (call on
        rank 0, after the run)."""
        return sinks.merge_dir(self.directory)

    def close(self) -> None:
        for s in self._sinks:
            s.close()


class _NullTelemetry:
    """Disabled telemetry: no files, no state, no-op everything."""

    enabled = False
    directory = None
    process_id = 0
    tracer = NULL_TRACER

    def emit(self, event: str, **fields) -> None:
        return None

    def emit_round(self, rec: Dict) -> None:
        return None

    def merge(self) -> None:
        return None

    def close(self) -> None:
        pass


DISABLED = _NullTelemetry()


def maybe(directory: Optional[str], **kwargs):
    """`Telemetry.create(directory, ...)` when ``directory`` is set,
    :data:`DISABLED` otherwise — the one-liner the drivers use to honor
    an optional ``telemetry_dir`` config field."""
    if not directory:
        return DISABLED
    return Telemetry.create(directory, **kwargs)
