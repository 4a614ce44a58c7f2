"""Span tracer for the DIALS runtime.

Three layers of the same idea — "name the time", at three costs:

* **Host spans** (:meth:`Tracer.span`) — nested spans, each entering a
  ``jax.profiler.TraceAnnotation`` of its name whether or not telemetry
  is on, so every span lands on the profiler's host plane, on the same
  clock as the device's ``XLA Modules`` / ``XLA Ops`` events, whenever
  a profiler session is live. With telemetry on, :class:`Tracer` also
  records ``(name, depth, t0, dur_s)`` on a monotonic clock
  (``time.perf_counter``); :meth:`Tracer.phase_seconds` aggregates them
  into the per-phase seconds the typed round record
  (``repro.obs.metrics``) carries. JAX dispatch is asynchronous, so an
  unfenced span around a jitted call measures *enqueue* time; a tracer
  built with ``fenced=True`` makes :meth:`Tracer.fence` call
  ``jax.block_until_ready`` — honest device timings, at the cost of a
  host sync per fence. The drivers default to unfenced.
* **Host syncs** (:meth:`Tracer.pull`) — every blocking device-to-host
  read of the drivers goes through ``pull(name, value)``, which performs
  the read inside a span ``dials.sync.<name>`` (:data:`SYNC`). One such
  span is one host sync: the profiler counts them, and
  :meth:`Tracer.sync_seconds` sums their host seconds for the round
  record's ``sync_s``.
* **Trace-time annotations** (:func:`annotate`) — ``jax.named_scope``
  pass-through for code *inside* jitted programs (the per-shard train
  body, the halo exchange). Zero runtime cost: the scope names travel
  into HLO metadata so the regions are attributable in an XLA profile.

:func:`profile` opens a ``jax.profiler`` session around a block.

The disabled path is :data:`NULL_TRACER`: its :meth:`~NullTracer.span`
returns the bare ``TraceAnnotation`` — nothing is recorded and the
tracer keeps no per-span state of its own; outside a profiler session
a span costs about half a microsecond of host time (a TPU v5e host).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax

SYNC = "dials.sync."
"""Name prefix of the spans around the drivers' device-to-host reads."""


def annotate(name: str):
    """Trace-time scope naming for jitted code: ``jax.named_scope``.
    Adds HLO metadata only — never a primitive, so the collective
    audits of ``repro.distributed.runtime`` see identical programs."""
    return jax.named_scope(name)


@contextlib.contextmanager
def profile(directory: Optional[str]):
    """Opt-in XLA profiler session writing to ``directory`` (TensorBoard
    / xprof format). ``None`` is a no-op, so call sites can thread the
    ``--profile-dir`` flag through unconditionally."""
    if not directory:
        yield
        return
    jax.profiler.start_trace(directory)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Tracer:
    """Records nested host spans; see module docstring."""

    def __init__(self, *, fenced: bool = False, clock=time.perf_counter):
        self.fenced = bool(fenced)
        self._clock = clock
        self._depth = 0
        self.events: List[Dict] = []

    @property
    def enabled(self) -> bool:
        return True

    @contextlib.contextmanager
    def span(self, name: str):
        depth, self._depth = self._depth, self._depth + 1
        with jax.profiler.TraceAnnotation(name):
            t0 = self._clock()
            try:
                yield
            finally:
                dur = self._clock() - t0
                self._depth = depth
                # appended at exit: children land before their parent,
                # report/asserts re-nest via (t0, depth)
                self.events.append({"name": name, "depth": depth,
                                    "t0": t0, "dur_s": dur})

    def fence(self, value):
        """``jax.block_until_ready(value)`` on a fenced tracer; returns
        ``value`` either way."""
        if self.fenced:
            jax.block_until_ready(value)
        return value

    def pull(self, name: str, value, cast=float):
        """The host-sync read ``cast(value)`` inside the span
        ``dials.sync.<name>``."""
        with self.span(SYNC + name):
            return cast(value)

    def reset(self) -> None:
        self.events.clear()

    def phase_seconds(self) -> Dict[str, float]:
        """Total seconds per span name (top-level occurrences of a name
        sum; a name nested under itself would double-count — the runtime
        never does that)."""
        out: Dict[str, float] = {}
        for e in self.events:
            out[e["name"]] = out.get(e["name"], 0.0) + e["dur_s"]
        return out

    def sync_seconds(self) -> float:
        """Host seconds inside ``dials.sync.*`` spans since the last
        :meth:`reset`."""
        return sum(e["dur_s"] for e in self.events
                   if e["name"].startswith(SYNC))


class NullTracer:
    """Disabled tracer: profiler annotations only, no recording."""

    fenced = False
    events: List[Dict] = []       # intentionally shared + always empty

    @property
    def enabled(self) -> bool:
        return False

    @staticmethod
    def span(name: str):
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def fence(value):
        return value

    @staticmethod
    def pull(name: str, value, cast=float):
        with jax.profiler.TraceAnnotation(SYNC + name):
            return cast(value)

    def reset(self) -> None:
        pass

    def phase_seconds(self) -> Dict[str, float]:
        return {}

    def sync_seconds(self) -> None:
        return None


NULL_TRACER = NullTracer()
