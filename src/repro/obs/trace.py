"""Lightweight span tracer for the DIALS runtime.

Three layers of the same idea — "name the time", at three costs:

* **Host spans** (:class:`Tracer.span`) — nested context-manager spans on
  a monotonic clock (``time.perf_counter``). Each span records
  ``(name, depth, t0, dur_s)``; :meth:`Tracer.phase_seconds` aggregates
  them into the per-phase seconds the typed round record
  (``repro.obs.metrics``) carries. JAX dispatch is asynchronous, so an
  unfenced span around a jitted call measures *enqueue* time; pass
  ``fence=True`` to the tracer and call ``sp.fence(outputs)`` inside the
  span to ``jax.block_until_ready`` before the clock stops — honest
  device timings, at the cost of a host sync per fenced span. The
  drivers default to unfenced (their one-sync-per-round contract is
  load-bearing); benchmarks fence.
* **Trace-time annotations** (:func:`annotate`) — ``jax.named_scope``
  pass-through for code *inside* jitted programs (the per-shard train
  body, the halo exchange). Zero runtime cost: the scope names travel
  into HLO metadata so the regions are attributable in an XLA profile.
* **Profiler sessions** (:func:`profile`) — an opt-in
  ``jax.profiler.start_trace`` window (``--profile-dir`` on
  ``benchmarks/run.py`` / ``benchmarks/scaling.py``); host spans
  additionally enter ``jax.profiler.TraceAnnotation`` while a session
  may be live, so the same span names land on the profiler timeline.

The disabled path is :data:`NULL_TRACER`: its :meth:`~NullTracer.span`
returns one shared no-op span object (context entry is a constant-time
attribute access, nothing is allocated or recorded), so leaving tracer
calls in place costs nothing when telemetry is off.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import jax


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


class Span:
    """One live span. ``fence(x)`` optionally blocks on device values so
    the span's duration covers real execution, then returns ``x``."""

    __slots__ = ("_tracer", "name", "depth", "t0")

    def __init__(self, tracer: "Tracer", name: str, depth: int, t0: float):
        self._tracer, self.name, self.depth, self.t0 = \
            tracer, name, depth, t0

    def fence(self, value):
        if self._tracer.fenced:
            jax.block_until_ready(value)
        return value


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
            sp = Span(self, name, depth, t0)
            try:
                yield sp
            finally:
                dur = self._clock() - t0
                self._depth = depth
                # appended at exit: children land before their parent,
                # report/asserts re-nest via (t0, depth)
                self.events.append({"name": name, "depth": depth,
                                    "t0": t0, "dur_s": dur})

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


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @staticmethod
    def fence(value):
        return value


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: one shared no-op span, no state, no recording."""

    fenced = False
    events: List[Dict] = []       # intentionally shared + always empty

    @property
    def enabled(self) -> bool:
        return False

    def span(self, name: str):
        return _NULL_SPAN

    def reset(self) -> None:
        pass

    def phase_seconds(self) -> Dict[str, float]:
        return {}


NULL_TRACER = NullTracer()
