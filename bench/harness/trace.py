"""Reduces a ``jax.profiler`` trace of the measured window to the sums
the per-layer metrics read.

Layout of a TPU trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``): one plane ``/device:TPU:<i>`` per chip,
with the line ``XLA Modules`` (one event per program run, named
``jit_<function>(<fingerprint>)``) and the line ``XLA Ops`` (one event per
HLO instruction run, named by the instruction's text; a loop's event
spans its body's events). Host planes (``/host:CPU``) hold the Python
and runtime threads, including the ``TraceAnnotation`` marks
``bench_window_start`` and ``bench_window_end`` that the harness writes
at the two round boundaries that close the window. Device and host
events share one clock.

A Pallas kernel is an instruction with
``custom_call_target="tpu_custom_call"``, and ``kernel_kind`` tells the
kernels apart by the name the program gives each: the instruction is
named after the kernel, ``<kind>_fwd`` or ``<kind>_bwd``, with XLA's
``.N`` suffix (``%gru_bwd.6``), and its kind is that name without the
suffix and the ending (``gru``). Under a transformation such as ``vmap``
the name is wrapped (``%vmap_gae_fwd_.3``), and the wrapping goes too.
A new kernel named so is found by its kind with no change here.
"""
from __future__ import annotations

import bisect
import glob
import re

START, END = "bench_window_start", "bench_window_end"
_OPCODE = re.compile(r"^%\S+ = .*? ([a-z][a-z0-9\-]*)\(")
_SUFFIX = re.compile(r"\.\d+$")
_ENDING = re.compile(r"_(?:fwd|bwd)$")
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


def opcode(name: str) -> str:
    m = _OPCODE.match(name)
    return m.group(1) if m else ""


def kernel_kind(name: str):
    """The kind of the Pallas kernel an ``XLA Ops`` event runs, from the
    instruction's name (``%gae_fwd.3 = ...`` gives ``"gae"``), or None
    for an event that runs none."""
    if 'custom_call_target="tpu_custom_call"' not in name:
        return None
    instr = _SUFFIX.sub("", name.split(" = ", 1)[0].lstrip("%"))
    # a kernel under a transformation is named ``<transform>(<name>)``,
    # which XLA writes ``<transform>_<name>_``: under ``vmap``, the GAE
    # kernel is ``vmap_gae_fwd_``
    while instr.endswith("_") and "_" in instr[:-1]:
        instr = instr.split("_", 1)[1][:-1]
    return _ENDING.sub("", instr)


def merged(intervals):
    """The union of ``(start, end)`` intervals as sorted disjoint
    ``[start, end]`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


class Device:
    """One chip's events inside the window: ``modules`` and ``ops`` as
    ``(name, start_ns, end_ns)``, clipped to the window."""

    def __init__(self, name, modules, ops):
        self.name, self.modules, self.ops = name, modules, ops

    def module_seconds(self, prefix: str) -> float:
        return sum(e - s for n, s, e in self.modules
                   if n.startswith(prefix + "(")) / 1e9

    def kernel_seconds(self, kind: str) -> float:
        return sum(e - s for n, s, e in self.ops
                   if kernel_kind(n) == kind) / 1e9

    def kernel_events(self, kind: str) -> int:
        return sum(1 for n, _, _ in self.ops if kernel_kind(n) == kind)

    def collective_seconds(self) -> float:
        return union((s, e) for n, s, e in self.ops
                     if opcode(n).replace("-start", "").replace("-done", "")
                     in COLLECTIVES) / 1e9

    def busy_seconds(self) -> float:
        return union((s, e) for _, s, e in self.ops) / 1e9


class Trace:
    """A reduced trace: ``window_s`` between the two marks, ``devices``
    (one ``Device`` per TPU plane, in plane order), and the host events
    (``host``) for labelling idle gaps."""

    def __init__(self, window, devices, host):
        self.lo, self.hi = window
        self.window_s = (self.hi - self.lo) / 1e9
        self.devices, self.host = devices, host

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        return sum(d.busy_seconds() for d in self.devices) / len(self.devices)

    def breakdown(self, top: int = 10) -> dict:
        """The device operations of chip 0 with the most self time
        (``<module>/<instruction>``), and the longest idle gaps of chip
        0, each named by the innermost host event that covers it."""
        dev = self.devices[0]
        mods = sorted((s, e, n.split("(")[0]) for n, s, e in dev.modules)
        starts = [m[0] for m in mods]
        selft = {}
        ops = sorted(((s, -e, n) for n, s, e in dev.ops))
        stack = []                       # [end, key, self_ns]
        for s, neg_e, n in ops:
            e = -neg_e
            while stack and stack[-1][0] <= s:
                end, key, own = stack.pop()
                selft[key] = selft.get(key, 0) + own
            if stack:
                stack[-1][2] -= e - s
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
            key = f"{mod}/{n.split(' = ')[0].lstrip('%')}"
            stack.append([e, key, e - s])
        for end, key, own in stack:
            selft[key] = selft.get(key, 0) + own
        device_ops = sorted(selft.items(), key=lambda kv: -kv[1])[:top]
        busy = merged((s, e) for _, s, e in dev.ops)
        gaps, prev = [], self.lo
        for s, e in busy + [[self.hi, self.hi]]:
            if s > prev:
                gaps.append((s - prev, prev, s))
            prev = max(prev, e)
        gaps.sort(reverse=True)
        return {"device_ops": [[k, v / 1e9] for k, v in device_ops],
                "idle_gaps": [[self._host_label(a, b), d / 1e9]
                              for d, a, b in gaps[:top]]}

    def _host_label(self, a, b) -> str:
        mid, best = (a + b) / 2, None
        for name, s, e in self.host:
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0][:120] if best else "none"


def load(trace_dir: str) -> Trace:
    """Reads the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce(ProfileData.from_file(paths[-1]))


def reduce(pd) -> Trace:
    marks, host, dev_planes = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in (START, END):
                        marks[ev.name] = ev.start_ns
                    else:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:TPU:"):
            dev_planes.append(plane)
    if START not in marks or END not in marks:
        raise ValueError("trace has no bench_window_start/end marks")
    if not dev_planes:
        raise ValueError("trace has no /device:TPU: plane")
    lo, hi = marks[START], marks[END]
    devices = []
    for plane in sorted(dev_planes, key=lambda p: int(p.name.split(":")[-1])):
        lines = {line.name: line for line in plane.lines}

        def events(line_name):
            out = []
            line = lines.get(line_name)
            for ev in (line.events if line is not None else ()):
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             lo, hi)
                if e > s:
                    out.append((ev.name, s, e))
            return out

        devices.append(Device(plane.name, events("XLA Modules"),
                              events("XLA Ops")))
    host = [(n, s, e) for n, s, e in host if e > lo and s < hi]
    return Trace((lo, hi), devices, host)
