"""Runs a tiny DIALS job under ``jax.profiler`` with telemetry off and
reads the program's host spans from its trace, for the span tests (on
the CPU: only host events are read)."""
import glob

import jax

from harness.spans import SYNC


def host_events(trace_dir: str):
    """Every host event of the newest ``.xplane.pb`` under
    ``trace_dir`` as ``(name, start_ns, end_ns)``, by start."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda ev: ev[1])


def run_traced(job: dict, rounds: int, trace_dir: str):
    """``DIALSTrainer.run`` of ``job`` for ``rounds`` rounds under the
    profiler, after a first run that compiles every program:
    ``(history, host events)``."""
    import run
    from harness import job as job_mod
    from repro.core import dials
    trainer = dials.DIALSTrainer(*job_mod.program(job, outer_rounds=rounds))
    key = run.seed_key(2 ** 31 + 11)
    trainer.run(key)
    jax.profiler.start_trace(trace_dir)
    try:
        _, history = trainer.run(key)
    finally:
        jax.profiler.stop_trace()
    return history, host_events(trace_dir)


def is_blocking_read(name: str) -> bool:
    """A Python-tracer event of a device-to-host read: ``jax.Array``'s
    ``_value`` or ``device_get``."""
    return (("array.py" in name and name.endswith("_value"))
            or "device_get" in name)


def rounds_report(events):
    """Per ``dials.round`` span: the ``dials.*`` phase spans in it, its
    ``dials.sync.*`` reads in order, the count of blocking reads in it,
    and those that lie under no sync span; plus the count of sync spans
    in all."""
    out = []
    for lo, hi in [(s, e) for n, s, e in events if n == "dials.round"]:
        inside = [ev for ev in events if ev[1] >= lo and ev[2] <= hi]
        syncs = [ev for ev in inside if ev[0].startswith(SYNC)]
        out.append({
            "phases": sorted({n for n, _, _ in inside
                              if n.startswith("dials.")
                              and not n.startswith(SYNC)}),
            "syncs": [n[len(SYNC):] for n, _, _ in syncs],
            "reads": sum(1 for n, _, _ in inside if is_blocking_read(n)),
            "stray_reads": [n for n, s, e in inside if is_blocking_read(n)
                            and not any(a <= s and e <= b
                                        for _, a, b in syncs)]})
    n_sync = sum(1 for n, _, _ in events if n.startswith(SYNC))
    return {"rounds": out, "sync_spans": n_sync}
