"""The program's host spans and the metrics that read them.

- the readers ``host_syncs``, ``sync_idle_ms`` and ``host_idle_ms`` on a
  hand-built trace with known idle gaps and ``dials.sync.*`` spans, and
  their sum against ``device_idle_share``;
- a tiny loop-path ``DIALSTrainer`` under ``jax.profiler`` with telemetry
  off: every ``dials.*`` phase span and one ``dials.sync.*`` span per
  read reach the trace, and every blocking read of a round lies under a
  sync span;
- the same for the sharded driver on four CPU devices, in a process of
  its own.
"""
import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

import profiled
import tiny
from harness import catalog
from harness.trace import Device, Trace

HERE = pathlib.Path(__file__).resolve().parent
LOOP_READS = ["stale_forced", "gs_return", "ials_reward", "aip_ce_before",
              "aip_ce_after", "staleness_min", "staleness_mean",
              "staleness_max"]
LOOP_PHASES = ["dials.aip_train", "dials.collect", "dials.gs_eval",
               "dials.inner_steps", "dials.record", "dials.round"]
RECORD_KEYS = ["aip_ce_after", "aip_ce_before", "data_round", "gs_return",
               "ials_reward", "stale_forced", "staleness_max",
               "staleness_mean", "staleness_min"]


def _run(host, rounds=2):
    """Two chips over a window [0, 1000) ns. Chip 0 idles in [150, 180)
    and [450, 550); chip 1 never idles."""
    ops0 = [("a", 0, 150), ("b", 180, 450), ("c", 190, 300),
            ("d", 550, 1000)]
    devices = [Device("/device:TPU:0", [], ops0),
               Device("/device:TPU:1", [], [("e", 0, 1000)])]
    return types.SimpleNamespace(trace=Trace((0, 1000), devices, host),
                                 rounds=rounds)


def _read(name, run):
    return catalog.reader(name)(run)


def test_readers_split_idle_time_by_sync_spans():
    host = [("dials.round", 0, 1000),
            ("dials.sync.gs_return", 100, 200),   # over [150, 180): 30
            ("dials.sync.aip_ce_before", 500, 600),  # over [500, 550): 50
            ("$array.py:631 _value", 510, 590)]
    run = _run(host)
    assert _read("host_syncs", run) == 1.0
    # 80 ns of chip 0's 130 idle ns lie under a sync span, 50 do not;
    # averaged over two chips, per round, in ms
    assert _read("sync_idle_ms", run) == pytest.approx(40 / 2 * 1e-6)
    assert _read("host_idle_ms", run) == pytest.approx(25 / 2 * 1e-6)
    idle = _read("device_idle_share", run) / 100 * run.trace.window_s \
        * 1e3 / run.rounds
    total = _read("sync_idle_ms", run) + _read("host_idle_ms", run)
    assert total == pytest.approx(idle, rel=1e-9)


def test_readers_clip_spans_and_merge_overlaps():
    host = [("dials.sync.obtain", -50, 160),      # clipped to [0, 160)
            ("dials.sync.reports", 140, 170),     # overlaps the one above
            ("dials.sync.mirror", 990, 1200)]     # chip 0 busy there
    run = _run(host, rounds=1)
    assert _read("host_syncs", run) == 3.0
    # under a span: [150, 170) of chip 0's idle time
    assert _read("sync_idle_ms", run) == pytest.approx(20 / 2 * 1e-6)
    assert _read("host_idle_ms", run) == pytest.approx(110 / 2 * 1e-6)


def test_readers_return_nothing_without_the_programs_spans():
    """A program without the spans (an older checkout) reads None."""
    run = _run([("bench_window_start", 0, 0), ("$array.py:631 _value",
                                               150, 180)])
    for name in ("host_syncs", "sync_idle_ms", "host_idle_ms"):
        assert _read(name, run) is None


def test_loop_driver_spans_reach_the_profiler(tmp_path):
    history, events = profiled.run_traced(tiny.job(), 3, str(tmp_path))
    assert all(r["sync_s"] is None for r in history)   # telemetry off
    report = profiled.rounds_report(events)
    assert len(report["rounds"]) == 3
    for r in report["rounds"]:
        assert r["phases"] == LOOP_PHASES
        assert r["syncs"] == LOOP_READS
        assert r["reads"] >= len(LOOP_READS) and r["stray_reads"] == []
    assert report["sync_spans"] == 3 * len(LOOP_READS)


def test_sharded_driver_spans_reach_the_profiler():
    p = subprocess.run([sys.executable, str(HERE / "spans_check.py")],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["n_shards"] == 4
    assert len(out["rounds"]) == 2
    for r in out["rounds"]:
        assert r["phases"] == ["dials.round"]
        assert sorted(r["syncs"]) == RECORD_KEYS
        assert r["reads"] >= len(RECORD_KEYS) and r["stray_reads"] == []
    assert out["sync_spans"] == 2 * len(RECORD_KEYS)


def test_readers_on_a_trace_recorded_without_the_spans():
    """The trace recorded on a TPU v5e (``data/tiny.xplane.pb``) holds
    no ``dials.*`` span: the three readers read nothing."""
    from jax.profiler import ProfileData
    from harness import trace
    t = trace.reduce(ProfileData.from_file(str(HERE / "data" /
                                               "tiny.xplane.pb")))
    run = types.SimpleNamespace(trace=t, rounds=2)
    for name in ("host_syncs", "sync_idle_ms", "host_idle_ms"):
        assert _read(name, run) is None
