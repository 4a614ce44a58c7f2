"""Telemetry sinks — per-process JSONL event logs and their merge.

Mirrors the ``fault.HostMonitor`` heartbeat-dir pattern: every process
appends to its own ``telemetry-p{PID}.jsonl`` in a shared directory
(one JSON object per line, flushed per line so a SIGKILL'd host's
events survive up to the final, possibly truncated, line), and rank 0
merges all per-process files into one ``telemetry.jsonl`` ordered by
``(t, proc, seq)``. No cross-process coordination is needed to write —
only the merge reads other processes' files.

Also here: a CSV sink (round events only, columns in
``metrics.ROUND_FIELDS`` order, for spreadsheet-style analysis).
"""
from __future__ import annotations

import csv
import json
import os
from typing import Dict, Iterable, List, Optional

from repro.obs import metrics

MERGED_NAME = "telemetry.jsonl"


def proc_path(directory: str, process_id: int) -> str:
    """Per-process event-log path inside the shared telemetry dir."""
    return os.path.join(directory, f"telemetry-p{process_id}.jsonl")


def read_jsonl(path: str) -> List[Dict]:
    """Parse a JSONL event file, tolerating a truncated final line (a
    host killed mid-write) — complete lines before it are kept."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                break             # truncated tail; nothing valid follows
    return events


def merge_dir(directory: str, *, out: Optional[str] = None) -> str:
    """Merge every ``telemetry-p*.jsonl`` in ``directory`` into one
    globally ordered file (sort key ``(t, proc, seq)``) and return its
    path. Rank 0 calls this after a run; re-merging is idempotent."""
    names = sorted(n for n in os.listdir(directory)
                   if n.startswith("telemetry-p") and
                   n.endswith(".jsonl"))
    events: List[Dict] = []
    for name in names:
        events.extend(read_jsonl(os.path.join(directory, name)))
    events.sort(key=lambda e: (e.get("t", 0.0), e.get("proc", 0),
                               e.get("seq", 0)))
    out = out or os.path.join(directory, MERGED_NAME)
    with open(out, "w") as f:
        for e in events:
            f.write(json.dumps(e, default=float) + "\n")
    return out


class JsonlSink:
    """Append-only per-process JSONL writer (line-buffered + flushed:
    crash-safe up to the last line)."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self._f = open(path, "a")

    def write(self, event: Dict) -> None:
        self._f.write(json.dumps(event, default=float) + "\n")
        self._f.flush()

    def close(self) -> None:
        self._f.close()


class CsvSink:
    """Round events as CSV, columns in ``metrics.ROUND_FIELDS`` order
    (``dead_hosts`` serialized as ``;``-joined host indices). Non-round
    events are skipped."""

    def __init__(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self.path = path
        self._f = open(path, "w", newline="")
        self._w = csv.writer(self._f)
        self._w.writerow(("proc",) + metrics.ROUND_KEYS)

    def write(self, event: Dict) -> None:
        if event.get("event") != "round":
            return
        row = [event.get("proc", 0)]
        for name in metrics.ROUND_KEYS:
            v = event.get(name)
            row.append(";".join(str(h) for h in v)
                       if isinstance(v, list) else v)
        self._w.writerow(row)
        self._f.flush()

    def close(self) -> None:
        self._f.close()


def write_events(events: Iterable[Dict], sink) -> None:
    """Replay an event stream (e.g. a merged file) through a sink."""
    for e in events:
        sink.write(e)
