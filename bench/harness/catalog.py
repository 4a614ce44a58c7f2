"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

- a configuration: the file its ``configs`` entry names;
- a traffic mix: ``bench/traffic/<traffic>.json``;
- a cell's correctness limits: ``bench/limits/<workload>.json``;
- a per-layer metric's reader: ``bench/metrics/<metric>.py``, a module
  with ``read(run) -> float | None``;
- what a configuration file names (``module``): its environment's plain
  reference, ``bench/harness/ref/<env>.py``, and the backbone of each
  network, ``bench/harness/ref/backbones/<kind>.py``.

A later cell, traffic mix, metric, environment or backbone is added as
files and entries; no file here changes.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(name: str, root: pathlib.Path = ROOT) -> dict:
    """The cell ``name`` with its files loaded: ``config``, ``traffic``,
    ``limits`` (dicts), ``chips``, and its metric entries."""
    s = spec(root)
    cell = dict(_by_name(s["workloads"], name, "workload"))
    cfg_entry = _by_name(s["configs"], cell["config"], "config")
    cell["config_file"] = json.loads((root / cfg_entry["file"]).read_text())
    cell["traffic_file"] = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["limits"] = json.loads(
        (root / "bench" / "limits" / f"{name}.json").read_text())
    cell["end_to_end"] = [m for m in s["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in s["per_layer"]
                         if _applies(m, name) and m["moves"] in moved]
    cell["run_seconds"] = s["run_seconds"]
    return cell


def _applies(metric: dict, workload_name: str) -> bool:
    return "workloads" not in metric or workload_name in metric["workloads"]


def reader(metric: str, root: pathlib.Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def module(package: str, name: str):
    """The module ``<package>.<name>``, found by the name a configuration
    gives it. Where the package holds no file ``<name>.py`` the KeyError
    names the file to add."""
    pkg = importlib.import_module(package)
    path = pathlib.Path(list(pkg.__path__)[0]) / f"{name}.py"
    if not (name.isidentifier() and path.is_file()):
        raise KeyError(f"no {package} module named {name!r}: add "
                       f"{path.relative_to(ROOT)}")
    return importlib.import_module(f"{package}.{name}")
