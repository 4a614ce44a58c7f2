"""Traffic files: a schedule given under two names is one schedule.

``BENCHMARK.json`` gives each pair of configuration and traffic once, so
the four-chip cell names its traffic ``f50.4chip``; it holds ``f50``'s
schedule key for key, and the shards follow the cell's chips."""
import json
import pathlib

import pytest

from harness import catalog, job as job_mod

ROOT = pathlib.Path(__file__).resolve().parents[2]
TRAFFIC = ROOT / "bench" / "traffic"


def _schedule(name):
    data = json.loads((TRAFFIC / f"{name}.json").read_text())
    return {k: data[k] for k in job_mod.TRAFFIC_KEYS}


def test_four_chip_traffic_is_f50():
    assert _schedule("f50.4chip") == _schedule("f50")


@pytest.mark.parametrize("one, four", [("traffic10.f50",
                                         "traffic10.f50.4chip")])
def test_four_chip_cell_runs_the_one_chip_job_on_a_mesh(one, four):
    a, b = catalog.workload(one), catalog.workload(four)
    assert a["config"] == b["config"] and a["traffic"] != b["traffic"]
    ja = job_mod.make_job(a["config_file"], a["traffic_file"], a["chips"])
    jb = job_mod.make_job(b["config_file"], b["traffic_file"], b["chips"])
    assert (ja.pop("shards"), jb.pop("shards")) == (1, None)
    assert ja == jb
