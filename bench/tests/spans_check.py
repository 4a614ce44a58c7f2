"""Run by ``test_bench_spans.py`` in a process of its own, with four CPU
devices: the sharded driver's spans at a tiny size, telemetry off.
Prints one JSON object: ``profiled.rounds_report`` of two rounds and
the shard count."""
import json
import os
import pathlib
import sys
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[1] / "src"):
    sys.path.insert(0, str(p))

import profiled  # noqa: E402
import tiny  # noqa: E402

# 4 agents over 4 chips, the replicated GS (side 2 cannot tile 4 bands)
with tempfile.TemporaryDirectory() as d:
    history, events = profiled.run_traced(tiny.job(shards=None), 2, d)
out = profiled.rounds_report(events)
out["n_shards"] = history[-1]["n_shards"]
print(json.dumps(out))
