"""Run by ``test_bench_sharded.py`` in a process of its own, with four
CPU devices: the sharded path's comparison at a tiny size. Prints one
JSON object of readings: sound, and with each fault the four-chip cell
can have planted."""
import json
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[1] / "src"):
    sys.path.insert(0, str(p))

import calibrate  # noqa: E402
import run  # noqa: E402
import tiny  # noqa: E402
from harness import faults  # noqa: E402

# 4 agents over 4 chips: side 2 cannot tile 4 row bands, so the round
# uses the replicated GS, as the four-chip traffic cell does
job = tiny.job(shards=None)
key = run.seed_key(2 ** 31 + 3)
out = {"sound": calibrate.sound(job, key),
       "control": calibrate.control(job, key)}
for name in ("state_unchanged", "half_batch", "exchange_dropped"):
    with faults.FAULTS[name]():
        out[name] = calibrate.sound(job, key)
print(json.dumps(out))
