"""The sharded path's comparison (whole rounds of the agent-sharded
program against the plain one-device reference) at a tiny size on four
CPU devices, in a process of its own. No cell of the benchmark runs
this path yet, so there are no chip limits to hold it to: a sound run
reads at float32 rounding, and the precision control and each fault
planted in the program read at least a hundred times more on some
number."""
import json
import os
import pathlib
import subprocess
import sys

from harness import correct

HERE = pathlib.Path(__file__).resolve().parent


def test_sharded_rounds_against_the_reference():
    p = subprocess.run([sys.executable, str(HERE / "sharded_check.py")],
                       capture_output=True, text=True, timeout=900,
                       env=dict(os.environ))
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(out["sound"]) == set(correct.ROUND_NUMBERS)
    assert max(out["sound"].values()) < 1e-5, out["sound"]
    for name in ("control", "state_unchanged", "half_batch",
                 "exchange_dropped"):
        assert max(out[name].values()) > 1e-3, (name, out[name])
