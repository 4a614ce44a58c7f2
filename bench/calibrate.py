"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload traffic10.f50 \
        --seeds 101,102,...  --control-seeds 201,202,203

For every seed of ``--seeds`` it drives the cell's trainer through the
harness's own set-up (``run.measure``, one warm-up round and one more)
and compares its first steps with the plain reference: these are the
lower readings, of sound runs. For every seed of ``--control-seeds`` it
puts the reference, computed in bfloat16 at the default matmul precision
(the nearest precision below the configuration's float32), in the
program's place: these are the upper readings. ``--fault`` plants
faults of ``harness.faults`` in the program and reads them on the
control's seeds. It prints one JSON line
per run and the largest sound and smallest control reading of every
number, and writes them to ``chiprun_out/calibrate-<workload>.json``.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import catalog, correct, faults, job as job_mod  # noqa: E402


def sound(job, key) -> dict:
    import jax.numpy as jnp
    import run
    warmup = correct.ROUND_STEPS if job["shards"] != 1 else 1
    _, got, reference = run.measure(job, key, 0.0, warmup=warmup)
    return correct.readings(got, reference(job, key, jnp.float32, "highest"))


def control(job, key) -> dict:
    import jax.numpy as jnp
    reference = (correct.reference_rounds if job["shards"] != 1
                 else correct.reference_steps)
    low = reference(job, key, jnp.bfloat16, "default")
    return correct.readings(low, reference(job, key, jnp.float32, "highest"))


def summary(rows) -> dict:
    """The largest sound reading and the smallest of the control and of
    each fault, per number."""
    out = {}
    for kind in dict.fromkeys(r["kind"] for r in rows):
        rs = [r["readings"] for r in rows if r["kind"] == kind]
        pick = max if kind == "sound" else min
        out[kind] = {k: pick(r[k] for r in rs) for k in rs[0]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="", help="comma-separated faults of "
                    "harness.faults, each planted in the program and run "
                    "on the control's seeds")
    args = ap.parse_args(argv)
    cell = catalog.workload(args.workload)
    job = job_mod.make_job(cell["config_file"], cell["traffic_file"],
                           cell["chips"])
    import jax
    import run
    run.enable_cache()
    fault = run.device_fault(jax.devices(), cell["chips"])
    if fault:
        print(f"calibrate: {fault}", file=sys.stderr)
        return 2
    rows = []
    plan = [("sound", s) for s in args.seeds.split(",") if s] + \
        [("control", s) for s in args.control_seeds.split(",") if s]
    plan += [(f, s) for f in args.fault.split(",") if f
             for s in args.control_seeds.split(",") if s]
    for kind, s in plan:
        key = run.seed_key(int(s))
        if kind in faults.FAULTS:
            with faults.FAULTS[kind]():
                readings = sound(job, key)
        else:
            readings = (sound if kind == "sound" else control)(job, key)
        rows.append({"kind": kind, "seed": int(s), "readings": readings})
        print(json.dumps(rows[-1]), flush=True)
    result = {"workload": args.workload, "rows": rows,
              "summary": summary(rows)}
    out = ROOT / "chiprun_out" / f"calibrate-{args.workload}.json"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(result, indent=1))
    print(json.dumps(result["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
