"""Readings that a cell's correctness limit is set from (not a benchmark run).

  python3 bench/calibrate.py --workload <name> --seconds <s> --seeds 11 12 13

In one process, for each seed: one run of the cell's driver at its own
load, the program's readings (what the run compares against its limits),
and the driver's ``control`` readings on the same work: the reference
computed a precision below the configuration's (``quant="fp8"``) in the
program's place, and for training the faults planted in the reference.
Each limit lies above every program reading and below the readings it
must fail (PERF.md gives them); the control's readings are also judged
by the run's own checks (``control_correct``, which must be false).
Prints one JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402
import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    workload = harness.find(harness.benchmark()["workloads"], args.workload,
                            "workload")
    cfg = harness.config(workload["config"])
    mix = harness.traffic(workload["traffic"])
    devices = harness.accelerators(int(workload["chips"]))
    harness.enable_caches()
    driver = harness.plugin("drivers", cfg["driver"])
    compiles = harness.CompileCounter()
    for seed in args.seeds:
        ctx = bench_run.Ctx(workload, cfg, mix, seed, args.seconds, False,
                            devices, harness.Clock(), compiles,
                            harness.ROOT / "bench_out" / "trace")
        out = driver.run(ctx)
        reading = {"seed": seed, "setup_s": ctx.setup_s,
                   "checks": out["checks"],
                   "e2e": {k: v[0] for k, v in out["e2e"].items()}}
        control = driver.control(ctx, out)
        if "control_checks" in control:
            control["control_correct"] = bench_run.correct_from(
                control.pop("control_checks"))
        reading.update(control)
        print(json.dumps(reading), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
