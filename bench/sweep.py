"""Find a serving cell's knee once, on the chip (not a benchmark run).

  python3 bench/sweep.py --workload <name> --seconds <s> --seed <n> \
      --rates 1 2 3 4

In one process, runs the cell's driver at each arrival rate in turn (the
mix's other parameters unchanged) and prints, per rate, the end-to-end
metrics and the admission backlog: requests waiting for a slot, at the
start and the end of the window, the largest running batch and the KV
pages claimed.  The knee is the highest rate at which the backlog does
not grow through the window; the cell's mix then runs at about 0.8 of it.
"""
from __future__ import annotations

import argparse
import copy
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    workload = harness.find(harness.benchmark()["workloads"], args.workload,
                            "workload")
    cfg = harness.config(workload["config"])
    cfg["check"]["sample_requests"] = 1     # the sweep checks little
    devices = harness.accelerators(int(workload["chips"]))
    harness.enable_caches()
    driver = harness.plugin("drivers", cfg["driver"])
    compiles = harness.CompileCounter()
    for rate in args.rates:
        mix = copy.deepcopy(harness.traffic(workload["traffic"]))
        mix["arrivals"]["rate_per_s"] = rate
        ctx = bench_run.Ctx(workload, cfg, mix, args.seed, args.seconds,
                            False, devices, harness.Clock(), compiles,
                            harness.ROOT / "bench_out" / "trace")
        out = driver.run(ctx)
        rec = out["records"]
        backlog = rec["backlog"]
        half = [b for t, b in backlog if t >= args.seconds / 2]
        print(json.dumps({
            "rate_per_s": rate, "due": rec["requests_due"],
            "e2e": {k: v[0] for k, v in out["e2e"].items()},
            "backlog_first": backlog[0][1] if backlog else 0,
            "backlog_mid": half[0] if half else 0,
            "backlog_last": backlog[-1][1] if backlog else 0,
            "max_running": rec["max_running"],
            "mean_running": rec["mean_running"],
            "pool_pages": rec["pool_pages"],
            "claimed_pages_peak": rec["claimed_pages_peak"],
            "live_pages_peak": rec["live_pages_peak"],
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "setup_s": ctx.setup_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
