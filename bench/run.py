"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``; the configuration's ``driver`` names the window loop
in ``bench/drivers/``.  ``--trace 0`` prints the cell's end-to-end metrics;
``--trace 1`` profiles the window and prints its per-layer metrics, each
read by ``bench/metrics/<metric>.py``, with the device's busy and window
seconds and a breakdown.  Every run checks what the timed path produced
against the plain reference and prints each number compared beside its
limit, last on standard error and under ``checks`` in the result.

Exits non-zero with no result when no TPU, or too few chips, are found.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                # noqa: E402
import contextlib                                              # noqa: E402
import dataclasses                                             # noqa: E402
import json                                                    # noqa: E402
import sys                                                     # noqa: E402
from pathlib import Path                                       # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness                                                 # noqa: E402
import devtrace                                                # noqa: E402

OUT = harness.ROOT / "bench_out"      # traces (git-ignored, inside the checkout)


@dataclasses.dataclass
class Ctx:
    """What a driver gets: the cell's files, the run's arguments, the
    devices, the host clock and the compile counter."""
    workload: dict
    cfg: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    clock: harness.Clock
    compiles: harness.CompileCounter
    trace_dir: Path
    setup_s: float | None = None
    marks: list = dataclasses.field(default_factory=list)

    def recording(self):
        return devtrace.recording(self.trace_dir, self.trace)

    def mark(self, label: str) -> None:
        """End of a phase of set-up (host clock)."""
        self.marks.append((label, self.clock.now()))

    def mark_window_start(self, t: float) -> None:
        self.setup_s = t

    def setup_split(self) -> str:
        parts, last = [], 0.0
        for label, t in self.marks:
            parts.append(f"{label} {t - last:.3f} s")
            last = t
        return (", ".join(parts) + f"; of which compiling "
                f"{self.compiles.compile_s:.3f} s")


def per_layer_metrics(workload: dict, e2e_names) -> list[dict]:
    """The per-layer metrics this cell reports (see BENCHMARK.json)."""
    out = []
    for m in harness.benchmark()["per_layer"]:
        cells = m.get("workloads")
        if (workload["name"] in cells) if cells is not None \
                else m["moves"] in e2e_names:
            out.append(m)
    return out


def end_to_end_metrics(workload: dict) -> list[dict]:
    return [m for m in harness.benchmark()["end_to_end"]
            if workload["name"] in m.get("workloads", [workload["name"]])]


def breakdown(trace: devtrace.Trace) -> dict:
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    lo, hi = trace.window()
    for dev in trace.ops:
        for o in trace.ops[dev]:
            if lo <= o.start_ns <= hi and not o.container:
                key = f"{o.module}/{o.name}"
                ops[key] = ops.get(key, 0.0) + o.dur_ns * 1e-9
        idle = trace.idle_gaps(dev)
        for (a, b), what in zip(idle, trace.host_doing(idle)):
            gaps[what] = gaps.get(what, 0.0) + (b - a) * 1e-9
    n = max(len(trace.ops), 1)

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def correct_from(checks: list[dict]) -> bool:
    ok = True
    for c in checks:
        if c["limit"] is None:          # no limit set yet: not correct
            ok = False
        elif c.get("at_least"):
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)


def run(args) -> dict:
    workload = harness.find(harness.benchmark()["workloads"], args.workload,
                            "workload")
    cfg = harness.config(workload["config"])
    mix = harness.traffic(workload["traffic"])
    devices = harness.accelerators(int(workload["chips"]))
    harness.enable_caches()
    driver = harness.plugin("drivers", cfg["driver"])
    ctx = Ctx(workload, cfg, mix, args.seed, float(args.seconds),
              bool(args.trace), devices, harness.Clock(T_START),
              harness.CompileCounter(),
              OUT / "trace" / workload["name"])
    ctx.mark("start, imports and devices")
    out = driver.run(ctx)
    e2e = {m["name"] for m in end_to_end_metrics(workload)}
    checks = out["checks"]
    compiles, traces = out["records"]["compiles_in_window"]
    harness.log(f"set-up {ctx.setup_s:.3f} s: {ctx.setup_split()}; in the "
                f"window: {compiles} compiles, {traces} traces (there should "
                "be none)")
    metrics = {}
    device = dict(out["device"])
    result = {"correct": correct_from(checks), "attempted": out["attempted"],
              "failed": out["failed"]}
    if not args.trace:
        values = dict(out["e2e"])
        values["setup_s"] = (ctx.setup_s, "s")
        for name in sorted(e2e):
            v, unit = values[name]
            metrics[name] = {"value": v, "unit": unit}
    else:
        t_read = time.perf_counter()
        trace = devtrace.load(ctx.trace_dir)
        peaks = harness.peaks(devices[0].device_kind)
        lo, hi = trace.window()
        device["busy_s"] = sum(trace.busy_ns(d) for d in trace.ops) \
            / max(len(trace.ops), 1) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        reading = Reading(cfg, mix, out["records"], trace, peaks)
        for m in per_layer_metrics(workload, e2e):
            v = harness.plugin("metrics", m["name"]).read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = breakdown(trace)
        harness.log(f"trace read and reduced in "
                    f"{time.perf_counter() - t_read:.1f} s")
    result.update(metrics=metrics, device=device,
                  checks={c["name"]: {"value": c["value"],
                                      "limit": c["limit"]} for c in checks})
    for c in checks:
        rel = ">=" if c.get("at_least") else "<="
        harness.log(f"check {c['name']}: {c['value']} (limit {rel} "
                    f"{c['limit']})")
    return result


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader gets."""
    cfg: dict
    mix: dict
    records: dict
    trace: devtrace.Trace
    peaks: dict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    with contextlib.suppress(BrokenPipeError):
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
