"""Where a traced serving window's time went, in the engine's own words.

  python3 bench/engine_breakdown.py [<trace directory>]

Reads the ``.xplane.pb`` of a ``--trace 1`` run (default: the chat cell's,
``bench_out/trace/qwen2-0.5b.chat``) through ``enginetrace`` and prints
one JSON object: for the decode and prefill-chunk programs, their runs and
device seconds by named scope (ops in no scope under ""); the idle gaps
charged to the innermost benchmark or engine span; the ``waited_ms`` of
the window's slot claims (submit to claim, inside the engine); the engine
spans counted by name; the two engine readers' values; and the trace
file's size and read time.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import enginetrace                                             # noqa: E402
import harness                                                 # noqa: E402
import run as bench_run                                        # noqa: E402

PROGRAMS = ("_decode_impl", "_prefill_chunk_impl")


def by_scope(trace: enginetrace.EngineTrace, program: str) -> dict:
    """Device seconds of ``program``'s ops in the window, by named scope
    (loops and calls, which hold other ops, left out)."""
    runs = trace.module_runs(program)
    out: dict[str, float] = {}
    for dev, evs in runs.items():
        for run in evs:
            for o in trace.ops_in(run, dev):
                if not o.container:
                    out[o.scope] = out.get(o.scope, 0.0) + o.dur_ns * 1e-9
    n = sum(len(v) for v in runs.values())
    run_s = sum(e.dur_ns for v in runs.values() for e in v) * 1e-9
    return {"runs": n, "device_s": run_s,
            "by_scope_s": dict(sorted(out.items(), key=lambda kv: -kv[1]))}


def summary(trace: enginetrace.EngineTrace) -> dict:
    waits = [s.args["waited_ms"] for s in trace.named("claim")
             if "slot" in s.args]
    counts: dict[str, int] = {}
    for s in trace.engine:
        counts[s.name] = counts.get(s.name, 0) + 1
    out = {p: by_scope(trace, p) for p in PROGRAMS}
    out["idle_gaps"] = bench_run.breakdown(trace)["idle_gaps"]
    if waits:
        out["claim_waited_ms"] = {
            "n": len(waits), "mean": sum(waits) / len(waits),
            "p50": harness.percentile(waits, 50),
            "p90": harness.percentile(waits, 90)}
    out["engine_spans"] = counts
    for name in ("itl_tail_chunk_share", "ttft_prefill_s"):
        mod = harness.plugin("metrics", name)
        out[name] = (mod.share(trace) if name.startswith("itl")
                     else mod.mean_prefill_s(trace))
    return out


def main(argv: list[str]) -> int:
    directory = Path(argv[0]) if argv else \
        enginetrace.TRACES / "qwen2-0.5b.chat"
    (path,) = sorted(directory.rglob("*.xplane.pb"))
    t0 = time.perf_counter()
    trace = enginetrace.load(directory)
    out = {"trace_bytes": path.stat().st_size,
           "read_s": time.perf_counter() - t0}
    out.update(summary(trace))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
