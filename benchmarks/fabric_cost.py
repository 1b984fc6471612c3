"""Fabric CollectiveSchedule cost model — predicted vs measured.

The schedule IR gives every collective a predicted completion time for
free (``fabric.estimate`` prices each step's transfers with the apelink
``NetModel``).  This bench reports those predictions across tori and
collectives, verifies the model's structural claims, and — where the host
can fake an 8-device ring — times the *executed* schedule so BENCH output
tracks predicted vs measured collective time.

Checked claims:
  * dual-DMA bidirectional rings finish in half the rounds and strictly
    less predicted time than unidirectional ones (paper §2.1);
  * predicted time is monotone in message size and in detour hops;
  * a fault-rewritten schedule around a dead link never gets cheaper.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from repro.core import fabric
from repro.core.topology import Torus

MiB = 1 << 20


def _sched_rows() -> list[dict]:
    rows = []
    cases = [
        ("ring8", Torus((8,)), ("x",)),
        ("torus4x4", Torus((4, 4)), ("x", "y")),
        ("torus4x4x4", Torus((4, 4, 4)), ("x", "y", "z")),
        ("pod16x16", Torus((16, 16)), ("data", "model")),
    ]
    for name, torus, axes in cases:
        sched = fabric.lower_all_reduce(torus, axes)
        est = fabric.estimate(sched, 4 * MiB)
        rows.append({"bench": "fabric_cost",
                     "metric": f"allreduce_{name}_pred_ms",
                     "value": est.total_s * 1e3,
                     "note": f"{est.rounds} rounds, 4 MiB"})
        rows.append({"bench": "fabric_cost",
                     "metric": f"allreduce_{name}_algbw_GBps",
                     "value": fabric.algorithmic_bandwidth(sched, 4 * MiB)
                     / 1e9, "note": "input bytes / predicted time"})
    return rows


def _claim_rows() -> list[dict]:
    t8 = Torus((8,))
    bidi = fabric.lower_all_reduce(t8, ("x",), bidirectional=True)
    uni = fabric.lower_all_reduce(t8, ("x",), bidirectional=False)
    t_bidi = fabric.estimate(bidi, 4 * MiB).total_s
    t_uni = fabric.estimate(uni, 4 * MiB).total_s
    rows = [
        {"bench": "fabric_cost", "metric": "bidi_rounds", "value":
         bidi.rounds,
         "note": f"{bidi.n_messages} ppermutes fused to 2-concurrent rounds"},
        {"bench": "fabric_cost", "metric": "bidi_speedup", "value":
         t_uni / t_bidi, "gate": "higher",
         "note": "dual-DMA predicted time cut"},
    ]
    # fault detour: kill link (0,1) on the 8-ring -> the 0->1 transfer
    # takes the 7-hop detour; schedule may never get cheaper
    faults = fabric.FaultMap.normalized(links=[(0, 1)])
    detour = fabric.rewrite(bidi, faults)
    rows.append({"bench": "fabric_cost", "metric": "detour_max_hops",
                 "value": detour.max_hops, "note": "dead link (0,1), 8-ring"})
    rows.append({"bench": "fabric_cost", "metric": "detour_cost_ratio",
                 "value": fabric.estimate(detour, 4 * MiB).total_s / t_bidi,
                 "note": "rewritten / clean predicted time"})
    # shrunk ring: node 3 dead -> 7 live ranks
    shrunk = fabric.rewrite(bidi, fabric.FaultMap.normalized(nodes=[3]))
    rows.append({"bench": "fabric_cost", "metric": "shrunk_ring_size",
                 "value": len(shrunk.phases[0].ring), "note": "node 3 dead"})
    return rows


_MEASURE_SRC = textwrap.dedent("""
    import os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    from repro.core import collectives as C
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("x",))
    x = np.random.default_rng(0).normal(size=(8, 1 << 20)) \\
        .astype(np.float32)
    f = C.make_stacked_all_reduce(mesh, ("x",))
    f(x).block_until_ready()          # compile
    t0 = time.perf_counter()
    reps = 5
    for _ in range(reps):
        f(x).block_until_ready()
    print((time.perf_counter() - t0) / reps)
""")


def _measured_rows() -> list[dict]:
    """Time the executed 8-ring schedule on forced host devices.

    Host-CPU ppermutes are not APEnet+ links, so the measured/predicted
    ratio is reported, not checked — the point is that both numbers come
    from the SAME schedule object.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    proc = subprocess.run([sys.executable, "-c", _MEASURE_SRC],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    if proc.returncode != 0:
        raise RuntimeError("8-host-device ring measurement failed:\n"
                           + proc.stderr[-4000:])
    measured = float(proc.stdout.strip().splitlines()[-1])
    sched = fabric.lower_all_reduce(Torus((8,)), ("x",))
    pred = fabric.estimate(sched, 4 * MiB).total_s
    return [
        {"bench": "fabric_cost", "metric": "allreduce_ring8_measured_ms",
         "value": measured * 1e3, "note": "8 host devices, 4 MiB"},
        {"bench": "fabric_cost", "metric": "measured_over_predicted",
         "value": measured / pred,
         "note": "host CPU fabric vs APEnet+ model"},
    ]


def run() -> list[dict]:
    return _sched_rows() + _claim_rows() + _measured_rows()


def check(rows) -> list[str]:
    vals = {r["metric"]: r["value"] for r in rows}
    errs = []
    if vals["bidi_speedup"] <= 1.0:
        errs.append(f"dual-DMA not faster: x{vals['bidi_speedup']:.2f}")
    sched8 = fabric.lower_all_reduce(Torus((8,)), ("x",))
    if vals["bidi_rounds"] != sched8.rounds \
            or sched8.n_messages != 2 * sched8.rounds:
        errs.append("bidirectional fusion lost: rounds/messages mismatch")
    if vals["detour_cost_ratio"] < 1.0:
        errs.append("fault detour made the schedule cheaper")
    if vals["detour_max_hops"] <= 1:
        errs.append("dead link produced no detour hops")
    if vals["shrunk_ring_size"] != 7:
        errs.append(f"shrunk ring size {vals['shrunk_ring_size']} != 7")
    # size monotonicity on the 4x4x4 schedule
    sched = fabric.lower_all_reduce(Torus((4, 4, 4)), ("x", "y", "z"))
    times = [fabric.estimate(sched, n).total_s
             for n in (1 << 12, 1 << 16, 1 << 20, 1 << 24)]
    if not all(a < b for a, b in zip(times, times[1:])):
        errs.append("predicted time not monotone in message size")
    return errs


if __name__ == "__main__":
    for r in run():
        print(f"{r['bench']},{r['metric']},{r['value']}")
