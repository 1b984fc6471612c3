"""Kernel: the paged-attention kernel's share of its roofline.

Least time = max(bytes / peak HBM bandwidth, FLOPs / peak bf16 compute)
for the K/V of the live tokens (each active slot's context) plus q and
out, all layers, summed over the window's decode calls (``flops.py``).
Kernel time = summed device time of the Pallas kernel, the one
``tpu_custom_call`` inside each run of the decode program (the kernel has
no name of its own, so it is found as that custom call).  Per call means
on both sides, so a call missed by the profiler does not skew it."""
import flops

PROGRAM = "_decode_impl"


def is_kernel(op) -> bool:
    return op.name.startswith("tpu_custom_call:")


def read(r):
    calls = r.records.get("decode_calls")
    runs = r.trace.module_runs(PROGRAM)
    n_runs = sum(len(v) for v in runs.values())
    if not calls or not n_runs:
        return None
    kernel_ns = sum(o.dur_ns for dev, evs in runs.items() for run in evs
                    for o in r.trace.ops_in(run, dev) if is_kernel(o))
    if not kernel_ns:
        return None
    least = 0.0
    for _, _, contexts in calls:
        f, b = flops.paged_attention_cost(r.cfg["model"], contexts)
        least += max(b / r.peaks["hbm_bytes_per_s"],
                     f / r.peaks["bf16_flops_per_s"])
    return 100.0 * (least / len(calls)) / (kernel_ns * 1e-9 / n_runs)
