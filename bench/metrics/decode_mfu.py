"""Whole decode step: model FLOPs of the step's live tokens (each active
slot's token through every layer and the head, with attention over its
context; ``flops.decode_flops``) over the device time of the decode
program times the chip's peak bf16 rate, mean per call."""
import flops

PROGRAM = "_decode_impl"


def read(r):
    calls = r.records.get("decode_calls")
    runs = [e for evs in r.trace.module_runs(PROGRAM).values() for e in evs]
    if not calls or not runs:
        return None
    per_call = sum(flops.decode_flops(r.cfg["model"], c[2])
                   for c in calls) / len(calls)
    step_s = sum(e.dur_ns for e in runs) / len(runs) * 1e-9
    return 100.0 * per_call / (step_s * r.peaks["bf16_flops_per_s"])
