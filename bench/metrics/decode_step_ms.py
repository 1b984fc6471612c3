"""Model step: device time of one run of the decode program
(``PagedLM._decode_impl``), mean over its runs in the window."""

PROGRAM = "_decode_impl"


def read(r):
    runs = [e for evs in r.trace.module_runs(PROGRAM).values() for e in evs]
    if not runs:
        return None
    return sum(e.dur_ns for e in runs) / len(runs) * 1e-6
