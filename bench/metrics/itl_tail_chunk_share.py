"""Engine scheduling: how much of the slowest inter-token intervals the
device spent on prefill chunks.

A token time is the end of an ``engine/sample`` span in the window (the
decode step's tokens reach the host); a step's interval is the time since
the token time before it, weighted by the step's ``tokens``.  Over the
intervals at or above the token-weighted 95th percentile: the share of
their time, token-weighted, in which a run of
``PagedLM._prefill_chunk_impl`` was on the device.  None without engine
spans (a program that does not place them) or without a device trace."""
import devtrace
import enginetrace

PROGRAM = "_prefill_chunk_impl"


def read(r):
    eng = enginetrace.of(r)
    return None if eng is None else share(eng)


def share(trace):
    samples = trace.named("sample")
    if len(samples) < 2 or not trace.modules:
        return None
    chunks = devtrace.merged((e.start_ns, e.end_ns)
                             for evs in trace.module_runs(PROGRAM).values()
                             for e in evs)
    steps = sorted((b.end_ns - a.end_ns, a.end_ns, b.end_ns,
                    b.args.get("tokens", 0))
                   for a, b in zip(samples, samples[1:]))
    total = sum(s[3] for s in steps)
    if not total:
        return None
    seen, cut = 0, len(steps) - 1
    for i, s in enumerate(steps):
        seen += s[3]
        if seen >= 0.95 * total:
            cut = i
            break
    tail = [s for s in steps if s[0] >= steps[cut][0]]
    busy = sum(n * sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in chunks)
               for _, lo, hi, n in tail)
    return 100.0 * busy / sum(n * dur for dur, _, _, n in tail)
