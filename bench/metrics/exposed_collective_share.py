"""Collectives: the share of the training step's device time in which a
collective op (the fabric executor's ring ``ppermute`` rounds lower to
collective-permutes) runs while no compute op runs on that chip, mean
over the chips.  The step is the program with the most device time in
the window."""

from devtrace import merged

MARKS = ("collective-permute", "all-reduce", "all-gather", "reduce-scatter",
         "all-to-all")


def is_collective(op) -> bool:
    return any(m in op.name for m in MARKS)


def _minus(spans, cover):
    """Length of ``spans`` not covered by ``cover`` (both merged)."""
    total, j = 0.0, 0
    for a, b in spans:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            lo, hi = cover[k]
            if lo > cur:
                total += lo - cur
            cur = max(cur, hi)
            k += 1
        if b > cur:
            total += b - cur
    return total


def read(r):
    step = r.trace.dominant_program()
    if step is None:
        return None
    shares = []
    for dev, runs in r.trace.module_runs(step).items():
        step_ns = sum(e.dur_ns for e in runs)
        ops = [o for run in runs for o in r.trace.ops_in(run, dev)
               if not o.container]
        coll = merged((o.start_ns, o.end_ns) for o in ops if is_collective(o))
        if not coll or not step_ns:
            continue
        comp = merged((o.start_ns, o.end_ns) for o in ops
                       if not is_collective(o))
        shares.append(_minus(coll, comp) / step_ns)
    return 100.0 * sum(shares) / len(shares) if shares else None
