"""Device: the share of the window in which no operation ran on the chip
(1 - union of operation intervals / window), mean over the chips."""


def read(r):
    devs = list(r.trace.ops)
    if not devs:
        return None
    lo, hi = r.trace.window()
    busy = sum(r.trace.busy_ns(d) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (hi - lo))
