"""Engine scheduling: the share of the window in which the running batch
waited on admission and prefill chunks (the program's own
``Engine.decode_stall_s`` counter, its growth over the window)."""


def read(r):
    if "decode_stall_s" not in r.records:
        return None
    return 100.0 * r.records["decode_stall_s"] / r.records["window_s"]
