"""Engine admission: 90th percentile of the wait from a request's due time
to the moment ``PagedLM.claim_slot`` gave it a slot (host clock, every
request due in the window; one still unadmitted counts with its wait so
far)."""
from harness import percentile


def read(r):
    waits = r.records.get("queue_waits_s")
    return percentile(waits, 90) if waits else None
