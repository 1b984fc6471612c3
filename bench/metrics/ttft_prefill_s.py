"""Engine scheduling: a request's prefill inside the engine, from its slot
claim to its first token.

From the end of the request's ``engine/claim`` span to the end of its
``engine/prefill_chunk`` span with ``last=1`` (the chunk that covers the
prompt's tail and reads the first token back), same ``rid``; the mean
over requests whose last chunk ended in the window.  The rest of
``ttft_mean_s`` is the wait to be claimed.  None without engine spans."""
import enginetrace


def read(r):
    eng = enginetrace.of(r)
    return None if eng is None else mean_prefill_s(eng)


def mean_prefill_s(trace):
    claimed = {s.args["rid"]: s.end_ns for s in trace.engine
               if s.name == "engine/claim" and "slot" in s.args}
    spans = [s.end_ns - claimed[s.args["rid"]]
             for s in trace.named("prefill_chunk")
             if s.args.get("last") == 1 and s.args.get("rid") in claimed]
    return sum(spans) / len(spans) * 1e-9 if spans else None
