"""Serving window: the program's ``PagedLM`` + ``Engine(chunked_prefill=
True)`` under an open loop.

Set-up makes the weights from the seed, builds the engine at the
configuration's deployment sizes, and runs the mix's warm requests in,
which compiles (or loads from the cache) the decode program at
``max_batch`` and the one prefill-chunk program.  The window then submits
each request when it is due and steps the engine while it has work.

The benchmark sees the engine only from outside: it wraps the PagedLM
instance's ``claim_slot``, ``prefill_slot_chunk`` and ``decode_batch`` to
time admissions, first tokens and decode tokens on the host clock, and
places a ``bench/...`` profiler span around each.  After the window it
frees the program's state and checks a sample of finished requests
against the float32 reference (``bench/reference``): the widest gap by
which a served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import harness
import trafficlib
import weights

E2E_UNITS = {"ttft_mean_s": "s", "itl_p95_ms": "ms",
             "output_tokens_per_s": "tokens/s"}


class Recorder:
    """Host timestamps of the engine's work, taken at the PagedLM calls."""

    def __init__(self, eng, clock: harness.Clock) -> None:
        import jax
        lm = eng.lm
        self.claim_t: dict[int, float] = {}    # slot -> last claim time
        self.first_t: dict[int, float] = {}    # rid -> first token time
        self.last_t: dict[int, float] = {}     # slot -> last token time
        self.token_t: list[tuple[float, int]] = []   # (time, tokens)
        self.gaps: list[tuple[float, float]] = []    # (time, gap)
        self.decode_calls: list[tuple[float, float, list[int]]] = []
        span = jax.profiler.TraceAnnotation
        claim, chunk, decode = (lm.claim_slot, lm.prefill_slot_chunk,
                                lm.decode_batch)

        def claim_slot(*a, **k):
            with span("bench/admit"):
                slot = claim(*a, **k)
            self.claim_t[slot] = clock.now()
            return slot

        def prefill_slot_chunk(slot, prompt, start, n):
            with span("bench/chunk"):
                tok = chunk(slot, prompt, start, n)
            t1 = clock.now()
            if tok is not None:
                self.first_t[eng.prefilling[slot].rid] = t1
                self.last_t[slot] = t1
                self.token_t.append((t1, 1))
            return tok

        def decode_batch(tokens, active):
            live = np.flatnonzero(active)
            contexts = (lm.seq_lens[live] + 1).tolist()
            t0 = clock.now()
            with span("bench/decode"):
                out = decode(tokens, active)
            t1 = clock.now()
            self.decode_calls.append((t0, t1, contexts))
            self.token_t.append((t1, len(live)))
            for s in live.tolist():
                self.gaps.append((t1, t1 - self.last_t[s]))
                self.last_t[s] = t1
            return out

        lm.claim_slot = claim_slot
        lm.prefill_slot_chunk = prefill_slot_chunk
        lm.decode_batch = decode_batch


def _pages(lm) -> tuple[int, int]:
    """(pages claimed by slots, pages that hold a live token)."""
    live = -(-lm.seq_lens[list(lm.slot_pages)] // lm.page)
    return lm.n_pages - len(lm.allocator.free), int(live.sum())


def _request(engine_mod, rid: int, r: trafficlib.ServeRequest):
    return engine_mod.Request(rid=rid, prompt=r.prompt,
                              max_new_tokens=r.max_new)


def _waited(done_at: float | None, due: float, t1: float) -> float:
    """Due to done; one not done by the window's end ``t1`` counts with
    the time it has waited, so a stall cannot hide."""
    return (done_at if done_at is not None and done_at <= t1 else t1) - due


def serve_metrics(due_at: dict, first_t: dict, claimed: dict, token_t: list,
                  gaps: list, t0: float, t1: float) -> tuple[dict, list]:
    """End-to-end metrics of a window [t0, t1] from host timestamps:
    ``due_at`` rid -> due time of every request due in the window,
    ``first_t`` / ``claimed`` rid -> first token / slot claim time,
    ``token_t`` (time, tokens produced), ``gaps`` (time, gap before a
    token).  Returns (metrics, queue waits, TTFTs).

    TTFT is a mean over every request due: its tail sits on the cliffs
    between prompts of 5 and 6 prefill chunks and read 27-29% apart
    between runs (PERF.md), more than any bound admits."""
    ttft = [_waited(first_t.get(r), due, t1) for r, due in due_at.items()]
    waits = [_waited(claimed.get(r), due, t1) for r, due in due_at.items()]
    tokens = sum(n for t, n in token_t if t0 <= t <= t1)
    itl = [g for t, g in gaps if t0 <= t <= t1]
    return ({"ttft_mean_s": sum(ttft) / len(ttft),
             "itl_p95_ms": harness.percentile(itl, 95) * 1e3,
             "output_tokens_per_s": tokens / (t1 - t0)}, waits, ttft)


def run(ctx) -> dict:
    import jax
    from program import arch_config
    from repro.serving import engine as engine_mod

    cfg, mix, clock = ctx.cfg, ctx.mix, ctx.clock
    model, dep = cfg["model"], cfg["deployment"]
    span = jax.profiler.TraceAnnotation
    arch = arch_config(cfg)
    warm, window = harness.generator(mix).serve_requests(
        mix, ctx.seed, ctx.seconds, model["vocab_size"])
    params = weights.make(model, ctx.seed, device=ctx.devices[0])
    jax.block_until_ready(params)
    ctx.mark("weights")
    lm = engine_mod.PagedLM(arch, params, max_batch=dep["max_batch"],
                            max_seq=dep["max_seq"],
                            page_tokens=dep["page_tokens"],
                            pool_pages=dep["kv_pool_pages"], tp_axes=())
    eng = engine_mod.Engine(lm, chunked_prefill=True,
                            prefill_chunk_pages=dep["prefill_chunk_pages"])
    rec = Recorder(eng, clock)
    ctx.mark("engine")
    # warm: fill the running batch (compiles or loads both programs)
    for i, r in enumerate(warm):
        eng.submit(_request(engine_mod, -1 - i, r))
    while eng.pending or eng.prefilling:
        eng.step()
    eng.step()
    ctx.mark("warm traffic")
    stall0 = eng.decode_stall_s
    compiles0 = ctx.compiles.snapshot()

    # -- the measured window ------------------------------------------------
    due_at, failed, backlog, pages = {}, 0, [], []
    waiting: dict[int, object] = {}      # rid -> request not yet admitted
    claimed: dict[int, float] = {}       # rid -> slot claim time
    submitted = 0
    with ctx.recording():
        with span("bench/window"):
            t0 = clock.now()
            ctx.mark_window_start(t0)
            deadline = t0 + ctx.seconds
            while True:
                now = clock.now()
                if now >= deadline:
                    break
                while submitted < len(window) and \
                        t0 + window[submitted].due_s <= now:
                    r = window[submitted]
                    due_at[submitted] = t0 + r.due_s
                    waiting[submitted] = _request(engine_mod, submitted, r)
                    eng.submit(waiting[submitted])
                    submitted += 1
                if not (eng.pending or eng.prefilling or eng.running):
                    nxt = (t0 + window[submitted].due_s
                           if submitted < len(window) else deadline)
                    with span("bench/wait"):
                        time.sleep(max(min(nxt, deadline) - clock.now(), 0))
                    continue
                try:
                    with span("bench/step"):
                        eng.step()
                except ValueError:          # a request the engine refuses
                    waiting.pop(eng.pending.pop(0).rid)
                    failed += 1
                # a slot is claimed at most once a step: its last claim
                # time is this request's
                for rid in [r for r, q in waiting.items()
                            if q.slot is not None]:
                    claimed[rid] = rec.claim_t[waiting.pop(rid).slot]
                backlog.append((clock.now() - t0, len(eng.pending)))
                pages.append(_pages(lm))
            t1 = clock.now()
    # due before the deadline but not yet handed over (a step overran):
    # attempted all the same, waiting to the window's end
    for i in range(submitted, len(window)):
        if window[i].due_s < ctx.seconds:
            due_at[i] = t0 + window[i].due_s
    compiles = np.subtract(ctx.compiles.snapshot(), compiles0).tolist()
    seconds = t1 - t0

    # -- end-to-end metrics ---------------------------------------------------
    calls = [c for c in rec.decode_calls if t0 <= c[0] <= t1]
    running = [len(c[2]) for c in calls] or [0]
    e2e, waits, ttft = serve_metrics(due_at, rec.first_t, claimed,
                                     rec.token_t, rec.gaps, t0, t1)
    claimed_pages, live_pages = np.array(pages or [(0, 0)]).T
    harness.log(f"KV pool of {lm.n_pages} pages: claimed peak "
                f"{claimed_pages.max()} mean {claimed_pages.mean():.0f}, "
                f"live peak {live_pages.max()} mean {live_pages.mean():.0f}")
    harness.log(f"{len(ttft)} requests due: TTFT p50 "
                f"{harness.percentile(ttft, 50):.3f} s, p90 "
                f"{harness.percentile(ttft, 90):.3f} s; mean running batch "
                f"{sum(running) / len(running):.1f}")
    device = harness.device_facts(ctx.devices)
    records = {
        "window_s": seconds, "requests_due": len(due_at),
        "queue_waits_s": waits,
        "decode_stall_s": eng.decode_stall_s - stall0,
        "decode_calls": calls, "backlog": backlog,
        "compiles_in_window": compiles,
        "max_running": max(running),
        "mean_running": sum(running) / len(running),
        "pool_pages": lm.n_pages,
        "claimed_pages_peak": int(claimed_pages.max()),
        "live_pages_peak": int(live_pages.max()),
    }

    # -- correct: a seeded sample of finished requests vs the reference -------
    check = cfg["check"]
    done = sorted(eng.finished, key=lambda r: r.rid)
    pick = np.random.default_rng([ctx.seed % (1 << 64), 5])
    by_length = sorted(range(len(done)), key=lambda i: -len(done[i].out_tokens))
    take = by_length[:1] + sorted(pick.choice(
        by_length[1:], size=min(len(done) - 1, check["sample_requests"] - 1),
        replace=False).tolist()) if done else []
    sample = [(np.asarray(done[i].prompt), np.asarray(done[i].out_tokens))
              for i in take]
    del rec, eng, lm, params
    gc.collect()
    gap, served = reference_gap(ctx, sample)
    checks = [{"name": "max_logit_gap", "value": gap,
               "limit": check["max_logit_gap"]},
              {"name": "served_tokens_checked", "value": served,
               "limit": check["min_tokens_checked"], "at_least": True}]
    return {"attempted": len(due_at), "failed": failed,
            "e2e": {k: (v, E2E_UNITS[k]) for k, v in e2e.items()},
            "checks": checks, "device": device, "records": records,
            "sample": sample}


def reference_gap(ctx, sample) -> tuple[float, int]:
    """Widest reference-logit gap of the served tokens in ``sample``."""
    ref = harness.plugin("reference", ctx.cfg["reference"])
    model = ctx.cfg["model"]
    params = ref.to_f32(weights.make(model, ctx.seed,
                                     device=ctx.devices[0]))
    widest, served = 0.0, 0
    for prompt, out in sample:
        g = ref.served_gaps(model, params, prompt, out,
                            seq_pad=ctx.cfg["deployment"]["max_seq"],
                            rows_pad=ctx.mix["output_tokens"]["max"])
        widest = max(widest, float(g.max()))
        served += len(out)
    return widest, served


def control(ctx, out) -> dict:
    """The control's reading on the run's sample: the gap of the token that
    the reference computed in fp8 puts first, at the same positions, as
    the run's checks with the control's gap in the program's place."""
    ref = harness.plugin("reference", ctx.cfg["reference"])
    model = ctx.cfg["model"]
    params = ref.to_f32(weights.make(model, ctx.seed, device=ctx.devices[0]))
    gap = max(float(ref.control_gaps(
        model, params, p, o, seq_pad=ctx.cfg["deployment"]["max_seq"],
        rows_pad=ctx.mix["output_tokens"]["max"]).max())
        for p, o in out["sample"])
    checks = [dict(c, value=gap) if c["name"] == "max_logit_gap" else c
              for c in out["checks"]]
    return {"control_gap": gap, "control_checks": checks}
