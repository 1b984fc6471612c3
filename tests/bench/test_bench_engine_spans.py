"""The engine's own spans in the benchmark's trace reading (``enginetrace``)
and the two readers built on them, ``itl_tail_chunk_share`` and
``ttft_prefill_s``: on a CPU recording of a tiny engine, on a small trace
recorded on a TPU v5e chip (a few decode steps of qwen2-0.5b with prefill
chunks between them) and on traces built by hand."""
import json
from pathlib import Path

import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ on sys.path)
from tiny import harness, bench_run

import devtrace
import enginetrace

FIXTURES = Path(__file__).parent / "fixtures"
CELL = "qwen2-0.5b.chat"
NAMES = {"step", "claim", "prefill_chunk", "decode", "sample", "retire"}


def _read(name, reading):
    return harness.plugin("metrics", name).read(reading)


def _reading(trace):
    return bench_run.Reading(harness.config("qwen2-0.5b-serve"),
                             harness.traffic("chat-steady"), {}, trace, {})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A tiny engine's window profiled on the CPU where run.py keeps the
    chat cell's trace (under a temporary root)."""
    import jax
    from repro.serving.engine import Engine, PagedLM, Request
    from program import arch_config
    import weights
    cfg = tiny.serve_cfg()
    params = weights.make(cfg["model"], 5, device=jax.devices()[0])
    lm = PagedLM(arch_config(cfg), params, max_batch=4, max_seq=128,
                 page_tokens=16, tp_axes=())
    eng = Engine(lm, chunked_prefill=True, prefill_chunk_pages=1)
    rng = np.random.default_rng(0)
    root = tmp_path_factory.mktemp("traces")
    with devtrace.recording(root / CELL, True):
        with jax.profiler.TraceAnnotation("bench/window"):
            for rid, n in enumerate((40, 20, 33)):
                eng.submit(Request(rid=rid, max_new_tokens=4,
                                   prompt=rng.integers(0, 512, n)
                                   .astype(np.int32)))
            eng.run_to_completion()
    return root


def test_load_keeps_engine_spans_and_their_arguments(recorded):
    trace = enginetrace.load(recorded / CELL)
    assert {s.name.removeprefix("engine/") for s in trace.engine} == NAMES
    assert [s.name for s in trace.spans] == ["bench/window"]
    chunks = trace.named("prefill_chunk")
    assert {c.args["rid"] for c in chunks} == {0, 1, 2}
    assert sum(c.args["last"] for c in chunks) == 3
    assert all(s.args["tokens"] > 0 for s in trace.named("sample"))


def test_of_finds_this_runs_trace_and_no_other(recorded, monkeypatch):
    monkeypatch.setattr(enginetrace, "TRACES", recorded)
    base = devtrace.load(recorded / CELL)
    found = enginetrace.of(_reading(base))
    assert found is not None and found.window() == base.window()
    lo, hi = base.window()
    other = devtrace.Trace({}, {}, [devtrace.Event("bench/window", "", lo + 1,
                                                   hi - lo)])
    assert enginetrace.of(_reading(other)) is None
    monkeypatch.setattr(enginetrace, "TRACES", recorded / "none")
    assert enginetrace.of(_reading(base)) is None


def test_readers_on_a_cpu_recording(recorded, monkeypatch):
    """No device plane on the CPU: the chunk share reads nothing; the
    prefill time reads the host spans."""
    monkeypatch.setattr(enginetrace, "TRACES", recorded)
    reading = _reading(devtrace.load(recorded / CELL))
    assert _read("itl_tail_chunk_share", reading) is None
    trace = enginetrace.load(recorded / CELL)
    claim = {s.args["rid"]: s.end_ns for s in trace.named("claim")}
    last = {s.args["rid"]: s.end_ns for s in trace.named("prefill_chunk")
            if s.args["last"]}
    want = sum(last[r] - claim[r] for r in last) / 3 * 1e-9
    assert _read("ttft_prefill_s", reading) == pytest.approx(want)
    assert want > 0


# -- recorded on the chip ---------------------------------------------------

@pytest.fixture(scope="module")
def chip():
    """qwen2-0.5b at full width, 16 slots, 256-token chunks: two requests
    decoding, then one of three chunks and one of one chunk admitted
    (device ops: every kernel op, and those over 20 us of the window's
    first decode and chunk runs)."""
    return enginetrace.EngineTrace.from_json(
        json.loads((FIXTURES / "engine_trace.json").read_text()))


def test_chip_trace_holds_every_engine_span_nested_in_its_step(chip):
    assert {s.name.removeprefix("engine/") for s in chip.engine} == NAMES
    steps = [s for s in chip.engine if s.name == "engine/step"]
    for s in chip.engine:
        if s.name != "engine/step":
            assert any(t.start_ns <= s.start_ns and s.end_ns <= t.end_ns
                       for t in steps), s.name
    for rid, n_chunks in ((0, 3), (1, 1)):
        (claim,) = [s for s in chip.named("claim") if s.args["rid"] == rid]
        chunks = [s for s in chip.named("prefill_chunk")
                  if s.args["rid"] == rid]
        assert [c.args["start"] for c in chunks] == [
            256 * i for i in range(n_chunks)]
        assert [c.args["last"] for c in chunks] == [0] * (n_chunks - 1) + [1]
        assert {c.args["slot"] for c in chunks} == {claim.args["slot"]}


def test_chip_trace_names_the_kernel_and_the_scopes(chip):
    (dev, ops), = chip.ops.items()
    kernel = [o for o in ops if o.name.startswith("tpu_custom_call:")]
    assert kernel and all(
        o.name.startswith("tpu_custom_call:paged_attention")
        and o.scope == "attention" for o in kernel)
    for program, scopes in (("_decode_impl", {"", "attention", "mlp"}),
                            ("_prefill_chunk_impl", {"", "mlp", "head"})):
        run = chip.module_runs(program)[dev][0]
        assert scopes <= {o.scope for o in chip.ops_in(run, dev)}, program
    top = bench_run.breakdown(chip)["device_ops"][0][0]
    assert top.startswith("jit__decode_impl/tpu_custom_call:paged_attention")


def test_readers_on_the_chip_trace(chip):
    """Token-weighted p95 falls on the longest interval alone (it closes
    with 3 of the window's 27 tokens); one chunk ran inside it."""
    (dev,) = chip.ops
    ends = [s.end_ns for s in chip.named("sample")]
    assert len(ends) == 12
    a, b = max(zip(ends, ends[1:]), key=lambda g: g[1] - g[0])
    (run,) = [r for r in chip.module_runs("_prefill_chunk_impl")[dev]
              if a <= r.start_ns and r.end_ns <= b]
    share = harness.plugin("metrics", "itl_tail_chunk_share").share(chip)
    assert share == pytest.approx(100 * run.dur_ns / (b - a))
    assert 0 < share < 100
    claim = {s.args["rid"]: s.end_ns for s in chip.named("claim")}
    last = {s.args["rid"]: s.end_ns for s in chip.named("prefill_chunk")
            if s.args["last"]}
    want = ((last[0] - claim[0]) + (last[1] - claim[1])) / 2 * 1e-9
    mean = harness.plugin("metrics", "ttft_prefill_s").mean_prefill_s
    assert mean(chip) == pytest.approx(want)


# -- traces built by hand ----------------------------------------------------

def _span(name, end, dur=5.0, **args):
    return enginetrace.Span("engine/" + name, "", end - dur, dur, args=args)


def _hand(engine, chunk_runs=(), hi=10_000.0):
    dev = "/device:TPU:0"
    runs = [devtrace.Event("jit__prefill_chunk_impl(1)", "", a, b - a)
            for a, b in chunk_runs]
    return enginetrace.EngineTrace(
        {dev: []}, {dev: runs},
        [devtrace.Event("bench/window", "", 0.0, hi)], engine=engine)


def test_itl_tail_chunk_share_by_hand():
    """Intervals 100..118 ns of one token each, then one of 400 ns closing
    with two tokens; a chunk ran 200 ns inside the long one and 50 ns
    inside the 118 ns one.  Token-weighted p95 (21 tokens: 19.95) falls on
    the 400 ns interval alone: 200 / 400."""
    ends, t = [0.0], 0.0
    for d in range(100, 119):
        t += d
        ends.append(t)
    ends.append(t + 400)
    samples = [_span("sample", e, tokens=2 if i == len(ends) - 1 else 1)
               for i, e in enumerate(ends)]
    long_lo, mid_lo = ends[-2], ends[-3]
    runs = [(long_lo + 100, long_lo + 300), (mid_lo + 10, mid_lo + 60)]
    share = harness.plugin("metrics", "itl_tail_chunk_share").share
    assert share(_hand(samples, runs)) == pytest.approx(50.0)
    # one token closing the long interval: the 118 ns one joins the tail
    samples[-1].args["tokens"] = 1
    assert share(_hand(samples, runs)) == pytest.approx(
        100 * (200 + 50) / (400 + 118))
    assert share(_hand(samples, [])) == 0.0


def test_ttft_prefill_s_by_hand():
    """rid 5: claimed at 100, last chunk ends 400; rid 7: a failed claim
    (no slot), claimed at 120, last chunk ends 520; rid 6's last chunk
    ends after the window."""
    engine = [_span("claim", 100, rid=5, slot=0, waited_ms=1.0),
              _span("claim", 50, rid=7, waited_ms=1.0),
              _span("claim", 120, rid=7, slot=1, waited_ms=2.0),
              _span("claim", 150, rid=6, slot=2, waited_ms=2.0),
              _span("prefill_chunk", 200, rid=5, slot=0, start=0, last=0),
              _span("prefill_chunk", 400, rid=5, slot=0, start=256, last=1),
              _span("prefill_chunk", 520, rid=7, slot=1, start=0, last=1),
              _span("prefill_chunk", 1500, rid=6, slot=2, start=0, last=1)]
    mean = harness.plugin("metrics", "ttft_prefill_s").mean_prefill_s
    assert mean(_hand(engine, hi=1000.0)) == pytest.approx(350e-9)


def test_no_engine_spans_read_nothing():
    """A program that places no engine spans (the hand-built trace, and the
    older devtrace fixture read as an EngineTrace) reads None."""
    old = enginetrace.EngineTrace.from_json(
        json.loads((FIXTURES / "serve_trace.json").read_text()))
    assert old.engine == [] and old.window() == devtrace.Trace.from_json(
        json.loads((FIXTURES / "serve_trace.json").read_text())).window()
    itl = harness.plugin("metrics", "itl_tail_chunk_share").share
    ttft = harness.plugin("metrics", "ttft_prefill_s").mean_prefill_s
    for trace in (old, _hand([], [(10.0, 20.0)])):
        assert itl(trace) is None and ttft(trace) is None


def test_readers_read_nothing_without_the_runs_trace(monkeypatch, tmp_path):
    monkeypatch.setattr(enginetrace, "TRACES", tmp_path)
    reading = _reading(devtrace.Trace({}, {}, [
        devtrace.Event("bench/window", "", 0.0, 1.0)]))
    for name in ("itl_tail_chunk_share", "ttft_prefill_s"):
        assert _read(name, reading) is None


def test_idle_gaps_are_charged_to_the_innermost_span_of_either_set():
    dev = "/device:TPU:0"
    ops = {dev: [enginetrace.Op("fusion.1", "jit__decode_impl", 0.0, 40.0),
                 enginetrace.Op("fusion.2", "jit__decode_impl", 60.0, 40.0)]}
    spans = [devtrace.Event("bench/window", "", 0.0, 100.0),
             devtrace.Event("bench/decode", "", 30.0, 60.0)]
    trace = enginetrace.EngineTrace(ops, {dev: []}, spans, engine=[
        _span("sample", 75.0, dur=40.0, step=0, tokens=3)])
    gaps = trace.idle_gaps(dev)
    assert gaps == [(40.0, 60.0)]
    assert trace.host_doing(gaps) == ["engine/sample"]
    assert devtrace.Trace(ops, {dev: []}, spans).host_doing(gaps) == [
        "bench/decode"]


def test_json_round_trip_keeps_op_names_and_arguments():
    dev = "/device:TPU:0"
    trace = enginetrace.EngineTrace(
        {dev: [enginetrace.Op("tpu_custom_call:paged_attention.1",
                              "jit__decode_impl", 5.0, 3.0, False,
                              "jit(_decode_impl)/while/body/closed_call/"
                              "attention/paged_attention/pallas_call")]},
        {dev: [devtrace.Event("jit__decode_impl(1)", "jit__decode_impl(1)",
                              0.0, 10.0)]},
        [devtrace.Event("bench/window", "", 0.0, 10.0)],
        engine=[_span("decode", 9.0, step=3, tokens=2)])
    back = enginetrace.EngineTrace.from_json(
        json.loads(json.dumps(trace.to_json())))
    assert back.ops[dev][0].scope == "attention"
    assert back.ops[dev][0].op_name == trace.ops[dev][0].op_name
    assert back.engine[0].args == {"step": 3, "tokens": 2}
    assert back.window() == (0.0, 10.0)
