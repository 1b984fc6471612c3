"""Each per-layer reader on a small trace recorded on a TPU v5e chip (two
decode steps of the chat cell, ops over 20 us and every kernel op kept)."""
import json
from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts bench/ on sys.path)
from tiny import harness, bench_run

import devtrace
import flops

FIXTURE = Path(__file__).parent / "fixtures" / "serve_trace.json"
CFG = harness.config("qwen2-0.5b-serve")
PEAKS = harness.peaks("TPU v5 lite")
CONTEXTS = [700] * 100 + [1500] * 20       # two steps of 120 live slots


@pytest.fixture(scope="module")
def trace():
    return devtrace.Trace.from_json(json.loads(FIXTURE.read_text()))


def _reading(trace, **records):
    rec = {"window_s": 10.0, "decode_stall_s": 1.5,
           "queue_waits_s": [0.1 * i for i in range(1, 21)],
           "decode_calls": [(0.0, 0.1, CONTEXTS), (0.1, 0.2, CONTEXTS)]}
    rec.update(records)
    return bench_run.Reading(CFG, {}, rec, trace, PEAKS)


def _read(name, reading):
    return harness.plugin("metrics", name).read(reading)


def _decode_runs(trace):
    (dev, runs), = trace.module_runs("_decode_impl").items()
    return dev, runs


def test_decode_step_ms(trace):
    _, runs = _decode_runs(trace)
    assert len(runs) == 2
    want = (runs[0].dur_ns + runs[1].dur_ns) / 2 * 1e-6
    assert _read("decode_step_ms", _reading(trace)) == pytest.approx(want)
    assert 50 < want < 200


def test_no_prefill_chunk_in_the_window_reads_nothing(trace):
    assert _read("prefill_chunk_ms", _reading(trace)) is None


def test_paged_attn_roofline(trace):
    dev, runs = _decode_runs(trace)
    kernel = [o for r in runs for o in trace.ops_in(r, dev)
              if o.name.startswith("tpu_custom_call:")]
    assert len(kernel) == 2 * 24                  # one per layer per step
    f, b = flops.paged_attention_cost(CFG["model"], CONTEXTS)
    least = max(b / 819e9, f / 197e12)            # memory-bound
    assert least == b / 819e9
    want = 100 * least / (sum(o.dur_ns for o in kernel) * 1e-9 / 2)
    got = _read("paged_attn_roofline", _reading(trace))
    assert got == pytest.approx(want) and 0 < got < 100


def test_decode_mfu(trace):
    _, runs = _decode_runs(trace)
    step_s = (runs[0].dur_ns + runs[1].dur_ns) / 2 * 1e-9
    want = 100 * flops.decode_flops(CFG["model"], CONTEXTS) / (step_s * 197e12)
    assert _read("decode_mfu", _reading(trace)) == pytest.approx(want)


def test_idle_share(trace):
    lo, hi = trace.window()
    (dev,) = trace.ops
    busy = trace.busy_ns(dev)
    assert 0 < busy <= hi - lo
    want = 100 * (1 - busy / (hi - lo))
    assert _read("idle_share.serve", _reading(trace)) == pytest.approx(want)


def test_host_side_readers(trace):
    r = _reading(trace)
    assert _read("queue_wait_p90_s", r) == pytest.approx(1.8)
    assert _read("decode_stall_share", r) == pytest.approx(15.0)


def test_readers_find_nothing_return_nothing(trace):
    empty = devtrace.Trace({}, {}, list(trace.spans))
    r = _reading(empty, decode_calls=[])
    for name in ("decode_step_ms", "prefill_chunk_ms", "paged_attn_roofline",
                 "decode_mfu", "idle_share.serve"):
        assert _read(name, r) is None, name


def test_breakdown_names_ops_by_program_and_skips_loops(trace):
    b = bench_run.breakdown(trace)
    names = [n for n, _ in b["device_ops"]]
    assert names[0].startswith("jit__decode_impl/tpu_custom_call:")
    assert not any("/while" in n for n in names)
    assert len(names) <= 10 and len(b["idle_gaps"]) <= 10


def _ev(name, start, dur, module="jit_per_shard"):
    return devtrace.Event(name, module, float(start), float(dur))


def test_exposed_collective_share_by_hand():
    """One step of 100 ns on each of two chips: a collective over [40, 70)
    with compute over [0, 50) and [60, 100): 10 ns exposed on chip 0; on
    chip 1 the collective [80, 95) is hidden under compute [0, 100)."""
    step = [_ev("jit_per_shard(1)", 0, 100)]
    ops = {"/device:TPU:0": [_ev("fusion.1", 0, 50),
                             _ev("collective-permute-done.3", 40, 30),
                             _ev("fusion.2", 60, 40)],
           "/device:TPU:1": [_ev("fusion.1", 0, 100),
                             _ev("collective-permute-done.3", 80, 15)]}
    trace = devtrace.Trace(ops, {d: list(step) for d in ops},
                           [_ev("bench/window", 0, 100, "")])
    assert trace.dominant_program() == "per_shard"
    got = _read("exposed_collective_share", _reading(trace))
    assert got == pytest.approx(100 * (10 / 100 + 0 / 100) / 2)


@pytest.mark.parametrize("steps", [10, 0])
def test_train_mfu_by_hand(steps):
    """The window's tokens over its seconds against every chip's peak; a
    window with no whole step has nothing to read."""
    cfg = harness.load_json(harness.BENCH / "configs"
                            / "qwen2-0.5b-train-dp4.json")
    rec = {"steps": steps, "tokens_per_step": 32768, "window_s": 5.0,
           "chips": 4}
    r = bench_run.Reading(cfg, {"seq_len": 2048}, rec, None, PEAKS)
    if not steps:
        assert _read("train_mfu", r) is None
        return
    per_token = flops.train_flops_per_token(cfg["model"], 2048)
    want = 100 * per_token * (10 * 32768 / 5.0) / (4 * 197e12)
    assert _read("train_mfu", r) == pytest.approx(want)
    assert 0 < want < 100
