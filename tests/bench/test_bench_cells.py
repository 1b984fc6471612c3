"""Each cell of BENCHMARK.json resolves by name to its files and reports
the metrics the file gives it; the training window's tokens per second is
the tokens of its whole steps over the window's seconds."""
import time

import pytest

import tiny
from tiny import harness, bench_run

TRAIN = harness.plugin("drivers", "train")

WORKLOADS = [w["name"] for w in harness.benchmark()["workloads"]]

# What two cells must report, at the least: a later cell, or a metric a
# later change adds to one of these in an entry of its own, is not listed
# here.
CELLS = {
    "qwen2-0.5b.chat": {
        "driver": "serve", "traffic": "chat-steady",
        "end_to_end": {"ttft_mean_s", "itl_p95_ms", "output_tokens_per_s",
                       "setup_s"},
        "per_layer": ["queue_wait_p90_s", "decode_stall_share",
                      "decode_step_ms", "prefill_chunk_ms",
                      "paged_attn_roofline", "decode_mfu",
                      "idle_share.serve", "itl_tail_chunk_share",
                      "ttft_prefill_s"]},
    "qwen2-0.5b.train-dp4": {
        "driver": "train", "traffic": "pretrain-2k",
        "end_to_end": {"train_tokens_per_s", "setup_s"},
        "per_layer": ["train_mfu", "exposed_collective_share",
                      "idle_share.train"]},
}


def _cell(name):
    return harness.find(harness.benchmark()["workloads"], name, "workload")


@pytest.mark.parametrize("name", WORKLOADS)
def test_a_cell_resolves_to_its_files(name):
    cell = _cell(name)
    cfg = harness.config(cell["config"])
    assert cfg["deployment"]["chips"] == cell["chips"]
    harness.generator(harness.traffic(cell["traffic"]))
    harness.plugin("drivers", cfg["driver"])
    harness.plugin("reference", cfg["reference"])
    for metric in bench_run.per_layer_metrics(
            cell, {m["name"] for m in bench_run.end_to_end_metrics(cell)}):
        assert callable(harness.plugin("metrics", metric["name"]).read)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cell_runs_its_driver_on_its_mix(name):
    cell = _cell(name)
    assert harness.config(cell["config"])["driver"] == CELLS[name]["driver"]
    assert cell["traffic"] == CELLS[name]["traffic"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cell_reports_its_end_to_end_metrics(name):
    got = {m["name"] for m in bench_run.end_to_end_metrics(_cell(name))}
    assert CELLS[name]["end_to_end"] <= got


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_cell_reports_its_per_layer_metrics(name):
    cell = _cell(name)
    e2e = {m["name"] for m in bench_run.end_to_end_metrics(cell)}
    got = [m["name"] for m in bench_run.per_layer_metrics(cell, e2e)]
    assert [m for m in got if m in CELLS[name]["per_layer"]] == \
        CELLS[name]["per_layer"]


def test_at_most_half_the_cells_take_four_chips():
    cells = harness.benchmark()["workloads"]
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 2)


class _Feed:
    rows = 3

    def batch(self, step):
        return None


class _Trainer:
    """Stands in for the program's trainer: a step takes ``STEP_S``."""
    STEP_S = 0.02

    def __init__(self):
        self.data = _Feed()
        self.steps = 0

    def train_step(self):
        time.sleep(self.STEP_S)
        self.steps += 1
        return {"loss": 1.0}


def test_train_tokens_per_s_is_whole_steps_over_the_window(monkeypatch):
    """Every step begun in the window is finished and counted, and the
    window ends when the last one does."""
    trainers = []

    def build(ctx):
        trainers.append(_Trainer())
        return trainers[-1]

    monkeypatch.setattr(TRAIN, "build", build)
    monkeypatch.setattr(TRAIN, "first_steps", lambda ctx, tr: {})
    monkeypatch.setattr(TRAIN, "reference_readings", lambda ctx, b: {})
    monkeypatch.setattr(TRAIN, "compare", lambda prog, ref: {
        "loss_gap": 0.0, "grad_norm_gap": 0.0, "change_norm_gap": 0.0,
        "worst": {"grad": "g", "change": "c"}})
    cfg, mix = tiny.train_cfg(), tiny.train_mix()
    ctx = tiny.ctx(cfg, mix, seconds=0.3)
    out = TRAIN.run(ctx)
    rec = out["records"]
    steps = trainers[0].steps
    assert out["attempted"] == rec["steps"] == steps
    assert steps >= 0.3 / _Trainer.STEP_S / 2
    assert rec["tokens_per_step"] == _Feed.rows * mix["seq_len"]
    assert 0.3 <= rec["window_s"] < 1.3
    rate, unit = out["e2e"]["train_tokens_per_s"]
    assert unit == "tokens/s"
    assert rate == pytest.approx(
        steps * _Feed.rows * mix["seq_len"] / rec["window_s"], rel=1e-12)
    assert ctx.setup_s is not None and ctx.setup_s > 0

