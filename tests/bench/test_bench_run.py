"""The harness end to end on the CPU (the look for a chip skipped): a
sound run is correct, a broken timed path is not, the fp8 control fails
the limit, and a run that finds no TPU fails with no result."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import tiny
from tiny import harness, bench_run

SERVE = harness.plugin("drivers", "serve")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_run_without_a_tpu_fails_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "qwen2-0.5b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_benchmark_json_names_files_that_exist():
    b = harness.benchmark()
    assert b["command"] == ["python3", "bench/run.py"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = {w["name"]: w for w in b["workloads"]}
    for c in b["configs"]:
        assert NAME.match(c["name"])
        cfg = harness.config(c["name"])
        assert cfg["reduced"] == c["reduced"]
        harness.plugin("drivers", cfg["driver"])
        harness.plugin("reference", cfg["reference"])
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        harness.traffic(w["traffic"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        harness.plugin("metrics", m["name"])
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", cells)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def _serve(seed=3):
    ctx = tiny.ctx(tiny.serve_cfg(), tiny.serve_mix(), seed=seed,
                   seconds=1.5)
    return ctx, SERVE.run(ctx)


def test_a_sound_serving_run_is_correct():
    _, out = _serve()
    assert bench_run.correct_from(out["checks"])
    names = [c["name"] for c in out["checks"]]
    assert names == ["max_logit_gap", "served_tokens_checked"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from repro.serving.engine import PagedLM
    decode = PagedLM.decode_batch

    def altered(self, tokens, active):
        out = np.asarray(decode(self, tokens, active)).copy()
        out[active] = (out[active] + 1) % self.cfg.vocab
        return out

    monkeypatch.setattr(PagedLM, "decode_batch", altered)
    _, out = _serve()
    assert not bench_run.correct_from(out["checks"])


def test_the_fp8_control_fails_the_limit():
    """The control's gap, put through the run's own checks in the
    program's place, comes out not correct."""
    ctx, out = _serve(seed=4)
    limit = ctx.cfg["check"]["max_logit_gap"]
    assert out["checks"][0]["value"] < limit
    assert bench_run.correct_from(out["checks"])
    control = SERVE.control(ctx, out)
    assert control["control_gap"] > 3 * limit
    assert [c["name"] for c in control["control_checks"]] == [
        c["name"] for c in out["checks"]]
    assert not bench_run.correct_from(control["control_checks"])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_result_line(monkeypatch, tmp_path, trace):
    """run.py end to end on the CPU with the look for a chip stubbed: the
    keys the driver reads, the cell's metrics by name, checks last."""
    import copy
    import jax
    cfg, mix = tiny.serve_cfg(), tiny.serve_mix()
    monkeypatch.setattr(harness, "accelerators",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "config", lambda name: copy.deepcopy(cfg))
    monkeypatch.setattr(harness, "traffic", lambda name: copy.deepcopy(mix))
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    monkeypatch.setattr(bench_run, "OUT", tmp_path)
    args = bench_run.argparse.Namespace(
        workload="qwen2-0.5b.chat", seed=2**31 + 17, seconds=1.5,
        trace=trace)
    result = json.loads(json.dumps(bench_run.run(args)))
    assert list(result)[-1] == "checks"
    assert result["correct"] is True
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        result)
    b = harness.benchmark()
    if trace:
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
        # no TPU plane in a CPU trace: the device readers find nothing
        assert set(result["metrics"]) == {"queue_wait_p90_s",
                                          "decode_stall_share"}
    else:
        want = {m["name"] for m in b["end_to_end"]
                if "qwen2-0.5b.chat" in m.get("workloads", ["qwen2-0.5b.chat"])}
        assert set(result["metrics"]) == want
        assert result["metrics"]["setup_s"]["value"] > 0
    assert not bench_run.correct_from(
        [{"name": "x", "value": 0.0, "limit": None}])
