"""The yardstick: end-to-end arithmetic on a synthetic log, the operation
and byte counts against hand counts, and the table of peaks."""
import pytest

import tiny
from tiny import harness

import flops

SERVE = harness.plugin("drivers", "serve")
QWEN = harness.config("qwen2-0.5b-serve")["model"]


def test_unfinished_requests_count_in_the_ttft_tail():
    due = {0: 0.0, 1: 1.0, 2: 2.0, 3: 2.5}
    first = {0: 0.5, 1: 1.2, 3: 9.0}          # 2 never, 3 after the window
    claimed = {0: 0.1, 1: 1.1, 3: 2.6}
    tokens = [(0.5, 1), (1.2, 1), (2.0, 3), (3.5, 9)]
    gaps = [(2.0, 0.25), (2.0, 0.75), (2.9, 0.5), (3.5, 7.0)]
    m, waits, ttft = SERVE.serve_metrics(due, first, claimed, tokens, gaps,
                                         0.0, 3.0)
    # ttft: 0.5, 0.2, 1.0 (waited to t1), 0.5 (waited to t1)
    assert ttft == pytest.approx([0.5, 0.2, 1.0, 0.5])
    assert m["ttft_mean_s"] == pytest.approx(2.2 / 4)
    # gaps in the window: 0.25, 0.75, 0.5 -> nearest-rank p95 = 0.75 s
    assert m["itl_p95_ms"] == pytest.approx(750.0)
    assert m["output_tokens_per_s"] == pytest.approx(5 / 3.0)
    assert waits == pytest.approx([0.1, 0.1, 1.0, 0.1])


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert harness.percentile(xs, 90) == 90
    assert harness.percentile(xs, 95) == 95
    assert harness.percentile([3.0], 90) == 3.0
    assert harness.percentile(list(range(1, 11)), 90) == 9


def test_matmul_params_of_qwen2_half_billion():
    L, d, H, Hkv, hd, f, V = 24, 896, 14, 2, 64, 4864, 151936
    per_layer = d * (H * hd) + 2 * d * (Hkv * hd) + (H * hd) * d + 3 * d * f
    assert flops.matmul_params(QWEN) == L * per_layer + d * V
    # 494M parameters in all: these plus biases, norms (tied embedding once)
    assert 0.49e9 < flops.matmul_params(QWEN) < 0.50e9


def test_paged_attention_cost_by_hand():
    m = dict(tiny.TINY_MODEL)        # L=2, H=4, Hkv=2, hd=16
    f, b = flops.paged_attention_cost(m, [10, 3])
    assert f == 2 * (4 * 4 * 16 * 10 + 4 * 4 * 16 * 3)
    kv = 2 * (10 + 3) * 2 * 16 * 2           # k and v, bf16
    qo = 2 * 2 * 4 * 16 * 2                  # q in, out back, per token
    assert b == 2 * (kv + qo)


def test_decode_and_train_flops_by_hand():
    m = dict(tiny.TINY_MODEL)
    n = flops.matmul_params(m)
    assert flops.decode_flops(m, [5, 7]) == 2 * (2 * n) + 4 * 2 * 4 * 16 * 12
    S = 64
    assert flops.train_flops_per_token(m, S) == int(
        6 * n + 3 * 4 * 2 * 4 * 16 * (S + 1) / 2)


def test_peaks_by_device_kind_and_no_default():
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    for kind in ("TPU v4", "cpu", "_source"):
        with pytest.raises(harness.BenchError):
            harness.peaks(kind)
