"""Multi-pod dry-run integration: lower+compile on the production meshes.

The full 64-cell sweep runs via ``python -m repro.launch.dryrun``; here we
gate the machinery itself: one real cell on the 512-chip multi-pod mesh in
a subprocess (forced host devices), plus the cell-enumeration logic.
"""
import json
import os
import subprocess
import sys

import pytest

from repro import configs
from repro.models import api


def test_cell_enumeration_counts():
    from repro.launch import dryrun

    cells = list(dryrun.all_cells(
        [configs.canonical(a) for a in configs.ALL_ARCHS], None,
        ["pod", "multipod"]))
    # 10 archs x 3 shapes + 2 long_500k (zamba2, rwkv6) = 32 per mesh
    assert len(cells) == 64
    longs = [c for c in cells if c[1] == "long_500k"]
    assert sorted({c[0] for c in longs}) == ["rwkv6-1_6b", "zamba2-1_2b"]


def test_long500k_gated_on_full_attention():
    for arch in configs.ALL_ARCHS:
        cfg = configs.get_config(arch)
        shapes = api.applicable_shapes(cfg)
        assert ("long_500k" in shapes) == (not cfg.full_attention)


@pytest.mark.slow
def test_dryrun_cell_multipod(tmp_path):
    """One full lower+compile on the 2x16x16 mesh must succeed and emit
    roofline-ready JSON."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-135m", "--shape", "train_4k",
         "--mesh", "multipod", "--force", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    out = json.loads(
        (tmp_path / "smollm-135m_train_4k_multipod.json").read_text())
    assert out["chips"] == 512
    assert out["flops_per_device"] > 0
    assert out["link_bytes_per_device"] > 0
    assert out["roofline"]["bottleneck"] in ("compute_s", "memory_s",
                                             "collective_s")
    # useful-flop sanity: params+attention model flops within 3x of the
    # analyzer count (smollm replicates its 9 heads over TP=16, so the
    # compiled flops carry real redundancy — the ratio sits well below 1)
    assert 0.01 <= out["useful_flop_ratio_attn"] <= 3.0
    assert out["useful_flop_ratio"] <= out["useful_flop_ratio_attn"]
    mem = out["memory_analysis"]
    assert "live_bytes_per_device" in mem
