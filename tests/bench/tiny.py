"""Tiny configurations and a driver context for CPU runs of the harness."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import run as bench_run  # noqa: E402

TINY_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512, "norm": "rmsnorm", "norm_eps": 1e-6,
    "mlp": "swiglu", "qkv_bias": True, "tie_word_embeddings": True,
    "rope_theta": 10000.0, "dtype": "bfloat16"}


def serve_cfg(**check) -> dict:
    cfg = copy.deepcopy(harness.config("qwen2-0.5b-serve"))
    cfg["model"] = dict(TINY_MODEL)
    cfg["deployment"].update(max_batch=4, max_seq=128, page_tokens=16,
                             prefill_chunk_pages=2, kv_pool_pages=40)
    cfg["check"].update(sample_requests=3, min_tokens_checked=8,
                        max_logit_gap=0.005, **check)
    return cfg


def serve_mix() -> dict:
    mix = copy.deepcopy(harness.traffic("chat-steady"))
    mix["arrivals"]["rate_per_s"] = 4.0
    mix["prompt_tokens"].update(median=24, min=8, max=96)
    mix["output_tokens"].update(median=8, min=4, max=32)
    mix["warm"]["requests"] = 3
    return mix


def ctx(cfg, mix, *, seed=7, seconds=2.0, trace=False, tmp=None):
    import jax
    return bench_run.Ctx(
        workload={"name": "tiny", "chips": 1}, cfg=cfg, mix=mix, seed=seed,
        seconds=seconds, trace=trace, devices=jax.devices()[:1],
        clock=harness.Clock(), compiles=harness.CompileCounter(),
        trace_dir=Path(tmp or "/nonexistent") / "trace")


def train_cfg(**check) -> dict:
    cfg = harness.load_json(harness.BENCH / "configs"
                            / "qwen2-0.5b-train-dp4.json")
    cfg["model"] = dict(TINY_MODEL)
    cfg["deployment"].update(chips=4, per_chip_batch=2)
    # limits between this size's sound readings (CPU: loss 3e-5, grad
    # 1e-3, change 2.7e-3) and the control's (loss 5e-4, grad 1.7e-2,
    # change 7.5e-3)
    cfg["check"].update(loss_gap=2e-4, grad_norm_gap=5e-3,
                        change_norm_gap=6e-3, block_rows=4, **check)
    return cfg


def train_mix() -> dict:
    mix = copy.deepcopy(harness.traffic("pretrain-2k"))
    mix["seq_len"] = 32
    return mix
