"""The Pallas kernels compile for a TPU v5e chip at real model widths.

Interpret mode (tests/test_kernels.py) checks the kernels' numbers but not
the TPU's tiling rules; here the installed TPU compiler compiles each kernel
for one chip of a described (not attached) v5e:2x2 topology and the
compiled program must hold the kernel (``tpu_custom_call``).  The topology
is described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import flash_attention as fa
from repro.kernels import mamba2_scan as m2
from repro.kernels import paged_attention as pa
from repro.kernels import rwkv6_scan as rw


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "deepseek-7b"])
def test_paged_attention_compiles(one_chip, arch):
    cfg = configs.get_config(arch)
    B, pages_per_seq, page = 32, 256, 16
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pool = (B * pages_per_seq, page, Hkv, D)
    text = _compiled_text(pa.paged_attention, one_chip,
                          ((B, H, D), BF16), (pool, BF16), (pool, BF16),
                          ((B, pages_per_seq), I32), ((B,), I32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    cfg = configs.get_config("qwen2-0.5b")
    S, D = 2048, cfg.resolved_head_dim
    kv = ((1, cfg.n_kv_heads, S, D), BF16)
    text = _compiled_text(fa.flash_attention, one_chip,
                          ((1, cfg.n_heads, S, D), BF16), kv, kv)
    assert "tpu_custom_call" in text


def test_mamba2_scan_compiles(one_chip):
    cfg = configs.get_config("zamba2-1.2b")
    H = cfg.ssm.expand * cfg.d_model // cfg.ssm.head_dim
    B, S, dh, ds = 2, 2048, cfg.ssm.head_dim, cfg.ssm.d_state
    text = _compiled_text(m2.mamba2_scan, one_chip,
                          ((B, S, H, dh), BF16), ((B, S, H), BF16),
                          ((H,), F32), ((B, S, ds), BF16),
                          ((B, S, ds), BF16), ((H,), F32))
    assert "tpu_custom_call" in text


def test_rwkv6_scan_compiles(one_chip):
    cfg = configs.get_config("rwkv6-1.6b")
    H, dh = cfg.n_heads, cfg.resolved_head_dim
    seq = ((2, 2048, H, dh), BF16)
    text = _compiled_text(rw.rwkv6_scan, one_chip, seq, seq, seq, seq,
                          ((H, dh), F32))
    assert "tpu_custom_call" in text
