"""The Pallas kernels compile for a TPU v5e chip at real model widths.

Interpret mode (tests/test_kernels.py) checks the kernels' numbers but not
the TPU's tiling rules; here the installed TPU compiler compiles each kernel
for one chip of a described (not attached) v5e:2x2 topology and the
compiled program must hold the kernel (``tpu_custom_call``) under its
name.  The serving engine's decode and prefill-chunk programs are compiled
at the chat cell's sizes, to show that they update the stacked KV pools in
place.  The topology
is described inside a fixture, never while a module is imported: only one
process at a time may load the TPU library.
"""
import os
import re

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


def _holds_kernel(text: str, name: str) -> bool:
    """The program holds the Pallas kernel as a custom call named ``name``."""
    return any(line.lstrip().startswith(f"%{name}") and
               'custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines())


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
    assert _holds_kernel(text, "paged_attention")


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "deepseek-7b"])
def test_paged_attention_compiles_on_the_stacked_pool(one_chip, arch):
    """The kernel reads one layer of the engine's stacked lane-dense pool,
    the layer a traced scalar as in the engine's layer loop."""
    cfg = configs.get_config(arch)
    B, pages_per_seq, page, L = 32, 256, 16, 4
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    pool = (L, B * pages_per_seq, page, Hkv * D)
    text = _compiled_text(
        lambda q, kp, vp, pt, sl, ly: pa.paged_attention(q, kp, vp, pt, sl,
                                                         layer=ly),
        one_chip, ((B, H, D), BF16), (pool, BF16), (pool, BF16),
        ((B, pages_per_seq), I32), ((B,), I32), ((), I32))
    assert _holds_kernel(text, "paged_attention")


def test_flash_attention_compiles(one_chip):
    cfg = configs.get_config("qwen2-0.5b")
    S, D = 2048, cfg.resolved_head_dim
    kv = ((1, cfg.n_kv_heads, S, D), BF16)
    text = _compiled_text(fa.flash_attention, one_chip,
                          ((1, cfg.n_heads, S, D), BF16), kv, kv)
    assert _holds_kernel(text, "flash_attention")


def test_mamba2_scan_compiles(one_chip):
    cfg = configs.get_config("zamba2-1.2b")
    H = cfg.ssm.expand * cfg.d_model // cfg.ssm.head_dim
    B, S, dh, ds = 2, 2048, cfg.ssm.head_dim, cfg.ssm.d_state
    text = _compiled_text(m2.mamba2_scan, one_chip,
                          ((B, S, H, dh), BF16), ((B, S, H), BF16),
                          ((H,), F32), ((B, S, ds), BF16),
                          ((B, S, ds), BF16), ((H,), F32))
    assert _holds_kernel(text, "mamba2_scan")


def test_rwkv6_scan_compiles(one_chip):
    cfg = configs.get_config("rwkv6-1.6b")
    H, dh = cfg.n_heads, cfg.resolved_head_dim
    seq = ((2, 2048, H, dh), BF16)
    text = _compiled_text(rw.rwkv6_scan, one_chip, seq, seq, seq, seq,
                          ((H, dh), F32))
    assert _holds_kernel(text, "rwkv6_scan")


def test_decode_program_holds_the_named_kernel_in_its_scope(one_chip,
                                                           monkeypatch):
    """The engine's decode program, traced as on a TPU, holds the paged-
    attention kernel by name inside its ``attention`` scope."""
    import numpy as np
    from repro.kernels import ops
    from repro.models import api
    from repro.serving.engine import PagedLM
    monkeypatch.setattr(ops, "_use_pallas", lambda impl: (True, False))
    cfg = configs.get_config("qwen2-0.5b").reduced()
    lm = PagedLM(cfg, None, max_batch=8, max_seq=256, page_tokens=16,
                 tp_axes=())
    params = jax.eval_shape(
        lambda: api.get_model(cfg).init(jax.random.key(0)))
    pool = lm.k_pool.shape
    B = lm.max_batch
    shapes = [jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                  x.shape, x.dtype, sharding=one_chip), params)] + [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
        (((B, 1), I32), (pool, cfg.dtype), (pool, cfg.dtype),
         (lm.page_table.shape, I32), ((B,), I32), ((B,), np.bool_))]
    text = jax.jit(lm._decode_impl).lower(*shapes).compile().as_text()
    (line,) = [ln for ln in text.splitlines()
               if ln.lstrip().startswith("%paged_attention")]
    assert 'custom_call_target="tpu_custom_call"' in line
    assert "/attention/paged_attention/" in line


# -- the serving programs at the chat cell's sizes ---------------------------

CELL = dict(arch="qwen2-0.5b", max_batch=128, max_seq=4096, page_tokens=16,
            pool_pages=10240, chunk_tokens=256)
_SHAPE = re.compile(r"\b(pred|[bsuf](?:f)?\d+)\[([\d,]*)\]")
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+?)\s+([\w\-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _array_bytes(dtype: str, dims: str) -> int:
    width = 1 if dtype == "pred" else int(re.sub(r"\D", "", dtype)) // 8
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * width


def _moves_at_least(text: str, nbytes: int) -> list[str]:
    """Every copy, dynamic-slice or dynamic-update-slice, alone or as a
    fusion named for one, anywhere in the program (fused bodies included)
    whose largest result array holds ``nbytes`` or more."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, result, opcode = m.groups()
        if not (opcode.startswith(_MOVES) or
                (opcode == "fusion" and any(w in name for w in _MOVES))):
            continue
        if max((_array_bytes(*a) for a in _SHAPE.findall(result)),
               default=0) >= nbytes:
            out.append(f"{name} = {result[:60]} {opcode}")
    return out


@pytest.fixture(scope="module")
def chat_cell_programs(one_chip):
    """The decode and prefill-chunk programs the engine runs in the chat
    cell, donation included, compiled for a v5e chip as a TPU traces them
    (through the Pallas kernel).  A modelled PagedLM allocates no pools."""
    import numpy as np
    from repro.kernels import ops
    from repro.models import api
    from repro.serving.engine import PagedLM
    cfg = configs.get_config(CELL["arch"])
    lm = PagedLM(cfg, None, max_batch=CELL["max_batch"],
                 max_seq=CELL["max_seq"], page_tokens=CELL["page_tokens"],
                 pool_pages=CELL["pool_pages"], tp_axes=(), modelled=True)
    params = jax.eval_shape(
        lambda: api.get_model(cfg).init(jax.random.key(0)))
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    p = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
    pool, table, B = lm.pool_shape, lm.page_table.shape, lm.max_batch
    args = {
        "decode": (lm._decode, [p, sds((B, 1), I32), sds(pool, cfg.dtype),
                                sds(pool, cfg.dtype), sds(table, I32),
                                sds((B,), I32), sds((B,), np.bool_)]),
        "chunk": (lm._prefill_chunk,
                  [p, sds((1, CELL["chunk_tokens"]), I32),
                   sds(pool, cfg.dtype), sds(pool, cfg.dtype),
                   sds(table, I32), sds((), I32), sds((), I32),
                   sds((), I32)]),
    }
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_use_pallas", lambda impl: (True, False))
        compiled = {k: fn.lower(*a).compile() for k, (fn, a) in args.items()}
    pool_bytes = int(np.prod(pool)) * jnp.dtype(cfg.dtype).itemsize
    return compiled, pool_bytes, pool_bytes // pool[0]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_serving_program_aliases_both_pools(chat_cell_programs, program):
    compiled, pool_bytes, _ = chat_cell_programs
    alias = compiled[program].memory_analysis().alias_size_in_bytes
    assert alias >= 2 * pool_bytes, (alias, 2 * pool_bytes)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_serving_program_moves_no_layer_of_the_pool(chat_cell_programs,
                                                    program):
    """No op copies, slices or re-stacks a layer's pages (or more): the
    layer loop writes and reads the stacked pools where they lie."""
    compiled, _, layer_bytes = chat_cell_programs
    text = compiled[program].as_text()
    assert _moves_at_least(text, layer_bytes) == []
    # the reading sees the program's moves: the small ones are there
    assert _moves_at_least(text, 1) != []


def test_decode_program_at_the_chat_cell_runs_the_kernel_in_its_scope(
        chat_cell_programs):
    compiled, _, _ = chat_cell_programs
    (line,) = [ln for ln in compiled["decode"].as_text().splitlines()
               if ln.lstrip().startswith("%paged_attention")]
    assert 'custom_call_target="tpu_custom_call"' in line
    assert "/attention/paged_attention/" in line
