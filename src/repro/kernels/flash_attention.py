"""Blocked (flash) attention Pallas kernel for TPU.

Tiling: grid (B, H, Sq/bq, Skv/bk) with the KV axis innermost — on TPU the
grid is executed sequentially over the last axis, so the output block and
the online-softmax running statistics live in VMEM scratch across KV steps
and are flushed once at the final step.  Block sizes are multiples of 128 on
the lane dimension to keep the MXU fed; K/V blocks for grouped queries are
selected in the index_map (h // group), so GQA costs no extra copies.

Causal skipping: KV blocks strictly above the diagonal are skipped via
pl.when (their compute would be fully masked), which halves FLOPs for long
sequences — the standard flash-attention triangle walk.

Validated on CPU with interpret=True against kernels/ref.py::mha_attention
(see tests/test_kernels.py); the TPU path compiles the same kernel with the
same BlockSpecs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int,
            seq_q: int, seq_kv: int):
    iq = pl.program_id(2)
    jk = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: query block [iq*bq, iq*bq+bq) can only attend to kv blocks with
    # start <= last query position (+ offset when Sq != Skv: right-aligned).
    offs = seq_kv - seq_q
    q_last = iq * block_q + block_q - 1 + offs
    visible = jnp.logical_or(jnp.logical_not(causal),
                             jk * block_k <= q_last)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qi = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + offs
            ki = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(ki <= qi, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + p.sum(axis=-1)
        m_ref[...] = m_new
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(jk == nk - 1)
    def _flush():
        l = l_ref[...]
        # rows that saw nothing (can't happen for causal diag) keep 0
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: (B,H,Sq,D), k/v: (B,Hkv,Skv,D); returns (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert H % Hkv == 0
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    grid = (B, H, Sq // block_q, Skv // block_k)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, seq_q=Sq, seq_kv=Skv)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
