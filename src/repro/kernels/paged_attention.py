"""Paged decode attention — the §2.2 hardware-TLB idea as a Pallas kernel.

APEnet+ §2.2 moved virtual->physical address translation out of the Nios II
soft-CPU into an FPGA TLB sitting directly in the RX datapath (+60% RX
bandwidth).  The TPU-native analogue: during decode, the KV cache is *paged*
(virtual per-sequence pages scattered over a physical page pool), and the
translation happens **inside the kernel's BlockSpec index_map** via scalar
prefetch — the DMA engine that streams K/V pages from HBM into VMEM is
programmed directly with translated physical page indices, with no
XLA-level gather materialising the sequence first.

  * fast path (this kernel): translation in the index_map = "hardware TLB";
  * slow path (kernels/ref.py::paged_attention): gather pages with XLA ops,
    then dense attention = "Nios II software walk".

benchmarks/tlb.py quantifies the byte-traffic gap between the two paths
(the gather path writes the gathered copy back to HBM before attending).

Grid: (B, max_pages), page axis innermost/sequential.  One step streams a
whole physical page for ALL KV heads: the pool is lane-dense,
(L, P, page, Hkv*D), so a page block (page, Hkv*D) is whole (bf16 (16, 128)
tiles for qwen2-0.5b) and KV head g is lanes [g*D, (g+1)*D) of it.  The
layer is a scalar-prefetch operand, so the serving engine's layer loop hands
the kernel the stacked pool as it is, with no per-layer slice.  Each KV head
serves its ``H/Hkv`` query heads inside the kernel (queries are viewed as
(B, Hkv, group, D) so a KV head's queries are one leading-dim slice).
Online-softmax running stats live in VMEM scratch; pages past a sequence's
length are skipped (pl.when), so ragged batches pay only for resident pages.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(layer_ref, page_table_ref, seq_lens_ref,  # scalar prefetch
            q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *,
            scale: float, page: int, n_kv: int, group: int, head_dim: int):
    b = pl.program_id(0)
    j = pl.program_id(1)
    npages = pl.num_programs(1)
    seq_len = seq_lens_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # The page is resident iff it holds any position < seq_len.
    @pl.when(j * page < seq_len)
    def _compute():
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (group, page), 1)
        valid = pos < seq_len
        for g in range(n_kv):                         # static: one KV head
            q = q_ref[0, g].astype(jnp.float32) * scale        # (group, D)
            lanes = slice(g * head_dim, (g + 1) * head_dim)
            k = k_ref[0, 0, :, lanes].astype(jnp.float32)      # (page, D)
            v = v_ref[0, 0, :, lanes].astype(jnp.float32)      # (page, D)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid, s, NEG_INF)                   # (group, page)
            m_prev = m_ref[g]                                  # (group, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = alpha * l_ref[g] + p.sum(axis=-1, keepdims=True)
            m_ref[g] = m_new
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == npages - 1)
    def _flush():
        l = l_ref[...]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, seq_lens: jax.Array, *,
                    layer: jax.Array | int | None = None,
                    scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B,H,D); k_pages/v_pages: (P,page,Hkv,D), or with ``layer`` the
    stacked lane-dense pool (L,P,page,Hkv*D) read at that layer;
    page_table: (B,max_pages) int32; seq_lens: (B,) int32 -> (B,H,D)."""
    B, H, D = q.shape
    if layer is None:
        P, page, Hkv, _ = k_pages.shape
        k_pages = k_pages.reshape(1, P, page, Hkv * D)
        v_pages = v_pages.reshape(1, P, page, Hkv * D)
        layer = 0
    _, _, page, lanes = k_pages.shape
    Hkv = lanes // D
    max_pages = page_table.shape[1]
    assert H % Hkv == 0
    group = H // Hkv
    scale = scale if scale is not None else D ** -0.5

    kernel = functools.partial(_kernel, scale=scale, page=page, n_kv=Hkv,
                               group=group, head_dim=D)
    # query head h = g * group + i serves KV head g (the repeat order of
    # ref.paged_attention)
    qg = q.reshape(B, Hkv, group, D)
    head_spec = pl.BlockSpec((1, Hkv, group, D),
                             lambda b, j, ly, pt, sl: (b, 0, 0, 0))
    # THE TLB: physical page id comes from the prefetched page table.
    page_spec = pl.BlockSpec((1, 1, page, lanes),
                             lambda b, j, ly, pt, sl: (ly[0], pt[b, j], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, max_pages),
        in_specs=[head_spec, page_spec, page_spec],
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
            pltpu.VMEM((Hkv, group, 1), jnp.float32),
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), page_table, seq_lens, qg,
      k_pages, v_pages)
    return out.reshape(B, H, D)
