"""RWKV6 (Finch) wkv recurrence Pallas kernel.

S_t = diag(w_t) S_{t-1} + k_t (x) v_t ;   y_t = r_t . (S_{t-1} + diag(u) k_t (x) v_t)

w_t is a *data-dependent per-channel* decay (the paper-series' headline
feature), so unlike Mamba2's scalar-decay SSD there is no cheap chunk-level
closed form; the kernel walks the chunk with a fori_loop and carries the
(dh x dh) state across chunks in VMEM scratch (sequential innermost grid
axis).  dh is the vector-lane dimension; the state is held transposed
(S^T, value index first) so every per-step operand is a lane row:

  y_t   = r_t S_{t-1} + (r_t . (u * k_t)) v_t     (row x S^T^T matvec)
  S^T_t = S^T_{t-1} * w_t + v_t^T k_t             (lane-broadcast decay,
                                                  rank-1 outer product)

The chunk's inputs are staged once into f32 VMEM scratch, so the per-step
row reads are 32-bit dynamic sublane slices; the chunk loop amortises the
state load/store to once per L steps.

Layout: the wrapper lays the head axis out ahead of the sequence axis
((B, S, H, dh) -> (B, H, S, dh)), so every block ends in (chunk, dh) as the
TPU tiling rule requires; the bonus u is viewed as (H, 1, dh).

Grid: (B, H, S/L).  Validated vs kernels/ref.py::rwkv6_scan in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref,
            s_ref, r_s, k_s, v_s, w_s, y_s, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    for src, dst in ((r_ref, r_s), (k_ref, k_s), (v_ref, v_s), (w_ref, w_s)):
        dst[...] = src[0, 0].astype(jnp.float32)      # (L, dh)
    u = u_ref[0].astype(jnp.float32)                  # (1, dh)

    def step(t, st):                                  # st = S^T (dh_v, dh_k)
        rt = r_s[pl.ds(t, 1), :]                      # (1, dh)
        kt = k_s[pl.ds(t, 1), :]
        vt = v_s[pl.ds(t, 1), :]
        wt = w_s[pl.ds(t, 1), :]
        bonus = jnp.sum(rt * u * kt, axis=-1, keepdims=True)     # (1, 1)
        yt = jax.lax.dot_general(rt, st, (((1,), (1,)), ((), ())),
                                 precision=_HI,
                                 preferred_element_type=jnp.float32)
        y_s[pl.ds(t, 1), :] = yt + bonus * vt
        vk = jax.lax.dot_general(vt, kt, (((0,), (0,)), ((), ())),
                                 precision=_HI,
                                 preferred_element_type=jnp.float32)
        return st * wt + vk

    s_ref[...] = jax.lax.fori_loop(0, chunk, step, s_ref[...])
    y_ref[0, 0] = y_s[...].astype(y_ref.dtype)


def rwkv6_scan(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
               u: jax.Array, *, chunk: int = DEFAULT_CHUNK,
               interpret: bool = False) -> jax.Array:
    """r/k/v/w: (B,S,H,dh), u: (H,dh) -> (B,S,H,dh)."""
    B, S, H, dh = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    grid = (B, H, S // chunk)

    seq_spec = pl.BlockSpec((1, 1, chunk, dh), lambda b, h, c: (b, h, c, 0))
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, dh), lambda b, h, c: (h, 0, 0))],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), r.dtype),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)]
        + [pltpu.VMEM((chunk, dh), jnp.float32)] * 5,
        name="rwkv6_scan",
        interpret=interpret,
    )(*(t.transpose(0, 2, 1, 3) for t in (r, k, v, w)), u.reshape(H, 1, dh))
    return y.transpose(0, 2, 1, 3)
