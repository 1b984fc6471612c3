"""Mamba2 (SSD) chunked selective-scan Pallas kernel.

The SSD recurrence  h_t = exp(A dt_t) h_{t-1} + dt_t B_t (x) x_t,
y_t = C_t . h_t + D x_t  is evaluated chunk-parallel: within a chunk of L
steps everything is expressed as (L x L) / (L x ds) matmuls (MXU work), and
only the (ds x dh) state crosses chunk boundaries, carried in VMEM scratch
across the sequential innermost grid axis.

Because A < 0 and dt > 0, every decay factor exp(.) used below is <= 1, so
the closed form is numerically stable without max-subtraction.

Layout: the wrapper lays the head axis out ahead of the sequence axis
((B, S, H, dh) -> (B, H, S, dh)), so every block ends in (chunk, dh) as the
TPU tiling rule requires.  The per-chunk inclusive cumulative log-decay
cum_t = sum_{tau<=t} A dt_tau is a cheap elementwise scan done by XLA in
the wrapper and handed to the kernel as a column (chunk, 1) and a row
(1, chunk) — the two orientations the pairwise decay needs — together with
dt in both orientations.  The per-head skip gain D sits in SMEM.

Grid: (B, H, S/L).  n_groups = 1 (B/C shared across heads), the Zamba2
configuration.  Validated vs kernels/ref.py::mamba2_scan in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128


def _kernel(x_ref, cum_c_ref, cum_r_ref, dt_c_ref, dt_r_ref, b_ref, c_ref,
            d_ref, y_ref, h_ref, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)             # (L, dh)
    s_c = cum_c_ref[0, 0]                           # (L, 1) cumulative log-decay
    s_r = cum_r_ref[0, 0]                           # (1, L)
    dt_c = dt_c_ref[0, 0]                           # (L, 1)
    dt_r = dt_r_ref[0, 0]                           # (1, L)
    bm = b_ref[0].astype(jnp.float32)               # (L, ds)
    cm = c_ref[0].astype(jnp.float32)               # (L, ds)
    dskip = d_ref[hi]                               # ()

    # state contribution: y_state[t] = (exp(s_t) C_t) . h_in
    y_state = jax.lax.dot_general(cm * jnp.exp(s_c), h_ref[...],
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # intra-chunk: att[t,tau] = exp(s_t - s_tau) (C_t.B_tau) dt_tau, tau <= t
    gram = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (L, L)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tau_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = tau_idx <= t_idx
    # mask the exponent before exp: above the diagonal it is positive
    decay = jnp.exp(jnp.where(causal, s_c - s_r, 0.0))
    att = jnp.where(causal, gram * decay * dt_r, 0.0)
    y = y_state + jax.lax.dot_general(att, x, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y + dskip * x).astype(y_ref.dtype)

    # state update: h_out = exp(s_L) h_in + sum_tau exp(s_L - s_tau) dt_tau
    #               B_tau (x) x_tau
    s_last = s_r[:, chunk - 1:]                     # (1, 1)
    w = jnp.exp(s_last - s_c) * dt_c                # (L, 1)
    inject = jax.lax.dot_general(bm * w, x,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    h_ref[...] = h_ref[...] * jnp.exp(s_last) + inject


def mamba2_scan(x: jax.Array, dt: jax.Array, A: jax.Array, Bmat: jax.Array,
                Cmat: jax.Array, D: jax.Array, *,
                chunk: int = DEFAULT_CHUNK,
                interpret: bool = False) -> jax.Array:
    """x: (B,S,H,dh), dt: (B,S,H), A/D: (H,), Bmat/Cmat: (B,S,ds) -> like x."""
    Bsz, S, H, dh = x.shape
    ds = Bmat.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    grid = (Bsz, H, nc)

    dth = dt.astype(jnp.float32).transpose(0, 2, 1)            # (B, H, S)
    la = A.astype(jnp.float32)[None, :, None] * dth
    cum = jnp.cumsum(la.reshape(Bsz, H, nc, chunk), axis=-1).reshape(
        Bsz, H, S)

    seq = pl.BlockSpec((1, 1, chunk, dh), lambda b, h, c: (b, h, c, 0))
    col = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0))
    row = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c))
    bc = pl.BlockSpec((1, chunk, ds), lambda b, h, c: (b, c, 0))
    y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[seq, col, row, col, row, bc, bc,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct((Bsz, H, S, dh), x.dtype),
        scratch_shapes=[pltpu.VMEM((ds, dh), jnp.float32)],
        name="mamba2_scan",
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), cum[..., None], cum[:, :, None, :],
      dth[..., None], dth[:, :, None, :], Bmat, Cmat,
      D.astype(jnp.float32))
    return y.transpose(0, 2, 1, 3)
