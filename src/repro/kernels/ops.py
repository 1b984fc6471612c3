"""Public kernel API: jit'd wrappers that dispatch Pallas vs. reference.

Dispatch policy (``impl=`` argument, default "auto"):

  * "pallas"   — the Pallas kernel, compiled for TPU (or interpret=True when
                 the backend is CPU, so CPU test runs still exercise the
                 kernel body; any other backend raises);
  * "ref"      — the pure-jnp sequential oracle ("pertoken" for the scans).
                 GSPMD-shardable but per-token state traffic (the dry-run
                 baseline);
  * "chunked"  — the pure-jnp chunked/SSD formulation (scans only):
                 GSPMD-shardable AND block-parallel — the optimized GSPMD
                 path (see EXPERIMENTS.md §Perf);
  * "auto"     — "pallas" on TPU backends, best jnp path elsewhere
                 ("chunked" for the scans, "ref" for attention).

Every wrapper is shape/dtype-polymorphic and jit-compatible.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import mamba2_scan as _m2
from repro.kernels import paged_attention as _pa
from repro.kernels import ref
from repro.kernels import rwkv6_scan as _rw

Impl = Literal["auto", "pallas", "ref", "pertoken", "chunked"]


def _use_pallas(impl: Impl) -> tuple[bool, bool]:
    """Returns (use_pallas, interpret).

    Interpret mode is for the CPU only: asking for the kernel on any other
    backend that is not a TPU raises instead of silently interpreting."""
    if impl in ("ref", "pertoken", "chunked"):
        return False, False
    backend = jax.default_backend()
    if backend == "tpu":
        return True, False
    if impl == "auto":
        return False, False
    if backend != "cpu":
        raise NotImplementedError(
            f"Pallas kernels compile for TPU and interpret on CPU only; "
            f"backend is {backend!r}")
    return True, True


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    impl: Impl = "auto", block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K):
    use, interp = _use_pallas(impl)
    if use:
        return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interp)
    return ref.mha_attention(q, k, v, causal=causal, scale=scale)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, *, layer=None,
                    scale=None, impl: Impl = "auto"):
    """Pools (P, page, Hkv, D), or with ``layer`` the stacked lane-dense
    pools (L, P, page, Hkv*D) read at that layer."""
    use, interp = _use_pallas(impl)
    if use:
        return _pa.paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                                   layer=layer, scale=scale, interpret=interp)
    if layer is not None:
        D = q.shape[-1]
        k_pages, v_pages = (p[layer].reshape(*p.shape[1:3], -1, D)
                            for p in (k_pages, v_pages))
    return ref.paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                               scale=scale)


def mamba2_scan(x, dt, A, Bmat, Cmat, D, *, impl: Impl = "auto",
                chunk: int = _m2.DEFAULT_CHUNK, h0=None,
                return_state: bool = False):
    use, interp = _use_pallas(impl)
    if use and not return_state and h0 is None:
        return _m2.mamba2_scan(x, dt, A, Bmat, Cmat, D, chunk=chunk,
                               interpret=interp)
    if impl in ("ref", "pertoken"):
        return ref.mamba2_scan(x, dt, A, Bmat, Cmat, D, h0=h0,
                               return_state=return_state)
    return ref.mamba2_scan_chunked(x, dt, A, Bmat, Cmat, D, h0=h0,
                                   return_state=return_state)


def rwkv6_scan(r, k, v, w, u, *, impl: Impl = "auto",
               chunk: int = _rw.DEFAULT_CHUNK, s0=None,
               return_state: bool = False):
    use, interp = _use_pallas(impl)
    if use and not return_state and s0 is None:
        return _rw.rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=interp)
    if impl in ("ref", "pertoken"):
        return ref.rwkv6_scan(r, k, v, w, u, s0=s0,
                              return_state=return_state)
    return ref.rwkv6_scan_chunked(r, k, v, w, u, s0=s0,
                                  return_state=return_state)


# ----------------------------------------------------------------------------
# shard_map'd distributed wrappers: batch over 'data', heads over 'model'.
# These are how the Pallas kernels run on a real mesh (each shard executes
# the kernel on its local (B/dp, H/tp) slice; no cross-shard attention state
# is needed because heads are independent).
# ----------------------------------------------------------------------------

def sharded_flash_attention(mesh, *, data_axes=("data",), model_axis="model",
                            **kw):
    from jax.sharding import PartitionSpec as P
    spec = P(tuple(data_axes), model_axis, None, None)

    fn = functools.partial(flash_attention, **kw)
    return jax.shard_map(lambda q, k, v: fn(q, k, v), mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)


def sharded_paged_attention(mesh, *, data_axes=("data",), model_axis="model",
                            **kw):
    from jax.sharding import PartitionSpec as P
    qspec = P(tuple(data_axes), model_axis, None)
    kvspec = P(None, None, model_axis, None)   # page pool sharded over heads
    tspec = P(tuple(data_axes), None)
    lspec = P(tuple(data_axes))

    fn = functools.partial(paged_attention, **kw)
    return jax.shard_map(
        lambda q, kp, vp, pt, sl: fn(q, kp, vp, pt, sl), mesh=mesh,
        in_specs=(qspec, kvspec, kvspec, tspec, lspec), out_specs=qspec,
        check_vma=False)
