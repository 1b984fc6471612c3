"""Torus collectives — thin lowering wrappers over ``core.fabric``.

APEnet+ moves data exclusively over first-neighbour torus links with
dimension-ordered routing (§1), and hides latency by keeping *two* DMA
engines per link in flight (§2.1, Fig 1: ~40% total-time reduction).  On a
TPU pod the ICI fabric has the same shape, and ``lax.ppermute`` *is* the
neighbour RDMA-put.

Since the fabric refactor every collective here is *lowered* to an explicit
``fabric.CollectiveSchedule`` (which hop moves which bytes when) and then
executed by ``fabric.execute`` — the same schedule object the cost
estimator prices and the LO|FA|MO fault rewriter detours.  Each function
accepts an optional pre-lowered ``schedule`` (e.g. a fault-rewritten one);
without it the schedule is lowered on the fly against the ring implied by
the bound mesh axis.

The collective set a trainer needs on this fabric:

  * ``ring_reduce_scatter`` / ``ring_all_gather`` / ``ring_all_reduce`` —
    k-ary ring algorithms along one named mesh axis, built purely from
    neighbour ppermutes;
  * **bidirectional** variants (default) — each round ships two half-chunks
    in opposite directions over the full-duplex links, fused into a single
    loop (the "dual DMA engine" idea: 2x link utilisation, half the
    sequential rounds);
  * multi-axis, **dimension-ordered** wrappers — reduce-scatter along X,
    then Y, then Z, and all-gather back in reverse order: the collective
    analogue of APEnet+'s X->Y->Z router policy;
  * ``ring_all_to_all`` — store-and-forward ring all-to-all (MoE dispatch
    on the torus) plus a direct XLA ``lax.all_to_all`` fast path;
  * ``halo_exchange`` — the one-sided neighbour put used by stencil demos
    and the LO|FA|MO status exchange.

All functions here are *per-shard* code: they must run inside ``shard_map``
(or any context where ``axis_name`` is bound).  ``make_*`` helpers wrap them
into jitted host-level callables for tests and demos.

Numerics note: ring reductions accumulate in fp32 when inputs are lower
precision (bf16/fp16), matching production all-reduce behaviour.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import fabric
from repro.core.fabric import CollectiveSchedule
# Re-exported executor helpers: the implementations (and all ring/hop math)
# live in core/fabric; these names are long-standing public API here.
from repro.core.fabric.execute import (_acc_dtype, _flatten_pad,  # noqa: F401
                                       _ring_perms)
from repro.core.topology import Torus


def _axis_torus(axis_names: Sequence[str]) -> Torus:
    """The ring/torus implied by the bound mesh axes (trace-time static)."""
    return Torus(tuple(jax.lax.axis_size(ax) for ax in axis_names))


# ----------------------------------------------------------------------------
# single-axis ring primitives (per-shard code)
# ----------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis_name: str, *,
                        bidirectional: bool = True, mean: bool = False,
                        schedule: CollectiveSchedule | None = None
                        ) -> jax.Array:
    """Reduce-scatter along a mesh-axis ring; ring slot r returns chunk r.

    Input: the full local array (same logical value on every rank is NOT
    required — this reduces across ranks elementwise, like psum, then
    scatters).  Output: flat fp32-accumulated chunk of size ceil(|x|/N)
    (zero-padded); see ``ring_all_reduce`` for the unpadded composite.
    """
    if schedule is None:
        schedule = fabric.lower_reduce_scatter(
            _axis_torus((axis_name,)), (axis_name,),
            bidirectional=bidirectional, mean=mean)
    chunk, _ = fabric.execute_reduce_scatter(schedule, x)
    return chunk


def ring_all_gather(x: jax.Array, axis_name: str, *,
                    bidirectional: bool = True,
                    schedule: CollectiveSchedule | None = None) -> jax.Array:
    """All-gather chunks along a ring: slot r contributes x, returns the
    concatenation ordered by ring slot, shape (n, *x.shape)."""
    if schedule is None:
        schedule = fabric.lower_all_gather(
            _axis_torus((axis_name,)), (axis_name,),
            bidirectional=bidirectional)
    return fabric.execute_all_gather(schedule, x)


def ring_all_reduce(x: jax.Array, axis_name: str, *,
                    bidirectional: bool = True, mean: bool = False,
                    schedule: CollectiveSchedule | None = None) -> jax.Array:
    """Ring all-reduce = reduce-scatter + all-gather (the classic 2(N-1)/N
    bytes-optimal schedule), preserving ``x``'s shape/dtype."""
    if schedule is None:
        schedule = fabric.lower_all_reduce(
            _axis_torus((axis_name,)), (axis_name,),
            bidirectional=bidirectional, mean=mean)
    return fabric.execute_all_reduce(schedule, x)


# ----------------------------------------------------------------------------
# multi-axis, dimension-ordered composites (APEnet+ X->Y->Z routing)
# ----------------------------------------------------------------------------

def dim_ordered_all_reduce(x: jax.Array, axis_names: Sequence[str], *,
                           bidirectional: bool = True, mean: bool = False,
                           schedule: CollectiveSchedule | None = None
                           ) -> jax.Array:
    """All-reduce over several mesh axes: reduce-scatter X,Y,...,Z then
    all-gather Z,...,Y,X.  Each phase only ever talks to first neighbours
    along one torus dimension — the collective analogue of dimension-ordered
    routing, and bytes-optimal on a torus (each axis moves 2(Ni-1)/Ni of the
    data it still owns)."""
    if schedule is None:
        schedule = fabric.lower_all_reduce(
            _axis_torus(axis_names), tuple(axis_names),
            bidirectional=bidirectional, mean=mean)
    return fabric.execute_all_reduce(schedule, x)


def dim_ordered_reduce_scatter(x: jax.Array, axis_names: Sequence[str], *,
                               bidirectional: bool = True, mean: bool = False,
                               schedule: CollectiveSchedule | None = None
                               ) -> tuple[jax.Array, list[int]]:
    """Multi-axis RS; also returns per-stage pre-pad sizes for the inverse
    ``dim_ordered_all_gather`` (ZeRO-1 shard/unshard round trip)."""
    if schedule is None:
        schedule = fabric.lower_reduce_scatter(
            _axis_torus(axis_names), tuple(axis_names),
            bidirectional=bidirectional, mean=mean)
    return fabric.execute_reduce_scatter(schedule, x)


def dim_ordered_all_gather(x: jax.Array, axis_names: Sequence[str],
                           stage_sizes: Sequence[int], *,
                           bidirectional: bool = True,
                           schedule: CollectiveSchedule | None = None
                           ) -> jax.Array:
    """Inverse of ``dim_ordered_reduce_scatter`` given its stage sizes."""
    if schedule is None:
        axes = tuple(reversed(tuple(axis_names)))
        dims = tuple(reversed(range(len(axes))))
        schedule = fabric.lower_all_gather(_axis_torus(axis_names), axes,
                                           axis_dims=dims,
                                           bidirectional=bidirectional)
    return fabric.execute_all_gather(schedule, x, list(stage_sizes))


# ----------------------------------------------------------------------------
# all-to-all
# ----------------------------------------------------------------------------

def ring_all_to_all(x: jax.Array, axis_name: str, *,
                    schedule: CollectiveSchedule | None = None) -> jax.Array:
    """Store-and-forward ring all-to-all along one torus axis.

    ``x`` has shape (n, ...): row j is this rank's block destined for rank j.
    Returns shape (n, ...): row j is the block received from rank j.  Pure
    first-neighbour traffic: the full buffer circulates n-1 hops and every
    rank picks out its addressed row at each stop — exactly how a torus
    router forwards non-local packets.
    """
    if schedule is None:
        schedule = fabric.lower_all_to_all(_axis_torus((axis_name,)),
                                           axis_name)
    return fabric.execute_all_to_all(schedule, x)


def fast_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """Direct XLA all-to-all (the compiler schedules it on the torus)."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)


# ----------------------------------------------------------------------------
# halo exchange / neighbour put
# ----------------------------------------------------------------------------

def halo_exchange(x: jax.Array, axis_name: str, halo: int = 1,
                  dim: int = 0, *,
                  schedule: CollectiveSchedule | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Exchange ``halo``-wide boundary slabs with both ring neighbours.

    Returns (from_prev, from_next): the neighbours' facing edges — a pair of
    one-sided RDMA puts in APEnet+ terms.
    """
    if schedule is None:
        schedule = fabric.lower_halo_exchange(_axis_torus((axis_name,)),
                                              axis_name)
    return fabric.execute_halo_exchange(schedule, x, halo, dim)


# ----------------------------------------------------------------------------
# host-level wrappers (tests / demos / the apex DP layer)
# ----------------------------------------------------------------------------

def make_stacked_all_reduce(mesh: Mesh, axis_names: Sequence[str], *,
                            bidirectional: bool = True, mean: bool = False,
                            schedule: CollectiveSchedule | None = None):
    """Host-level all-reduce for tests/demos.

    Takes a global array of shape (n_0, ..., n_k, *payload) whose leading
    dims are sharded over ``axis_names``; every (i, ..., j) slot is one
    rank's contribution.  Returns the same shape where every slot holds the
    (mean-)reduction — so correctness is checkable against ``x.sum(axis=lead)``.
    """
    axes = tuple(axis_names)
    lead = len(axes)

    def per_shard(x):
        y = x.reshape(x.shape[lead:])
        out = dim_ordered_all_reduce(y, axes, bidirectional=bidirectional,
                                     mean=mean, schedule=schedule)
        return out.reshape(x.shape)

    spec = P(*axes)
    mapped = jax.shard_map(per_shard, mesh=mesh, in_specs=(spec,),
                           out_specs=spec, check_vma=False)
    return jax.jit(mapped)


def tree_all_reduce(tree, axis_names: Sequence[str], *,
                    bidirectional: bool = True, mean: bool = True,
                    schedule: CollectiveSchedule | None = None):
    """Per-shard: all-reduce every leaf of a pytree (gradient sync)."""
    return jax.tree.map(
        lambda g: dim_ordered_all_reduce(g, axis_names,
                                         bidirectional=bidirectional,
                                         mean=mean, schedule=schedule), tree)
