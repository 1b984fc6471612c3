"""Schedule executor — walks a ``CollectiveSchedule`` and emits the
shard_map/ppermute program implementing it.

All entry points are *per-shard* code: they must run inside ``shard_map``
(or any context binding the schedule's axis names).  The executor is the
only consumer that turns schedule steps into data movement; it derives
nothing about rings or hops itself — perms come verbatim from the
schedule's transfers, so a fault-rewritten schedule executes with zero
extra code.

Dual-DMA fusion: where the legacy collectives ran the +1 ring pass to
completion and then the -1 pass (2(n-1) sequential ppermute rounds), the
executor advances both directions of a bidirectional phase inside ONE
fori_loop — n-1 rounds, each issuing two data-independent ppermutes that
XLA overlaps exactly like the two DMA engines of an APEnet+ link (paper
§2.1, Fig 1).  ``schedule.rounds`` is therefore the true sequential depth.

Numerics: ring reductions accumulate in fp32 when inputs are lower
precision (bf16/fp16), matching production all-reduce behaviour.  Layouts
match the legacy collectives bit-for-bit on healthy fabrics: reduce-scatter
hands ring-slot r the contiguous chunk r (front half via the +1 ring, back
half via the -1 ring), all-gather returns slot-ordered rows.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.fabric.schedule import (
    A2A, AG, AR, HALO, RS, BucketPlan, CollectiveSchedule, Phase)


# ----------------------------------------------------------------------------
# small helpers (shared with core.collectives for API continuity)
# ----------------------------------------------------------------------------

def _ring_perms(axis_size: int, step: int) -> list[tuple[int, int]]:
    """ppermute perm for a one-hop shift (+1 = "clockwise") along a ring."""
    return [(i, (i + step) % axis_size) for i in range(axis_size)]


def _acc_dtype(dtype: jnp.dtype) -> jnp.dtype:
    if jnp.issubdtype(dtype, jnp.floating) and jnp.finfo(dtype).bits < 32:
        return jnp.float32
    return dtype


def _flatten_pad(x: jax.Array, n: int) -> tuple[jax.Array, int]:
    """Flatten to 1D and zero-pad so the length divides ``n``."""
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, flat.size // n


LANES = 128   # TPU vector lane width


def _halves(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Front and back halves of every row of ``x`` (rows, n).

    When n is a whole number of 2 x 128 lanes the halves come back as
    (rows, n // 256, 128) views sliced on a major dim: slicing inside the
    minor dim of a (rows, n) array makes the TPU compiler take minutes once
    n reaches embedding-table sizes (tens of millions)."""
    rows, n = x.shape
    if n % (2 * LANES) == 0:
        v = x.reshape(rows, 2, n // (2 * LANES), LANES)
        return v[:, 0], v[:, 1]
    half = n // 2
    return x[:, :half], x[:, half:]


def _join(front: jax.Array, back: jax.Array) -> jax.Array:
    """Inverse of _halves: each row's front then back half, as (rows, n)."""
    if front.ndim == 3:
        return jnp.stack([front, back], axis=1).reshape(front.shape[0], -1)
    return jnp.concatenate([front, back], axis=-1)


def _lanes(x: jax.Array) -> jax.Array:
    """(rows, n) as (rows, n // 128, 128) when lane-aligned (see _halves)."""
    rows, n = x.shape
    return x.reshape(rows, n // LANES, LANES) if n % LANES == 0 else x


def ring_slot(phase: Phase, axis_name: str | None = None):
    """This rank's slot on the phase ring (traced; = axis index when the
    ring is the identity).  Ranks at dead positions get slot 0 — their
    output is undefined, they send nothing and receive zeros."""
    axis = axis_name or phase.axis
    pos = lax.axis_index(axis)
    n = lax.axis_size(axis)
    if phase.ring == tuple(range(n)):
        return pos
    inv = np.zeros((n,), np.int32)
    for j, p in enumerate(phase.ring):
        inv[p] = j
    return jnp.asarray(inv)[pos]


def _phase_perms(phase: Phase) -> list[list[tuple[int, int]]]:
    return [list(tr.perm) for tr in phase.steps[0].transfers]


# ----------------------------------------------------------------------------
# reduce-scatter
# ----------------------------------------------------------------------------

def _rs_directed(acc, axis: str, perm, slot, m: int, sgn: int, nsteps: int):
    """One directed ring pass over ``acc`` of shape (m, *chunk); returns
    the fully reduced chunk owned by this rank's slot."""
    def body(s, acc):
        send_idx = (slot - sgn * (s + 1)) % m
        recv_idx = (slot - sgn * (s + 2)) % m
        sent = lax.dynamic_index_in_dim(acc, send_idx, axis=0, keepdims=False)
        got = lax.ppermute(sent, axis, perm)
        cur = lax.dynamic_index_in_dim(acc, recv_idx, axis=0, keepdims=False)
        return lax.dynamic_update_index_in_dim(acc, cur + got, recv_idx,
                                               axis=0)

    acc = lax.fori_loop(0, nsteps, body, acc)
    return lax.dynamic_index_in_dim(acc, slot, axis=0, keepdims=False)


def _rs_bidi(acc_f, acc_b, axis: str, perm_f, perm_b, slot, m: int,
             nsteps: int):
    """Both ring directions advanced per round — the fused dual-DMA pass."""
    def body(s, carry):
        af, ab = carry
        send_f = (slot - (s + 1)) % m
        recv_f = (slot - (s + 2)) % m
        send_b = (slot + (s + 1)) % m
        recv_b = (slot + (s + 2)) % m
        got_f = lax.ppermute(
            lax.dynamic_index_in_dim(af, send_f, 0, keepdims=False),
            axis, perm_f)
        got_b = lax.ppermute(
            lax.dynamic_index_in_dim(ab, send_b, 0, keepdims=False),
            axis, perm_b)
        cur_f = lax.dynamic_index_in_dim(af, recv_f, 0, keepdims=False)
        cur_b = lax.dynamic_index_in_dim(ab, recv_b, 0, keepdims=False)
        af = lax.dynamic_update_index_in_dim(af, cur_f + got_f, recv_f, 0)
        ab = lax.dynamic_update_index_in_dim(ab, cur_b + got_b, recv_b, 0)
        return af, ab

    acc_f, acc_b = lax.fori_loop(0, nsteps, body, (acc_f, acc_b))
    out_f = lax.dynamic_index_in_dim(acc_f, slot, 0, keepdims=False)
    out_b = lax.dynamic_index_in_dim(acc_b, slot, 0, keepdims=False)
    return out_f, out_b


def _exec_rs_phase(work: jax.Array, phase: Phase) -> jax.Array:
    """Reduce-scatter one ring phase over flat ``work``; returns this
    slot's fp32-accumulated chunk (front half via +1, back half via -1)."""
    m = phase.ring_size
    flat, chunk = _flatten_pad(work, max(m, 1))
    if m <= 1 or not phase.steps:
        return flat.astype(_acc_dtype(work.dtype))
    acc = flat.reshape(m, chunk).astype(_acc_dtype(work.dtype))
    slot = ring_slot(phase)
    perms = _phase_perms(phase)
    nsteps = len(phase.steps)
    if phase.directions == 2:
        acc_f, acc_b = _halves(acc)
        out_f, out_b = _rs_bidi(acc_f, acc_b, phase.axis,
                                perms[0], perms[1], slot, m, nsteps)
        out = _join(out_f[None], out_b[None])[0]
    else:
        out = _rs_directed(_lanes(acc), phase.axis, perms[0], slot, m, +1,
                           nsteps).reshape(-1)
    return out / m if phase.mean else out


# ----------------------------------------------------------------------------
# all-gather
# ----------------------------------------------------------------------------

def _ag_directed(x, axis: str, perm, slot, m: int, sgn: int, nsteps: int):
    out = jnp.zeros((m,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, slot, axis=0)

    def body(s, carry):
        out, cur = carry
        cur = lax.ppermute(cur, axis, perm)
        src = (slot - sgn * (s + 1)) % m
        out = lax.dynamic_update_index_in_dim(out, cur, src, axis=0)
        return out, cur

    out, _ = lax.fori_loop(0, nsteps, body, (out, x))
    return out


def _ag_bidi(x_f, x_b, axis: str, perm_f, perm_b, slot, m: int, nsteps: int):
    out_f = jnp.zeros((m,) + x_f.shape, x_f.dtype)
    out_b = jnp.zeros((m,) + x_b.shape, x_b.dtype)
    out_f = lax.dynamic_update_index_in_dim(out_f, x_f, slot, axis=0)
    out_b = lax.dynamic_update_index_in_dim(out_b, x_b, slot, axis=0)

    def body(s, carry):
        out_f, cur_f, out_b, cur_b = carry
        cur_f = lax.ppermute(cur_f, axis, perm_f)
        cur_b = lax.ppermute(cur_b, axis, perm_b)
        src_f = (slot - (s + 1)) % m
        src_b = (slot + (s + 1)) % m
        out_f = lax.dynamic_update_index_in_dim(out_f, cur_f, src_f, axis=0)
        out_b = lax.dynamic_update_index_in_dim(out_b, cur_b, src_b, axis=0)
        return out_f, cur_f, out_b, cur_b

    out_f, _, out_b, _ = lax.fori_loop(0, nsteps, body,
                                       (out_f, x_f, out_b, x_b))
    return out_f, out_b


def _exec_ag_phase(work: jax.Array, phase: Phase) -> jax.Array:
    """All-gather one ring phase: flat local chunk -> (m, chunk) rows in
    ring-slot order."""
    m = phase.ring_size
    flat = work.reshape(-1)
    if m <= 1 or not phase.steps:
        return flat[None]
    slot = ring_slot(phase)
    perms = _phase_perms(phase)
    nsteps = len(phase.steps)
    if phase.directions == 2:
        x_f, x_b = _halves(flat[None])
        out_f, out_b = _ag_bidi(x_f[0], x_b[0], phase.axis,
                                perms[0], perms[1], slot, m, nsteps)
        return _join(out_f, out_b)
    out = _ag_directed(_lanes(flat[None])[0], phase.axis, perms[0], slot, m,
                       +1, nsteps)
    return out.reshape(m, -1)


# ----------------------------------------------------------------------------
# whole-schedule executors
# ----------------------------------------------------------------------------

def execute_reduce_scatter(schedule: CollectiveSchedule, x: jax.Array
                           ) -> tuple[jax.Array, list[int]]:
    """Returns (chunk, stage_sizes): the reduced flat chunk this rank owns
    and the per-phase pre-pad sizes an inverse all-gather needs."""
    assert schedule.collective == RS, schedule.collective
    work = x.reshape(-1)
    sizes: list[int] = []
    for ph in schedule.phases:
        sizes.append(work.size)
        work = _exec_rs_phase(work, ph)
    return work, sizes


def execute_all_gather(schedule: CollectiveSchedule, x: jax.Array,
                       stage_sizes: list[int] | None = None) -> jax.Array:
    """Single-phase schedules return slot-ordered rows (m, *x.shape);
    multi-phase (dimension-ordered) walks need ``stage_sizes`` from the
    forward reduce-scatter and return the flat reassembled array."""
    assert schedule.collective == AG, schedule.collective
    if stage_sizes is None:
        if len(schedule.phases) != 1:
            raise ValueError("multi-phase all-gather needs stage_sizes")
        ph = schedule.phases[0]
        out = _exec_ag_phase(x.reshape(-1), ph)
        return out.reshape((max(ph.ring_size, 1),) + x.shape)
    work = x.reshape(-1)
    for ph, size in zip(schedule.phases, reversed(tuple(stage_sizes))):
        work = _exec_ag_phase(work, ph).reshape(-1)[:size]
    return work


def execute_all_reduce(schedule: CollectiveSchedule, x: jax.Array
                       ) -> jax.Array:
    assert schedule.collective == AR, schedule.collective
    work = x.reshape(-1)
    sizes: list[int] = []
    for ph in schedule.phases:
        if ph.kind == RS:
            sizes.append(work.size)
            work = _exec_rs_phase(work, ph)
        else:
            work = _exec_ag_phase(work, ph).reshape(-1)[: sizes.pop()]
    return work.reshape(x.shape).astype(x.dtype)


def execute_all_to_all(schedule: CollectiveSchedule, x: jax.Array
                       ) -> jax.Array:
    """Store-and-forward: x[j] is this rank's block for rank j; returns
    rows holding the block received from each rank."""
    assert schedule.collective == A2A, schedule.collective
    ph = schedule.phases[0]
    n = ph.ring_size
    if ph.ring != tuple(range(n)):
        raise ValueError("all-to-all schedules keep the identity ring")
    if x.shape[0] != n:
        raise ValueError(f"leading dim {x.shape[0]} != ring size {n}")
    if not ph.steps:
        return x
    r = lax.axis_index(ph.axis)
    perm = _phase_perms(ph)[0]
    out = jnp.zeros_like(x)
    out = lax.dynamic_update_index_in_dim(
        out, lax.dynamic_index_in_dim(x, r, 0, keepdims=False), r, axis=0)

    def body(s, carry):
        out, buf = carry
        buf = lax.ppermute(buf, ph.axis, perm)  # buf originated at r-s-1
        src = (r - s - 1) % n
        mine = lax.dynamic_index_in_dim(buf, r, 0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(out, mine, src, axis=0)
        return out, buf

    out, _ = lax.fori_loop(0, len(ph.steps), body, (out, x))
    return out


def execute_halo_exchange(schedule: CollectiveSchedule, x: jax.Array,
                          halo: int = 1, dim: int = 0
                          ) -> tuple[jax.Array, jax.Array]:
    """Returns (from_prev, from_next): both ring neighbours' facing slabs —
    a pair of one-sided puts fired in the same round."""
    assert schedule.collective == HALO, schedule.collective
    ph = schedule.phases[0]
    lo = lax.slice_in_dim(x, 0, halo, axis=dim)
    hi = lax.slice_in_dim(x, x.shape[dim] - halo, x.shape[dim], axis=dim)
    if not ph.steps:
        return hi, lo  # ring of one: own edges wrap straight around
    perm_f, perm_b = _phase_perms(ph)
    from_prev = lax.ppermute(hi, ph.axis, perm_f)
    from_next = lax.ppermute(lo, ph.axis, perm_b)
    return from_prev, from_next


# ----------------------------------------------------------------------------
# bucketed gradient hook — the overlap engine's executor entry point
# ----------------------------------------------------------------------------

def _bucket_identity(schedule: CollectiveSchedule, phase: Phase, m: int,
                     metas: tuple):
    """A tuple-identity whose VJP reduce-scatters the incoming cotangents.

    The forward is a no-op; the backward executes ``schedule`` on each
    leaf's gradient *at the point in the backward pass where that gradient
    materialises* — the fabric rounds are therefore free to overlap the
    remaining backward compute, exactly like the dual-DMA engine draining
    its prefetchable command queue while the host is still producing work
    (paper §2.1).  The returned cotangent is zeros except this rank's
    reduced chunk at its ring slot — the pre-reduced ZeRO-1 shard, embedded
    in a full-size buffer so it is a valid cotangent for the primal.
    ``metas`` are static (shape, dtype) pairs for the bucket's leaves.
    """

    @jax.custom_vjp
    def ident(*leaves):
        return leaves

    def fwd(*leaves):
        return leaves, None

    def bwd(_, gs):
        slot = ring_slot(phase)
        outs = []
        for (shape, dtype), g in zip(metas, gs):
            chunk, _ = execute_reduce_scatter(schedule, g)
            full = jnp.zeros((chunk.shape[0] * m,), chunk.dtype)
            full = lax.dynamic_update_slice(full, chunk,
                                            (slot * chunk.shape[0],))
            n = int(np.prod(shape)) if shape else 1
            outs.append(full[:n].reshape(shape).astype(dtype))
        return tuple(outs)

    ident.defvjp(fwd, bwd)
    return ident


def make_bucket_grad_hook(plan: BucketPlan, schedule: CollectiveSchedule):
    """Per-shard identity over a param tree that bucket-reduce-scatters
    gradients inside the backward pass.

    ``schedule`` must be a single-axis reduce-scatter (possibly fault-
    rewritten).  Wrap the params fed to the differentiated loss:

        hook = make_bucket_grad_hook(plan, rs_schedule)
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(hook(p), batch))(params)

    ``grads`` then hold each leaf's *reduced* chunk at this rank's slice
    (zeros elsewhere); pair with ``apex_zero1_update(pre_reduced=True)``.
    Wire numerics match the sequential per-leaf path bit-for-bit for fp32
    params (lower-precision params pay one extra wire-dtype cast, like any
    bucketed DDP implementation).
    """
    if schedule.collective != RS:
        raise ValueError(
            f"bucket hook needs a reduce-scatter schedule, got "
            f"{schedule.collective!r}")
    if len(schedule.phases) != 1:
        raise ValueError("bucket hook supports single-axis schedules only")
    phase = schedule.phases[0]
    m = max(phase.ring_size, 1)
    if phase.ring != tuple(range(m)):
        # a node-fault-shrunk/reordered ring changes where each rank's
        # reduced chunk lands, but the pre-reduced ZeRO update slices at
        # axis_index over the FULL axis — silent divergence.  Link-fault
        # rewrites keep the identity ring and are fine; node faults must
        # remesh (which the trainer does) rather than reroute.
        raise ValueError(
            f"bucket hook requires the identity ring, got {phase.ring}; "
            "node-fault-shrunk rings change the ZeRO chunk layout")

    def hook(tree):
        leaves, treedef = jax.tree.flatten(tree)
        if len(leaves) != plan.n_leaves:
            raise ValueError(f"tree has {len(leaves)} leaves, plan expects "
                             f"{plan.n_leaves}")
        out = list(leaves)
        for b in plan.buckets:
            group = tuple(leaves[i] for i in b.leaves)
            metas = tuple((jnp.shape(lf), jnp.result_type(lf))
                          for lf in group)
            group = _bucket_identity(schedule, phase, m, metas)(*group)
            for i, v in zip(b.leaves, group):
                out[i] = v
        return jax.tree.unflatten(treedef, out)

    return hook


_EXECUTORS = {
    RS: execute_reduce_scatter,
    AG: execute_all_gather,
    AR: execute_all_reduce,
    A2A: execute_all_to_all,
    HALO: execute_halo_exchange,
}


def execute(schedule: CollectiveSchedule, x: jax.Array, **kw):
    """Dispatch on the schedule's collective kind (per-shard code)."""
    fn = _EXECUTORS.get(schedule.collective)
    if fn is None:
        raise ValueError(
            f"schedule kind {schedule.collective!r} has no per-shard "
            "executor (p2p schedules are priced and fault-rewritten; their "
            "data movement is modelled by the RDMA layer's put_pages)")
    return fn(schedule, x, **kw)
