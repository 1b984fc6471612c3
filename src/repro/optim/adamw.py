"""AdamW with global-norm clipping, cosine schedule, ZeRO-1-friendly state.

Moments are fp32 regardless of param dtype (bf16 training); their
PartitionSpecs come from parallel.sharding.zero1_specs, which shards them
further over the "data" axis — XLA then keeps the update fully sharded and
reduce-scatters gradients into it (ZeRO-1 under GSPMD).

Also provides the *explicit* APEX update used by the paper-faithful DP
trainer: gradients reduce-scattered with the torus ring collectives, the
shard-local moment update, and the parameter all-gather — the RDMA-fabric
version of the same math (runtime/trainer.py wires it into shard_map).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def cosine_schedule(cfg: AdamWConfig, step):
    step = jnp.asarray(step, jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    t = jnp.clip((step - cfg.warmup_steps)
                 / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * t))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def adamw_init(params):
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return {"m": zeros,
            "v": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def adamw_update(cfg: AdamWConfig, grads, state, params):
    """Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    lr = cosine_schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, p):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh = m / bc1
        vh = v / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps)
        if cfg.weight_decay and p.ndim >= 2:   # no decay on norms/biases
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(g, m, v, p)
           for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, metrics


# ----------------------------------------------------------------------------
# APEX explicit ZeRO-1 update (inside shard_map over the DP axes):
#   RS(grads) -> shard-local AdamW on the 1/N state slice -> AG(params)
# All traffic is first-neighbour torus ppermutes (core/collectives).
# ----------------------------------------------------------------------------

def apex_zero1_init(params, dp: int):
    """Shard-local fp32 moment slices: each DP rank owns 1/dp of every
    (flattened, padded) parameter.  Run inside shard_map (out_specs P(dp))
    so the global representation is the concatenation of rank slices."""
    def shard_zeros(p):
        n = p.size
        chunk = -(-n // dp)  # ceil
        return jnp.zeros((chunk,), jnp.float32)

    zeros = jax.tree.map(shard_zeros, params)
    return {"m": zeros, "v": jax.tree.map(jnp.copy, zeros),
            "step": jnp.zeros((), jnp.int32)}


def apex_zero1_update(cfg: AdamWConfig, grads, state, params, *,
                      axis_name: str, rs_schedule=None, ag_schedule=None,
                      pre_reduced: bool = False):
    """Per-shard code (inside shard_map).  grads/params are the full
    (replicated w.r.t. the DP axis) values; moments are 1/N slices.

    ``rs_schedule``/``ag_schedule`` are optional pre-lowered (possibly
    fault-rewritten) ``fabric.CollectiveSchedule`` objects for the gradient
    reduce-scatter and parameter all-gather.

    ``pre_reduced=True`` is the overlap-engine contract: gradients were
    already reduce-scattered inside the backward pass by the fabric's
    bucket grad hook (``fabric.make_bucket_grad_hook``) — each leaf holds
    this rank's reduced chunk at its ring slot (zeros elsewhere), so the
    update only slices its shard out instead of running the collective
    again."""
    from repro.core import collectives as C

    step = state["step"] + 1
    # global grad norm: local full grads are identical only AFTER sync; here
    # grads are per-shard microbatch grads -> mean-reduce first (RS gives us
    # the mean shard directly).
    lr = cosine_schedule(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, p):
        if pre_reduced:
            # bucket hook already ran the ring RS inside backward: slice
            # this rank's chunk (the rest of the buffer is zeros)
            n_ = jax.lax.axis_size(axis_name)
            chunk_ = m.shape[0]
            gflat = g.reshape(-1).astype(jnp.float32)
            gshard = jax.lax.dynamic_slice(
                jnp.pad(gflat, (0, chunk_ * n_ - gflat.size)),
                (jax.lax.axis_index(axis_name) * chunk_,), (chunk_,))
        else:
            # mean gradient shard for this rank (ring reduce-scatter)
            gshard = C.ring_reduce_scatter(g.astype(jnp.float32), axis_name,
                                           mean=True, schedule=rs_schedule)
        pflat = p.reshape(-1)
        m = b1 * m + (1 - b1) * gshard
        v = b2 * v + (1 - b2) * gshard * gshard
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        # matching param shard
        n = jax.lax.axis_size(axis_name)
        chunk = m.shape[0]
        r = jax.lax.axis_index(axis_name)
        pshard = jax.lax.dynamic_slice(
            jnp.pad(pflat, (0, chunk * n - pflat.size)), (r * chunk,),
            (chunk,)).astype(jnp.float32)
        if cfg.weight_decay and p.ndim >= 2:
            delta = delta + cfg.weight_decay * pshard
        new_shard = pshard - lr * delta
        # all-gather the updated parameter (bf16 on the wire)
        full = C.ring_all_gather(new_shard.astype(p.dtype), axis_name,
                                 schedule=ag_schedule)
        return full.reshape(-1)[: p.size].reshape(p.shape), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(g, m, v, p)
           for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_state = {"m": treedef.unflatten([o[1] for o in out]),
                 "v": treedef.unflatten([o[2] for o in out]),
                 "step": step}
    return new_p, new_state
