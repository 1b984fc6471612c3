"""Sharding rules: PartitionSpecs for params, batches and decode state.

Axes: DP over ("pod", "data") [batch], TP over "model" [heads / hidden /
vocab / experts], ZeRO-1 optimizer-state sharding over "data".  PP is not
enabled for the assigned shapes (every config fits TP x DP at 512 chips);
the natural hook is a leading "stage" mesh axis plus a stage-sliced layer
scan — documented here, implemented when depth x batch demands it.

Parameter rules are path+shape driven so one rule set covers all ten arch
families (stacked layer params carry a leading L axis that is never
sharded):

  * MoE expert tensors (E, d, f): E -> "model"  (expert parallelism; the
    token dispatch then lowers to the torus all-to-all)
  * other >=2D weights: shard the last dim whose size divides |model| and
    that is not d_model; fall back to any divisible dim; else replicate
    (e.g. GQA kv projections with 2 kv heads < 16-way TP stay replicated)
  * 1D tensors: shard iff not d_model-sized and divisible (biases of
    sharded projections follow their matrix)
  * norms / scalars / tiny leaves: replicated

Batch rule: batch dim over DP axes when divisible (long_500k has batch 1 —
the KV-cache sequence dim shards over "data" instead: SP-style decode).
"""
from __future__ import annotations


import numpy as np

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ArchCfg

STACKED_KEYS = {"layers", "mamba", "enc_layers", "dec_layers"}
MOE_EXPERT_KEYS = {"w_gate", "w_up", "w_down"}


def abstract_mesh(shape, axes) -> AbstractMesh:
    """Device-less mesh: spec-level tests build production meshes here."""
    return AbstractMesh(tuple(shape), tuple(axes))


def dp_axes(mesh: Mesh, cfg: ArchCfg | None = None) -> tuple[str, ...]:
    names = ["pod", "data"]
    if cfg is not None and cfg.parallelism == "dp_only":
        names.append("model")   # batch over every axis, params replicated
    return tuple(a for a in names if a in mesh.axis_names)


def dp_size(mesh: Mesh, cfg: ArchCfg | None = None) -> int:
    return int(np.prod([mesh.shape[a] for a in dp_axes(mesh, cfg)],
                       initial=1))


def tp_size(mesh: Mesh, cfg: ArchCfg | None = None) -> int:
    if cfg is not None and cfg.parallelism == "dp_only":
        return 1
    return mesh.shape.get("model", 1)


def _param_spec(path, shape, cfg: ArchCfg, tp: int) -> P:
    keys = [getattr(k, "key", None) for k in path]
    name = keys[-1]
    stacked = any(k in STACKED_KEYS for k in keys)
    dims = list(shape[1:]) if stacked else list(shape)
    offset = 1 if stacked else 0

    def lift(spec_dims):
        return P(*([None] * offset + spec_dims))

    if not dims:
        return P()
    if tp <= 1:  # no TP axis in this mesh: everything replicated
        return lift([None] * len(dims))
    # MoE expert tensors: expert-parallel on the leading E axis
    if "moe" in keys and name in MOE_EXPERT_KEYS and len(dims) == 3:
        if dims[0] % tp == 0:
            return lift(["model", None, None])
        return lift([None, None, None])
    if len(dims) == 1:
        n = dims[0]
        if n != cfg.d_model and n % tp == 0 and n >= tp:
            return lift(["model"])
        return lift([None])
    # >= 2D: prefer last non-d_model divisible dim, then any divisible dim
    spec = [None] * len(dims)
    candidates = [i for i in reversed(range(len(dims)))
                  if dims[i] % tp == 0 and dims[i] >= tp]
    preferred = [i for i in candidates if dims[i] != cfg.d_model]
    pick = (preferred or candidates)
    if pick:
        spec[pick[0]] = "model"
    return lift(spec)


def param_specs(cfg: ArchCfg, shapes, mesh: Mesh):
    """PartitionSpec pytree matching the param-shape pytree."""
    tp = tp_size(mesh, cfg)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_spec(path, leaf.shape, cfg, tp), shapes)


def zero1_specs(cfg: ArchCfg, shapes, mesh: Mesh):
    """Optimizer-moment specs: params' specs + the largest remaining dim
    sharded over "data" (ZeRO-1: moments never need to be re-gathered for
    the forward pass, so they can shard further than params)."""
    base = param_specs(cfg, shapes, mesh)
    nd = mesh.shape.get("data", 1)
    if nd <= 1:  # no data axis: ZeRO-1 degenerates to plain param specs
        return base

    # dp_only: params are replicated, so moments can shard over the whole
    # (data x model) device grid
    zaxes = ("data", "model") if cfg.parallelism == "dp_only" \
        and "model" in mesh.axis_names else ("data",)
    nz = int(np.prod([mesh.shape[a] for a in zaxes]))

    def extend(path, leaf, spec):
        dims = list(leaf.shape)
        used = list(spec) + [None] * (len(dims) - len(spec))
        order = sorted(range(len(dims)), key=lambda i: -dims[i])
        for i in order:
            if used[i] is None and dims[i] % nz == 0 and dims[i] >= nz:
                used[i] = zaxes if len(zaxes) > 1 else "data"
                break
        else:
            for i in order:
                if used[i] is None and dims[i] % nd == 0 and dims[i] >= nd:
                    used[i] = "data"
                    break
        return P(*used)

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf, spec: extend(path, leaf, spec), shapes, base)


# ----------------------------------------------------------------------------
# batch / state specs
# ----------------------------------------------------------------------------

def _dp_prefix(mesh: Mesh, cfg: ArchCfg | None, n: int) \
        -> tuple[tuple[str, ...], int]:
    """Longest prefix of the DP axes whose size product divides n."""
    out: list[str] = []
    prod = 1
    for a in dp_axes(mesh, cfg):
        if n > 0 and n % (prod * mesh.shape[a]) == 0:
            out.append(a)
            prod *= mesh.shape[a]
        else:
            break
    return tuple(out), prod


def batch_specs(cfg: ArchCfg, batch_shapes, mesh: Mesh):
    """Shard the leading (batch) dim of every input over the largest
    dividing DP-axis prefix; under dp_only an idle 'model' axis picks up
    the sequence dim instead (SP) — a global batch smaller than the
    device grid must never silently replicate the whole computation."""
    def one(leaf):
        dims = list(leaf.shape)
        if not dims:
            return P()
        axes_used, _ = _dp_prefix(mesh, cfg, dims[0])
        spec: list = [None] * len(dims)
        if axes_used:
            spec[0] = axes_used
        if cfg.parallelism == "dp_only" and "model" not in axes_used \
                and "model" in mesh.axis_names and len(dims) >= 2 \
                and dims[1] % mesh.shape["model"] == 0 and dims[1] > 1:
            spec[1] = "model"
        return P(*spec)

    return jax.tree.map(one, batch_shapes)


def decode_state_specs(cfg: ArchCfg, state_shapes, mesh: Mesh,
                       global_batch: int):
    """Decode caches: batch over the largest dividing DP-axis prefix;
    head-indexed dims shard over "model" under TP; a leftover axis
    ("model" under dp_only, "data" at batch 1) picks up the cache
    *sequence* dim (sequence-parallel decode)."""
    axes_used, nprod = _dp_prefix(mesh, cfg, global_batch)
    tp = tp_size(mesh, cfg)

    def _seq_shard(spec, dims, axis_name, min_dim=1024):
        m = mesh.shape[axis_name]
        order = sorted(range(len(dims)), key=lambda i: -dims[i])
        for i in order:
            if spec[i] is None and dims[i] % m == 0 and dims[i] > min_dim:
                spec[i] = axis_name
                return

    def one(path, leaf):
        dims = list(leaf.shape)
        spec = [None] * len(dims)
        # find the batch dim (== global_batch); caches carry leading L axis
        if axes_used:
            for i, d in enumerate(dims):
                if d == global_batch:
                    spec[i] = axes_used
                    break
        # shard one more dim over model: prefer head-count / feature dims
        if tp > 1:
            for i in reversed(range(len(dims))):
                if spec[i] is None and dims[i] % tp == 0 and dims[i] >= tp \
                        and i != len(dims) - 1:  # keep head_dim/lane dim whole
                    spec[i] = "model"
                    break
        elif cfg.parallelism == "dp_only" and "model" not in axes_used \
                and "model" in mesh.axis_names:
            _seq_shard(spec, dims, "model")      # SP decode over 'model'
        if not axes_used and "data" in mesh.axis_names:
            _seq_shard(spec, dims, "data")       # long_500k: seq over data
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, state_shapes)


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree)


# ----------------------------------------------------------------------------
# runtime mesh registry: models are pure functions of (cfg, params, batch),
# but two §Perf optimizations need the ambient mesh while tracing —
# activation sharding constraints (Megatron TP) and the shard_map EP
# all-to-all.  The launcher/trainer registers its mesh here; with no mesh
# registered both helpers are no-ops and the model stays mesh-agnostic.
# ----------------------------------------------------------------------------

_RUNTIME_MESH: Mesh | None = None


def set_runtime_mesh(mesh: Mesh | None) -> None:
    global _RUNTIME_MESH
    _RUNTIME_MESH = mesh


def runtime_mesh() -> Mesh | None:
    return _RUNTIME_MESH


def constrain_activations(x, *, seq_axis: str | None = None):
    """Pin a (B, S, d) activation to batch-over-DP [,seq-over-model].

    Megatron-style TP keeps the residual stream replicated over 'model'
    (seq_axis=None); sequence parallelism shards S over 'model' instead
    (seq_axis='model').  No-op without a registered mesh."""
    mesh = _RUNTIME_MESH
    if mesh is None:
        return x
    axes = dp_axes(mesh)  # constraint path: cfg-independent (tp modes only)
    if not axes:
        return x
    spec = [axes] + [None] * (x.ndim - 1)
    if seq_axis and seq_axis in mesh.axis_names and x.ndim >= 2 \
            and x.shape[1] % mesh.shape[seq_axis] == 0:
        spec[1] = seq_axis
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
