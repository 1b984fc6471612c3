"""Mixture-of-Experts FFN with sort-based (dropless-style) dispatch.

Dispatch is the sorted-scatter formulation: expand each token k times,
stable-sort by expert id, place into an (E, C, d) capacity buffer, run the
batched expert FFN as (E, C, d) x (E, d, f) einsums (MXU-friendly), then
combine back with the router probabilities.  No (T, E, C) one-hot tensor is
ever materialised — peak extra memory is the k-expanded token buffer.

Under GSPMD the expert axis shards over 'model' (EP): the scatter/gather
pair lowers to the expert all-to-all, which on the torus fabric is exactly
the dimension-ordered A2A of core/collectives (cf. benchmarks/roofline —
the MoE cells are the most collective-bound of the pool).

Overflowed tokens (per-expert demand beyond capacity) are dropped by the
scatter's OOB semantics and contribute zero to the combine — the standard
capacity-factor trade-off; tests cover both the no-drop and drop regimes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import ArchCfg, dense_init


def init_moe(cfg: ArchCfg, key):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_expert, m.n_experts
    kr, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": dense_init(kr, (d, e), jnp.float32, scale=0.02),
        "w_gate": dense_init(k1, (e, d, f), cfg.dtype),
        "w_up": dense_init(k2, (e, d, f), cfg.dtype),
        "w_down": dense_init(k3, (e, f, d), cfg.dtype),
    }


def capacity(cfg: ArchCfg, n_tokens: int) -> int:
    m = cfg.moe
    c = int(n_tokens * m.top_k / m.n_experts * m.capacity_factor)
    return max(c, m.top_k)


def apply_moe(cfg: ArchCfg, p, x, *, dropless: bool = False):
    """x: (B, S, d) -> (y: (B, S, d), aux_loss: scalar fp32).

    ``dropless=True`` sizes every expert's buffer to the full token count,
    so no token is ever capacity-dropped.  Serving paths require this:
    with capacity drops a token's output depends on which other tokens
    share the forward (C scales with T), which would make decode results
    vary with batching and prefill chunking.  Training keeps the capacity
    model (the paper-relevant comm-bounded dispatch)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.n_experts, m.top_k
    C = T if dropless else capacity(cfg, T)
    xt = x.reshape(T, d)

    # --- routing (fp32 for a stable softmax) ---------------------------------
    logits = xt.astype(jnp.float32) @ p["router"]           # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                  # (T, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style)
    me = probs.mean(0)                                       # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (T * K))
    aux = m.router_aux_weight * E * jnp.sum(me * ce)

    # --- sort-based dispatch ---------------------------------------------------
    flat_e = top_e.reshape(-1)                               # (T*K,)
    flat_p = top_p.reshape(-1)
    tok_id = jnp.arange(T * K, dtype=jnp.int32) // K
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)                  # (E,)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)   # OOB -> dropped

    buf = jnp.zeros((E * C, d), x.dtype)
    buf = buf.at[dest].set(xt[tok_id[order]], mode="drop")
    buf = buf.reshape(E, C, d)

    # --- expert FFN (batched over E) --------------------------------------------
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                               p["w_gate"]).astype(jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"]).astype(jnp.float32)
    h = (g * u).astype(x.dtype)
    out = jnp.einsum("ecf,efd->ecd", h, p["w_down"]).reshape(E * C, d)

    # --- combine -------------------------------------------------------------------
    gathered = jnp.where(keep[:, None],
                         jnp.take(out, jnp.minimum(dest, E * C - 1), axis=0),
                         0.0)
    weighted = gathered.astype(jnp.float32) * flat_p[order][:, None]
    y = jnp.zeros((T, d), jnp.float32).at[tok_id[order]].add(weighted)
    return y.reshape(B, S, d).astype(x.dtype), aux


# ----------------------------------------------------------------------------
# Expert-parallel dispatch with explicit all-to-alls (§Perf H2).
#
# The global sort-based dispatch above is a single data-dependent scatter
# over a (T*K, d) buffer: GSPMD cannot see an all-to-all in it, so at 256
# chips it all-gathers the expanded token buffer (the olmoe/moonshot train
# cells were ~50x collective-bound at baseline).  Here the routing runs
# *locally* per (data x model) shard inside shard_map and only the
# capacity-bounded expert buffers cross the 'model' axis — two explicit
# lax.all_to_all ops (dispatch + return), which is exactly the
# dimension-ordered torus A2A of the paper's fabric.
# ----------------------------------------------------------------------------


def _local_dispatch(cfg: ArchCfg, xt, router, K, E, C):
    """Route a local token block: returns (buf (E*C, d), combine closure)."""
    T, d = xt.shape
    m = cfg.moe
    logits = xt.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    tok_id = jnp.arange(T * K, dtype=jnp.int32) // K
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(T * K, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    dest = jnp.where(keep, sorted_e * C + pos_in_e, E * C)
    buf = jnp.zeros((E * C, d), xt.dtype)
    buf = buf.at[dest].set(xt[tok_id[order]], mode="drop")

    def combine(outbuf):
        gathered = jnp.where(
            keep[:, None],
            jnp.take(outbuf, jnp.minimum(dest, E * C - 1), axis=0), 0.0)
        weighted = gathered.astype(jnp.float32) * flat_p[order][:, None]
        return jnp.zeros((T, d), jnp.float32).at[tok_id[order]].add(weighted)

    return buf, combine, probs, flat_e


def apply_moe_ep(cfg: ArchCfg, p, x):
    """shard_map EP MoE: x (B, S, d) -> (y, aux).  Tokens are sharded over
    (DP x 'model') for routing; capacity buffers cross 'model' via two
    explicit all_to_alls; experts stay sharded over 'model' (EP)."""
    from jax.sharding import PartitionSpec as P

    from repro.parallel import sharding as shd

    mesh = shd.runtime_mesh()
    m = cfg.moe
    tp = 1 if mesh is None else shd.tp_size(mesh)
    B, S, d = x.shape
    if mesh is None or tp <= 1 or m.n_experts % tp or S % tp \
            or (B % max(shd.dp_size(mesh), 1)):
        return apply_moe(cfg, p, x)   # graceful fallback: global dispatch
    dpx = shd.dp_axes(mesh)
    E, K = m.n_experts, m.top_k
    E_loc = E // tp
    T_loc = (B // max(shd.dp_size(mesh), 1)) * (S // tp)
    C = max(int(T_loc * K / E * m.capacity_factor), K)
    all_axes = tuple(dpx) + ("model",)

    def local(xs, router, wg, wu, wd):
        Bl, Sl, _ = xs.shape
        xt = xs.reshape(Bl * Sl, d)
        buf, combine, probs, flat_e = _local_dispatch(cfg, xt, router, K, E,
                                                      C)
        # Switch-style aux loss from globally-averaged router stats
        me = jax.lax.pmean(probs.mean(0), all_axes)
        ce = jax.lax.pmean(
            jnp.zeros((E,), jnp.float32).at[flat_e].add(
                1.0 / flat_e.shape[0]), all_axes)
        aux = m.router_aux_weight * E * jnp.sum(me * ce)
        # dispatch A2A: (tp, E_loc*C, d) -> dim0 becomes the sender rank
        send = buf.reshape(tp, E_loc * C, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0)
        toks = recv.reshape(tp, E_loc, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_loc, tp * C, d)
        # local expert FFN (E_loc experts on this shard)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", toks,
                                   wg).astype(jnp.float32))
        u = jnp.einsum("ecd,edf->ecf", toks, wu).astype(jnp.float32)
        hmid = (g * u).astype(xs.dtype)
        out = jnp.einsum("ecf,efd->ecd", hmid, wd)
        # return A2A: route expert outputs back to their senders
        back = out.reshape(E_loc, tp, C, d).transpose(1, 0, 2, 3) \
            .reshape(tp, E_loc * C, d)
        ret = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0)
        y = combine(ret.reshape(E * C, d))
        return y.reshape(Bl, Sl, d).astype(x.dtype), aux

    in_specs = (P(tuple(dpx), "model", None), P(), P("model", None, None),
                P("model", None, None), P("model", None, None))
    out_specs = (P(tuple(dpx), "model", None), P())
    mapped = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    y, aux = mapped(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, jnp.mean(aux)
