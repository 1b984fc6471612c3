"""Decoder-only transformer LM (dense + MoE) — also the VLM backbone.

Layer stacks are walked with lax.scan over stacked parameters (L leading
axis) and rematerialised per layer, so the lowered HLO is depth-independent:
an 80-layer dry-run compiles as fast as a 2-layer one, and activation
memory for train_4k stays at O(1 layer).

Three entry points per the assigned shape families:
  * train_loss  — full-sequence causal LM loss (train_4k)
  * prefill     — full forward that also returns the KV cache (prefill_32k)
  * decode_step — one token against the dense KV cache (decode_32k)
The paged decode path (the §2.2 TLB adaptation) lives in serving/engine.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.models import attention as attn
from repro.models import common, moe
from repro.models.common import ArchCfg
from repro.parallel import sharding


def init_layer(cfg: ArchCfg, key):
    k1, k2 = jax.random.split(key)
    p = {"ln1": common.init_norm(cfg), "ln2": common.init_norm(cfg),
         "attn": attn.init_attn(cfg, k1)}
    if cfg.moe is not None:
        p["moe"] = moe.init_moe(cfg, k2)
    else:
        p["mlp"] = common.init_mlp(cfg, k2)
    return p


def init_lm(cfg: ArchCfg, key):
    ke, kl, kn = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    return {
        "embed": common.init_embed(cfg, ke),
        "layers": common.stacked(layer_keys,
                                 functools.partial(init_layer, cfg)),
        "final_norm": common.init_norm(cfg),
    }


def _constrain(cfg: ArchCfg, h):
    if cfg.tp_activations == "megatron":
        return sharding.constrain_activations(h)
    if cfg.tp_activations == "sp":
        return sharding.constrain_activations(h, seq_axis="model")
    return h


def _layer_fwd(cfg: ArchCfg, lp, h, freqs, causal):
    h = _constrain(cfg, h)
    a, _ = attn.attn_full(cfg, lp["attn"], common.apply_norm(cfg, lp["ln1"], h),
                          freqs=freqs, causal=causal)
    h = _constrain(cfg, h + a)
    if cfg.moe is not None:
        apply = moe.apply_moe_ep if cfg.moe_impl == "ep_a2a" else \
            moe.apply_moe
        m, aux = apply(cfg, lp["moe"], common.apply_norm(cfg, lp["ln2"], h))
    else:
        m = common.apply_mlp(cfg, lp["mlp"],
                             common.apply_norm(cfg, lp["ln2"], h))
        aux = jnp.zeros((), jnp.float32)
    return _constrain(cfg, h + m), aux


def forward(cfg: ArchCfg, params, h, *, causal: bool = True,
            remat: bool = True):
    """Run the layer stack over embeddings h: (B, S, d) -> (h, aux_loss)."""
    if cfg.tp_activations == "manual_sp" and causal \
            and _manual_sp_applicable(cfg):
        out = _stack_manual_sp(cfg, params["layers"], h, remat=remat)
        if out is not None:
            h, aux = out
            return common.apply_norm(cfg, params["final_norm"], h), aux
    freqs = common.rope_freqs(cfg)

    def body(carry, lp):
        h, aux = carry
        h, a = _layer_fwd(cfg, lp, h, freqs, causal)
        return (h, aux + a), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                               params["layers"])
    return common.apply_norm(cfg, params["final_norm"], h), aux


# ----------------------------------------------------------------------------
# §Perf "manual_sp": the dense layer stack hand-SPMD'd in shard_map —
# Megatron-style sequence parallelism with EXPLICIT collectives, so the
# wire dtype is the activation dtype (bf16) instead of the partitioner's
# post-upcast f32, and exactly one all-gather + one reduce-scatter of the
# (B, S, d) stream crosses 'model' per block:
#
#   h_loc --ln--> AG(seq) -> qkv (local heads) -> attn -> @wo (partial)
#         --RS(seq, summed)--> +residual --ln--> AG -> swiglu (f-sharded)
#         -> @w_down (partial) --RS--> +residual
#
# This is the same schedule the APEnet+ fabric would run as neighbour RDMA
# rings; autodiff of all_gather/psum_scatter gives the transposed
# collectives in the backward pass for free.
# ----------------------------------------------------------------------------


def _manual_sp_applicable(cfg: ArchCfg) -> bool:
    return cfg.moe is None and cfg.mlp == "swiglu" and cfg.n_heads > 0


def _manual_sp_ok(cfg: ArchCfg, mesh) -> bool:
    tp = mesh.shape.get("model", 1)
    return (tp > 1 and cfg.n_heads % tp == 0 and cfg.n_kv_heads % tp == 0
            and cfg.d_ff % tp == 0)


def _stack_manual_sp(cfg: ArchCfg, layers, h, *, remat: bool):
    from jax.sharding import PartitionSpec as P

    mesh = sharding.runtime_mesh()
    if mesh is None or not _manual_sp_ok(cfg, mesh):
        return None
    dpx = sharding.dp_axes(mesh)
    S = h.shape[1]
    if not dpx or S % mesh.shape["model"] or h.shape[0] % \
            sharding.dp_size(mesh):
        return None
    hd = cfg.resolved_head_dim
    freqs = common.rope_freqs(cfg)

    def layer(h_loc, lp):
        x = common.apply_norm(cfg, lp["ln1"], h_loc)
        xf = jax.lax.all_gather(x, "model", axis=1, tiled=True)  # (B,S,d)
        B, S_, _ = xf.shape
        q = xf @ lp["attn"]["wq"]
        k = xf @ lp["attn"]["wk"]
        v = xf @ lp["attn"]["wv"]
        if cfg.qkv_bias:
            q, k, v = q + lp["attn"]["bq"], k + lp["attn"]["bk"], \
                v + lp["attn"]["bv"]
        q = q.reshape(B, S_, -1, hd)
        k = k.reshape(B, S_, -1, hd)
        v = v.reshape(B, S_, -1, hd)
        pos = jnp.arange(S_)[None]
        q = common.apply_rope(q, pos, freqs)
        k = common.apply_rope(k, pos, freqs)
        out = kref.mha_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True,
            compute_dtype=jnp.bfloat16 if cfg.attn_dtype == "bf16"
            else jnp.float32)
        out = out.transpose(0, 2, 1, 3).reshape(B, S_, -1)
        part = (out @ lp["attn"]["wo"]).astype(h_loc.dtype)
        h_loc = h_loc + jax.lax.psum_scatter(part, "model",
                                             scatter_dimension=1, tiled=True)
        x2 = common.apply_norm(cfg, lp["ln2"], h_loc)
        x2f = jax.lax.all_gather(x2, "model", axis=1, tiled=True)
        mp = common.apply_mlp(cfg, lp["mlp"], x2f).astype(h_loc.dtype)
        h_loc = h_loc + jax.lax.psum_scatter(mp, "model",
                                             scatter_dimension=1, tiled=True)
        return h_loc

    def stack(h_loc, ls):
        def body(carry, lp):
            return layer(carry, lp), None

        b = body
        if remat:
            b = jax.checkpoint(
                b, policy=jax.checkpoint_policies.nothing_saveable)
        h_loc, _ = jax.lax.scan(b, h_loc, ls)
        return h_loc

    def leaf_spec(path, leaf):
        name = [getattr(kk, "key", None) for kk in path][-1]
        nd = leaf.ndim
        if name in ("wq", "wk", "wv"):
            return P(*([None] * (nd - 1) + ["model"]))
        if name in ("bq", "bk", "bv"):
            return P(None, "model")
        if name == "wo":
            return P(None, "model", None)
        if name in ("w_gate", "w_up"):
            return P(None, None, "model")
        if name == "w_down":
            return P(None, "model", None)
        return P(*([None] * nd))      # norms etc: replicated

    lspecs = jax.tree_util.tree_map_with_path(leaf_spec, layers)
    hspec = P(tuple(dpx), "model", None)
    mapped = jax.shard_map(stack, mesh=mesh, in_specs=(hspec, lspecs),
                           out_specs=hspec, check_vma=False)
    return mapped(h, layers), jnp.zeros((), jnp.float32)


def embed_inputs(cfg: ArchCfg, params, batch) -> tuple[jax.Array, jax.Array]:
    """tokens (+ optional stub-frontend prefix embeddings) -> (h, labels)."""
    h = common.embed_tokens(params["embed"], batch["tokens"])
    labels = batch.get("labels")
    if "prefix_embeds" in batch:  # VLM: precomputed patch embeddings
        pre = batch["prefix_embeds"].astype(h.dtype)
        h = jnp.concatenate([pre, h], axis=1)
        if labels is not None:
            ignore = jnp.full(pre.shape[:2], -1, labels.dtype)
            labels = jnp.concatenate([ignore, labels], axis=1)
    return h, labels


def train_loss(cfg: ArchCfg, params, batch, *, remat: bool = True):
    h, labels = embed_inputs(cfg, params, batch)
    h, aux = forward(cfg, params, h, causal=True, remat=remat)
    logits = common.lm_head(cfg, params["embed"], h)
    return common.cross_entropy(logits, labels) + aux


# ----------------------------------------------------------------------------
# serving paths
# ----------------------------------------------------------------------------

def prefill(cfg: ArchCfg, params, batch, *, max_len: int | None = None,
            remat: bool = True, return_hidden: bool = False,
            moe_dropless: bool = False):
    """Forward + build the dense KV cache.  Returns (logits_last, cache)
    [+ final hidden states when return_hidden — serving engines pick their
    own logits position for padded prompts].  ``moe_dropless`` forces the
    capacity-free MoE dispatch serving requires (tokens must not depend on
    what else shares the forward)."""
    h, _ = embed_inputs(cfg, params, batch)
    B, S, _ = h.shape
    # VLM prefix embeddings extend S beyond the token budget: the cache must
    # cover the full (prefix + tokens) context
    max_len = max(max_len or S, S)
    freqs = common.rope_freqs(cfg)

    def body(h, lp):
        x = common.apply_norm(cfg, lp["ln1"], h)
        a, (k, v) = attn.attn_full(cfg, lp["attn"], x, freqs=freqs,
                                   causal=True)
        h = h + a
        if cfg.moe is not None:
            x2 = common.apply_norm(cfg, lp["ln2"], h)
            if moe_dropless:
                m, _ = moe.apply_moe(cfg, lp["moe"], x2, dropless=True)
            elif cfg.moe_impl == "ep_a2a":
                m, _ = moe.apply_moe_ep(cfg, lp["moe"], x2)
            else:
                m, _ = moe.apply_moe(cfg, lp["moe"], x2)
        else:
            m = common.apply_mlp(cfg, lp["mlp"],
                                 common.apply_norm(cfg, lp["ln2"], h))
        pad = max_len - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return h + m, (k, v)

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    h, (ks, vs) = jax.lax.scan(body, h, params["layers"])
    h = common.apply_norm(cfg, params["final_norm"], h)
    logits = common.lm_head(cfg, params["embed"], h[:, -1:])
    if return_hidden:
        return logits, {"k": ks, "v": vs}, h
    return logits, {"k": ks, "v": vs}


def decode_step(cfg: ArchCfg, params, token, cache, pos):
    """token: (B, 1) int32; cache: {'k','v'}: (L,B,Smax,Hkv,hd); pos scalar.

    Returns (logits (B,1,V), new_cache)."""
    h = common.embed_tokens(params["embed"], token)
    freqs = common.rope_freqs(cfg)

    def body(h, xs):
        lp, kc, vc = xs
        x = common.apply_norm(cfg, lp["ln1"], h)
        a, kc, vc = attn.attn_decode(cfg, lp["attn"], x, kc, vc, pos,
                                     freqs=freqs)
        h = h + a
        if cfg.moe is not None:
            m, _ = moe.apply_moe(cfg, lp["moe"],
                                 common.apply_norm(cfg, lp["ln2"], h))
        else:
            m = common.apply_mlp(cfg, lp["mlp"],
                                 common.apply_norm(cfg, lp["ln2"], h))
        return h + m, (kc, vc)

    h, (ks, vs) = jax.lax.scan(body, h, (params["layers"], cache["k"],
                                         cache["v"]))
    h = common.apply_norm(cfg, params["final_norm"], h)
    logits = common.lm_head(cfg, params["embed"], h)
    return logits, {"k": ks, "v": vs}
