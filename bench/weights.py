"""Seeded random weights of a dense decoder, made on the device in one
jitted call, in the layout the program's transformer takes:

  embed: {tok: (V, d)[, head: (d, V)]}
  layers (stacked over L): ln1, ln2: {scale[, bias]},
    attn: {wq, wk, wv, wo[, bq, bk, bv]},
    mlp: {w_gate, w_up, w_down} (swiglu) | {w_up, b_up, w_down, b_down}
  final_norm: {scale[, bias]}

The benchmark hands these to the program and makes them again, from the
same seed, for its reference: the reference takes nothing the program
made.  Norm scales and biases are random too, so that every term of the
layer equations is exercised.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import jax_key_seed


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _norm(key, m, lead, dtype):
    d = m["hidden_size"]
    k1, k2 = jax.random.split(key)
    p = {"scale": (1.0 + _normal(k1, lead + (d,), 0.1, jnp.float32))
         .astype(dtype)}
    if m["norm"] == "layernorm":
        p["bias"] = _normal(k2, lead + (d,), 0.1, dtype)
    return p


def _make(m: dict, dtype, key):
    d, L = m["hidden_size"], m["num_hidden_layers"]
    H, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    f, V = m["intermediate_size"], m["vocab_size"]
    ks = iter(jax.random.split(key, 32))
    lead = (L,)

    def w(fan_in, fan_out):
        return _normal(next(ks), lead + (fan_in, fan_out), fan_in ** -0.5,
                       dtype)

    attn = {"wq": w(d, H * hd), "wk": w(d, Hkv * hd), "wv": w(d, Hkv * hd),
            "wo": w(H * hd, d)}
    if m["qkv_bias"]:
        attn["bq"] = _normal(next(ks), lead + (H * hd,), 0.1, dtype)
        attn["bk"] = _normal(next(ks), lead + (Hkv * hd,), 0.1, dtype)
        attn["bv"] = _normal(next(ks), lead + (Hkv * hd,), 0.1, dtype)
    if m["mlp"] == "swiglu":
        mlp = {"w_gate": w(d, f), "w_up": w(d, f), "w_down": w(f, d)}
    else:
        mlp = {"w_up": w(d, f),
               "b_up": _normal(next(ks), lead + (f,), 0.1, dtype),
               "w_down": w(f, d),
               "b_down": _normal(next(ks), lead + (d,), 0.1, dtype)}
    embed = {"tok": _normal(next(ks), (V, d), 0.02, dtype)}
    if not m["tie_word_embeddings"]:
        embed["head"] = _normal(next(ks), (d, V), d ** -0.5, dtype)
    return {"embed": embed,
            "layers": {"ln1": _norm(next(ks), m, lead, dtype),
                       "ln2": _norm(next(ks), m, lead, dtype),
                       "attn": attn, "mlp": mlp},
            "final_norm": _norm(next(ks), m, (), dtype)}


@functools.lru_cache(maxsize=None)
def _maker(model_items: tuple, dtype_name: str, sharding):
    return jax.jit(functools.partial(_make, dict(model_items),
                                     jnp.dtype(dtype_name)),
                   out_shardings=sharding)


def make(model: dict, seed: int, dtype: str = "bfloat16", device=None,
         sharding=None):
    """The weights for ``seed``, as ``dtype`` arrays on ``device`` (or
    laid out by ``sharding``, e.g. replicated over a mesh)."""
    if device is not None:
        sharding = jax.sharding.SingleDeviceSharding(device)
    lo, hi = jax_key_seed(seed)
    key = jax.random.fold_in(jax.random.key(lo), hi)
    items = tuple(sorted((k, v) for k, v in model.items()
                         if not isinstance(v, (dict, list))))
    return _maker(items, dtype, sharding)(key)
