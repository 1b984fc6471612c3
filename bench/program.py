"""The system under test, as a configuration file names it.

The configuration's ``model`` block holds the published sizes; the
program's own ``ArchCfg`` of the named ``arch`` is overridden with every
one of them, so the file, not the program's preset, says what runs.
"""
from __future__ import annotations

import dataclasses

import harness  # noqa: F401  (puts the program on sys.path)

_NORM = {"rmsnorm": "rms", "layernorm": "ln"}
_MLP = {"swiglu": "swiglu", "gelu_tanh": "gelu"}


def arch_config(cfg: dict):
    """The program's ArchCfg for a configuration file."""
    from repro import configs
    m = cfg["model"]
    base = configs.get_config(cfg["arch"])
    if base.family != "dense":
        raise ValueError(f"{cfg['arch']}: only dense decoders are wired up")
    return dataclasses.replace(
        base,
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        norm=_NORM[m["norm"]], mlp=_MLP[m["mlp"]], qkv_bias=m["qkv_bias"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["norm_eps"])
