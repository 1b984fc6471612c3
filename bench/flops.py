"""Operations and bytes a dense decoder's work needs, from its shapes.

``model`` is a configuration's ``model`` block (published key names).
These are the yardstick's counts: what the algorithm needs, not what an
implementation happens to do, so a change to how the program computes
leaves them alone.  Model FLOPs count a multiply-add as 2 and leave out
recomputation, the embedding lookup, norms and softmax.
"""
from __future__ import annotations


def _dims(m: dict):
    return (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"])


def matmul_params(m: dict) -> int:
    """Weights that multiply each token: the layers' projections and MLP,
    and the output head (the tied embedding when tied)."""
    L, d, H, Hkv, hd, f, V = _dims(m)
    attn = d * (H + 2 * Hkv) * hd + H * hd * d
    mlp = (3 if m["mlp"] == "swiglu" else 2) * d * f
    return L * (attn + mlp) + d * V


def attention_flops(m: dict, context: int) -> int:
    """Score and value products of one query over ``context`` positions,
    all layers: 2 * H * hd each for q.k and p.v, per position."""
    L, _, H, _, hd, _, _ = _dims(m)
    return 4 * L * H * hd * int(context)


def decode_flops(m: dict, contexts) -> int:
    """One decode step: each live token, with its context length
    (itself included), through every layer and the head."""
    per_token = 2 * matmul_params(m)
    return sum(per_token + attention_flops(m, c) for c in contexts)


def paged_attention_cost(m: dict, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) of decode attention over the paged cache for live
    tokens with these context lengths, all layers: K and V of every live
    position once, q read and the output written once.  Independent of
    how a kernel walks pages, so the same work reads the same."""
    L, _, H, Hkv, hd, _, _ = _dims(m)
    contexts = [int(c) for c in contexts]
    item = 2   # bf16 cache and activations
    flops = sum(attention_flops(m, c) for c in contexts)
    kv = sum(2 * c * Hkv * hd * item for c in contexts)
    qo = len(contexts) * 2 * H * hd * item
    return flops, L * (kv + qo)


def train_flops_per_token(m: dict, seq_len: int) -> int:
    """Forward and backward of one token of a causal sequence of
    ``seq_len``: 6 per weight, and the attention products over the mean
    causal context (seq_len + 1) / 2, three times over."""
    L, _, H, _, hd, _, _ = _dims(m)
    attn = 3 * 4 * L * H * hd * (seq_len + 1) / 2
    return int(6 * matmul_params(m) + attn)
