"""Plain float32 dense decoder: forward, logits, loss and gradient.

Written from the layer equations, in ``jax.numpy`` with every product at
``Precision.HIGHEST``, with no kernel, cache or batching of the program's:

  h = E[tokens]
  per layer:  x = norm1(h);  q, k, v = x Wq (+bq), x Wk (+bk), x Wv (+bv)
              q, k = rope(q), rope(k)          (rotate-half, base theta)
              a = softmax(q k^T / sqrt(hd) + causal) v, GQA: query head i
                  reads KV head i // (H / Hkv)
              h = h + a Wo
              h = h + mlp(norm2(h))   swiglu: (silu(x Wg) * x Wu) Wd
                                      gelu_tanh: gelu(x Wu + bu) Wd + bd
  logits = norm_f(h) E^T (tied) or norm_f(h) W_head
  norm: RMSNorm x / sqrt(mean(x^2) + eps) * s, or LayerNorm with a bias

``quant="fp8"`` is the control: every projection's two operands rounded
to float8 e4m3 with one scale per tensor (and their gradients to e5m2),
the precision a step below the bfloat16 the configurations run in.  Layers are walked one at a time
(a scan) so the reference fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _scaled(t, dtype):
    """``t`` rounded to an fp8 ``dtype`` under one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / float(jnp.finfo(dtype).max)
    return (t / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(t):
    """An operand in fp8: e4m3 forward; the gradient flowing back through
    it in e5m2, each with its own per-tensor scale (the usual recipe)."""
    return _scaled(t, jnp.float8_e4m3fn)


def _fp8_fwd(t):
    return _fp8(t), None


def _fp8_bwd(_, ct):
    return (_scaled(ct, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def _norm(m, p, x):
    eps = m["norm_eps"]
    if m["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    """x: (..., S, heads, hd); pos: (..., S)."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[..., None].astype(jnp.float32) * inv           # (..., S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    half = hd // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def _mlp(m, p, x, quant):
    if m["mlp"] == "swiglu":
        g = _mm(x, p["w_gate"], quant)
        return _mm(jax.nn.sigmoid(g) * g * _mm(x, p["w_up"], quant),
                   p["w_down"], quant)
    u = _gelu_tanh(_mm(x, p["w_up"], quant) + p["b_up"])
    return _mm(u, p["w_down"], quant) + p["b_down"]


def _attention(m, p, x, quant):
    """x: (B, S, d) -> (B, S, d), causal."""
    B, S, _ = x.shape
    H, Hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    q, k, v = (_mm(x, p["wq"], quant), _mm(x, p["wk"], quant),
               _mm(x, p["wv"], quant))
    if m["qkv_bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = _rope(q.reshape(B, S, H, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(B, S, Hkv, hd), pos, m["rope_theta"])
    v = v.reshape(B, S, Hkv, hd)
    kv_of = jnp.arange(H) // (H // Hkv)
    k, v = k[:, :, kv_of], v[:, :, kv_of]                    # (B, S, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    return _mm(a.reshape(B, S, H * hd), p["wo"], quant)


def hidden(m: dict, params, tokens, *, quant=None, remat=False):
    """Final normed hidden states, (B, S, d), of tokens (B, S)."""
    h = params["embed"]["tok"][tokens].astype(jnp.float32)

    def layer(h, lp):
        h = h + _attention(m, lp["attn"], _norm(m, lp["ln1"], h), quant)
        h = h + _mlp(m, lp["mlp"], _norm(m, lp["ln2"], h), quant)
        return h, None

    if remat:
        layer = jax.checkpoint(layer)
    h, _ = jax.lax.scan(layer, h, params["layers"])
    return _norm(m, params["final_norm"], h)


def head(m: dict, params, h, *, quant=None):
    w = (params["embed"]["tok"].T if m["tie_word_embeddings"]
         else params["embed"]["head"])
    return _mm(h, w, quant)


def to_f32(params):
    return jax.tree.map(lambda x: x.astype(jnp.float32), params)


def _items(m: dict) -> tuple:
    return tuple(sorted((a, b) for a, b in m.items()
                        if not isinstance(b, (dict, list))))


def _position_logits(m, params, tokens, rows, quant=None):
    h = hidden(m, params, tokens[None], quant=quant)[0]
    return head(m, params, h[rows], quant=quant)


@functools.partial(jax.jit, static_argnums=(0,))
def _gaps(m_items, params, tokens, rows, served):
    ref = _position_logits(dict(m_items), params, tokens, rows)
    pick = jnp.take_along_axis(ref, served[:, None], 1)[:, 0]
    return ref.max(-1) - pick


@functools.partial(jax.jit, static_argnums=(0,))
def _control_gaps(m_items, params, tokens, rows):
    m = dict(m_items)
    ref = _position_logits(m, params, tokens, rows)
    ctrl = _position_logits(m, params, tokens, rows, quant="fp8")
    pick = jnp.take_along_axis(ref, jnp.argmax(ctrl, -1)[:, None], 1)[:, 0]
    return ref.max(-1) - pick


def _served_rows(prompt, out_tokens, seq_pad, rows_pad):
    import numpy as np
    n, k = len(prompt), len(out_tokens)
    seq = np.zeros(seq_pad, np.int32)
    seq[:n] = prompt
    seq[n:n + k - 1] = out_tokens[:-1]
    rows = np.zeros(rows_pad, np.int32)
    rows[:k] = n - 1 + np.arange(k)
    served = np.zeros(rows_pad, np.int32)
    served[:k] = out_tokens
    return seq, rows, served


def served_gaps(m: dict, params, prompt, out_tokens, *, seq_pad: int,
                rows_pad: int):
    """For one served request: at each served token's position, how far
    its reference logit lies below the reference's best (0 where the
    reference would have served the same token).

    The sequence is prompt + served tokens but the last, padded to
    ``seq_pad`` (causal, so padding after it changes nothing); served
    token j is read at position len(prompt) - 1 + j.  Returns a numpy
    array of len(out_tokens)."""
    import numpy as np
    seq, rows, served = _served_rows(prompt, out_tokens, seq_pad, rows_pad)
    gap = _gaps(_items(m), params, seq, rows, served)
    return np.asarray(gap)[:len(out_tokens)]


def control_gaps(m: dict, params, prompt, out_tokens, *, seq_pad: int,
                 rows_pad: int):
    """The control's reading at the same positions as ``served_gaps``: the
    gap of the token that the fp8 reference puts first."""
    import numpy as np
    seq, rows, _ = _served_rows(prompt, out_tokens, seq_pad, rows_pad)
    gap = _control_gaps(_items(m), params, seq, rows)
    return np.asarray(gap)[:len(out_tokens)]


def loss(m: dict, params, tokens, labels, *, remat=True, quant=None):
    """Mean next-token cross-entropy over (B, S)."""
    logits = head(m, params, hidden(m, params, tokens, remat=remat,
                                    quant=quant), quant=quant)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


# -- training: the loss, its gradient and AdamW, step by step ---------------

@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(5,))
def _loss_grad_sum(m_items, quant, params, tokens, labels, acc):
    """Summed token loss of one block of rows, and ``acc`` plus its
    gradient (``acc`` is donated: the sum is built in place)."""
    m = dict(m_items)

    def total(p):
        return loss(m, p, tokens, labels, quant=quant) * tokens.size
    lsum, g = jax.value_and_grad(total)(params)
    return lsum, jax.tree.map(jnp.add, acc, g)


def loss_and_grad(m: dict, params, batch, *, block_rows: int, quant=None):
    """Mean loss over a (rows, S + 1) token batch and its gradient, the
    rows taken ``block_rows`` at a time so the activations fit."""
    import numpy as np
    rows = batch.shape[0]
    total = 0.0
    grads = jax.tree.map(jnp.zeros_like, params)
    for r in range(0, rows, block_rows):
        blk = jnp.asarray(np.asarray(batch[r:r + block_rows]))
        lsum, grads = _loss_grad_sum(_items(m), quant, params, blk[:, :-1],
                                     blk[:, 1:], grads)
        total += float(lsum)
    n = batch[:, 1:].size
    return total / n, _scale(grads, 1.0 / n)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(tree, k):
    return jax.tree.map(lambda x: x * k, tree)


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=(0,))
def _own_chunk(assembled, g, rank: int, ranks: int):
    """``assembled`` with each leaf's chunk ``rank`` (of ``ranks``, over the
    flattened, padded leaf) taken from ``g``."""
    def leaf(a, b):
        size = a.size
        chunk = -(-size // ranks)
        idx = jnp.arange(size)
        mine = (idx >= rank * chunk) & (idx < (rank + 1) * chunk)
        return jnp.where(mine, b.reshape(-1), a.reshape(-1)).reshape(a.shape)
    return jax.tree.map(leaf, assembled, g)


def learning_rate(opt: dict, step: int) -> float:
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to ``min_lr_frac`` of it at ``total_steps``."""
    import math
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * t))
    return opt["lr"] * warm * frac


def _stored(x, dtype):
    """``x`` rounded to ``dtype``, kept in float32.  By ``reduce_precision``
    and not a float32 -> ``dtype`` -> float32 convert pair: on TPU, XLA's
    excess-precision simplification drops such a pair, which left the
    parameters in float32 across steps."""
    f = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=f.nexp,
                                    mantissa_bits=f.nmant)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3, 4))
def _adamw(opt_items, params, m, v, g, step, lr):
    """One AdamW update (no clipping).  Weight decay applies to leaves of
    two or more dimensions as stored (layers stacked on a leading axis);
    parameters are rounded to ``store_dtype`` after every step."""
    opt = dict(opt_items)
    b1, b2 = opt["b1"], opt["b2"]
    bc1 = 1 - b1 ** step
    bc2 = 1 - b2 ** step
    store = jnp.dtype(opt["store_dtype"])

    def upd(p, m, v, g):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + opt["eps"])
        if opt["weight_decay"] and p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        return _stored(p - lr * delta, store), m, v

    out = jax.tree.map(upd, params, m, v, g)
    pick = (lambda i: jax.tree.map(lambda _, o: o[i], params, out))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree) -> dict:
    """{leaf path: float32 2-norm}."""
    import numpy as np
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    norms = jax.jit(lambda t: [jnp.linalg.norm(x.astype(jnp.float32))
                               for x in jax.tree.leaves(t)])(tree)
    return {jax.tree_util.keystr(p): float(np.asarray(n))
            for (p, _), n in zip(leaves, norms)}


def train_steps(m: dict, params, batches, opt: dict, *, block_rows: int,
                quant=None, fault=None, ranks: int = 1):
    """The first ``len(batches)`` AdamW steps from ``params`` (float32,
    consumed: their buffers are reused).  Returns each step's loss, the
    first gradient's and the parameters' change's norms per leaf.

    ``quant="fp8"`` computes the control.  ``fault`` plants one in the
    reference: "half_batch" (the mean over the first half of the rows
    only), "no_exchange" (each of ``ranks`` data-parallel ranks keeps its
    own rows' gradient for the chunk of each leaf it updates)."""
    items = _items(opt)
    store = jnp.dtype(opt["store_dtype"])
    p0 = jax.tree.map(lambda x: x.astype(store), params)   # exact: stored so
    mom = jax.tree.map(jnp.zeros_like, params)
    vel = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        if fault == "half_batch":
            batch = batch[:batch.shape[0] // 2]
        lval, g = loss_and_grad(m, params, batch, block_rows=block_rows,
                                quant=quant)
        if fault == "no_exchange":
            per = batch.shape[0] // ranks
            for k in range(ranks):
                own = loss_and_grad(m, params, batch[k * per:(k + 1) * per],
                                    block_rows=block_rows, quant=quant)[1]
                g = _own_chunk(g, own, k, ranks)
                del own
        losses.append(lval)
        if first is None:
            first = leaf_norms(g)
        params, mom, vel = _adamw(items, params, mom, vel, g,
                                  jnp.float32(i + 1),
                                  jnp.float32(learning_rate(opt, i + 1)))
    change = leaf_norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), params, p0))
    return {"losses": losses, "grad_norms": first, "change_norms": change}
