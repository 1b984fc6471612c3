"""The float32 reference agrees with the program's transformer at a small
size on the CPU: forward logits, the loss and its gradient, for both
families of layer the reference covers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from tiny import harness

import program
import weights

REF = harness.plugin("reference", "dense")

VARIANTS = {
    "rms-swiglu-bias-tied": {},
    "ln-gelu-untied": {"norm": "layernorm", "mlp": "gelu_tanh",
                       "qkv_bias": False, "tie_word_embeddings": False,
                       "rope_theta": 1e6, "norm_eps": 1e-5},
}


def _setup(variant):
    m = dict(tiny.TINY_MODEL, **VARIANTS[variant])
    cfg = {"arch": "qwen2-0.5b", "model": m}
    arch = dataclasses.replace(program.arch_config(cfg), dtype=jnp.float32)
    params = weights.make(m, 3, dtype="float32")
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], (2, 24))
    return m, arch, params, jnp.asarray(toks, jnp.int32)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_reference_logits_match_the_program(variant):
    from repro.models import common, transformer
    m, arch, params, toks = _setup(variant)
    _, _, h = transformer.prefill(arch, params, {"tokens": toks},
                                  remat=False, return_hidden=True)
    got = common.lm_head(arch, params["embed"], h)
    want = REF.head(m, params, REF.hidden(m, params, toks))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_reference_loss_and_grads_match_the_program(variant):
    from repro.models import transformer
    m, arch, params, toks = _setup(variant)
    x, y = toks[:, :-1], toks[:, 1:]
    got_l, got_g = jax.value_and_grad(
        lambda p: transformer.train_loss(arch, p, {"tokens": x, "labels": y},
                                         remat=True))(params)
    want_l, want_g = jax.value_and_grad(
        lambda p: REF.loss(m, p, x, y))(params)
    assert float(got_l) == pytest.approx(float(want_l), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree.leaves(want_g)):
        scale = float(jnp.abs(b).max()) + 1e-6
        err = float(jnp.abs(a - b).max()) / scale
        assert err < 2e-3, (jax.tree_util.keystr(path), err)


def test_served_gaps_are_zero_for_the_reference_own_tokens():
    """Greedy tokens of the reference itself read a gap of 0, and a token
    changed where it is produced reads a gap above 0."""
    m, _, params, toks = _setup("rms-swiglu-bias-tied")
    prompt = np.asarray(toks[0, :10])
    seq = list(prompt)
    for _ in range(5):
        logits = REF.head(m, params, REF.hidden(
            m, params, jnp.asarray(seq, jnp.int32)[None]))[0, -1]
        seq.append(int(jnp.argmax(logits)))
    out = np.asarray(seq[10:], np.int32)
    gap = REF.served_gaps(m, params, prompt, out, seq_pad=32, rows_pad=8)
    assert gap.shape == (5,) and float(gap.max()) <= 1e-5
    bad = out.copy()
    bad[2] = (bad[2] + 1) % m["vocab_size"]
    assert float(REF.served_gaps(m, params, prompt, bad, seq_pad=32,
                                 rows_pad=8)[2]) > 1e-3


@pytest.mark.parametrize("store", ["bfloat16", "float32"])
def test_adamw_rounds_stored_parameters_by_an_op_the_compiler_keeps(store):
    """Each update leaves the parameters on the ``store_dtype`` grid, by
    ``reduce_precision``: a float32 -> bfloat16 -> float32 convert pair is
    dropped by XLA's excess-precision simplification on TPU."""
    opt = {"b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.0,
           "store_dtype": store}
    rng = np.random.default_rng(1)
    # norm scales near 1: a step of lr = 3e-4 is under half a bfloat16 ulp
    p = jnp.asarray(1 + rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    p = p.astype(store).astype(jnp.float32)
    before = np.asarray(p)          # the update consumes its inputs
    g = jnp.asarray(rng.standard_normal((64, 32)) * 1e-3, jnp.float32)
    args = (REF._items(opt), {"w": p}, {"w": jnp.zeros_like(p)},
            {"w": jnp.zeros_like(p)}, {"w": g}, jnp.float32(1),
            jnp.float32(3e-4))
    assert "reduce_precision" in REF._adamw.lower(*args).as_text()
    new = np.asarray(REF._adamw(*args)[0]["w"])
    on_grid = np.asarray(jnp.asarray(new).astype(store).astype(jnp.float32))
    np.testing.assert_array_equal(new, on_grid)
    moved = np.mean(new != before)
    assert moved == (0.0 if store == "bfloat16" else 1.0)
