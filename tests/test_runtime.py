"""Runtime tests: trainer loop, checkpoint integrity, data resumability,
serving engine (paged cache vs dense-decode oracle), fault recovery,
overlap engine (bucketed apex step, chunked prefill).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import (CheckpointStore, latest_step, load_checkpoint,
                              save_checkpoint)
from repro.data import Prefetcher, SyntheticTokens
from repro.models import api
from repro.models.common import ArchCfg
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.serving.engine import Engine, PagedLM, Request

CFG = ArchCfg(name="tiny", family="dense", n_layers=2, d_model=32,
              n_heads=4, n_kv_heads=2, d_ff=64, vocab=257,
              dtype=jnp.float32)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------------------
# optimizer
# ----------------------------------------------------------------------------

def test_adamw_reduces_loss_on_quadratic():
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                      total_steps=100)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = adamw_init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    l0 = float(loss(params))
    for _ in range(50):
        grads = jax.grad(loss)(params)
        params, state, _ = adamw_update(cfg, grads, state, params)
    assert float(loss(params)) < 1e-2 * l0


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_frac=0.1)
    assert float(cosine_schedule(cfg, 0)) == 0.0
    assert float(cosine_schedule(cfg, 10)) == pytest.approx(1.0)
    assert float(cosine_schedule(cfg, 100)) == pytest.approx(0.1)
    assert float(cosine_schedule(cfg, 55)) < 1.0


def test_grad_clipping_bounds_update():
    cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = adamw_init(params)
    huge = {"w": jnp.full(4, 1e9)}
    _, state, metrics = adamw_update(cfg, huge, state, params)
    assert float(metrics["grad_norm"]) > 1e8
    assert float(jnp.abs(state["m"]["w"]).max()) <= 0.11  # clipped


# ----------------------------------------------------------------------------
# data
# ----------------------------------------------------------------------------

def test_data_deterministic_and_resumable():
    a = SyntheticTokens(CFG, 4, 32, seed=7)
    b1, b2 = a.next_batch(), a.next_batch()
    resumed = SyntheticTokens.from_state(CFG, 4, 32,
                                         {"seed": 7, "step": 1})
    np.testing.assert_array_equal(resumed.next_batch()["tokens"],
                                  b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    assert (b1["labels"][:, -1] == -1).all()


def test_prefetcher_yields_and_closes():
    src = SyntheticTokens(CFG, 2, 16, seed=0)
    pf = Prefetcher(iter(src), depth=2)
    batches = [next(pf) for _ in range(3)]
    assert all(b["tokens"].shape == (2, 16) for b in batches)
    pf.close()


# ----------------------------------------------------------------------------
# checkpoint
# ----------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.asarray([1, 2, 3], np.int32)}}
    save_checkpoint(str(tmp_path), 5, tree, extra={"x": 1})
    got, extra = load_checkpoint(str(tmp_path), template=tree)
    np.testing.assert_array_equal(np.asarray(got["a"]), tree["a"])
    np.testing.assert_array_equal(np.asarray(got["b"]["c"]), tree["b"]["c"])
    assert extra == {"x": 1}
    assert latest_step(str(tmp_path)) == 5


def test_checkpoint_corruption_detected(tmp_path):
    tree = {"a": np.arange(100, dtype=np.float32)}
    path = save_checkpoint(str(tmp_path), 1, tree)
    # corrupt a tensor in place
    z = dict(np.load(os.path.join(path, "tensors.npz")))
    z["a"][3] += 1.0
    np.savez(os.path.join(path, "tensors.npz"), **z)
    with pytest.raises(ValueError, match="CRC"):
        load_checkpoint(str(tmp_path), template=tree)


def test_checkpoint_gc_and_async(tmp_path):
    store = CheckpointStore(str(tmp_path), keep_last=2)
    for s in (1, 2, 3, 4):
        store.save_async(s, {"a": np.full(4, s, np.float32)})
    store.wait()
    assert latest_step(str(tmp_path)) == 4
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [3, 4]


# ----------------------------------------------------------------------------
# trainer (single device)
# ----------------------------------------------------------------------------

@pytest.mark.slow
def test_trainer_loss_decreases(tmp_path):
    tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=0, batch=8,
                         seq_len=64,
                         opt=AdamWConfig(lr=3e-3, warmup_steps=5,
                                         total_steps=60))
    tr = Trainer(CFG, tcfg)
    metrics = tr.train(40)
    first = np.mean([m["loss"] for m in metrics[:5]])
    last = np.mean([m["loss"] for m in metrics[-5:]])
    assert last < first - 0.5, (first, last)  # structured stream is learnable


@pytest.mark.slow
def test_trainer_checkpoint_restart_bitwise(tmp_path):
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100)
    t1 = TrainerConfig(ckpt_dir=str(tmp_path / "a"), ckpt_every=5, batch=4,
                       seq_len=32, opt=opt)
    tr1 = Trainer(CFG, t1)
    tr1.train(10)   # checkpoints at steps 5 and 10
    ref = tr1.train(3)

    # restart from the step-10 checkpoint and replay
    t2 = TrainerConfig(ckpt_dir=str(tmp_path / "a"), ckpt_every=0, batch=4,
                       seq_len=32, opt=opt)
    tr2 = Trainer(CFG, t2)
    tree, extra = tr2.store.restore_latest(
        {"params": jax.tree.map(np.asarray, tr2.params),
         "opt": jax.tree.map(np.asarray, tr2.opt_state)})
    tr2.params = jax.tree.map(jnp.asarray, tree["params"])
    tr2.opt_state = jax.tree.map(jnp.asarray, tree["opt"])
    tr2.data = SyntheticTokens.from_state(CFG, 4, 32, extra["data"])
    got = tr2.train(3)
    for a, b in zip(ref, got):
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)


# ----------------------------------------------------------------------------
# serving engine: paged decode vs dense decode oracle
# ----------------------------------------------------------------------------

@pytest.mark.slow
def test_paged_engine_matches_dense_decode():
    cfg = CFG
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (7, 13, 5)]
    lm = PagedLM(cfg, params, max_batch=4, max_seq=64, page_tokens=8)
    eng = Engine(lm)
    for i, pr in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=pr, max_new_tokens=6))
    eng.run_to_completion()
    assert len(eng.finished) == 3
    st = eng.stats()
    assert 0.0 <= st["tlb_hit_rate"] <= 1.0

    # oracle: dense-cache greedy decode, one request at a time
    for req in eng.finished:
        toks = jnp.asarray(req.prompt[None])
        logits, cache = model.prefill(params, {"tokens": toks},
                                      max_len=64, remat=False)
        cur = int(jnp.argmax(logits[0, -1]))
        want = [cur]
        pos = len(req.prompt)
        for _ in range(5):
            lg, cache = model.decode_step(
                params, jnp.asarray([[cur]], jnp.int32), cache, pos)
            cur = int(jnp.argmax(lg[0, -1]))
            want.append(cur)
            pos += 1
        assert req.out_tokens == want, f"request {req.rid}"


def test_claim_slot_releases_partial_pages_on_exhaustion():
    """Regression: a mid-claim pool exhaustion must hand already-allocated
    pages back (a leak permanently shrinks the pool and admission can
    never retry)."""
    cfg = CFG
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0))
    lm = PagedLM(cfg, params, max_batch=2, max_seq=32, page_tokens=8,
                 pool_pages=3)
    free_before = len(lm.allocator.free)
    with pytest.raises(RuntimeError):
        lm.claim_slot(prompt_len=22, max_new=10)   # needs 4 of 3 pages
    assert len(lm.allocator.free) == free_before
    assert not lm.slot_pages
    # an outright oversize request (> pages_per_seq) is a ValueError, not
    # the retryable exhaustion RuntimeError — admission must not re-queue it
    with pytest.raises(ValueError):
        lm.claim_slot(prompt_len=30, max_new=10)   # needs 5 > 4 pages/seq
    assert len(lm.allocator.free) == free_before
    # and the slot is still claimable once the request fits
    slot = lm.claim_slot(prompt_len=10, max_new=6)
    assert len(lm.slot_pages[slot]) == 2


@pytest.mark.slow
def test_chunked_prefill_tokens_identical_to_whole_prompt():
    """Overlap engine, serving side: page-sized chunked prefill interleaved
    with decode must produce exactly the tokens of whole-prompt prefill."""
    cfg = CFG
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    # lengths straddle page boundaries (page_tokens=8): 5 < 8, 21 spans 3
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (7, 21, 5, 13)]

    def run(chunked):
        lm = PagedLM(cfg, params, max_batch=4, max_seq=64, page_tokens=8)
        eng = Engine(lm, chunked_prefill=chunked, prefill_chunk_pages=1)
        for i, pr in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=pr, max_new_tokens=6))
        eng.run_to_completion()
        assert len(eng.finished) == len(prompts)
        return {r.rid: r.out_tokens for r in eng.finished}, eng.stats()

    whole, _ = run(False)
    chunk, st = run(True)
    assert whole == chunk
    assert st["chunked_prefill"] and st["prefill_chunks"] >= sum(
        -(-len(p) // 8) for p in prompts)


@pytest.mark.slow
def test_overlap_trainer_multidevice_equivalence():
    """Bucketed-overlapped apex step bitwise-matches the sequential step
    (8-device DP ring), stats report overlap efficiency, and the engine
    survives a link-fault reroute — see tests/overlap_checks.py."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "overlap_checks.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, \
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL OVERLAP CHECKS PASSED" in proc.stdout


@pytest.mark.slow
def test_engine_continuous_batching_reuses_pages():
    cfg = CFG
    model = api.get_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(1)
    # pool sized so all 6 requests cannot be resident at once
    lm = PagedLM(cfg, params, max_batch=2, max_seq=32, page_tokens=8,
                 pool_pages=8)
    eng = Engine(lm)
    for i in range(6):
        eng.submit(Request(rid=i,
                           prompt=rng.integers(0, cfg.vocab, size=6)
                           .astype(np.int32),
                           max_new_tokens=4))
    eng.run_to_completion()
    assert len(eng.finished) == 6
    assert len(lm.allocator.free) == 8  # all pages returned


# ----------------------------------------------------------------------------
# LO|FA|MO-driven recovery (single-device torus of 1 — logic-level test;
# the multi-device elastic re-mesh runs in tests/multidevice_checks.py)
# ----------------------------------------------------------------------------

@pytest.mark.slow
def test_trainer_fault_recovery_restores_and_replays(tmp_path):
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100)
    tcfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=4, batch=4,
                         seq_len=32, opt=opt, torus_dims=(4,))
    tr = Trainer(CFG, tcfg)
    tr.train(8)  # checkpoints at 4 and 8

    def fault_at_2(i):
        if i == 2:
            tr.lofamo.kill_host(1)  # neighbours 0 and 2 will report it

    tr.train(6, fault_hook=fault_at_2)
    evs = " | ".join(tr.events)
    assert "LO|FA|MO" in evs and "restored step" in evs
    # training continued after recovery
    assert np.isfinite(tr.metrics_log[-1]["loss"])


def test_grad_accum_matches_single_step():
    """grad_accum=2 on the same global batch must track accum=1 closely
    (same summed gradients up to fp32 association)."""
    import tempfile

    import numpy as np

    from repro import configs
    from repro.optim import AdamWConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = configs.get_reduced("smollm-135m")
    losses = {}
    for accum in (1, 2, 4):
        with tempfile.TemporaryDirectory() as td:
            tcfg = TrainerConfig(
                ckpt_dir=td, ckpt_every=0, batch=8, seq_len=32,
                opt=AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=50),
                comm="single", grad_accum=accum)
            tr = Trainer(cfg, tcfg)
            losses[accum] = [m["loss"] for m in tr.train(5)]
    np.testing.assert_allclose(losses[1], losses[2], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(losses[1], losses[4], rtol=2e-4, atol=2e-4)
    assert losses[1][-1] < losses[1][0]
