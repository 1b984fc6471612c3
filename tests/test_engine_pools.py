"""The serving engine's KV pools: one stacked, lane-dense pool for K and
one for V, (L, P, page, Hkv*D), donated to every program and written in
place through the layer loop.

Chunked prefill followed by decode through the Pallas kernel (interpreted
here) gives the tokens of whole-prompt prefill, on every family the engine
serves; each program writes exactly the positions it owns and drops the
writes of inactive slots and of chunk pages past a slot's allocation; the
arrays handed to a program are gone after it, where the backend honours
donation.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.kernels import ops
from repro.models import api
from repro.models.common import ArchCfg
from repro.serving.engine import Engine, PagedLM, Request

CFG = ArchCfg(name="tiny", family="dense", n_layers=2, d_model=32,
              n_heads=4, n_kv_heads=2, d_ff=64, vocab=257,
              dtype=jnp.float32)
PAGE = 8


@pytest.fixture(scope="module")
def params():
    return api.get_model(CFG).init(jax.random.key(0))


def _lm(params, cfg=CFG, **kw) -> PagedLM:
    kw = dict(dict(max_batch=3, max_seq=32, page_tokens=PAGE, tp_axes=()),
              **kw)
    return PagedLM(cfg, params, **kw)


def _marked(lm: PagedLM, seed: int = 0) -> PagedLM:
    """Fill both pools with distinct values, so any write shows."""
    rng = np.random.default_rng(seed)
    lm.k_pool, lm.v_pool = (jnp.asarray(rng.normal(size=lm.pool_shape),
                                        lm.cfg.dtype) for _ in range(2))
    return lm


def _written(before: np.ndarray, after: np.ndarray) -> set:
    """(layer, page, offset) of every token row that changed."""
    return set(map(tuple, np.argwhere((before != after).any(-1)).tolist()))


def test_pools_are_stacked_and_lane_dense(params):
    lm = _lm(params)
    hd = CFG.resolved_head_dim
    assert lm.pool_shape == (CFG.n_layers, lm.n_pages, PAGE,
                             CFG.n_kv_heads * hd)
    assert lm.k_pool.shape == lm.v_pool.shape == lm.pool_shape
    # one token's K and V over every layer, as the wire prices it
    assert lm.bytes_per_token == 2 * CFG.n_layers * CFG.n_kv_heads * hd * 2


@pytest.mark.parametrize("arch", ["smollm-135m", "olmoe-1b-7b",
                                  "internvl2-76b"])
def test_chunked_prefill_then_decode_through_the_kernel_matches_whole_prompt(
        arch, monkeypatch):
    """Every family the engine serves takes the one path: the chunk program
    and the kernel reading the stacked pool give whole-prompt tokens."""
    monkeypatch.setattr(ops, "paged_attention",
                        functools.partial(ops.paged_attention, impl="pallas"))
    cfg = configs.get_reduced(arch)
    params = api.get_model(cfg).init(jax.random.key(1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (7, 21, 13)]

    def run(chunked):
        lm = _lm(params, cfg)
        eng = Engine(lm, chunked_prefill=chunked, prefill_chunk_pages=2)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=4))
        eng.run_to_completion()
        assert len(eng.finished) == len(prompts)
        return {r.rid: r.out_tokens for r in eng.finished}

    assert run(True) == run(False)


def test_decode_writes_each_active_slots_position_and_nothing_else(params):
    lm = _lm(params)
    a = lm.claim_slot(prompt_len=7, max_new=4)     # position 7: page 0
    b = lm.claim_slot(prompt_len=9, max_new=4)     # position 9: page 1
    lm.seq_lens[[a, b]] = 7, 9
    # the third slot is free: its page-table row still points at page 0
    _marked(lm)
    before = np.asarray(lm.k_pool), np.asarray(lm.v_pool)
    active = np.array([True, True, False])
    lm.decode_batch(np.array([5, 6, 0]), active)
    want = {(layer, int(lm.page_table[s, pos // PAGE]), pos % PAGE)
            for layer in range(CFG.n_layers)
            for s, pos in ((a, 7), (b, 9))}
    assert _written(before[0], np.asarray(lm.k_pool)) == want
    assert _written(before[1], np.asarray(lm.v_pool)) == want
    assert lm.seq_lens.tolist() == [8, 10, 0]


def test_a_chunk_writes_its_allocated_pages_and_drops_the_rest(params):
    """A padded 2-page chunk of a slot that owns one page writes that page
    in every layer; the second page has no allocation and is dropped."""
    lm = _lm(params)
    lm.claim_slot(prompt_len=3, max_new=2)         # holds page 0
    slot = lm.claim_slot(prompt_len=5, max_new=2)  # one page
    (page,) = lm.slot_pages[slot]
    _marked(lm)
    before = np.asarray(lm.k_pool)
    prompt = np.arange(5, dtype=np.int32)
    tok = lm.prefill_slot_chunk(slot, prompt, 0, 2 * PAGE)
    assert tok is not None and lm.seq_lens[slot] == 5
    assert _written(before, np.asarray(lm.k_pool)) == {
        (layer, page, off) for layer in range(CFG.n_layers)
        for off in range(PAGE)}


def _donation_is_honoured() -> bool:
    x = jnp.zeros((4,))
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    return x.is_deleted()


def test_each_program_consumes_the_pools_it_is_handed(params):
    if not _donation_is_honoured():
        pytest.skip(f"{jax.default_backend()} does not honour donation")
    lm = _lm(params)
    prompt = np.arange(11, dtype=np.int32)
    whole = lm.claim_slot(len(prompt), 3)
    chunked = lm.claim_slot(len(prompt), 3)
    calls = [
        lambda: lm.prefill_slot(whole, prompt),
        lambda: lm.prefill_slot_chunk(chunked, prompt, 0, 2 * PAGE),
        lambda: lm.decode_batch(np.array([1, 2, 0]),
                                np.array([True, True, False])),
    ]
    for call in calls:
        k, v = lm.k_pool, lm.v_pool
        call()
        assert k.is_deleted() and v.is_deleted()
        assert not (lm.k_pool.is_deleted() or lm.v_pool.is_deleted())
