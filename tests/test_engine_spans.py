"""The serving engine's profiler spans and the decode program's named scopes.

A tiny ``PagedLM`` + ``Engine(chunked_prefill=True)`` runs under the JAX
profiler; its ``engine/`` spans are read back from the ``.xplane.pb`` with
their arguments.  The compiled decode and prefill-chunk programs carry the
named scopes (and the Pallas kernel's name) in their HLO metadata.
"""
import functools
import re
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import api
from repro.models.common import ArchCfg
from repro.serving import engine as engine_mod
from repro.serving.engine import Engine, PagedLM, Request

CFG = ArchCfg(name="tiny", family="dense", n_layers=2, d_model=32,
              n_heads=4, n_kv_heads=2, d_ff=64, vocab=257,
              dtype=jnp.float32)
PROMPTS = (20, 9, 13)          # 3, 2 and 2 chunks of one 8-token page
ARGS = {"step": {"step", "pending", "prefilling", "running"},
        "claim": {"rid", "slot", "waited_ms"},
        "prefill_chunk": {"rid", "slot", "start", "last"},
        "decode": {"step", "tokens"}, "sample": {"step", "tokens"},
        "retire": {"finished"}}
SCOPES = ("embed", "qkv", "kv_write", "attention", "attn_out", "mlp", "head")


@pytest.fixture(scope="module")
def params():
    return api.get_model(CFG).init(jax.random.key(0))


def _engine(params, **kw):
    lm = PagedLM(CFG, params, max_batch=4, max_seq=64, page_tokens=8,
                 tp_axes=(), **kw)
    eng = Engine(lm, chunked_prefill=True)
    rng = np.random.default_rng(0)
    for rid, n in enumerate(PROMPTS):
        eng.submit(Request(rid=rid, max_new_tokens=3,
                           prompt=rng.integers(0, CFG.vocab, n)
                           .astype(np.int32)))
    return eng


def _engine_spans(directory: Path) -> list[tuple[str, float, float, dict]]:
    """(name without prefix, start, end, args) of every engine span."""
    from jax.profiler import ProfileData
    (path,) = directory.rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(engine_mod.SPAN_PREFIX):
                    out.append((e.name.removeprefix(engine_mod.SPAN_PREFIX),
                                e.start_ns, e.start_ns + e.duration_ns,
                                {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: s[1])


def _recorded(params, tmp_path, **kw):
    eng = _engine(params, **kw)
    with jax.profiler.trace(str(tmp_path)):
        eng.run_to_completion()
    assert len(eng.finished) == len(PROMPTS)
    return eng, _engine_spans(tmp_path)


def test_engine_spans_carry_their_arguments_and_nest(params, tmp_path):
    eng, spans = _recorded(params, tmp_path)
    assert {s[0] for s in spans} == set(engine_mod.SPANS) == set(ARGS)
    for name, _, _, args in spans:
        assert set(args) == ARGS[name], name
    steps = [s for s in spans if s[0] == "step"]
    assert len(steps) == eng.steps + 1      # the first step only prefills
    for name, a, b, _ in spans:
        if name != "step":
            assert any(s[1] <= a and b <= s[2] for s in steps), name

    claims = {s[3]["rid"]: s[3] for s in spans if s[0] == "claim"}
    chunks = [s[3] for s in spans if s[0] == "prefill_chunk"]
    assert sorted(claims) == list(range(len(PROMPTS)))
    for rid, n in enumerate(PROMPTS):
        mine = [c for c in chunks if c["rid"] == rid]
        assert [c["start"] for c in mine] == list(range(0, n, 8))
        assert [c["last"] for c in mine] == [0] * (len(mine) - 1) + [1]
        assert {c["slot"] for c in mine} == {claims[rid]["slot"]}
        assert claims[rid]["waited_ms"] >= 0

    decodes = [s[3] for s in spans if s[0] == "decode"]
    samples = [s[3] for s in spans if s[0] == "sample"]
    assert decodes == samples
    assert [d["step"] for d in decodes] == list(range(eng.steps))
    assert sum(d["tokens"] for d in decodes) == sum(
        len(r.out_tokens) - 1 for r in eng.finished)
    assert sum(s[3]["finished"] for s in spans if s[0] == "retire") == 3


def test_a_modelled_engine_places_only_step_spans(params, tmp_path):
    _, spans = _recorded(params, tmp_path, modelled=True)
    assert spans and {s[0] for s in spans} == {"step"}


def test_stats_keep_no_step_time_list(params):
    eng = _engine(params)
    eng.run_to_completion()
    assert "measured_step_s" not in eng.stats()
    assert not hasattr(eng, "_step_times")


def _op_names(fn, *args) -> set[str]:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_programs_carry_named_scopes_and_the_kernel_name(params,
                                                         monkeypatch):
    # the decode program as a TPU traces it: through the Pallas kernel
    # (interpreted here)
    monkeypatch.setattr(ops, "paged_attention",
                        functools.partial(ops.paged_attention, impl="pallas"))
    lm = PagedLM(CFG, params, max_batch=4, max_seq=64, page_tokens=8,
                 tp_axes=())
    B = lm.max_batch
    table = jnp.asarray(lm.page_table)
    decode = _op_names(lm._decode_impl, params, jnp.zeros((B, 1), jnp.int32),
                       lm.k_pool, lm.v_pool, table, jnp.zeros((B,), jnp.int32),
                       jnp.ones((B,), bool))
    chunk = _op_names(lm._prefill_chunk_impl, params,
                      jnp.zeros((1, 8), jnp.int32), lm.k_pool, lm.v_pool,
                      table, 0, 0, 1)
    for names, program in ((decode, "_decode_impl"),
                           (chunk, "_prefill_chunk_impl")):
        for scope in SCOPES:
            assert any(f"/{scope}/" in n and f"jit({program})" in n
                       for n in names), (program, scope)
    assert any("/attention/paged_attention/" in n for n in decode)
