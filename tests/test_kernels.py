"""Per-kernel validation: Pallas (interpret=True) vs. pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests on invariants.
"""
import pytest

hp = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mamba2_scan import mamba2_scan
from repro.kernels.paged_attention import paged_attention
from repro.kernels.rwkv6_scan import rwkv6_scan

RNG = np.random.default_rng(42)


def tol(dtype):
    return dict(rtol=6e-2, atol=6e-2) if dtype == jnp.bfloat16 else \
           dict(rtol=3e-4, atol=3e-4)


def assert_close(got, want, dtype):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


# ----------------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,Sq,Skv,D,bq,bk", [
    (1, 2, 2, 128, 128, 32, 64, 64),       # MHA square
    (2, 4, 2, 128, 128, 64, 128, 64),      # GQA group=2
    (1, 8, 1, 64, 64, 16, 32, 32),         # MQA
    (1, 2, 2, 64, 256, 32, 64, 64),        # cross Sq != Skv (right-aligned)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(B, H, Hkv, Sq, Skv, D, bq, bk, causal,
                                     dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, Sq, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, Skv, D)), dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = ref.mha_attention(q, k, v, causal=causal)
    assert got.dtype == dtype
    assert_close(got, want, dtype)


def test_flash_attention_is_jittable():
    q = jnp.asarray(RNG.normal(size=(1, 2, 64, 32)), jnp.float32)
    f = jax.jit(lambda q: flash_attention(q, q, q, interpret=True,
                                          block_q=32, block_k=32))
    out = f(q)
    assert out.shape == q.shape and not bool(jnp.any(jnp.isnan(out)))


@hp.given(st.integers(1, 3), st.integers(0, 2), st.integers(1, 4))
@hp.settings(max_examples=10, deadline=None)
def test_flash_attention_property(batch, group_log2, blocks):
    """softmax(QK^T)V rows are convex combinations of V rows: outputs stay
    within [min(V), max(V)] per feature."""
    group = 2 ** group_log2
    Hkv, D = 2, 16
    S = 32 * blocks
    q = jnp.asarray(RNG.normal(size=(batch, Hkv * group, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(batch, Hkv, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(batch, Hkv, S, D)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    hi = np.asarray(v).max() + 1e-4
    lo = np.asarray(v).min() - 1e-4
    assert np.all(np.asarray(out) <= hi) and np.all(np.asarray(out) >= lo)


# ----------------------------------------------------------------------------
# paged attention (the TLB kernel)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,D,page,max_pages,pool", [
    (2, 4, 2, 32, 16, 4, 12),
    (3, 4, 4, 64, 8, 8, 30),
    (1, 8, 1, 16, 32, 2, 4),
])
def test_paged_attention_matches_ref(B, H, Hkv, D, page, max_pages, pool,
                                     dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(RNG.normal(size=(pool, page, Hkv, D)), dtype)
    vp = jnp.asarray(RNG.normal(size=(pool, page, Hkv, D)), dtype)
    pt = jnp.asarray(RNG.permutation(pool)[:B * max_pages].reshape(
        B, max_pages).astype(np.int32))
    sl = jnp.asarray(RNG.integers(1, page * max_pages + 1, size=B)
                     .astype(np.int32))
    got = paged_attention(q, kp, vp, pt, sl, interpret=True)
    want = ref.paged_attention(q, kp, vp, pt, sl)
    assert got.dtype == dtype
    assert_close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,B,H,Hkv,D,page,max_pages,pool", [
    (3, 4, 4, 2, 32, 16, 4, 20),
    (2, 3, 8, 1, 64, 8, 3, 12),
])
def test_paged_attention_on_the_stacked_pool_matches_ref_per_layer(
        L, B, H, Hkv, D, page, max_pages, pool, dtype):
    """Given the stacked lane-dense pool (L, P, page, Hkv*D) and a layer,
    the kernel reads that layer's pages: equal to the reference on them,
    for every layer, with ragged lengths and a slot that holds no token."""
    q = jnp.asarray(RNG.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(RNG.normal(size=(L, pool, page, Hkv * D)), dtype)
    vp = jnp.asarray(RNG.normal(size=(L, pool, page, Hkv * D)), dtype)
    pt = jnp.asarray(RNG.permutation(pool)[:B * max_pages].reshape(
        B, max_pages).astype(np.int32))
    sl = RNG.integers(1, page * max_pages + 1, size=B).astype(np.int32)
    sl[0], sl[-1] = page + 1, 0           # one page and a token; inactive
    live = sl > 0
    sl = jnp.asarray(sl)
    for layer in range(L):
        pages = (lambda p: p[layer].reshape(pool, page, Hkv, D))
        want = ref.paged_attention(q, pages(kp), pages(vp), pt, sl)
        got = paged_attention(q, kp, vp, pt, sl, layer=jnp.int32(layer),
                              interpret=True)
        assert got.dtype == dtype
        assert_close(got[live], want[live], dtype)
        assert not np.asarray(got[~live], np.float32).any()
        no_kernel = ops.paged_attention(q, kp, vp, pt, sl, layer=layer,
                                        impl="ref")
        np.testing.assert_array_equal(np.asarray(no_kernel[live]),
                                      np.asarray(want[live]))


def test_paged_attention_ignores_unmapped_pages():
    """Pages past seq_len must not influence the result even if the page
    table points at garbage there (RDMA safety: no reads beyond the
    registered region)."""
    B, H, D, page, mp, pool = 1, 2, 16, 8, 4, 8
    q = jnp.asarray(RNG.normal(size=(B, H, D)), jnp.float32)
    kp = jnp.asarray(RNG.normal(size=(pool, page, H, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(pool, page, H, D)), jnp.float32)
    sl = jnp.asarray([9], np.int32)  # 2 pages resident
    pt_a = jnp.asarray([[0, 1, 2, 3]], np.int32)
    pt_b = jnp.asarray([[0, 1, 7, 6]], np.int32)  # same resident pages
    out_a = paged_attention(q, kp, vp, pt_a, sl, interpret=True)
    out_b = paged_attention(q, kp, vp, pt_b, sl, interpret=True)
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b))


def test_paged_vs_contiguous_attention():
    """Paged decode == dense decode when pages are laid out contiguously."""
    B, H, D, page, mp = 2, 2, 32, 16, 4
    S = page * mp
    kp = jnp.asarray(RNG.normal(size=(B * mp, page, H, D)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(B * mp, page, H, D)), jnp.float32)
    pt = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    sl = jnp.asarray([S, S - 5], np.int32)
    q = jnp.asarray(RNG.normal(size=(B, H, D)), jnp.float32)
    got = paged_attention(q, kp, vp, pt, sl, interpret=True)
    # dense oracle: q attends over the flattened cache with length mask
    k_dense = kp.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    v_dense = vp.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    logits = jnp.einsum("bhd,bhsd->bhs", q * D ** -0.5, k_dense)
    mask = jnp.arange(S)[None, :] < sl[:, None]
    logits = jnp.where(mask[:, None], logits, -jnp.inf)
    want = jnp.einsum("bhs,bhsd->bhd", jax.nn.softmax(logits, -1), v_dense)
    assert_close(got, want, jnp.float32)


# ----------------------------------------------------------------------------
# mamba2 SSD scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,dh,ds,chunk", [
    (2, 128, 3, 32, 16, 32),
    (1, 64, 2, 16, 8, 64),    # single chunk
    (1, 256, 1, 8, 4, 32),    # long, tiny
])
def test_mamba2_matches_ref(B, S, H, dh, ds, chunk, dtype):
    x = jnp.asarray(RNG.normal(size=(B, S, H, dh)), dtype)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.1 + 0.01, dtype)
    A = jnp.asarray(-np.abs(RNG.normal(size=(H,))) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, ds)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, ds)), dtype)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    got = mamba2_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    want = ref.mamba2_scan(x, dt, A, Bm, Cm, D)
    assert got.dtype == dtype
    assert_close(got, want, dtype)


def test_mamba2_chunk_invariance():
    """The chunked closed form must not depend on the chunk size."""
    B, S, H, dh, ds = 1, 128, 2, 16, 8
    args = (jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32),
            jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.1, jnp.float32),
            jnp.asarray(-np.abs(RNG.normal(size=(H,))), jnp.float32),
            jnp.asarray(RNG.normal(size=(B, S, ds)), jnp.float32),
            jnp.asarray(RNG.normal(size=(B, S, ds)), jnp.float32),
            jnp.asarray(RNG.normal(size=(H,)), jnp.float32))
    outs = [mamba2_scan(*args, chunk=c, interpret=True) for c in (16, 32, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


@hp.given(st.floats(0.01, 0.5), st.integers(1, 3))
@hp.settings(max_examples=8, deadline=None)
def test_mamba2_decay_property(dt_scale, heads):
    """With x = 0 after t0, outputs decay toward D-skip only (state decays:
    A < 0)."""
    B, S, dh, ds = 1, 64, 8, 4
    x = np.zeros((B, S, heads, dh), np.float32)
    x[:, 0] = 1.0
    dt = np.full((B, S, heads), dt_scale, np.float32)
    A = np.full((heads,), -5.0, np.float32)
    Bm = np.ones((B, S, ds), np.float32)
    Cm = np.ones((B, S, ds), np.float32)
    D = np.zeros((heads,), np.float32)
    out = mamba2_scan(*map(jnp.asarray, (x, dt, A, Bm, Cm, D)), chunk=32,
                      interpret=True)
    mags = np.abs(np.asarray(out)).max(axis=(0, 2, 3))
    assert mags[-1] < mags[1] + 1e-6  # decayed


# ----------------------------------------------------------------------------
# rwkv6 scan
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,dh,chunk", [
    (2, 64, 2, 16, 16),
    (1, 128, 1, 32, 64),
    (1, 32, 4, 8, 32),    # single chunk
])
def test_rwkv6_matches_ref(B, S, H, dh, chunk, dtype):
    r = jnp.asarray(RNG.normal(size=(B, S, H, dh)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, H, dh)) * 0.3, dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, H, dh)), dtype)
    w = jnp.asarray(1 / (1 + np.exp(-RNG.normal(size=(B, S, H, dh)))) * 0.5
                    + 0.5, dtype)
    u = jnp.asarray(RNG.normal(size=(H, dh)), jnp.float32)
    got = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    want = ref.rwkv6_scan(r, k, v, w, u)
    assert got.dtype == dtype
    assert_close(got, want, dtype)


def test_rwkv6_chunk_invariance():
    B, S, H, dh = 1, 64, 2, 8
    args = (jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32),
            jnp.asarray(RNG.normal(size=(B, S, H, dh)) * 0.3, jnp.float32),
            jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32),
            jnp.asarray(np.full((B, S, H, dh), 0.9), jnp.float32),
            jnp.asarray(RNG.normal(size=(H, dh)), jnp.float32))
    outs = [rwkv6_scan(*args, chunk=c, interpret=True) for c in (8, 16, 64)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-4, atol=2e-4)


def test_rwkv6_zero_decay_is_memoryless():
    """w == 0 wipes the state every step: y_t depends only on step t
    (bonus term), so permuting earlier steps must not change later outputs
    ... actually with w=0: y_t = r_t.(k_{t-1} (x) v_{t-1} + u k_t (x) v_t)."""
    B, S, H, dh = 1, 16, 1, 4
    r = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    w = jnp.zeros((B, S, H, dh), jnp.float32)
    u = jnp.zeros((H, dh), jnp.float32)
    out = rwkv6_scan(r, k, v, w, u, chunk=8, interpret=True)
    # with u=0 and w=0: y_t = r_t . (k_{t-1} (x) v_{t-1});  y_0 = 0
    want = np.zeros((B, S, H, dh), np.float32)
    rn, kn, vn = map(np.asarray, (r, k, v))
    for t in range(1, S):
        s = np.einsum("bhk,bhv->bhkv", kn[:, t - 1], vn[:, t - 1])
        want[:, t] = np.einsum("bhk,bhkv->bhv", rn[:, t], s)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------------
# ops dispatch
# ----------------------------------------------------------------------------

def test_ops_dispatch_ref_equals_pallas():
    q = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)), jnp.float32)
    a = ops.flash_attention(q, q, q, impl="pallas", block_q=32, block_k=32)
    b = ops.flash_attention(q, q, q, impl="ref")
    assert_close(a, b, jnp.float32)
    # auto on CPU routes to ref
    c = ops.flash_attention(q, q, q, impl="auto")
    np.testing.assert_allclose(np.asarray(b), np.asarray(c))


# ----------------------------------------------------------------------------
# chunked (SSD-style) jnp scans — the optimized GSPMD path (§Perf H1)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("S,chunk", [(67, 16), (128, 64), (31, 64), (256, 32)])
def test_mamba2_chunked_jnp_matches_oracle(S, chunk):
    B, H, dh, ds = 2, 3, 16, 8
    x = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.2 + 1e-3,
                     jnp.float32)
    A = jnp.asarray(-np.abs(RNG.normal(size=(H,))) - 0.1, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, ds)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, ds)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, H, ds, dh)), jnp.float32)
    y0, hf0 = ref.mamba2_scan(x, dt, A, Bm, Cm, D, h0=h0, return_state=True)
    y1, hf1 = ref.mamba2_scan_chunked(x, dt, A, Bm, Cm, D, h0=h0,
                                      return_state=True, chunk=chunk)
    np.testing.assert_allclose(y0, y1, rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(hf0, hf1, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("S,chunk", [(53, 16), (128, 32), (20, 32)])
def test_rwkv6_chunked_jnp_matches_oracle(S, chunk):
    B, H, dh = 2, 3, 8
    r = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(
        RNG.normal(size=(B, S, H, dh)) * 0.5 - 1.5)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, dh)) * 0.1, jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(B, H, dh, dh)), jnp.float32)
    y0, sf0 = ref.rwkv6_scan(r, k, v, w, u, s0=s0, return_state=True)
    y1, sf1 = ref.rwkv6_scan_chunked(r, k, v, w, u, s0=s0,
                                     return_state=True, chunk=chunk)
    np.testing.assert_allclose(y0, y1, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(sf0, sf1, rtol=2e-3, atol=2e-3)


def test_rwkv6_chunked_strong_decay_stable():
    """w underflowing to exactly 0 (decay ~ e^-400) must stay finite and
    match the sequential oracle (the factored exp(-cum) form blows up
    here; the exact pairwise form must not)."""
    B, S, H, dh = 2, 53, 3, 8
    r = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
    w = jnp.asarray(np.exp(-np.exp(
        RNG.normal(size=(B, S, H, dh)) * 2 + 1.0)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, dh)) * 0.1, jnp.float32)
    y0 = np.asarray(ref.rwkv6_scan(r, k, v, w, u))
    y1 = np.asarray(ref.rwkv6_scan_chunked(r, k, v, w, u, chunk=16))
    assert np.isfinite(y1).all()
    np.testing.assert_allclose(y0, y1, rtol=5e-3, atol=5e-3)


@hp.given(st.integers(1, 64), st.integers(1, 2))
@hp.settings(deadline=None, max_examples=12)
def test_chunked_scans_arbitrary_length_property(S, B):
    """Chunked == oracle for any sequence length (padding invariant)."""
    H, dh, ds = 2, 8, 4
    rng = np.random.default_rng(S * 7 + B)
    x = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))) * 0.1 + 1e-3,
                     jnp.float32)
    A = jnp.asarray(-np.abs(rng.normal(size=(H,))) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32)
    D = jnp.asarray(rng.normal(size=(H,)), jnp.float32)
    np.testing.assert_allclose(
        ref.mamba2_scan(x, dt, A, Bm, Cm, D),
        ref.mamba2_scan_chunked(x, dt, A, Bm, Cm, D, chunk=16),
        rtol=3e-4, atol=3e-4)


def test_ops_scan_dispatch_chunked_default_on_cpu():
    """impl='auto' must resolve to the chunked path off-TPU and agree with
    the sequential oracle."""
    B, S, H, dh = 1, 40, 2, 8
    r, k, v = (jnp.asarray(RNG.normal(size=(B, S, H, dh)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(np.exp(-np.exp(
        RNG.normal(size=(B, S, H, dh)) * 0.5 - 1.5)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, dh)) * 0.1, jnp.float32)
    got = ops.rwkv6_scan(r, k, v, w, u, impl="auto")
    want = ops.rwkv6_scan(r, k, v, w, u, impl="pertoken")
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_mamba2_chunked_strong_decay_stable():
    """Large A*dt (upper-triangle exponents >> 0 before masking) must not
    produce inf*0 = NaN and must match the oracle."""
    B, S, H, dh, ds = 2, 40, 4, 8, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(B, S, H, dh)), jnp.float32)
    dt = jnp.asarray(np.abs(rng.normal(size=(B, S, H))) * 2.0 + 0.5,
                     jnp.float32)
    A = jnp.asarray(-np.linspace(1, 16, H), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, ds)), jnp.float32)
    D = jnp.ones((H,), jnp.float32)
    y1 = np.asarray(ref.mamba2_scan_chunked(x, dt, A, Bm, Cm, D, chunk=16))
    assert np.isfinite(y1).all()
    y0 = np.asarray(ref.mamba2_scan(x, dt, A, Bm, Cm, D))
    np.testing.assert_allclose(y0, y1, rtol=1e-3, atol=1e-3)
