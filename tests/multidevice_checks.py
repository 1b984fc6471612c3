"""Numeric checks that need >1 device — run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see test_collectives.py).

Exit code 0 = all checks passed.  Each check prints its name so failures are
attributable from the parent test's captured output.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collectives as C  # noqa: E402
from repro.core import rdma  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def check(name):
    print(f"[multidevice] {name}")


def main() -> None:
    assert jax.device_count() == 8, jax.device_count()
    rng = np.random.default_rng(0)
    mesh = make_mesh((8,), ("x",))

    # --- ring all-reduce: bidirectional / unidirectional / mean, odd sizes ---
    # (2048 and 3072 give lane-aligned chunks: the executor's (rows, 128)
    # views for both directions, and for the directed pass only)
    for size in (8, 37, 64, 1000, 2048, 3072):
        for bidi in (True, False):
            for mean in (True, False):
                x = rng.normal(size=(8, size)).astype(np.float32)
                f = C.make_stacked_all_reduce(mesh, ("x",),
                                              bidirectional=bidi, mean=mean)
                out = np.asarray(f(x))
                want = x.mean(0) if mean else x.sum(0)
                np.testing.assert_allclose(out, want[None].repeat(8, 0),
                                           rtol=2e-5, atol=1e-5)
    check("ring all-reduce (1 axis) ok")

    # --- vs lax.psum oracle -------------------------------------------------
    x = rng.normal(size=(8, 129)).astype(np.float32)
    ours = np.asarray(C.make_stacked_all_reduce(mesh, ("x",))(x))
    def psum_ref(v):
        return jax.lax.psum(v, "x")
    ref = jax.jit(jax.shard_map(psum_ref, mesh=mesh, in_specs=(P("x"),),
                                out_specs=P("x"), check_vma=False))
    got_ref = np.asarray(ref(x))
    np.testing.assert_allclose(ours, got_ref, rtol=2e-5, atol=1e-5)
    check("matches lax.psum oracle")

    # --- bf16 inputs accumulate in fp32 --------------------------------------
    xb = (rng.normal(size=(8, 256)) * 10).astype(jnp.bfloat16)
    f = C.make_stacked_all_reduce(mesh, ("x",))
    out = np.asarray(f(xb).astype(np.float32))
    want = np.asarray(xb.astype(np.float32)).sum(0)
    np.testing.assert_allclose(out, want[None].repeat(8, 0), rtol=2e-2)
    assert f(xb).dtype == jnp.bfloat16
    check("bf16 all-reduce w/ fp32 accumulation ok")

    # --- multi-axis dimension-ordered all-reduce ------------------------------
    mesh24 = make_mesh((2, 4), ("a", "b"))
    x2 = rng.normal(size=(2, 4, 77)).astype(np.float32)
    f2 = C.make_stacked_all_reduce(mesh24, ("a", "b"))
    out2 = np.asarray(f2(x2))
    want2 = x2.sum((0, 1))[None, None].repeat(2, 0).repeat(4, 1)
    np.testing.assert_allclose(out2, want2, rtol=2e-5, atol=1e-5)
    check("dim-ordered 2-axis all-reduce ok")

    # --- reduce-scatter / all-gather inverse pair -----------------------------
    def rs_ag(v):
        chunk, sizes = C.dim_ordered_reduce_scatter(v, ("a", "b"))
        return C.dim_ordered_all_gather(chunk, ("a", "b"), sizes)
    g = jax.jit(jax.shard_map(lambda v: rs_ag(v[0, 0])[None, None],
                              mesh=mesh24, in_specs=(P("a", "b"),),
                              out_specs=P("a", "b"), check_vma=False))
    out3 = np.asarray(g(x2))
    np.testing.assert_allclose(
        out3, x2.sum((0, 1))[None, None].repeat(2, 0).repeat(4, 1),
        rtol=2e-5, atol=1e-5)
    check("RS+AG round trip ok")

    # --- reduce-scatter: every rank owns its correct chunk --------------------
    def rs_only(v):
        out = C.ring_reduce_scatter(v[0], "x")
        return out[None]
    h = jax.jit(jax.shard_map(rs_only, mesh=mesh, in_specs=(P("x"),),
                              out_specs=P("x"), check_vma=False))
    for size in (64, 2048):
        xr = rng.normal(size=(8, size)).astype(np.float32)
        chunks = np.asarray(h(xr))       # (8, size/8): rank r -> chunk r
        want = xr.sum(0).reshape(8, size // 8)
        # bidirectional layout: chunk r = [front half of chunk r, back half]
        np.testing.assert_allclose(chunks, want, rtol=2e-5, atol=1e-5)
    check("reduce-scatter chunk ownership ok")

    # --- all-gather rank ordering ---------------------------------------------
    def ag_only(v):
        return C.ring_all_gather(v[0], "x")[None]
    k = jax.jit(jax.shard_map(ag_only, mesh=mesh, in_specs=(P("x"),),
                              out_specs=P("x"), check_vma=False))
    for size in (6, 256):
        xg = rng.normal(size=(8, size)).astype(np.float32)
        out = np.asarray(k(xg))          # (8, 8, size), row j == xg[j]
        for r in range(8):
            np.testing.assert_allclose(out[r], xg, rtol=1e-6)
    check("all-gather ordering ok")

    # --- ring all-to-all == transpose ------------------------------------------
    def a2a(v):
        return C.ring_all_to_all(v[0], "x")[None]
    m = jax.jit(jax.shard_map(a2a, mesh=mesh, in_specs=(P("x"),),
                              out_specs=P("x"), check_vma=False))
    xa = rng.normal(size=(8, 8, 3)).astype(np.float32)
    out = np.asarray(m(xa))
    np.testing.assert_allclose(out, xa.transpose(1, 0, 2), rtol=1e-6)
    # fast path oracle
    def a2a_fast(v):
        return C.fast_all_to_all(v[0], "x")[None]
    mf = jax.jit(jax.shard_map(a2a_fast, mesh=mesh, in_specs=(P("x"),),
                               out_specs=P("x"), check_vma=False))
    np.testing.assert_allclose(np.asarray(mf(xa)), out, rtol=1e-6)
    check("ring all-to-all == transpose == lax.all_to_all")

    # --- halo exchange -----------------------------------------------------------
    def halo(v):
        prev, nxt = C.halo_exchange(v[0], "x", halo=2)
        return jnp.stack([prev, nxt])[None]
    hx = jax.jit(jax.shard_map(halo, mesh=mesh, in_specs=(P("x"),),
                               out_specs=P("x"), check_vma=False))
    xh = rng.normal(size=(8, 5, 4)).astype(np.float32)
    out = np.asarray(hx(xh))  # (8, 2, 2, 4)
    for r in range(8):
        np.testing.assert_allclose(out[r, 0], xh[(r - 1) % 8][-2:], rtol=1e-6)
        np.testing.assert_allclose(out[r, 1], xh[(r + 1) % 8][:2], rtol=1e-6)
    check("halo exchange ok")

    # --- rdma put_shift / put_coords ----------------------------------------------
    def shift3(v):
        return rdma.put_shift(v[0], "x", 3)[None]
    sh = jax.jit(jax.shard_map(shift3, mesh=mesh, in_specs=(P("x"),),
                               out_specs=P("x"), check_vma=False))
    xs = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)
    out = np.asarray(sh(xs))
    np.testing.assert_allclose(out, np.roll(xs, 3, axis=0), rtol=0)

    def coords_put(v):
        return rdma.put_coords(v[0, 0], ("a", "b"), (1, -2))[None, None]
    cp = jax.jit(jax.shard_map(coords_put, mesh=mesh24, in_specs=(P("a", "b"),),
                               out_specs=P("a", "b"), check_vma=False))
    xc = np.arange(2 * 4 * 3, dtype=np.float32).reshape(2, 4, 3)
    out = np.asarray(cp(xc))
    np.testing.assert_allclose(out, np.roll(np.roll(xc, 1, 0), -2, 1), rtol=0)
    check("rdma put_shift / put_coords ok")

    # --- apex trainer: explicit torus-collective DP == GSPMD DP ---------------
    import tempfile
    from repro.models.common import ArchCfg
    from repro.optim import AdamWConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = ArchCfg(name="tiny", family="dense", n_layers=2, d_model=32,
                  n_heads=4, n_kv_heads=2, d_ff=64, vocab=257,
                  dtype=jnp.float32)
    opt = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=50)
    with tempfile.TemporaryDirectory() as td:
        apex = Trainer(cfg, TrainerConfig(ckpt_dir=td + "/a", ckpt_every=0,
                                          batch=8, seq_len=32, opt=opt,
                                          comm="apex", dp_axis="x"),
                       mesh=make_mesh((8,), ("x",)))
        gspmd = Trainer(cfg, TrainerConfig(ckpt_dir=td + "/g", ckpt_every=0,
                                           batch=8, seq_len=32, opt=opt,
                                           comm="gspmd"),
                        mesh=make_mesh((8,), ("x",)))
        la = [m["loss"] for m in apex.train(4)]
        lg = [m["loss"] for m in gspmd.train(4)]
        # same math, different collectives: losses must track closely
        np.testing.assert_allclose(la, lg, rtol=2e-3, atol=2e-3)
        assert la[-1] < la[0]
    check("apex (torus-collective) trainer matches GSPMD trainer")

    # --- elastic re-mesh: kill a node, shrink 8 -> 4 devices, keep training ---
    with tempfile.TemporaryDirectory() as td:
        tr = Trainer(cfg, TrainerConfig(ckpt_dir=td, ckpt_every=3, batch=8,
                                        seq_len=32, opt=opt, comm="gspmd"),
                     mesh=make_mesh((8,), ("x",)))
        tr.train(4)  # checkpoint at step 3

        def fault(i):
            if i == 1:
                tr.lofamo.kill_node(5)

        metrics = tr.train(4, fault_hook=fault)
        assert tr.mesh.devices.size == 4, tr.mesh
        assert all(np.isfinite(m["loss"]) for m in metrics)
        evs = " | ".join(tr.events)
        assert "elastic re-mesh: 8 -> 4" in evs and "restored step" in evs
    check("elastic re-mesh after LO|FA|MO fault ok")

    print("ALL MULTIDEVICE CHECKS PASSED")


if __name__ == "__main__":
    main()
