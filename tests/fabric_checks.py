"""Numeric schedule-executor checks that need >1 device — run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (see
test_fabric.py).

Covers the acceptance bar for the fabric refactor:
  * schedule-executed collectives == oracle (psum / sum / transpose / roll)
    for every collective on 1D (8), 2D (2,4) and 3D (2,2,2) tori;
  * fault-rewritten schedules: a detoured dead link changes NOTHING
    numerically (all ranks still participate); a dead node shrinks the
    ring and the live ranks reduce exactly the live contributions.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import collectives as C  # noqa: E402
from repro.core import fabric  # noqa: E402
from repro.core.topology import Torus  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def check(name):
    print(f"[fabric] {name}")


MESHES = {
    "1d": ((8,), ("x",)),
    "2d": ((2, 4), ("a", "b")),
    "3d": ((2, 2, 2), ("u", "v", "w")),
}


def run_sharded(mesh, axes, fn, x):
    lead = len(axes)
    spec = P(*axes)

    def per_shard(v):
        return fn(v.reshape(v.shape[lead:])).reshape(v.shape)

    return np.asarray(jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False))(x))


def all_reduce_checks(rng):
    for tag, (shape, axes) in MESHES.items():
        mesh = make_mesh(shape, axes)
        torus = Torus(shape)
        x = rng.normal(size=shape + (51,)).astype(np.float32)
        lead = tuple(range(len(shape)))
        want = x.sum(lead)
        for bidi in (True, False):
            sched = fabric.lower_all_reduce(torus, axes, bidirectional=bidi)
            out = run_sharded(
                mesh, axes,
                lambda v, s=sched: fabric.execute_all_reduce(s, v), x)
            np.testing.assert_allclose(
                out, np.broadcast_to(want, x.shape), rtol=2e-5, atol=1e-5)
        check(f"all-reduce schedule == sum oracle ({tag}, bidi+uni)")


def rs_ag_roundtrip_checks(rng):
    for tag, (shape, axes) in MESHES.items():
        mesh = make_mesh(shape, axes)
        torus = Torus(shape)
        x = rng.normal(size=shape + (37,)).astype(np.float32)
        rs = fabric.lower_reduce_scatter(torus, axes)
        ag = fabric.lower_all_gather(
            torus, tuple(reversed(axes)),
            axis_dims=tuple(reversed(range(len(axes)))))

        def round_trip(v):
            chunk, sizes = fabric.execute_reduce_scatter(rs, v)
            return fabric.execute_all_gather(ag, chunk, sizes) \
                .reshape(v.shape)

        out = run_sharded(mesh, axes, round_trip, x)
        want = np.broadcast_to(x.sum(tuple(range(len(shape)))), x.shape)
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=1e-5)
        check(f"RS+AG schedule round trip ({tag})")


def chunk_ownership_check(rng):
    mesh = make_mesh((8,), ("x",))
    sched = fabric.lower_reduce_scatter(Torus((8,)), ("x",))
    x = rng.normal(size=(8, 64)).astype(np.float32)

    def rs_only(v):
        out, _ = fabric.execute_reduce_scatter(sched, v[0])
        return out[None]

    h = jax.jit(jax.shard_map(rs_only, mesh=mesh, in_specs=(P("x"),),
                              out_specs=P("x"), check_vma=False))
    chunks = np.asarray(h(x))
    np.testing.assert_allclose(chunks, x.sum(0).reshape(8, 8),
                               rtol=2e-5, atol=1e-5)
    check("reduce-scatter slot owns contiguous chunk")


def a2a_and_halo_checks(rng):
    mesh = make_mesh((8,), ("x",))
    torus = Torus((8,))
    sched = fabric.lower_all_to_all(torus, "x")
    xa = rng.normal(size=(8, 8, 3)).astype(np.float32)

    def a2a(v):
        return fabric.execute_all_to_all(sched, v[0])[None]

    out = np.asarray(jax.jit(jax.shard_map(
        a2a, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
        check_vma=False))(xa))
    np.testing.assert_allclose(out, xa.transpose(1, 0, 2), rtol=1e-6)
    check("all-to-all schedule == transpose")

    hs = fabric.lower_halo_exchange(torus, "x")
    xh = rng.normal(size=(8, 5, 4)).astype(np.float32)

    def halo(v):
        prev, nxt = fabric.execute_halo_exchange(hs, v[0], halo=2)
        return jax.numpy.stack([prev, nxt])[None]

    out = np.asarray(jax.jit(jax.shard_map(
        halo, mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
        check_vma=False))(xh))
    for r in range(8):
        np.testing.assert_allclose(out[r, 0], xh[(r - 1) % 8][-2:], rtol=1e-6)
        np.testing.assert_allclose(out[r, 1], xh[(r + 1) % 8][:2], rtol=1e-6)
    check("halo-exchange schedule == ring neighbours")


def fault_rewrite_checks(rng):
    # dead LINK: all ranks alive, detour is logical -> results identical
    mesh = make_mesh((8,), ("x",))
    torus = Torus((8,))
    clean = fabric.lower_all_reduce(torus, ("x",))
    detoured = fabric.rewrite(clean,
                              fabric.FaultMap.normalized(links=[(2, 3)]))
    assert detoured.max_hops == 7
    x = rng.normal(size=(8, 100)).astype(np.float32)
    out_c = run_sharded(mesh, ("x",),
                        lambda v: fabric.execute_all_reduce(clean, v), x)
    out_d = run_sharded(mesh, ("x",),
                        lambda v: fabric.execute_all_reduce(detoured, v), x)
    np.testing.assert_array_equal(out_c, out_d)
    check("dead-link detour: results bit-identical")

    # dead NODE: ring shrinks to 7; live ranks reduce live contributions
    dead = 3
    shrunk = fabric.rewrite(clean, fabric.FaultMap.normalized(nodes=[dead]))
    out_s = run_sharded(mesh, ("x",),
                        lambda v: fabric.execute_all_reduce(shrunk, v), x)
    live = [r for r in range(8) if r != dead]
    want_live = x[live].sum(0)
    for r in live:
        np.testing.assert_allclose(out_s[r], want_live, rtol=2e-5, atol=1e-5)
    check("dead-node shrunk ring: live ranks reduce live contributions")

    # mean over the shrunk ring divides by the LIVE count
    shrunk_mean = fabric.rewrite(
        fabric.lower_all_reduce(torus, ("x",), mean=True),
        fabric.FaultMap.normalized(nodes=[dead]))
    out_m = run_sharded(
        mesh, ("x",),
        lambda v: fabric.execute_all_reduce(shrunk_mean, v), x)
    for r in live:
        np.testing.assert_allclose(out_m[r], want_live / 7,
                                   rtol=2e-5, atol=1e-5)
    check("shrunk-ring mean divides by live count")


def bucket_hook_equivalence_checks(rng):
    """Overlap engine: the bucketed grad hook (reduce-scatter issued inside
    the VJP) must match the sequential per-leaf schedule execution
    bit-for-bit — on a 1D ring and along one axis of a 2D torus."""
    import jax.numpy as jnp

    cases = [("1d", (8,), ("x",), 0), ("2d", (2, 4), ("a", "b"), 1)]
    shapes = [(13,), (3, 5), (4, 4, 2), (25,), (7,)]
    for tag, mshape, axes, dim in cases:
        mesh = make_mesh(mshape, axes)
        torus = Torus(mshape)
        sched = fabric.lower_reduce_scatter(torus, (axes[dim],),
                                            axis_dims=(dim,), mean=True)
        m = torus.dims[dim]
        leaves = [rng.normal(size=mshape + s).astype(np.float32)
                  for s in shapes]
        plan = fabric.plan_buckets([int(np.prod(s)) for s in shapes],
                                   40 * 4, itemsize=4)
        assert plan.n_buckets > 1  # exercise multi-bucket issue
        lead = len(mshape)

        def seq_leaf(g):
            chunk, _ = fabric.execute_reduce_scatter(sched, g)
            slot = fabric.ring_slot(sched.phases[0])
            full = jnp.zeros((chunk.shape[0] * m,), chunk.dtype)
            full = jax.lax.dynamic_update_slice(
                full, chunk, (slot * chunk.shape[0],))
            return full[:g.size].reshape(g.shape).astype(g.dtype)

        def per_shard(*gs):
            gs = [g.reshape(g.shape[lead:]) for g in gs]
            hook = fabric.make_bucket_grad_hook(plan, sched)
            _, vjp = jax.vjp(hook, [jnp.zeros_like(g) for g in gs])
            (bucketed,) = vjp(list(gs))
            seq = [seq_leaf(g) for g in gs]
            return tuple(x.reshape((1,) * lead + x.shape)
                         for x in list(bucketed) + seq)

        spec = P(*axes)
        out = jax.jit(jax.shard_map(
            per_shard, mesh=mesh, in_specs=(spec,) * len(leaves),
            out_specs=(spec,) * (2 * len(leaves)),
            check_vma=False))(*leaves)
        n = len(leaves)
        for i in range(n):
            np.testing.assert_array_equal(
                np.asarray(out[i]), np.asarray(out[n + i]),
                err_msg=f"leaf {i} ({tag})")
        check(f"bucketed grad hook == sequential RS, bitwise ({tag})")


def sim_analytic_differential_checks():
    """The two cost backends must agree on single-flow ring schedules:
    every round's messages ride disjoint link directions, so the
    event-driven sim (fabric/sim.py) and the closed-form model price the
    exact same timeline.  10% is the acceptance bar; the assertion is the
    differential that validates BOTH models."""
    for tag, (shape, axes) in MESHES.items():
        torus = Torus(shape)
        scheds = {
            "all-reduce": fabric.lower_all_reduce(torus, axes),
            "reduce-scatter": fabric.lower_reduce_scatter(torus, axes),
            "all-gather": fabric.lower_all_gather(torus, axes),
        }
        for name, sched in scheds.items():
            for nbytes in (0, 4096, 1 << 20):
                a = fabric.estimate(sched, nbytes).total_s
                s = fabric.estimate(sched, nbytes, backend="sim").total_s
                err = abs(s - a) / a if a else abs(s - a)
                assert err <= 0.10, \
                    f"{name} ({tag}, {nbytes} B): sim {s} vs analytic " \
                    f"{a} — {err * 100:.1f}% > 10%"
        check(f"sim backend == analytic on single-flow schedules ({tag})")
    # multi-hop p2p unicast rides the same differential
    t3 = Torus((2, 2, 2))
    p2p = fabric.lower_p2p(t3, 0, t3.size - 1)
    for nbytes in (64, 1 << 20):
        a = fabric.estimate(p2p, nbytes).total_s
        s = fabric.estimate(p2p, nbytes, backend="sim").total_s
        assert abs(s - a) / a <= 0.10
    check("sim backend == analytic on p2p unicast (3d)")


def main() -> None:
    assert jax.device_count() == 8, jax.device_count()
    rng = np.random.default_rng(7)
    all_reduce_checks(rng)
    rs_ag_roundtrip_checks(rng)
    chunk_ownership_check(rng)
    a2a_and_halo_checks(rng)
    fault_rewrite_checks(rng)
    bucket_hook_equivalence_checks(rng)
    sim_analytic_differential_checks()
    print("ALL FABRIC CHECKS PASSED")


if __name__ == "__main__":
    main()
