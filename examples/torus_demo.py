"""The APEnet+ fabric itself: 3D torus RDMA + ring collectives demo.

  PYTHONPATH=src python examples/torus_demo.py

Shows the paper's communication layer as a library:
  * 3D-torus coordinate math, dimension-ordered routing, hop metrics;
  * one-sided RDMA put/get over mesh axes (shard_map + ppermute);
  * the bidirectional double-buffered ring all-reduce ("dual DMA engines")
    matching jax.lax.psum bit-for-bit in fp32;
  * the APElink efficiency / latency models reproducing the paper numbers.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"   # forced host devices, never the chip

import numpy as np  # noqa: E402

import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import apelink, collectives as C, rdma  # noqa: E402
from repro.core.lofamo import awareness_time_model  # noqa: E402
from repro.core.topology import Torus  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def main() -> None:
    # --- topology: the QUonG 4x4x1 deployment --------------------------------
    t = Torus((4, 4, 1))
    print(f"QUonG torus {t.dims}: {t.size} nodes, diameter {t.diameter}, "
          f"{len(t.links())} links, bisection {t.bisection_links} links")
    src, dst = 0, t.rank((2, 3, 0))
    print(f"dimension-ordered route {t.coords(src)} -> {t.coords(dst)}: "
          f"{[t.coords(r) for r in t.route(src, dst)]}")

    # --- RDMA put over a mesh axis -------------------------------------------
    mesh = make_mesh((8,), ("x",))
    x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    shifted = jax.jit(jax.shard_map(
        lambda v: rdma.put_shift(v[0], "x", +1)[None],
        mesh=mesh, in_specs=(P("x"),), out_specs=P("x"),
        check_vma=False))(x)
    print("rdma.put_shift(+1) moved every rank's row to its +X neighbour:",
          np.allclose(np.asarray(shifted), np.roll(x, 1, axis=0)))

    # --- bidirectional ring all-reduce vs psum --------------------------------
    v = np.random.default_rng(0).normal(size=(8, 1000)).astype(np.float32)
    ours = np.asarray(C.make_stacked_all_reduce(mesh, ("x",))(v))
    want = v.sum(0)
    print("bidirectional double-buffered ring all-reduce == sum:",
          np.allclose(ours, want[None], rtol=2e-5, atol=1e-5))

    # --- the paper's numbers ---------------------------------------------------
    net = apelink.NetModel()
    print("\npaper model reproduction:")
    print(f"  APElink efficiency          {apelink.protocol_efficiency():.3f}"
          "   (paper 0.784)")
    print(f"  sustained link bandwidth    "
          f"{apelink.sustained_bandwidth()/1e9:.2f} GB/s (paper ~2.2)")
    print(f"  GPU-GPU latency, P2P        "
          f"{net.latency(32, src_gpu=True, dst_gpu=True)*1e6:.1f} us "
          "(paper ~8.2)")
    print(f"  GPU-GPU latency, staged     "
          f"{net.latency(32, src_gpu=True, dst_gpu=True, p2p=False)*1e6:.1f}"
          " us (paper ~16.8)")
    print(f"  GPU-GPU latency, IB+MVAPICH "
          f"{net.latency(32, fabric='ib')*1e6:.1f} us (paper ~17.4)")
    print(f"  LO|FA|MO Ta @ WD=500ms      {awareness_time_model(0.5):.2f} s "
          "(paper 0.9)")
    print("\ntorus demo OK")


if __name__ == "__main__":
    main()
