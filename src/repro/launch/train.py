"""Training launcher.

On a real TPU pod this is the per-host entrypoint (jax.distributed
initialises from the TPU runtime; the GSPMD step then spans the full mesh).
On CPU it runs the same code path over forced host devices, which is how
the examples and integration tests exercise it end-to-end.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
      --reduced --steps 20 --batch 8 --seq 128 --devices 8

``--comm apex`` selects the paper-faithful explicit torus-collective data
parallelism (shard_map + bidirectional ring reduce-scatter/all-gather);
``--comm gspmd`` (default) lets XLA place the collectives from the
parallel.sharding specs.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (0 = real devices)")
    ap.add_argument("--mesh", default="",
                    help="mesh as 'dp,tp' (e.g. '4,2'); default: all-DP")
    ap.add_argument("--comm", choices=["gspmd", "apex", "single"],
                    default="gspmd")
    ap.add_argument("--ckpt-dir", default="/tmp/apex_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--grad-accum", type=int, default=1,
                    help="microbatches per optimizer step")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    if args.devices:
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")
        os.environ["JAX_PLATFORMS"] = "cpu"   # host devices, not the chip

    import jax  # noqa: E402  (after XLA_FLAGS)
    import numpy as np  # noqa: E402

    from repro import configs  # noqa: E402
    from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
    from repro.launch.mesh import make_mesh  # noqa: E402
    from repro.optim import AdamWConfig  # noqa: E402
    from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402

    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    n = len(jax.devices())
    if args.comm == "single" or n == 1:
        mesh = None
        args.comm = "single"
    elif args.mesh:
        dp, tp = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((dp, tp), ("data", "model"))
    elif args.comm == "apex":
        mesh = make_mesh((n,), ("data",))
    else:
        mesh = make_mesh((n, 1), ("data", "model"))

    opt = AdamWConfig(lr=args.lr, warmup_steps=min(100, args.steps // 10),
                      total_steps=max(args.steps, 1))
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         opt=opt, batch=args.batch, seq_len=args.seq,
                         comm=args.comm, dp_axis="data", seed=args.seed,
                         grad_accum=args.grad_accum)
    tr = Trainer(cfg, tcfg, mesh=mesh)
    if args.resume:
        try:
            tr.resume()
        except FileNotFoundError:
            print("[train] no checkpoint found; starting fresh")
    print(f"[train] arch={cfg.name} params={tr.n_params:,} "
          f"devices={n} comm={args.comm}")
    for m in tr.train(args.steps):
        print(f"  step {m['step']:>5d}  loss {m['loss']:.4f}  "
              f"{m['step_time_s']*1e3:7.1f} ms")
    if tr.events:
        print("[events]")
        for e in tr.events:
            print("  ", e)
    losses = [m["loss"] for m in tr.metrics_log]
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
