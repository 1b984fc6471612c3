"""The training driver on four forced CPU devices, sound and broken.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
      python tests/bench/train_checks.py

Runs the driver's window (the look for a chip skipped) at a tiny size:
once sound (with the control and the faults planted in the reference
read against its reference), then once with each fault the apex training
cell can have planted in the program underneath it: a step that returns
its state unchanged, half of the batch left out (the mean over the rest),
and the exchange between chips left out.  Prints one JSON line per case
with the compared numbers and whether they come out correct.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402
from tiny import bench_run, harness  # noqa: E402


def unchanged(mp):
    from repro.runtime import trainer

    def same(cfg, grads, state, params, **kw):
        return params, dict(state, step=state["step"] + 1)
    mp.setattr(trainer, "apex_zero1_update", same)


def half_batch(mp):
    from repro.models import transformer
    loss = transformer.train_loss

    def half(cfg, params, batch, **kw):
        n = batch["tokens"].shape[0] // 2
        return loss(cfg, params, {k: v[:n] for k, v in batch.items()}, **kw)
    mp.setattr(transformer, "train_loss", half)


def no_exchange(mp):
    import jax
    import jax.numpy as jnp
    from repro.core import collectives

    def local(x, axis_name, *, mean=False, **kw):
        n = jax.lax.axis_size(axis_name)
        flat = x.reshape(-1).astype(jnp.float32)
        chunk = -(-flat.size // n)
        flat = jnp.pad(flat, (0, chunk * n - flat.size))
        return jax.lax.dynamic_slice(
            flat, (jax.lax.axis_index(axis_name) * chunk,), (chunk,))
    mp.setattr(collectives, "ring_reduce_scatter", local)


class Patch:
    """A small monkeypatch that undoes itself."""

    def __init__(self):
        self.undo = []

    def setattr(self, obj, name, value):
        self.undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def close(self):
        for obj, name, value in reversed(self.undo):
            setattr(obj, name, value)


def main() -> int:
    import jax
    driver = harness.plugin("drivers", "train")
    cfg, mix = tiny.train_cfg(), tiny.train_mix()
    for name, fault in (("sound", None), ("unchanged", unchanged),
                        ("half_batch", half_batch),
                        ("no_exchange", no_exchange)):
        patch = Patch()
        if fault:
            fault(patch)
        try:
            ctx = tiny.ctx(cfg, mix, seed=5, seconds=0.5)
            ctx.devices = jax.devices()[:4]
            out = driver.run(ctx)
        finally:
            patch.close()
        print(json.dumps({
            "case": name, "correct": bench_run.correct_from(out["checks"]),
            "readings": {c["name"]: c["value"] for c in out["checks"]},
            "limits": {c["name"]: c["limit"] for c in out["checks"]},
            "steps": out["attempted"]}), flush=True)
        if name == "sound":     # the control and the planted faults
            control = driver.control(ctx, out)
            control_checks = control.pop("control_checks")
            for case, readings in control.items():
                checks = (control_checks if case == "control" else
                          driver.run_checks(readings, cfg["check"]))
                print(json.dumps({
                    "case": f"reference_{case}", "readings": readings,
                    "checks": checks,
                    "correct": bench_run.correct_from(checks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
