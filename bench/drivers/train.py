"""Training window: the program's ``Trainer(comm="apex")`` on a ("data",)
mesh over the cell's chips.

Set-up builds one trainer, gives it the benchmark's weights (made from the
seed, replicated over the mesh) and the benchmark's token batches in place
of its own source, and drives it through its first three steps with
``Trainer.train_step``, the call the window makes; the first compiles (or
loads from the cache) the step.  Those steps are what the reference
follows: their losses, the first gradient as the optimizer got it (the
first moment after one step, over 1 - b1), and each leaf's change after the
three.  The window then runs whole steps until it closes.
"""
from __future__ import annotations

import gc

import numpy as np

import harness
import weights

CHECKED_STEPS = 3
COMPARED = ("loss_gap", "grad_norm_gap", "change_norm_gap")


class Feed:
    """The benchmark's batches, in the trainer's ``next_batch`` form."""

    def __init__(self, mix: dict, seed: int, rows: int, vocab: int) -> None:
        self.mix, self.seed, self.rows, self.vocab = mix, seed, rows, vocab
        self.gen = harness.generator(mix)
        self.step = 0

    def batch(self, step: int) -> np.ndarray:
        return self.gen.train_batch(self.mix, self.seed, step, self.rows,
                                   self.vocab)

    def next_batch(self) -> dict:
        b = self.batch(self.step)
        self.step += 1
        return {"tokens": b[:, :-1], "labels": b[:, 1:]}


def _opt_config(opt: dict):
    from repro.optim import AdamWConfig
    return AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"],
                       clip_norm=float("inf"),
                       warmup_steps=opt["warmup_steps"],
                       total_steps=opt["total_steps"],
                       min_lr_frac=opt["min_lr_frac"])


def build(ctx):
    """The trainer, set up as the window runs it, and its feed."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from program import arch_config
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg, mix = ctx.cfg, ctx.mix
    model, dep = cfg["model"], cfg["deployment"]
    mesh = Mesh(np.asarray(ctx.devices), ("data",))
    rows = dep["per_chip_batch"] * len(ctx.devices)
    tcfg = TrainerConfig(
        ckpt_dir=str(harness.ROOT / "bench_out" / "ckpt"), ckpt_every=0,
        opt=_opt_config(cfg["optimizer"]), batch=rows,
        seq_len=mix["seq_len"], remat=True, comm="apex", seed=0)
    tr = Trainer(arch_config(cfg), tcfg, mesh=mesh)
    ctx.mark("trainer")
    tr.params = weights.make(model, ctx.seed,
                             sharding=NamedSharding(mesh, P()))
    tr.data = Feed(mix, ctx.seed, rows, model["vocab_size"])
    jax.block_until_ready(tr.params)
    ctx.mark("weights")
    return tr


def first_steps(ctx, tr) -> dict:
    """Three steps through the window's call, with what they compare."""
    import jax
    norms = harness.plugin("reference", ctx.cfg["reference"]).leaf_norms
    p0 = jax.tree.map(lambda x: x.copy(), tr.params)
    losses = [tr.train_step()["loss"]]
    b1 = ctx.cfg["optimizer"]["b1"]
    grads = {k: v / (1.0 - b1) for k, v in norms(tr.opt_state["m"]).items()}
    for _ in range(CHECKED_STEPS - 1):
        losses.append(tr.train_step()["loss"])
    change = norms(jax.tree.map(
        lambda a, b: a.astype("float32") - b.astype("float32"),
        tr.params, p0))
    return {"losses": losses, "grad_norms": grads, "change_norms": change}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers a run holds against its limits: the largest relative
    gap of the three losses, and by the worst leaf the gap of the first
    gradient's norm and of each leaf's change over the three steps, each
    against the larger of that leaf's reference norm and the median
    leaf's.  Leaves whose reference gradient is under a thousandth of the
    median leaf's are left out of the change (they move by round-off
    alone).  ``worst`` names the leaves."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    gmed = float(np.median(list(ref["grad_norms"].values())))
    cmed = float(np.median(list(ref["change_norms"].values())))
    grad = {k: abs(prog["grad_norms"][k] - v) / max(v, gmed)
            for k, v in ref["grad_norms"].items()}
    change = {k: abs(prog["change_norms"][k] - ref["change_norms"][k])
              / max(ref["change_norms"][k], cmed)
              for k, v in ref["grad_norms"].items() if v >= 1e-3 * gmed}
    worst_g, worst_c = max(grad, key=grad.get), max(change, key=change.get)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad[worst_g],
            "change_norm_gap": change[worst_c],
            "worst": {"grad": worst_g, "change": worst_c}}


def run_checks(readings: dict, limits: dict) -> list:
    """The compared numbers, each beside its limit."""
    return [{"name": k, "value": readings[k], "limit": limits[k]}
            for k in COMPARED]


def reference_readings(ctx, batches, **kw) -> dict:
    """The reference's first steps on ``batches`` from the seed's weights,
    on one chip (``kw``: the control or a planted fault)."""
    ref = harness.plugin("reference", ctx.cfg["reference"])
    cfg = ctx.cfg
    params = ref.to_f32(weights.make(cfg["model"], ctx.seed,
                                     device=ctx.devices[0]))
    opt = dict(cfg["optimizer"], store_dtype=cfg["model"]["dtype"])
    return ref.train_steps(cfg["model"], params, batches, opt,
                           block_rows=cfg["check"]["block_rows"], **kw)


def run(ctx) -> dict:
    import jax

    clock = ctx.clock
    tr = build(ctx)
    prog = first_steps(ctx, tr)
    ctx.mark("first steps")
    compiles0 = ctx.compiles.snapshot()
    rows, seq = tr.data.rows, ctx.mix["seq_len"]
    steps = 0
    with ctx.recording():
        with jax.profiler.TraceAnnotation("bench/window"):
            t0 = clock.now()
            ctx.mark_window_start(t0)
            deadline = t0 + ctx.seconds
            while clock.now() < deadline:
                with jax.profiler.TraceAnnotation("bench/step"):
                    tr.train_step()
                steps += 1
            t1 = clock.now()
    compiles = np.subtract(ctx.compiles.snapshot(), compiles0).tolist()
    device = harness.device_facts(ctx.devices)
    batches = [tr.data.batch(i) for i in range(CHECKED_STEPS)]
    del tr
    gc.collect()
    ref = reference_readings(ctx, batches)
    readings = compare(prog, ref)
    harness.log(f"worst leaf of grad_norm_gap {readings['worst']['grad']}, "
                f"of change_norm_gap {readings['worst']['change']}")
    checks = run_checks(readings, ctx.cfg["check"])
    tokens = steps * rows * seq
    return {"attempted": steps, "failed": 0,
            "e2e": {"train_tokens_per_s": (tokens / (t1 - t0), "tokens/s")},
            "checks": checks, "device": device,
            "records": {"window_s": t1 - t0, "steps": steps,
                        "tokens_per_step": rows * seq,
                        "chips": len(ctx.devices),
                        "compiles_in_window": compiles,
                        "program": prog, "reference": ref}}


def control(ctx, out) -> dict:
    """Readings of the control (the reference in fp8) and of the faults
    planted in the reference, against the run's reference, on the same
    batches: what the limits must separate from the program's.  The
    control's readings also go through the run's own checks
    (``control_checks``)."""
    ref_mod = harness.plugin("reference", ctx.cfg["reference"])
    cfg = ctx.cfg
    rows = cfg["deployment"]["per_chip_batch"] * len(ctx.devices)
    feed = Feed(ctx.mix, ctx.seed, rows, cfg["model"]["vocab_size"])
    batches = [feed.batch(i) for i in range(CHECKED_STEPS)]
    ref = out["records"]["reference"]
    readings = {}
    for name, kw in (("control", {"quant": "fp8"}),
                     ("half_batch", {"fault": "half_batch"}),
                     ("no_exchange", {"fault": "no_exchange",
                                      "ranks": len(ctx.devices)})):
        readings[name] = compare(reference_readings(ctx, batches, **kw), ref)
    readings["control_checks"] = run_checks(readings["control"], cfg["check"])
    return readings
