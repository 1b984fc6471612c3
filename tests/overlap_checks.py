"""Overlap-engine trainer checks that need >1 device — run in a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (see
test_runtime.py).

Acceptance bar for the overlap engine's apex path:
  * the bucketed-overlapped apex step (gradient reduce-scatter issued
    inside backward by the fabric bucket grad hook, ZeRO-1 update on the
    pre-reduced shards) is numerically IDENTICAL to the sequential apex
    step — losses equal, every param leaf bitwise equal;
  * train_step() stats report predicted vs measured overlap efficiency;
  * a LO|FA|MO link fault reroutes the bucketed schedules (fault_mode
    "reroute") and the overlapped trainer still tracks the sequential one.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import tempfile  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models.common import ArchCfg  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.runtime.trainer import Trainer, TrainerConfig  # noqa: E402


def check(name):
    print(f"[overlap] {name}")


CFG = ArchCfg(name="tiny", family="dense", n_layers=2, d_model=32,
              n_heads=4, n_kv_heads=2, d_ff=64, vocab=257,
              dtype=jnp.float32)
OPT = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=50)


def make(td, tag, **kw):
    tcfg = TrainerConfig(ckpt_dir=os.path.join(td, tag), ckpt_every=0,
                         batch=8, seq_len=32, opt=OPT, comm="apex",
                         dp_axis="x", **kw)
    return Trainer(CFG, tcfg, mesh=make_mesh((8,), ("x",)))


def assert_same_params(a, b, msg):
    for pa, pb in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(pa), np.asarray(pb),
                                      err_msg=msg)


def equivalence_check(td):
    seq = make(td, "seq")
    ov = make(td, "ov", overlap=True, bucket_mb=0.05)
    assert ov.bucket_plan is not None and ov.bucket_plan.n_buckets > 1
    ms, mo = seq.train(3), ov.train(3)
    for a, b in zip(ms, mo):
        assert a["loss"] == b["loss"], (a["loss"], b["loss"])
    assert_same_params(seq, ov, "overlapped step diverged from sequential")
    check("bucketed-overlapped apex step == sequential, bitwise (8-ring)")

    last = mo[-1]
    for key in ("overlap_eff_pred", "overlap_eff_measured",
                "overlap_pred_reduction", "predicted_comm_s"):
        assert key in last, f"missing {key} in train_step() stats"
        assert np.isfinite(last[key])
    assert 0.0 <= last["overlap_eff_pred"] <= 1.0
    assert 0.0 <= last["overlap_eff_measured"] <= 1.0
    check("train_step() reports predicted vs measured overlap efficiency")
    return seq, ov


def reroute_check(seq, ov):
    """Kill a ring link mid-training: both trainers rewrite their
    schedules around it (detour hops) and must stay in lockstep."""
    for tr in (seq, ov):
        tr.tcfg.fault_mode = "reroute"

    def fault(i):
        if i == 1:
            seq.lofamo.kill_link(3, 4)
            ov.lofamo.kill_link(3, 4)

    ms = seq.train(4, fault_hook=fault)
    mo = ov.train(4, fault_hook=fault)
    assert any("rerouted collectives" in e for e in seq.events)
    assert any("rerouted collectives" in e for e in ov.events)
    assert ov.apex_schedules["rs"].max_hops == 7  # the long way around
    for a, b in zip(ms, mo):
        assert a["loss"] == b["loss"], (a["loss"], b["loss"])
    assert_same_params(seq, ov, "post-reroute divergence")
    check("overlap engine survives link-fault reroute, still bitwise")


def main() -> None:
    assert jax.device_count() == 8, jax.device_count()
    with tempfile.TemporaryDirectory() as td:
        seq, ov = equivalence_check(td)
        reroute_check(seq, ov)
    print("ALL OVERLAP CHECKS PASSED")


if __name__ == "__main__":
    main()
