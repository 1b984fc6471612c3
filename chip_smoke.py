"""Chip smoke run: the paged serving engine and the trainer on a TPU.

  python chip_smoke.py             # one chip: serve, kernel checks, train
  python chip_smoke.py --chips 4   # four chips: apex vs GSPMD data
                                   # parallelism, fabric ring all-reduce
                                   # vs lax.psum

Everything runs qwen2-0.5b at full width (24 layers, d_model 896, 14 heads
over 2 KV heads, vocab 151936) from a seeded random init, in this one
process.  Phases on one chip:

  * serve   — PagedLM + Engine answer 8 requests (prompts of 64-512
              tokens, 32 new tokens each); the compiled decode step must
              hold the Pallas paged-attention kernel (``tpu_custom_call``);
  * kernels — every Pallas kernel against its kernels/ref.py oracle on
              the chip, at the serve phase's decode shapes and at the
              scan families' real head widths;
  * train   — a few Trainer steps (comm="single"); losses must be finite.

The timings printed are those of one cold smoke run, compile included:
smoke timings, not benchmark metrics.  Any failed phase exits non-zero,
and so does a run that finds no TPU — there is no CPU fallback.  The last
line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-0.5b"
SEED = 0
BF16_TOL = dict(rtol=6e-2, atol=6e-2)    # the kernel tests' bf16 tolerance
LOSS_ATOL = 2e-2                         # apex vs GSPMD loss agreement


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class PhaseClock:
    """Wall seconds of a phase and the XLA compile seconds inside it."""

    def __init__(self) -> None:
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.compile_s = 0.0

        def listen(event, duration, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.compile_s += duration

        jax.monitoring.register_event_duration_secs_listener(listen)

    def run(self, name: str, fn, *args):
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args)
        log(f"phase {name}: wall {time.perf_counter() - t0:.1f} s, "
            f"compile {self.compile_s - c0:.1f} s (smoke timing)")
        return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def serve_phase(cfg, *, n_requests=8, prompt_lens=(64, 512), max_new=32,
                page_tokens=16):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import api
    from repro.serving.engine import Engine, PagedLM, Request

    params = jax.jit(api.get_model(cfg).init)(jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_requests)
    lens[0], lens[-1] = prompt_lens               # cover both ends
    lm = PagedLM(cfg, params, max_batch=n_requests,
                 max_seq=prompt_lens[1] + max_new, page_tokens=page_tokens)
    eng = Engine(lm)
    for rid, n in enumerate(lens):
        eng.submit(Request(rid=rid, max_new_tokens=max_new,
                           prompt=rng.integers(0, cfg.vocab, size=int(n))
                           .astype(np.int32)))
    eng.run_to_completion()
    done = [r for r in eng.finished if len(r.out_tokens) == max_new]
    tokens = sum(len(r.out_tokens) for r in eng.finished)
    log(f"serve: {len(done)}/{n_requests} requests finished with "
        f"{max_new} tokens each; prompt lens {sorted(lens.tolist())}; "
        f"{tokens} tokens, {eng.steps} decode steps, batch {n_requests}")
    assert len(done) == n_requests, "not every request finished"

    # the program decode_batch runs, compiled for the same arguments
    B = lm.max_batch
    decode_text = lm._decode.lower(
        lm.params, jnp.zeros((B, 1), jnp.int32), lm.k_pool, lm.v_pool,
        jnp.asarray(lm.page_table), jnp.asarray(lm.seq_lens),
        jnp.ones((B,), bool)).compile().as_text()
    has_kernel = "tpu_custom_call" in decode_text
    log(f"serve: tpu_custom_call in the compiled decode step: {has_kernel}")
    assert has_kernel, "decode step does not run the Pallas kernel"
    return lm


def _compare(name, got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite kernel output"
    err = float(np.abs(got - want).max())
    bound = BF16_TOL["atol"] + BF16_TOL["rtol"] * np.abs(want)
    ok = bool((np.abs(got - want) <= bound).all())
    log(f"kernel {name}: shape {got.shape}, max |kernel - ref| = {err:.3e} "
        f"(bf16 tolerance atol={BF16_TOL['atol']} rtol={BF16_TOL['rtol']}): "
        f"{'ok' if ok else 'FAIL'}")
    return ok


def kernel_phase(cfg, lm):
    """Each Pallas kernel vs its oracle, on the chip, in bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED + 1)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)

    def check(name, op, oracle, *args):
        kernel = jax.jit(functools.partial(op, impl="pallas"))
        return _compare(name, kernel(*args), jax.jit(oracle)(*args))

    ok = True
    # paged attention at the serve phase's decode shapes and page pool
    B, H, D = lm.max_batch, cfg.n_heads, cfg.resolved_head_dim
    q = normal(B, H, D)
    kp = normal(lm.n_pages, lm.page, cfg.n_kv_heads, D)
    vp = normal(lm.n_pages, lm.page, cfg.n_kv_heads, D)
    mp = lm.pages_per_seq
    pt = jnp.asarray(rng.permutation(lm.n_pages)[:B * mp].reshape(B, mp)
                     .astype(np.int32))
    sl = jnp.asarray(rng.integers(1, mp * lm.page + 1, size=B)
                     .astype(np.int32))
    ok &= check("paged_attention", ops.paged_attention, ref.paged_attention,
                q, kp, vp, pt, sl)
    # flash attention at the model's head layout
    S = 1024
    qf = normal(1, H, S, D)
    kf = normal(1, cfg.n_kv_heads, S, D)
    vf = normal(1, cfg.n_kv_heads, S, D)
    ok &= check("flash_attention", ops.flash_attention, ref.mha_attention,
                qf, kf, vf)
    # mamba2 scan at zamba2-1.2b head widths
    z = configs.get_config("zamba2-1.2b")
    Hm = z.ssm.expand * z.d_model // z.ssm.head_dim
    dh, ds, S = z.ssm.head_dim, z.ssm.d_state, 512
    m_args = (normal(2, S, Hm, dh),
              jnp.asarray(np.abs(rng.normal(size=(2, S, Hm))) * 0.1 + 0.01,
                          jnp.bfloat16),
              jnp.asarray(-np.abs(rng.normal(size=(Hm,))) - 0.1, jnp.float32),
              normal(2, S, ds, scale=ds ** -0.5),
              normal(2, S, ds, scale=ds ** -0.5),
              jnp.asarray(rng.normal(size=(Hm,)), jnp.float32))
    ok &= check("mamba2_scan", ops.mamba2_scan, ref.mamba2_scan, *m_args)
    # rwkv6 scan at rwkv6-1.6b head widths
    r6 = configs.get_config("rwkv6-1.6b")
    Hr, dh, S = r6.n_heads, r6.resolved_head_dim, 256
    w = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(2, S, Hr, dh)))) * 0.5
                    + 0.5, jnp.bfloat16)
    r_args = (normal(2, S, Hr, dh), normal(2, S, Hr, dh, scale=0.3),
              normal(2, S, Hr, dh), w,
              jnp.asarray(rng.normal(size=(Hr, dh)), jnp.float32))
    ok &= check("rwkv6_scan", ops.rwkv6_scan, ref.rwkv6_scan, *r_args)
    assert ok, "a Pallas kernel disagrees with its reference"


def train_phase(cfg, *, steps=3, batch=4, seq_len=512):
    import numpy as np

    from repro.optim import AdamWConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(
        ckpt_every=0, opt=AdamWConfig(warmup_steps=1, total_steps=steps),
        batch=batch, seq_len=seq_len, comm="single", seed=SEED)
    tr = Trainer(cfg, tcfg)
    losses = [m["loss"] for m in tr.train(steps)]
    log(f"train: comm=single batch {batch} x seq {seq_len}, "
        f"{tr.n_params:,} params, losses {losses}")
    assert np.isfinite(losses).all(), "non-finite training loss"


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def dp_phase(cfg, devices, *, steps=3, batch=8, seq_len=256):
    """apex (fabric ring collectives in shard_map) vs GSPMD on a (n,) data
    mesh: same seed, same batches, same optimizer (clipping off, which
    the apex ZeRO-1 update does not do)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.optim import AdamWConfig
    from repro.runtime.trainer import Trainer, TrainerConfig

    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    losses = {}
    for comm in ("apex", "gspmd"):
        tcfg = TrainerConfig(
            ckpt_every=0,       # no checkpoint is written
            opt=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=steps,
                            clip_norm=float("inf")),
            batch=batch, seq_len=seq_len, comm=comm, seed=SEED)
        tr = Trainer(cfg, tcfg, mesh=mesh)
        losses[comm] = [m["loss"] for m in tr.train(steps)]
        placed = tr._place_batch(tr.data.next_batch())["tokens"]
        shard_devs = {s.device for s in placed.addressable_shards}
        rows = {s.data.shape[0] for s in placed.addressable_shards}
        log(f"dp {comm}: batch {batch} x seq {seq_len} over {n} devices, "
            f"batch shards on {len(shard_devs)} devices ({rows} rows each); "
            f"losses {losses[comm]}")
        assert shard_devs == set(devices) and rows == {batch // n}, \
            f"{comm}: batch not spread over all {n} devices"
        m_devs = {d for leaf in jax.tree.leaves(tr.opt_state["m"])
                  for d in leaf.sharding.device_set}
        assert m_devs == set(devices), f"{comm}: optimizer state not spread"
    gap = float(np.abs(np.subtract(losses["apex"], losses["gspmd"])).max())
    log(f"dp: max |apex - gspmd| loss = {gap:.3e} (tolerance {LOSS_ATOL})")
    assert np.isfinite(losses["apex"]).all() and gap <= LOSS_ATOL, \
        "apex and GSPMD losses disagree"


def ring_phase(devices, *, payload=1 << 20):
    """The fabric's bidirectional ring all-reduce vs lax.psum."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import collectives as C

    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("x",))
    x = np.random.default_rng(SEED).normal(size=(n, payload)) \
        .astype(np.float32)
    ring = np.asarray(C.make_stacked_all_reduce(mesh, ("x",))(x))
    psum = np.asarray(jax.jit(jax.shard_map(
        lambda v: jax.lax.psum(v, "x"), mesh=mesh, in_specs=(P("x"),),
        out_specs=P("x"), check_vma=False))(x))
    err = float(np.abs(ring - psum).max())
    log(f"ring all-reduce over {n} devices, {payload} f32 per rank: "
        f"max |ring - psum| = {err:.3e} (tolerance 1e-4)")
    assert err <= 1e-4, "ring all-reduce disagrees with lax.psum"


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip data-parallel phases")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX platform is {platform!r}",
              file=sys.stderr)
        return 1

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    count = len(devices)
    devices = devices[:args.chips]
    log(f"cache dir {enable_compile_cache()}")
    cfg = configs.get_config(ARCH)
    log(f"device {devices[0].device_kind} x {len(devices)}; model {cfg.name} "
        f"full width: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv heads, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}")
    clock = PhaseClock()
    if args.chips == 1:
        lm = clock.run("serve", serve_phase, cfg)
        clock.run("kernels", kernel_phase, cfg, lm)
        del lm
        clock.run("train", train_phase, cfg)
    else:
        clock.run("dp", dp_phase, cfg, devices)
        clock.run("ring", ring_phase, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
