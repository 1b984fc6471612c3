"""manual_sp (hand-SPMD Megatron-SP layer stack) numerics — subprocess
check on 8 forced host devices (launched by tests/test_manual_sp.py)."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import api  # noqa: E402
from repro.parallel import sharding  # noqa: E402


def main() -> None:
    assert jax.device_count() == 8
    cfg = dataclasses.replace(get_reduced("deepseek-7b"), n_heads=4,
                              n_kv_heads=4, d_ff=128, dtype=jnp.float32,
                              attn_dtype="f32")  # exact parity in f32
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                   jnp.int32)}
    m0 = api.get_model(cfg)
    p = m0.init(jax.random.key(0))
    l0, g0 = jax.value_and_grad(lambda p: m0.train_loss(p, batch))(p)

    mesh = make_mesh((2, 4), ("data", "model"))
    m2 = api.get_model(dataclasses.replace(cfg, tp_activations="manual_sp"))
    sharding.set_runtime_mesh(mesh)
    try:
        with mesh:
            l2, g2 = jax.jit(jax.value_and_grad(
                lambda p: m2.train_loss(p, batch)))(p)
    finally:
        sharding.set_runtime_mesh(None)
    np.testing.assert_allclose(float(l0), float(l2), rtol=2e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-3, atol=5e-5)
    # GQA + qkv-bias flavour
    cfgq = dataclasses.replace(get_reduced("qwen2-0.5b"), n_heads=8,
                               n_kv_heads=4, d_ff=128, dtype=jnp.float32)
    mq = api.get_model(cfgq)
    pq = mq.init(jax.random.key(1))
    lq = mq.train_loss(pq, batch)
    mq2 = api.get_model(dataclasses.replace(cfgq,
                                            tp_activations="manual_sp"))
    sharding.set_runtime_mesh(mesh)
    try:
        with mesh:
            lq2 = jax.jit(lambda p: mq2.train_loss(p, batch))(pq)
    finally:
        sharding.set_runtime_mesh(None)
    np.testing.assert_allclose(float(lq), float(lq2), rtol=2e-5)
    print("ALL MANUAL_SP CHECKS PASSED")


if __name__ == "__main__":
    main()
