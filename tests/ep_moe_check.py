"""EP all-to-all MoE vs dense per-token reference — subprocess check
(needs 8 forced host devices; launched by tests/test_moe_ep.py).

With an ample capacity factor nothing drops, so both the global sort-based
dispatch and the shard_map EP dispatch must equal the dense reference
y_t = sum_k p_k FFN_{e_k}(x_t) computed directly per token.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import dataclasses  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import moe  # noqa: E402
from repro.models.common import ArchCfg, MoeCfg  # noqa: E402
from repro.parallel import sharding  # noqa: E402


def dense_reference(cfg, p, x):
    """y_t = sum_k p_k FFN_{e_k}(x_t), computed with every expert on every
    token (no capacity, no dispatch)."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    g = jax.nn.silu(jnp.einsum("td,edf->tef", xt,
                               p["w_gate"]).astype(jnp.float32))
    u = jnp.einsum("td,edf->tef", xt, p["w_up"]).astype(jnp.float32)
    h = (g * u).astype(x.dtype)
    every = jnp.einsum("tef,efd->ted", h, p["w_down"])   # (T, E, d)
    sel = jnp.take_along_axis(every, top_e[:, :, None], axis=1)
    y = (sel.astype(jnp.float32) * top_p[:, :, None]).sum(1)
    return y.reshape(B, S, d).astype(x.dtype)


def main() -> None:
    assert jax.device_count() == 8
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(
        configs.get_config("olmoe-1b-7b").reduced(),
        moe=MoeCfg(n_experts=8, top_k=2, d_expert=32, capacity_factor=8.0),
        d_model=64, dtype=jnp.float32, moe_impl="ep_a2a")
    p = moe.init_moe(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)) * 0.3, jnp.float32)

    want = dense_reference(cfg, p, x)
    y_global, aux_g = moe.apply_moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y_global), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    print("[ep_moe] global dispatch == dense reference")

    sharding.set_runtime_mesh(mesh)
    try:
        with mesh:
            y_ep, aux_e = jax.jit(
                lambda p, x: moe.apply_moe_ep(cfg, p, x))(p, x)
    finally:
        sharding.set_runtime_mesh(None)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_e), float(aux_g), rtol=1e-3)
    print("[ep_moe] shard_map EP all-to-all == dense reference; aux matches")

    # drop regime: tight capacity must still run and stay finite
    cfg2 = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=0.5))
    sharding.set_runtime_mesh(mesh)
    try:
        with mesh:
            y2, _ = jax.jit(
                lambda p, x: moe.apply_moe_ep(cfg2, p, x))(p, x)
    finally:
        sharding.set_runtime_mesh(None)
    assert np.isfinite(np.asarray(y2)).all()
    print("[ep_moe] drop regime finite")
    print("ALL EP MOE CHECKS PASSED")


if __name__ == "__main__":
    main()
