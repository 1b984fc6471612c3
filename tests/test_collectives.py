"""Collectives + RDMA tests.

Numeric multi-device checks run in one subprocess (8 forced host devices) so
that the main pytest process keeps the default single-device view — the
dry-run explicitly forbids setting the device-count flag globally.
"""
import os
import subprocess
import sys

import pytest

from repro.core import collectives as C
from repro.core import rdma
from repro.core.topology import Torus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_multidevice_numerics():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "multidevice_checks.py")],
        capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    assert "ALL MULTIDEVICE CHECKS PASSED" in proc.stdout


def test_ring_perms():
    perm = C._ring_perms(4, +1)
    assert perm == [(0, 1), (1, 2), (2, 3), (3, 0)]
    perm = C._ring_perms(4, -1)
    assert perm == [(0, 3), (1, 0), (2, 1), (3, 2)]


def test_flatten_pad():
    import jax.numpy as jnp
    flat, chunk = C._flatten_pad(jnp.ones((3, 5)), 4)
    assert flat.shape == (16,) and chunk == 4
    flat, chunk = C._flatten_pad(jnp.ones((8,)), 4)
    assert flat.shape == (8,) and chunk == 2


# ---------------------------------------------------------------------------
# RdmaEndpoint host-side model (registration/TLB/dual-DMA cost model)
# ---------------------------------------------------------------------------

def make_ep(**kw):
    return rdma.RdmaEndpoint(Torus((4, 4)), rank=0, **kw)


def test_registration_lifecycle():
    ep = make_ep()
    r = ep.register(10 * 4096)
    cold = ep.translate_region(r)       # all misses
    warm = ep.translate_region(r)       # all hits
    assert warm < cold / 5
    ep.deregister(r)
    with pytest.raises(KeyError):
        ep.translate_region(r)


def test_deregister_invalidates_tlb():
    ep = make_ep()
    r1 = ep.register(4 * 4096)
    ep.translate_region(r1)
    hits_before = ep.tlb.stats.hits
    ep.deregister(r1)
    r2 = ep.register(4 * 4096)
    # new region occupies fresh vaddrs; old entries were shot down
    ep.translate_region(r2)
    assert ep.tlb.stats.hits == hits_before


def test_dual_dma_fig1_claims():
    """§2.1: single-engine efficiency ~50%; dual-engine ~40% time cut."""
    ep = make_ep()
    nbytes = 1 << 20
    t1 = ep.transfer_time(nbytes, engines=1)
    t2 = ep.transfer_time(nbytes, engines=2)
    reduction = 1.0 - t2 / t1
    assert reduction == pytest.approx(0.40, abs=0.03)
    # single-engine effective bandwidth ~50% of the interface's
    eff1 = (nbytes / t1) / ep.net.host_if.effective_bandwidth
    assert eff1 == pytest.approx(0.50, abs=0.05)
    # a third engine gains nothing once the gap is hidden
    t3 = ep.transfer_time(nbytes, engines=3)
    assert t3 == pytest.approx(t2, rel=1e-6)


def test_put_time_monotone_in_hops_and_size():
    ep = make_ep()
    r = ep.register(1 << 20)
    ep.translate_region(r)  # warm the TLB
    t_near = ep.put_time(1, 4096, r)
    t_far = ep.put_time(5, 4096, r)     # rank 5 = (1,1): 2 hops
    assert t_far > t_near
    assert ep.put_time(1, 1 << 20, r) > t_near
