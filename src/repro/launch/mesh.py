"""Production mesh definitions.

The production target is a TPU v5e pod: 256 chips in a 16x16 ICI torus, and
two such pods linked over the "pod" axis for the multi-pod configuration —
the same 3D-torus shape APEnet+ builds out of 6-link FPGA NICs (Z = pod,
Y = data, X = model).

Everything here is a FUNCTION (never module-level device state) so importing
this module does not initialise the JAX backend — critical because the
dry-run must set XLA_FLAGS before first jax use, while smoke tests must see
the real single-CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.core.topology import Torus

POD_AXES = ("data", "model")
MULTIPOD_AXES = ("pod", "data", "model")


def make_mesh(shape, axes, *, devices=None) -> jax.sharding.Mesh:
    """jax.make_mesh with explicit Auto axis types (GSPMD sharding).

    Uses the first prod(shape) devices when more are available (the dry-run
    forces 512 host devices but the single-pod mesh needs only 256)."""
    import numpy as np
    need = int(np.prod(tuple(shape)))
    if devices is None and len(jax.devices()) > need:
        devices = jax.devices()[:need]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The graded production mesh: 16x16 single pod / 2x16x16 multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = MULTIPOD_AXES if multi_pod else POD_AXES
    return make_mesh(shape, axes)


def production_torus(*, multi_pod: bool = False) -> Torus:
    """Topology-model twin of the production mesh (LO|FA|MO, routing math).

    Rank i of the torus is device i of the mesh (both row-major)."""
    return Torus((2, 16, 16) if multi_pod else (16, 16))


def host_test_mesh(shape=(8,), axes=("x",)) -> jax.sharding.Mesh:
    """Small mesh over forced host devices (tests / demos only)."""
    return make_mesh(shape, axes)
