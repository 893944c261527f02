"""Mesh construction (production + test/CPU).

Functions (not module constants) so importing never touches jax device
state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod: 2x16x16 =
512 chips with a leading 'pod' pure-DP axis (gradient all-reduce over DCN).

All mesh construction in the repo goes through ``make_mesh``, which makes
every axis ``AxisType.Auto`` (GSPMD propagation, as the sharding rules in
``distributed/sharding.py`` assume).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax


def make_mesh(shape: Sequence[int], axis_names: Sequence[str],
              devices=None) -> jax.sharding.Mesh:
    """jax.make_mesh with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axis_names), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """All local devices on the 'data' axis (CPU smoke runs / fake devices)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def single_device_mesh():
    """Degenerate 1x1 mesh: the sharded code path with single-device numerics.

    The ProgressiveTrainer always runs under a mesh; this is the mesh that
    makes it bit-identical to an unsharded run (used by ``loop.train`` and
    single-device baselines in tests).
    """
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


def make_train_mesh(spec: str = "single"):
    """Resolve a CLI/test mesh spec to a Mesh.

    'single'        1x1 over the first device (default; exact single-device)
    'host'          all local devices on 'data' (pure FSDP/DP)
    'prod'          the 256-chip production mesh
    'prod-multipod' the 512-chip multi-pod mesh
    'AxB'           explicit (data, model) shape, e.g. '4x2' on 8 devices
    """
    if spec == "single":
        return single_device_mesh()
    if spec == "host":
        return make_host_mesh()
    if spec == "prod":
        return make_production_mesh()
    if spec == "prod-multipod":
        return make_production_mesh(multi_pod=True)
    if "x" in spec:
        shape = tuple(int(s) for s in spec.split("x"))
        names = ("data", "model") if len(shape) == 2 else \
            ("pod", "data", "model")
        if len(shape) != len(names):
            raise ValueError(f"mesh spec {spec!r}: need 2 or 3 axes")
        return make_mesh(shape, names)
    raise ValueError(f"unknown mesh spec {spec!r}")
