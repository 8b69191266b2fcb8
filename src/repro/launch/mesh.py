"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax use;
tests and benches see the single real device.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import MeshConfig


def _mk(shape, axes) -> Mesh:
    n = math.prod(shape)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def mesh_config(*, multi_pod: bool = False) -> MeshConfig:
    return MeshConfig(data=16, model=16, pods=2 if multi_pod else 1)


def make_mesh_from_config(mesh_cfg: MeshConfig) -> Mesh:
    return _mk(mesh_cfg.shape, mesh_cfg.axis_names)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many local devices exist (tests / examples)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return _mk((data, model), ("data", "model"))
