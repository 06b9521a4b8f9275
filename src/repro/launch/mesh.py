"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first use — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any init).

Axes:
  * ``data``  — pure data parallelism (gradient all-reduce tier; intra-pod)
  * ``model`` — tensor parallelism (heads / ff / vocab sharding; ICI)
  * ``pod``   — the cross-pod DCN tier (multi-pod only); this is the
    oversubscribed fabric tier from the paper's study, and the axis the
    int8 gradient compressor targets.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.configs.base import MeshConfig, MULTI_POD_MESH, SINGLE_POD_MESH


def _auto(n: int) -> tuple:
    """Auto axis types: the sharding rules here are constraints the
    compiler propagates, not explicit per-array types."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(cfg: MeshConfig) -> jax.sharding.Mesh:
    return jax.make_mesh(tuple(cfg.shape), tuple(cfg.axes),
                         axis_types=_auto(len(cfg.axes)))


def make_local_mesh(model_parallel: Optional[int] = None
                    ) -> jax.sharding.Mesh:
    """Smoke/test mesh over whatever devices exist (usually 1 CPU)."""
    n = len(jax.devices())
    mp = model_parallel or 1
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=_auto(2))


def mesh_config_for(mesh: jax.sharding.Mesh) -> MeshConfig:
    return MeshConfig(tuple(mesh.devices.shape), tuple(mesh.axis_names))


def batch_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def dp_size(mesh: jax.sharding.Mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
