"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.

Target hardware: TPU v5e — 197 bf16 TFLOP/s, 819 GB/s HBM, 16 GB HBM per
chip, ~50 GB/s/link ICI (constants live in repro.profiling.hw).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.distributed.context import DistContext


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]
              ) -> jax.sharding.Mesh:
    """Arbitrary mesh (tests / smoke runs on few devices)."""
    return _auto_mesh(shape, axes)


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]
               ) -> jax.sharding.Mesh:
    # jax.make_mesh defaults to Explicit axes, under which
    # with_sharding_constraint refuses every axis; the model code places
    # its activations with constraints, so all axes are Auto
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def context_for_mesh(mesh: Optional[jax.sharding.Mesh],
                     use_ep: bool = True,
                     flash_decode: bool = False) -> DistContext:
    """DistContext with batch axes = every axis except 'model'."""
    if mesh is None:
        return DistContext(mesh=None, batch_axes=("data",), use_ep=False)
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    return DistContext(mesh=mesh, batch_axes=batch_axes or ("data",),
                       model_axis="model", use_ep=use_ep,
                       flash_decode=flash_decode)
