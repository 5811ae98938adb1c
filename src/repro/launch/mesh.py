"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax use,
and smoke tests must keep seeing 1 device.

Every axis is ``AxisType.Auto``: ``jax.make_mesh`` would otherwise build
``Explicit`` axes, and under ``jax.set_mesh`` (``runtime.sharding.use_mesh``)
every jit would then be typed by explicit sharding, which the model code
(gathers, ``jnp.repeat``, the pipeline) does not carry.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis of type ``Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    return make_mesh((data, model), ("data", "model"))
