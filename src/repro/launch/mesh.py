"""Mesh construction — the one place the repo builds a ``jax.sharding.Mesh``.

Functions, not module-level constants: importing this module never touches
jax device state.  Callers needing 512 placeholder devices must set XLA_FLAGS
before any jax import (see launch/dryrun.py's first two lines).

Every axis is ``AxisType.Auto``: shardings are propagated by the compiler,
so ``with_sharding_constraint(x, PartitionSpec(...))`` and ``shard_map`` work
inside plain ``jax.jit`` without entering a ``jax.set_mesh`` context
(``jax.make_mesh`` alone defaults to ``Explicit`` axes).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], devices=None
) -> jax.sharding.Mesh:
    """``jax.make_mesh(shape, axes)`` with every axis ``Auto``."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1, data: int | None = None) -> jax.sharding.Mesh:
    """``(data, model)`` mesh over whatever devices exist (tests / examples /
    the chip): ``data`` defaults to every device not used by ``model``."""
    n = len(jax.devices())
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))
