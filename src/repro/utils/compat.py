"""``shard_map`` / ``pvary`` entry points.

Every module in this repo that needs ``shard_map`` goes through this module
(never ``jax.shard_map`` directly), so the keyword conventions live in one
place: ``axis_names`` (the manual axes) and ``check_vma`` are forwarded only
when given, and ``pvary`` accepts a single axis name or a sequence.
"""

from __future__ import annotations

from typing import Callable

import jax

__all__ = ["shard_map", "pvary"]


def shard_map(
    f: Callable,
    mesh,
    in_specs,
    out_specs,
    axis_names: set | frozenset | None = None,
    check_vma: bool | None = None,
):
    """``jax.shard_map``.

    ``axis_names``: mesh axes the body is *manual* over (None = all axes).
    ``check_vma``: varying-manual-axes checking (None = JAX's default).
    """
    kwargs = {}
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def pvary(x, axis_names) -> jax.Array:
    """Mark ``x`` as varying over manual ``axis_names`` (``jax.lax.pcast``).

    Scan carries inside ``shard_map`` must be cast explicitly under the
    varying-manual-axes type rules; an empty ``axis_names`` is the identity.
    """
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    axis_names = tuple(axis_names)
    if not axis_names:
        return x
    return jax.lax.pcast(x, axis_names, to="varying")
