"""The ``"dense"`` frequency operator — the paper's materialised Ω, wrapped.

``apply`` is exactly the pre-refactor ``x @ w`` (same draw, same dtype, same
XLA graph), so selecting ``freq_op="dense"`` through the registry is bitwise
identical to the historical dense-matrix path on every backend — asserted by
``tests/test_freq_ops.py``.  What changes is the bookkeeping: the operator
knows its ``spec()`` (PRNG key + hyperparams), so checkpoints and cross-host
broadcast can carry O(1) bytes and redraw the matrix instead of shipping it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import frequencies as freq_mod
from repro.core.freq_ops.base import (
    FreqOpSpec,
    FrequencyOperator,
    register_freq_op,
    try_spec,
)


class DenseOperator(FrequencyOperator):
    """Ω held as a materialised ``(n, m)`` matrix (column frequencies)."""

    name = "dense"

    def __init__(self, w: jax.Array, spec: FreqOpSpec | None = None):
        self.w = w
        self._spec = spec

    @property
    def n(self) -> int:
        return self.w.shape[0]

    @property
    def m(self) -> int:
        return self.w.shape[1]

    # HIGHEST: on the TPU a default-precision f32 matmul is one bf16 pass,
    # ~0.2 rad of error on phases of tens of radians (same result on CPU).
    def apply(self, x: jax.Array) -> jax.Array:
        return jnp.matmul(x, self.w, precision=jax.lax.Precision.HIGHEST)

    def adjoint(self, v: jax.Array) -> jax.Array:
        return jnp.matmul(v, self.w.T, precision=jax.lax.Precision.HIGHEST)

    def materialize(self) -> jax.Array:
        return self.w

    def col_norms(self) -> jax.Array:
        return jnp.linalg.norm(self.w, axis=0)

    def col_sq_norms(self) -> jax.Array:
        return jnp.sum(self.w * self.w, axis=0)

    def spec(self) -> FreqOpSpec:
        if self._spec is None:
            raise ValueError(
                "this DenseOperator has no spec: it wraps a raw matrix, or "
                "was rebuilt from its leaves (inside jit/shard_map, by a "
                "tree_map, or as a fleet tenant's slice), and the spec is "
                "not pytree data; build it with freq_ops.make_operator("
                "'dense', key, m, n, sigma2), or read the recipe from "
                "SketchEngine.spec() / FleetEngine.specs"
            )
        return self._spec


# No aux data: the leaf's shape is all that shapes the program, and the spec
# stays out of every jit cache key (see ``FrequencyOperator``).
def _flatten(op: DenseOperator):
    return (op.w,), ()


def _unflatten(aux, children):
    return DenseOperator(children[0])


jax.tree_util.register_pytree_node(DenseOperator, _flatten, _unflatten)


@register_freq_op("dense")
def build_dense(
    key: jax.Array,
    m: int,
    n: int,
    sigma2,
    *,
    dist: str = "adapted_radius",
    dtype=jnp.float32,
) -> DenseOperator:
    """Draw the paper's dense Ω (``frequencies.draw_frequencies``) + its spec."""
    w = freq_mod.draw_frequencies(key, m, n, sigma2, dist, dtype=jnp.dtype(dtype))
    return DenseOperator(w, try_spec("dense", key, m, n, sigma2, dist, dtype))
