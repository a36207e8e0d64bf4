"""The ``"structured"`` frequency operator — stacked HD-Rademacher blocks.

Instead of drawing ``m`` dense directions, each block of ``d = 2^ceil(log2 n)``
frequencies uses the SRHT/SORF-style fast transform

    B = c·H D_2 · c·H D_1 · c·H D_0        (c = d^{-1/2}, D_i Rademacher ±1)

— a product of orthogonal factors, so B is *exactly* orthogonal and its rows
are unit-norm quasi-uniform directions; ``ceil(m/d)`` independent blocks are
stacked for ``m > d``.  The radial part is the paper's **adapted-radius**
distribution (``frequencies.draw_radii``), with the rescaling that makes the
radial law exact despite the zero-padding ``n -> d``: a unit row of B
restricted to the first ``n`` coordinates has norm ``< 1``, so each drawn
radius ``rho_j`` is divided by that restricted norm — the realised ``||ω_j||``
then equals ``rho_j`` *exactly* (and ``col_norms()`` is just the stored rho).

Costs per point: ``apply`` is 3 Walsh–Hadamard transforms per block —
``O(m·sqrt(d))`` flops with the Kronecker-factored WHT
(``kernels.freq_transform.fwht``) vs the dense ``O(n·m)`` matvec; the operator
state is ``O(m)`` floats (signs + radii) vs the dense ``O(n·m)`` matrix, and
its ``spec()`` is O(1).  The fused Pallas path is
``kernels.freq_transform.structured_sketch_kernel`` (dispatched by
``kernels/ops.py``); autodiff through ``apply``/``adjoint`` is plain jnp, so
decoders optimise through the fast transform unchanged.
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
from repro.kernels import freq_transform as ft


# Minimum WHT block width.  At small n the HD orbit contains few distinct
# directions (at d = 4 ~a dozen); embedding n into a wider block and
# restricting the rows back to the first n coordinates (with the radial
# rescaling below keeping the radius law exact) recovers the angular
# diversity of dense draws at negligible cost.
_MIN_BLOCK = 32


def block_dim(n: int) -> int:
    """The WHT block width: next power of two >= n, floored at ``_MIN_BLOCK``."""
    return max(1 << max(0, int(n) - 1).bit_length(), _MIN_BLOCK)


def _pad_last(x: jax.Array, size: int) -> jax.Array:
    pad = size - x.shape[-1]
    if pad == 0:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (pad,), x.dtype)], axis=-1
    )


class StructuredOperator(FrequencyOperator):
    """Stacked fast-transform blocks with adapted-radius radial rescaling.

    Leaves: ``diags (nblocks, 3, d)`` Rademacher signs, ``radii (nblocks, d)``
    rescaled step sizes, ``rho (nblocks, d)`` the drawn target magnitudes
    (``col_norms``).  ``n``/``m`` are static (the block tail past ``m`` is
    sliced off).
    """

    name = "structured"

    def __init__(self, diags, radii, rho, n: int, m: int, spec=None):
        self.diags = diags
        self.radii = radii
        self.rho = rho
        self._n = n
        self._m = m
        self._spec = spec

    @property
    def n(self) -> int:
        return self._n

    @property
    def m(self) -> int:
        return self._m

    @property
    def d(self) -> int:
        return self.diags.shape[-1]

    @property
    def nblocks(self) -> int:
        return self.diags.shape[0]

    def apply(self, x: jax.Array) -> jax.Array:
        x = jnp.asarray(x, self.diags.dtype)
        xp = _pad_last(x, self.d)  # zero feature pad shifts no phases
        v = ft.hd_chain(xp[..., None, :], self.diags)  # (..., nblocks, d)
        y = v * self.radii
        return y.reshape(x.shape[:-1] + (self.nblocks * self.d,))[..., : self.m]

    def adjoint(self, v: jax.Array) -> jax.Array:
        v = jnp.asarray(v, self.diags.dtype)
        vp = _pad_last(v, self.nblocks * self.d)
        u = vp.reshape(v.shape[:-1] + (self.nblocks, self.d)) * self.radii
        # Transpose of the hd_chain: same symmetric H stages, diags reversed.
        d = self.d
        c = jnp.asarray(d, u.dtype) ** -0.5
        for s in (2, 1, 0):
            u = ft.fwht(u) * c * self.diags[..., s, :]
        return jnp.sum(u, axis=-2)[..., : self.n]

    def materialize(self) -> jax.Array:
        return self.apply(jnp.eye(self.n, dtype=self.diags.dtype))

    def col_norms(self) -> jax.Array:
        return self.rho.reshape(-1)[: self.m]

    def spec(self) -> FreqOpSpec:
        if self._spec is None:
            raise ValueError(
                "this structured operator has no spec: it was built under "
                "jit/vmap tracing, where no concrete key exists, or rebuilt "
                "from its leaves (inside jit/shard_map, by a tree_map, or as "
                "a fleet tenant's slice), and the spec is not pytree data; "
                "read the recipe from SketchEngine.spec() / FleetEngine.specs"
            )
        return self._spec


# Only n and m shape the traced program; the spec stays out of the aux data,
# and so out of every jit cache key (see ``FrequencyOperator``).
def _flatten(op: StructuredOperator):
    return (op.diags, op.radii, op.rho), (op._n, op._m)


def _unflatten(aux, children):
    return StructuredOperator(*children, n=aux[0], m=aux[1])


jax.tree_util.register_pytree_node(StructuredOperator, _flatten, _unflatten)


@register_freq_op("structured")
def build_structured(
    key: jax.Array,
    m: int,
    n: int,
    sigma2,
    *,
    dist: str = "adapted_radius",
    dtype=jnp.float32,
) -> StructuredOperator:
    """Draw signs + adapted radii and compute the restricted-norm rescaling."""
    dtype = jnp.dtype(dtype)
    d = block_dim(n)
    nblocks = -(-int(m) // d)
    k_diag, k_rad = jax.random.split(key)
    diags = jax.random.rademacher(k_diag, (nblocks, 3, d), dtype)
    rho = freq_mod.draw_radii(
        k_rad, nblocks * d, n, sigma2, dist, dtype=dtype
    ).reshape(nblocks, d)
    # Restricted row norms of B: ||row_j restricted to the first n coords||.
    # One batched chain over the n basis vectors — O(n·m·sqrt(d)), once.
    basis = jnp.eye(d, dtype=dtype)[:n]  # (n, d): e_i zero-padded
    cols = ft.hd_chain(basis[:, None, :], diags)  # (n, nblocks, d)
    restricted = jnp.sqrt(jnp.sum(cols * cols, axis=0))  # (nblocks, d)
    radii = rho / jnp.maximum(restricted, 1e-6)
    spec = try_spec("structured", key, m, n, sigma2, dist, dtype)
    return StructuredOperator(diags, radii, rho, int(n), int(m), spec)
