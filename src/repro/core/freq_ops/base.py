"""The frequency-operator contract + registry — the sketch's third pluggable axis.

The sketch operator of the paper is "draw Ω ~ Lambda, compute exp(-i Ωᵀx)".
Historically Ω was a materialised dense ``(n, m)`` array threaded *by value*
through the whole stack — every kernel op, every decoder cost, every
cross-device broadcast and checkpoint carried O(n·m) bytes, and the sketch
family was not a degree of freedom.  This package makes Ω an object:

    op.apply(x)      # (..., n) -> (..., m)   Ωᵀx — the projection
    op.adjoint(v)    # (..., m) -> (..., n)   Ωv  — decoder gradients
    op.materialize() # (n, m)                 the dense matrix, on demand
    op.col_norms()   # (m,)                   ||ω_j|| (resolution radii)
    op.spec()        # FreqOpSpec             PRNG key + hyperparams, O(1)

mirroring the decoder registry (``core.decoders``) and the topology registry
(``core.topology``): operators register under a name, ``CKMConfig.freq_op``
selects one end-to-end, and new families (subsampled DFTs, learned
operators, …) are one ``@register_freq_op`` away.

Why ``spec()`` matters: the spec — a NamedTuple of plain Python scalars
(name, PRNG key words, ``m``, ``n``, ``sigma2``, ``dist``, ``dtype``) — fully
determines the operator, so engine state, checkpoints and cross-host
broadcast can carry ~O(1) bytes (``spec_wire_bytes``) and rebuild the
operator with :func:`from_spec` instead of shipping the O(n·m) matrix.

The spec is host bookkeeping on the eager operator object, recorded when
the operator is drawn; it is not pytree data.  A treedef holds only what shapes the traced
program, so operators that differ only in key or sigma^2 share every compiled
program, and an operator rebuilt from its leaves (inside ``jit`` or
``shard_map``, out of a ``tree_map``, a fleet tenant's slice) carries no spec:
its ``spec()`` raises, and ``SketchEngine.spec()`` / ``FleetEngine.specs``
hold the recipe.

Raw arrays: :func:`as_operator` wraps a raw ``(n, m)`` array in a ``"dense"``
operator (such a wrapper has no spec; ``spec()`` raises).  The sketch/engine
entry points still wrap silently for convenience, but the decoder helpers and
kernel wrappers closed their one-release deprecation window in PR 6 and now
raise ``TypeError`` on raw arrays — wrap explicitly at the boundary.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "FreqOpSpec",
    "FrequencyOperator",
    "FREQ_OPS",
    "register_freq_op",
    "get_freq_op",
    "available_freq_ops",
    "make_operator",
    "from_spec",
    "as_operator",
    "spec_wire_bytes",
]


class FreqOpSpec(NamedTuple):
    """Plain-scalar description from which an operator rebuilds exactly.

    ``key_data`` is the PRNG key's raw uint32 words (hashable, serialisable);
    everything else is a Python scalar/string, so a spec fits in a checkpoint
    manifest or a control-plane message at ~O(1) bytes (:func:`spec_wire_bytes`).
    """

    name: str
    key_data: tuple[int, ...]
    m: int
    n: int
    sigma2: float
    dist: str = "adapted_radius"
    dtype: str = "float32"


def spec_wire_bytes(spec: FreqOpSpec) -> int:
    """Serialized size of a spec: strings + 4B/key word + 3 int64 + 1 f64.

    The number the scaling guide compares against the ``4·n·m`` bytes of the
    dense matrix this spec replaces on the wire / in checkpoints.
    """
    return (
        len(spec.name.encode())
        + len(spec.dist.encode())
        + len(spec.dtype.encode())
        + 4 * len(spec.key_data)
        + 3 * 8  # m, n + a length/tag word
        + 8  # sigma2
    )


def key_data_tuple(key: jax.Array) -> tuple[int, ...]:
    """PRNG key (legacy uint32 or new typed) -> hashable uint32 words."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            key = jax.random.key_data(key)
    except (AttributeError, TypeError):  # pragma: no cover - old jax
        pass
    return tuple(int(v) for v in np.asarray(key).reshape(-1).tolist())


def try_spec(
    name: str, key, m: int, n: int, sigma2, dist: str, dtype
) -> FreqOpSpec | None:
    """The spec for a build, or ``None`` when built under tracing.

    Builders run eagerly in the pipeline (concrete key/sigma2 -> full spec),
    but ``ckm.fit`` is also legal inside ``jit``/``vmap`` (e.g. the
    per-head KV-cache compression), where the key and scale are tracers and
    no concrete spec exists — the operator still works; only ``spec()``
    raises.
    """
    if isinstance(key, jax.core.Tracer) or isinstance(sigma2, jax.core.Tracer):
        return None
    return FreqOpSpec(
        name=name,
        key_data=key_data_tuple(key),
        m=int(m),
        n=int(n),
        sigma2=float(sigma2),
        dist=dist,
        dtype=jnp.dtype(dtype).name,
    )


def key_from_data(key_data: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`key_data_tuple` (as a legacy uint32 key array)."""
    return jnp.asarray(key_data, jnp.uint32)


class FrequencyOperator:
    """Abstract linear frequency operator Ω: apply/adjoint/materialize/spec.

    Subclasses must be registered JAX pytrees (their array leaves flow through
    ``jit`` / ``scan`` / ``shard_map`` transparently; static hyperparameters
    that shape the program live in hashable aux data) and define ``name``,
    ``n``, ``m``.  The spec stays out of the aux data: the treedef is part of
    every ``jit`` cache key, so a spec there would retrace every jitted
    function for each new key or sigma^2.
    """

    name: str = "?"

    # -- shape -------------------------------------------------------------
    @property
    def n(self) -> int:
        raise NotImplementedError

    @property
    def m(self) -> int:
        raise NotImplementedError

    # -- linear algebra ----------------------------------------------------
    def apply(self, x: jax.Array) -> jax.Array:
        """``(..., n) -> (..., m)``: the projection ``Ωᵀx`` (sketch phases)."""
        raise NotImplementedError

    def adjoint(self, v: jax.Array) -> jax.Array:
        """``(..., m) -> (..., n)``: ``Ωv`` — decoder cost/score gradients."""
        raise NotImplementedError

    def materialize(self) -> jax.Array:
        """The dense ``(n, m)`` matrix (on demand — never carried by state)."""
        raise NotImplementedError

    def col_norms(self) -> jax.Array:
        """``(m,)`` frequency magnitudes ``||ω_j||`` (resolution radii)."""
        raise NotImplementedError

    def col_sq_norms(self) -> jax.Array:
        """``(m,)`` squared magnitudes (mean-shift bandwidth h²)."""
        return self.col_norms() ** 2

    # -- bookkeeping -------------------------------------------------------
    def spec(self) -> FreqOpSpec:
        """The O(1) rebuild recipe; raises ``ValueError`` for raw matrices
        and for operators rebuilt from their leaves, which carry none."""
        raise NotImplementedError

    def state_bytes(self) -> int:
        """Bytes of the operator's array leaves (what a by-value carry ships)."""
        return int(
            sum(
                np.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(self)
            )
        )

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"{type(self).__name__}(n={self.n}, m={self.m})"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

# name -> builder(key, m, n, sigma2, *, dist, dtype) -> FrequencyOperator
FREQ_OPS: dict[str, Callable] = {}


def register_freq_op(name: str) -> Callable:
    """Decorator: register an operator *builder* under ``name`` (unique)."""

    def deco(builder: Callable) -> Callable:
        if name in FREQ_OPS:
            raise ValueError(f"frequency operator {name!r} already registered")
        FREQ_OPS[name] = builder
        return builder

    return deco


def get_freq_op(name: str) -> Callable:
    """Look up a registered builder; raises with the available names."""
    try:
        return FREQ_OPS[name]
    except KeyError:
        raise KeyError(
            f"unknown frequency operator {name!r}; available: "
            f"{sorted(FREQ_OPS)}"
        ) from None


def available_freq_ops() -> list[str]:
    """Sorted names of all registered frequency operators."""
    return sorted(FREQ_OPS)


def make_operator(
    name: str,
    key: jax.Array,
    m: int,
    n: int,
    sigma2,
    *,
    dist: str = "adapted_radius",
    dtype=jnp.float32,
) -> FrequencyOperator:
    """Build a registered operator for ``m`` frequencies in R^n at scale
    ``sigma2`` (builders run outside ``jit`` — construction draws PRNG bits
    and records a concrete spec)."""
    return get_freq_op(name)(key, m, n, sigma2, dist=dist, dtype=dtype)


def from_spec(spec: FreqOpSpec) -> FrequencyOperator:
    """Rebuild an operator exactly from its spec (same key -> same leaves)."""
    return make_operator(
        spec.name,
        key_from_data(spec.key_data),
        spec.m,
        spec.n,
        spec.sigma2,
        dist=spec.dist,
        dtype=jnp.dtype(spec.dtype),
    )


def as_operator(w) -> FrequencyOperator:
    """Pass operators through; wrap raw ``(n, m)`` arrays in a dense operator.

    A wrapped raw matrix behaves exactly like the dense operator it is
    (``apply`` is the same ``x @ w``) but carries no spec.  This is the
    *explicit* wrapping entry point — the decoder helpers and kernel wrappers
    no longer accept raw matrices themselves (their one-release deprecation
    window closed in PR 6; they raise ``TypeError``), so call this at the
    boundary when you hold a plain array.
    """
    if isinstance(w, FrequencyOperator):
        return w
    from repro.core.freq_ops.dense import DenseOperator

    return DenseOperator(jnp.asarray(w))
