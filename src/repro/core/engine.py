"""Unified streaming SketchEngine — one mergeable-sketch API, three backends.

The paper's central object is the sketch ``z = Sk(X, 1/N)``: a one-pass,
*linear* summary of the empirical distribution.  Linearity makes the partial
sums a **commutative monoid**: any way of splitting the data over batches,
devices, or hosts and any order of combining the partials yields the same
sketch.  This module is the single implementation of that contract; every
producer (in-memory, streaming, distributed) and every consumer (CLOMPR,
monitors, the data balancer) goes through it.

Mergeable-state contract
------------------------
``SketchEngineState(cos_acc, sin_acc, weight_sum, lower, upper, count)`` with

- identity:      ``init_state()`` (zero sums, ``+inf/-inf`` bounds),
- ``update``:    fold one weighted batch into a state (one pass, O(m) memory),
- ``merge``:     elementwise combine — **associative and commutative**, so
                 states may be combined across batches/devices/hosts in any
                 order (tree reductions, psum, delayed stragglers all legal),
- ``finalize``:  normalise to the paper's sketch:  ``z = sums / weight_sum``
                 (stacked-real ``[sum b cos, -sum b sin] / sum b``), plus the
                 CLOMPR box bounds ``(lower, upper)`` harvested in the same
                 pass.

Where the state format lives
----------------------------
This module alone knows which leaves a state has and how each combines.  Its
pure, shape-agnostic state functions are the only code that builds a state:
``_identity`` (the monoid identity, any leading shape), ``_batch_partial``
(one batch -> one undecayed partial, every backend), ``_lift`` and ``_tick``
(a partial stamped at a tick; the empty-stamp rule), ``_merge_states`` with
the ``_COMBINE`` table of each undecayed field's combine (sum / min / max),
``_finalize`` (the flavour's finalize) and ``_check_capacity`` (the int32
capacity check).  :class:`SketchEngine` calls them on one state;
``core.fleet.FleetEngine`` vmaps (and, on a mesh, shard_maps) the same
functions over a leading tenant axis.  A new state transform is therefore
written here once, and the fleet inherits it.

Backend matrix
--------------
=========  ==================================================================
backend    update path
=========  ==================================================================
xla        ``core.sketch.sketch`` — chunked ``lax.scan``; the (N, m)
           projection never materialises.  Runs everywhere; the default.
pallas     ``kernels.ops.fourier_sketch_sums`` — fused MXU+VPU TPU kernel
           (projection tile stays in VMEM).  Inputs are auto-padded to tile
           alignment (N→block_n, n→8, m→block_m); off-TPU the kernel body
           runs in ``interpret=True`` mode for correctness.
sharded    ``shard_map`` over a device mesh: every device sketches its local
           shard, one ``psum/pmin/pmax`` merges — O(m) cross-device traffic,
           independent of N.  Requires ``mesh=``; uses the version-compat
           shim in ``utils.compat`` (old and new ``shard_map`` APIs).
=========  ==================================================================

All three backends produce identical sketches (within float tolerance) — the
tier-1 suite asserts pairwise parity at 1e-4 on CPU.

State transforms
----------------
Passing ``quantizer=`` (a ``core.quantize.SketchQuantizer``) swaps the state
for its universally-quantized twin ``QuantizedSketchEngineState``: per-point
contributions are quantized to 1-bit signs or ``b``-bit integer codes of the
dithered phase, and the accumulators become **int32** sums — still a
commutative monoid (integer addition), still exactly split-invariant (codes
are deterministic per point), but 2-4x cheaper on the wire at minimal integer
width when partials are merged across devices (the sharded backend psums the
integer accumulators; the 32x factor applies to the raw per-sample codes).
``finalize`` dequantizes via the known E[sign] correction and returns the same
``(z, lower, upper)`` contract, so consumers — CLOMPR included — are unchanged.
See ``docs/architecture.md`` for the full contract and ``core.quantize`` for
the encoding/decoding math.

Passing ``decay=gamma`` (0 < gamma <= 1) switches the state to its
**time-decayed** twin: every accumulator entry carries the timestamp of the
newest contribution folded in, and merging two states first scales the older
operand's trig/weight sums by ``gamma**dt`` (dt = stamp difference) before the
elementwise combine.  The decayed merge is still commutative with the same
identity (``stamp=-inf``); associativity holds exactly in the algebra (each
batch contribution ends scaled by ``gamma**(t_newest - t_batch)`` under any
association) and bitwise whenever the operands share a stamp — cross-stamp
regroupings agree to float rounding, like any float re-association.  The
finalized sketch becomes the exponentially-reweighted average
``z = sum_i gamma**(T - t_i) part_i / sum_i gamma**(T - t_i) w_i`` — a live
estimate of the *recent* distribution on non-stationary streams.  On the
quantized transform the int32 code accumulators are never scaled (a decayed
integer is not an integer): the newest-stamp segment stays an exact int32
sum, and decay moves older segments into a float32 side-channel
(``dcos_acc``/``dsin_acc``) carrying the accumulated ``gamma`` powers, so
same-stamp merges remain bitwise split-invariant.  Bounds ``lower/upper`` and
``count`` are lifetime (min/max and counts cannot be decayed).  Composes with
every backend and with ``quantizer=``; see ``core.window`` for the bucketed
ring window built on top.

Scaling hooks
-------------
Batch *production* and cross-device *merging* are pluggable too.
``core.ingest`` overlaps host-side batch generation/transfer with ``update``
(double-buffered producer thread behind the ``BatchSource`` protocol —
``sketch_stream(..., async_ingest=True)`` or ``CKMConfig.ingest="async"``),
and ``core.topology`` makes the merge *schedule* a registry choice:
``reduce_topology="allreduce" | "tree" | "ring"`` selects how the sharded
backend combines per-device partials (and how :meth:`SketchEngine.reduce_partials`
folds host-level partials).  Every schedule yields the same sketch — bitwise
on the quantized path — by the monoid laws above.  See ``docs/scaling.md``.
"""

from __future__ import annotations

import functools
from typing import Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import freq_ops as fo
from repro.core import quantize as qz
from repro.core import sketch as sk
from repro.core import topology as topo
from repro.obs import runtime as obs_rt
from repro.parallel.sharding import axis_extent
from repro.utils import compat

__all__ = [
    "SketchEngineState",
    "QuantizedSketchEngineState",
    "DecayedSketchEngineState",
    "DecayedQuantizedSketchEngineState",
    "SketchEngine",
    "BACKENDS",
]

BACKENDS = ("xla", "pallas", "sharded")


class SketchEngineState(NamedTuple):
    """Commutative-monoid accumulator of the one-pass sketch statistics."""

    cos_acc: jax.Array  # (m,) f32 — sum_l beta_l cos(w^T y_l), unnormalised
    sin_acc: jax.Array  # (m,) f32 — sum_l beta_l sin(w^T y_l), unnormalised
    weight_sum: jax.Array  # () f32 — sum of weights folded in so far
    lower: jax.Array  # (n,) f32 — running per-coordinate min
    upper: jax.Array  # (n,) f32 — running per-coordinate max
    count: jax.Array  # () f32 — number of points folded in


class QuantizedSketchEngineState(NamedTuple):
    """QCKM twin of :class:`SketchEngineState`: integer code accumulators.

    Same monoid (identity = zeros, merge = elementwise add/min/max), but the
    trig accumulators hold **int32 sums of universal-quantization codes** of
    the dithered phases, so a partial state is 2-4x smaller at minimal
    integer width and exactly split-invariant (codes deterministic per point).  Only unit
    weights are representable — quantized states count points, not masses.
    Capacity: int32 sums hold ``accumulator_capacity(bits)`` points before
    wrapping (~2.1e9 at 1 bit); ``finalize`` checks the folded count.
    """

    qcos_acc: jax.Array  # (m,) i32 — sum_l Q(cos(w^T y_l + xi))
    qsin_acc: jax.Array  # (m,) i32 — sum_l Q(sin(w^T y_l + xi))
    weight_sum: jax.Array  # () f32 — == count (unit weights only)
    lower: jax.Array  # (n,) f32 — running per-coordinate min
    upper: jax.Array  # (n,) f32 — running per-coordinate max
    count: jax.Array  # () f32 — number of points folded in


class DecayedSketchEngineState(NamedTuple):
    """Time-decayed twin of :class:`SketchEngineState`.

    ``cos_acc/sin_acc/weight_sum`` are held *in the units of* ``stamp`` (the
    tick of the newest contribution): merging decays the older operand by
    ``gamma**dt`` first, so at any moment the sums equal
    ``sum_i gamma**(stamp - t_i) * contribution_i``.  ``lower/upper`` stay
    the lifetime envelope and ``count`` the raw folded-point total (bounds
    and counts have no meaningful decay).  ``gamma`` rides the state so the
    merge is self-describing (checkpoints, stacked fleets, vmap).
    """

    cos_acc: jax.Array  # (m,) f32 — decayed sum of beta_l cos(w^T y_l)
    sin_acc: jax.Array  # (m,) f32 — decayed sum of beta_l sin(w^T y_l)
    weight_sum: jax.Array  # () f32 — decayed mass sum_i gamma^dt_i * w_i
    lower: jax.Array  # (n,) f32 — lifetime per-coordinate min
    upper: jax.Array  # (n,) f32 — lifetime per-coordinate max
    count: jax.Array  # () f32 — raw number of points folded (undecayed)
    stamp: jax.Array  # () f32 — tick of the newest fold; -inf = identity
    gamma: jax.Array  # () f32 — decay base per tick (static per engine)


class DecayedQuantizedSketchEngineState(NamedTuple):
    """Decay + quantization: exact int32 codes, decay in a float side-scale.

    An int32 code sum cannot be scaled by ``gamma**dt`` and stay an integer,
    so the decayed quantized state is segmented by stamp: ``qcos/qsin_acc``
    hold the **exact int32 code sums of the newest-stamp segment** (same-tick
    merges add integers — bitwise split-invariant, exactly as the lifetime
    quantized state), while ``dcos/dsin_acc`` carry every older segment as
    float32 code mass with its accumulated decay factors applied.  When a
    merge advances the stamp, the older operand's whole content (ints +
    side-channel) folds into the side-channel through one ``gamma**dt``
    multiply; ``finalize`` dequantizes the sum of both segments (the E[sign]
    correction is linear, so it applies to the combined code mass).
    """

    qcos_acc: jax.Array  # (m,) i32 — exact code sums of the newest segment
    qsin_acc: jax.Array  # (m,) i32
    dcos_acc: jax.Array  # (m,) f32 — decayed older code mass (side-scale)
    dsin_acc: jax.Array  # (m,) f32
    weight_sum: jax.Array  # () f32 — decayed effective count
    lower: jax.Array  # (n,) f32 — lifetime per-coordinate min
    upper: jax.Array  # (n,) f32 — lifetime per-coordinate max
    count: jax.Array  # () f32 — raw number of points folded (undecayed)
    stamp: jax.Array  # () f32 — tick of the newest fold; -inf = identity
    gamma: jax.Array  # () f32 — decay base per tick


DECAYED_STATE_TYPES = (DecayedSketchEngineState, DecayedQuantizedSketchEngineState)


class _EngineInstruments(NamedTuple):
    """Per-engine cached metric handles (resolved once per registry
    generation, so the enabled steady state is plain ``float +=``)."""

    gen: int
    update_calls: object
    update_rows: object
    merge_calls: object
    finalize_calls: object
    state_bytes: object


def _state_nbytes(state) -> int:
    """Bytes of a state's array leaves — what a partial ships on merge."""
    return int(
        sum(
            int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
            for leaf in state
        )
    )


def _decay_factor(gamma, dt):
    """``gamma**dt`` with the identity edge cases pinned.

    ``dt`` can be ``nan`` (both operands are the ``stamp=-inf`` identity:
    ``(-inf) - (-inf)``) or ``inf`` (identity folding into a stamped state);
    both must behave as "no decay of nothing".  The double ``where`` keeps
    ``nan`` out of the power's gradient-free forward value and pins
    ``dt <= 0`` (the newest operand, or identity-identity) to exactly 1.0 so
    same-stamp merges stay bitwise equal to the undecayed merge.
    """
    safe = jnp.where(dt > 0, dt, 0.0)
    return jnp.where(dt > 0, gamma**safe, 1.0)


# How each field of an undecayed state combines: the monoid's elementwise
# rule, written once.  ``_merge_states``, the sharded backend's cross-device
# reduction and the fleet's unique-id scatter all read it; the decayed twins
# merge through ``_merge_states``'s own decay-aware branches.
_COMBINE = {
    "cos_acc": "sum",
    "sin_acc": "sum",
    "qcos_acc": "sum",
    "qsin_acc": "sum",
    "weight_sum": "sum",
    "lower": "min",
    "upper": "max",
    "count": "sum",
}


@jax.jit
def _merge_states(a, b):
    """Merge for either state flavour (dispatch happens at trace time)."""
    if type(a) is not type(b):
        raise TypeError(
            f"cannot merge mismatched state flavours: "
            f"{type(a).__name__} vs {type(b).__name__}"
        )
    if isinstance(a, DecayedSketchEngineState):
        t = jnp.maximum(a.stamp, b.stamp)
        fa = _decay_factor(a.gamma, t - a.stamp)
        fb = _decay_factor(b.gamma, t - b.stamp)
        return DecayedSketchEngineState(
            cos_acc=fa[..., None] * a.cos_acc + fb[..., None] * b.cos_acc,
            sin_acc=fa[..., None] * a.sin_acc + fb[..., None] * b.sin_acc,
            weight_sum=fa * a.weight_sum + fb * b.weight_sum,
            lower=jnp.minimum(a.lower, b.lower),
            upper=jnp.maximum(a.upper, b.upper),
            count=a.count + b.count,
            stamp=t,
            gamma=jnp.maximum(a.gamma, b.gamma),
        )
    if isinstance(a, DecayedQuantizedSketchEngineState):
        t = jnp.maximum(a.stamp, b.stamp)
        fa = _decay_factor(a.gamma, t - a.stamp)
        fb = _decay_factor(b.gamma, t - b.stamp)
        # Segment by stamp: the operand(s) at the new stamp keep their int32
        # codes exact (same-tick merge = integer add, bitwise); an older
        # operand folds *entirely* (ints + side-channel) into the float
        # side-channel through one gamma**dt multiply.
        a_new = a.stamp >= t
        b_new = b.stamp >= t

        def _i(new, q):
            return jnp.where(new[..., None], q, 0)

        def _d(new, f, q, d):
            qf = q.astype(jnp.float32)
            return jnp.where(new[..., None], d, f[..., None] * (d + qf))

        return DecayedQuantizedSketchEngineState(
            qcos_acc=_i(a_new, a.qcos_acc) + _i(b_new, b.qcos_acc),
            qsin_acc=_i(a_new, a.qsin_acc) + _i(b_new, b.qsin_acc),
            dcos_acc=_d(a_new, fa, a.qcos_acc, a.dcos_acc)
            + _d(b_new, fb, b.qcos_acc, b.dcos_acc),
            dsin_acc=_d(a_new, fa, a.qsin_acc, a.dsin_acc)
            + _d(b_new, fb, b.qsin_acc, b.dsin_acc),
            weight_sum=fa * a.weight_sum + fb * b.weight_sum,
            lower=jnp.minimum(a.lower, b.lower),
            upper=jnp.maximum(a.upper, b.upper),
            count=a.count + b.count,
            stamp=t,
            gamma=jnp.maximum(a.gamma, b.gamma),
        )
    return type(a)(
        *(
            topo._COMBINE[_COMBINE[f]](x, y)
            for f, x, y in zip(a._fields, a, b)
        )
    )


def _normalise(state, cos_acc, sin_acc):
    """``(z, lower, upper)``: the stacked-real sums over the weight sum.

    An empty stream (or an all-zero-weight shard) has nothing to average:
    return the zero sketch rather than accumulator/denom garbage, float or
    quantized.  The tiny denom floor alone is not enough — cos_acc can be
    exactly 0 while a negative-weight cancellation leaves weight_sum at -0.0
    or ~1e-38.
    """
    denom = jnp.maximum(state.weight_sum, 1e-30)
    z = jnp.concatenate([cos_acc, -sin_acc]) / denom
    z = jnp.where(state.weight_sum > 0, z, jnp.zeros_like(z))
    return z, state.lower, state.upper


@jax.jit
def _finalize_state(state: SketchEngineState):
    return _normalise(state, state.cos_acc, state.sin_acc)


@functools.partial(jax.jit, static_argnames=("bits",))
def _finalize_quantized(state: QuantizedSketchEngineState, dither, bits: int):
    return _normalise(
        state, *qz.dequantize_sums(state.qcos_acc, state.qsin_acc, dither, bits)
    )


@functools.partial(jax.jit, static_argnames=("bits",))
def _finalize_decayed_quantized(
    state: DecayedQuantizedSketchEngineState, dither, bits: int
):
    # The dequantization correction is linear in the code sums, so it applies
    # to the combined (exact int newest segment + decayed float older mass)
    # code total directly.  With an empty side-channel this is bitwise equal
    # to ``_finalize_quantized``: ``q.astype(f32) + 0.0`` and the int path's
    # internal ``astype(f32)`` produce the same float.
    return _normalise(
        state,
        *qz.dequantize_sums(
            state.qcos_acc.astype(jnp.float32) + state.dcos_acc,
            state.qsin_acc.astype(jnp.float32) + state.dsin_acc,
            dither,
            bits,
        ),
    )


def _finalize(state, dither=None, bits=None):
    """The finalize of a state's flavour: dequantize with ``dither`` and
    ``bits`` when the accumulators hold codes.  Shape-agnostic through
    ``vmap``: the fleet maps it over its tenant rows."""
    if isinstance(state, DecayedQuantizedSketchEngineState):
        return _finalize_decayed_quantized(state, dither, bits)
    if isinstance(state, QuantizedSketchEngineState):
        return _finalize_quantized(state, dither, bits)
    # ``_finalize_state`` duck-types over the float flavours — the decayed
    # state has the same accumulator fields (jit retraces per pytree).
    return _finalize_state(state)


def _check_capacity(count, bits: int) -> None:
    """Raise if int32 code sums may have wrapped.

    They wrap silently once ``count * scale`` exceeds the int32 range, so
    this reads the (non-wrapping) f32 ``count`` rather than garbage-decode:
    one state's count, or a stacked fleet's, where its fullest row decides.
    Skipped under tracing.  ``float`` waits for the device: one sync.
    """
    if isinstance(count, jax.core.Tracer):
        return
    folded = float(jnp.max(count) if jnp.ndim(count) else count)
    cap = qz.accumulator_capacity(bits)
    if folded > cap:
        raise ValueError(
            f"quantized accumulators overflow: {folded:.0f} points folded "
            f"at {bits} bits exceeds the int32 capacity of {cap} points "
            "(core.quantize.accumulator_capacity)"
        )


def _identity(n: int, m: int, *, quantized: bool, decay=None, lead=()):
    """The monoid identity of a flavour, with leading axes ``lead`` (a
    fleet's tenant axis): zero sums, ``+inf/-inf`` bounds and, under
    ``decay``, the ``-inf`` stamp."""
    f32 = jnp.float32
    acc = jnp.int32 if quantized else f32
    part = (QuantizedSketchEngineState if quantized else SketchEngineState)(
        jnp.zeros(lead + (m,), acc),
        jnp.zeros(lead + (m,), acc),
        jnp.zeros(lead, f32),
        jnp.full(lead + (n,), jnp.inf, f32),
        jnp.full(lead + (n,), -jnp.inf, f32),
        jnp.zeros(lead, f32),
    )
    if decay is None:
        return part
    return _lift(part, jnp.full(lead, -jnp.inf, f32), decay)


def _lift(part, stamp, decay: float):
    """An undecayed partial as its decayed twin stamped at tick ``stamp``
    (any leading shape) — the bridge between the batch partials, which know
    nothing about time, and the timestamped merge."""
    stamp = jnp.asarray(stamp, jnp.float32)
    gamma = jnp.full(jnp.shape(stamp), decay, jnp.float32)
    if isinstance(part, QuantizedSketchEngineState):
        return DecayedQuantizedSketchEngineState(
            part.qcos_acc,
            part.qsin_acc,
            jnp.zeros_like(part.qcos_acc, jnp.float32),
            jnp.zeros_like(part.qsin_acc, jnp.float32),
            *part[2:],
            stamp,
            gamma,
        )
    return DecayedSketchEngineState(*part, stamp, gamma)


def _tick(stamp, t=None):
    """The tick a partial is lifted at, against a state clock ``stamp``.

    ``t=None``, and every ``nan`` entry of ``t`` (the fleet's per-request
    "my row's clock" sentinel), take the state's own clock: no time
    advance, with the identity's ``-inf`` resolving to tick 0.  A partial
    must never carry ``-inf`` itself: a non-empty contribution stamped
    ``-inf`` would be decayed to nothing by any later merge.
    """
    if t is None:
        return jnp.where(jnp.isfinite(stamp), stamp, 0.0)
    return jnp.where(jnp.isnan(t), _tick(stamp), t)


def _decay_to(state, identity, t):
    """Advance a decayed state's clock to tick ``t`` without folding data,
    inside the merge algebra: merge with ``identity`` stamped ``t``."""
    stamp = jnp.broadcast_to(
        jnp.asarray(t, jnp.float32), jnp.shape(identity.stamp)
    )
    return _merge_states(state, identity._replace(stamp=stamp))


def _weights(weights, shape, quantized: bool):
    """Per-point weights of ``shape`` points: unit when ``None``.  The
    quantized transform counts points and takes none."""
    if quantized:
        if weights is not None:
            raise ValueError(
                "quantized sketch states accumulate unit-weight integer "
                "counts; per-point weights are not representable"
            )
        return None
    if weights is None:
        return jnp.ones(shape, jnp.float32)
    return jnp.asarray(weights, jnp.float32)


def _xla_sums(x, op, dither, weights, *, bits, chunk, vary_axes=()):
    """``(cos, sin)`` sums of one batch on XLA: float sums, or int32 code
    sums (``weights`` then masks padding rows, or is ``None``)."""
    chunk = min(chunk, max(x.shape[0], 1))
    if bits is None:
        part = sk.sketch(
            x, op, weights=weights, chunk=chunk, vary_axes=vary_axes
        )
        return part[: op.m], -part[op.m :]
    return sk.sketch_quantized(
        x, op, dither, valid=weights, bits=bits, chunk=chunk,
        vary_axes=vary_axes,
    )


def _batch_partial(
    op,
    dither,
    x,
    weights,
    *,
    backend: str,
    bits: int | None,
    chunk: int,
    block_n: int,
    block_m: int,
    interpret: bool | None,
    mesh: Mesh | None = None,
    data_axes: tuple[str, ...] = (),
    topology: str = "allreduce",
):
    """One batch ``x: (B, n)`` -> one undecayed partial state.

    ``dither`` is the quantizer's (``bits`` set) or ``None``; ``weights``
    default to 1 per point.  The keywords are static backend settings, so
    the fleet can ``vmap`` a ``functools.partial`` of this over
    ``(op, dither, x, weights)``.
    """
    weights = _weights(weights, x.shape[:1], bits is not None)
    if backend == "sharded":
        return _sharded_partial(
            op, dither, x, weights, bits=bits, chunk=chunk, mesh=mesh,
            axes=data_axes, topology=topology,
        )
    if backend == "pallas":
        from repro.kernels import ops

        tiles = dict(block_n=block_n, block_m=block_m, interpret=interpret)
        if bits is None:
            sums = ops.fourier_sketch_sums(x, op, weights, **tiles)
        else:
            sums = ops.quantized_fourier_sketch_sums(
                x, op, dither, bits=bits, **tiles
            )
    else:  # xla
        sums = _xla_sums(x, op, dither, weights, bits=bits, chunk=chunk)
    count = jnp.asarray(x.shape[0], jnp.float32)
    if bits is None:
        return SketchEngineState(
            *sums, jnp.sum(weights), jnp.min(x, axis=0), jnp.max(x, axis=0),
            count,
        )
    # Quantized states count points: the weight sum is the count.
    return QuantizedSketchEngineState(
        *sums, count, jnp.min(x, axis=0), jnp.max(x, axis=0), count
    )


def _sharded_partial(op, dither, x, weights, *, bits, chunk, mesh, axes, topology):
    """The sharded backend's partial: each device sketches its shard of the
    batch, and one reduction per field (``_COMBINE``) merges them — O(m)
    cross-device traffic, int32 code sums on the quantized path.

    shard_map needs the leading axis divisible by the data-axis extent;
    streaming batches (ragged tail chunks) generally aren't.  Pad with
    zero-weight copies of the first row: weight 0 keeps the sums exact (on
    the quantized path the weights are the mask of real rows), and a copied
    point cannot move the min/max bounds.  The count is the unpadded one.
    """
    b = x.shape[0]
    mask = jnp.ones((b,), jnp.float32) if weights is None else weights
    pad = (-b) % axis_extent(mesh, axes)
    if pad:
        x = jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad, x.shape[1]))], axis=0
        )
        mask = jnp.concatenate([mask, jnp.zeros((pad,), jnp.float32)], axis=0)
    cls = SketchEngineState if bits is None else QuantizedSketchEngineState

    def local(x_shard, op_rep, dither_rep, mask_shard):
        sums = _xla_sums(
            x_shard, op_rep, dither_rep, mask_shard, bits=bits, chunk=chunk,
            vary_axes=axes,
        )
        leaves = (
            *sums,
            jnp.sum(mask_shard),
            jnp.min(x_shard, axis=0),
            jnp.max(x_shard, axis=0),
        )
        # The engine's monoid merge as a collective schedule: bitwise the
        # same for every topology on the int32 path.
        return tuple(
            topo.axis_reduce(v, axes, topology, op=_COMBINE[f])
            for f, v in zip(cls._fields, leaves)
        )

    # The operator rides shard_map as a replicated pytree: its leaves are
    # what the broadcast ships — O(m) signs/radii for the structured family
    # instead of the O(n·m) dense matrix.  tree/ring reductions return
    # ppermute-derived values the VMA checker cannot see as replicated (they
    # are — exactly for integers, to association-order ulps for floats), so
    # checking is off for them; allreduce (psum) keeps it as a safety net.
    fn = compat.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes), P(), P(), P(axes)),
        out_specs=(P(),) * 5,
        check_vma=False if topology != "allreduce" else None,
    )
    return cls(*fn(x, op, dither, mask), jnp.asarray(b, jnp.float32))


class SketchEngine:
    """Streaming/mergeable sketch computation over pluggable backends.

    Parameters
    ----------
    w : the frequency operator — a ``core.freq_ops.FrequencyOperator``
        (``freq_ops.make_operator("dense" | "structured", ...)``); a raw
        ``(n, m)`` matrix is also accepted here for convenience (wrapped in a
        spec-less dense operator).  The engine carries the operator's O(m)
        leaves (dense: the matrix; structured: signs + radii) and exposes
        ``spec()`` so checkpoints/broadcast can carry the O(1) rebuild recipe
        instead of any materialised state.
    backend : one of ``BACKENDS`` — see the backend matrix in the module doc.
    chunk : scan chunk for the xla/sharded backends.
    block_n, block_m : Pallas tile sizes (pallas backend).
    interpret : force Pallas interpret mode (None = auto: interpret off-TPU).
    mesh, data_axes : device mesh + data axes (sharded backend only).  Batches
        passed to ``update`` must be shardable along their leading axis.
    quantizer : optional ``core.quantize.SketchQuantizer`` — switches the
        engine to the quantized state transform (int32 code accumulators,
        unit weights only; see the module doc's "State transforms").
    reduce_topology : merge schedule for the sharded backend's cross-device
        combine and for :meth:`reduce_partials` — any name registered in
        ``core.topology`` (``"allreduce"`` | ``"tree"`` | ``"ring"``).  The
        monoid laws make every schedule produce the same sketch (bitwise on
        the quantized path); the choice trades wire bytes against hop count
        (``core.topology.wire_cost_model``, ``docs/scaling.md``).
    decay : optional per-tick exponential decay base ``gamma`` in (0, 1].
        Switches the engine to the time-decayed state transform: states gain
        a ``stamp`` (tick of the newest contribution), ``update`` accepts a
        keyword ``t``, and merging scales the older operand's
        ``cos_acc/sin_acc/weight_sum`` by ``gamma**dt`` first, so the sketch
        is always an exponentially weighted average favouring recent data.
        ``decay=1.0`` keeps timestamps but decays nothing.  Composes with
        every backend and with ``quantizer`` (see "State transforms").
    """

    def __init__(
        self,
        w: jax.Array,
        backend: str = "xla",
        *,
        chunk: int = 8192,
        block_n: int = 1024,
        block_m: int = 512,
        interpret: bool | None = None,
        mesh: Mesh | None = None,
        data_axes: Sequence[str] = ("data",),
        quantizer: qz.SketchQuantizer | None = None,
        reduce_topology: str = "allreduce",
        decay: float | None = None,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        if backend == "sharded" and mesh is None:
            raise ValueError("backend='sharded' requires a mesh")
        if decay is not None and not 0.0 < float(decay) <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay!r}")
        topo.get_topology(reduce_topology)  # fail fast on unknown names
        self.freq_op = fo.as_operator(w)
        self.n, self.m = self.freq_op.n, self.freq_op.m
        self.backend = backend
        self.chunk = chunk
        self.block_n = block_n
        self.block_m = block_m
        self.interpret = interpret
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.reduce_topology = reduce_topology
        if quantizer is not None and quantizer.dither.shape != (self.m,):
            raise ValueError(
                f"quantizer dither shape {quantizer.dither.shape} != (m,)="
                f"{(self.m,)}"
            )
        self.quantizer = quantizer
        self.decay = None if decay is None else float(decay)
        self._obs_h: _EngineInstruments | None = None

    def _obs(self) -> _EngineInstruments:
        """Resolve (or re-resolve after a registry reset) the engine's
        cached instrument handles.  Only reached when telemetry is on."""
        from repro.obs import metrics as obs_metrics

        h = self._obs_h
        gen = obs_metrics.REGISTRY.generation
        if h is None or h.gen != gen:
            bits = (
                str(self.quantizer.bits) if self.quantizer is not None else "none"
            )
            labels = dict(backend=self.backend, bits=bits)
            h = self._obs_h = _EngineInstruments(
                gen=gen,
                update_calls=obs_metrics.counter("engine.update.calls", **labels),
                update_rows=obs_metrics.counter("engine.update.rows", **labels),
                merge_calls=obs_metrics.counter("engine.merge.calls", **labels),
                finalize_calls=obs_metrics.counter(
                    "engine.finalize.calls", **labels
                ),
                state_bytes=obs_metrics.gauge("engine.state.bytes", **labels),
            )
        return h

    @property
    def w(self) -> jax.Array:
        """Materialised ``(n, m)`` frequency matrix (back-compat; on demand —
        the engine itself never carries it for non-dense operators)."""
        return self.freq_op.materialize()

    def spec(self) -> fo.FreqOpSpec:
        """The operator's O(1) rebuild recipe (``core.freq_ops.FreqOpSpec``)
        — what checkpoints and cross-host broadcast should carry instead of
        the O(n·m) matrix; raises for shim-wrapped raw matrices."""
        return self.freq_op.spec()

    # -- monoid ops ---------------------------------------------------------

    def init_state(self) -> SketchEngineState | QuantizedSketchEngineState:
        """The monoid identity: merge(init_state(), s) == s for any s."""
        return _identity(
            self.n, self.m, quantized=self.quantizer is not None,
            decay=self.decay,
        )

    def _partial_state(self, batch: jax.Array, weights: jax.Array | None):
        """One batch -> one partial state (the pre-merge half of update)."""
        q = self.quantizer
        return _batch_partial(
            self.freq_op,
            None if q is None else q.dither,
            jnp.asarray(batch, jnp.float32),
            weights,
            backend=self.backend,
            bits=None if q is None else q.bits,
            chunk=self.chunk,
            block_n=self.block_n,
            block_m=self.block_m,
            interpret=self.interpret,
            mesh=self.mesh,
            data_axes=self.data_axes,
            topology=self.reduce_topology,
        )

    def update(
        self,
        state,
        batch: jax.Array,
        weights: jax.Array | None = None,
        *,
        t: float | jax.Array | None = None,
    ):
        """Fold ``batch: (B, n)`` into ``state``.  ``weights`` default to 1
        per point, so streaming batches of any size weight points equally.
        The quantized state transform only represents unit weights (integer
        code counts) and rejects explicit ``weights``.

        Under ``decay``, ``t`` is the batch's tick: older state content is
        scaled by ``gamma**(t - state.stamp)`` as it merges.  ``t=None``
        reuses the state's current stamp (fold with no time advance — the
        empty state resolves to tick 0).  Passing ``t`` without ``decay``
        is an error.
        """
        if t is not None and self.decay is None:
            raise ValueError(
                "update(t=...) requires a decay-enabled engine "
                "(SketchEngine(decay=gamma))"
            )
        if not obs_rt.ENABLED:
            return self._update(state, batch, weights, t)
        from repro.obs import trace as obs_trace

        h = self._obs()
        with obs_trace.span("engine.update", backend=self.backend):
            out = self._update(state, batch, weights, t)
        h.update_calls.inc()
        h.update_rows.inc(float(np.shape(batch)[0]))
        h.merge_calls.inc()
        h.state_bytes.set(_state_nbytes(out))
        return out

    def _update(self, state, batch, weights, t):
        part = self._partial_state(batch, weights)
        if self.decay is not None:
            part = _lift(part, _tick(state.stamp) if t is None else t, self.decay)
        return _merge_states(state, part)

    def decay_to(self, state, t: float | jax.Array):
        """Advance a decayed state's clock to tick ``t`` without folding data:
        ``cos_acc/sin_acc/weight_sum`` scale by ``gamma**(t - stamp)``.

        Expressed inside the merge algebra — merging with an empty state
        stamped ``t`` — so it commutes with every other monoid op.  A ``t``
        at or before the current stamp is a bitwise no-op (states never move
        backwards in time).
        """
        if self.decay is None:
            raise ValueError(
                "decay_to requires a decay-enabled engine "
                "(SketchEngine(decay=gamma))"
            )
        return _decay_to(state, self.init_state(), t)

    def merge(self, a, b):
        """Associative + commutative combine of two partial states."""
        if not obs_rt.ENABLED:
            return _merge_states(a, b)
        from repro.obs import trace as obs_trace

        h = self._obs()
        with obs_trace.span("engine.merge", backend=self.backend):
            out = _merge_states(a, b)
        h.merge_calls.inc()
        return out

    def reduce_partials(self, states, topology: str | None = None):
        """Reduce many partial states through a named merge schedule.

        Host-level counterpart of the sharded backend's in-mesh collective:
        partials built anywhere (other hosts, edge sketchers, delayed
        stragglers) are folded with ``merge`` following the engine's
        ``reduce_topology`` (or an override).  Any schedule and any arrival
        order give the same state — bitwise for quantized int32 partials.
        """
        return topo.reduce_states(
            self.merge, states, topology or self.reduce_topology
        )

    def finalize(self, state):
        """-> ``(z stacked-real (2m,), lower (n,), upper (n,))``.

        Quantized states are dequantized here (E[sign] correction + dither
        rotation, ``core.quantize.dequantize_sums``) so every consumer sees
        the same float-sketch contract regardless of the state transform.
        """
        if not obs_rt.ENABLED:
            return self._finalize_impl(state)
        from repro.obs import trace as obs_trace

        h = self._obs()
        with obs_trace.span("engine.finalize", backend=self.backend):
            out = self._finalize_impl(state)
        h.finalize_calls.inc()
        return out

    def _finalize_impl(self, state):
        q = self.quantizer
        if q is None:
            return _finalize(state)
        from repro.obs import trace as obs_trace

        # The capacity check and the dequantization, under one span.
        with obs_trace.span("engine.dequantize", bits=q.bits):
            _check_capacity(state.count, q.bits)
            return _finalize(state, q.dither, q.bits)

    # -- conveniences -------------------------------------------------------

    def sketch(self, x: jax.Array, weights: jax.Array | None = None):
        """One-shot ``(z, lower, upper)`` — init/update/finalize in one call."""
        return self.finalize(self.update(self.init_state(), x, weights))

    def sketch_stream(
        self,
        batches: Iterable[jax.Array],
        *,
        async_ingest: bool = False,
        prefetch: int = 2,
    ):
        """One pass over an iterator of ``(B_i, n)`` batches -> (z, lo, hi).

        ``async_ingest=True`` routes the pass through
        ``core.ingest.ingest_stream``: a producer thread keeps ``prefetch``
        batches staged on device so batch production overlaps sketch compute.
        Same batches, same order, identical result.
        """
        if async_ingest:
            from repro.core import ingest as ingest_mod

            state, _ = ingest_mod.ingest_stream(self, batches, prefetch=prefetch)
            return self.finalize(state)
        state = self.init_state()
        for batch in batches:
            state = self.update(state, batch)
        return self.finalize(state)

    # -- placement ---------------------------------------------------------

    def shard_points(self, x: jax.Array) -> jax.Array:
        """Place ``x`` with its leading axis sharded over the data axes.

        When N is not divisible by the data-axis extent the array is left
        where it is — ``update`` zero-weight pads and reshards internally,
        so placement here is a locality optimisation, not a requirement.
        """
        assert self.mesh is not None
        from jax.sharding import NamedSharding

        if x.shape[0] % axis_extent(self.mesh, self.data_axes):
            return x
        return jax.device_put(x, NamedSharding(self.mesh, P(self.data_axes)))
