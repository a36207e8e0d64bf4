"""Compressive K-means — the user-facing API (paper §3.3).

The pipeline is exactly the paper's four steps:

1. choose a frequency scale sigma^2 on a small fraction of the data
   (``frequencies.estimate_sigma2``),
2. build the frequency operator for ``m`` frequencies from the adapted-radius
   distribution (``core.freq_ops``; ``CKMConfig.freq_op`` selects the paper's
   dense matrix or the structured fast-transform family),
3. compute the sketch ``z = Sk(X, 1/N)`` (one pass, through the unified
   ``core.engine.SketchEngine`` — xla / pallas / sharded backends; streaming
   via ``fit_streaming``) together with the box bounds ``l, u``,
4. decode K centroids from the sketch with a registered decoder
   (``core.decoders``): ``CKMConfig.decoder`` selects ``"clompr"`` (paper
   Algorithm 1, the default) or ``"sketch_shift"`` (mean-shift on the
   sketched characteristic function — more robust modes from the same
   sketch).

Beyond the paper, ``CKMConfig.sketch_quantization`` switches step 3 to the
QCKM universally-quantized sketch (``core.quantize``): per-point 1-bit/b-bit
integer codes, dequantized via the E[sign] correction before step 4 — the
decoders are unchanged (see ``docs/architecture.md``).  Step 3's scaling
knobs: ``CKMConfig.ingest="async"`` overlaps batch production with sketch
compute in ``fit_streaming`` (``core.ingest``), and
``CKMConfig.reduce_topology`` picks the sharded backend's cross-device merge
schedule (``core.topology``; see ``docs/scaling.md``).

Replicates are ``lax.map``-ed over PRNG keys and selected by the value of the
sketch-domain cost (4) — the SSE is *not* available once data is discarded.
Every registered decoder reports that same cost, so selection (and decoder
comparison) is apples-to-apples.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import decoders as dec_mod
from repro.core import freq_ops as fo
from repro.core import frequencies as freq_mod
from repro.core import quantize as qz
from repro.core import sketch as sk
from repro.core.decoders import AMPConfig, CLOMPRConfig, SketchShiftConfig
from repro.core.engine import SketchEngine
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class CKMConfig:
    k: int
    m: int | None = None  # sketch size; default m = 10*K*n (paper Fig. 1 uses
    # m = 1000 at K = n = 10; Fig. 2 shows relSSE hits 2.0 already at 5*K*n)
    freq_dist: freq_mod.FreqDist = "adapted_radius"
    # Frequency operator family (core.freq_ops registry): "dense" draws the
    # paper's materialized (n, m) matrix; "structured" uses stacked
    # HD-Rademacher fast-transform blocks with adapted-radius radial
    # rescaling — O(m·sqrt(d)) projections, O(m) operator state, O(1) spec on
    # the wire/in checkpoints.  Any registered name is valid end-to-end
    # (engine backends, decoders, quantization, streaming).
    freq_op: str = "dense"
    # Sampling/projection dtype of the frequency operator ("float64" needs
    # jax.enable_x64); propagated to frequencies.draw_frequencies.
    freq_dtype: str = "float32"
    replicates: int = 1
    sigma2: float | None = None  # None -> estimate from a data fraction
    sigma2_sample: int = 2048
    init: str = "range"
    atom_steps: int = 300
    joint_steps: int = 200
    nnls_iters: int = 150
    atom_lr: float = 0.05
    joint_lr: float = 0.02
    atom_restarts: int = 1
    final_steps: int = 1000
    merge_radius_scale: float = 2.5
    sketch_chunk: int = 8192
    # Sketch-computation backend: "xla" | "pallas" | "sharded" (see
    # core.engine.SketchEngine's backend matrix).  "sharded" needs a mesh
    # passed to fit()/compute_sketch().
    sketch_backend: str = "xla"
    # Cross-device merge schedule of the sharded backend (and of host-level
    # reduce_partials): any name registered in core.topology — "allreduce"
    # (native psum), "tree" (butterfly, log2 p hops), "ring" (token passing).
    # Every topology produces the same sketch (bitwise when quantized); the
    # choice trades wire bytes vs hop count — see docs/scaling.md.
    reduce_topology: str = "allreduce"
    # Streaming ingest mode for fit_streaming: "sync" feeds the engine batch
    # by batch; "async" overlaps batch production/transfer with sketch
    # compute through core.ingest (double-buffered producer thread,
    # ingest_prefetch batches staged).  Results are identical either way.
    ingest: str = "sync"
    ingest_prefetch: int = 2
    # Universal quantization of the sketch (QCKM): "none" | "1bit" | "<b>bit".
    # Per-point contributions are quantized to integer codes of the dithered
    # phase and accumulated in int32; finalize dequantizes via the E[sign]
    # correction before decoding (see core.quantize).  Works on every
    # backend; on "sharded" the cross-device merge psums integer accumulators.
    sketch_quantization: str = "none"
    # Exponential time decay of the sketch state (None = lifetime average).
    # A gamma in (0, 1] switches the engine to the timestamped state
    # transform: update/merge scale older accumulator content by gamma**dt,
    # so the sketch tracks non-stationary streams ("cluster recent traffic").
    # Composes with every backend and with sketch_quantization; see
    # core.engine ("State transforms") and core.window for bucketed windows.
    decay: float | None = None
    # Sketch decoder: any name in the registry (core.decoders) — "clompr"
    # (paper Algorithm 1), "sketch_shift" (mean-shift on the sketched
    # characteristic function) or "amp" (CL-AMP joint message passing,
    # accurate at small m).  Replicate selection, quantized sketches and
    # fit/fit_streaming work identically for every decoder.
    decoder: str = "clompr"
    # sketch_shift decoder knobs (ignored by "clompr"); nnls_iters and init
    # above are shared by both decoders.  merge_radius_scale is clompr-only:
    # the sketch_shift dedup radius is the (deliberately tighter)
    # shift_dedup_scale below.
    shift_candidates: int = 8  # mean-shift swarm size, per cluster (P = 8*K)
    shift_steps: int = 150  # fixed-point iterations
    shift_step_scale: float = 1.0  # multiplier on the natural step h^2
    shift_polish_steps: int = 400  # joint (C, alpha) Adam after mode selection
    shift_impl: str = "xla"  # score/shift step impl: "xla" | "pallas"
    # Mode-harvest dedup radius, in units of 1/median||omega|| (one kernel
    # std).  Deliberately tighter than merge_radius_scale: it only guards
    # against re-picking leftover residue of an already-kept mode, and a
    # larger radius would forbid genuinely overlapping clusters.
    shift_dedup_scale: float = 1.0
    # amp (CL-AMP) decoder knobs (ignored by the other decoders); nnls_iters,
    # joint_lr and init above are shared.
    amp_iters: int = 300  # GAMP iterations
    amp_damp: float = 0.3  # damping on the message updates (1 = undamped)
    amp_polish_steps: int = 600  # joint (C, alpha) Adam after the loop
    amp_impl: str = "xla"  # amp_denoise kernel impl: "xla" | "pallas"
    # Decoder convergence tracing: thread ``trace=True`` into the decoder
    # config, so the decode also returns its per-iteration trajectory
    # (CLOMPR/sketch_shift: residual norms; amp: unexplained energy +
    # posterior variance).  ``decode_sketch`` emits the selected replicate's
    # series through ``repro.obs.trace`` when telemetry is enabled.  This
    # flag alone decides it: telemetry never switches it on, since a tracing
    # decoder is another program (the traced buffers are
    # dead-code-eliminated whenever the flag is off).
    trace_convergence: bool = False

    def sketch_size(self, n: int) -> int:
        return self.m if self.m is not None else 10 * self.k * n

    def sketch_shift_config(self) -> SketchShiftConfig:
        return SketchShiftConfig(
            k=self.k,
            candidates=max(self.shift_candidates * self.k, self.k),
            shift_steps=self.shift_steps,
            step_scale=self.shift_step_scale,
            nnls_iters=self.nnls_iters,
            polish_steps=self.shift_polish_steps,
            polish_lr=self.joint_lr,
            init=self.init,
            dedup_radius_scale=self.shift_dedup_scale,
            impl=self.shift_impl,
            trace=self.trace_convergence,
        )

    def amp_config(self) -> AMPConfig:
        return AMPConfig(
            k=self.k,
            iters=self.amp_iters,
            damp=self.amp_damp,
            nnls_iters=self.nnls_iters,
            polish_steps=self.amp_polish_steps,
            polish_lr=self.joint_lr,
            init=self.init,
            impl=self.amp_impl,
            trace=self.trace_convergence,
        )

    def clompr_config(self) -> CLOMPRConfig:
        return CLOMPRConfig(
            k=self.k,
            atom_steps=self.atom_steps,
            joint_steps=self.joint_steps,
            nnls_iters=self.nnls_iters,
            atom_lr=self.atom_lr,
            joint_lr=self.joint_lr,
            init=self.init,  # type: ignore[arg-type]
            atom_restarts=self.atom_restarts,
            final_steps=self.final_steps,
            merge_radius_scale=self.merge_radius_scale,
            trace=self.trace_convergence,
        )


class CKMResult(NamedTuple):
    centroids: jax.Array  # (K, n)
    weights: jax.Array  # (K,) — mixture weights alpha, sum to 1
    cost: jax.Array  # sketch-domain objective (4) of the selected replicate
    sigma2: jax.Array
    freq_op: "fo.FrequencyOperator"  # the operator (O(m) state, O(1) spec)
    sketch: jax.Array  # stacked-real (2m,)
    bounds: tuple[jax.Array, jax.Array]

    @property
    def frequencies(self) -> jax.Array:
        """Materialised ``(n, m)`` frequency matrix (back-compat, on demand —
        the result itself carries the operator, not the matrix)."""
        return self.freq_op.materialize()


def stream_keys(key: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The sketch pass's three PRNG streams: ``(sigma2, frequencies, dither)``.

    One ``split`` fan-out from the parent key — the single derivation point
    shared by :func:`_draw_freqs` and :func:`make_quantizer`.  (The dither
    stream used to be ``fold_in(key, 0x51)`` while sigma2/frequencies came
    from ``split(key)`` of the *same* parent — two derivation schemes applied
    to one key, with no independence guarantee between them.)  Because every
    stream has its own branch, enabling quantization still does not perturb
    the frequency/sigma2 draws: a quantized run sees the same frequencies as
    its float twin under the same key.
    """
    k_sig, k_freq, k_dither = jax.random.split(key, 3)
    return k_sig, k_freq, k_dither


def make_quantizer(key: jax.Array, cfg: CKMConfig, m: int):
    """The sketch quantizer for ``cfg`` (or None for the float path).

    Draws only from the dither branch of :func:`stream_keys`, so the float
    and quantized pipelines share frequencies under the same parent key.
    """
    if cfg.sketch_quantization == "none":
        return None
    _, _, k_dither = stream_keys(key)
    return qz.make_quantizer(k_dither, m, cfg.sketch_quantization)


def make_engine(
    w, cfg: CKMConfig, mesh=None, quantizer=None
) -> SketchEngine:
    """The SketchEngine for ``cfg`` — backend, quantization and the merge
    topology are config flags.  ``w``: a frequency operator (or raw matrix)."""
    return SketchEngine(
        w, cfg.sketch_backend, chunk=cfg.sketch_chunk, mesh=mesh,
        quantizer=quantizer, reduce_topology=cfg.reduce_topology,
        decay=cfg.decay,
    )


def _draw_freqs(key, sample: jax.Array, n: int, cfg: CKMConfig):
    """Steps 1–2 on a data sample: scale estimation + operator construction.

    Returns the registered frequency operator ``cfg.freq_op`` (the ``"dense"``
    builder calls ``frequencies.draw_frequencies`` with the same key — the
    registry path is bitwise-identical to the historical direct draw).  The
    sigma2/frequency keys come from the shared :func:`stream_keys` fan-out.
    Step 1 runs under the ``ckm.sigma2`` span, step 2 under ``ckm.operator``.
    """
    k_sig, k_freq, _ = stream_keys(key)
    with obs_trace.span("ckm.sigma2"):
        if cfg.sigma2 is None:
            take = min(cfg.sigma2_sample, sample.shape[0])
            sigma2 = freq_mod.estimate_sigma2(k_sig, sample[:take])
        else:
            sigma2 = jnp.asarray(cfg.sigma2, jnp.float32)
    with obs_trace.span("ckm.operator", freq_op=cfg.freq_op):
        op = fo.make_operator(
            cfg.freq_op, k_freq, cfg.sketch_size(n), n, sigma2,
            dist=cfg.freq_dist, dtype=jnp.dtype(cfg.freq_dtype),
        )
    return op, sigma2


def _sketch_engine(key, sample: jax.Array, cfg: CKMConfig, mesh):
    """Steps 1–2 on ``sample`` and the engine that runs step 3 on their
    operator: ``(engine, operator, sigma2)``.  The quantizer and the engine
    are built under a second ``ckm.operator`` span."""
    op, sigma2 = _draw_freqs(key, sample, sample.shape[1], cfg)
    with obs_trace.span("ckm.operator", freq_op=cfg.freq_op):
        eng = make_engine(op, cfg, mesh, make_quantizer(key, cfg, op.m))
    return eng, op, sigma2


def compute_sketch(
    key: jax.Array, x: jax.Array, cfg: CKMConfig, mesh=None
) -> tuple[jax.Array, jax.Array, jax.Array, tuple[jax.Array, jax.Array]]:
    """Steps 1–3: scale estimation, operator construction, one-pass sketch.

    The sketch pass runs through the unified engine; ``cfg.sketch_backend``
    selects xla / pallas / sharded (``mesh`` required for sharded).  The
    second return value is the frequency *operator* (``core.freq_ops``) —
    ``op.materialize()`` recovers the dense matrix when needed.
    """
    x = jnp.asarray(x, jnp.float32)
    eng, op, sigma2 = _sketch_engine(key, x, cfg, mesh)
    with obs_trace.span("ckm.ingest", chunk=0):
        z, lo, hi = eng.sketch(x)
    return z, op, sigma2, (lo, hi)


def compute_sketch_streaming(
    key: jax.Array, batches: Iterable[jax.Array], cfg: CKMConfig, mesh=None
) -> tuple[jax.Array, jax.Array, jax.Array, tuple[jax.Array, jax.Array], jax.Array]:
    """One-pass sketch of an out-of-core batch iterator.

    The first batch doubles as the sigma^2-estimation sample (paper step 1
    uses "a small fraction of the data"); every batch — the first included —
    is then folded into the engine state.  Returns the first batch as the
    last element so callers may reuse it for sample/kpp decoder inits.
    Batch i is folded under a ``ckm.ingest`` span with ``chunk=i`` (the
    async path folds batches 1 onward under one, ``ingest="async"``).
    """
    if cfg.ingest not in ("sync", "async"):
        raise ValueError(
            f"CKMConfig.ingest must be 'sync' or 'async', got {cfg.ingest!r}"
        )
    it = iter(batches)
    try:
        first = jnp.asarray(next(it), jnp.float32)
    except StopIteration:
        raise ValueError("compute_sketch_streaming needs at least one batch")
    eng, op, sigma2 = _sketch_engine(key, first, cfg, mesh)
    with obs_trace.span("ckm.ingest", chunk=0):
        state = eng.update(eng.init_state(), first)
    if cfg.ingest == "async":
        # Overlap production/transfer of the remaining batches with sketch
        # compute (core.ingest).  Same batches, same order -> same result.
        from repro.core import ingest as ingest_mod

        with obs_trace.span("ckm.ingest", chunk=1, ingest="async"):
            state, _ = ingest_mod.ingest_stream(
                eng, it, state=state, prefetch=cfg.ingest_prefetch
            )
    else:
        for i, batch in enumerate(it, start=1):
            with obs_trace.span("ckm.ingest", chunk=i):
                state = eng.update(state, batch)
                # Strict streaming backpressure: the batch may be discarded
                # the moment it is folded in (the O(m)-memory contract).
                # Without this, async dispatch would buffer every pending
                # batch whenever the source outruns compute.  ingest="async"
                # relaxes it to a bounded double buffer (core.ingest) to
                # overlap the two.
                jax.block_until_ready(state)
    z, lo, hi = eng.finalize(state)
    return z, op, sigma2, (lo, hi), first


def decode_sketch(
    key: jax.Array,
    z: jax.Array,
    w,
    lower: jax.Array,
    upper: jax.Array,
    cfg: CKMConfig,
    x_init: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Step 4: decoding via the registered decoder ``cfg.decoder``, with
    replicates selected by the cost (4).

    ``w`` is the frequency operator (raw ``(n, m)`` arrays are still accepted
    through the deprecation shim).  Replicate r uses ``fold_in(key, r)``, so
    the replicate-key sequence for R replicates is a prefix of the sequence
    for R' > R, and replicates run sequentially via ``lax.map`` (the
    *unbatched* decoder trace — identical numerics to a single run).
    Together these make replicate selection monotone for every decoder: more
    replicates can never return a higher cost (all registry decoders report
    the same objective (4)).

    Convergence tracing: when ``cfg.trace_convergence`` is set the decoder
    runs with its ``trace`` flag on, and (under telemetry, ``repro.obs``) the
    selected replicate's trajectory is emitted as ``decoder.<name>.<series>``
    events on the default tracer.  The return contract stays ``(centroids,
    weights, cost)`` either way.  The decode runs under the ``ckm.decode``
    span.
    """
    with obs_trace.span(
        "ckm.decode", decoder=cfg.decoder, replicates=cfg.replicates
    ):
        w = fo.as_operator(w)
        decode = dec_mod.get_decoder(cfg.decoder)
        keys = jnp.stack(
            [jax.random.fold_in(key, r) for r in range(cfg.replicates)]
        )
        # Every decoder contraction (atoms, residuals, NNLS Gram) in f32: on
        # the TPU a default-precision f32 matmul is a single bf16 pass.
        with jax.default_matmul_precision("highest"):
            if cfg.replicates == 1:
                out = decode(keys[0], z, w, lower, upper, cfg, x_init)
            elif x_init is None:
                out = jax.lax.map(
                    lambda k_: decode(k_, z, w, lower, upper, cfg), keys
                )
            else:
                out = jax.lax.map(
                    lambda k_: decode(k_, z, w, lower, upper, cfg, x_init),
                    keys,
                )
        # A tracing decoder returns (cents, alphas, cost, {series}); one with
        # no trace support (or trace off) returns the plain 3-tuple.
        traces = out[3] if len(out) == 4 else None
        cents, alphas, costs = out[0], out[1], out[2]
        if cfg.replicates > 1:
            best = jnp.argmin(costs)
            cents, alphas, costs = cents[best], alphas[best], costs[best]
            if traces is not None:
                traces = {name: vals[best] for name, vals in traces.items()}
        if traces is not None and not isinstance(costs, jax.core.Tracer):
            for name, vals in traces.items():
                obs_trace.series(
                    f"decoder.{cfg.decoder}.{name}",
                    jnp.asarray(vals),
                    decoder=cfg.decoder,
                )
        return cents, alphas, costs


def fit(key: jax.Array, x: jax.Array, cfg: CKMConfig, mesh=None) -> CKMResult:
    """End-to-end compressive K-means on an in-memory dataset (under the
    root span ``ckm.fit``)."""
    with obs_trace.span("ckm.fit", streaming=False):
        k_sketch, k_dec = jax.random.split(key)
        z, op, sigma2, (lo, hi) = compute_sketch(k_sketch, x, cfg, mesh)
        x_init = x if cfg.init in ("sample", "kpp") else None
        cents, alphas, cost = decode_sketch(k_dec, z, op, lo, hi, cfg, x_init)
        return CKMResult(cents, alphas, cost, sigma2, op, z, (lo, hi))


def fit_streaming(
    key: jax.Array, batches: Iterable[jax.Array], cfg: CKMConfig, mesh=None
) -> CKMResult:
    """End-to-end CKM over an out-of-core iterator of ``(B_i, n)`` batches.

    One pass, O(m) memory: each batch is folded into the engine state and may
    be discarded immediately — the dataset never has to fit in memory, which
    is the paper's whole point (cost after sketching is N-independent).  The
    "sample"/"kpp" decoder inits draw from the *first* batch only (the rest
    of the stream is gone by decode time).  The whole fit runs under the
    root span ``ckm.fit``; finalize is its own time.
    """
    with obs_trace.span("ckm.fit", streaming=True):
        k_sketch, k_dec = jax.random.split(key)
        z, op, sigma2, (lo, hi), first = compute_sketch_streaming(
            k_sketch, batches, cfg, mesh
        )
        x_init = first if cfg.init in ("sample", "kpp") else None
        cents, alphas, cost = decode_sketch(k_dec, z, op, lo, hi, cfg, x_init)
        return CKMResult(cents, alphas, cost, sigma2, op, z, (lo, hi))


def diagnose(result: CKMResult, **kwargs):
    """Attribute a (possibly bad) fit to sketch size m, frequency scale
    sigma, or the decoder — ``repro.obs.diagnose.diagnose`` re-exported at
    the pipeline API (``ckm.diagnose(ckm.fit(...))``).  Data-free: the probe
    decodes run on the result's own sketch; see the full parameter list and
    the verdict semantics in :mod:`repro.obs.diagnose`.
    """
    from repro.obs.diagnose import diagnose as obs_diagnose

    return obs_diagnose(result, **kwargs)


# ---------------------------------------------------------------------------
# Evaluation helpers (need data access — used for experiments only)
# ---------------------------------------------------------------------------

# Exact f32 distances on the TPU too (its default f32 matmul is one bf16 pass).
_HI = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("chunk",))
def sse(x: jax.Array, centroids: jax.Array, chunk: int = 16384) -> jax.Array:
    """Sum of squared errors (1):  sum_i min_k ||x_i - c_k||^2 (chunked over N)."""
    x = jnp.asarray(x, jnp.float32)
    n_pts = x.shape[0]
    pad = (-n_pts) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    valid = jnp.arange(x.shape[0]) < n_pts
    xs = x.reshape(-1, chunk, x.shape[1])
    vs = valid.reshape(-1, chunk)
    c2 = jnp.sum(centroids * centroids, axis=1)  # (K,)

    def body(acc, inp):
        xc, vc = inp
        d2 = (
            jnp.sum(xc * xc, axis=1, keepdims=True)
            - 2.0 * jnp.matmul(xc, centroids.T, precision=_HI)
            + c2[None, :]
        )
        return acc + jnp.sum(jnp.where(vc, jnp.min(d2, axis=1), 0.0)), None

    total, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32), (xs, vs))
    return total


@functools.partial(jax.jit, static_argnames=("chunk",))
def predict(
    x: jax.Array, centroids: jax.Array, chunk: int = 16384
) -> jax.Array:
    """Hard assignment of each point to its nearest centroid (chunked over N).

    Same pad+scan scheme as :func:`sse`: the ``(N, K)`` distance matrix never
    materialises — only one ``(chunk, K)`` block lives at a time, so the
    assignment pass works at the paper's N = 10^7 scale in O(chunk·K) memory.
    """
    x = jnp.asarray(x, jnp.float32)
    n_pts = x.shape[0]
    # N is a trace-time constant: shrink the chunk to it so small inputs
    # (e.g. per-head KV caches on the serving path) don't pad up to 16384
    # rows of wasted distance work.  jit retraces per shape anyway.
    chunk = min(chunk, max(n_pts, 1))
    pad = (-n_pts) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0)
    xs = x.reshape(-1, chunk, x.shape[1])
    c2 = jnp.sum(centroids * centroids, axis=1)  # (K,)

    def body(_, xc):
        d2 = (
            jnp.sum(xc * xc, axis=1, keepdims=True)
            - 2.0 * jnp.matmul(xc, centroids.T, precision=_HI)
            + c2[None, :]
        )
        return None, jnp.argmin(d2, axis=1)

    _, labels = jax.lax.scan(body, None, xs)
    return labels.reshape(-1)[:n_pts]
