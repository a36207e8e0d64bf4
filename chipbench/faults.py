"""Faults planted in the program, and the control, to show that the
comparison deciding ``correct`` catches them.

    python3 -m chipbench.run --workload fit_paper --seed 7 --seconds 5 --trace 0 --plant control

The benchmark's own runs plant nothing.  Each entry breaks the timed path
underneath the benchmark, where the program produces its result:

- ``control``: the reference put in the program's place for the sketch,
  computed at the next precision below the configuration's float32 (one
  bf16 pass, ``reference.sketch(..., "bf16")``) on the frequencies the
  program draws;
- ``state_unchanged``: a sketch step returns its state unchanged;
- ``half_batch``: half of every batch is left out (the mean is then taken
  over the rest);
- ``answer_altered``: the decoded centroids are negated where they are
  produced;
- ``sigma2_scaled``: the frequency scale the program estimates is doubled;
- ``radius_law``: the program draws its frequencies from the Gaussian law
  instead of the adapted radius.

One-chip cells exchange nothing between chips, so that fault has no place.
"""

from __future__ import annotations

import contextlib

FAULTS = ("control", "state_unchanged", "half_batch", "answer_altered",
          "sigma2_scaled", "radius_law")


@contextlib.contextmanager
def planted(kind: str, fault: str):
    """Plant ``fault`` for a cell runner of ``kind`` while the block runs."""
    patches = _patches(kind, fault)
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    try:
        for obj, name, value in patches:
            setattr(obj, name, value)
        yield
    finally:
        for obj, name, value in saved:
            setattr(obj, name, value)


def _patches(kind: str, fault: str):
    import jax.numpy as jnp

    from chipbench import reference as ref
    from repro.core import ckm, freq_ops, frequencies
    from repro.core.engine import SketchEngine

    if kind != "ckm_fit_streaming":
        raise ValueError(f"no faults for a {kind!r} cell")
    update = SketchEngine.update
    if fault == "control":
        def control_sketch(key, batches, cfg, mesh=None):
            chunks = [jnp.asarray(b, jnp.float32) for b in batches]
            op, sigma2 = ckm._draw_freqs(key, chunks[0], chunks[0].shape[1], cfg)
            z, lo, hi = ref.sketch(chunks, op.materialize(), "bf16")
            return z, op, sigma2, (lo, hi), chunks[0]

        return [(ckm, "compute_sketch_streaming", control_sketch)]
    if fault == "state_unchanged":
        return [(SketchEngine, "update", lambda self, state, batch, *a, **k: state)]
    if fault == "half_batch":
        return [(SketchEngine, "update", lambda self, state, batch, *a, **k: update(
            self, state, batch[: batch.shape[0] // 2], *a, **k))]
    if fault == "answer_altered":
        decode = ckm.decode_sketch

        def altered(*a, **k):
            c, w, cost = decode(*a, **k)
            return -c, w, cost

        return [(ckm, "decode_sketch", altered)]
    if fault == "sigma2_scaled":
        estimate = frequencies.estimate_sigma2
        return [(frequencies, "estimate_sigma2",
                 lambda *a, **k: 2.0 * estimate(*a, **k))]
    if fault == "radius_law":
        make = freq_ops.make_operator
        return [(freq_ops, "make_operator",
                 lambda *a, **k: make(*a, **{**k, "dist": "gaussian"}))]
    raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
