"""Faults planted in the quantized fit, and its control, to show that the
comparison deciding ``correct`` of a ``ckm_qfit_streaming`` cell catches
them.

    python3 -m chipbench.faults_qckm --workload qfit_paper_1bit --seconds 5 \\
        --seed 7 8 9 --plant control dither_dropped

runs the cell once per fault and seed, in one process, through
``harness.run_cell``, and prints each result line after a ``[plant]`` line
on stderr.  The benchmark's own runs plant nothing.  Each entry breaks the
timed path underneath the benchmark, where the program produces its result:

- ``control``: the reference put in the program's place for the sketch,
  its codes taken from one-pass bf16 phases
  (``reference_qckm.sketch(..., "bf16")``) at the frequencies and dither
  the program draws;
- ``state_unchanged``: a sketch step returns its state unchanged;
- ``answer_altered``: the decoded centroids are negated where they are
  produced;
- ``dither_dropped``: the program's dither is 0 at every frequency;
- ``float_sketch``: the program sketches in float (no quantizer) where the
  configuration asks for 1-bit codes.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
from pathlib import Path
from unittest import mock

FAULTS = ("control", "state_unchanged", "answer_altered", "dither_dropped",
          "float_sketch")


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program while the block runs."""
    with contextlib.ExitStack() as stack:
        for obj, name, value in _patches(fault):
            stack.enter_context(mock.patch.object(obj, name, value))
        yield


def _patches(fault: str):
    import jax.numpy as jnp

    from chipbench import reference_qckm as rq
    from repro.core import ckm, quantize
    from repro.core.engine import SketchEngine

    if fault == "control":
        def control_sketch(key, batches, cfg, mesh=None):
            chunks = [jnp.asarray(b, jnp.float32) for b in batches]
            op, sigma2 = ckm._draw_freqs(key, chunks[0], chunks[0].shape[1], cfg)
            q = ckm.make_quantizer(key, cfg, op.m)
            z, lo, hi = rq.sketch(chunks, op.materialize(), q.dither, "bf16")
            return jnp.asarray(z, jnp.float32), op, sigma2, (lo, hi), chunks[0]

        return [(ckm, "compute_sketch_streaming", control_sketch)]
    if fault == "state_unchanged":
        return [(SketchEngine, "update", lambda self, state, batch, *a, **k: state)]
    if fault == "answer_altered":
        decode = ckm.decode_sketch

        def altered(*a, **k):
            c, w, cost = decode(*a, **k)
            return -c, w, cost

        return [(ckm, "decode_sketch", altered)]
    if fault == "dither_dropped":
        return [(quantize, "draw_dither",
                 lambda key, m: jnp.zeros((m,), jnp.float32))]
    if fault == "float_sketch":
        return [(ckm, "make_quantizer", lambda *a, **k: None)]
    raise ValueError(f"no fault {fault!r}; known: {FAULTS}")


def main(argv=None) -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    from chipbench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--plant", choices=FAULTS, nargs="+", required=True)
    args = ap.parse_args(argv)
    for fault in args.plant:
        for seed in args.seed:
            harness.log(f"[plant] {fault} seed {seed}")
            try:
                with planted(fault):
                    result = harness.run_cell(args.workload, seed, args.seconds, False)
            except harness.NoChip as e:
                harness.log(f"chipbench: {e}")
                return 2
            harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
