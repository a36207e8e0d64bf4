"""Benchmark runner — one module per paper table/figure (+ kernels).

``PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1,...]``
Prints ``name,us_per_call,derived`` CSV lines; JSON artifacts land in
experiments/paper/.  Default sizes are reduced for the CPU container
(noted inside each module); --full restores paper-scale.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, help="comma list: fig1,fig2,fig3,fig4,kernels")
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from benchmarks import (
        fig1_init,
        fig2_frequencies,
        fig3_spectral,
        fig4_scaling,
        kernels,
    )

    suites = {
        "fig1": fig1_init.run,
        "fig2": fig2_frequencies.run,
        "fig3": fig3_spectral.run,
        "fig4": fig4_scaling.run,
        "kernels": kernels.run,
    }
    wanted = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failures = 0
    for name in wanted:
        try:
            suites[name](full=args.full)
        except Exception:
            traceback.print_exc()
            failures += 1
    if failures:
        sys.exit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
