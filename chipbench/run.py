"""Run one benchmark cell once and print its result line.

    python3 -m chipbench.run --workload fit_paper --seed 7 --seconds 10 --trace 0

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  ``--plant <fault>`` breaks the program underneath
with one of ``chipbench.faults`` (the control among them), to show that
``correct`` then reads false; the benchmark's own runs plant nothing.
"""

from __future__ import annotations

import argparse
import contextlib
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from chipbench import harness  # noqa: E402  (starts the set-up clock)
from chipbench import faults  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", choices=faults.FAULTS,
                    help="break the program with this fault (chipbench.faults)")
    args = ap.parse_args(argv)
    try:
        planted = contextlib.nullcontext()
        if args.plant:
            kind = harness.load_cell(args.workload).config["kind"]
            planted = faults.planted(kind, args.plant)
        with planted:
            result = harness.run_cell(args.workload, args.seed, args.seconds,
                                      bool(args.trace))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
