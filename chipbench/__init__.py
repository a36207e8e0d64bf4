"""Chip benchmark for the Compressive K-means repo, driven by BENCHMARK.json.

One run measures one cell (a configuration under a traffic mix) on a TPU:

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``metrics/<metric>.py``; the configuration's
``kind`` names the cell runner under ``cells/``.
"""
