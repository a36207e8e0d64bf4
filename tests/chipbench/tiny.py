"""A copy of the benchmark with every cell cut to CPU size, for the tests."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CONFIGS = {
    "ckm_paper_1e7": {"points": 8192, "chunk": 2048, "n": 3, "k": 2, "m": 60},
}
TINY_TRAFFIC = {
    "fit_repeat": {"checked_fits": 2},
}


def tiny_root(tmp: Path) -> Path:
    """``tmp`` holding BENCHMARK.json and chipbench/ with tiny sizes."""
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name, sizes in TINY_CONFIGS.items():
        path = tmp / "chipbench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(sizes)
        path.write_text(json.dumps(cfg))
    for name, params in TINY_TRAFFIC.items():
        path = tmp / "chipbench" / "traffic" / f"{name}.json"
        t = json.loads(path.read_text())
        t.update(params)
        path.write_text(json.dumps(t))
    return tmp


def restore_jax_cache_config():
    """Undo ``harness.enable_compile_cache`` for the rest of the session."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
