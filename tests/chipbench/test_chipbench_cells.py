"""Each cell rehearsed end to end on the CPU at tiny sizes (kernels in
interpret mode), the runner's refusal without a TPU, and a toy cell added
from files alone."""

import json
import os
import subprocess
import sys

import pytest

import tiny
from chipbench import harness

E2E = {"fit_paper": "fit_s"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    yield tiny.tiny_root(tmp_path_factory.mktemp("bench"))
    tiny.restore_jax_cache_config()


@pytest.mark.parametrize("workload", sorted(E2E))
def test_cell_rehearsal(root, workload):
    r = harness.run_cell(workload, 2**31 + 5, 0.5, False, root=root,
                         require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {E2E[workload], "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"  # the compared numbers come last


def test_traced_rehearsal_reports_per_layer_metrics(root):
    r = harness.run_cell("fit_paper", 3, 0.5, True, root=root,
                         require_tpu=False)
    assert r["correct"]
    # On the CPU there is no device plane: device readers find nothing,
    # the compile counter still reads.
    assert set(r["metrics"]) == {"jit_compiles.fit"}
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs():
    import numpy as np

    from chipbench import traffic

    def draw(seed):
        key = harness.seed_key(seed)
        chunks, means = traffic.mixture_chunks(key, 4096, 1024, 2, 3, 1.5)
        return np.concatenate([np.asarray(c) for c in chunks])

    xa, xb, xc = draw(2**33 + 1), draw(2**33 + 1), draw(2**33 + 2)
    assert np.array_equal(xa, xb)
    assert not np.array_equal(xa, xc)


def test_toy_cell_from_files_alone(root):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and new BENCHMARK.json entries, no existing file edited."""
    cb = root / "chipbench"
    cfg = json.loads((cb / "configs" / "ckm_paper_1e7.json").read_text())
    cfg.update(name="toy_fit", k=3)
    (cb / "configs" / "toy_fit.json").write_text(json.dumps(cfg))
    traffic = json.loads((cb / "traffic" / "fit_repeat.json").read_text())
    traffic["checked_fits"] = 1
    (cb / "traffic" / "toy_repeat.json").write_text(json.dumps(traffic))
    (cb / "metrics" / "toy_fits.toy.py").write_text(
        "def read(ctx, device_kind):\n    return ctx.counts['fits']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy_fit", "source": "toy",
                             "file": "chipbench/configs/toy_fit.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toy_cell", "config": "toy_fit",
                               "traffic": "toy_repeat", "chips": 1, "why": "toy"})
    bench["end_to_end"][0]["workloads"].append("toy_cell")
    bench["per_layer"].append({"name": "toy_fits.toy", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "toy", "moves": "fit_s",
                               "workloads": ["toy_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = harness.run_cell("toy_cell", 9, 0.2, True, root=root, require_tpu=False)
    assert r["correct"]
    assert r["metrics"]["toy_fits.toy"]["value"] >= 1


def _run(args, cwd, env):
    return subprocess.run([sys.executable, "-m", "chipbench.run", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "fit_paper", "--seed", "1", "--seconds", "1", "--trace", "0"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ARGS, tiny.REPO, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files
    (no program) fails with no result."""
    import shutil

    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.REPO / "chipbench", tmp_path / "chipbench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = _run(ARGS, tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
