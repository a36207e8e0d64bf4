"""Substrate tests: optimizers, checkpointing, data determinism, train loop
fault tolerance (checkpoint/restart), gradient compression, balancer."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.optimizers import OptConfig, make_optimizer


class TestOptimizers:
    def _quadratic_converges(self, name):
        cfg = OptConfig(name=name, lr=0.1, warmup=5, total_steps=300, weight_decay=0.0)
        opt = make_optimizer(cfg)
        params = {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(5.0)}
        state = opt.init(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2) + p["b"] ** 2

        for step in range(300):
            g = jax.grad(loss)(params)
            params, state, _ = opt.update(g, state, params, jnp.asarray(step))
        assert float(loss(params)) < 1e-2, (name, float(loss(params)))

    @pytest.mark.parametrize("name", ["adamw", "adamw8", "adafactor", "sgd"])
    def test_converges_on_quadratic(self, name):
        self._quadratic_converges(name)

    def test_adamw8_tracks_adamw(self):
        """int8 state quantisation stays close to exact Adam trajectories."""
        key = jax.random.PRNGKey(0)
        w0 = jax.random.normal(key, (64, 32))
        target = jax.random.normal(jax.random.PRNGKey(1), (64, 32))

        def run(name):
            opt = make_optimizer(OptConfig(name=name, lr=0.05, warmup=1,
                                           total_steps=100, weight_decay=0.0))
            p = {"w": w0}
            s = opt.init(p)
            for i in range(60):
                g = jax.grad(lambda pp: jnp.mean((pp["w"] - target) ** 2))(p)
                p, s, _ = opt.update(g, s, p, jnp.asarray(i))
            return p["w"]

        exact = run("adamw")
        quant = run("adamw8")
        rel = float(jnp.linalg.norm(exact - quant) / jnp.linalg.norm(exact))
        assert rel < 0.10, rel

    def test_adafactor_memory_factored(self):
        opt = make_optimizer(OptConfig(name="adafactor"))
        params = {"w": jnp.zeros((128, 64))}
        state = opt.init(params)
        n_state = sum(x.size for x in jax.tree.leaves(state["stats"]))
        assert n_state == 128 + 64  # vr + vc, not 128*64

    def test_grad_clipping(self):
        from repro.optim.optimizers import clip_by_global_norm

        g = {"a": jnp.full((10,), 100.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(norm) > 100
        total = jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(clipped)))
        np.testing.assert_allclose(float(total), 1.0, rtol=1e-5)


class TestCheckpointer:
    def test_roundtrip_and_latest(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        ck = Checkpointer(tmp_path, keep=2)
        state = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                 "step": jnp.asarray(7)}
        ck.save(7, state)
        ck.save(14, jax.tree.map(lambda x: x * 2, state))
        assert ck.latest_step() == 14
        restored = ck.restore(state, step=7)
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(state["w"]))

    def test_retention_prunes(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        ck = Checkpointer(tmp_path, keep=2)
        state = {"x": jnp.zeros(3)}
        for s in (1, 2, 3, 4):
            ck.save(s, state)
        assert ck.all_steps() == [3, 4]

    def test_torn_checkpoint_ignored(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        ck = Checkpointer(tmp_path, keep=3)
        state = {"x": jnp.ones(4)}
        ck.save(5, state)
        # simulate a crash mid-write: tmp dir + a final dir missing manifest
        (tmp_path / "step_0000000009.tmp").mkdir()
        (tmp_path / "step_0000000008").mkdir()
        assert ck.latest_step() == 5
        restored = ck.restore(state)
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.ones(4))

    def test_async_save(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        ck = Checkpointer(tmp_path, keep=3)
        state = {"x": jnp.full((1000,), 3.0)}
        ck.save_async(11, state)
        ck.wait()
        restored = ck.restore(state, step=11)
        np.testing.assert_array_equal(np.asarray(restored["x"]), np.full(1000, 3.0))


class TestDataPipeline:
    def test_deterministic_restart(self):
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_smoke_config("llama3.2-1b")
        shape = ShapeConfig("t", 32, 4, "train")
        a = SyntheticLM(cfg, shape, DataConfig(seed=3))
        b = SyntheticLM(cfg, shape, DataConfig(seed=3))
        ba, bb = a.batch(17), b.batch(17)
        np.testing.assert_array_equal(np.asarray(ba["tokens"]), np.asarray(bb["tokens"]))

    def test_labels_shifted(self):
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_smoke_config("llama3.2-1b")
        shape = ShapeConfig("t", 32, 4, "train")
        s = SyntheticLM(cfg, shape, DataConfig(seed=0))
        batch = s.batch(0)
        assert batch["tokens"].shape == (4, 32) and batch["labels"].shape == (4, 32)

    def test_mixture_reweighting_changes_domain_rates(self):
        from repro.configs.base import ShapeConfig, get_smoke_config
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_smoke_config("llama3.2-1b")
        shape = ShapeConfig("t", 16, 64, "train")
        s = SyntheticLM(cfg, shape, DataConfig(seed=1, n_domains=4))
        s.set_domain_weights(np.array([1.0, 0.0, 0.0, 0.0]))
        batch = s.batch(0)
        assert np.all(np.asarray(batch["_domains"]) == 0)


class TestBalancer:
    def test_recovers_planted_imbalance(self):
        """CKM-from-sketch finds domain mass; balancer inverts it."""
        from repro.data.clustering import CompressiveBalancer

        key = jax.random.PRNGKey(0)
        cents = jax.random.normal(key, (3, 4)) * 8.0
        # domain mass 0.6 / 0.3 / 0.1
        counts = np.array([1800, 900, 300])
        pts = jnp.concatenate(
            [
                cents[i] + jax.random.normal(jax.random.PRNGKey(i), (int(c), 4))
                for i, c in enumerate(counts)
            ]
        )
        bal = CompressiveBalancer(k=3, dim=4, seed=5)
        for i in range(0, pts.shape[0], 500):
            bal.update(pts[i : i + 500])
        res = bal.cluster()
        alpha = np.sort(np.asarray(res.weights))[::-1]
        np.testing.assert_allclose(alpha, [0.6, 0.3, 0.1], atol=0.08)
        w = bal.balanced_weights(res)
        # heaviest cluster gets the smallest sampling weight
        assert np.argmin(w) == np.argmax(np.asarray(res.weights))


_TRAIN_LOOP = textwrap.dedent(
    """
    import jax, jax.numpy as jnp, numpy as np, sys
    from repro.configs.base import ShapeConfig, get_smoke_config
    from repro.launch.mesh import make_local_mesh
    from repro.train.train_loop import LoopConfig, run
    from repro.data.pipeline import DataConfig

    ckpt_dir = sys.argv[1]
    steps = int(sys.argv[2])
    cfg = get_smoke_config("llama3.2-1b")
    shape = ShapeConfig("t", 32, 4, "train")
    mesh = make_local_mesh()
    loop = LoopConfig(steps=steps, ckpt_dir=ckpt_dir, ckpt_every=3,
                      monitor_k=2, log_every=2, dtype=jnp.float32)
    out = run(cfg, shape, mesh, loop, DataConfig(seed=0))
    print("FINAL", out["history"][-1]["step"], out["history"][-1]["loss"])
    cents = np.asarray(out["monitor_result"].centroids)
    assert np.all(np.isfinite(cents))
    """
)


@pytest.mark.slow
class TestTrainLoopFaultTolerance:
    def test_checkpoint_restart_matches_uninterrupted(self, tmp_path):
        """Train 6 steps straight vs 3 + restart + 3: identical final loss."""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        env.pop("XLA_FLAGS", None)

        def run_loop(d, steps):
            out = subprocess.run(
                [sys.executable, "-c", _TRAIN_LOOP, str(d), str(steps)],
                env=env, capture_output=True, text=True, timeout=600,
            )
            assert out.returncode == 0, out.stderr[-3000:]
            final = [l for l in out.stdout.splitlines() if l.startswith("FINAL")][-1]
            return float(final.split()[2])

        straight = run_loop(tmp_path / "a", 6)
        run_loop(tmp_path / "b", 3)  # writes ckpt at step 3
        resumed = run_loop(tmp_path / "b", 6)  # resumes from step 3
        np.testing.assert_allclose(resumed, straight, rtol=1e-4)


class TestGradCompression:
    def test_compressed_allreduce_with_error_feedback(self):
        prog = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import PartitionSpec as P
            from repro.optim.grad_compression import (
                compress_allreduce_tree, init_error_state)
            from repro.utils.compat import shard_map

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2), ("pod", "data"))
            n = 4096
            key = jax.random.PRNGKey(0)
            g_pods = jax.random.normal(key, (2, n))  # one grad per pod
            exact = jnp.sum(g_pods, axis=0)

            def body(g, e):
                return compress_allreduce_tree({"g": g[0]}, {"g": e}, "pod")

            fn = shard_map(body, mesh=mesh,
                               in_specs=(P("pod"), P("pod")),
                               out_specs=({"g": P()}, {"g": P("pod")}),
                               axis_names={"pod"}, check_vma=True)

            err = jnp.zeros((2, n))
            # accumulated compressed sums over repeated steps track the exact
            # sum thanks to error feedback.
            acc_c = jnp.zeros(n); acc_e = jnp.zeros(n)
            for _ in range(20):
                out, err_d = fn(g_pods, err)
                err = err_d["g"]
                acc_c = acc_c + out["g"]
                acc_e = acc_e + exact
            rel = float(jnp.linalg.norm(acc_c - acc_e) / jnp.linalg.norm(acc_e))
            assert rel < 0.01, rel
            # single-shot quantisation error is bounded by the int16 grid
            one, _ = fn(g_pods, jnp.zeros((2, n)))
            amax = float(jnp.max(jnp.abs(g_pods)))
            assert float(jnp.max(jnp.abs(one["g"] - exact))) <= 2 * amax / 8192 + 1e-6
            print("OK")
            """
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True, text=True,
            timeout=300,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout
