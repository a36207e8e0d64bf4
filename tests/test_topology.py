"""Reduction topologies: schedule invariance, stragglers, in-mesh parity.

The load-bearing property (ISSUE 4 acceptance): for ANY registered merge
schedule and ANY straggler arrival order, the reduced monoid state is
- **bitwise equal** on the int32 quantized path (integer addition is exactly
  associative and commutative), and
- equal to 1e-6 on the float path (schedules only re-associate sums).

Device-level, the sharded backend's collective merge must produce the same
sketch for every ``reduce_topology`` — checked in a subprocess with 8 forced
host devices, bitwise on the quantized path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st

from repro.core import engine as eng_mod
from repro.core import frequencies as fq
from repro.core import quantize as qz
from repro.core import topology as topo
from repro.data import pipeline as pipe
from repro.launch.specs import SketchJobSpec

TOPOLOGY_NAMES = ("allreduce", "tree", "ring")


def _partials(seed, n_parts, quantized, npts=600, n=4, m=32):
    key = jax.random.PRNGKey(seed)
    kx, kw, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, (npts, n)) * 2.0
    w = fq.draw_frequencies(kw, m, n, 1.0)
    q = qz.make_quantizer(kd, m, "1bit") if quantized else None
    e = eng_mod.SketchEngine(w, "xla", chunk=128, quantizer=q)
    size = max(1, npts // n_parts)
    return e, [e.update(e.init_state(), b) for b in pipe.chunked(x, size)]


class TestRegistry:
    def test_names(self):
        assert set(topo.available_topologies()) >= set(TOPOLOGY_NAMES)
        with pytest.raises(ValueError):
            topo.get_topology("hypercube9000")
        with pytest.raises(ValueError):
            eng_mod.SketchEngine(
                jnp.ones((2, 4)), "xla", reduce_topology="hypercube9000"
            )

    def test_register_rejects_collisions(self):
        with pytest.raises(ValueError):
            topo.register_topology(topo.get_topology("tree"))

    def test_plans_cover_every_state_once(self):
        """Every schedule merges each non-root slot exactly once as a source."""
        for name in TOPOLOGY_NAMES:
            for n in (1, 2, 3, 5, 8, 13):
                plan = topo.merge_schedule(n, name)
                srcs = [s for rnd in plan for _, s in rnd]
                root = topo.get_topology(name).root(n)
                assert sorted(srcs + [root]) == list(range(n)), (name, n)

    def test_wire_cost_model(self):
        # log2(8)=3 hops tree; 7 hops ring; psum ring RS+AG moves the least.
        s = 1024
        costs = {t: topo.wire_cost_model(s, 8, t) for t in TOPOLOGY_NAMES}
        assert costs["tree"]["hops"] == 3
        assert costs["ring"]["hops"] == 7
        assert (
            costs["allreduce"]["bytes_per_device"]
            < costs["tree"]["bytes_per_device"]
            < costs["ring"]["bytes_per_device"]
        )
        assert topo.wire_cost_model(s, 1, "ring")["bytes_per_device"] == 0


class TestScheduleInvariance:
    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        n_parts=st.integers(1, 9),
        order_seed=st.integers(0, 2**31 - 1),
    )
    def test_quantized_bitwise_any_schedule_any_order(
        self, seed, n_parts, order_seed
    ):
        """Acceptance: any topology x any straggler order -> bitwise-equal
        int32 state on the quantized path."""
        e, parts = _partials(seed, n_parts, quantized=True)
        ref = None
        rng = np.random.default_rng(order_seed)
        for name in TOPOLOGY_NAMES:
            order = list(rng.permutation(len(parts)))
            s = topo.reduce_states(e.merge, parts, name, order=order)
            if ref is None:
                ref = s
                continue
            assert bool(jnp.array_equal(ref.qcos_acc, s.qcos_acc)), name
            assert bool(jnp.array_equal(ref.qsin_acc, s.qsin_acc)), name
            assert bool(jnp.array_equal(ref.lower, s.lower)), name
            assert bool(jnp.array_equal(ref.upper, s.upper)), name
            np.testing.assert_allclose(
                float(ref.weight_sum), float(s.weight_sum)
            )

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n_parts=st.integers(1, 9))
    def test_float_schedules_agree_to_1e6(self, seed, n_parts):
        e, parts = _partials(seed, n_parts, quantized=False)
        finals = [
            e.finalize(topo.reduce_states(e.merge, parts, name))
            for name in TOPOLOGY_NAMES
        ]
        for z, lo, hi in finals[1:]:
            np.testing.assert_allclose(
                np.asarray(z), np.asarray(finals[0][0]), atol=1e-6
            )
            np.testing.assert_allclose(np.asarray(lo), np.asarray(finals[0][1]))
            np.testing.assert_allclose(np.asarray(hi), np.asarray(finals[0][2]))

    def test_straggler_merger_matches_schedules(self):
        """Online arrival-order fold == any scheduled reduction (bitwise)."""
        e, parts = _partials(11, 7, quantized=True)
        ref = topo.reduce_states(e.merge, parts, "tree")
        sm = topo.StragglerMerger(e.merge, e.init_state())
        for i in np.random.default_rng(0).permutation(len(parts)):
            sm.add(parts[i])
        late = sm.result()
        assert sm.arrived == len(parts)
        assert bool(jnp.array_equal(ref.qcos_acc, late.qcos_acc))
        assert bool(jnp.array_equal(ref.qsin_acc, late.qsin_acc))

    def test_reduce_partials_method(self):
        e, parts = _partials(3, 5, quantized=False)
        z_a, *_ = e.finalize(e.reduce_partials(parts))
        z_r, *_ = e.finalize(e.reduce_partials(parts, "ring"))
        np.testing.assert_allclose(np.asarray(z_a), np.asarray(z_r), atol=1e-6)

    def test_bad_order_rejected(self):
        e, parts = _partials(5, 4, quantized=False)
        with pytest.raises(ValueError):
            topo.reduce_states(e.merge, parts, "tree", order=[0, 0, 1, 2])
        with pytest.raises(ValueError):
            topo.reduce_states(e.merge, [], "tree")


class TestShardedTopologies:
    def test_in_mesh_parity_all_topologies(self):
        """Subprocess, 8 host devices: every reduce_topology matches the
        reference sketch (float, 1e-4) and is bitwise-identical across
        topologies on the quantized path — the collective IS the monoid
        merge under every schedule."""
        import os
        import subprocess
        import sys
        import textwrap

        prog = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, numpy as np
            import jax.numpy as jnp
            from repro.core import engine as eng_mod
            from repro.core import frequencies as fq
            from repro.core import quantize as qz
            from repro.core import sketch as sk
            from repro.data.pipeline import chunked

            key = jax.random.PRNGKey(0)
            kx, kw, kd = jax.random.split(key, 3)
            x = jax.random.normal(kx, (4096, 6))
            w = fq.draw_frequencies(kw, 48, 6, 1.0)
            z_ref = np.asarray(sk.sketch(x, w))
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))

            for name in ("allreduce", "tree", "ring"):
                e = eng_mod.SketchEngine(w, "sharded", mesh=mesh, chunk=512,
                                         reduce_topology=name)
                z, lo, hi = e.sketch(x)
                err = float(np.max(np.abs(np.asarray(z) - z_ref)))
                assert err < 1e-4, (name, err)
                np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)),
                                           atol=1e-6)
                np.testing.assert_allclose(np.asarray(hi), np.asarray(x.max(0)),
                                           atol=1e-6)
                # ragged streaming tail through the same topology
                z2, lo2, _ = e.sketch_stream(chunked(x[:4003], 1000))
                err2 = float(np.max(np.abs(
                    np.asarray(z2) - np.asarray(sk.sketch(x[:4003], w)))))
                assert err2 < 1e-4, (name, "ragged", err2)

            q = qz.make_quantizer(kd, 48, "1bit")
            states = []
            for name in ("allreduce", "tree", "ring"):
                e = eng_mod.SketchEngine(w, "sharded", mesh=mesh, chunk=512,
                                         quantizer=q, reduce_topology=name)
                states.append(e.update(e.init_state(), x))
            for s in states[1:]:
                assert bool(jnp.array_equal(states[0].qcos_acc, s.qcos_acc))
                assert bool(jnp.array_equal(states[0].qsin_acc, s.qsin_acc))
            print("OK")
            """
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout

    def test_tree_requires_power_of_two_axis(self):
        """The butterfly needs 2^k devices; the error must say what to use."""
        import os
        import subprocess
        import sys
        import textwrap

        prog = textwrap.dedent(
            """
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
            import jax
            from repro.core import engine as eng_mod
            from repro.core import frequencies as fq

            w = fq.draw_frequencies(jax.random.PRNGKey(0), 16, 4, 1.0)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((3, 2), ("data", "model"))
            e = eng_mod.SketchEngine(w, "sharded", mesh=mesh,
                                     reduce_topology="tree")
            try:
                e.sketch(jax.random.normal(jax.random.PRNGKey(1), (96, 4)))
            except ValueError as err:
                assert "power-of-two" in str(err), err
                print("OK")
            """
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True,
            text=True, timeout=300,
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "OK" in out.stdout


class TestSketchJobSpec:
    def test_validates_against_registries(self):
        SketchJobSpec(backend="sharded", reduce_topology="ring").validate()
        with pytest.raises(ValueError):
            SketchJobSpec(reduce_topology="star").validate()
        with pytest.raises(ValueError):
            SketchJobSpec(backend="tpu9000").validate()
        with pytest.raises(ValueError):
            SketchJobSpec(ingest="eager").validate()
        with pytest.raises(ValueError):
            SketchJobSpec(ingest_prefetch=0).validate()
        SketchJobSpec(decoder="amp").validate()
        with pytest.raises(KeyError):
            SketchJobSpec(decoder="nope").validate()

    def test_ckm_overrides_round_trip(self):
        import dataclasses

        from repro.core import ckm as ckm_mod

        spec = SketchJobSpec(
            reduce_topology="tree", ingest="async", ingest_prefetch=4,
            sketch_quantization="1bit", decoder="amp",
        )
        cfg = dataclasses.replace(
            ckm_mod.CKMConfig(k=3), **spec.ckm_overrides()
        )
        assert cfg.reduce_topology == "tree"
        assert cfg.ingest == "async" and cfg.ingest_prefetch == 4
        assert cfg.sketch_quantization == "1bit"
        assert cfg.decoder == "amp"
        assert "topology=tree" in spec.describe()
        assert "decoder=amp" in spec.describe()
