"""Distributed sketch: shard_map psum-merge must equal the single-host sketch.

Multi-device tests run in a subprocess with XLA_FLAGS host-device overrides so
the main pytest process keeps exactly one CPU device (see conftest note).
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed_sketch as ds
from repro.core import frequencies as fq
from repro.core import sketch as sk


class TestAccumulator:
    def test_update_merge_finalize_equals_batch_sketch(self, rng):
        kx, kw = jax.random.split(rng)
        x = jax.random.normal(kx, (300, 4))
        w = fq.draw_frequencies(kw, 16, 4, 1.0)
        # Stream in 3 uneven chunks through two accumulators, then merge.
        a = ds.init_state(16, 4)
        b = ds.init_state(16, 4)
        a = ds.update(a, x[:50], w)
        a = ds.update(a, x[50:120], w)
        b = ds.update(b, x[120:], w)
        z, lo, hi = ds.finalize(ds.merge(a, b))
        np.testing.assert_allclose(np.asarray(z), np.asarray(sk.sketch(x, w)), atol=1e-5)
        np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)), atol=1e-6)
        np.testing.assert_allclose(np.asarray(hi), np.asarray(x.max(0)), atol=1e-6)

    def test_merge_commutative(self, rng):
        kx, kw = jax.random.split(rng)
        x = jax.random.normal(kx, (100, 3))
        w = fq.draw_frequencies(kw, 8, 3, 1.0)
        a = ds.update(ds.init_state(8, 3), x[:40], w)
        b = ds.update(ds.init_state(8, 3), x[40:], w)
        z1, *_ = ds.finalize(ds.merge(a, b))
        z2, *_ = ds.finalize(ds.merge(b, a))
        np.testing.assert_allclose(np.asarray(z1), np.asarray(z2), atol=1e-6)


_SUBPROC = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import distributed_sketch as ds
    from repro.core import frequencies as fq
    from repro.core import sketch as sk

    assert len(jax.devices()) == 8
    key = jax.random.PRNGKey(0)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (4096, 6))
    w = fq.draw_frequencies(kw, 32, 6, 1.0)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    xs = ds.shard_points(x, mesh, ("data",))
    z, lo, hi = ds.sharded_sketch(xs, w, mesh, ("data",), chunk=512)
    z_ref = sk.sketch(x, w)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(hi), np.asarray(x.max(0)), atol=1e-6)

    # pod x data mesh: merge across both axes.
    mesh2 = make_mesh((2, 2, 2), ("pod", "data", "model"))
    xs2 = ds.shard_points(x, mesh2, ("pod", "data"))
    z2, lo2, hi2 = ds.sharded_sketch(xs2, w, mesh2, ("pod", "data"), chunk=512)
    np.testing.assert_allclose(np.asarray(z2), np.asarray(z_ref), atol=1e-5)
    print("OK")
    """
)


def test_sharded_sketch_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
