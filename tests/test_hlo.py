"""The trip-count-aware HLO cost analyzer vs known-flop programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.utils import hlo


def _compiled(f, *shapes):
    return jax.jit(f).lower(*shapes).compile()


class TestHloAnalyzer:
    def test_single_matmul_flops(self):
        m, k, n = 128, 256, 512
        c = _compiled(
            lambda a, b: a @ b,
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        )
        costs = hlo.analyze_compiled(c)
        assert costs.flops == pytest.approx(2 * m * k * n, rel=0.01)

    def test_scan_multiplies_by_trip_count(self):
        n_steps = 8
        d = 128

        def f(x, w):
            def body(c, _):
                return c @ w, None

            y, _ = jax.lax.scan(body, x, None, length=n_steps)
            return y

        c = _compiled(
            f,
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
        )
        costs = hlo.analyze_compiled(c)
        assert costs.flops == pytest.approx(n_steps * 2 * d**3, rel=0.01)
        # XLA's own cost_analysis undercounts — that's why this module exists.
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        assert float(ca["flops"]) < costs.flops

    def test_nested_scan(self):
        d, outer, inner = 64, 3, 5

        def f(x, w):
            def inner_body(c, _):
                return c @ w, None

            def outer_body(c, _):
                c, _ = jax.lax.scan(inner_body, c, None, length=inner)
                return c, None

            y, _ = jax.lax.scan(outer_body, x, None, length=outer)
            return y

        c = _compiled(
            f,
            jax.ShapeDtypeStruct((d, d), jnp.float32),
            jax.ShapeDtypeStruct((d, d), jnp.float32),
        )
        costs = hlo.analyze_compiled(c)
        assert costs.flops == pytest.approx(outer * inner * 2 * d**3, rel=0.01)

    def test_batched_dot_flops(self):
        b, m, k, n = 4, 32, 64, 16
        c = _compiled(
            lambda a, w: jnp.einsum("bmk,bkn->bmn", a, w),
            jax.ShapeDtypeStruct((b, m, k), jnp.float32),
            jax.ShapeDtypeStruct((b, k, n), jnp.float32),
        )
        costs = hlo.analyze_compiled(c)
        assert costs.flops == pytest.approx(2 * b * m * k * n, rel=0.01)

    def test_bytes_at_least_io(self):
        n = 1 << 16
        c = _compiled(lambda a: a * 2.0 + 1.0, jax.ShapeDtypeStruct((n,), jnp.float32))
        costs = hlo.analyze_compiled(c)
        assert costs.bytes >= 2 * 4 * n  # read + write once
        assert costs.bytes <= 6 * 4 * n  # and not wildly more

    def test_collectives_counted_with_trip_count(self):
        """psum inside a scanned body over a 4-device mesh."""
        import subprocess, sys, os, textwrap

        prog = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.utils import hlo
            from repro.utils.compat import shard_map

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("d",))
            steps, n = 6, 1024

            def f(x):
                def body(c, _):
                    return jax.lax.psum(c, "d"), None
                y, _ = jax.lax.scan(body, x, None, length=steps)
                return y

            fn = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())
            c = jax.jit(fn).lower(jax.ShapeDtypeStruct((n,), jnp.float32)).compile()
            costs = hlo.analyze_compiled(c)
            expect = steps * n * 4
            assert abs(costs.coll_by_op.get("all-reduce", 0) - expect) / expect < 0.05, costs.coll_by_op
            assert costs.coll_count["all-reduce"] == steps
            print("OK")
        """)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
        env.pop("XLA_FLAGS", None)
        out = subprocess.run(
            [sys.executable, "-c", prog], env=env, capture_output=True, text=True,
            timeout=180,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "OK" in out.stdout


class TestSliceCostSemantics:
    def test_scan_xs_not_billed_full_per_iteration(self):
        """A scan body dynamic-slices its stacked xs: per-iteration bytes must
        be slice-sized, not the whole stacked tensor (the xlstm 369 TiB
        phantom of EXPERIMENTS §Perf P5)."""
        import jax, jax.numpy as jnp

        steps, d = 64, 128

        def f(xs):
            def body(c, x):
                return c + jnp.sum(x * 2.0), None

            out, _ = jax.lax.scan(body, jnp.zeros(()), xs)
            return out

        c = jax.jit(f).lower(
            jax.ShapeDtypeStruct((steps, d, d), jnp.float32)
        ).compile()
        costs = hlo.analyze_compiled(c)
        full_every_iter = steps * steps * d * d * 4
        one_pass = steps * d * d * 4
        assert costs.bytes < 0.2 * full_every_iter, costs.bytes
        assert costs.bytes >= one_pass, costs.bytes
