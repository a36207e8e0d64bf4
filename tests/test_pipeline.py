"""GPipe pipeline parallelism: schedule correctness vs sequential reference."""

import os
import subprocess
import sys
import textwrap

_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import pipeline_apply, bubble_fraction

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("pipe",))
    n_stages, n_micro, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    kw, kx = jax.random.split(key)
    ws = jax.random.normal(kw, (n_stages, d, d)) / jnp.sqrt(d)
    x = jax.random.normal(kx, (n_micro, mb, d))

    def stage_fn(w, h):
        return jnp.tanh(h @ w)

    out = pipeline_apply(stage_fn, ws, x, mesh, axis="pipe")

    # sequential reference: apply the 4 stages in order to each microbatch
    ref = x
    for s in range(n_stages):
        ref = jax.vmap(lambda h: stage_fn(ws[s], h))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    # the compiled schedule must use point-to-point collective-permute
    c = jax.jit(lambda ws, x: pipeline_apply(stage_fn, ws, x, mesh)).lower(ws, x).compile()
    assert "collective-permute" in c.as_text()
    assert abs(bubble_fraction(4, 8) - 3 / 11) < 1e-9
    print("OK")
    """
)


def test_gpipe_schedule_matches_sequential():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _PROG], env=env, capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
