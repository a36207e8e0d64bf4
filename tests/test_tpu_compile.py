"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Nothing runs here.  Each kernel is lowered at the paper's widths (n = 10
features, m = 1000 frequencies, 2^20-point batches, K = 10 centroids,
T = 1024 fleet tenants) with ``interpret=False`` and compiled by the TPU
compiler for one chip of a described ``v5e:2x2`` host.  That compiler
refuses what interpret mode on the CPU accepts: a block shape off the
(8, 128) tiling, a primitive Mosaic cannot lower, more VMEM than a kernel
may use.  Every test asserts the compiled program holds the kernel
(``tpu_custom_call``), so no path silently falls back to plain XLA.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.

The mesh-sharded fleet is compiled for all four chips of the described
host: a Pallas kernel inside a multi-device program compiles only within
a ``shard_map``, which interpret mode on forced CPU devices never checks.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import fleet as fl
from repro.core.freq_ops.dense import DenseOperator
from repro.core.freq_ops.structured import StructuredOperator, block_dim
from repro.kernels import ops

N_PTS = 1 << 20  # one streamed chunk of the 10^7-point fit
FEAT = 10
M = 1000  # 10 * K * n
K = 10
TENANTS = 1024
REQUEST = 256  # points per fleet request


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU executable written to a persistent cache cannot be read back
    # without a chip; keep such compiles out of any configured cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


WIDE_FEAT, WIDE_M = 512, 4096  # a WHT block of d = 512 (activation sketches)


def _structured(diags, radii, rho, n=FEAT, m=M):
    return StructuredOperator(diags, radii, rho, n=n, m=m)


def _structured_shapes(n=FEAT, m=M):
    d = block_dim(n)
    nb = -(-m // d)
    return [(nb, 3, d), (nb, d), (nb, d)]


# name -> (function of the operands, operand shapes (dtype f32))
CASES = {
    "fourier_sketch": (
        lambda x, w, b: ops.fourier_sketch_sums(
            x, DenseOperator(w), b, interpret=False
        ),
        [(N_PTS, FEAT), (FEAT, M), (N_PTS,)],
    ),
    "fourier_sketch_1bit": (
        lambda x, w, dth: ops.quantized_fourier_sketch_sums(
            x, DenseOperator(w), dth, bits=1, interpret=False
        ),
        [(N_PTS, FEAT), (FEAT, M), (M,)],
    ),
    "structured_sketch": (
        lambda x, dg, r, rho, b: ops.fourier_sketch_sums(
            x, _structured(dg, r, rho), b, interpret=False
        ),
        [(N_PTS, FEAT)] + _structured_shapes() + [(N_PTS,)],
    ),
    "structured_sketch_1bit": (
        lambda x, dg, r, rho, dth: ops.quantized_fourier_sketch_sums(
            x, _structured(dg, r, rho), dth, bits=1, interpret=False
        ),
        [(N_PTS, FEAT)] + _structured_shapes() + [(M,)],
    ),
    "structured_sketch_d512": (
        lambda x, dg, r, rho, b: ops.fourier_sketch_sums(
            x, _structured(dg, r, rho, WIDE_FEAT, WIDE_M), b, interpret=False
        ),
        [(1 << 16, WIDE_FEAT)] + _structured_shapes(WIDE_FEAT, WIDE_M)
        + [(1 << 16,)],
    ),
    "sketch_shift": (
        lambda c, w, z: ops.sketch_shift_scores(
            c, DenseOperator(w), z, impl="pallas", interpret=False
        ),
        [(8 * K, FEAT), (FEAT, M), (2 * M,)],
    ),
    "amp_denoise": (
        lambda r, q, lo, hi: ops.amp_denoise(
            r, q, lo, hi, impl="pallas", interpret=False
        ),
        [(K, FEAT), (), (FEAT,), (FEAT,)],
    ),
    "assign_argmin": (
        lambda x, c: ops.assign_argmin(x, c, interpret=False),
        [(N_PTS, FEAT), (K, FEAT)],
    ),
    # The fleet's pallas update: the per-tenant sketch vmapped over one
    # request per tenant (core.engine._batch_partial under FleetEngine._parts).
    "fleet_fourier_sketch": (
        jax.vmap(
            lambda w, x, b: ops.fourier_sketch_sums(
                x, DenseOperator(w), b, interpret=False
            )
        ),
        [(TENANTS, FEAT, M), (TENANTS, REQUEST, FEAT), (TENANTS, REQUEST)],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [
        jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip) for s in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "collective-permute")


def test_mesh_fleet_compiles_for_v5e_2x2(topo, monkeypatch):
    """The four-shard fleet's update and ingest (the path ``FleetService``
    flushes through) at T = 1024: the kernel on every chip, no collective."""
    from repro.parallel.sharding import tenant_mesh

    # Described devices hold no data: the engine places shapes instead.
    monkeypatch.setattr(
        jax, "device_put",
        lambda a, s=None, **_: jax.ShapeDtypeStruct(
            jnp.shape(a), jnp.result_type(a), sharding=s
        ),
    )
    specs = fl.fleet_specs(jax.random.PRNGKey(0), TENANTS, "dense", M, FEAT, 1.0)
    eng = fl.FleetEngine(
        specs, backend="pallas", sharding="mesh", tenant_shards=4,
        interpret=False, mesh=tenant_mesh(4, devices=list(topo.devices)),
    )
    state = eng.init_state()
    row = NamedSharding(eng.mesh, PartitionSpec(eng.tenant_shard_axis))

    def shape(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=row)

    width = 256  # requests per shard in one flush
    programs = [
        eng._mesh_update_fn(state).lower(
            state, eng._stacked_op, shape(TENANTS, REQUEST, FEAT),
            shape(TENANTS, REQUEST),
        )
    ] + [
        eng._mesh_ingest_fn(state, unique).lower(
            state, eng._stacked_op, shape(4, width, dtype=jnp.int32),
            shape(4, width, REQUEST, FEAT), shape(4, width, REQUEST),
        )
        for unique in (True, False)
    ]
    for lowered in programs:
        text = lowered.compile().as_text()
        assert "tpu_custom_call" in text
        assert not [op for op in COLLECTIVES if op in text]
