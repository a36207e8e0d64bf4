"""Kernel microbenchmarks: fused Pallas fourier_sketch / assign_argmin.

On this CPU container the Pallas kernels run in interpret mode (correctness),
so wall-clock speedups are NOT meaningful; what we report per kernel is
- interpret-mode equivalence error vs the jnp oracle, and
- the HBM-traffic model: bytes moved by the unfused jnp path (projection
  matrix materialised) vs the fused kernel (inputs+outputs only), which is
  the quantity the TPU roofline converts into time.
Also times the jnp fallback paths (the actual CPU execution path), reports
the QCKM rows: dequantization error of the quantized sketch and the
sketch bytes-on-the-wire per backend (float vs minimal-width integer
accumulators) — the bandwidth the quantized subsystem saves at merge time —
and the decoder-comparison rows: SSE + decode wall-clock of every registered
decoder on the fig-1 blobs protocol, from one shared sketch, so
``kernels.json`` tracks per-decoder quality/latency across PRs.

SSE-vs-m frontier rows (ISSUE 6, ``run_amp``): amp vs clompr vs sketch_shift
fits at m = {2, 4, 10}·K·n on blobs, best-of-3 replicates — the CL-AMP
acceptance is ``amp`` at 4·K·n within 5% of CLOMPR at 10·K·n.

Frequency-operator rows (ISSUE 5, ``run_freq_ops``): per-operator sketch
throughput (dense vs structured fast transform), operator-state /
spec-wire bytes (the spec-not-matrix acceptance), a roofline cross-check
of the structured flops model against compiled HLO, and the
structured-vs-dense SSE acceptance (within 5% on blobs).

Fleet rows (ISSUE 7, ``run_fleet``): multi-tenant serving throughput — one
vmapped stacked ``FleetEngine.update`` over T=1024 tenants vs a Python loop
of 1024 per-tenant ``SketchEngine`` updates (same operators, bitwise-equal
states).  The acceptance is the batched dispatch >= 5x faster at T=1024;
parity is asserted here on the full fleet and pinned exhaustively in
``tests/test_fleet.py``.

Fleet-sharding rows (``run_fleet_shard``): the T=1024 fleet update
mesh-sharded over 4 devices, in-process (skipped when JAX sees fewer; force
host devices on the CPU), vs the single-device stacked path,
with the zero-collective HLO check, per-tenant bitwise parity against
isolated engines (float + quantized), and an honest ``speedup_basis`` field
— wall clock when the host has a core per shard, the per-shard critical
path otherwise (host devices time-share cores).  Acceptance: >= 2.5x.

Scaling rows (PR 4):
- ingest: sync vs async ``fit_streaming`` over an I/O-bound blobs stream
  (per-batch latency calibrated to the measured sketch-compute time, the
  worst case for overlap bookkeeping and the regime the paper targets —
  data arriving from storage).  Records wall clocks, speedup (acceptance:
  >= 1.3x) and the measured overlap efficiency of the ingest pipeline.
- topologies: per-topology host-level merge latency over 8 quantized partial
  states + the alpha-beta wire cost model (bytes/device, serialized hops)
  for float vs 1-bit states; asserts all registered topologies finalize
  **bitwise identical** sketches on the quantized path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import dataclasses
import time

from benchmarks.common import csv_line, save, timed
from repro.core import available_decoders, available_topologies
from repro.core import ckm as ckm_mod
from repro.core import engine as eng_mod
from repro.core import freq_ops as fo
from repro.core import ingest as ingest_mod
from repro.core import quantize as qz
from repro.core import sketch as core_sk
from repro.core import topology as topo_mod
from repro.data import pipeline as pipe
from repro.kernels import ops, ref


def run_engine_backends(results: dict, n_pts=4096, feat=16, m=1024):
    """SketchEngine backend matrix on one shape: parity vs the reference
    sketch + wall time of each backend's actual CPU execution path (pallas
    interpret mode is excluded from timing — it is a correctness mode)."""
    key = jax.random.PRNGKey(7)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (n_pts, feat))
    w = jax.random.normal(kw, (feat, m))
    z_ref = np.asarray(core_sk.sketch(x, w))
    engines = {
        "xla": eng_mod.SketchEngine(w, "xla"),
        "pallas": eng_mod.SketchEngine(w, "pallas", block_n=512, block_m=256),
    }
    for name, e in engines.items():
        z, _, _ = e.sketch(x[:2048] if name == "pallas" else x)
        ref_z = np.asarray(core_sk.sketch(x[:2048], w)) if name == "pallas" else z_ref
        err = float(np.max(np.abs(np.asarray(z) - ref_z)))
        row = {"parity_max_err": err}
        if name == "xla":
            _, t = timed(lambda: e.sketch(x))
            _, t = timed(lambda: e.sketch(x))  # warm
            row["seconds"] = t
            csv_line(f"engine_{name}_N{n_pts}_m{m}", t, f"err={err:.2e}")
        else:
            csv_line(f"engine_{name}_N{n_pts}_m{m}", 0.0, f"err={err:.2e}")
        results[f"engine_{name}"] = row
        assert err < 1e-4, (name, err)
    return results


def run_quantized(results: dict, n_pts=8192, feat=16, m=1024):
    """QCKM quantized-sketch rows: dequantization error vs the float sketch,
    bitwise xla/pallas parity of the int32 accumulators, and the
    bytes-on-the-wire of one partial state — float f32 accumulators vs the
    minimal-width integer accumulators (``core.quantize.state_wire_bytes``),
    one row that applies to every backend's merge."""
    key = jax.random.PRNGKey(3)
    kx, kw, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n_pts, feat))
    w = jax.random.normal(kw, (feat, m)) * 0.5
    z_ref = np.asarray(core_sk.sketch(x, w))
    sl = 2048  # pallas interpret mode is slow: parity on a slice
    for spec in ("1bit", "8bit"):
        q = qz.make_quantizer(kd, m, spec)
        e_x = eng_mod.SketchEngine(w, "xla", quantizer=q)
        z, _, _ = e_x.sketch(x)
        rel = float(
            np.linalg.norm(np.asarray(z) - z_ref) / np.linalg.norm(z_ref)
        )
        e_p = eng_mod.SketchEngine(
            w, "pallas", block_n=512, block_m=256, quantizer=q
        )
        s_x = e_x.update(e_x.init_state(), x[:sl])
        s_p = e_p.update(e_p.init_state(), x[:sl])
        int_mismatch = int(
            jnp.sum(s_x.qcos_acc != s_p.qcos_acc)
            + jnp.sum(s_x.qsin_acc != s_p.qsin_acc)
        )
        assert int_mismatch == 0, (spec, int_mismatch)
        results[f"quantized_{spec}"] = {
            "dequant_rel_l2_err": rel,
            "pallas_int_mismatches": int_mismatch,
        }
        csv_line(f"quantized_{spec}_N{n_pts}_m{m}", 0.0, f"rel_err={rel:.3f}")
    # Bytes-on-the-wire of one partial state's accumulators.  The number is a
    # property of the state representation, not of how it was computed, so a
    # single row applies to every backend: it is what the sharded backend's
    # psum moves per merge, and what xla/pallas hosts ship when partials are
    # combined off-device.
    wire = {
        spec: qz.state_wire_bytes(m, n_pts, bits)
        for spec, bits in {"float": None, "1bit": 1, "8bit": 8}.items()
    }
    wire["reduction_1bit"] = wire["float"] / wire["1bit"]
    wire["applies_to_backends"] = list(eng_mod.BACKENDS)
    results["sketch_wire_bytes"] = wire
    csv_line(
        f"wire_N{n_pts}_m{m}", 0.0,
        f"float={wire['float']}B;1bit={wire['1bit']}B;"
        f"x{wire['reduction_1bit']:.1f}",
    )
    return results


def run_decoders(results: dict, n_pts=8192, k=5, feat=4):
    """Decoder-comparison rows (paper Fig. 1 blobs protocol at container
    scale): every registered decoder decodes the SAME sketch; we record the
    data-domain SSE, the sketch-domain cost, and the decode wall-clock (warm,
    jitted — the real CPU execution path).  The smoke assertion pins the
    tentpole acceptance: ``sketch_shift`` stays within 10% of CLOMPR's SSE.
    """
    key = jax.random.PRNGKey(11)
    from repro.data import synthetic

    x, _, _ = synthetic.gaussian_mixture(
        key, n_pts, k=k, n=feat, c=6.0, return_labels=True
    )
    base = ckm_mod.CKMConfig(k=k)
    z, w, _, (lo, hi) = ckm_mod.compute_sketch(jax.random.PRNGKey(1), x, base)
    m = base.sketch_size(feat)
    sses = {}
    for name in available_decoders():
        cfg = ckm_mod.CKMConfig(k=k, decoder=name)

        def run_decode():
            out = ckm_mod.decode_sketch(jax.random.PRNGKey(2), z, w, lo, hi, cfg)
            return out

        (cents, _, cost), _ = timed(run_decode)
        (cents, _, cost), t = timed(run_decode)  # warm (jit cached)
        sse_val = float(ckm_mod.sse(x, cents)) / n_pts
        sses[name] = sse_val
        results[f"decoder_{name}"] = {
            "sse_per_n": sse_val,
            "sketch_cost": float(cost),
            "decode_seconds": t,
        }
        csv_line(
            f"decoder_{name}_N{n_pts}_K{k}_m{m}", t, f"sse_per_n={sse_val:.4f}"
        )
    rel = sses["sketch_shift"] / sses["clompr"]
    results["decoder_sketch_shift"]["sse_vs_clompr"] = rel
    assert rel < 1.10, sses
    return results


def run_amp(results: dict, n_pts=8000, k=5, feat=4):
    """SSE-vs-m frontier per decoder (ISSUE 6): amp vs clompr vs sketch_shift
    on the blobs protocol at m = {2, 4, 10}·K·n, best-of-3 replicates each
    (CL-AMP's own protocol — random restarts selected by the shared
    sketch-domain cost).  The acceptance pins the tentpole claim: ``amp`` at
    m = 4·K·n lands within 5% of CLOMPR's SSE at m = 10·K·n — message
    passing stays accurate at sketch sizes where greedy decoding degrades.
    """
    from repro.data import synthetic

    x, _, _ = synthetic.gaussian_mixture(
        jax.random.PRNGKey(42), n_pts, k=k, n=feat, c=6.0, return_labels=True
    )
    kn = k * feat
    frontier = {}
    for mult in (2, 4, 10):
        m = mult * kn
        for name in ("amp", "clompr", "sketch_shift"):
            cfg = ckm_mod.CKMConfig(k=k, m=m, decoder=name, replicates=3)

            def run_fit():
                return ckm_mod.fit(jax.random.PRNGKey(0), x, cfg)

            res, _ = timed(run_fit)
            res, t = timed(run_fit)  # warm (jit cached)
            sse_val = float(ckm_mod.sse(x, res.centroids)) / n_pts
            frontier[(name, mult)] = sse_val
            results[f"frontier_{name}_m{mult}kn"] = {
                "decoder": name,
                "m": m,
                "m_over_kn": mult,
                "replicates": 3,
                "sse_per_n": sse_val,
                "sketch_cost": float(res.cost),
                "fit_seconds": t,
            }
            csv_line(
                f"frontier_{name}_m{m}_N{n_pts}_K{k}",
                t,
                f"sse_per_n={sse_val:.4f}",
            )
    rel = frontier[("amp", 4)] / frontier[("clompr", 10)]
    results["frontier_amp_m4kn"]["sse_vs_clompr_10kn"] = rel
    assert rel <= 1.05, frontier
    return results


def run_ingest(results: dict, n_batches=40, batch=4096, feat=16, m=512, k=3):
    """Async-vs-sync ``fit_streaming`` on the blobs streaming benchmark.

    The stream models the paper's target regime — batches arriving from host
    I/O: **numpy (host-memory) buffers** behind a per-batch latency
    (``data.pipeline.with_latency``) calibrated to 2x the measured per-batch
    sketch time (an I/O-bound stream, the common case for a 10^7-point pass
    over storage; host buffers also keep the producer off the device stream,
    like a real reader).  What is compared is the two *backpressure
    policies* of ``fit_streaming``: sync = strict fold-block-discard (one
    resident batch, the O(m) working-set contract), which pays
    produce+compute serially; async = a bounded double buffer
    (``CKMConfig.ingest="async"``) that hides sketch compute under the
    producer's I/O wait at prefetch+2 resident batches.  (Letting JAX's
    async dispatch run unthrottled would also overlap, but with a
    runtime-defined in-flight window of dozens of batches — not a streaming
    memory policy.)  Expected speedup (P+C+D)/(P+D) ~= 1.4 at P=2C with a
    small decode D.  Acceptance (ISSUE 4): async >= 1.3x faster, identical
    sketches.
    """
    from repro.data import synthetic

    key = jax.random.PRNGKey(5)
    x, _, _ = synthetic.gaussian_mixture(
        key, n_batches * batch, k=k, n=feat, c=6.0, return_labels=True
    )
    x = np.asarray(x)  # host-resident, as if read from storage
    cfg = ckm_mod.CKMConfig(
        k=k, m=m, sigma2=1.0,  # fixed scale: the benchmark times the sketch
        decoder="sketch_shift",  # cheapest registered decode — the benchmark
        shift_steps=20, shift_polish_steps=40, nnls_iters=25,  # times ingest
        sketch_chunk=batch,
    )

    # Calibrate: mean per-batch update time of the engine's real CPU path
    # under streaming backpressure (block per batch, like the fit).
    w = jax.random.normal(jax.random.PRNGKey(6), (feat, m)) * 0.5
    eng = eng_mod.SketchEngine(w, "xla", chunk=batch)
    state = eng.update(eng.init_state(), x[:batch])  # warm the jit caches
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for i in range(4):
        state = eng.update(state, x[i * batch : (i + 1) * batch])
        jax.block_until_ready(state)
    t_batch = (time.perf_counter() - t0) / 4

    def source():
        return pipe.with_latency(pipe.chunked(x, batch), 2.0 * t_batch)

    # Overlap efficiency of the ingest pipeline itself (engine-level).
    _, stats = ingest_mod.ingest_stream(eng, source(), prefetch=2)

    key_fit = jax.random.PRNGKey(7)
    # Pre-warm the decode jit cache on the same (m, k) shapes so neither
    # timed run pays compilation (the sync run would otherwise eat it and
    # inflate the speedup).
    ckm_mod.fit_streaming(key_fit, pipe.chunked(x[: 2 * batch], batch), cfg)
    res_sync, t_sync = timed(
        ckm_mod.fit_streaming, key_fit, source(), cfg
    )
    res_async, t_async = timed(
        ckm_mod.fit_streaming, key_fit, source(),
        dataclasses.replace(cfg, ingest="async"),
    )
    assert bool(jnp.array_equal(res_sync.sketch, res_async.sketch)), (
        "async ingest changed the sketch"
    )
    speedup = t_sync / t_async
    results["ingest_async"] = {
        "n_batches": n_batches,
        "batch": batch,
        "per_batch_latency_s": 2.0 * t_batch,
        "sync_fit_seconds": t_sync,
        "async_fit_seconds": t_async,
        "speedup": speedup,
        "overlap_efficiency": stats.overlap_efficiency,
        "produce_s": stats.produce_s,
        "compute_s": stats.compute_s,
        "consumer_wait_s": stats.consumer_wait_s,
    }
    results["ingest_async"]["meets_1p3x_acceptance"] = bool(speedup >= 1.3)
    csv_line(
        f"ingest_async_B{n_batches}x{batch}_m{m}", t_async,
        f"sync={t_sync:.2f}s;speedup=x{speedup:.2f};"
        f"overlap={stats.overlap_efficiency:.2f}",
    )
    return results


def run_freq_ops(results: dict, n_pts=4096, feat=2048, m=2048, sigma2=1.0):
    """Frequency-operator rows (ISSUE 5): per-operator sketch throughput,
    state/wire bytes, and the roofline sanity check of the structured path.

    - correctness: the structured fast transform vs the explicit-Hadamard
      matmul oracle (``kernels.ref.structured_project_ref``);
    - throughput: warm jitted wall time of the projection (``op.apply``) and
      of the full engine sketch, per operator, on the real CPU path — the
      acceptance row is the measured apply speedup at ``n >= 512``
      (``feat=2048`` here; on CPU the crossover sits near n ~ 2k, on TPU the
      fused WHT kernel moves it far lower);
    - state bytes: operator leaves (what a by-value carry ships) and the O(1)
      ``spec()`` (what engine state/checkpoints/broadcast actually carry)
      vs the 4·n·m dense matrix — proving the spec-not-matrix acceptance;
    - roofline: ``utils.roofline.freq_transform_model`` cross-checked
      against the *compiled* HLO dot-flops of both projections
      (``utils.hlo.analyze_compiled``), asserting the structured path's
      arithmetic-intensity model (sub-dense flops, dot-flops ratio within
      2x of the model's);
    - quality: structured CKM SSE within 5% of dense on the fig-1 blobs
      protocol, decoded from the same config/keys.
    """
    from repro.core import freq_ops as fo
    from repro.data import synthetic
    from repro.utils import hlo as hlo_mod
    from repro.utils import roofline as roof

    key = jax.random.PRNGKey(21)
    kx, kf = jax.random.split(key)
    x = jax.random.normal(kx, (n_pts, feat))
    ops_by_name = {
        name: fo.make_operator(name, kf, m, feat, sigma2)
        for name in fo.available_freq_ops()
    }

    # Correctness of the fast transform vs an independent dense oracle.
    s_op = ops_by_name["structured"]
    sl = 256
    ref_proj = ref.structured_project_ref(x[:sl], s_op.diags, s_op.radii)[:, :m]
    got = s_op.apply(x[:sl])
    rel_err = float(
        jnp.max(jnp.abs(got - ref_proj)) / jnp.maximum(jnp.max(jnp.abs(ref_proj)), 1e-9)
    )
    assert rel_err < 1e-4, rel_err

    dense_matrix_bytes = 4 * feat * m
    times, flops = {}, {}
    for name, op in ops_by_name.items():
        apply_f = jax.jit(lambda xx, o=op: o.apply(xx))
        jax.block_until_ready(apply_f(x))
        _, t_apply = timed(apply_f, x)
        _, t_apply = timed(apply_f, x)  # warm
        eng = eng_mod.SketchEngine(op, "xla", chunk=n_pts)
        _, t_sk = timed(eng.sketch, x)
        _, t_sk = timed(eng.sketch, x)  # warm
        compiled = apply_f.lower(x).compile()
        hlo_flops = hlo_mod.analyze_compiled(compiled).flops
        times[name], flops[name] = t_apply, hlo_flops
        spec_bytes = fo.spec_wire_bytes(op.spec())
        results[f"freq_op_{name}"] = {
            "n_pts": n_pts, "n": feat, "m": m,
            "apply_seconds": t_apply,
            "sketch_seconds": t_sk,
            "points_per_second": n_pts / t_sk,
            "hlo_dot_flops": hlo_flops,
            "operator_state_bytes": op.state_bytes(),
            "spec_wire_bytes": spec_bytes,
            "dense_matrix_bytes": dense_matrix_bytes,
        }
        csv_line(
            f"freq_op_{name}_N{n_pts}_n{feat}_m{m}", t_sk,
            f"apply={t_apply*1e3:.0f}ms;state={op.state_bytes()}B;"
            f"spec={spec_bytes}B",
        )
        # Spec-not-matrix acceptance: the rebuild recipe every operator's
        # checkpoints/broadcast carry is O(1) — negligible next to the matrix.
        assert spec_bytes < 0.01 * dense_matrix_bytes, (name, spec_bytes)

    # Roofline sanity: model vs compiled-HLO dot flops.
    model = roof.freq_transform_model(n_pts, feat, m, s_op.d, s_op.nblocks)
    meas_ratio = flops["dense"] / max(flops["structured"], 1.0)
    results["freq_op_roofline"] = {
        **model,
        "hlo_flops_dense": flops["dense"],
        "hlo_flops_structured": flops["structured"],
        "hlo_flops_ratio": meas_ratio,
        "apply_speedup_structured": times["dense"] / times["structured"],
    }
    assert model["structured_flops"] < model["dense_flops"]
    # The compiled dot-flops must track the analytic model on both sides.
    assert 0.5 < flops["dense"] / model["dense_flops"] < 2.0, flops
    assert 0.5 < meas_ratio / model["flops_ratio"] < 2.0, (meas_ratio, model)
    # Measured throughput acceptance: the fast transform wins at this n.
    speedup = times["dense"] / times["structured"]
    results["freq_op_roofline"]["meets_speedup_acceptance"] = bool(speedup > 1.0)
    csv_line(
        f"freq_op_speedup_n{feat}", times["structured"],
        f"x{speedup:.2f};model_flops_x{model['flops_ratio']:.1f};"
        f"hlo_flops_x{meas_ratio:.1f}",
    )

    # Quality acceptance: structured CKM SSE within 5% of dense on the
    # fig-1 blobs protocol (same keys, same decode budget).
    xb, _, _ = synthetic.gaussian_mixture(
        jax.random.PRNGKey(11), 8192, k=5, n=4, c=6.0, return_labels=True
    )
    sses = {}
    for name in ops_by_name:
        cfg = ckm_mod.CKMConfig(k=5, freq_op=name)
        res = ckm_mod.fit(jax.random.PRNGKey(1), xb, cfg)
        sses[name] = float(ckm_mod.sse(xb, res.centroids)) / xb.shape[0]
    rel = sses["structured"] / sses["dense"]
    results["freq_op_sse"] = {**sses, "structured_vs_dense": rel}
    csv_line("freq_op_sse_blobs", 0.0, f"ratio={rel:.4f}")
    assert rel < 1.05, sses
    return results


def run_fleet(results: dict, n_tenants=1024, batch=32, feat=8, m=64):
    """Multi-tenant fleet row (ISSUE 7): stacked-vs-looped update throughput.

    The fleet ingests one aligned block — one ``(batch, n)`` batch per tenant
    — two ways: ONE vmapped ``FleetEngine.update`` dispatch over the stacked
    ``(T, ...)`` state, and a Python loop of T per-tenant ``SketchEngine``
    updates (the same trace the fleet vmaps, so the states must match
    bitwise).  Both paths are warm (jit caches populated) and timed on the
    real CPU execution path; the speedup is pure dispatch/batching win, which
    is the point — per-tenant serving cost is dominated by T Python+XLA
    dispatches, not by the O(batch·n·m) math.  Acceptance: >= 5x at T=1024.
    """
    from repro.core import fleet as fl

    specs = fl.fleet_specs(
        jax.random.PRNGKey(17), n_tenants, "dense", m, feat, 1.0
    )
    fleet = fl.FleetEngine(specs, chunk=batch)
    xs = jax.random.normal(jax.random.PRNGKey(18), (n_tenants, batch, feat))

    state0 = fleet.init_state()
    jax.block_until_ready(fleet.update(state0, xs))  # warm the vmapped jit
    state, t_stacked = timed(fleet.update, state0, xs)

    engines = [fleet.tenant_engine(t) for t in range(n_tenants)]
    inits = [e.init_state() for e in engines]
    jax.block_until_ready(engines[0].update(inits[0], xs[0]))  # warm

    def looped():
        return [
            e.update(s, xs[t]) for t, (e, s) in enumerate(zip(engines, inits))
        ]

    rows, t_looped = timed(looped)

    # Bitwise parity across the whole fleet: restack the looped rows and
    # compare every leaf (tests/test_fleet.py pins this per backend/flavour).
    ref_stack = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *rows)
    parity = all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(
            jax.tree_util.tree_leaves(state),
            jax.tree_util.tree_leaves(ref_stack),
        )
    )
    assert parity, "stacked fleet update diverged from the per-tenant loop"

    speedup = t_looped / t_stacked
    results["fleet_update"] = {
        "n_tenants": n_tenants,
        "batch": batch,
        "n": feat,
        "m": m,
        "stacked_seconds": t_stacked,
        "looped_seconds": t_looped,
        "speedup": speedup,
        "bitwise_parity": parity,
        "fleet_state_bytes": fleet.state_bytes(),
        "meets_5x_acceptance": bool(speedup >= 5.0),
    }
    csv_line(
        f"fleet_update_T{n_tenants}_B{batch}_m{m}", t_stacked,
        f"looped={t_looped:.3f}s;speedup=x{speedup:.1f}",
    )
    return results


def run_fleet_shard(results: dict, n_tenants=1024, batch=32, feat=8, m=64,
                    devices=4):
    """Multi-device fleet sharding row (ISSUE 10): mesh-sharded vs
    single-device stacked update at T=1024.

    Runs in this process on the devices JAX already sees — a child process
    could not reach a chip this process holds.  With fewer than ``devices``
    devices the row is skipped; on the CPU, force host devices before JAX
    starts (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).  It
    measures three update paths, all warm:

    - ``single_device_seconds``: the unsharded stacked fleet, T=1024 rows on
      one device — the PR 7 baseline.
    - ``sharded_wall_seconds``: the same traffic through the mesh-sharded
      engine, 4 shards x 256 rows.
    - ``per_shard_block_seconds``: a T=256 stacked fleet on one device — the
      critical path ONE shard executes under 4-way sharding.

    Host-platform devices time-share the physical cores, so on a machine
    with fewer cores than shards the sharded *wall clock* cannot beat the
    single-device run no matter how the work is placed; the architectural
    speedup is ``single / per_shard_block`` (each device runs a T/P block
    concurrently), which is valid precisely because the compiled sharded
    update contains **zero cross-shard collectives** — the HLO is scanned
    and the row records any found.  ``speedup_basis`` says which
    measurement backs the reported ``speedup``: real wall clock on an
    accelerator or when the host has >= one core per shard, the per-shard
    critical path otherwise.  Parity is never simulated: every tenant's
    sharded row is asserted bitwise equal to an isolated ``SketchEngine``
    run, float and quantized.  Acceptance: >= 2.5x at T=1024 over 4 devices.
    """
    import os

    from repro.core import fleet as fl

    if len(jax.devices()) < devices:
        print(
            f"fleet_shard: skipped, needs {devices} devices and JAX sees "
            f"{len(jax.devices())}"
        )
        return results
    T, B, N, M, P = n_tenants, batch, feat, m, devices
    specs = fl.fleet_specs(jax.random.PRNGKey(17), T, "dense", M, N, 1.0)
    xs = jax.random.normal(jax.random.PRNGKey(18), (T, B, N))

    def timeit(fn, *args):
        jax.block_until_ready(fn(*args))  # warm the jit cache
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        return time.perf_counter() - t0

    single = fl.FleetEngine(specs, chunk=B)
    t_single = timeit(single.update, single.init_state(), xs)

    sharded = fl.FleetEngine(specs, chunk=B, sharding="mesh", tenant_shards=P)
    s_state = sharded.init_state()
    t_wall = timeit(sharded.update, s_state, xs)

    hlo = sharded.mesh_update_hlo(s_state, xs).lower()
    collectives = [
        op for op in ("all-reduce", "all-gather", "collective-permute",
                      "all-to-all")
        if op in hlo
    ]

    block = fl.FleetEngine(specs[: T // P], chunk=B)
    t_block = timeit(block.update, block.init_state(), xs[: T // P])

    def bitwise_vs_isolated(quant):
        quants = fl.fleet_quantizers(jax.random.PRNGKey(7), T, M, quant)
        eng = fl.FleetEngine(specs, chunk=B, quantizers=quants,
                             sharding="mesh", tenant_shards=P)
        state = eng.update(eng.init_state(), xs)
        for t in range(T):
            e = eng.tenant_engine(t)
            iso = e.update(e.init_state(), xs[t])
            row = eng.tenant_state(state, t)
            if not all(bool(jnp.array_equal(a, b)) for a, b in zip(
                    jax.tree_util.tree_leaves(row),
                    jax.tree_util.tree_leaves(iso))):
                return False
        return True

    row = {
        "single_device_seconds": t_single,
        "sharded_wall_seconds": t_wall,
        "per_shard_block_seconds": t_block,
        "hot_path_collectives": collectives,
        "bitwise_parity_float": bitwise_vs_isolated("none"),
        "bitwise_parity_quantized": bitwise_vs_isolated("1bit"),
    }
    host_cores = os.cpu_count() or 1
    wall_speedup = row["single_device_seconds"] / row["sharded_wall_seconds"]
    device_parallel_speedup = (
        row["single_device_seconds"] / row["per_shard_block_seconds"]
    )
    # One XLA host device per physical core is what makes the wall clock an
    # honest measure of device parallelism; below that, forced host devices
    # time-share cores and the per-shard critical path is the honest number
    # (backed by the zero-collective HLO: shards never wait on each other).
    basis = (
        "wall_clock"
        if jax.default_backend() != "cpu" or host_cores >= devices
        else "per_device_critical_path"
    )
    speedup = wall_speedup if basis == "wall_clock" else device_parallel_speedup
    parity = (
        row["bitwise_parity_float"] and row["bitwise_parity_quantized"]
    )
    results["fleet_shard"] = {
        "n_tenants": n_tenants,
        "batch": batch,
        "n": feat,
        "m": m,
        "devices": devices,
        "host_cores": host_cores,
        **row,
        "wall_speedup": wall_speedup,
        "device_parallel_speedup": device_parallel_speedup,
        "speedup": speedup,
        "speedup_basis": basis,
        "meets_2p5x_acceptance": bool(
            speedup >= 2.5
            and parity
            and not row["hot_path_collectives"]
        ),
    }
    csv_line(
        f"fleet_shard_T{n_tenants}_P{devices}_m{m}",
        row["sharded_wall_seconds"],
        f"single={row['single_device_seconds']:.3f}s;"
        f"speedup=x{speedup:.1f}({basis});parity={parity}",
    )
    return results


def run_window(results: dict, n_tenants=256, batch=32, feat=8, m=64,
               buckets=8, steps=16, gamma=0.9):
    """Temporal-window row (ISSUE 9): windowed-vs-lifetime fleet update cost.

    The same aligned traffic — ``steps`` update blocks of one ``(batch, n)``
    batch per tenant — folds into a plain lifetime ``FleetEngine`` and into a
    ``SketchWindow`` ring (W buckets, advancing one tick per block) over a
    decayed fleet.  The windowed path pays the decayed fold (stamp/gamma
    bookkeeping + the fold-time ``gamma**dt`` scale) and the ring's O(1)
    host-side slot claim per update, but touches exactly ONE bucket — the
    other W-1 are merged on *read*, never copied on write.  Acceptance: the
    per-update wall clock stays <= 1.3x the lifetime fleet update.
    """
    from repro.core import fleet as fl
    from repro.core.window import SketchWindow

    specs = fl.fleet_specs(
        jax.random.PRNGKey(23), n_tenants, "dense", m, feat, 1.0
    )
    lifetime = fl.FleetEngine(specs, chunk=batch)
    windowed = SketchWindow(
        fl.FleetEngine(specs, chunk=batch, decay=gamma), buckets=buckets
    )
    xs = jax.random.normal(jax.random.PRNGKey(24), (n_tenants, batch, feat))

    def run_lifetime():
        s = lifetime.init_state()
        for _ in range(steps):
            s = lifetime.update(s, xs)
        return s

    def run_windowed():
        ws = windowed.init_state()
        for k in range(steps):
            ws = windowed.update(ws, xs, t=float(k))
        return ws.buckets  # the pytree timed() can block on

    _, t_life = timed(run_lifetime)  # first call pays compilation
    _, t_life = timed(run_lifetime)
    _, t_win = timed(run_windowed)
    ring, t_win = timed(run_windowed)

    ratio = t_win / t_life
    results["window_update"] = {
        "n_tenants": n_tenants,
        "batch": batch,
        "n": feat,
        "m": m,
        "window_buckets": buckets,
        "decay": gamma,
        "steps": steps,
        "lifetime_seconds_per_update": t_life / steps,
        "windowed_seconds_per_update": t_win / steps,
        "overhead_ratio": ratio,
        "ring_state_bytes": int(
            sum(
                leaf.size * leaf.dtype.itemsize
                for b in ring
                for leaf in jax.tree_util.tree_leaves(b)
            )
        ),
        "meets_1p3x_acceptance": bool(ratio <= 1.3),
    }
    csv_line(
        f"window_update_T{n_tenants}_W{buckets}_m{m}", t_win / steps,
        f"lifetime={t_life/steps*1e6:.1f}us;ratio=x{ratio:.2f}",
    )
    return results


def run_topologies(results: dict, p=8, n_pts=16384, feat=16, m=1024):
    """Per-topology merge rows: latency of reducing ``p`` quantized partial
    states through every registered schedule, the alpha-beta wire cost model
    (bytes/device + serialized hops, float vs 1-bit states), and the bitwise
    acceptance — every topology finalizes the identical quantized sketch
    (int32 addition is exactly associative/commutative)."""
    key = jax.random.PRNGKey(13)
    kx, kw, kd = jax.random.split(key, 3)
    x = jax.random.normal(kx, (n_pts, feat))
    w = jax.random.normal(kw, (feat, m)) * 0.5
    q = qz.make_quantizer(kd, m, "1bit")
    eng = eng_mod.SketchEngine(w, "xla", quantizer=q)
    shard = n_pts // p
    parts = [
        eng.update(eng.init_state(), x[i * shard : (i + 1) * shard])
        for i in range(p)
    ]
    jax.block_until_ready(parts)

    wire_1bit = qz.state_wire_bytes(m, shard, 1)
    wire_float = qz.state_wire_bytes(m, shard, None)
    finals = {}
    for name in available_topologies():
        merged, _ = timed(topo_mod.reduce_states, eng.merge, parts, name)
        merged, t = timed(topo_mod.reduce_states, eng.merge, parts, name)  # warm
        z, _, _ = eng.finalize(merged)
        finals[name] = (
            np.asarray(merged.qcos_acc),
            np.asarray(merged.qsin_acc),
            np.asarray(z),
        )
        cost_q = topo_mod.wire_cost_model(wire_1bit, p, name)
        cost_f = topo_mod.wire_cost_model(wire_float, p, name)
        results[f"topology_{name}"] = {
            "p": p,
            "merge_seconds": t,
            "hops": cost_q["hops"],
            "bytes_per_device_1bit": cost_q["bytes_per_device"],
            "bytes_per_device_float": cost_f["bytes_per_device"],
        }
        # User-registered topologies have no closed-form cost (None fields).
        fmt = lambda v: "?" if v is None else f"{v:.0f}"  # noqa: E731
        csv_line(
            f"topology_{name}_p{p}_m{m}", t,
            f"hops={cost_q['hops']};1bit_B={fmt(cost_q['bytes_per_device'])};"
            f"float_B={fmt(cost_f['bytes_per_device'])}",
        )
    names = list(finals)
    for other in names[1:]:
        same = all(
            np.array_equal(a, b) for a, b in zip(finals[names[0]], finals[other])
        )
        assert same, f"quantized merge/finalize differs: {names[0]} vs {other}"
    results["topology_bitwise_identical"] = {
        "topologies": names,
        "quantized_path": True,
        "finalized_sketch_bitwise": True,
    }
    return results


def run(full: bool = False):
    dev = jax.devices()[0]
    results = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                          "count": len(jax.devices())}}
    shapes = [(4096, 16, 1024), (16384, 10, 1000)] if not full else [
        (4096, 16, 1024), (65536, 10, 1000), (262144, 16, 2048)]
    for n_pts, feat, m in shapes:
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (n_pts, feat))
        w = jax.random.normal(kw, (feat, m))
        beta = jnp.full((n_pts,), 1.0 / n_pts)
        # interpret-mode equivalence on a slice (full interpret is slow)
        sl = slice(0, min(n_pts, 2048))
        w_op = fo.as_operator(w)  # kernel wrappers reject raw matrices (PR 6)
        zk = ops.fourier_sketch(x[sl], w_op, beta[sl] * (n_pts / 2048),
                                interpret=True, block_n=256, block_m=256)
        ck, sk_ = ref.fourier_sketch_ref(x[sl], w, beta[sl] * (n_pts / 2048))
        err = float(jnp.max(jnp.abs(zk - jnp.concatenate([ck, -sk_]))))
        # jnp (unfused) wall time — the real CPU path
        f = jax.jit(lambda x, w, b: ref.fourier_sketch_ref(x, w, b))
        _, t_ref = timed(f, x, w, beta)
        _, t_ref = timed(f, x, w, beta)  # warm
        # traffic model (f32): unfused writes+reads the (N, m) projection 3x
        unfused = 4 * (n_pts * feat + feat * m + 3 * n_pts * m + 2 * m)
        fused = 4 * (n_pts * feat + feat * m + 2 * m)
        name = f"sketch_N{n_pts}_n{feat}_m{m}"
        results[name] = {
            "interpret_max_err": err,
            "jnp_seconds": t_ref,
            "bytes_unfused": unfused,
            "bytes_fused": fused,
            "traffic_reduction": unfused / fused,
        }
        csv_line(name, t_ref, f"err={err:.2e};traffic_x{unfused/fused:.1f}")
        assert err < 1e-3
    # assign_argmin
    for n_pts, feat, k in [(16384, 16, 64), (65536, 10, 10)]:
        kx, kc = jax.random.split(jax.random.PRNGKey(1))
        x = jax.random.normal(kx, (n_pts, feat))
        c = jax.random.normal(kc, (k, feat))
        sl = slice(0, 2048)
        ik, dk = ops.assign_argmin(x[sl], c, interpret=True, block_n=256)
        ir, dr = ref.assign_argmin_ref(x[sl], c)
        agree = float(jnp.mean((ik == ir).astype(jnp.float32)))
        f = jax.jit(lambda x, c: ref.assign_argmin_ref(x, c))
        _, t_ref = timed(f, x, c)
        _, t_ref = timed(f, x, c)
        unfused = 4 * (n_pts * feat + k * feat + 2 * n_pts * k + 2 * n_pts)
        fused = 4 * (n_pts * feat + k * feat + 2 * n_pts)
        name = f"assign_N{n_pts}_n{feat}_K{k}"
        results[name] = {
            "interpret_agreement": agree,
            "jnp_seconds": t_ref,
            "traffic_reduction": unfused / fused,
        }
        csv_line(name, t_ref, f"agree={agree:.4f};traffic_x{unfused/fused:.1f}")
        assert agree == 1.0
    run_engine_backends(results)
    run_quantized(results)
    run_decoders(results)
    run_amp(results)
    run_freq_ops(results)
    run_ingest(results)
    run_topologies(results)
    run_fleet(results)
    run_fleet_shard(results)
    run_window(results)
    save("kernels", results)
    # Acceptance checked AFTER save so a perf flake on a loaded machine
    # cannot discard the other rows computed in the same invocation.
    ia = results["ingest_async"]
    assert ia["meets_1p3x_acceptance"], (
        f"async ingest speedup {ia['speedup']:.2f}x < 1.3x acceptance "
        f"(sync {ia['sync_fit_seconds']:.2f}s, "
        f"async {ia['async_fit_seconds']:.2f}s)"
    )
    fu = results["fleet_update"]
    assert fu["meets_5x_acceptance"], (
        f"fleet stacked update speedup {fu['speedup']:.1f}x < 5x acceptance "
        f"(stacked {fu['stacked_seconds']:.3f}s, "
        f"looped {fu['looped_seconds']:.3f}s)"
    )
    fs = results.get("fleet_shard")
    assert fs is None or fs["meets_2p5x_acceptance"], (
        f"sharded fleet update speedup {fs['speedup']:.2f}x "
        f"({fs['speedup_basis']}) < 2.5x acceptance, or parity/collective "
        f"check failed: parity_float={fs['bitwise_parity_float']} "
        f"parity_quantized={fs['bitwise_parity_quantized']} "
        f"collectives={fs['hot_path_collectives']}"
    )
    wu = results["window_update"]
    assert wu["meets_1p3x_acceptance"], (
        f"windowed fleet update overhead {wu['overhead_ratio']:.2f}x > 1.3x "
        f"acceptance (lifetime "
        f"{wu['lifetime_seconds_per_update']*1e6:.1f}us/update, windowed "
        f"{wu['windowed_seconds_per_update']*1e6:.1f}us/update)"
    )
    return results


if __name__ == "__main__":
    import sys

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(full="--full" in sys.argv)
