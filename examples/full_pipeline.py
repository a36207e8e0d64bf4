"""End-to-end driver (the paper's kind of workload): cluster a large dataset
through the full distributed pipeline.

    PYTHONPATH=src python examples/full_pipeline.py [--n 1000000]
                                                    [--backend sharded|xla|pallas]
                                                    [--decoder clompr|sketch_shift|amp]
                                                    [--topology allreduce|tree|ring]
                                                    [--ingest sync|async]
                                                    [--freq-op dense|structured]

On the CPU, force host devices for the sharded backend (the flag must be set
before JAX starts):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/full_pipeline.py --backend sharded

Stages (all from the library, nothing bespoke):
1. a (data x 1 model) mesh over every device JAX sees (sharded backend);
2. the dataset is sketched in ONE pass through the unified SketchEngine —
   backend is a flag: "sharded" (shard_map + psum-merge over the data axis,
   O(m) cross-device traffic), "xla" (chunked scan) or "pallas" (fused
   kernel; interpret mode off-TPU);
3. a registered decoder ("clompr", "sketch_shift" or "amp", the --decoder
   flag) decodes K centroids from the sketch alone;
4. a second, *streaming* CKM fit consumes the same data as a chunked
   iterator (fit_streaming) — out-of-core one-pass path;
5. Lloyd-Max x5 runs on the gathered data as the reference;
6. wall-clock + quality comparison (paper Fig. 4 protocol, container scale).
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import (
    BACKENDS,
    CKMConfig,
    available_decoders,
    available_freq_ops,
    decode_sketch,
    fit_streaming,
    sse,
)
from repro.core import available_topologies, ckm, freq_ops, lloyd
from repro.data import pipeline as pipe
from repro.data import synthetic
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import SketchJobSpec
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--dim", type=int, default=10)
    ap.add_argument("--backend", choices=BACKENDS, default="sharded")
    ap.add_argument("--decoder", choices=available_decoders(), default="clompr",
                    help="sketch decoder (core.decoders registry): clompr = "
                         "paper Algorithm 1; sketch_shift = mean shift on the "
                         "sketched characteristic function; amp = CL-AMP "
                         "joint message passing (accurate at small m; pair "
                         "with --replicates-style restarts via CKMConfig)")
    ap.add_argument("--stream-chunk", type=int, default=0,
                    help="also run the one-pass streaming fit at this chunk "
                         "size (0 = skip)")
    ap.add_argument("--quantize", default="none",
                    help="universal sketch quantization (QCKM): none | 1bit "
                         "| <b>bit — integer accumulators, cheaper merges")
    ap.add_argument("--topology", choices=available_topologies(),
                    default="allreduce",
                    help="cross-device merge schedule of the sharded backend "
                         "(core.topology registry); same sketch either way, "
                         "different wire cost — see docs/scaling.md")
    ap.add_argument("--ingest", choices=("sync", "async"), default="sync",
                    help="streaming-fit ingest mode: async overlaps batch "
                         "production with sketch compute (core.ingest)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="async ingest queue depth (2 = double buffering)")
    ap.add_argument("--freq-op", choices=available_freq_ops(), default="dense",
                    help="frequency operator (core.freq_ops registry): dense "
                         "= the paper's materialized matrix; structured = "
                         "stacked fast-transform blocks (O(m·sqrt(d)) "
                         "projections, O(1) spec on the wire)")
    args = ap.parse_args()
    enable_compile_cache()
    job = SketchJobSpec(
        backend=args.backend, reduce_topology=args.topology,
        ingest=args.ingest, ingest_prefetch=args.prefetch,
        sketch_quantization=args.quantize, freq_op=args.freq_op,
        decoder=args.decoder,
    ).validate()

    key = jax.random.PRNGKey(0)
    kd, kf, kdec, kl = jax.random.split(key, 4)
    x, labels, means = synthetic.gaussian_mixture(
        kd, args.n, args.k, args.dim, return_labels=True
    )

    cfg = CKMConfig(k=args.k, **job.ckm_overrides())
    m = cfg.sketch_size(args.dim)
    from repro.core import frequencies as fq
    from repro.core import quantize as qz

    sigma2 = fq.estimate_sigma2(kf, x[:2048])
    freqs = freq_ops.make_operator(args.freq_op, kf, m, args.dim, sigma2)

    mesh = None
    xin = x
    if args.backend == "sharded":
        mesh = make_local_mesh()
    quantizer = ckm.make_quantizer(kf, cfg, m)
    engine = ckm.make_engine(freqs, cfg, mesh, quantizer)
    if args.backend == "sharded":
        xin = engine.shard_points(x)

    t0 = time.perf_counter()
    z, lo, hi = engine.sketch(xin)
    jax.block_until_ready(z)
    t_sketch = time.perf_counter() - t0
    bits = qz.parse_bits(args.quantize)
    wire = qz.state_wire_bytes(m, args.n, bits)
    print(
        f"[1] sketch ({job.describe()}): {t_sketch:.2f}s  (m={m}, one pass, "
        f"merge wire bytes/state={wire}, operator leaves="
        f"{freqs.state_bytes()}B vs spec={freq_ops.spec_wire_bytes(freqs.spec())}B)"
    )

    t0 = time.perf_counter()
    cents, alphas, cost = decode_sketch(kdec, z, freqs, lo, hi, cfg)
    jax.block_until_ready(cents)
    t_decode = time.perf_counter() - t0
    sse_ckm = float(sse(x, cents)) / args.n
    print(
        f"[2] {args.decoder} decode (sketch only): {t_decode:.2f}s  "
        f"SSE/N={sse_ckm:.4f}"
    )

    if args.stream_chunk > 0:
        t0 = time.perf_counter()
        res = fit_streaming(
            key, pipe.chunked(x, args.stream_chunk), cfg, mesh
        )
        jax.block_until_ready(res.centroids)
        t_stream = time.perf_counter() - t0
        print(
            f"[2b] streaming fit ({args.stream_chunk}-pt chunks): "
            f"{t_stream:.2f}s  SSE/N={float(sse(x, res.centroids))/args.n:.4f}"
        )

    t0 = time.perf_counter()
    base = lloyd.kmeans(
        kl, x, lloyd.LloydConfig(k=args.k, replicates=5, init="range")
    )
    jax.block_until_ready(base.centroids)
    t_km = time.perf_counter() - t0
    print(f"[3] Lloyd-Max x5 (full data): {t_km:.2f}s  SSE/N={float(base.sse)/args.n:.4f}")
    print(
        f"[4] relative SSE {sse_ckm * args.n / float(base.sse):.3f}; "
        f"decode speedup vs kmeans x5: {t_km / t_decode:.1f}x; "
        f"memory {args.n * args.dim * 4 / (2*m+args.dim*m)/4:.0f}x smaller working set"
    )


if __name__ == "__main__":
    main()
