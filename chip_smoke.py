#!/usr/bin/env python3
"""Drive the two user paths once on a TPU and check what comes out.

    python chip_smoke.py              # paths A and B on one chip
    python chip_smoke.py --chips 4    # only the four-chip comparisons

Path A, the paper's headline fit: N = 10^7 points of a K = 10 Gaussian
mixture in n = 10 dimensions, made on the device from a seed, streamed in
10^6-point chunks through ``ckm.fit_streaming`` with the Pallas sketch
kernel (m = 10·K·n = 1000, CLOMPR decoder).  Checked against references that
do not use the kernel: the XLA sketch of one chunk (float within 1e-4
relative; 1-bit code sums equal), and Lloyd-Max with
k-means++ seeding and 5 replicates on the same 10^7 points (CKM SSE at most
1.3x Lloyd's).

Path B, the fleet service: a ``FleetEngine(backend="pallas")`` of T = 1024
tenants (n = 10, m = 1000 each) behind ``FleetService``; 4096 requests of
256 points, Zipf(0.99)-skewed over tenants (YCSB's default skew), each
tenant drawing from its own mixture of K = 5 well-separated unit clusters,
submitted in four flushes.  The fleet's frequency scale is estimated once
from a sample of one tenant's traffic.  Every flushed tenant row is checked
against an isolated ``SketchEngine("xla")`` fed the same batches in the
same order, then the 8 busiest tenants are decoded with the service's
default decoder (``sketch_shift``) and matched to their true means.

``--chips 4`` runs only what exists across chips: the mesh-sharded fleet
(``sharding="mesh"``, 4 tenant shards) against the unsharded fleet on the
same traffic, and the ``sharded`` engine backend on a 4-way data mesh
against the one-device Pallas sketch, with a check that every device holds
its own block.

Timings, ``memory_analysis`` and peak device memory are printed on the way.
The script fails (non-zero exit, no result line) when JAX finds no TPU or
any check fails; otherwise its last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
``--rehearse`` runs tiny shapes on whatever devices exist (the CPU, with
the kernels in interpret mode) and prints no result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


@dataclasses.dataclass(frozen=True)
class Sizes:
    points: int  # path A: total points
    chunk: int  # path A: points per streamed chunk
    n: int  # features
    k: int  # path A clusters (m = 10·K·n)
    tenant_k: int  # clusters per tenant, path B
    tenants: int  # path B
    requests: int  # path B, over all flushes
    request_points: int
    flushes: int
    decoded: int  # tenants decoded in path B
    lloyd_replicates: int = 5
    seed: int = 0


FULL = Sizes(
    points=10_000_000, chunk=1_000_000, n=10, k=10, tenant_k=5,
    tenants=1024, requests=4096, request_points=256, flushes=4, decoded=8,
)
TINY = Sizes(
    points=16_384, chunk=4096, n=4, k=3, tenant_k=2,
    tenants=16, requests=64, request_points=32, flushes=2, decoded=2,
)


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named pass/fail results; every failure is reported at the end."""

    def __init__(self):
        self.failed: list[str] = []

    def add(self, name: str, ok: bool, detail: str) -> None:
        log(f"[{'ok' if ok else 'FAIL'}] {name}: {detail}")
        if not ok:
            self.failed.append(name)


class Timer:
    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        log(f"  time {self.label}: {self.seconds:.3f} s")


def peak_memory(label: str) -> None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(
        f"  peak_bytes_in_use after {label}: "
        + (f"{peak} ({peak / 2**30:.2f} GiB)" if peak is not None else "n/a")
    )


def memory_line(label: str, compiled) -> None:
    ma = compiled.memory_analysis()
    if ma is None:
        return
    log(
        f"  memory_analysis {label}: argument {ma.argument_size_in_bytes} B, "
        f"output {ma.output_size_in_bytes} B, temp {ma.temp_size_in_bytes} B"
    )


def holds_kernel(checks: Checks, name: str, compiled, on_tpu: bool) -> None:
    """The compiled program calls the Mosaic kernel: nothing interpreted."""
    memory_line(name, compiled)
    if on_tpu:
        checks.add(
            f"{name} runs the compiled kernel",
            "tpu_custom_call" in compiled.as_text(),
            "tpu_custom_call in the compiled HLO",
        )


def mixture_stream(sz: Sizes, key):
    """The path A stream: chunk i of one fixed mixture, drawn on the device."""
    import jax

    from repro.data import synthetic

    k_means, k_data = jax.random.split(key)
    _, _, means = synthetic.gaussian_mixture(
        k_means, 1, sz.k, sz.n, return_labels=True
    )
    draw = jax.jit(
        lambda kk, mu: synthetic.gaussian_mixture(
            kk, sz.chunk, sz.k, sz.n, means=mu
        )
    )
    return [
        draw(jax.random.fold_in(k_data, i), means)
        for i in range(sz.points // sz.chunk)
    ]


def path_a(sz: Sizes, checks: Checks, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ckm, lloyd
    from repro.core import quantize as qz
    from repro.core.engine import SketchEngine
    from repro.kernels import ops

    log(f"== path A: fit_streaming, N={sz.points}, chunk={sz.chunk}, "
        f"n={sz.n}, K={sz.k}")
    key = jax.random.PRNGKey(sz.seed)
    k_data, k_fit, k_lloyd, k_q = jax.random.split(key, 4)
    with Timer("draw the points on the device (set-up)"):
        chunks = mixture_stream(sz, k_data)
        jax.block_until_ready(chunks)

    cfg = ckm.CKMConfig(k=sz.k, sketch_backend="pallas")
    with Timer("fit_streaming, cold (compile + sketch + CLOMPR decode)"):
        res = ckm.fit_streaming(k_fit, iter(chunks), cfg)
        jax.block_until_ready(res.centroids)
    op = res.freq_op
    log(f"  m={op.m}, sigma2={float(res.sigma2):.4f}")

    engine = ckm.make_engine(op, cfg)
    with Timer("sketch pass over every chunk, warm") as t_pass:
        state = engine.init_state()
        for x in chunks:
            state = engine.update(state, x)
        jax.block_until_ready(state)
    log(f"  sketch rate, warm: {sz.points / t_pass.seconds:.4g} points/s")
    with Timer("decode_sketch (CLOMPR), warm"):
        z, lo, hi = engine.finalize(state)
        cents, _, _ = ckm.decode_sketch(
            jax.random.split(k_fit)[1], z, op, lo, hi, cfg
        )
        jax.block_until_ready(cents)

    x0 = chunks[0]
    beta = jnp.ones((x0.shape[0],), jnp.float32)
    holds_kernel(
        checks, "A: pallas sketch step",
        ops.fourier_sketch_sums.lower(
            x0, op, beta, block_n=engine.block_n, block_m=engine.block_m
        ).compile(),
        on_tpu,
    )
    z_p, _, _ = SketchEngine(op, "pallas").sketch(x0)
    z_x, _, _ = SketchEngine(op, "xla").sketch(x0)
    rel = float(jnp.linalg.norm(z_p - z_x) / jnp.linalg.norm(z_x))
    checks.add("A: pallas vs xla sketch of one chunk", rel <= 1e-4,
               f"relative difference {rel:.3e} (limit 1e-4)")

    quant = qz.make_quantizer(k_q, op.m, "1bit")
    qe_p = SketchEngine(op, "pallas", quantizer=quant)
    qe_x = SketchEngine(op, "xla", quantizer=quant)
    holds_kernel(
        checks, "A: 1-bit pallas sketch step",
        ops.quantized_fourier_sketch_sums.lower(
            x0, op, quant.dither, bits=1, block_n=qe_p.block_n,
            block_m=qe_p.block_m,
        ).compile(),
        on_tpu,
    )
    s_p = qe_p.update(qe_p.init_state(), x0)
    s_x = qe_x.update(qe_x.init_state(), x0)
    diff = int(jnp.sum(jnp.abs(s_p.qcos_acc - s_x.qcos_acc))
               + jnp.sum(jnp.abs(s_p.qsin_acc - s_x.qsin_acc)))
    checks.add(
        "A: 1-bit pallas code sums == xla code sums, one chunk", diff == 0,
        f"sum |difference| {diff} over {2 * op.m} sums of "
        f"{x0.shape[0]} codes",
    )

    x_all = jnp.concatenate(chunks)
    del chunks, state
    lcfg = lloyd.LloydConfig(
        k=sz.k, replicates=sz.lloyd_replicates, init="kpp"
    )
    with Timer(f"Lloyd-Max x{sz.lloyd_replicates} (k-means++), cold"):
        base = lloyd.kmeans(k_lloyd, x_all, lcfg)
        jax.block_until_ready(base.centroids)
    sse_ckm = float(ckm.sse(x_all, res.centroids))
    sse_lloyd = float(ckm.sse(x_all, base.centroids))
    ratio = sse_ckm / sse_lloyd
    log(f"  SSE/N: CKM {sse_ckm / sz.points:.5f}, "
        f"Lloyd {sse_lloyd / sz.points:.5f}")
    checks.add("A: CKM SSE / Lloyd-Max SSE", bool(np.isfinite(ratio))
               and ratio <= 1.3, f"{ratio:.4f} (limit 1.3)")
    peak_memory("path A")


def fleet_traffic(sz: Sizes, key):
    """Path B traffic: ``flushes`` rounds of (tenant ids, host batches).

    Tenant popularity is Zipf(0.99) over a seeded permutation of the tenant
    ids; each tenant draws from its own mixture of ``tenant_k`` unit
    clusters, means spread as in the paper's §4.1 law with ``c = 6`` (well
    separated, so every cluster is recoverable).  Batches are made on the
    device in bulk and handed to the service as host arrays, the form a
    request arrives in.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    k_mu, k_round = jax.random.split(key)
    spread = float(np.sqrt(6.0 * sz.tenant_k ** (1.0 / sz.n)))
    means = jax.random.normal(k_mu, (sz.tenants, sz.tenant_k, sz.n)) * spread
    rng = np.random.default_rng(sz.seed)
    pop = 1.0 / np.arange(1, sz.tenants + 1) ** 0.99
    order = rng.permutation(sz.tenants)
    ids = order[rng.choice(sz.tenants, size=sz.requests, p=pop / pop.sum())]

    @jax.jit
    def draw(kk, mu):  # mu: (R, K, n) -> (R, B, n)
        kl, kx = jax.random.split(kk)
        lab = jax.random.randint(kl, mu.shape[:1] + (sz.request_points,),
                                 0, sz.tenant_k)
        pick = jnp.take_along_axis(mu, lab[..., None], axis=1)
        return pick + jax.random.normal(kx, pick.shape)

    per = sz.requests // sz.flushes
    rounds = []
    for r in range(sz.flushes):
        rid = ids[r * per:(r + 1) * per]
        xs = np.asarray(draw(jax.random.fold_in(k_round, r), means[rid]))
        rounds.append((rid, xs))
    return means, rounds


def traffic_sigma2(sz: Sizes, key, rounds) -> float:
    """The fleet's frequency scale, estimated once from a sample of the
    first round's busiest tenant (every tenant shares the mixture law)."""
    import numpy as np

    from repro.core import frequencies

    rid, xs = rounds[0]
    busiest = np.bincount(rid).argmax()
    sample = xs[rid == busiest].reshape(-1, sz.n)[:2048]
    return float(frequencies.estimate_sigma2(key, sample))


def serve(service, rounds) -> None:
    for rid, xs in rounds:
        for t, x in zip(rid, xs):
            service.submit(int(t), x)
        service.flush()


def path_b(sz: Sizes, checks: Checks, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ckm
    from repro.core import fleet as fl
    from repro.core.engine import SketchEngine
    from repro.core.freq_ops.dense import DenseOperator
    from repro.serve.fleet_service import FleetService

    m = 10 * sz.k * sz.n
    log(f"== path B: FleetService, T={sz.tenants}, n={sz.n}, m={m}, "
        f"K={sz.tenant_k} per tenant, {sz.requests} requests x "
        f"{sz.request_points} points")
    key = jax.random.PRNGKey(sz.seed + 1)
    k_spec, k_traffic, k_dec, k_sig = jax.random.split(key, 4)
    with Timer("traffic drawn on the device (set-up)"):
        means, rounds = fleet_traffic(sz, k_traffic)
    sigma2 = traffic_sigma2(sz, k_sig, rounds)
    log(f"  fleet sigma2 (estimated from one tenant's sample): {sigma2:.4f}")
    with Timer("fleet specs + engine (set-up)"):
        specs = fl.fleet_specs(k_spec, sz.tenants, "dense", m, sz.n, sigma2)
        fleet = fl.FleetEngine(specs, backend="pallas")

    block = jax.ShapeDtypeStruct(
        (sz.tenants, sz.request_points, sz.n), jnp.float32
    )
    holds_kernel(
        checks, "B: vmapped fleet update",
        jax.jit(fleet.update).lower(fleet.init_state(), block).compile(),
        on_tpu,
    )
    service = FleetService(
        fleet, ckm.CKMConfig(k=sz.tenant_k), decode_key=k_dec
    )
    with Timer(f"{sz.flushes} x (submit {sz.requests // sz.flushes} + "
               "flush), cold"):
        serve(service, rounds)
        jax.block_until_ready(service.state)
    log(f"  flush dispatches: {service.stats.flushes}, "
        f"points: {service.stats.points}")

    # Reference: one isolated XLA engine per tenant, same batches, same order.
    # Its operator is the tenant's matrix without the spec, which would make
    # every tenant a new jit cache entry (the spec is pytree metadata), and
    # its update is compiled once for every tenant.
    @jax.jit
    def iso_update(w, state, x):
        return SketchEngine(DenseOperator(w), "xla").update(state, x)

    with Timer("isolated xla engines (reference)"):
        batches: dict[int, list] = {}
        for rid, xs in rounds:
            for t, x in zip(rid, xs):
                batches.setdefault(int(t), []).append(x)
        iso_rows = {}
        for t, xs in batches.items():
            eng = SketchEngine(DenseOperator(fleet.operator(t).w), "xla")
            st = eng.init_state()
            for x in xs:
                st = iso_update(eng.freq_op.w, st, x)
            iso_rows[t] = eng.finalize(st) + (st.count,)
    tenants = sorted(iso_rows)
    z_f, lo_f, hi_f = fleet.finalize(service.state)
    sel = np.asarray(tenants)
    z_f, lo_f, hi_f = (np.asarray(a)[sel] for a in (z_f, lo_f, hi_f))
    cnt_f = np.asarray(service.state.count)[sel]
    z_i = np.stack([np.asarray(iso_rows[t][0]) for t in tenants])
    lo_i = np.stack([np.asarray(iso_rows[t][1]) for t in tenants])
    hi_i = np.stack([np.asarray(iso_rows[t][2]) for t in tenants])
    cnt_i = np.asarray([float(iso_rows[t][3]) for t in tenants])
    zdiff = float(np.max(np.abs(z_f - z_i)))
    bounds_equal = bool(np.array_equal(lo_f, lo_i) and np.array_equal(hi_f, hi_i)
                        and np.array_equal(cnt_f, cnt_i))
    checks.add(
        f"B: {len(tenants)} flushed tenant rows vs isolated xla engines",
        zdiff <= 1e-4 and bounds_equal,
        f"max |z_fleet - z_isolated| {zdiff:.3e} (limit 1e-4); bounds and "
        f"counts equal: {bounds_equal}",
    )

    hot = sorted(batches, key=lambda t: -len(batches[t]))[: sz.decoded]
    errs = []
    with Timer(f"decode {len(hot)} tenants (sketch_shift), cold"):
        for t in hot:
            res = service.decode(t)
            errs.append(matched_error(np.asarray(means[t]),
                                      np.asarray(res.centroids)))
    log("  decoded tenants (requests): "
        + ", ".join(f"{t} ({len(batches[t])})" for t in hot))
    log("  worst matched centroid error per tenant: "
        + ", ".join(f"{e:.3f}" for e in errs))
    checks.add("B: decoded centroids within one cluster std of true means",
               max(errs) < 1.0, f"worst {max(errs):.3f} (limit 1.0)")
    peak_memory("path B")


def matched_error(true_means, cents) -> float:
    """Greedy one-to-one matching; the largest matched distance."""
    import numpy as np

    d = np.linalg.norm(true_means[:, None] - cents[None], axis=-1)
    worst = 0.0
    for _ in range(true_means.shape[0]):
        i, j = np.unravel_index(np.argmin(d), d.shape)
        worst = max(worst, float(d[i, j]))
        d[i, :] = np.inf
        d[:, j] = np.inf
    return worst


def own_blocks(arr, devices, rows: int) -> bool:
    """Shard s of ``arr`` lives on ``devices[s]`` and holds rows
    ``[s·rows, (s+1)·rows)`` — nothing is left on device 0."""
    shards = sorted(arr.addressable_shards, key=lambda s: s.index[0].start or 0)
    return len(shards) == len(devices) and all(
        s.device == dev
        and (s.index[0].start or 0) == i * rows
        and s.data.shape[0] == rows
        for i, (s, dev) in enumerate(zip(shards, devices))
    )


def four_chips(sz: Sizes, checks: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ckm
    from repro.core import fleet as fl
    from repro.core import freq_ops
    from repro.core.engine import SketchEngine
    from repro.launch.mesh import make_mesh
    from repro.serve.fleet_service import FleetService

    p = 4
    m = 10 * sz.k * sz.n
    log(f"== four chips: mesh fleet (T={sz.tenants}, {p} shards) and the "
        "sharded sketch backend")
    key = jax.random.PRNGKey(sz.seed + 2)
    k_spec, k_traffic, k_op, k_x, k_sig = jax.random.split(key, 5)
    _, rounds = fleet_traffic(sz, k_traffic)
    sigma2 = traffic_sigma2(sz, k_sig, rounds)
    specs = fl.fleet_specs(k_spec, sz.tenants, "dense", m, sz.n, sigma2)
    cfg = ckm.CKMConfig(k=sz.tenant_k)
    plain = FleetService(fl.FleetEngine(specs, backend="pallas"), cfg)
    meshed_engine = fl.FleetEngine(
        specs, backend="pallas", sharding="mesh", tenant_shards=p
    )
    meshed = FleetService(meshed_engine, cfg)
    with Timer("unsharded fleet: serve the traffic, cold"):
        serve(plain, rounds)
        jax.block_until_ready(plain.state)
    with Timer(f"mesh fleet ({p} shards): serve the traffic, cold"):
        serve(meshed, rounds)
        jax.block_until_ready(meshed.state)
    devices = list(meshed_engine.mesh.devices.flat)
    placed = all(
        own_blocks(leaf, devices, meshed_engine.shard_rows)
        for leaf in jax.tree_util.tree_leaves(meshed.state)
    )
    checks.add("4: mesh fleet state, one row block per device", placed,
               f"{p} devices x {meshed_engine.shard_rows} tenant rows")
    z_a, lo_a, hi_a = (np.asarray(a) for a in plain.engine.finalize(plain.state))
    z_b, lo_b, hi_b = (np.asarray(a) for a in meshed_engine.finalize(meshed.state))
    zdiff = float(np.max(np.abs(z_a - z_b)))
    bitwise = bool(np.array_equal(z_a, z_b))
    checks.add(
        f"4: mesh fleet vs unsharded fleet, all {sz.tenants} tenants",
        zdiff <= 1e-4 and np.array_equal(lo_a, lo_b)
        and np.array_equal(hi_a, hi_b),
        f"max |z difference| {zdiff:.3e} (limit 1e-4), bitwise: {bitwise}",
    )

    op = freq_ops.make_operator("dense", k_op, m, sz.n, 1.0)
    x = mixture_stream(dataclasses.replace(sz, points=sz.chunk), k_x)[0]
    sharded = SketchEngine(op, "sharded", mesh=make_mesh((p,), ("data",)))
    xs = sharded.shard_points(x)
    checks.add("4: sharded backend points, one block per device",
               own_blocks(xs, list(sharded.mesh.devices.flat), sz.chunk // p),
               f"{p} devices x {sz.chunk // p} points")
    with Timer(f"sharded backend sketch on a {p}-way data mesh, cold"):
        z_s, lo_s, hi_s = sharded.sketch(xs)
        jax.block_until_ready(z_s)
    z_p, lo_p, hi_p = SketchEngine(op, "pallas").sketch(x)
    rel = float(jnp.linalg.norm(z_s - z_p) / jnp.linalg.norm(z_p))
    checks.add(
        f"4: sharded sketch ({p} devices) vs one-device pallas sketch",
        rel <= 1e-4 and bool(jnp.array_equal(lo_s, lo_p))
        and bool(jnp.array_equal(hi_s, hi_p)),
        f"relative difference {rel:.3e} (limit 1e-4)",
    )
    peak_memory("the four-chip phase")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip comparisons")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on whatever devices JAX finds; prints "
                         "no result line")
    args = ap.parse_args(argv)

    import jax

    from repro.utils.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    log(f"devices: {len(devices)} x {dev.platform} ({dev.device_kind})")
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    sizes = TINY if args.rehearse else FULL
    checks = Checks()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(sizes, checks)
    else:
        path_a(sizes, checks, on_tpu)
        path_b(sizes, checks, on_tpu)
    log(f"total {time.perf_counter() - t0:.1f} s")
    if checks.failed:
        print("chip_smoke: failed: " + "; ".join(checks.failed),
              file=sys.stderr)
        return 1
    if args.rehearse:
        log("rehearsal passed (no result line)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
