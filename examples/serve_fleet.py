"""Multi-tenant sketch serving: one stacked fleet, decode-on-demand.

    PYTHONPATH=src python examples/serve_fleet.py
    PYTHONPATH=src python examples/serve_fleet.py --devices 4 --shards 4

Runs a small fleet end-to-end: per-tenant operators from ~70 B specs, a
burst of interleaved ``(tenant, batch)`` requests folded through the
segment-scatter ingest, decode-on-demand with the (tenant, version) LRU,
and evict/restore of a cold tenant — then prints the service stats and the
bitwise-isolation check against a standalone per-tenant engine.

Sharding flags:

``--shards P`` splits the tenant axis over P devices (a contiguous block of
``tenants / P`` rows per device, ``FleetEngine(sharding="mesh")``); the
flush then shard-routes interleaved requests host-side and the run prints
per-shard request counts and update throughput.  On a machine without P
real accelerators, ``--devices N`` forces N XLA host-platform (CPU)
devices by setting ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
— this MUST happen before jax initialises, which is why this script parses
argv and sets the flag before importing jax.  Host devices share the
physical cores, so they demonstrate placement and routing, not wall-clock
speedup; real speedup needs real devices (see docs/scaling.md).
"""

import argparse
import os
import tempfile
import time


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    p.add_argument(
        "--tenants", type=int, default=64,
        help="fleet size T (default 64); must be divisible by --shards",
    )
    p.add_argument(
        "--shards", type=int, default=1,
        help="tenant shards P: contiguous T/P-row blocks, one per device",
    )
    p.add_argument(
        "--devices", type=int, default=0,
        help="force this many XLA host-platform devices (0 = leave the "
        "platform alone); must be >= --shards",
    )
    p.add_argument(
        "--requests", type=int, default=200,
        help="interleaved (tenant, batch) requests to serve (default 200)",
    )
    return p.parse_args()


ARGS = parse_args()
if ARGS.devices:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ARGS.devices}"
    ).strip()

import jax  # noqa: E402  (after XLA_FLAGS — device count is set at init)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import CKMConfig, FleetEngine, fleet_specs  # noqa: E402
from repro.data import synthetic  # noqa: E402
from repro.launch.specs import SketchJobSpec  # noqa: E402
from repro.serve.fleet_service import FleetService  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

K, FEAT = 3, 4
M = 10 * K * FEAT


def main():
    enable_compile_cache()
    job = SketchJobSpec(
        n_tenants=ARGS.tenants, tenant_shards=ARGS.shards
    ).validate()
    # Each tenant is an independent clustering problem: its own frequency
    # operator (rebuilt from a ~70 B spec) over its own data distribution.
    specs = fleet_specs(
        jax.random.PRNGKey(0), job.n_tenants, "dense", M, FEAT, 1.0
    )
    engine = FleetEngine(specs, **job.fleet_kwargs())
    print(f"{engine} holding {engine.state_bytes() / 1024:.0f} KiB of state "
          f"on {len(jax.devices())} device(s)")

    decode_cfg = CKMConfig(k=K)  # decoder defaults to sketch_shift in-service
    with tempfile.TemporaryDirectory() as ckpt_dir:
        svc = FleetService(
            engine, decode_cfg, checkpoint_dir=ckpt_dir,
            **{**job.service_kwargs(), "decode_cache_entries": 16},
        )

        # A burst of interleaved requests: random tenants, each batch drawn
        # from that tenant's own mixture.
        rng = np.random.default_rng(7)
        shard_requests = np.zeros(engine.tenant_shards, np.int64)
        t_serve = time.perf_counter()
        points = 0
        for step in range(ARGS.requests):
            t = int(rng.integers(job.n_tenants))
            x, _, _ = synthetic.gaussian_mixture(
                jax.random.fold_in(jax.random.PRNGKey(t), step),
                256, k=K, n=FEAT, c=6.0, return_labels=True,
            )
            svc.submit(t, x)
            shard_requests[engine.owner_shard(t)] += 1
            points += x.shape[0]
            if step % 8 == 7:  # flush every few requests, async staging
                svc.flush(async_ingest=True)
        svc.flush()
        jax.block_until_ready(svc.state)
        serve_s = time.perf_counter() - t_serve
        print(f"served {ARGS.requests} requests ({points} points) in "
              f"{serve_s:.3f}s -> {points / serve_s:,.0f} points/s")
        if engine.tenant_shards > 1:
            for s in range(engine.tenant_shards):
                lo = s * engine.shard_rows
                print(f"  shard {s}: tenants [{lo}, "
                      f"{lo + engine.shard_rows}) | "
                      f"{int(shard_requests[s])} requests | "
                      f"{shard_requests[s] * 256 / serve_s:,.0f} points/s")

        # Decode-on-demand: only the tenants somebody asks about pay decode.
        hot = [0, 1, 2, 0, 1, 0]
        for t in hot:
            res = svc.decode(t)
            tag = "cache hit " if res.cached else "fresh decode"
            print(f"tenant {t}: {tag} v{res.version} "
                  f"cost={float(res.cost):.4f}")

        # Evict a cold tenant (state row + spec -> checkpoint, row reset);
        # the next touch restores it transparently and bitwise.
        cold = 3
        before = engine.tenant_state(svc.state, cold)
        svc.evict(cold)
        restored = svc.decode(cold)  # auto-restore, then decode
        after = engine.tenant_state(svc.state, cold)
        bitwise = all(
            bool(jnp.array_equal(a, b))
            for a, b in zip(before, after)
        )
        print(f"tenant {cold}: evicted -> restored bitwise={bitwise}, "
              f"decode cost={float(restored.cost):.4f}")

        s = svc.stats
        print(f"requests={s.requests} points={s.points} "
              f"flushes={s.flushes} decodes={s.decodes} "
              f"hit_rate={s.hit_rate:.2f} "
              f"evictions={s.evictions} restores={s.restores}")


if __name__ == "__main__":
    main()
