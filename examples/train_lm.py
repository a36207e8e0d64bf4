"""Train a small LM end-to-end with the full substrate: sharded train step,
checkpoints (+restart), CKM activation monitor, compressive data balancing.

    PYTHONPATH=src python examples/train_lm.py --arch llama3.2-1b --steps 200

Uses the reduced (smoke) config by default so a few hundred steps run on CPU;
pass --full-config on real hardware.  Kill it mid-run and re-invoke: it
resumes from the latest checkpoint and reproduces the uninterrupted loss
curve exactly (deterministic data = f(seed, step)).
"""

import argparse

import jax.numpy as jnp

from repro.configs.base import ShapeConfig, get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.launch.mesh import make_local_mesh
from repro.train.train_loop import LoopConfig, run
from repro.utils.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="checkpoints/train_lm")
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch) if args.full_config else get_smoke_config(args.arch)
    shape = ShapeConfig("example", args.seq, args.batch, "train")
    mesh = make_local_mesh()
    loop = LoopConfig(
        steps=args.steps,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=50,
        monitor_k=4,  # CKM activation monitor: 4 clusters of pooled hiddens
        balance_every=50,  # compressive mixture re-balancing
        log_every=10,
        dtype=jnp.float32,
    )
    out = run(cfg, shape, mesh, loop, DataConfig(seed=0, n_domains=4))
    mres = out["monitor_result"]
    print("\nactivation-space clusters (CKM from the streaming sketch):")
    print("  mixture weights:", [f"{w:.3f}" for w in mres.weights])
    print("  final loss:", out["history"][-1]["loss"])


if __name__ == "__main__":
    main()
