import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN).

For every (arch x shape) cell: build the production mesh, jit the train /
prefill / serve step with full FSDP+TP(+EP/SP) shardings, ``.lower()``,
``.compile()``, print ``memory_analysis()`` + ``cost_analysis()``, and write
the roofline terms to experiments/dryrun/<arch>__<shape>__<mesh>.json.

    python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--jobs N]

The XLA_FLAGS line above MUST precede any jax import (jax locks the device
count at first init) — the 512 placeholder CPU devices exist only here.
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import ARCHS, SHAPES, ShapeConfig, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import prefill_batch_specs, train_batch_specs
from repro.utils import roofline as rl

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def cell_skipped(cfg, shape: ShapeConfig) -> str | None:
    if shape.kind == "long_decode" and cfg.long_context == "skip":
        return "pure full-attention arch: long_500k skipped per DESIGN.md §4"
    return None


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            pass
    return k, v


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    verbose: bool = True,
    overrides: dict | None = None,
) -> dict:
    import dataclasses as _dc

    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": shape.kind,
    }
    skip = cell_skipped(cfg, shape)
    if skip:
        result["status"] = "skipped"
        result["reason"] = skip
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    tokens = shape.global_batch * shape.seq_len

    if shape.kind == "train":
        from repro.launch.train import default_opt_config, jit_train_step
        from repro.optim.optimizers import make_optimizer

        jitted, shapes, state_sh, _ = jit_train_step(cfg, shape, mesh)
        batch = train_batch_specs(cfg, shape)
        lowered = jitted.lower(shapes, batch)
        model_flops = rl.train_model_flops(cfg.active_param_count(), tokens)
    elif shape.kind == "prefill":
        from repro.launch.serve import jit_prefill

        jitted, (pshapes, bshapes) = jit_prefill(cfg, shape, mesh)
        lowered = jitted.lower(pshapes, bshapes)
        model_flops = 2.0 * cfg.active_param_count() * tokens
    else:  # decode / long_decode
        from repro.launch.serve import jit_serve_step

        jitted, (pshapes, tok, cshapes, idx) = jit_serve_step(cfg, shape, mesh)
        lowered = jitted.lower(pshapes, tok, cshapes, idx)
        model_flops = rl.decode_model_flops(
            cfg.active_param_count(), shape.global_batch
        )

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    # The production mesh is TPU v5e; the CPU placeholders only compile.
    roof = rl.analyze(compiled, chips, model_flops, "TPU v5 lite")
    result.update(
        status="ok",
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        chips=chips,
        params=cfg.param_count(),
        active_params=cfg.active_param_count(),
        tokens=tokens,
        flops_per_device=roof.flops,
        hbm_bytes_per_device=roof.hbm_bytes,
        collective_bytes_per_device=roof.collective_bytes,
        compute_s=roof.compute_s,
        memory_s=roof.memory_s,
        collective_s=roof.collective_s,
        dominant=roof.dominant,
        model_flops=roof.model_flops,
        useful_ratio=round(roof.useful_ratio, 4),
        roofline_fraction=round(roof.roofline_fraction(), 4),
    )
    from repro.utils import hlo as hlo_mod

    coll = hlo_mod.analyze_compiled(compiled)
    result["collectives"] = {
        op: {"bytes": b, "count": int(coll.coll_count[op])}
        for op, b in sorted(coll.coll_by_op.items())
    }
    try:
        result["memory_analysis"] = {
            "argument_size": mem.argument_size_in_bytes,
            "output_size": mem.output_size_in_bytes,
            "temp_size": mem.temp_size_in_bytes,
            "generated_code_size": mem.generated_code_size_in_bytes,
        }
    except AttributeError:
        result["memory_analysis"] = str(mem)
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}]")
        print(f"  memory_analysis: {result['memory_analysis']}")
        print(
            f"  flops/dev {roof.flops:.3e}  hbm/dev {roof.hbm_bytes:.3e}  "
            f"coll/dev {roof.collective_bytes:.3e}"
        )
        print(
            f"  compute {roof.compute_s*1e3:.2f} ms | memory {roof.memory_s*1e3:.2f} ms"
            f" | collective {roof.collective_s*1e3:.2f} ms -> {roof.dominant}-bound"
        )
        print(
            f"  useful_ratio {roof.useful_ratio:.3f}  roofline_fraction "
            f"{roof.roofline_fraction():.3f}  (lower {t_lower:.0f}s compile {t_compile:.0f}s)"
        )
    return result


def save(result: dict):
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    name = f"{result['arch']}__{result['shape']}__{result['mesh']}.json"
    (OUT_DIR / name).write_text(json.dumps(result, indent=2))


def main():  # pragma: no cover - CLI
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument(
        "--set", action="append", default=[],
        help="config override key=value (repeatable), e.g. --set score_dtype=bf16",
    )
    ap.add_argument("--tag", default=None, help="suffix for the output json")
    args = ap.parse_args()
    overrides = dict(_parse_override(kv) for kv in args.set) or None

    cells = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        try:
            result = run_cell(arch, shape, args.multi_pod, overrides=overrides)
        except Exception as e:  # noqa: BLE001 - report and continue
            traceback.print_exc()
            result = {
                "arch": arch, "shape": shape,
                "mesh": "2x16x16" if args.multi_pod else "16x16",
                "status": "error", "error": f"{type(e).__name__}: {e}",
            }
            failures += 1
        if args.tag:
            result["tag"] = args.tag
            result["mesh"] = f"{result['mesh']}__{args.tag}"
        save(result)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
