"""Sharding rules: FSDP + TP (+ EP + SP) over the production mesh.

Axes (launch/mesh.py): ``("pod", "data", "model")`` multi-pod or
``("data", "model")`` single-pod.

- Parameters: tensor-parallel dim over "model" (attention heads / FFN hidden /
  vocab / experts), FSDP dim over "data" (MaxText-style: XLA inserts per-layer
  all-gathers forward and reduce-scatters backward => ZeRO-3 memory without
  manual collectives).  Optimizer state mirrors parameter shardings.
- Batch: global batch over ("pod", "data").
- Decode caches: the KV-cache *sequence* dimension shards over "model"
  (sequence-parallel decode attention: scores/softmax reductions over the
  sharded axis become psums — the cache never gathers).  Recurrent states
  shard over their channel dim where divisible.
- Any dim not divisible by its axis size falls back to replication (guarded
  here, so odd vocab sizes like 92553 compile; see §Perf for the padded-vocab
  optimisation).

Param rules match by path suffix; recurrent-family (xlstm) params stay
replicated except embeddings (125M model — TP would only add latency).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

# (path-regex, spec builder) — first match wins.  "F" = fsdp axis, "M" = model.
_PARAM_RULES: list[tuple[str, tuple[str | None, ...]]] = [
    (r"embed/table$", ("M", "F")),  # (vocab, d)
    (r"lm_head/w$", ("F", "M")),  # (d, vocab)
    (r"(mixer|cross)/wq$", ("F", "M")),
    (r"(mixer|cross)/wk$", ("F", "M")),
    (r"(mixer|cross)/wv$", ("F", "M")),
    (r"(mixer|cross)/wo$", ("M", "F")),
    (r"mlp/w_gate$", ("F", "M")),
    (r"mlp/w_up$", ("F", "M")),
    (r"mlp/w_down$", ("M", "F")),
    (r"mlp/router$", (None, None)),  # replicated: shard_map body computes it
    # MoE experts (E, d, f)/(E, f, d): EP over model, FSDP over d/f.
    (r"mlp/w_(gate|up)$", ("M", "F", None)),
    (r"mlp/w_down$", ("M", None, "F")),
    # Mamba: channel (d_inner) dim over model.
    (r"mixer/in_proj$", ("F", "M")),
    (r"mixer/conv_w$", (None, "M")),
    (r"mixer/conv_b$", ("M",)),
    (r"mixer/x_proj$", ("M", None)),
    (r"mixer/dt_proj$", (None, "M")),
    (r"mixer/dt_bias$", ("M",)),
    (r"mixer/a_log$", ("M", None)),
    (r"mixer/d_skip$", ("M",)),
    (r"mixer/out_proj$", ("M", "F")),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def axis_extent(mesh: Mesh, axes) -> int:
    """Product of the named mesh axes' sizes — the device count a leading
    data axis is split over.  Shared by the batch/cache spec builders here
    and the SketchEngine's sharded backend (padding + merge fan-in p, the
    ``p`` of ``core.topology.wire_cost_model``)."""
    sizes = _axis_sizes(mesh)
    ext = 1
    for a in axes:
        ext *= sizes[a]
    return ext


def tenant_mesh(shards: int, axis: str = "tenant", devices=None) -> Mesh:
    """1-D mesh for fleet tenant sharding: ``shards`` devices on one axis.

    The fleet's stacked state (``core.fleet.FleetEngine(sharding="mesh")``)
    splits its leading tenant axis over this mesh — each device owns one
    contiguous block of ``n_tenants / shards`` tenant rows.  Tenant sharding
    is pure data parallelism, so a single axis is always enough; the axis
    name defaults to ``SketchJobSpec.tenant_shard_axis``'s default.
    """
    from repro.launch.mesh import make_mesh

    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    devices = list(jax.devices()) if devices is None else list(devices)
    if shards > len(devices):
        raise ValueError(
            f"tenant_mesh needs {shards} devices, only {len(devices)} "
            "available (force host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            "jax initialises)"
        )
    return make_mesh((shards,), (axis,), devices=devices[:shards])


def tenant_shard_specs(tree: Any, axis: str = "tenant") -> Any:
    """``P(axis)`` for every leaf of a stacked fleet pytree.

    Every fleet leaf — state accumulators ``(T, m)``, bounds ``(T, n)``,
    scalars-per-tenant ``(T,)``, stacked operator leaves, dither rows —
    carries the tenant axis leading, so one spec rule covers the whole
    tree: shard dim 0 over ``axis``, replicate the rest.  Feed the result
    to :func:`to_shardings` for placement or to ``compat.shard_map``
    in/out specs.
    """
    return jax.tree_util.tree_map(lambda _: P(axis), tree)


def _resolve(spec_tags, shape, mesh, fsdp_axis, stacked: bool):
    """Tags -> PartitionSpec with divisibility guards.  ``stacked``: the leaf
    has a leading layer-group axis (from scan stacking) that stays unsharded."""
    sizes = _axis_sizes(mesh)
    model = sizes.get("model", 1)
    fsdp = sizes.get(fsdp_axis, 1) if fsdp_axis else 1
    dims = list(shape[1:]) if stacked else list(shape)
    if len(spec_tags) != len(dims):
        return P()  # rank mismatch — replicate
    out: list[Any] = []
    for tag, d in zip(spec_tags, dims):
        if tag == "M" and model > 1 and d % model == 0:
            out.append("model")
        elif tag == "F" and fsdp > 1 and d % fsdp == 0:
            out.append(fsdp_axis)
        else:
            out.append(None)
    if stacked:
        out = [None] + out
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_specs(
    params_shape: Any, cfg: ModelConfig, mesh: Mesh, fsdp_axis: str | None = "data"
) -> Any:
    """PartitionSpec pytree matching a params (shape) pytree."""
    moe_3d = {"w_gate", "w_up", "w_down"}
    replicate_families = cfg.family == "ssm"

    def spec(path, leaf):
        ps = _path_str(path)
        stacked = ps.startswith("groups/") or ps.startswith("encoder/groups")
        shape = leaf.shape
        if replicate_families and "embed" not in ps and "lm_head" not in ps:
            return P()
        # Distinguish dense-mlp 2D vs moe 3D weights sharing the name.
        name = ps.rsplit("/", 1)[-1]
        rank = len(shape) - (1 if stacked else 0)
        if name in moe_3d and rank == 3:
            tags = ("M", "F", None) if name in ("w_gate", "w_up") else ("M", None, "F")
            return _resolve(tags, shape, mesh, fsdp_axis, stacked)
        for pat, tags in _PARAM_RULES:
            if re.search(pat, ps) and len(tags) == rank:
                return _resolve(tags, shape, mesh, fsdp_axis, stacked)
        return P()  # norms, biases, gates: replicated

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def opt_state_specs(opt_shape: Any, pspecs: Any) -> Any:
    """Optimizer state mirrors param shardings (ZeRO via GSPMD).

    Adam m/v share the parameter spec; Adafactor's factored stats inherit the
    spec with the reduced dim removed; int8-quantised payloads replicate
    (their blocked layout decouples from the logical dims).
    """
    flat_p = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    by_path = {_path_str(k): v for k, v in flat_p}

    def pad(base: P, rank: int) -> tuple:
        t = tuple(base)
        return t + (None,) * (rank - len(t))

    def spec(path, leaf):
        ps = _path_str(path)
        for prefix in ("m/", "v/", "stats/"):
            if not ps.startswith(prefix):
                continue
            rest = ps[len(prefix) :]
            if rest in by_path:  # plain adam m/v — same shape, same spec
                return by_path[rest]
            if "/" in rest:
                cand, suffix = rest.rsplit("/", 1)
                if cand in by_path:
                    base = pad(by_path[cand], len(leaf.shape) + 1)
                    if suffix == "vr":  # param shape minus last dim
                        return P(*base[:-1])
                    if suffix == "vc":  # param shape minus 2nd-to-last dim
                        return P(*(base[:-2] + base[-1:]))
                    if suffix == "v":
                        return P(*base[: len(leaf.shape)])
                    return P()  # q/scale payloads
        return P()

    return jax.tree_util.tree_map_with_path(spec, opt_shape)


# ---------------------------------------------------------------------------
# Batch / cache specs per shape cell
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Any:
    """Specs for the training/prefill input batch dict."""
    ba = batch_axes(mesh)
    dp = axis_extent(mesh, ba)
    bspec = ba if shape.global_batch % dp == 0 and shape.global_batch >= dp else None
    specs = {"tokens": P(bspec, None)}
    if shape.kind == "train":
        specs["labels"] = P(bspec, None)
    if cfg.frontend == "vision":
        specs["patches"] = P(bspec, None, None)
    elif cfg.frontend == "audio":
        specs["frames"] = P(bspec, None, None)
    return specs


def cache_specs(cache_shape: Any, cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh):
    """Decode-cache specs: batch over (pod,data) when divisible; KV-cache
    sequence dim over "model" (SP decode); recurrent channels over "model"."""
    ba = batch_axes(mesh)
    dp = axis_extent(mesh, ba)
    model = _axis_sizes(mesh).get("model", 1)
    b = shape.global_batch
    bspec = ba if b % dp == 0 and b >= dp else None

    def spec(path, leaf):
        ps = _path_str(path)
        shp = leaf.shape
        stacked = ps.startswith("groups/")
        dims = shp[1:] if stacked else shp
        name = ps.rsplit("/", 1)[-1]
        out: list[Any] = [bspec]  # dim0 after optional stack = batch
        if name in ("k", "v", "ck", "cv", "cross_k", "cross_v"):
            # (B, S, KV, hd): shard S over model if divisible.
            s = dims[1]
            out += ["model" if s % model == 0 and not cfg.family == "ssm" else None,
                    None, None]
        elif name == "clogw":
            s = dims[1]
            out += ["model" if s % model == 0 else None, None]
        elif ps.endswith("state/conv"):
            out += [None, "model" if dims[2] % model == 0 else None]
        elif ps.endswith("state/ssm"):
            out += ["model" if dims[1] % model == 0 else None, None]
        elif "state/" in ps:  # mlstm C/n, slstm h/c/n/m — small: replicate
            out += [None] * (len(dims) - 1)
        else:
            out += [None] * (len(dims) - 1)
        if stacked:
            out = [None] + out
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def activation_sharder(mesh: Mesh | None, seq_shard: bool = False):
    """Constraint hook threaded through the model (MaxText-style).

    GSPMD sharding propagation alone loses the batch sharding deep inside
    scanned layers (observed: attention scores materialising with the GLOBAL
    batch per device).  Explicit constraints on the residual stream and the
    attention/FFN intermediates pin every activation's sharding.

    ``seq_shard`` (Megatron-style sequence parallelism) additionally shards
    the residual stream's sequence dim over "model": the per-layer remat save
    shrinks by the TP degree (61 x 940 MB -> 61 x 59 MB for kimi); XLA
    inserts the all-gather at attention/MLP entry and the reduce-scatter at
    exit.  Enabled for d_model >= 4096 archs (configs/base.py).

    kinds: resid (B,S,d) | heads (B,S,H,hd) | kv (B,S,KV,hd) | ffn (B,S,ff)
    """
    if mesh is None:
        return lambda x, kind: x
    sizes = _axis_sizes(mesh)
    ba = batch_axes(mesh)
    dp = axis_extent(mesh, ba)
    model = sizes.get("model", 1)

    def shard(x, kind: str):
        bspec = ba if (x.shape[0] % dp == 0 and x.shape[0] >= dp) else None
        if kind == "resid":
            s = x.shape[1]
            sspec = (
                "model" if seq_shard and s % model == 0 and s > model else None
            )
            spec = P(bspec, sspec, None)
        elif kind in ("heads", "kv"):
            h = x.shape[2]
            spec = P(bspec, None, "model" if h % model == 0 else None, None)
        elif kind == "ffn":
            f = x.shape[2]
            spec = P(bspec, None, "model" if f % model == 0 else None)
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return shard


def to_shardings(specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )
