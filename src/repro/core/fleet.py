"""Multi-tenant fleet engine: thousands of sketch states as ONE stacked state.

The paper's selling point — sketch size O(K·n) independent of dataset size —
compounds across users: a tenant's entire clustering state is its O(m) sketch
accumulators plus the ~70 B ``FreqOpSpec`` rebuild recipe (PR 5), so thousands
of independent tenants fit in the memory one Lloyd-Max run would need.  This
module is the compute layer that exploits that: per-tenant
:class:`~repro.core.engine.SketchEngineState` s are held **stacked along a
leading tenant axis** (``cos_acc (T, m)``, ``lower (T, n)``, …) and every
monoid op runs ``vmap``-ed over that axis — one XLA dispatch for the whole
fleet instead of T Python-dispatched engine calls.

Contract: the vmapped monoid law
--------------------------------
The fleet has no state code of its own: the state format, the batch
partials, the lift to a tick, the merge and the finalize are
``core.engine``'s state functions, vmapped over the tenant axis (and
shard_mapped on a mesh).  For every tenant t, ``FleetEngine``
update/merge/finalize therefore runs the *same* per-tenant trace as a
:class:`~repro.core.engine.SketchEngine` with the same operator/quantizer,
batched.  ``tests/test_fleet.py`` pins row t **bitwise** equal to that
engine for float and 1-bit states, undecayed and decayed, on the xla and
pallas backends, at a batch of 12 points per tenant.  Batching may change
the association of a float reduction: on the CPU's float XLA path, rows at
64 points per tenant differ from the isolated engine by up to ~1.5e-5 in
``cos_acc`` (one float32 ulp at its magnitude), while 1-bit states and the
Pallas kernel stay bitwise.  Everything the single-sketch stack guarantees
(split invariance, merge associativity/commutativity, quantized bitwise
merges) lifts to the fleet up to that rounding.

Request routing: segment-scatter
--------------------------------
Serving traffic arrives as interleaved ``(tenant_id, batch)`` requests, not
as one aligned ``(T, B, n)`` block.  :meth:`FleetEngine.ingest` computes all
request partials in one vmapped pass (per-request operators gathered from the
stacked leaves by tenant id) and folds them into the stacked state with a
segment-scatter: when tenant ids are unique within the call this is one XLA
scatter-add/min/max per leaf; when a flush carries several requests for the
same tenant it falls back to an ordered ``lax.scan`` fold so float partials
combine in **arrival order** — exactly the association the tenant's isolated
engine would have used, keeping the bitwise-isolation contract.

Tenant state surgery (``tenant_state`` / ``set_tenant`` / ``reset_tenant``)
is what eviction/restore builds on: a cold tenant's row is checkpointed
(state leaves + spec), reset to the monoid identity, and scattered back in
on demand — see ``repro.serve.fleet_service``.

Mesh sharding: tenant parallelism
---------------------------------
``FleetEngine(sharding="mesh", tenant_shards=p)`` splits the stacked state
over a p-device mesh along ``tenant_shard_axis``: device s owns the
contiguous block of ``n_tenants / p`` tenant rows ``[s·block, (s+1)·block)``
— float and quantized int32 twins, the stacked operator leaves, dither rows,
and decay stamps all shard together (every fleet leaf leads with the tenant
axis, so one ``P(axis)`` spec rule covers the tree).  Tenants never talk to
each other, so this is *pure* data parallelism: ``update``/``finalize`` run
through ``utils.compat.shard_map`` (never ``jax.shard_map`` directly — repo
rule) with the same vmapped per-tenant trace inside each shard — one
dispatch per device, zero cross-shard collectives in the compiled program
(:meth:`FleetEngine.mesh_update_hlo` exposes the HLO so tests/benchmarks can
assert that), and per-tenant results stay bitwise equal to the unsharded
stack and to isolated engines.  ``merge`` and the tenant surgery are
elementwise/row-wise, so XLA keeps them on the owning shard without an
explicit shard_map.  ``ingest`` routes interleaved requests to their owning
shard on the host and folds each shard's requests on its own device inside
one shard_map (a Pallas kernel cannot be partitioned by XLA, and no operator
row leaves its device).  Wire costs of the remaining
control-plane paths are modeled by ``core.topology.fleet_wire_cost_model``.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng_mod
from repro.core import freq_ops as fo
from repro.core import quantize as qz

__all__ = [
    "FLEET_BACKENDS",
    "FLEET_SHARDINGS",
    "FleetEngine",
    "fleet_specs",
    "fleet_quantizers",
    "stack_operators",
]

# The fleet batches per-tenant compute with vmap; the sharded backend manages
# its own mesh collective and is not a per-tenant trace to batch.
FLEET_BACKENDS = ("xla", "pallas")

# How the stacked state is placed: "none" keeps every tenant row on the
# default device; "mesh" splits the tenant axis over a device mesh (the
# per-tenant trace backend above stays orthogonal — both backends vmap
# within each shard).
FLEET_SHARDINGS = ("none", "mesh")


def fleet_specs(
    key: jax.Array,
    n_tenants: int,
    name: str,
    m: int,
    n: int,
    sigma2,
    *,
    dist: str = "adapted_radius",
    dtype=jnp.float32,
) -> list[fo.FreqOpSpec]:
    """Independent per-tenant operator specs from one parent key.

    Tenant t draws from ``fold_in(key, t)`` — the recipe list is what a
    control plane ships (~70 B/tenant) and what :class:`FleetEngine` rebuilds
    operators from.
    """
    specs = []
    for t in range(n_tenants):
        op = fo.make_operator(
            name, jax.random.fold_in(key, t), m, n, sigma2, dist=dist,
            dtype=dtype,
        )
        specs.append(op.spec())
    return specs


def fleet_quantizers(
    key: jax.Array, n_tenants: int, m: int, spec: str
) -> list[qz.SketchQuantizer] | None:
    """Per-tenant quantizers (independent dither draws) or None for float."""
    if spec == "none":
        return None
    return [
        qz.make_quantizer(jax.random.fold_in(key, t), m, spec)
        for t in range(n_tenants)
    ]


def stack_operators(ops: Sequence[fo.FrequencyOperator]):
    """Stack operator leaves along a new leading tenant axis.

    Returns ``(stacked_op, treedef)``: ``stacked_op`` is a pytree of the
    operator class whose array leaves carry the tenant axis (valid *only* as
    a vmap/gather carrier), and ``treedef`` the tenants' shared treedef,
    used to slice per-tenant operators back out.  A treedef holds only what
    shapes the traced program (the family, and ``(n, m)`` for structured
    operators), never the spec, so every tenant of a valid fleet has the
    same one.
    """
    flat = [jax.tree_util.tree_flatten(op) for op in ops]
    leaves0, treedef0 = flat[0]
    for t, (leaves, treedef) in enumerate(flat[1:], start=1):
        if treedef != treedef0 or any(
            a.shape != b.shape or a.dtype != b.dtype
            for a, b in zip(leaves, leaves0)
        ):
            raise ValueError(
                f"tenant {t} operator leaves do not match tenant 0 "
                "(all fleet tenants must share the operator family and (n, m))"
            )
    stacked = [jnp.stack(ls) for ls in zip(*(leaves for leaves, _ in flat))]
    return jax.tree_util.tree_unflatten(treedef0, stacked), treedef0


class FleetEngine:
    """T independent sketch engines as one vmapped, stacked-state engine.

    Parameters
    ----------
    operators : per-tenant frequency operators **or** their ``FreqOpSpec`` s
        (rebuilt via ``freq_ops.from_spec`` — the ~70 B recipe is the
        canonical fleet description).  All tenants must share the family and
        ``(n, m)``; keys/scales may differ freely.
    backend : ``"xla"`` or ``"pallas"`` — the per-tenant update trace that is
        vmapped (same dispatch as ``SketchEngine``'s backend matrix).
    quantizers : optional per-tenant ``SketchQuantizer`` s (one dither row
        each, shared bit width) — switches the stacked state to the int32
        :class:`~repro.core.engine.QuantizedSketchEngineState` twin.
    chunk, block_n, block_m, interpret : forwarded to the per-tenant trace.
    decay : optional per-tick exponential decay base gamma in (0, 1], shared
        by every tenant — switches the stacked state to the timestamped
        decayed twin (stamps ``(T,)``), exactly as
        ``SketchEngine(decay=...)`` does per tenant.  ``update``/``ingest``
        then accept a keyword ``t`` and :meth:`decay_to` advances the whole
        fleet's clock in one dispatch.
    sharding : ``"none"`` (default — the whole stack on one device) or
        ``"mesh"`` — split the tenant axis over a device mesh so shard s
        owns the contiguous rows ``[s·T/p, (s+1)·T/p)``.  Update/finalize
        then run the vmapped trace *within each shard* through the
        ``utils.compat.shard_map`` shim: one dispatch per device, zero
        cross-shard collectives, bitwise the unsharded rows.
    mesh : the 1-D mesh to shard over (``sharding="mesh"`` only).  Default:
        ``parallel.sharding.tenant_mesh(tenant_shards, tenant_shard_axis)``
        over the first ``tenant_shards`` local devices.
    tenant_shards : shard count p — must divide ``n_tenants`` (matches
        ``SketchJobSpec.tenant_shards`` validation).  Default: the given
        mesh's axis size, else every local device.
    tenant_shard_axis : mesh-axis name the tenant axis maps onto
        (``SketchJobSpec.tenant_shard_axis``).
    """

    def __init__(
        self,
        operators: Sequence[fo.FrequencyOperator | fo.FreqOpSpec],
        *,
        backend: str = "xla",
        quantizers: Sequence[qz.SketchQuantizer] | None = None,
        chunk: int = 8192,
        block_n: int = 1024,
        block_m: int = 512,
        interpret: bool | None = None,
        decay: float | None = None,
        sharding: str = "none",
        mesh=None,
        tenant_shards: int | None = None,
        tenant_shard_axis: str = "tenant",
    ):
        if backend not in FLEET_BACKENDS:
            raise ValueError(
                f"fleet backend must be one of {FLEET_BACKENDS}, got "
                f"{backend!r}"
            )
        if sharding not in FLEET_SHARDINGS:
            raise ValueError(
                f"fleet sharding must be one of {FLEET_SHARDINGS}, got "
                f"{sharding!r}"
            )
        if sharding == "none" and (mesh is not None or tenant_shards not in (None, 1)):
            raise ValueError(
                "mesh=/tenant_shards= require FleetEngine(sharding='mesh')"
            )
        if decay is not None and not 0.0 < float(decay) <= 1.0:
            raise ValueError(f"decay must be in (0, 1], got {decay!r}")
        if not operators:
            raise ValueError("a fleet needs at least one tenant operator")
        ops = [
            fo.from_spec(o) if isinstance(o, fo.FreqOpSpec) else o
            for o in operators
        ]
        self.n_tenants = len(ops)
        self.n, self.m = ops[0].n, ops[0].m
        self.backend = backend
        self.chunk = chunk
        self.block_n = block_n
        self.block_m = block_m
        self.interpret = interpret
        self.decay = None if decay is None else float(decay)
        self.specs: tuple[fo.FreqOpSpec | None, ...] = tuple(
            self._try_spec(op) for op in ops
        )
        self._stacked_op, self._op_treedef = stack_operators(ops)
        self._op_leaves = jax.tree_util.tree_leaves(self._stacked_op)
        self.bits: int | None = None
        self.dither: jax.Array | None = None
        if quantizers is not None:
            if len(quantizers) != self.n_tenants:
                raise ValueError(
                    f"{len(quantizers)} quantizers for {self.n_tenants} "
                    "tenants"
                )
            bits = {q.bits for q in quantizers}
            if len(bits) != 1:
                raise ValueError(
                    f"all fleet tenants must share a bit width, got {bits}"
                )
            self.bits = bits.pop()
            self.dither = jnp.stack([q.dither for q in quantizers])
            if self.dither.shape != (self.n_tenants, self.m):
                raise ValueError(
                    f"stacked dither shape {self.dither.shape} != "
                    f"{(self.n_tenants, self.m)}"
                )
        self.sharding = sharding
        self.tenant_shard_axis = str(tenant_shard_axis)
        self.mesh = None
        self.tenant_shards = 1
        self._tenant_sharding = None
        self._mesh_update_jit = None
        self._mesh_finalize_jit = None
        self._mesh_ingest_jit = {}
        if sharding == "mesh":
            from repro.parallel.sharding import axis_extent, tenant_mesh

            if mesh is None:
                mesh = tenant_mesh(
                    tenant_shards
                    if tenant_shards is not None
                    else len(jax.devices()),
                    axis=self.tenant_shard_axis,
                )
            if self.tenant_shard_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh axes {mesh.axis_names} do not include the tenant "
                    f"shard axis {self.tenant_shard_axis!r}"
                )
            p = axis_extent(mesh, (self.tenant_shard_axis,))
            if tenant_shards is not None and int(tenant_shards) != p:
                raise ValueError(
                    f"tenant_shards={tenant_shards} but the mesh's "
                    f"{self.tenant_shard_axis!r} axis has {p} devices"
                )
            if self.n_tenants % p:
                raise ValueError(
                    f"n_tenants={self.n_tenants} is not divisible by "
                    f"tenant_shards={p}; every shard must hold an equal "
                    "contiguous block of tenant rows"
                )
            self.mesh = mesh
            self.tenant_shards = p
            self._tenant_sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(self.tenant_shard_axis)
            )
            # The stacked operator leaves and dither rows live with their
            # tenants: placed once here, a shard's update never reads
            # another device's memory.
            self._stacked_op = jax.tree_util.tree_map(
                lambda l: jax.device_put(l, self._tenant_sharding),
                self._stacked_op,
            )
            self._op_leaves = jax.tree_util.tree_leaves(self._stacked_op)
            if self.dither is not None:
                self.dither = jax.device_put(
                    self.dither, self._tenant_sharding
                )

    @property
    def shard_rows(self) -> int:
        """Tenant rows per shard (= n_tenants with ``sharding="none"``)."""
        return self.n_tenants // self.tenant_shards

    def owner_shard(self, tenant: int) -> int:
        """The shard whose contiguous block holds ``tenant``'s row — what
        ``serve.fleet_service`` partitions interleaved requests by."""
        t = int(tenant)
        if not 0 <= t < self.n_tenants:
            raise ValueError(
                f"tenant {t} out of range [0, {self.n_tenants})"
            )
        return t // self.shard_rows

    def place_state(self, state):
        """Pin a stacked state's leaves onto the tenant sharding (identity
        for ``sharding="none"``).  ``init_state`` places automatically; use
        this after building a stacked state host-side (restored checkpoints,
        restacked rows) so the hot path starts on the owning devices."""
        if self._tenant_sharding is None:
            return state
        return jax.tree_util.tree_map(
            lambda l: jax.device_put(l, self._tenant_sharding), state
        )

    @staticmethod
    def _try_spec(op: fo.FrequencyOperator) -> fo.FreqOpSpec | None:
        try:
            return op.spec()
        except ValueError:
            return None

    @property
    def quantized(self) -> bool:
        return self.bits is not None

    # -- per-tenant views ---------------------------------------------------

    def operator(self, tenant: int) -> fo.FrequencyOperator:
        """Tenant ``tenant``'s own operator, sliced from the stacked leaves:
        its leaves are bitwise those of the operator it was constructed from,
        but, rebuilt from leaves, it carries no spec (``spec()`` raises);
        the tenant's recipe is ``specs[tenant]``."""
        leaves = [l[tenant] for l in self._op_leaves]
        return jax.tree_util.tree_unflatten(self._op_treedef, leaves)

    def quantizer(self, tenant: int) -> qz.SketchQuantizer | None:
        if self.bits is None:
            return None
        return qz.SketchQuantizer(bits=self.bits, dither=self.dither[tenant])

    def tenant_engine(self, tenant: int) -> eng_mod.SketchEngine:
        """A plain single-tenant ``SketchEngine`` over tenant's operator —
        the reference this fleet is bitwise-parity-tested against."""
        return eng_mod.SketchEngine(
            self.operator(tenant),
            self.backend,
            chunk=self.chunk,
            block_n=self.block_n,
            block_m=self.block_m,
            interpret=self.interpret,
            quantizer=self.quantizer(tenant),
            decay=self.decay,
        )

    # -- stacked monoid ops -------------------------------------------------

    def init_state(self):
        """Stacked monoid identity: every tenant row is ``init_state()``."""
        return self.place_state(
            eng_mod._identity(
                self.n, self.m, quantized=self.quantized, decay=self.decay,
                lead=(self.n_tenants,),
            )
        )

    def _parts(self, op, x, aux):
        """Vmapped batch partials of stacked ``(R, B, n)`` batches, one
        operator row each; ``aux`` is the rows' dither (quantized) or
        weights (float; ``None`` = unit)."""
        if self.quantized:
            dither, weights = aux, None
        else:
            dither, weights = None, eng_mod._weights(aux, x.shape[:2], False)
        partial = functools.partial(
            eng_mod._batch_partial,
            backend=self.backend,
            bits=self.bits,
            chunk=self.chunk,
            block_n=self.block_n,
            block_m=self.block_m,
            interpret=self.interpret,
        )
        return jax.vmap(partial)(op, dither, x, weights)

    def _operands(self, batches, weights, *, fill: bool = False):
        """``(x, aux)`` of a stacked ``(R, B, n)`` block: ``aux`` is the
        dither stack (quantized: no weights) or the weights — ``None`` for
        unit weights unless ``fill`` (a shard_map operand is an array)."""
        x = jnp.asarray(batches, jnp.float32)
        if x.ndim != 3 or x.shape[-1] != self.n:
            raise ValueError(
                f"batches must be (T, B, {self.n}), got {x.shape}"
            )
        if self.quantized:
            eng_mod._weights(weights, None, True)  # raises on any weights
            return x, self.dither
        if fill:
            weights = eng_mod._weights(weights, x.shape[:2], False)
        return x, weights

    def _shard_mapped(self, body, n_args: int):
        """``body`` shard_mapped over the tenant axis and jitted: each device
        runs it over its own contiguous block of rows, with no collective.
        Every operand and output leaf leads with the tenant axis, so one
        ``P(tenant_shard_axis)`` prefix covers each whole pytree."""
        from repro.utils import compat

        row = jax.sharding.PartitionSpec(self.tenant_shard_axis)
        return jax.jit(
            compat.shard_map(
                body, self.mesh, in_specs=(row,) * n_args, out_specs=row,
                check_vma=False,
            )
        )

    # -- update ---------------------------------------------------------------

    def _update_body(self, st, op, x, aux, *t):
        """The update, ``(state, op, x, aux[, t]) -> state``: run on the whole
        stack, or shard-mapped on each block (so row t is bitwise the
        unsharded row t).  ``t``: the rows' ticks; none = their own clocks."""
        parts = self._parts(op, x, aux)
        if self.decay is not None:
            parts = eng_mod._lift(
                parts, self._update_stamps(st, *t), self.decay
            )
        return eng_mod._merge_states(st, parts)

    @staticmethod
    def _update_stamps(state, t=None):
        """Per-row ticks of an update: ``t`` over the rows, or each row's own
        clock (empty rows resolve to tick 0)."""
        if t is None:
            return eng_mod._tick(state.stamp)
        return jnp.broadcast_to(jnp.asarray(t, jnp.float32), state.stamp.shape)

    def _mesh_update_fn(self, state):
        """The shard-mapped update, built once per engine (``state`` is not
        read: one tenant-axis spec covers every operand)."""
        if self._mesh_update_jit is None:
            self._mesh_update_jit = self._shard_mapped(
                self._update_body, 4 + (self.decay is not None)
            )
        return self._mesh_update_jit

    def _mesh_update_args(self, state, batches, weights, t):
        """Validated ``(jitted_fn, operands)`` of the mesh update — shared by
        :meth:`update` and :meth:`mesh_update_hlo`.  The ticks are resolved
        here: every shard_map operand is a tenant-sharded array."""
        operands = (state, self._stacked_op) + self._operands(
            batches, weights, fill=True
        )
        if self.decay is not None:
            operands += (self._update_stamps(state, t),)
        return self._mesh_update_fn(state), operands

    def mesh_update_hlo(self, state, batches, weights=None, *, t=None) -> str:
        """Compiled HLO of the shard-mapped :meth:`update` — the artifact
        tests/benchmarks grep to assert the hot path carries ZERO cross-shard
        collectives (no all-reduce/all-gather/collective-permute/all-to-all:
        tenant sharding is pure data parallelism)."""
        if self.sharding != "mesh":
            raise ValueError("mesh_update_hlo requires sharding='mesh'")
        fn, operands = self._mesh_update_args(state, batches, weights, t)
        return fn.lower(*operands).compile().as_text()

    def update(self, state, batches, weights=None, *, t=None):
        """Fold one aligned block ``batches: (T, B, n)`` — one batch per
        tenant — into the stacked state in a single vmapped dispatch.

        Row t is bitwise what ``tenant_engine(t).update`` would produce.
        Under ``decay``, ``t`` is the block's tick — a scalar (every tenant)
        or ``(T,)`` (per tenant); ``t=None`` reuses each row's current stamp
        (empty rows resolve to tick 0), matching ``SketchEngine.update``.
        With ``sharding="mesh"`` the same trace runs shard-mapped: one
        dispatch per device over its own block, nothing on the wire.
        """
        if t is not None and self.decay is None:
            raise ValueError(
                "update(t=...) requires a decay-enabled fleet "
                "(FleetEngine(..., decay=gamma))"
            )
        if self.sharding == "mesh":
            fn, operands = self._mesh_update_args(state, batches, weights, t)
            return fn(*operands)
        x, aux = self._operands(batches, weights)
        ticks = () if t is None else (t,)
        return self._update_body(state, self._stacked_op, x, aux, *ticks)

    def merge(self, a, b):
        """Stacked associative+commutative combine (elementwise, so the
        single-engine merge applies to (T, …) leaves unchanged)."""
        return eng_mod._merge_states(a, b)

    # -- finalize -------------------------------------------------------------

    def _finalize_body(self, st, dither):
        """The vmapped finalize of every row of ``st`` — the shard_map body
        reuses it verbatim, which is what keeps sharded finalize bitwise."""
        fin = functools.partial(eng_mod._finalize, bits=self.bits)
        return jax.vmap(fin)(st, dither)

    def finalize(self, state):
        """-> ``(z (T, 2m), lower (T, n), upper (T, n))``, all tenants.
        With ``sharding="mesh"`` the vmapped finalize runs within each
        shard (shard-mapped, no collectives); outputs stay tenant-sharded.
        """
        if self.quantized:
            eng_mod._check_capacity(state.count, self.bits)
        if self.sharding == "mesh":
            if self._mesh_finalize_jit is None:
                self._mesh_finalize_jit = self._shard_mapped(
                    self._finalize_body, 2
                )
            return self._mesh_finalize_jit(state, self.dither)
        return self._finalize_body(state, self.dither)

    def finalize_tenant(self, state, tenant: int):
        """Finalize ONE tenant — O(m), the decode-on-demand hot path (the
        full-fleet :meth:`finalize` is O(T·m))."""
        row = self.tenant_state(state, tenant)
        if not self.quantized:
            return eng_mod._finalize(row)
        eng_mod._check_capacity(state.count, self.bits)
        return eng_mod._finalize(row, self.dither[tenant], self.bits)

    # -- request routing: segment-scatter -----------------------------------

    def ingest(self, state, tenant_ids, batches, weights=None, *, t=None):
        """Fold interleaved requests ``(tenant_ids (R,), batches (R, B, n))``
        into the stacked state.

        Partials are computed in ONE vmapped pass over per-request operators
        gathered by tenant id.  The fold into the state is a segment-scatter:
        unique ids within a call use one scatter-add/min/max per leaf; calls
        carrying duplicate ids (several requests for one tenant in a flush)
        take an ordered ``lax.scan`` fold so the tenant's float partials
        combine in arrival order — the same association its isolated engine
        uses, preserving bitwise tenant isolation.  Ids that are not
        concrete on the host (traced) always take the scan fold.

        Under ``decay``, ``t`` is the requests' tick — a scalar or ``(R,)``
        per request — and the fold ALWAYS takes the ordered scan path: the
        decay factor each merge applies depends on the row's current stamp,
        which a scatter-add cannot express.  ``t=None`` stamps each request
        with its tenant row's current stamp (empty rows -> tick 0), resolved
        per-request inside the scan.
        """
        if t is not None and self.decay is None:
            raise ValueError(
                "ingest(t=...) requires a decay-enabled fleet "
                "(FleetEngine(..., decay=gamma))"
            )
        ids = jnp.asarray(tenant_ids, jnp.int32)
        x, aux = self._operands(
            batches, weights, fill=self.sharding == "mesh"
        )
        if ids.ndim != 1 or ids.shape[0] != x.shape[0]:
            raise ValueError(
                f"tenant_ids {ids.shape} must be (R,) matching batches "
                f"{x.shape}"
            )
        host = (
            None
            if isinstance(tenant_ids, jax.core.Tracer)
            else np.asarray(tenant_ids, np.int64)
        )
        unique = host is not None and np.unique(host).size == host.size
        if self.sharding == "mesh":
            return self._mesh_ingest(state, host, x, aux, t, unique)
        ticks = () if t is None else (t,)
        return self._ingest_body(
            state, self._stacked_op, ids, x, aux, *ticks, unique=unique
        )

    def _ingest_body(self, st, op, ids, x, aux, *t, unique, rows=None):
        """The request fold: gather each request's operator (and dither) by
        its row id, compute the partials in one vmapped pass, and fold them
        in — one scatter per leaf for unique ids, else in arrival order.
        ``rows``: the block's row count, when slots with id ``rows`` are
        padding that every fold skips (a mesh shard's requests)."""
        valid = None if rows is None else ids < rows
        safe = ids if rows is None else jnp.where(valid, ids, 0)
        gathered = jax.tree_util.tree_map(lambda l: l[safe], op)
        parts = self._parts(gathered, x, aux[safe] if self.quantized else aux)
        if self.decay is not None:
            stamps = self._request_stamps(ids.shape, *t)
            parts = eng_mod._lift(parts, stamps, self.decay)
            return self._scan_parts(st, safe, parts, valid)
        if unique:
            mode = None if rows is None else "drop"
            return self._scatter_parts(st, ids, parts, mode=mode)
        return self._scan_parts(st, safe, parts, valid)

    @staticmethod
    def _request_stamps(shape, t=None):
        """Per-request ticks: ``t`` over the requests, or ``nan`` — "stamp me
        with my row's clock", resolved per request in the scan fold.
        (``-inf`` cannot be the sentinel: a non-empty partial stamped
        ``-inf`` would decay to nothing on merge.)"""
        if t is None:
            return jnp.full(shape, jnp.nan, jnp.float32)
        return jnp.broadcast_to(jnp.asarray(t, jnp.float32), shape)

    @staticmethod
    def _scatter_parts(state, ids, parts, mode=None):
        """One scatter per leaf, combining as ``core.engine`` merges each
        field — with unique ids each row sees exactly one partial, so this
        is the per-tenant merge with no association ambiguity.  ``mode``:
        the scatters' out-of-bounds rule (``"drop"`` skips padding rows)."""
        method = {"sum": "add", "min": "min", "max": "max"}
        return type(state)(
            *(
                getattr(l.at[ids], method[eng_mod._COMBINE[f]])(p, mode=mode)
                for f, l, p in zip(state._fields, state, parts)
            )
        )

    @staticmethod
    def _scan_parts(state, ids, parts, valid=None):
        """Arrival-order fold for duplicate ids: request r merges into its
        tenant's row before request r+1 — float association matches the
        isolated engine's sequential update exactly.  Requests whose
        ``valid`` is False (padding) leave every row untouched."""
        if valid is None:
            valid = jnp.ones(jnp.shape(ids), bool)

        def fold(st, inp):
            tid, part, ok = inp
            row = jax.tree_util.tree_map(lambda l: l[tid], st)
            if isinstance(part, eng_mod.DECAYED_STATE_TYPES):
                part = part._replace(stamp=eng_mod._tick(row.stamp, part.stamp))
            merged = eng_mod._merge_states(row, part)
            st = jax.tree_util.tree_map(
                lambda l, m, r: l.at[tid].set(jnp.where(ok, m, r)),
                st, merged, row,
            )
            return st, None

        state, _ = jax.lax.scan(fold, state, (ids, parts, valid))
        return state

    def _mesh_ingest(self, state, host, x, aux, t, unique):
        """:meth:`ingest` on a mesh-sharded fleet.

        Shard s receives its own requests, in arrival order, padded to the
        busiest shard's count; padding carries the out-of-block row index
        ``shard_rows``, which every fold skips.  Each device gathers its
        requests' operators from its own block and folds them into its own
        rows: one shard_map, no collective.
        """
        if host is None:
            raise ValueError(
                "mesh-sharded ingest routes requests to shards on the host; "
                "tenant_ids must be concrete"
            )
        p, rows = self.tenant_shards, self.shard_rows
        owned = [np.flatnonzero(host // rows == s) for s in range(p)]
        width = max(len(o) for o in owned)
        pick = np.zeros((p, width), np.int64)  # request index per slot
        local = np.full((p, width), rows, np.int32)  # row in the block
        for s, o in enumerate(owned):
            pick[s, : len(o)] = o
            local[s, : len(o)] = host[o] - s * rows

        def place(a):
            return jax.device_put(a, self._tenant_sharding)

        if not self.quantized:  # the dither stack is placed already
            aux = place(aux[pick])
        operands = (state, self._stacked_op, place(local), place(x[pick]), aux)
        if self.decay is not None:
            stamps = self._request_stamps(host.shape, t)
            operands += (place(stamps[pick]),)
        return self._mesh_ingest_fn(state, unique)(*operands)

    def _mesh_ingest_fn(self, state, unique: bool):
        """The shard-mapped fold of :meth:`_mesh_ingest`, built once per
        engine and id pattern (unique ids scatter; duplicates scan)."""
        if unique in self._mesh_ingest_jit:
            return self._mesh_ingest_jit[unique]
        quantized, rows = self.quantized, self.shard_rows

        def body(st, op, local, x, aux, *stamps):
            # Each operand but the state, the operators and the dither stack
            # arrives as this shard's (1, width, ...) block.
            return self._ingest_body(
                st, op, local[0], x[0], aux if quantized else aux[0],
                *(s[0] for s in stamps), unique=unique, rows=rows,
            )

        self._mesh_ingest_jit[unique] = self._shard_mapped(
            body, 5 + (self.decay is not None)
        )
        return self._mesh_ingest_jit[unique]

    def decay_to(self, state, t):
        """Advance every tenant's clock to tick ``t`` (scalar or ``(T,)``)
        without folding data — one vmapped merge with stamped identities,
        matching ``SketchEngine.decay_to`` row for row."""
        if self.decay is None:
            raise ValueError(
                "decay_to requires a decay-enabled fleet "
                "(FleetEngine(..., decay=gamma))"
            )
        return eng_mod._decay_to(state, self.init_state(), t)

    # -- tenant state surgery (evict / restore build on these) --------------

    def tenant_state(self, state, tenant: int):
        """Tenant ``tenant``'s row as a plain single-engine state."""
        return jax.tree_util.tree_map(lambda l: l[tenant], state)

    def set_tenant(self, state, tenant: int, row):
        """Stacked state with tenant's row replaced by ``row``."""
        return jax.tree_util.tree_map(
            lambda l, r: l.at[tenant].set(jnp.asarray(r, l.dtype)), state, row
        )

    def reset_tenant(self, state, tenant: int):
        """Tenant's row back to the monoid identity (post-eviction hole)."""
        identity = eng_mod._identity(
            self.n, self.m, quantized=self.quantized, decay=self.decay
        )
        return self.set_tenant(state, tenant, identity)

    def merge_tenant(self, state, tenant: int, partial):
        """Fold an externally produced partial (edge sketcher, restored
        checkpoint) into one tenant's row: ``row <- merge(row, partial)``."""
        row = self.tenant_state(state, tenant)
        return self.set_tenant(
            state, tenant, eng_mod._merge_states(row, partial)
        )

    def state_bytes(self) -> int:
        """Resident bytes of the stacked fleet state (all T tenants)."""
        return eng_mod._state_nbytes(self.init_state())

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        q = f", bits={self.bits}" if self.quantized else ""
        s = (
            f", shards={self.tenant_shards}x{self.shard_rows}rows"
            f"(axis={self.tenant_shard_axis!r})"
            if self.sharding == "mesh"
            else ""
        )
        return (
            f"FleetEngine(T={self.n_tenants}, n={self.n}, m={self.m}, "
            f"backend={self.backend!r}{q}{s})"
        )
