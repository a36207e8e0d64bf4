"""Fleet test battery: the vmapped monoid law and tenant isolation.

Four pillars (ISSUE 7):

1. **Vmapped monoid parity** — stacked ``FleetEngine`` update/merge/finalize
   is bitwise identical to a Python loop of per-tenant ``SketchEngine`` calls,
   for float and quantized states, on the xla and pallas backends.
2. **Isolation fuzz** — hypothesis-generated random interleavings of
   update/merge/evict/restore streams across tenants leave every tenant's
   state bitwise equal to an isolated single-tenant run, and decode-LRU hits
   equal fresh decodes.
3. **Checkpoint round-trip** — evict-then-restore reproduces the exact
   accumulator state and operator spec for float/quantized states and
   dense/structured operators (plus the checkpointer meta/flavour-guard
   regressions the fleet surfaced).
4. **Launch-spec validation** — fleet configs with a tenant count not
   divisible by the shard extent are rejected.

Run alone with:  pytest -m fleet
"""

import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import fleet as fl
from repro.core.ckm import CKMConfig
from repro.core.engine import QuantizedSketchEngineState, SketchEngineState
from repro.launch.specs import SketchJobSpec
from repro.serve.fleet_service import FleetService

from tests._hypothesis_compat import given, settings, st

pytestmark = pytest.mark.fleet

T, B, N, M = 4, 12, 3, 32

BACKENDS = ["xla", "pallas"]
QUANTS = ["none", "1bit"]


def _make_engine(
    backend="xla", quant="none", n_tenants=T, name="dense", decay=None
):
    specs = fl.fleet_specs(jax.random.PRNGKey(0), n_tenants, name, M, N, 1.5)
    quants = fl.fleet_quantizers(jax.random.PRNGKey(7), n_tenants, M, quant)
    kwargs = {}
    if backend == "pallas":
        # Tiny blocks + interpret so the kernel path runs off-TPU in tests.
        kwargs = dict(block_n=32, block_m=32, interpret=True)
    return fl.FleetEngine(
        specs, backend=backend, quantizers=quants, decay=decay, **kwargs
    )


def _batches(key, rounds=1, n_tenants=T, batch=B):
    return jax.random.normal(key, (rounds, n_tenants, batch, N))


def _rows_equal(row, ref):
    return all(
        bool(jnp.array_equal(a, b))
        for a, b in zip(
            jax.tree_util.tree_leaves(row), jax.tree_util.tree_leaves(ref)
        )
    )


def _cheap_decode_cfg(**overrides):
    """A decode config that finishes in milliseconds (tests hammer decode)."""
    cfg = CKMConfig(
        k=2,
        decoder="sketch_shift",
        shift_candidates=2,
        shift_steps=3,
        shift_polish_steps=2,
        nnls_iters=4,
    )
    return dataclasses.replace(cfg, **overrides)


# -- 1. the vmapped monoid law -------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("decay", [None, 0.9])
def test_vmapped_monoid_parity(backend, quant, decay):
    """Stacked update/merge/finalize == Python loop of SketchEngine calls,
    bitwise, for every tenant — every state flavour, each round at its own
    tick under decay, so the merge decays the older round."""
    eng = _make_engine(backend, quant, decay=decay)
    xs = _batches(jax.random.PRNGKey(1), rounds=2)
    ticks = [{}, {}] if decay is None else [dict(t=1.0), dict(t=3.0)]

    # Stacked path: two update rounds into two states, then a merge.
    sa = eng.update(eng.init_state(), xs[0], **ticks[0])
    sb = eng.update(eng.init_state(), xs[1], **ticks[1])
    merged = eng.merge(sa, sb)
    z, lo, hi = eng.finalize(merged)

    for t in range(T):
        ref_eng = eng.tenant_engine(t)
        ra = ref_eng.update(ref_eng.init_state(), xs[0, t], **ticks[0])
        rb = ref_eng.update(ref_eng.init_state(), xs[1, t], **ticks[1])
        rm = ref_eng.merge(ra, rb)
        assert _rows_equal(eng.tenant_state(sa, t), ra)
        assert _rows_equal(eng.tenant_state(merged, t), rm)
        rz, rlo, rhi = ref_eng.finalize(rm)
        assert bool(jnp.array_equal(z[t], rz))
        assert bool(jnp.array_equal(lo[t], rlo))
        assert bool(jnp.array_equal(hi[t], rhi))
        # finalize_tenant is the decode hot path — same numbers, O(m).
        tz, tlo, thi = eng.finalize_tenant(merged, t)
        assert bool(jnp.array_equal(tz, rz))
        assert bool(jnp.array_equal(tlo, rlo))
        assert bool(jnp.array_equal(thi, rhi))


@pytest.mark.parametrize("quant", QUANTS)
def test_ingest_unique_ids_scatter(quant):
    """Unique tenant ids take the one-scatter-per-leaf path and still match
    the per-tenant engines bitwise."""
    eng = _make_engine("xla", quant)
    xs = _batches(jax.random.PRNGKey(2))[0]
    ids = np.array([2, 0, 3, 1])  # permuted on purpose
    state = eng.ingest(eng.init_state(), ids, xs)
    for r, t in enumerate(ids):
        ref_eng = eng.tenant_engine(int(t))
        ref = ref_eng.update(ref_eng.init_state(), xs[r])
        assert _rows_equal(eng.tenant_state(state, int(t)), ref)


@pytest.mark.parametrize("quant", QUANTS)
def test_ingest_duplicate_ids_arrival_order(quant):
    """Duplicate ids in one ingest call fold in arrival order — bitwise the
    association the tenant's isolated engine uses."""
    eng = _make_engine("xla", quant)
    xs = _batches(jax.random.PRNGKey(3), n_tenants=5)[0]
    ids = np.array([1, 0, 1, 2, 1])  # tenant 1 appears three times
    state = eng.ingest(eng.init_state(), ids, xs)
    refs = {}
    for r, t in enumerate(ids):
        t = int(t)
        ref_eng = eng.tenant_engine(t)
        refs[t] = ref_eng.update(
            refs.get(t, ref_eng.init_state()), xs[r]
        )
    for t, ref in refs.items():
        assert _rows_equal(eng.tenant_state(state, t), ref)
    # Untouched tenant stays at the monoid identity.
    assert _rows_equal(
        eng.tenant_state(state, 3),
        eng.tenant_engine(3).init_state(),
    )


def test_structured_operator_fleet():
    """The fleet is operator-family agnostic: structured fast-transform
    tenants batch and match their reference engines bitwise too."""
    eng = _make_engine("xla", "none", name="structured")
    xs = _batches(jax.random.PRNGKey(4))[0]
    state = eng.update(eng.init_state(), xs)
    for t in range(T):
        ref_eng = eng.tenant_engine(t)
        ref = ref_eng.update(ref_eng.init_state(), xs[t])
        assert _rows_equal(eng.tenant_state(state, t), ref)


def test_quantized_fleet_rejects_weights():
    eng = _make_engine("xla", "1bit")
    xs = _batches(jax.random.PRNGKey(5))[0]
    with pytest.raises(ValueError, match="unit-weight"):
        eng.update(eng.init_state(), xs, weights=jnp.ones((T, B)))


def test_stack_operators_rejects_mismatched_tenants():
    a = fl.fleet_specs(jax.random.PRNGKey(0), 1, "dense", M, N, 1.0)
    b = fl.fleet_specs(jax.random.PRNGKey(1), 1, "dense", M // 2, N, 1.0)
    with pytest.raises(ValueError, match="tenant 1"):
        fl.FleetEngine(a + b)


# -- 2. isolation fuzz ---------------------------------------------------------


def _reference_tenant(eng, ops):
    """Replay one tenant's op stream on an isolated SketchEngine."""
    ref_eng = eng.tenant_engine(ops["tenant"])
    state = ref_eng.init_state()
    for kind, payload in ops["stream"]:
        if kind == "update":
            state = ref_eng.update(state, payload)
        elif kind == "merge":
            state = ref_eng.merge(state, payload)
    return state


@settings(max_examples=5, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    quant=st.sampled_from(QUANTS),
)
def test_isolation_fuzz(seed, quant):
    """Random interleavings of update/merge/evict/restore across tenants:
    every tenant ends bitwise equal to an isolated run of its own stream,
    and cached decodes equal fresh decodes."""
    n_tenants = 3
    eng = _make_engine("xla", quant, n_tenants=n_tenants)
    rng = np.random.default_rng(seed)
    per_tenant = [
        {"tenant": t, "stream": []} for t in range(n_tenants)
    ]
    with tempfile.TemporaryDirectory() as ckpt_dir:
        svc = FleetService(
            eng,
            _cheap_decode_cfg(),
            decode_cache_entries=8,
            checkpoint_dir=ckpt_dir,
        )
        for step in range(12):
            t = int(rng.integers(n_tenants))
            action = rng.choice(["update", "update", "merge", "evict"])
            if action == "update":
                batch = jnp.asarray(
                    rng.standard_normal((int(rng.integers(2, 7)), N)),
                    jnp.float32,
                )
                svc.submit(t, batch)
                svc.flush(async_ingest=bool(rng.integers(2)))
                per_tenant[t]["stream"].append(("update", batch))
            elif action == "merge":
                ref_eng = eng.tenant_engine(t)
                batch = jnp.asarray(
                    rng.standard_normal((3, N)), jnp.float32
                )
                partial = ref_eng.update(ref_eng.init_state(), batch)
                svc.merge_partial(t, partial)
                per_tenant[t]["stream"].append(("merge", partial))
            else:
                svc.evict(t)
                if rng.integers(2):  # explicit restore half the time;
                    svc.restore(t)  # the other half auto-restores on touch
        for t in range(n_tenants):
            if t in svc.evicted:
                svc.restore(t)
            ref = _reference_tenant(eng, per_tenant[t])
            assert _rows_equal(eng.tenant_state(svc.state, t), ref), (
                f"tenant {t} diverged from its isolated engine "
                f"(seed={seed}, quant={quant})"
            )
        # Decode-LRU: a cache hit is bitwise the fresh decode.
        t = int(rng.integers(n_tenants))
        fresh = svc.decode(t, use_cache=False)
        first = svc.decode(t)
        hit = svc.decode(t)
        assert not first.cached and hit.cached
        # equal_nan: a tenant that never received data decodes to NaNs.
        assert bool(jnp.array_equal(fresh.centroids, hit.centroids, equal_nan=True))
        assert bool(jnp.array_equal(fresh.weights, hit.weights, equal_nan=True))
        assert hit.version == svc.version(t)


def test_decode_cache_invalidated_by_writes():
    """Any write to a tenant bumps its version: the next decode is a miss
    and reflects the new state; other tenants' cached decodes survive."""
    eng = _make_engine("xla", "none", n_tenants=2)
    svc = FleetService(eng, _cheap_decode_cfg(), decode_cache_entries=4)
    xs = _batches(jax.random.PRNGKey(6), n_tenants=2)[0]
    svc.ingest([0, 1], list(xs))
    d0 = svc.decode(0)
    d1 = svc.decode(1)
    svc.submit(0, xs[1])
    svc.flush()
    again0 = svc.decode(0)
    again1 = svc.decode(1)
    assert not again0.cached and again0.version == d0.version + 1
    assert again1.cached and again1.version == d1.version
    assert svc.stats.decode_hits == 1 and svc.stats.decode_misses == 3


def test_decode_lru_capacity_eviction():
    """The LRU holds at most decode_cache_entries models and evicts the
    least-recently-used key."""
    eng = _make_engine("xla", "none", n_tenants=3)
    svc = FleetService(eng, _cheap_decode_cfg(), decode_cache_entries=2)
    xs = _batches(jax.random.PRNGKey(8), n_tenants=3)[0]
    svc.ingest([0, 1, 2], list(xs))
    svc.decode(0)
    svc.decode(1)
    svc.decode(0)  # refresh 0 so tenant 1 is the LRU entry
    svc.decode(2)  # capacity 2: evicts tenant 1
    assert svc.cache_len() == 2
    assert svc.decode(0).cached
    assert svc.decode(2).cached
    assert not svc.decode(1).cached  # was evicted -> fresh decode


def test_decode_cache_disabled():
    eng = _make_engine("xla", "none", n_tenants=1)
    svc = FleetService(eng, _cheap_decode_cfg(), decode_cache_entries=0)
    xs = _batches(jax.random.PRNGKey(9), n_tenants=1)[0]
    svc.ingest([0], list(xs))
    assert not svc.decode(0).cached
    assert not svc.decode(0).cached
    assert svc.cache_len() == 0


# -- 3. checkpoint round-trip --------------------------------------------------


@pytest.mark.parametrize("quant", QUANTS)
@pytest.mark.parametrize("op_name", ["dense", "structured"])
def test_evict_restore_roundtrip(quant, op_name, tmp_path):
    """Evict-then-restore is invisible: exact state row, spec-checked
    identity, version rewound, pre-eviction cached decodes valid again."""
    eng = _make_engine("xla", quant, n_tenants=2, name=op_name)
    svc = FleetService(
        eng, _cheap_decode_cfg(), decode_cache_entries=4,
        checkpoint_dir=tmp_path,
    )
    xs = _batches(jax.random.PRNGKey(10), n_tenants=2)[0]
    svc.ingest([0, 1], list(xs))
    before = eng.tenant_state(svc.state, 0)
    version = svc.version(0)
    cached = svc.decode(0)

    svc.evict(0)
    assert 0 in svc.evicted
    assert _rows_equal(
        eng.tenant_state(svc.state, 0), eng.tenant_engine(0).init_state()
    )
    # The untouched tenant is unaffected by its neighbour's eviction.
    assert _rows_equal(
        eng.tenant_state(svc.state, 1),
        eng.tenant_engine(1).update(eng.tenant_engine(1).init_state(), xs[1]),
    )

    svc.restore(0)
    assert 0 not in svc.evicted
    assert _rows_equal(eng.tenant_state(svc.state, 0), before)
    assert svc.version(0) == version
    hit = svc.decode(0)
    assert hit.cached and hit.version == cached.version
    assert bool(jnp.array_equal(hit.centroids, cached.centroids))


def test_auto_restore_on_touch(tmp_path):
    """Submitting to or decoding an evicted tenant restores it first."""
    eng = _make_engine("xla", "none", n_tenants=2)
    svc = FleetService(
        eng, _cheap_decode_cfg(), checkpoint_dir=tmp_path,
    )
    xs = _batches(jax.random.PRNGKey(11), n_tenants=2, rounds=2)
    svc.ingest([0, 1], list(xs[0]))
    svc.evict(0)
    svc.submit(0, xs[1, 0])
    svc.flush()
    assert 0 not in svc.evicted
    ref_eng = eng.tenant_engine(0)
    ref = ref_eng.update(ref_eng.init_state(), xs[0, 0])
    ref = ref_eng.update(ref, xs[1, 0])
    assert _rows_equal(eng.tenant_state(svc.state, 0), ref)
    assert svc.stats.restores == 1


def test_restore_rejects_wrong_bits(tmp_path):
    """A checkpoint written by a float fleet cannot restore into a quantized
    fleet of the same (n, m) — the flavour guard fails loudly."""
    float_eng = _make_engine("xla", "none", n_tenants=2)
    svc = FleetService(
        float_eng, _cheap_decode_cfg(), checkpoint_dir=tmp_path,
    )
    xs = _batches(jax.random.PRNGKey(12), n_tenants=2)[0]
    svc.ingest([0, 1], list(xs))
    svc.evict(0)

    q_eng = _make_engine("xla", "1bit", n_tenants=2)
    q_svc = FleetService(
        q_eng, _cheap_decode_cfg(), checkpoint_dir=tmp_path,
    )
    q_svc._evicted.add(0)
    with pytest.raises(ValueError):
        q_svc.restore(0)


def test_checkpointer_meta_roundtrip(tmp_path):
    """Checkpointer gap fix: save(meta=...) survives the atomic write and
    read_meta returns it (latest step by default)."""
    ckpt = Checkpointer(tmp_path)
    state = {"a": jnp.arange(4.0)}
    ckpt.save(3, state, meta={"tenant": 7, "freq_op_spec": ["dense", 1]})
    ckpt.save(5, state, meta={"tenant": 7, "version": 5})
    assert ckpt.read_meta(3) == {"tenant": 7, "freq_op_spec": ["dense", 1]}
    assert ckpt.read_meta() == {"tenant": 7, "version": 5}
    ckpt.save(6, state)  # no meta -> {}
    assert ckpt.read_meta(6) == {}


def test_checkpointer_rejects_wrong_flavour(tmp_path):
    """Checkpointer gap fix: restore validates dtype (not just leaf count),
    so a float row cannot silently load into a quantized state twin."""
    ckpt = Checkpointer(tmp_path)
    fstate = SketchEngineState(
        cos_acc=jnp.zeros(M),
        sin_acc=jnp.zeros(M),
        weight_sum=jnp.zeros(()),
        lower=jnp.zeros(N),
        upper=jnp.zeros(N),
        count=jnp.zeros(()),
    )
    ckpt.save(0, fstate)
    qlike = QuantizedSketchEngineState(
        qcos_acc=jnp.zeros(M, jnp.int32),
        qsin_acc=jnp.zeros(M, jnp.int32),
        weight_sum=jnp.zeros(()),
        lower=jnp.zeros(N),
        upper=jnp.zeros(N),
        count=jnp.zeros(()),
    )
    with pytest.raises(ValueError, match="flavour"):
        ckpt.restore(qlike)
    wrong_shape = fstate._replace(cos_acc=jnp.zeros(M * 2))
    with pytest.raises(ValueError, match="shape"):
        ckpt.restore(wrong_shape)


# -- 4. launch-spec validation -------------------------------------------------


def test_jobspec_fleet_divisibility():
    """n_tenants must split evenly over the tenant shard extent."""
    good = SketchJobSpec(n_tenants=1024, tenant_shards=8)
    assert good.validate() is good
    with pytest.raises(ValueError, match="tenant shard extent"):
        SketchJobSpec(n_tenants=1000, tenant_shards=7).validate()


def test_jobspec_fleet_field_validation():
    with pytest.raises(ValueError, match="n_tenants"):
        SketchJobSpec(n_tenants=0).validate()
    with pytest.raises(ValueError, match="tenant_shards"):
        SketchJobSpec(tenant_shards=0).validate()
    with pytest.raises(ValueError, match="axis name"):
        SketchJobSpec(tenant_shard_axis="").validate()
    with pytest.raises(ValueError, match="decode_cache_entries"):
        SketchJobSpec(decode_cache_entries=-1).validate()
    with pytest.raises(ValueError, match="fleet jobs"):
        SketchJobSpec(n_tenants=4, backend="sharded").validate()
    assert "fleet=1024x8shards" in SketchJobSpec(
        n_tenants=1024, tenant_shards=8
    ).describe()
    # Single-tenant specs neither mention the fleet nor hit its validation.
    assert "fleet" not in SketchJobSpec().describe()
    SketchJobSpec(backend="sharded").validate()


# -- 5. windowed evict/restore (ISSUE 10 satellite) ----------------------------


def _windowed_service(tmp_path, buckets=3, **kw):
    eng = _make_engine(n_tenants=T)
    svc = FleetService(
        eng, _cheap_decode_cfg(), checkpoint_dir=tmp_path,
        window_buckets=buckets, **kw,
    )
    return eng, svc


def test_windowed_submit_requires_tick(tmp_path):
    _, svc = _windowed_service(tmp_path)
    with pytest.raises(ValueError, match="tick"):
        svc.submit(0, np.zeros((B, N), np.float32))


def test_windowed_evict_restore_roundtrip(tmp_path):
    """Evict checkpoints the lifetime row AND the W bucket columns; restore
    brings both back bitwise while the ring has not moved."""
    eng, svc = _windowed_service(tmp_path)
    xs = _batches(jax.random.PRNGKey(20), rounds=2)
    for r in range(2):
        for t in range(T):
            svc.submit(t, np.asarray(xs[r, t]), t=float(r))
        svc.flush()
    row = eng.tenant_state(svc.state, 1)
    column = svc.window.tenant_column(svc.window_state, 1)
    assert any(float(c.weight_sum) > 0 for c in column)

    svc.evict(1)
    for c in svc.window.tenant_column(svc.window_state, 1):
        assert float(c.weight_sum) == 0.0  # window hole, like the row
    svc.restore(1)
    assert _rows_equal(eng.tenant_state(svc.state, 1), row)
    for got, want in zip(
        svc.window.tenant_column(svc.window_state, 1), column
    ):
        assert _rows_equal(got, want)


def test_windowed_restore_skips_expired_slots(tmp_path):
    """A checkpointed bucket column only re-enters the ring while its slot
    still holds the tick it was saved under; slots reclaimed by newer ticks
    keep their fresh occupants."""
    eng, svc = _windowed_service(tmp_path, buckets=2)
    svc.submit(0, np.asarray(_batches(jax.random.PRNGKey(21))[0, 0]), t=0.0)
    svc.flush()
    svc.evict(0)  # checkpoint holds tenant 0's slot-0 column at tick 0
    # tick 2 reclaims slot 0 (2 % W == 0) for tenant 1's fresh bucket
    svc.submit(1, np.asarray(_batches(jax.random.PRNGKey(22))[0, 1]), t=2.0)
    svc.flush()
    fresh = svc.window.tenant_column(svc.window_state, 1)[0]

    svc.restore(0)
    # tenant 0's expired column stays out of the ring ...
    assert float(
        svc.window.tenant_column(svc.window_state, 0)[0].weight_sum
    ) == 0.0
    # ... tenant 1's fresh bucket is untouched, and the lifetime row is back
    assert _rows_equal(svc.window.tenant_column(svc.window_state, 1)[0], fresh)
    assert float(eng.tenant_state(svc.state, 0).weight_sum) > 0.0


def test_windowed_restore_validates_meta(tmp_path):
    """Bucket count/ticks live in the manifest meta and must match."""
    _, svc = _windowed_service(tmp_path / "a", buckets=2)
    svc.submit(0, np.asarray(_batches(jax.random.PRNGKey(23))[0, 0]), t=0.0)
    svc.flush()
    svc.evict(0)
    # same engine family, windowless service -> window/no-window mismatch
    eng2 = _make_engine(n_tenants=T)
    svc2 = FleetService(
        eng2, _cheap_decode_cfg(), checkpoint_dir=tmp_path / "a"
    )
    svc2._evicted.add(0)
    with pytest.raises(ValueError, match="window"):
        svc2.restore(0)
    # windowed service with a different bucket count
    eng3 = _make_engine(n_tenants=T)
    svc3 = FleetService(
        eng3, _cheap_decode_cfg(), checkpoint_dir=tmp_path / "a",
        window_buckets=4,
    )
    svc3._evicted.add(0)
    with pytest.raises(ValueError, match="window_buckets"):
        svc3.restore(0)
    # windowless checkpoint into a windowed service
    _, svc4 = _windowed_service(tmp_path / "b", buckets=2)
    eng5 = _make_engine(n_tenants=T)
    svc5 = FleetService(
        eng5, _cheap_decode_cfg(), checkpoint_dir=tmp_path / "b"
    )
    svc5.submit(0, np.asarray(_batches(jax.random.PRNGKey(24))[0, 0]))
    svc5.flush()
    svc5.evict(0)
    svc4._evicted.add(0)
    with pytest.raises(ValueError, match="not windowed|window"):
        svc4.restore(0)


# -- 6. per-tenant drift thresholds (ISSUE 10 satellite) -----------------------


def test_drift_threshold_array_validation():
    eng = _make_engine()
    with pytest.raises(ValueError, match="positive"):
        FleetService(eng, _cheap_decode_cfg(), drift_threshold=-1.0)
    with pytest.raises(ValueError, match=r"shape \(4,\)"):
        FleetService(
            eng, _cheap_decode_cfg(), drift_threshold=np.ones(3)
        )
    with pytest.raises(ValueError, match="positive"):
        FleetService(
            eng, _cheap_decode_cfg(),
            drift_threshold=np.array([0.1, -0.1, 0.1, 0.1]),
        )
    svc = FleetService(
        eng, _cheap_decode_cfg(), drift_threshold=np.full(T, 0.5)
    )
    assert svc.threshold(2) == 0.5
    assert FleetService(
        eng, _cheap_decode_cfg(), drift_threshold=0.25
    ).threshold(3) == 0.25
    assert FleetService(eng, _cheap_decode_cfg()).threshold(0) is None


def test_per_tenant_drift_redecode():
    """A hot tenant with a tight bound re-decodes on drifting traffic; a
    cold tenant with a loose bound keeps serving its cached model."""
    eng = _make_engine()
    thresholds = np.full(T, 1e9)
    thresholds[0] = 1e-12  # hot tenant: any movement re-decodes
    svc = FleetService(
        eng, _cheap_decode_cfg(), drift_threshold=thresholds
    )
    xs = _batches(jax.random.PRNGKey(30))[0]
    svc.ingest(range(T), list(np.asarray(xs)))
    svc.decode(0)
    svc.decode(1)

    shifted = np.asarray(xs) + 7.0
    svc.ingest([0, 1], [shifted[0], shifted[1]])  # flush auto-maintains
    assert svc.stats.drift_redecodes == 1  # tenant 0 only
    # tenant 0's fresh model is cached at the current version
    assert svc.decode(0).cached
