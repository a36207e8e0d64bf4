"""Temporal-sketching battery: decay algebra, windows, drift re-decode.

Four pillars (ISSUE 9):

1. **Decay algebra** — the timestamped decayed state is still a commutative
   monoid: identity and commutativity bitwise, same-stamp merges bitwise
   equal to the undecayed merge (associating bitwise on quantized integer
   segments), cross-stamp associativity to
   float tolerance, and a closed-form check that any interleaving of
   update/decay_to/merge equals direct ``gamma**dt`` reweighting of the
   per-batch contributions.  Per backend (xla | pallas | sharded), decay at
   a constant tick is bitwise-transparent over the lifetime engine, and the
   quantized side-channel agrees with the float decay path.
2. **Ring-of-sketches window** — merge-on-read returns exactly the last W
   buckets, slot reuse never leaks an expired bucket into a read, and
   too-late arrivals are dropped rather than corrupting a reclaimed slot.
3. **Fleet-window isolation fuzz** — random timestamped schedules of
   aligned updates / routed ingests / tenant column evict-restore on a
   ``FleetEngine`` window stay bitwise equal to isolated per-tenant
   ``SketchEngine`` windows.
4. **Drift-triggered re-decode acceptance** — on a seeded drifting blobs
   stream, a decayed fleet with ``drift_threshold`` re-decodes itself back
   to within 5% of a fresh fit's SSE while the lifetime sketch degrades.

Run alone with:  pytest -m window
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ckm as ckm_mod
from repro.core import engine as eng_mod
from repro.core import fleet as fl
from repro.core import frequencies as fq
from repro.core import quantize as qz
from repro.core.ckm import CKMConfig
from repro.core.window import SketchWindow, WindowState
from repro.launch.specs import SketchJobSpec
from repro.serve.fleet_service import FleetService

from tests._hypothesis_compat import given, settings, st

pytestmark = pytest.mark.window

GAMMA = 0.5


def _data(seed, npts=200, n=4, m=24):
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (npts, n)) * 2.0
    w = fq.draw_frequencies(kw, m, n, 1.0)
    return x, w


def _states_equal(a, b):
    return all(
        bool(jnp.array_equal(x, y))
        for x, y in zip(
            jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        )
    )


def _engines(quant="none", decay=GAMMA, m=24):
    """One decay-enabled engine per backend (pallas interpreted off-TPU)."""
    _, w = _data(5, npts=8, m=m)
    q = (
        qz.make_quantizer(jax.random.PRNGKey(3), m, quant)
        if quant != "none"
        else None
    )
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    return {
        "xla": eng_mod.SketchEngine(w, "xla", quantizer=q, decay=decay),
        "pallas": eng_mod.SketchEngine(
            w, "pallas", block_n=128, block_m=128, quantizer=q, decay=decay
        ),
        "sharded": eng_mod.SketchEngine(
            w, "sharded", mesh=mesh, quantizer=q, decay=decay
        ),
    }


# -- 1. the decay algebra ------------------------------------------------------


class TestDecayMonoidLaws:
    @pytest.mark.parametrize("quant", ["none", "1bit", "8bit"])
    def test_identity_bitwise(self, quant):
        """merge(identity, s) == s == merge(s, identity), every leaf bitwise,
        for any stamp — the stamp=-inf identity decays to nothing."""
        x, w = _data(0)
        q = (
            qz.make_quantizer(jax.random.PRNGKey(3), 24, quant)
            if quant != "none"
            else None
        )
        e = eng_mod.SketchEngine(w, quantizer=q, decay=GAMMA)
        s = e.update(e.init_state(), x[:120], t=3.0)
        s = e.update(s, x[120:], t=7.0)
        assert _states_equal(e.merge(e.init_state(), s), s)
        assert _states_equal(e.merge(s, e.init_state()), s)
        # identity + identity stays the identity (the (-inf)-(-inf) edge)
        both = e.merge(e.init_state(), e.init_state())
        assert _states_equal(both, e.init_state())

    @pytest.mark.parametrize("quant", ["none", "8bit"])
    def test_commutativity_bitwise(self, quant):
        """merge(a, b) == merge(b, a) bitwise even across different stamps —
        both factor pairs and the symmetric adds are order-free."""
        x, w = _data(1)
        q = (
            qz.make_quantizer(jax.random.PRNGKey(3), 24, quant)
            if quant != "none"
            else None
        )
        e = eng_mod.SketchEngine(w, quantizer=q, decay=GAMMA)
        a = e.update(e.init_state(), x[:80], t=0.0)
        b = e.update(e.init_state(), x[80:], t=5.0)
        assert _states_equal(e.merge(a, b), e.merge(b, a))

    def test_same_stamp_merge_equals_undecayed_bitwise(self):
        """With equal stamps every decay factor is exactly 1.0 and the
        decayed merge reduces to the undecayed merge, bitwise — the decay
        layer perturbs nothing until time actually advances."""
        x, w = _data(2)
        e = eng_mod.SketchEngine(w, decay=GAMMA)
        base = eng_mod.SketchEngine(w)
        a = e.update(e.init_state(), x[:60], t=4.0)
        b = e.update(e.init_state(), x[60:], t=4.0)
        ab = e.merge(a, b)
        ref = base.merge(
            base.update(base.init_state(), x[:60]),
            base.update(base.init_state(), x[60:]),
        )
        for field in ("cos_acc", "sin_acc", "weight_sum", "lower", "upper",
                      "count"):
            assert bool(
                jnp.array_equal(getattr(ab, field), getattr(ref, field))
            ), field

    def test_same_stamp_associativity(self):
        """Same-stamp associativity: bitwise on the quantized int segments
        (integer adds associate exactly); float accumulators associate to
        the same tolerance the undecayed monoid tests pin (float + is not
        associative, decayed or not)."""
        x, w = _data(2)
        q = qz.make_quantizer(jax.random.PRNGKey(3), 24, "1bit")
        eq = eng_mod.SketchEngine(w, quantizer=q, decay=GAMMA)
        a, b, c = (
            eq.update(eq.init_state(), p, t=4.0)
            for p in (x[:60], x[60:130], x[130:])
        )
        left = eq.merge(eq.merge(a, b), c)
        right = eq.merge(a, eq.merge(b, c))
        assert _states_equal(left, right)  # int segments: fully bitwise

        ef = eng_mod.SketchEngine(w, decay=GAMMA)
        a, b, c = (
            ef.update(ef.init_state(), p, t=4.0)
            for p in (x[:60], x[60:130], x[130:])
        )
        left = ef.merge(ef.merge(a, b), c)
        right = ef.merge(a, ef.merge(b, c))
        for zl, zr in zip(ef.finalize(left), ef.finalize(right)):
            np.testing.assert_allclose(
                np.asarray(zl), np.asarray(zr), atol=1e-5
            )

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        ta=st.integers(0, 6),
        tb=st.integers(0, 6),
        tc=st.integers(0, 6),
    )
    def test_cross_stamp_associativity(self, seed, ta, tb, tc):
        """Across stamps the merge is associative to float tolerance (the
        factors distribute mathematically; float * is not associative)."""
        x, w = _data(seed)
        e = eng_mod.SketchEngine(w, decay=GAMMA)
        a = e.update(e.init_state(), x[:60], t=float(ta))
        b = e.update(e.init_state(), x[60:130], t=float(tb))
        c = e.update(e.init_state(), x[130:], t=float(tc))
        left = e.merge(e.merge(a, b), c)
        right = e.merge(a, e.merge(b, c))
        for zl, zr in zip(e.finalize(left), e.finalize(right)):
            np.testing.assert_allclose(
                np.asarray(zl), np.asarray(zr), atol=1e-5
            )
        assert float(left.stamp) == float(right.stamp) == max(ta, tb, tc)

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        ticks=st.lists(
            st.integers(0, 8), min_size=2, max_size=5, unique=True
        ),
    )
    def test_closed_form_exponential_reweighting(self, seed, ticks):
        """Interleaved update/decay_to/merge == direct gamma**dt reweighting
        of the per-batch contributions — the semantic anchor of the whole
        transform."""
        ticks = sorted(ticks)
        x, w = _data(seed, npts=60 * len(ticks))
        e = eng_mod.SketchEngine(w, decay=GAMMA)
        base = eng_mod.SketchEngine(w)  # undecayed partials for the oracle
        batches = [x[i * 60 : (i + 1) * 60] for i in range(len(ticks))]

        s = e.init_state()
        for tk, b in zip(ticks, batches):
            # a gratuitous clock advance between folds must change nothing
            s = e.decay_to(s, float(tk))
            s = e.update(s, b, t=float(tk))
        t_end = float(ticks[-1]) + 2.0
        s = e.decay_to(s, t_end)
        z, lo, hi = e.finalize(s)

        cos = jnp.zeros((24,))
        sin = jnp.zeros((24,))
        wsum = jnp.zeros(())
        for tk, b in zip(ticks, batches):
            p = base._partial_state(b, None)
            f = GAMMA ** (t_end - tk)
            cos = cos + f * p.cos_acc
            sin = sin + f * p.sin_acc
            wsum = wsum + f * p.weight_sum
        z_ref = jnp.concatenate([cos, -sin]) / wsum
        np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(lo), np.asarray(x.min(0)))
        np.testing.assert_allclose(np.asarray(hi), np.asarray(x.max(0)))
        assert float(s.count) == x.shape[0]  # counts never decay

    def test_full_decay_finalizes_to_zero_sketch(self):
        """weight_sum -> 0 under long decay hits the zero-weight finalize
        guard, not accumulator/denom garbage."""
        x, w = _data(4)
        e = eng_mod.SketchEngine(w, decay=GAMMA)
        s = e.update(e.init_state(), x, t=0.0)
        s = e.decay_to(s, 1e4)
        z, _, _ = e.finalize(s)
        assert bool(jnp.all(z == 0.0))

    def test_merge_rejects_mismatched_flavours(self):
        x, w = _data(6)
        e = eng_mod.SketchEngine(w, decay=GAMMA)
        base = eng_mod.SketchEngine(w)
        with pytest.raises(TypeError, match="mismatched state flavours"):
            eng_mod._merge_states(
                e.update(e.init_state(), x, t=0.0),
                base.update(base.init_state(), x),
            )

    def test_t_requires_decay(self):
        x, w = _data(6)
        e = eng_mod.SketchEngine(w)
        with pytest.raises(ValueError, match="decay-enabled"):
            e.update(e.init_state(), x, t=1.0)
        with pytest.raises(ValueError, match="decay-enabled"):
            e.decay_to(e.init_state(), 1.0)
        with pytest.raises(ValueError, match="decay must be in"):
            eng_mod.SketchEngine(w, decay=1.5)


class TestDecayBackendParity:
    @pytest.mark.parametrize("quant", ["none", "1bit"])
    def test_constant_tick_bitwise_transparent(self, quant):
        """Per backend: folding everything at one tick through the decayed
        transform finalizes bitwise equal to the same backend's lifetime
        engine — the decay layer adds no numeric perturbation of its own."""
        x, _ = _data(8)
        for name, e in _engines(quant).items():
            life = eng_mod.SketchEngine(
                e.freq_op,
                e.backend,
                block_n=e.block_n,
                block_m=e.block_m,
                mesh=e.mesh,
                quantizer=e.quantizer,
            )
            sd = e.update(e.init_state(), x[:100], t=2.0)
            sd = e.update(sd, x[100:], t=2.0)
            sl = life.update(life.init_state(), x[:100])
            sl = life.update(sl, x[100:])
            for zd, zl in zip(e.finalize(sd), life.finalize(sl)):
                assert bool(jnp.array_equal(zd, zl)), name

    def test_quantized_decay_bitwise_across_backends(self):
        """Quantized decayed states are bitwise identical across the three
        backends: int codes are bitwise (the existing engine contract) and
        the decay factors are the same scalar float ops everywhere."""
        x, _ = _data(9)
        states, finals = {}, {}
        for name, e in _engines("1bit").items():
            s = e.update(e.init_state(), x[:100], t=0.0)
            s = e.update(s, x[100:], t=3.0)
            states[name], finals[name] = s, e.finalize(s)
        ref = states["xla"]
        for name in ("pallas", "sharded"):
            assert _states_equal(states[name], ref), name
            for za, zb in zip(finals[name], finals["xla"]):
                assert bool(jnp.array_equal(za, zb)), name

    def test_float_decay_parity_across_backends(self):
        """Float decayed sketches agree across backends to the same 1e-4 the
        undecayed parity tests pin."""
        x, _ = _data(10)
        finals = {}
        for name, e in _engines("none").items():
            s = e.update(e.init_state(), x[:100], t=0.0)
            s = e.update(s, x[100:], t=3.0)
            finals[name] = e.finalize(s)
        for name in ("pallas", "sharded"):
            for za, zb in zip(finals[name], finals["xla"]):
                np.testing.assert_allclose(
                    np.asarray(za), np.asarray(zb), atol=1e-4
                )

    def test_quantized_agrees_with_float_decay(self):
        """The int-segment + float-side-channel construction tracks the pure
        float decay path: 8-bit codes keep the decayed sketch within a few
        1e-3, same ballpark as undecayed quantization error."""
        x, w = _data(11, npts=400)
        q = qz.make_quantizer(jax.random.PRNGKey(3), 24, "8bit")
        ef = eng_mod.SketchEngine(w, decay=GAMMA)
        eq = eng_mod.SketchEngine(w, quantizer=q, decay=GAMMA)
        sf, sq = ef.init_state(), eq.init_state()
        for i, tk in enumerate([0.0, 1.0, 4.0]):
            b = x[i * 130 : (i + 1) * 130]
            sf = ef.update(sf, b, t=tk)
            sq = eq.update(sq, b, t=tk)
        zf, _, _ = ef.finalize(sf)
        zq, _, _ = eq.finalize(sq)
        np.testing.assert_allclose(np.asarray(zq), np.asarray(zf), atol=5e-3)

    def test_quantized_same_tick_split_invariance_bitwise(self):
        """Same-tick folds keep the int32 segment exact: any batch split at
        one tick gives bitwise identical decayed quantized states."""
        x, w = _data(12)
        q = qz.make_quantizer(jax.random.PRNGKey(3), 24, "1bit")
        e = eng_mod.SketchEngine(w, quantizer=q, decay=GAMMA)
        one = e.update(e.init_state(), x, t=5.0)
        two = e.update(e.init_state(), x[:77], t=5.0)
        two = e.update(two, x[77:], t=5.0)
        assert _states_equal(one, two)

    def test_ckm_config_threads_decay(self):
        """CKMConfig.decay reaches the engine; the streaming fit runs on the
        decayed transform end to end."""
        _, w = _data(13)
        cfg = CKMConfig(k=2, decay=GAMMA)
        e = ckm_mod.make_engine(w, cfg)
        assert e.decay == GAMMA
        assert isinstance(e.init_state(), eng_mod.DecayedSketchEngineState)
        assert ckm_mod.make_engine(w, CKMConfig(k=2)).decay is None


# -- 2. the ring-of-sketches window --------------------------------------------


class TestSketchWindow:
    def _setup(self, decay=None, buckets=3):
        x, w = _data(20, npts=600)
        e = eng_mod.SketchEngine(w, decay=decay)
        return x, e, SketchWindow(e, buckets)

    def test_merge_on_read_is_exactly_last_w_buckets(self):
        x, e, sw = self._setup()
        ws = sw.init_state()
        chunks = {t: x[t * 100 : (t + 1) * 100] for t in range(6)}
        for t, b in chunks.items():
            ws = sw.update(ws, b, t=float(t))
        # read at t=5 with W=3 -> ticks {3, 4, 5}
        ref = e.init_state()
        for t in (3, 4, 5):
            ref = e.update(ref, chunks[t])
        assert _states_equal(sw.read(ws, 5.0), ref)
        # t=None reads at the newest claimed tick
        assert _states_equal(sw.read(ws), ref)
        for za, zb in zip(sw.finalize(ws), e.finalize(ref)):
            assert bool(jnp.array_equal(za, zb))

    def test_slot_reuse_never_leaks_expired_bucket(self):
        """Tick 0 and tick 3 share slot 0 (W=3): once tick 3 claims it, no
        read at any time can see tick 0's data again."""
        x, e, sw = self._setup()
        ws = sw.init_state()
        poison = x[:100] + 100.0  # unmistakable if it leaks
        ws = sw.update(ws, poison, t=0.0)
        for t in (1, 2, 3):
            ws = sw.update(ws, x[t * 100 : (t + 1) * 100], t=float(t))
        assert int(ws.slot_tick[0]) == 3  # slot 0 recycled
        for read_t in (3.0, 4.0, 5.0, 100.0):
            st_read = sw.read(ws, read_t)
            if float(st_read.count) > 0:
                assert float(st_read.upper.max()) < 50.0
        # a mid-ring read older than head excludes the newer buckets too:
        # at t=2 only ticks {1, 2} are visible (tick 3 is in the future)
        ref = e.init_state()
        for t in (1, 2):
            ref = e.update(ref, x[t * 100 : (t + 1) * 100])
        assert _states_equal(sw.read(ws, 2.0), ref)

    def test_late_arrival_is_dropped_not_folded(self):
        """An update older than the whole ring must not corrupt the slot its
        tick hashes to."""
        x, e, sw = self._setup()
        ws = sw.init_state()
        for t in (1, 2, 3, 4):
            ws = sw.update(ws, x[t * 100 : (t + 1) * 100], t=float(t))
        before = sw.read(ws, 4.0)
        ws2 = sw.update(ws, x[:100] + 999.0, t=0.0)  # tick 0 <= head-W
        assert _states_equal(sw.read(ws2, 4.0), before)

    def test_window_with_decay_reads_at_query_time(self):
        """decay inside the window + hard cutoff at its edge: a read at t
        equals the closed-form reweighting of the surviving buckets."""
        x, e, sw = self._setup(decay=GAMMA)
        base = eng_mod.SketchEngine(e.freq_op)
        ws = sw.init_state()
        chunks = {t: x[t * 100 : (t + 1) * 100] for t in (0, 1, 2, 4)}
        for t, b in chunks.items():
            ws = sw.update(ws, b, t=float(t))
        t_q = 5.0
        got = sw.read(ws, t_q)
        z, _, _ = e.finalize(got)
        cos = jnp.zeros((24,))
        sin = jnp.zeros((24,))
        wsum = jnp.zeros(())
        for t in (4,):  # W=3 at tick 5 -> ticks {3,4,5}; only 4 has data
            p = base._partial_state(chunks[t], None)
            f = GAMMA ** (t_q - t)
            cos, sin = cos + f * p.cos_acc, sin + f * p.sin_acc
            wsum = wsum + f * p.weight_sum
        z_ref = jnp.concatenate([cos, -sin]) / wsum
        np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref), atol=1e-5)
        assert float(got.stamp) == t_q

    def test_bucket_ticks_scaling(self):
        """bucket_ticks groups a tick range into one bucket."""
        x, e, _ = self._setup()
        sw = SketchWindow(e, 2, bucket_ticks=10.0)
        ws = sw.init_state()
        ws = sw.update(ws, x[:100], t=3.0)  # tick 0
        ws = sw.update(ws, x[100:200], t=9.9)  # tick 0 (same bucket)
        ws = sw.update(ws, x[200:300], t=10.0)  # tick 1
        ref = e.update(e.init_state(), x[:100])
        ref = e.update(ref, x[100:200])
        ref = e.update(ref, x[200:300])
        assert _states_equal(sw.read(ws, 15.0), ref)
        # tick 2 expires bucket 0
        ws = sw.update(ws, x[300:400], t=25.0)
        ref2 = e.update(e.init_state(), x[200:300])
        ref2 = e.update(ref2, x[300:400])
        assert _states_equal(sw.read(ws, 25.0), ref2)

    def test_constructor_validation(self):
        _, e, _ = self._setup()
        with pytest.raises(ValueError, match="buckets"):
            SketchWindow(e, 0)
        with pytest.raises(ValueError, match="bucket_ticks"):
            SketchWindow(e, 3, bucket_ticks=0.0)

    def test_memory_is_o_w_m(self):
        _, e, _ = self._setup()
        w2, w8 = SketchWindow(e, 2), SketchWindow(e, 8)
        b2 = w2.state_bytes(w2.init_state())
        b8 = w8.state_bytes(w8.init_state())
        assert b8 == 4 * b2


# -- 3. fleet-window isolation fuzz --------------------------------------------


T_FLEET, B_FLEET, N_FLEET, M_FLEET = 3, 8, 3, 32


def _fleet_window(quant="none", decay=GAMMA, buckets=3):
    specs = fl.fleet_specs(
        jax.random.PRNGKey(0), T_FLEET, "dense", M_FLEET, N_FLEET, 1.5
    )
    quants = fl.fleet_quantizers(
        jax.random.PRNGKey(7), T_FLEET, M_FLEET, quant
    )
    fe = fl.FleetEngine(specs, quantizers=quants, decay=decay)
    return fe, SketchWindow(fe, buckets)


class TestFleetWindowIsolation:
    @settings(max_examples=5, deadline=None)
    @given(
        seed=st.integers(0, 2**31 - 1),
        quant=st.sampled_from(["none", "1bit"]),
    )
    def test_fuzz_bitwise_vs_isolated_tenant_windows(self, seed, quant):
        """Random timestamped schedules of aligned update / routed ingest /
        tenant column reset-restore on a FleetEngine window == isolated
        per-tenant SketchEngine windows, bitwise, read at the same global t.
        """
        rng = np.random.default_rng(seed)
        fe, fw = _fleet_window(quant)
        refs = [fe.tenant_engine(t) for t in range(T_FLEET)]
        rws = [SketchWindow(e, fw.buckets) for e in refs]

        ws = fw.init_state()
        rstates = [w.init_state() for w in rws]
        clock = 0.0
        for _ in range(rng.integers(4, 9)):
            clock += float(rng.integers(0, 3))
            action = rng.choice(["update", "ingest", "evict_restore"])
            if action == "update":
                blk = jnp.asarray(
                    rng.normal(size=(T_FLEET, B_FLEET, N_FLEET)), jnp.float32
                )
                ws = fw.update(ws, blk, t=clock)
                for t in range(T_FLEET):
                    rstates[t] = rws[t].update(rstates[t], blk[t], t=clock)
            elif action == "ingest":
                r = int(rng.integers(1, 5))
                ids = rng.integers(0, T_FLEET, r)  # duplicates welcome
                bt = jnp.asarray(
                    rng.normal(size=(r, B_FLEET, N_FLEET)), jnp.float32
                )
                ws = fw.ingest(ws, ids, bt, t=clock)
                for j, tid in enumerate(ids):
                    rstates[tid] = rws[tid].update(
                        rstates[tid], bt[j], t=clock
                    )
            else:  # evict + immediate restore must be invisible
                tid = int(rng.integers(0, T_FLEET))
                col = fw.tenant_column(ws, tid)
                ws = fw.reset_tenant(ws, tid)
                ws = fw.set_tenant_column(ws, tid, col)

        # Both sides read at the same explicit global time — per-tenant slot
        # bookkeeping may lag the fleet's (a tenant can skip ticks), but the
        # read filter sees the identical tick range either way.
        merged = fw.read(ws, clock)
        for t in range(T_FLEET):
            row = fe.tenant_state(merged, t)
            ref = rws[t].read(rstates[t], clock)
            assert _states_equal(row, ref), f"tenant {t} diverged"
            zf, zl, zh = fe.finalize_tenant(merged, t)
            rf, rl, rh = refs[t].finalize(ref)
            assert bool(jnp.array_equal(zf, rf))

    def test_ring_rotation_no_stale_bucket_fleet(self):
        """Fleet flavour of the leak test: wrap the ring, assert the expired
        block's unmistakable data is gone from merge-on-read."""
        fe, fw = _fleet_window("none", decay=None)
        rng = np.random.default_rng(0)
        ws = fw.init_state()
        poison = jnp.full((T_FLEET, B_FLEET, N_FLEET), 100.0, jnp.float32)
        ws = fw.update(ws, poison, t=0.0)
        for t in (1, 2, 3):
            blk = jnp.asarray(
                rng.normal(size=(T_FLEET, B_FLEET, N_FLEET)), jnp.float32
            )
            ws = fw.update(ws, blk, t=float(t))
        merged = fw.read(ws, 3.0)
        assert float(merged.upper.max()) < 50.0


# -- 4. drift-triggered re-decode acceptance -----------------------------------


def _decode_cfg(**overrides):
    cfg = CKMConfig(
        k=2,
        decoder="sketch_shift",
        shift_candidates=4,
        shift_steps=40,
        shift_polish_steps=10,
        nnls_iters=10,
        replicates=3,  # single-replicate sketch_shift can land on a bad basin
    )
    return dataclasses.replace(cfg, **overrides)


def _blobs(rng, centers, n=160, scale=0.25):
    centers = np.asarray(centers, np.float32)
    lab = rng.integers(0, centers.shape[0], n)
    return (centers[lab] + rng.normal(0, scale, (n, 2))).astype(np.float32)


def _sse(x, centroids):
    x = np.asarray(x)
    c = np.asarray(centroids)
    d = ((x[:, None] - c[None]) ** 2).sum(-1)
    return float(d.min(1).sum())


class TestDriftTriggeredRedecode:
    def test_redecode_recovers_sse_lifetime_degrades(self):
        """Acceptance (ISSUE 9): on a seeded drifting blobs stream the
        decay + drift_threshold fleet's *served model* re-decodes to within
        5% of a fresh same-operator fit's SSE on the live distribution,
        while the lifetime fleet — whose drift gauge can see the shift but
        which has nothing acting on it — keeps serving the stale phase-A
        decode and degrades by orders of magnitude."""
        rng = np.random.default_rng(42)
        m = 64
        old_c = [[-3.0, -3.0], [3.0, 3.0]]
        new_c = [[9.0, 9.0], [15.0, 3.0]]
        specs = fl.fleet_specs(jax.random.PRNGKey(2), 1, "dense", m, 2, 4.0)

        decayed = FleetService(
            fl.FleetEngine(specs, decay=0.5),
            _decode_cfg(),
            drift_threshold=0.15,
        )
        lifetime = FleetService(fl.FleetEngine(specs), _decode_cfg())

        phase_a = [_blobs(rng, old_c) for _ in range(4)]
        phase_b = [_blobs(rng, new_c) for _ in range(10)]
        tick = 0.0
        for batch in phase_a:
            decayed.submit(0, batch, t=tick)
            decayed.flush()
            lifetime.submit(0, batch)
            lifetime.flush()
            tick += 1.0
        decayed.decode(0)  # the served model maintenance will refresh
        lifetime.decode(0)  # the served model nothing will ever refresh
        assert decayed.stats.drift_redecodes == 0
        for batch in phase_b:
            decayed.submit(0, batch, t=tick)
            decayed.flush()  # auto-maintains: scores drift, re-decodes
            lifetime.submit(0, batch)
            lifetime.flush()
            tick += 1.0

        assert decayed.stats.drift_redecodes >= 1
        eval_pts = _blobs(rng, new_c, n=600)

        # Recovery target: a fresh decode of the live distribution through
        # the SAME operator the fleet uses (apples-to-apples — a separately
        # drawn operator with data-adapted sigma^2 would measure operator
        # quality, not staleness), keyed the way FleetService keys tenant 0.
        op = decayed.engine.operator(0)
        z, lo, hi = eng_mod.SketchEngine(op).sketch(
            jnp.asarray(np.concatenate(phase_b))
        )
        fresh_c, _, _ = ckm_mod.decode_sketch(
            jax.random.fold_in(jax.random.PRNGKey(0), 0),
            z,
            op,
            lo,
            hi,
            _decode_cfg(),
        )
        sse_fresh = _sse(eval_pts, fresh_c)
        sse_decayed = _sse(eval_pts, decayed.served_model(0).centroids)
        sse_lifetime = _sse(eval_pts, lifetime.served_model(0).centroids)

        assert sse_decayed <= 1.05 * sse_fresh, (
            f"drift-maintained served SSE {sse_decayed:.1f} not within 5% "
            f"of fresh-fit SSE {sse_fresh:.1f}"
        )
        assert sse_lifetime > 2.0 * sse_fresh, (
            f"stale lifetime served model unexpectedly kept up: "
            f"{sse_lifetime:.1f} vs fresh {sse_fresh:.1f}"
        )

    def test_fresh_tenant_drift_is_defined(self):
        """Regression (ISSUE 9): drift on an all-zero sketch — fresh tenant
        or fully decayed — is 0.0, not NaN, and never decodes."""
        specs = fl.fleet_specs(jax.random.PRNGKey(0), 2, "dense", 32, 2, 1.0)
        svc = FleetService(fl.FleetEngine(specs, decay=0.5), _decode_cfg())
        score = svc.drift(0)
        assert score == 0.0 and not np.isnan(score)
        assert svc.stats.decodes == 0  # the guard short-circuits the decode

        # fully decayed: fold data, then let the mass decay to ~0 exactly
        rng = np.random.default_rng(1)
        svc.submit(1, _blobs(rng, [[0.0, 0.0]]), t=0.0)
        svc.flush()
        svc.state = svc.engine.decay_to(svc.state, 1e4)
        svc._touch([1])
        assert svc.drift(1) == 0.0

    def test_zero_live_sketch_drift_score(self):
        """obs.diagnose.sketch_drift itself defines the 0/0 case as 0.0."""
        from repro.obs.diagnose import sketch_drift

        _, w = _data(30, m=24)
        z0 = jnp.zeros((48,))
        cents = jnp.asarray([[1.0, 0.0, 0.0, 0.0], [0.0, 1.0, 0.0, 0.0]])
        wts = jnp.asarray([0.5, 0.5])
        s = sketch_drift(z0, cents, wts, w)
        assert s == 0.0 and not np.isnan(s)

    def test_submit_t_requires_decay(self):
        specs = fl.fleet_specs(jax.random.PRNGKey(0), 1, "dense", 32, 2, 1.0)
        svc = FleetService(fl.FleetEngine(specs), _decode_cfg())
        with pytest.raises(ValueError, match="decay-enabled"):
            svc.submit(0, np.zeros((4, 2), np.float32), t=1.0)
        with pytest.raises(ValueError, match="drift_threshold"):
            FleetService(
                fl.FleetEngine(specs), _decode_cfg(), drift_threshold=0.0
            )


# -- launch-spec plumbing ------------------------------------------------------


class TestTemporalJobSpec:
    def test_spec_accepts_and_describes_temporal_fields(self):
        spec = SketchJobSpec(
            decay=0.9,
            window_buckets=8,
            window_bucket_ticks=60.0,
            drift_threshold=0.4,
        ).validate()
        assert spec.ckm_overrides()["decay"] == 0.9
        d = spec.describe()
        assert "decay=0.9" in d and "window=8x60.0" in d
        assert "drift_threshold=0.4" in d

    def test_spec_rejects_bad_temporal_fields(self):
        with pytest.raises(ValueError, match="decay"):
            SketchJobSpec(decay=0.0).validate()
        with pytest.raises(ValueError, match="window_buckets"):
            SketchJobSpec(window_buckets=-1).validate()
        with pytest.raises(ValueError, match="window_bucket_ticks"):
            SketchJobSpec(window_buckets=4, window_bucket_ticks=0.0).validate()
        with pytest.raises(ValueError, match="drift_threshold"):
            SketchJobSpec(drift_threshold=-0.1).validate()
