"""Tail-latency observability: client army, on-device sketches, SLO
detection, and the emit-time timeline sidecar.

Contracts pinned here:

* the latency tap is DERIVED state — ``latency=None`` runs are
  bit-identical to tap-on runs, across dense/scatter/compact, and the
  army's arrival schedule is a pure function of the seed (open loop);
* the per-seed log-linear sketch is EXACTLY mergeable (fleet sketch ==
  sketch of the concatenated per-op latencies) and its quantiles match
  exact numpy quantiles within one bucket of rank error;
* ``check.slo_bounded`` flags provable per-window p99 breaches only;
* the emit-time sidecar anchors Perfetto flow arrows at the true send
  time and never perturbs the certified trace refold;
* checkpoint format 9 round-trips the new columns.
"""

import dataclasses

import numpy as np
import pytest

from madsim_tpu import check, obs
from madsim_tpu.chaos import ClientArmy, FaultPlan, GrayFailure, Nemesis
from madsim_tpu.chaos.plan import stack_plan_rows
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    LatencySpec,
    lat_bucket,
    load_checkpoint,
    make_init,
    make_run,
    save_checkpoint,
    search_seeds,
    user_kind,
)
from madsim_tpu.engine.core import N_LAT_BUCKETS
from madsim_tpu.models import kvchaos as KV

N_OPS = 16
N_SEEDS = 8
MAX_STEPS = 1500

WL = KV.make_kvchaos(writes=12, n_replicas=2, chaos=False, army=True)
ARMY = KV.client_army(
    n_ops=N_OPS, t_min_ns=5_000_000, t_max_ns=280_000_000, n_replicas=2
)
PLAN = FaultPlan(
    (ARMY, GrayFailure(targets=(0, 3), n_links=1, mult_min=6, mult_max=12)),
    name="latency-test",
)
CFG = EngineConfig(pool_size=64, time_limit_ns=450_000_000)
SPEC = LatencySpec(ops=N_OPS, phases=3, phase_ns=1 << 27)

_ONES = lambda v: np.ones(np.asarray(v["halted"]).shape[0], bool)  # noqa: E731

_KW = dict(n_seeds=N_SEEDS, max_steps=MAX_STEPS, plan=PLAN,
           require_halt=False)


@pytest.fixture(scope="module")
def reports():
    """One sweep per (layout/compact, tap) combination — every test
    reads these, so the module costs a handful of compiles total."""
    r_off = search_seeds(WL, CFG, _ONES, layout="scatter", **_KW)
    r_sc = search_seeds(WL, CFG, _ONES, layout="scatter", latency=SPEC, **_KW)
    r_de = search_seeds(WL, CFG, _ONES, layout="dense", latency=SPEC, **_KW)
    r_co = search_seeds(WL, CFG, _ONES, compact=True, latency=SPEC, **_KW)
    return r_off, r_sc, r_de, r_co


@pytest.fixture(scope="module")
def lat_state():
    """The raw final state (per-op columns included) of the scatter run."""
    import jax

    from madsim_tpu.engine import make_run_while

    seeds = np.arange(N_SEEDS, dtype=np.uint64)
    init = make_init(WL, CFG, Build(plan_slots=PLAN.slots, latency=SPEC))
    run = jax.jit(make_run_while(WL, CFG, MAX_STEPS, Build(latency=SPEC)))
    return jax.block_until_ready(
        run(init(seeds, PLAN.compile_batch(seeds, wl=WL)))
    )


class TestClientArmy:
    def test_compiles_deterministically_to_client_rows(self):
        ev1 = PLAN.compile(7)
        ev2 = PLAN.compile(7)
        assert ev1 == ev2
        ops = [e for e in ev1 if e.kind == ARMY.kind]
        assert len(ops) == N_OPS
        assert all(e.node == 3 for e in ops)  # the kvchaos client node
        assert sorted(e.a0 for e in ops) == list(range(N_OPS))
        assert all(
            ARMY.t_min_ns <= e.t < ARMY.t_max_ns for e in ops
        )
        # a different seed draws different arrivals (open-loop per seed)
        assert [e.t for e in PLAN.compile(8) if e.kind == ARMY.kind] != [
            e.t for e in ops
        ]

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="user kind"):
            ClientArmy(node=0, kind=3)  # an engine kind is not a surface
        with pytest.raises(ValueError, match="n_ops"):
            ClientArmy(node=0, kind=user_kind(0), n_ops=0)
        with pytest.raises(ValueError, match="targets node 9"):
            FaultPlan((ClientArmy(node=9, kind=ARMY.kind),)).compile_batch(
                np.arange(2, dtype=np.uint64), wl=WL
            )

    def test_literalize_round_trips_node(self):
        lit = PLAN.literalize(3, wl=WL)
        assert any(e.node == 3 for e in lit.events)
        rt = type(lit).from_dict(lit.to_dict())
        assert rt.events == lit.events
        # the literal replays the FaultPlan run bit-identically,
        # including the army rows (the explore corpus-entry path);
        # layout pinned so the module's compiled-run cache entry is hit
        r_plan = search_seeds(
            WL, CFG, _ONES, seeds=np.asarray([3], np.uint64),
            max_steps=MAX_STEPS, plan=PLAN, require_halt=False,
            layout="scatter",
        )
        r_lit = search_seeds(
            WL, CFG, _ONES, seeds=np.asarray([3], np.uint64),
            max_steps=MAX_STEPS, plan_rows=stack_plan_rows([lit]),
            require_halt=False, layout="scatter",
        )
        assert r_plan.traces[0] == r_lit.traces[0]

    def test_nemesis_rejects_army_rows(self):
        ev = PLAN.compile(0)
        op = next(e for e in ev if e.kind == ARMY.kind)
        with pytest.raises(ValueError, match="client-army"):
            Nemesis(PLAN)._apply(None, op)

    def test_mis_sized_army_rejected_at_sweep_entry(self):
        """An army whose op ids exceed LatencySpec.ops is a build
        error: every out-of-range marker would silently drop (lat_drop
        counts it, but the sweep refuses the whole mis-sizing)."""
        with pytest.raises(ValueError, match="exceed LatencySpec.ops"):
            search_seeds(
                WL, CFG, _ONES, plan=PLAN, n_seeds=2, max_steps=10,
                require_halt=False, latency=LatencySpec(ops=N_OPS - 1),
            )

    def test_army_requires_the_client_surface(self):
        """An army composed with a workload built WITHOUT the client
        surface must error at compile, not silently dispatch the
        clamped last handler with army args."""
        no_army = KV.make_kvchaos(writes=4, n_replicas=2, chaos=False)
        with pytest.raises(ValueError, match="client surface"):
            PLAN.compile_batch(np.arange(2, dtype=np.uint64), wl=no_army)
        lit = PLAN.literalize(0, wl=WL)
        with pytest.raises(ValueError, match="client surface"):
            lit.compile_batch(np.arange(2, dtype=np.uint64), wl=no_army)

    def test_ops_resume_after_client_restart(self):
        """Army rows ride the any-epoch sentinel: a kill+restart of the
        client drops only the ops arriving while it is DOWN — load
        resumes on the new incarnation instead of silently zeroing for
        the rest of the run (which would make crash-the-client
        schedules read as vacuously SLO-clean)."""
        from madsim_tpu.chaos import FaultEvent, LiteralPlan
        from madsim_tpu.engine import KIND_KILL, KIND_RESTART

        lit = LiteralPlan(events=(
            FaultEvent(t=50_000_000, kind=ARMY.kind, a0=0, node=3),
            FaultEvent(t=150_000_000, kind=ARMY.kind, a0=1, node=3),
            FaultEvent(t=300_000_000, kind=ARMY.kind, a0=2, node=3),
            FaultEvent(t=100_000_000, kind=KIND_KILL, a0=3),
            FaultEvent(t=200_000_000, kind=KIND_RESTART, a0=3),
        ), name="client-crash")
        r = search_seeds(
            WL, CFG, _ONES, plan=lit, n_seeds=4, max_steps=MAX_STEPS,
            require_halt=False, latency=LatencySpec(ops=3),
        )
        # decode per seed: op 0 (before the kill) and op 2 (after the
        # restart) complete; op 1 (client down) is dropped at dispatch
        import jax

        from madsim_tpu.engine import make_init, make_run_while

        spec3 = LatencySpec(ops=3)
        seeds = np.arange(4, dtype=np.uint64)
        init = make_init(WL, CFG, Build(plan_slots=lit.slots, latency=spec3))
        run = jax.jit(make_run_while(WL, CFG, MAX_STEPS, Build(latency=spec3)))
        out = jax.block_until_ready(
            run(init(seeds, lit.compile_batch(seeds, wl=WL)))
        )
        inv = np.asarray(out.lat_inv)
        resp = np.asarray(out.lat_resp)
        assert (inv[:, 0] >= 0).all() and (resp[:, 0] >= 0).all()
        assert (inv[:, 1] < 0).all()  # arrived at a dead client
        assert (inv[:, 2] >= 0).all() and (resp[:, 2] >= 0).all()
        assert (r.lat_count == 2).all()


class TestLatencyIdentity:
    def test_tap_off_vs_on_identical(self, reports):
        r_off, r_sc, _r_de, _r_co = reports
        assert np.array_equal(r_off.traces, r_sc.traces)
        assert r_off.lat_hist is None and r_off.lat_count is None
        assert r_sc.lat_hist.shape == (N_SEEDS, SPEC.phases, N_LAT_BUCKETS)

    def test_identical_across_layouts_and_compact(self, reports):
        _r_off, r_sc, r_de, r_co = reports
        for other in (r_de, r_co):
            assert np.array_equal(r_sc.traces, other.traces)
            assert np.array_equal(r_sc.lat_hist, other.lat_hist)
            assert np.array_equal(r_sc.lat_count, other.lat_count)

    def test_checkpoint_roundtrip_format9(self, tmp_path):
        import jax

        seeds = np.arange(4, dtype=np.uint64)
        init = make_init(WL, CFG, Build(plan_slots=PLAN.slots, latency=SPEC))
        run = jax.jit(make_run(WL, CFG, 250, Build(latency=SPEC)))
        mid = run(init(seeds, PLAN.compile_batch(seeds, wl=WL)))
        path = str(tmp_path / "lat.ckpt")
        save_checkpoint(path, mid, CFG)
        resumed = run(load_checkpoint(path, WL, CFG))
        straight = run(mid)
        assert np.array_equal(
            np.asarray(resumed.trace), np.asarray(straight.trace)
        )
        for f in ("lat_inv", "lat_resp", "lat_hist", "lat_count"):
            assert np.array_equal(
                np.asarray(getattr(resumed, f)),
                np.asarray(getattr(straight, f)),
            ), f


class TestSketch:
    def _exact(self, lat_state):
        inv = np.asarray(lat_state.lat_inv)
        resp = np.asarray(lat_state.lat_resp)
        done = (inv >= 0) & (resp >= 0)
        return (resp - inv)[done]

    def test_sketch_equals_exact_bucketing(self, reports, lat_state):
        """The merged fleet sketch IS the histogram of the concatenated
        per-op latencies — exact mergeability, the t-digest property
        the fixed ladder buys outright."""
        _r_off, r_sc, _r_de, _r_co = reports
        lats = self._exact(lat_state)
        assert lats.size > 30, "army produced too few completed ops"
        assert lats.min() > 0
        merged = r_sc.lat_hist.sum(axis=(0, 1))
        exact = np.bincount(lat_bucket(lats), minlength=N_LAT_BUCKETS)
        assert np.array_equal(merged, exact)
        assert merged.sum() == int(r_sc.lat_count.sum())

    def test_merge_matches_concatenation(self, reports):
        from madsim_tpu.parallel import merge_latency

        _r_off, r_sc, _r_de, _r_co = reports
        h = r_sc.lat_hist
        whole = merge_latency(h)
        halves = merge_latency(h[: N_SEEDS // 2]) + merge_latency(
            h[N_SEEDS // 2:]
        )
        assert np.array_equal(whole, halves)
        fl = obs.latency_reduce(h, r_sc.lat_count, phase_ns=SPEC.phase_ns)
        assert np.array_equal(fl.hist, whole)
        assert fl.completed == int(r_sc.lat_count.sum())
        assert "p99" in fl.format()

    def test_quantiles_within_one_bucket_of_exact(self, reports, lat_state):
        _r_off, r_sc, _r_de, _r_co = reports
        lats = self._exact(lat_state)
        merged = r_sc.lat_hist.sum(axis=(0, 1))
        for q in (0.5, 0.9, 0.99):
            sk = int(obs.hist_quantile_bucket(merged, q))
            exact_q = float(np.quantile(lats, q))
            assert abs(sk - int(lat_bucket(exact_q))) <= 1, (q, sk, exact_q)

    def test_fleet_latency_device_resident(self, reports):
        """The tail-only sweep returns the same totals as reducing the
        search report's columns — without a SearchReport in between."""
        _r_off, r_sc, _r_de, _r_co = reports
        fl = obs.fleet_latency(
            WL, CFG, SPEC, n_seeds=N_SEEDS, max_steps=MAX_STEPS, plan=PLAN,
        )
        ref = obs.latency_reduce(
            r_sc.lat_hist, r_sc.lat_count, phase_ns=SPEC.phase_ns
        )
        assert np.array_equal(fl.hist, ref.hist)
        assert fl.quantile(0.99) >= fl.quantile(0.5) > 0


class TestSlo:
    def test_clean_run_passes_generous_bound(self, reports):
        _r_off, r_sc, _r_de, _r_co = reports
        inv = check.slo_bounded(10_000_000_000, min_ops=1)
        ok = inv({"lat_hist": r_sc.lat_hist})
        assert ok.all()

    def test_provable_breach_flags_at_bucket_resolution(self):
        from madsim_tpu.engine import lat_bucket_hi, lat_bucket_lo

        h = np.zeros((2, 1, N_LAT_BUCKETS), np.int64)
        h[0, 0, 40] = 100  # every op lands in bucket 40
        h[1, 0, 10] = 100
        lo = int(lat_bucket_lo(40))
        # bound below the bucket: provably breached -> flagged
        assert np.array_equal(
            check.slo_breaches(h, lo - 1, min_ops=10), [True, False]
        )
        # bound AT the bucket's lower edge: not provable -> clean
        # (under-flag, never false-flag)
        assert not check.slo_breaches(h, lo, min_ops=10).any()
        # bound above: clean
        assert not check.slo_breaches(
            h, int(lat_bucket_hi(40)), min_ops=10
        ).any()
        # the min_ops floor keeps thin windows unjudged
        assert not check.slo_breaches(h, lo - 1, min_ops=101).any()

    def test_requires_latency_columns(self):
        with pytest.raises(ValueError, match="LatencySpec"):
            check.slo_bounded(1)( {"lat_hist": np.zeros((2, 0, 0))} )


class TestEmitTime:
    @pytest.fixture(scope="class")
    def ring_report(self):
        return search_seeds(
            WL, CFG, _ONES, layout="scatter", latency=SPEC,
            timeline_cap=2048, **_KW,
        )

    def test_emit_at_or_before_dispatch_and_refold_exact(self, ring_report):
        r = ring_report
        assert not r.tl_dropped.any()
        events = obs.decode_timeline(r.timeline, WL, 0)
        assert events, "empty timeline"
        assert all(e.emit_ns >= 0 for e in events)
        assert all(e.emit_ns <= e.time_ns for e in events)
        # a delivered message's emit time is some earlier dispatch of
        # the SENDER — the true send instant
        msgs = [e for e in events if e.src >= 0]
        assert msgs, "no messages captured"
        times_at = {}
        for e in events:
            times_at.setdefault(e.node, set()).add(e.time_ns)
        anchored = sum(
            1 for m in msgs if m.emit_ns in times_at.get(m.src, ())
        )
        assert anchored == len(msgs)
        # the sidecar never touches the certified trace
        assert obs.refold_timeline(events, WL) == int(r.traces[0])

    def test_perfetto_anchors_flows_at_emit(self, ring_report):
        events = obs.decode_timeline(ring_report.timeline, WL, 0)
        doc = obs.to_perfetto(events, WL, seed=0)
        rows = doc["traceEvents"]
        dispatch = [e for e in rows if e.get("cat") == "dispatch"]
        assert len(dispatch) == len(events)
        starts = [e for e in rows if e["ph"] == "s"]
        assert starts, "no flow arrows"
        emit_us = {}
        for e in events:
            if e.src >= 0:
                emit_us.setdefault(e.src, set()).add(e.emit_ns / 1e3)
        for s in starts:
            assert s["ts"] in emit_us[s["pid"]]


class TestExplain:
    def test_explain_narrates_tail_percentiles(self):
        text = obs.explain(
            WL, CFG, seed=1, plan=PLAN, max_steps=MAX_STEPS,
            timeline_cap=2048, latency=SPEC,
            invariant=check.slo_bounded(10_000_000_000, min_ops=1),
        )
        assert "--- latency:" in text
        assert "p99<=" in text
        assert "slowest completed:" in text
        assert "invariant HOLDS" in text


@pytest.mark.slow
class TestSloHunt:
    def test_guided_hunt_finds_shrinks_and_replays_breach(self):
        """The acceptance loop at test scale: a gray-failure space over
        the army, an SLO invariant, the guided campaign finds a breach,
        ddmin shrinks it, the shrunk literal replays to the identical
        violation + trace (the soak runs this at 2k-seed scale with a
        uniform-baseline comparison)."""
        from madsim_tpu import explore
        from madsim_tpu.chaos import shrink_plan

        wl = KV.make_kvchaos(writes=12, n_replicas=2, chaos=False, army=True)
        army = KV.client_army(
            n_ops=N_OPS, t_min_ns=5_000_000, t_max_ns=280_000_000,
            n_replicas=2,
        )
        space = FaultPlan(
            (army, GrayFailure(
                targets=(0, 3), n_links=2, mult_min=2, mult_max=64,
                dur_min_ns=150_000_000, dur_max_ns=400_000_000,
            )),
            name="slo-hunt",
        )
        slo = check.slo_bounded(60_000_000, q=0.99, min_ops=8)
        rep = explore.run(
            wl, CFG, space, invariant=slo, generations=4, batch=48,
            root_seed=11, max_steps=MAX_STEPS, cov_words=32,
            latency=SPEC,
        )
        assert rep.violations, "guided hunt found no SLO breach"
        entry = rep.violations[0]
        res = shrink_plan(
            wl, CFG, entry.seed, entry.plan, invariant=slo,
            max_steps=MAX_STEPS, latency=SPEC,
        )
        assert len(res.events) <= entry.plan.slots
        replay = explore.replay_entry(
            wl, CFG, dataclasses.replace(entry, plan=res.plan),
            invariant=slo, max_steps=MAX_STEPS, latency=SPEC,
        )
        assert int(replay.traces[0]) == res.trace
        assert not replay.ok[0], "shrunk plan no longer breaches"
