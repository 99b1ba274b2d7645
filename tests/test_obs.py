"""madsim_tpu.obs — fleet metrics, timeline capture, Perfetto export,
hit-count coverage, campaign persistence, and the obs-off identity.

The subsystem's contract is the coverage-tap discipline generalized:
every observability column is DERIVED state — obs-off runs are
bit-identical to pre-obs traces across layouts and the compacted
runner, and obs-on runs change no trace, verdict, or RNG draw. The
timeline's strongest self-check is the refold: the captured stream
re-hashes to the certified trace.
"""

import io
import json

import numpy as np
import pytest

from madsim_tpu import explore, obs
from madsim_tpu.chaos import CrashStorm, FaultPlan, Partition
from madsim_tpu.check import election_safety
from madsim_tpu.engine import (
    Build,
    HALT_DONE,
    HALT_TIME_LIMIT,
    MET_HALT_CODE,
    METRIC_NAMES,
    EngineConfig,
    make_init,
    search_seeds,
)
from madsim_tpu.engine.core import (
    MET_CRASH,
    MET_DELIVERED,
    MET_PAUSE,
    MET_RESTART,
    MET_SENT,
)
from madsim_tpu.models import make_pingpong, make_raft
from madsim_tpu.models.raft import OP_ELECT

RAFT_CFG = EngineConfig(pool_size=64, loss_p=0.02)
RAFT_PLAN = FaultPlan((
    CrashStorm(targets=(1, 2, 3), n=1),
    Partition(targets=(0, 1, 2, 3, 4)),
), name="obs-test")

_ONES = lambda v: np.ones(np.asarray(v["halted"]).shape[0], bool)  # noqa: E731


def _elect_inv(h):
    return election_safety(h, elect_op=OP_ELECT)


class TestObsIdentity:
    def test_obs_off_and_on_identical_traces(self):
        """Metrics, timeline and hit-count taps are derived state:
        enabling all three changes no trace and no verdict."""
        wl = make_raft(record=True)
        kw = dict(n_seeds=16, max_steps=600, plan=RAFT_PLAN,
                  history_invariant=_elect_inv)
        r0 = search_seeds(wl, RAFT_CFG, None, **kw)
        r1 = search_seeds(
            wl, RAFT_CFG, None, metrics=True, timeline_cap=256,
            cov_words=16, cov_hitcount=True, **kw,
        )
        assert np.array_equal(r0.traces, r1.traces)
        assert np.array_equal(r0.ok, r1.ok)
        assert r0.met is None and r0.timeline is None
        assert r1.met.shape == (16, len(METRIC_NAMES))
        assert r1.timeline.tl_t.shape[0] == 16

    def test_obs_identical_across_layouts_and_compact(self):
        wl = make_raft(record=True)
        kw = dict(n_seeds=16, max_steps=600, plan=RAFT_PLAN,
                  history_invariant=_elect_inv, metrics=True,
                  timeline_cap=256, cov_words=16, cov_hitcount=True)
        base = search_seeds(wl, RAFT_CFG, None, layout="scatter", **kw)
        dense = search_seeds(wl, RAFT_CFG, None, layout="dense", **kw)
        comp = search_seeds(wl, RAFT_CFG, None, compact=True, **kw)
        for other in (dense, comp):
            assert np.array_equal(base.traces, other.traces)
            assert np.array_equal(base.met, other.met)
            assert np.array_equal(base.cov, other.cov)
            for f in ("tl_count", "tl_drop", "tl_t", "tl_meta", "tl_args"):
                assert np.array_equal(
                    getattr(base.timeline, f), getattr(other.timeline, f)
                ), f

    def test_build_validation(self):
        wl = make_pingpong(rounds=2)
        with pytest.raises(ValueError, match="cov_hitcount"):
            make_init(wl, EngineConfig(), Build(cov_hitcount=True))
        with pytest.raises(ValueError, match="timeline_cap"):
            make_init(wl, EngineConfig(), Build(timeline_cap=-1))


class TestFleetMetrics:
    def test_counters_match_known_workload(self):
        """Pingpong's message economy is exact: every dispatch of a
        message is a delivery, and the per-seed sent count equals the
        engine's own msg_count stat."""
        wl = make_pingpong(rounds=3)
        cfg = EngineConfig()
        r = search_seeds(wl, cfg, _ONES, n_seeds=8, max_steps=200,
                         metrics=True)
        # sent == the engine's msg_count (the same fold condition)
        rr = search_seeds(wl, cfg, _ONES, n_seeds=8, max_steps=200)
        assert rr.ok.all()
        assert (r.met[:, MET_SENT] > 0).all()
        assert (r.met[:, MET_HALT_CODE] == HALT_DONE).all()
        # lossless config, no chaos: every sent message is delivered
        assert np.array_equal(r.met[:, MET_SENT], r.met[:, MET_DELIVERED])

    def test_chaos_counters(self):
        """A one-crash storm plan shows up as exactly one crash per
        seed (the window is drawn before any raft election can halt
        the scenario)."""
        wl = make_raft()
        plan = FaultPlan((
            CrashStorm(targets=(1, 2, 3), n=1, t_min_ns=1_000_000,
                       t_max_ns=50_000_000, down_min_ns=10_000_000,
                       down_max_ns=50_000_000),
        ), name="c1")
        r = search_seeds(
            wl, EngineConfig(pool_size=96), _ONES, n_seeds=16,
            max_steps=800, plan=plan, metrics=True,
        )
        assert (r.met[:, MET_CRASH] == 1).all()
        # the restart fires unless the seed halted before its time
        assert (r.met[:, MET_RESTART] <= 1).all()
        assert r.met[:, MET_RESTART].sum() > 0
        assert (r.met[:, MET_PAUSE] == 0).all()

    def test_halt_code_time_limit(self):
        wl = make_pingpong(rounds=50)
        cfg = EngineConfig(time_limit_ns=20_000_000)
        r = search_seeds(wl, cfg, _ONES, n_seeds=4, max_steps=2000,
                         metrics=True, require_halt=False)
        assert (r.met[:, MET_HALT_CODE] == HALT_TIME_LIMIT).all()
        assert "time-limit" in r.banner()

    def test_fleet_reduce_matches_host_math(self):
        wl = make_raft()
        r = search_seeds(wl, RAFT_CFG, _ONES, n_seeds=32, max_steps=600,
                         metrics=True)
        fm = obs.fleet_reduce(r.met)
        assert fm.n_seeds == 32
        assert np.array_equal(fm.totals, r.met.astype(np.int64).sum(axis=0))
        assert np.array_equal(fm.mins, r.met.min(axis=0))
        assert np.array_equal(fm.maxs, r.met.max(axis=0))
        # histogram rows partition the seeds
        assert (fm.hist.sum(axis=1) == 32).all()
        assert fm.halt_codes.sum() == 32
        assert "fleet metrics over 32 seeds" in fm.format(histograms=True)

    def test_fleet_metrics_device_only_path(self):
        """The metrics-only sweep reduces on device: it returns only
        (M,)-shaped results and matches the search_seeds-reduced
        values for the same seeds."""
        wl = make_raft()
        fm = obs.fleet_metrics(wl, RAFT_CFG, n_seeds=16, max_steps=600)
        r = search_seeds(wl, RAFT_CFG, _ONES, n_seeds=16, max_steps=600,
                         metrics=True)
        ref = obs.fleet_reduce(r.met)
        assert np.array_equal(fm.totals, ref.totals)
        assert np.array_equal(fm.hist, ref.hist)
        assert np.array_equal(fm.halt_codes, ref.halt_codes)

    def test_merge_metrics_sharded_equals_host(self):
        from madsim_tpu.parallel import make_mesh, merge_metrics

        rng = np.random.default_rng(1)
        met = rng.integers(0, 1000, size=(64, 13), dtype=np.int32)
        host = met.astype(np.int64).sum(axis=0)
        assert np.array_equal(merge_metrics(met), host)
        assert np.array_equal(merge_metrics(met, make_mesh()), host)


class TestTimeline:
    def test_refold_recovers_certified_trace(self):
        """The captured stream IS the folded stream: re-hashing the
        decoded timeline reproduces each seed's trace hash — including
        under an injected chaos plan."""
        wl = make_raft(record=True)
        r = search_seeds(
            wl, RAFT_CFG, None, n_seeds=8, max_steps=600,
            plan=RAFT_PLAN, history_invariant=_elect_inv,
            metrics=True, timeline_cap=512,
        )
        assert not r.tl_dropped.any()
        for s in range(8):
            events = obs.decode_timeline(r.timeline, wl, s)
            assert len(events) > 0
            assert obs.refold_timeline(events, wl) == int(r.traces[s])

    def test_overflow_is_loud_not_quarantining(self):
        wl = make_raft()
        r = search_seeds(wl, RAFT_CFG, _ONES, n_seeds=4, max_steps=600,
                         timeline_cap=4)
        assert r.tl_dropped.all()
        assert (r.timeline.tl_count == 4).all()
        assert "timeline ring" in r.banner()
        # forensics never voids evidence: verdicts are unaffected
        assert not r.overflowed.any()

    def test_refold_covers_payload_workloads(self):
        """The ring captures payload words, so the refold certificate
        holds for kvchaos-class models too."""
        from madsim_tpu.models import make_kvchaos

        wl = make_kvchaos(writes=3, record=True, chaos=True, payload=True)
        assert wl.payload_words > 0
        cfg = EngineConfig(pool_size=192)
        r = search_seeds(wl, cfg, _ONES, n_seeds=2, max_steps=4000,
                         timeline_cap=2048, require_halt=False)
        assert not r.tl_dropped.any()
        for s in range(2):
            events = obs.decode_timeline(r.timeline, wl, s)
            assert any(any(w != 0 for w in e.pay) for e in events)
            assert obs.refold_timeline(events, wl) == int(r.traces[s])


class TestPerfetto:
    def _events(self):
        wl = make_raft()
        # kill early so the fault lands before the election halts
        plan = FaultPlan((
            CrashStorm(targets=(1, 2), n=1, t_min_ns=1_000_000,
                       t_max_ns=50_000_000, down_min_ns=10_000_000,
                       down_max_ns=50_000_000),
        ), name="p")
        r = search_seeds(
            wl, EngineConfig(pool_size=96), _ONES, n_seeds=2,
            max_steps=800, plan=plan, timeline_cap=512,
        )
        return wl, obs.decode_timeline(r.timeline, wl, 0)

    def test_valid_trace_event_json_with_matching_count(self):
        wl, events = self._events()
        doc = obs.to_perfetto(events, wl, seed=0)
        # valid JSON end to end
        rt = json.loads(json.dumps(doc))
        assert rt["otherData"]["events"] == len(events)
        disp = [e for e in rt["traceEvents"] if e.get("cat") == "dispatch"]
        assert len(disp) == len(events)
        # every event names required trace-event fields
        for e in rt["traceEvents"]:
            assert "ph" in e and "pid" in e
            if e["ph"] in ("X", "i", "s", "f"):
                assert "ts" in e

    def test_node_tracks_and_chaos_spans(self):
        wl, events = self._events()
        doc = obs.to_perfetto(events, wl)
        names = [
            e["args"]["name"]
            for e in doc["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"
        ]
        assert any(n.startswith("node 0") for n in names)
        assert "chaos" in names
        spans = [
            e for e in doc["traceEvents"]
            if e.get("cat") == "chaos" and e["ph"] == "X"
        ]
        assert any(s["name"].startswith("killed") for s in spans)

    def test_flow_arrows_pair_up(self):
        wl, events = self._events()
        doc = obs.to_perfetto(events, wl)
        starts = [e for e in doc["traceEvents"]
                  if e.get("cat") == "flow" and e["ph"] == "s"]
        ends = [e for e in doc["traceEvents"]
                if e.get("cat") == "flow" and e["ph"] == "f"]
        assert len(starts) == len(ends) > 0
        assert {e["id"] for e in starts} == {e["id"] for e in ends}

    def test_write_perfetto(self, tmp_path):
        wl, events = self._events()
        p = tmp_path / "trace.json"
        doc = obs.write_perfetto(str(p), events, wl)
        assert json.loads(p.read_text()) == json.loads(json.dumps(doc))


class TestHitcountCoverage:
    def test_traces_unchanged_and_bitmaps_bucketed(self):
        """Hit-counting changes which bits exist, never the run."""
        wl = make_raft(record=True)
        kw = dict(n_seeds=16, max_steps=600, cov_words=16,
                  history_invariant=_elect_inv)
        r0 = search_seeds(wl, RAFT_CFG, None, **kw)
        r1 = search_seeds(wl, RAFT_CFG, None, cov_hitcount=True, **kw)
        assert np.array_equal(r0.traces, r1.traces)
        assert r1.cov.any()
        # bucketed and set-only bitmaps are different coordinate systems
        assert not np.array_equal(r0.cov, r1.cov)

    def test_hitcount_deterministic_across_layouts(self):
        wl = make_raft(record=True)
        kw = dict(n_seeds=8, max_steps=600, cov_words=16,
                  cov_hitcount=True, history_invariant=_elect_inv)
        a = search_seeds(wl, RAFT_CFG, None, layout="dense", **kw)
        b = search_seeds(wl, RAFT_CFG, None, layout="scatter", **kw)
        c = search_seeds(wl, RAFT_CFG, None, compact=True, **kw)
        assert np.array_equal(a.cov, b.cov)
        assert np.array_equal(a.cov, c.cov)

    # tier-1 budget: the bucketing QUALITY claim (recurrence grows
    # coverage) is OBS_r09 cert 4's re-measurement; tier-1 keeps the
    # hit-count identity/determinism rows in this class.
    @pytest.mark.slow
    def test_recurrence_becomes_coverage(self):
        """More rounds of the same behavior grow bucketed coverage
        faster than set-only coverage (which only gains time-phase
        bits) — the AFL refinement's whole point."""
        cfg = EngineConfig()
        cov_n = lambda rounds, hc: explore.popcount(  # noqa: E731
            search_seeds(
                make_pingpong(rounds=rounds), cfg, _ONES, n_seeds=1,
                max_steps=400, cov_words=16, cov_hitcount=hc,
            ).cov
        )
        d_set = cov_n(16, False) - cov_n(4, False)
        d_hc = cov_n(16, True) - cov_n(4, True)
        assert d_hc > d_set
        assert cov_n(16, True) > cov_n(16, False)


class TestCampaignPersistence:
    KW = dict(batch=24, root_seed=11, max_steps=600, cov_words=16)

    def _space(self):
        return FaultPlan((CrashStorm(targets=(1, 2, 3), n=1),), name="t")

    def test_resume_equals_uninterrupted(self, tmp_path):
        wl = make_raft(record=True)
        path = str(tmp_path / "camp.json")
        full = explore.run(wl, RAFT_CFG, self._space(), generations=4,
                           history_invariant=_elect_inv, **self.KW)
        explore.run(wl, RAFT_CFG, self._space(), generations=2,
                    history_invariant=_elect_inv, checkpoint_path=path,
                    **self.KW)
        res = explore.run(wl, RAFT_CFG, self._space(), generations=2,
                          history_invariant=_elect_inv, resume=path,
                          **self.KW)
        fp = lambda r: (  # noqa: E731
            [(e.id, e.seed, e.plan.hash(), e.trace) for e in r.corpus],
            r.cov_map.tolist(),
            [(e.seed, e.trace) for e in r.violations],
            r.curve, r.next_id, r.generations, r.sims,
        )
        assert fp(full) == fp(res)

    def test_resume_validates_campaign_identity(self, tmp_path):
        wl = make_raft(record=True)
        path = str(tmp_path / "camp.json")
        rep = explore.run(wl, RAFT_CFG, self._space(), generations=1,
                          history_invariant=_elect_inv, **self.KW)
        explore.save_campaign(path, rep)
        with pytest.raises(ValueError, match="root seed"):
            explore.run(wl, RAFT_CFG, self._space(), generations=1,
                        history_invariant=_elect_inv, resume=path,
                        **{**self.KW, "root_seed": 12})
        other = FaultPlan((CrashStorm(targets=(1, 2), n=1),), name="x")
        with pytest.raises(ValueError, match="plan-space"):
            explore.run(wl, RAFT_CFG, other, generations=1,
                        history_invariant=_elect_inv, resume=path,
                        **self.KW)

    def test_state_roundtrip_exact(self, tmp_path):
        wl = make_raft(record=True)
        path = str(tmp_path / "camp.json")
        rep = explore.run(wl, RAFT_CFG, self._space(), generations=2,
                          history_invariant=_elect_inv, **self.KW)
        st = explore.save_campaign(path, rep)
        back = explore.load_campaign(path)
        assert np.array_equal(back.cov_map, st.cov_map)
        assert [e.id for e in back.corpus] == [e.id for e in st.corpus]
        for a, b in zip(back.corpus, st.corpus):
            assert (a.seed, a.trace, a.plan.hash()) == (
                b.seed, b.trace, b.plan.hash()
            )
            assert np.array_equal(a.cov, b.cov)


class TestCampaignTelemetry:
    def test_jsonl_records(self):
        wl = make_raft(record=True)
        buf = io.StringIO()
        explore.run(
            wl, RAFT_CFG,
            FaultPlan((CrashStorm(targets=(1, 2, 3), n=1),), name="t"),
            generations=2, batch=16, root_seed=3, max_steps=600,
            cov_words=16, history_invariant=_elect_inv,
            telemetry=obs.JsonlSink(buf),
        )
        recs = [json.loads(ln) for ln in buf.getvalue().splitlines()]
        assert recs[0]["event"] == "campaign_start"
        assert recs[-1]["event"] == "campaign_end"
        gens = [r for r in recs if r["event"] == "generation"]
        assert len(gens) == 2
        for g in gens:
            for key in ("cov_bits", "corpus_size", "violations",
                        "dispatch_wall_s", "sims"):
                assert key in g


class TestExplain:
    def test_narrative_contains_the_story(self):
        wl = make_raft(record=True)
        plan = FaultPlan((CrashStorm(targets=(1, 2, 3), n=1),), name="t")
        text = obs.explain(
            wl, EngineConfig(pool_size=96), seed=5, plan=plan,
            history_invariant=_elect_inv, max_steps=600,
        )
        assert "injected fault plan" in text
        assert "kill" in text
        assert "history:" in text  # the recorded election event
        assert "verdict: history invariant HOLDS" in text
        assert "repro: seed=5" in text

    def test_narrative_flags_violation(self):
        """The kvchaos lost-write mutant's explain says VIOLATED."""
        from madsim_tpu.check import stale_reads
        from madsim_tpu.models import make_kvchaos

        wl = make_kvchaos(writes=6, record=True, bug=True, chaos=True)
        cfg = EngineConfig(pool_size=192)
        box = {}

        def inv(h):
            box["ok"] = stale_reads(h)
            return box["ok"]

        r = search_seeds(wl, cfg, None, n_seeds=64, max_steps=4000,
                         history_invariant=inv)
        bad = r.failing_seeds
        if not len(bad):
            pytest.skip("mutant not caught in this tiny sweep")
        text = obs.explain(
            wl, cfg, seed=int(bad[0]), history_invariant=inv,
            max_steps=4000,
        )
        assert "history invariant VIOLATED" in text


class TestExplainDiff:
    """obs.explain_diff: localize the first divergent timeline row
    between a clean and a violating sibling."""

    # shared across the class so the telemetry capture cache reuses the
    # compiled run (id(wl) keys the cache)
    WL = make_raft(record=True)
    CFG = EngineConfig(pool_size=96)

    def test_localizes_first_divergence(self):
        wl, cfg = self.WL, self.CFG
        # an early kill perturbs the election prefix, so the plan-driven
        # sibling departs from the bare seeded run mid-stream
        plan = FaultPlan(
            (CrashStorm(
                targets=(0, 1, 2, 3, 4), n=2, t_min_ns=5_000_000,
                t_max_ns=60_000_000, down_min_ns=200_000_000,
                down_max_ns=400_000_000,
            ),),
            name="early",
        )
        text = obs.explain_diff(
            wl, cfg, (5, None), (5, plan),
            history_invariant=_elect_inv, max_steps=600,
            timeline_cap=1024,
        )
        assert "first divergent timeline row" in text
        assert "clean continues:" in text
        assert "violating continues:" in text
        assert "violating plan" in text
        assert "clean outcome:" in text and "violating outcome:" in text
        assert "history invariant" in text
        # the divergence index is a certified statement over the
        # captured stream: the common prefix really is common
        import re

        m = re.search(r"first divergent timeline row: (\d+)", text)
        div = int(m.group(1))
        ev_a = obs.decode_timeline(
            obs.telemetry._capture(wl, cfg, 5, None, 600, 1024, None)[0],
            wl, 0,
        )
        ev_b = obs.decode_timeline(
            obs.telemetry._capture(wl, cfg, 5, plan, 600, 1024, None)[0],
            wl, 0,
        )
        for i in range(div):
            assert obs.telemetry._row_key(ev_a[i]) == obs.telemetry._row_key(
                ev_b[i]
            )
        assert (
            div == min(len(ev_a), len(ev_b))
            or obs.telemetry._row_key(ev_a[div])
            != obs.telemetry._row_key(ev_b[div])
        )

    def test_identical_runs_report_identity(self):
        # same (wl, cfg, caps) as above: the capture cache makes this
        # re-trace nothing
        text = obs.explain_diff(
            self.WL, self.CFG, (7, None), (7, None), max_steps=600,
            timeline_cap=1024,
        )
        assert "timelines IDENTICAL" in text
