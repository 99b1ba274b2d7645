"""Causal provenance (ISSUE 19): device-folded happens-before clocks.

The contract under test, clause by clause:

* **Derived state.** ``causal=False`` is the pre-causal engine:
  zero-size provenance columns and bit-identical traces/pools/rings
  across the scatter/dense lowerings, the time32 representation and
  the compacted runner — turning the axis on changes what is CAPTURED,
  never what HAPPENS.
* **DAG == derivation.** The device fold writes ``seq``/``parent``/
  ``lam`` into the ring; ``obs.causal.rederive`` recomputes the
  Lamport column host-side from nothing but the decoded stream. They
  must agree row for row — the refold discipline applied to lineage.
* **Cones.** ``causal_slice`` is the backward happens-before closure:
  sound (every member's causes are members) and minimal (a pinned
  pingpong scenario where one concurrent event is provably excluded).
* **Checkpoints.** Format 10 carries the causal columns (a Lamport
  clock is history, not a pool function): a causal run snapshots and
  resumes bit-identically, and a causal-off checkpoint refuses to
  resume under a causal step with the designed shape error.
* **Perfetto arrows.** Causal captures attribute flow arrows EXACTLY
  (by parent seq); the same-timestamp fixture shows the heuristic
  fallback mis-attributing precisely the case the causal path fixes —
  and that the fallback still renders for old captures.
* **Lint/absint.** The noninterference and interval provers sweep a
  causal axis: the clock fold is isolated derived state and the
  lam/seq counters are proved overflow-free.

tools/causal_soak.py runs the same pins at evidence scale
(CAUSAL_r13.txt); the one campaign-scale identity here rides the slow
tier.
"""

import dataclasses

import numpy as np
import pytest

import jax

from madsim_tpu import explore, obs
from madsim_tpu.chaos import CrashStorm, FaultPlan, GrayFailure, PauseStorm
from madsim_tpu.check import device as dc
from madsim_tpu.check import election_safety, violation_cones
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    load_checkpoint,
    make_init,
    make_run,
    save_checkpoint,
    search_seeds,
)
from madsim_tpu.engine.core import (
    FIRST_USER_KIND,
    PARENT_NONE,
    PARENT_PLAN,
    time32_eligible,
)
from madsim_tpu.engine.replay import ReplayEvent
from madsim_tpu.models import make_kvchaos, make_pingpong, make_raft
from madsim_tpu.models.raft import OP_ELECT
from madsim_tpu.obs.causal import (
    causal_slice,
    derive_parents,
    parent_class,
    rederive,
)

RAFT_CFG = EngineConfig(pool_size=64, loss_p=0.02)
RAFT_PLAN = FaultPlan((
    CrashStorm(targets=(1, 2, 3), n=1),
), name="causal-test")

_ONES = lambda v: np.ones(np.asarray(v["halted"]).shape[0], bool)  # noqa: E731


def _elect_inv(h):
    return election_safety(h, elect_op=OP_ELECT)


def _pingpong_events(**kw):
    """Decoded causal capture of pingpong seed 0 — the 3-node fixture
    (server + 2 clients) whose lineage the module docstring narrates."""
    wl = make_pingpong(rounds=4)
    r = search_seeds(
        wl, EngineConfig(), _ONES, n_seeds=4, max_steps=200,
        timeline_cap=256, causal=True, **kw,
    )
    return wl, obs.decode_timeline(r.timeline, wl, 0)


# ------------------------------------------------------------- identity
class TestOffIdentity:
    def test_causal_off_columns_are_zero_size(self):
        wl = make_raft()
        seeds = np.arange(4, dtype=np.uint64)
        off = make_init(wl, RAFT_CFG, Build(timeline_cap=8))(seeds)
        on = make_init(wl, RAFT_CFG, Build(timeline_cap=8, causal=True))(seeds)
        for f in ("lam", "ev_parent", "ev_lam", "tl_seq", "tl_parent",
                  "tl_lam"):
            assert np.asarray(getattr(off, f)).size == 0, f
            assert np.asarray(getattr(on, f)).size > 0, f
        # the clock is per (seed, node); provenance is per pool row
        assert on.lam.shape == (4, wl.n_nodes)
        assert on.ev_parent.shape == on.ev_time.shape

    def test_off_on_bit_identity_layouts_and_time32(self):
        """The fold is derived state on every lowering: same trace,
        clock, step count and pools with the axis on or off."""
        wl = make_raft()
        # the bounded-backoff config is what makes raft time32-eligible
        # (test_pool_index.py idiom)
        cfg = EngineConfig(pool_size=64, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        assert time32_eligible(wl, cfg)
        seeds = np.arange(8, dtype=np.uint64)
        for layout in ("scatter", "dense"):
            for t32 in (False, True):
                outs = {}
                for causal in (False, True):
                    init = make_init(wl, cfg, Build(time32=t32, causal=causal))
                    run = jax.jit(make_run(
                        wl, cfg, 200,
                        Build(layout=layout, time32=t32, causal=causal),
                    ))
                    outs[causal] = jax.block_until_ready(run(init(seeds)))
                for f in ("trace", "now", "step", "halted", "ev_time",
                          "ev_meta", "overflow"):
                    assert np.array_equal(
                        np.asarray(getattr(outs[False], f)),
                        np.asarray(getattr(outs[True], f)),
                    ), (layout, t32, f)

    def test_search_off_on_and_compact_identity(self):
        """search_seeds: causal changes no verdict and no captured
        tl_t row; the compacted runner banks identical causal columns
        to the lockstep path."""
        wl = make_raft(record=True)
        kw = dict(n_seeds=16, max_steps=600, plan=RAFT_PLAN,
                  history_invariant=_elect_inv, timeline_cap=256)
        off = search_seeds(wl, RAFT_CFG, None, **kw)
        on = search_seeds(wl, RAFT_CFG, None, causal=True, **kw)
        comp = search_seeds(wl, RAFT_CFG, None, causal=True,
                            compact=True, **kw)
        assert np.array_equal(off.traces, on.traces)
        assert np.array_equal(off.ok, on.ok)
        assert np.array_equal(off.timeline.tl_t, on.timeline.tl_t)
        assert off.lam is None
        assert not hasattr(off.timeline, "tl_seq")
        assert on.lam.shape == (16, wl.n_nodes)
        for f in ("tl_t", "tl_seq", "tl_parent", "tl_lam"):
            assert np.array_equal(
                getattr(on.timeline, f), getattr(comp.timeline, f)
            ), f
        assert np.array_equal(on.lam, comp.lam)


# ------------------------------------------------- DAG == derivation
class TestLineage:
    def test_rederive_matches_device_fold(self):
        """The captured lam column equals the host Lamport re-fold over
        the decoded stream — the device DAG and the replay derivation
        describe the same happens-before relation."""
        wl, ev = _pingpong_events()
        assert len(ev) > 10
        assert rederive(ev) == [e.lam for e in ev]
        # dispatch order IS seq order, gap-free on an un-dropped ring
        assert [e.seq for e in ev] == list(range(len(ev)))
        for i, p in enumerate(derive_parents(ev)):
            if ev[i].parent >= 0:
                assert p is not None and p < i
                # a delivery's emitter dispatched at its src node; a
                # timer's (src=-1) emitter is a dispatch at its OWN
                # node (timers are scheduled locally)
                emitter_node = ev[i].src if ev[i].src >= 0 else ev[i].node
                assert ev[p].node == emitter_node
            else:
                assert p is None

    def test_parent_sentinel_classes(self):
        # init rows: the t=0 on_init dispatches carry the init sentinel
        _, ev = _pingpong_events()
        assert ev[0].parent == PARENT_NONE
        assert parent_class(ev[0].parent) == "init"
        assert parent_class(0) == "event"
        # chaos plan rows carry the plan sentinel through the ring
        wl = make_raft(record=True)
        r = search_seeds(wl, RAFT_CFG, None, n_seeds=8, max_steps=600,
                         plan=RAFT_PLAN, history_invariant=_elect_inv,
                         timeline_cap=512, causal=True)
        classes = set()
        for s in range(8):
            for e in obs.decode_timeline(r.timeline, wl, s):
                classes.add(parent_class(e.parent))
        assert "plan" in classes
        assert PARENT_PLAN < 0  # sentinels never collide with seqs

    def test_rederive_requires_causal_capture(self):
        wl = make_raft()
        r = search_seeds(wl, RAFT_CFG, _ONES, n_seeds=4, max_steps=400,
                         timeline_cap=128)
        ev = obs.decode_timeline(r.timeline, wl, 0)
        with pytest.raises(ValueError, match="causal=True"):
            rederive(ev)
        with pytest.raises(ValueError, match="causal=True"):
            causal_slice(ev)


# --------------------------------------------------------------- cones
class TestCone:
    def test_cone_soundness_closed_under_causes(self):
        wl = make_raft(record=True)
        r = search_seeds(wl, RAFT_CFG, None, n_seeds=8, max_steps=600,
                         plan=RAFT_PLAN, history_invariant=_elect_inv,
                         timeline_cap=512, causal=True)
        ev = obs.decode_timeline(r.timeline, wl, 3)
        cone = causal_slice(ev)
        assert cone.anchor == len(ev) - 1
        member = set(cone.indices)
        parents = derive_parents(ev)
        last: dict = {}
        pred = []
        for i, e in enumerate(ev):
            pred.append(last.get(e.node))
            last[e.node] = i
        for i in member:
            for j in (parents[i], pred[i]):
                assert j is None or j in member, (i, j)
        assert cone.depth == ev[cone.anchor].lam
        assert 0 < cone.fraction <= 1.0

    def test_cone_minimality_pinned_pingpong(self):
        """Anchor event 5 (node0's delivery from client 2): its cone is
        exactly {0,1,2,3,5} — event 4 (client 1's concurrent delivery)
        is EXCLUDED, the provable-concurrency claim in miniature."""
        _, ev = _pingpong_events()
        cone = causal_slice(ev, anchor=5)
        assert cone.indices == (0, 1, 2, 3, 5)
        assert 4 not in cone.indices
        assert cone.depth == 3
        assert cone.missing_parents == 0

    def test_anchor_forms_agree(self):
        _, ev = _pingpong_events()
        by_index = causal_slice(ev, anchor=5)
        by_time = causal_slice(ev, anchor=(ev[5].time_ns, ev[5].node))
        assert by_index.indices == by_time.indices
        with pytest.raises(ValueError, match="outside the captured"):
            causal_slice(ev, anchor=len(ev))
        with pytest.raises(ValueError, match="predates the capture"):
            causal_slice(ev, anchor=(-1, 0))

    def test_violation_cones_from_device_check(self):
        """The escalation payload: every device-flagged seed gets a
        cone anchored at its last completed history record."""
        cfg = EngineConfig(pool_size=40, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        screens = (dc.stale_reads(), dc.read_your_writes(),
                   dc.monotonic_reads())
        wl = make_kvchaos(writes=5, record=True, bug=True)
        r = search_seeds(wl, cfg, None, device_check=screens,
                         n_seeds=128, max_steps=600, require_halt=False,
                         timeline_cap=512, causal=True)
        if not len(r.flagged_idx):
            pytest.skip("mutant not caught in this tiny sweep")
        cones = violation_cones(r)
        assert set(cones) == set(int(i) for i in r.flagged_idx)
        for row, cone in cones.items():
            assert cone.seed == row
            assert len(cone.indices) > 0
            assert cone.anchor in cone.indices

    def test_violation_cones_requires_flags_and_ring(self):
        wl = make_raft()
        r = search_seeds(wl, RAFT_CFG, _ONES, n_seeds=4, max_steps=400)
        with pytest.raises(ValueError, match="device_check"):
            violation_cones(r)


# --------------------------------------------------------- checkpoints
class TestCheckpoint:
    def test_causal_roundtrip_resumes_identically(self, tmp_path):
        """Save mid-run, resume: the spliced causal run equals the
        uninterrupted one — clock, provenance and ring included."""
        wl = make_raft()
        seeds = np.arange(6, dtype=np.uint64)
        init = make_init(wl, RAFT_CFG, Build(timeline_cap=128, causal=True))
        run = jax.jit(make_run(
            wl, RAFT_CFG, 120, Build(timeline_cap=128, causal=True),
        ))
        mid = jax.block_until_ready(run(init(seeds)))
        p = str(tmp_path / "causal.npz")
        save_checkpoint(p, mid, RAFT_CFG)
        resumed = jax.block_until_ready(run(load_checkpoint(p, wl, RAFT_CFG)))
        straight = jax.block_until_ready(run(run(init(seeds))))
        for f in dataclasses.fields(straight):
            assert np.array_equal(
                np.asarray(getattr(straight, f.name)),
                np.asarray(getattr(resumed, f.name)),
            ), f.name

    def test_off_checkpoint_refuses_causal_resume(self, tmp_path):
        """A causal-off snapshot has zero-size provenance columns; the
        causal step refuses it with the designed shape error instead of
        silently restarting the clock."""
        wl = make_raft()
        st = make_init(wl, RAFT_CFG, Build(timeline_cap=8))(
            np.arange(4, dtype=np.uint64)
        )
        p = str(tmp_path / "off.npz")
        save_checkpoint(p, st, RAFT_CFG)
        run = make_run(wl, RAFT_CFG, 20, Build(timeline_cap=8, causal=True))
        with pytest.raises(TypeError, match="causal"):
            jax.jit(run)(load_checkpoint(p, wl, RAFT_CFG))


# ----------------------------------------------------- perfetto arrows
_K = FIRST_USER_KIND  # any user kind: the fixture only needs non-engine


def _fixture_events():
    """The same-timestamp mis-attribution case (obs/perfetto.py module
    docstring): node 1 emits at t=100us, then dispatches again at the
    DELIVERY's timestamp — the sender's-last-dispatch heuristic anchors
    the arrow at the decoy, the causal parent at the true emitter."""
    return [
        ReplayEvent(time_ns=100_000, kind=_K, node=1, src=-1,
                    args=(0, 0, 0, 0), pay=(), seq=0, parent=-1, lam=1),
        ReplayEvent(time_ns=200_000, kind=_K, node=1, src=-1,
                    args=(0, 0, 0, 0), pay=(), seq=1, parent=-1, lam=2),
        ReplayEvent(time_ns=200_000, kind=_K, node=2, src=1,
                    args=(0, 0, 0, 0), pay=(), seq=2, parent=0, lam=2),
    ]


def _flow_starts(doc):
    return [r for r in doc["traceEvents"]
            if r.get("cat") == "flow" and r["ph"] == "s"]


class TestPerfettoArrows:
    def test_causal_capture_attributes_exactly(self):
        ev = _fixture_events()
        doc = obs.to_perfetto(ev)
        (s,) = _flow_starts(doc)
        assert s["ts"] == 100.0 and s["pid"] == 1  # the true emitter
        # causal columns ride the dispatch slices' args
        rows = [r for r in doc["traceEvents"] if r.get("cat") == "dispatch"]
        assert len(rows) == len(ev)
        assert [r["args"]["seq"] for r in rows] == [0, 1, 2]
        assert rows[2]["args"]["parent"] == 0

    def test_heuristic_fallback_misattributes_the_fixture(self):
        """Strip the causal columns: the old capture still renders, and
        the arrow lands on the same-timestamp decoy — the tested reason
        the exact path exists."""
        ev = [dataclasses.replace(e, seq=-1, parent=-1, lam=0)
              for e in _fixture_events()]
        doc = obs.to_perfetto(ev)
        (s,) = _flow_starts(doc)
        assert s["ts"] == 200.0 and s["pid"] == 1  # the decoy dispatch
        rows = [r for r in doc["traceEvents"] if r.get("cat") == "dispatch"]
        assert len(rows) == len(ev)
        assert all("seq" not in r["args"] for r in rows)

    def test_emit_sidecar_middle_precedence(self):
        """emit_ns-only captures anchor at the true send time (node-
        attributed) — finer than the heuristic, coarser than causal."""
        ev = [dataclasses.replace(e, seq=-1, parent=-1, lam=0,
                                  emit_ns=(100_000 if e.src >= 0 else -1))
              for e in _fixture_events()]
        (s,) = _flow_starts(obs.to_perfetto(ev))
        assert s["ts"] == 100.0 and s["pid"] == 1

    def test_real_capture_every_arrow_exact(self):
        """On an un-dropped causal ring every delivery's arrow leaves
        its parent dispatch: arrow (pid, ts) pairs match the parent
        column exactly, arrow count equals delivery count."""
        wl, ev = _pingpong_events()
        doc = obs.to_perfetto(ev, wl, seed=0)
        starts = _flow_starts(doc)
        deliveries = [e for e in ev if e.src >= 0]
        assert len(starts) == len(deliveries)
        by_seq = {e.seq: e for e in ev}
        want = sorted(
            (by_seq[e.parent].node,
             (e.emit_ns if e.emit_ns >= 0
              else by_seq[e.parent].time_ns) / 1e3)
            for e in deliveries
        )
        got = sorted((s["pid"], s["ts"]) for s in starts)
        assert got == want


# ------------------------------------------------------- explain/fleet
class TestExplainCausal:
    def test_explain_narrates_the_cone(self):
        wl = make_raft(record=True)
        plan = FaultPlan((CrashStorm(targets=(1, 2, 3), n=1),), name="t")
        text = obs.explain(
            wl, EngineConfig(pool_size=96), seed=5, plan=plan,
            history_invariant=_elect_inv, max_steps=600, causal=True,
        )
        assert "--- causal anchor:" in text
        assert "causal cone:" in text
        assert "** ANCHOR" in text
        assert "precede the anchor" in text
        # the shared tail still narrates outcome and repro line
        assert "verdict: history invariant HOLDS" in text
        assert "repro: seed=5" in text

    def test_explain_diff_names_first_divergent_edge(self):
        wl = make_raft(record=True)
        cfg = EngineConfig(pool_size=96)
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
            timeline_cap=1024, causal=True,
        )
        assert "first divergent causal edge: row 5" in text
        assert "clean:" in text and "violating:" in text
        # identical runs report edge identity, not a fork
        same = obs.explain_diff(
            wl, cfg, (7, None), (7, None), max_steps=600,
            timeline_cap=1024, causal=True,
        )
        assert "causal edges identical" in same


class TestFleetCausal:
    def test_fleet_reduce_depth_and_width(self):
        wl = make_raft(record=True)
        r = search_seeds(wl, RAFT_CFG, None, n_seeds=16, max_steps=600,
                         plan=RAFT_PLAN, history_invariant=_elect_inv,
                         metrics=True, causal=True)
        fm = obs.fleet_reduce(r.met, lam=r.lam)
        assert fm.depth_min is not None and fm.depth_min >= 1
        assert fm.depth_max >= fm.depth_min
        # width = sum(lam)/max(lam): 1.0 = serial, n_nodes = parallel
        assert 1.0 <= fm.width_mean <= wl.n_nodes
        assert int(fm.depth_hist.sum()) == 16
        assert "causal: depth" in fm.format()
        # per-seed depth really is the row max of the clock
        assert fm.depth_max == int(np.max(r.lam))
        off = obs.fleet_reduce(r.met)
        assert off.depth_min is None and off.width_mean is None
        assert "causal:" not in off.format()


# ---------------------------------------------------------- lint/absint
class TestLintCausal:
    def test_matrix_rows_exist(self):
        from madsim_tpu.lint.absint import ABSINT_AXES
        from madsim_tpu.lint.noninterference import (
            BUILD_AXES,
            CAMPAIGN_AXES,
        )

        assert BUILD_AXES["causal"]["causal"] is True
        assert BUILD_AXES["all"]["causal"] is True
        assert CAMPAIGN_AXES["sharded-causal"]["causal"] is True
        assert ABSINT_AXES["causal"]["causal"] is True
        assert ABSINT_AXES["all"]["causal"] is True

    def test_noninterference_causal_smoke(self):
        """The Lamport fold is isolated derived state: core outputs
        come back label-free with the causal taps on (the full matrix
        is tools/lint_soak.py's job)."""
        from madsim_tpu.lint.noninterference import check_noninterference
        from madsim_tpu.models import raft as raft_mod

        _tag, wl, cfg_kw = raft_mod.lint_entries()[0]
        rep = check_noninterference(
            wl, EngineConfig(**cfg_kw),
            Build(layout="scatter", time32=False, causal=True, timeline_cap=8),
            entry="step",
        )
        assert rep.ok, rep.summary()
        assert rep.flags["causal"] is True
        assert {"lam", "ev_parent", "ev_lam"} <= set(rep.derived)

    def test_absint_causal_smoke(self):
        """lam and the dispatch-seq stamp are proved overflow-free
        under the step-budget contract — no new unproved counters."""
        from madsim_tpu.lint.absint import check_ranges
        from madsim_tpu.models import raft as raft_mod

        _tag, wl, cfg_kw, horizon = raft_mod.absint_entries()[0]
        rep = check_ranges(
            wl, EngineConfig(**cfg_kw),
            Build(layout="scatter", time32=False, causal=True, timeline_cap=8),
            entry="step", horizon_ns=horizon,
        )
        assert rep.ok, rep.summary()
        assert rep.flags["causal"] is True


# ------------------------------------------------------ campaign scale
@pytest.mark.slow
class TestCampaignCausal:
    def test_host_device_identity_and_coverage_feature(self):
        """causal=True threads through both campaign drivers: host and
        device runs stay bit-identical, and the causal depth/width
        coverage features add signal the base run cannot see."""
        cfg = EngineConfig(pool_size=64, loss_p=0.02)
        plan = FaultPlan((
            PauseStorm(targets=(0, 1, 2, 3, 4), n=1,
                       t_min_ns=20_000_000, t_max_ns=300_000_000,
                       down_min_ns=50_000_000, down_max_ns=200_000_000),
            GrayFailure(targets=(0, 1, 2, 3, 4), n_links=1),
        ), name="causal-campaign")
        kw = dict(generations=3, batch=24, root_seed=11, max_steps=600,
                  cov_words=16, invariant=lambda v: v["halted"])

        def fp(rep):
            return (
                [(e.id, e.generation, e.seed, e.trace, e.new_bits)
                 for e in rep.corpus],
                rep.cov_map.tolist(), rep.curve,
            )

        host = explore.run(make_raft(), cfg, plan, causal=True, **kw)
        dev = explore.run_device(make_raft(), cfg, plan, causal=True,
                                 **kw)
        assert fp(host) == fp(dev)
        base = explore.run(make_raft(), cfg, plan, **kw)
        # generation 0 runs IDENTICAL schedules on both (uniform draws,
        # no steering yet), so the causal depth/jump feature class can
        # only ADD bits there; later generations steer differently —
        # the feature class observably changes the hunt
        assert host.curve[0] > base.curve[0]
