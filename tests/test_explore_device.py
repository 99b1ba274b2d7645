"""explore.run_device — the device-resident campaign is a lowering of
the host driver, not a fork.

Every test pins one clause of the contract: bit-identical campaign
outcomes (corpus ids/seeds/plans/traces/new-bit scores, coverage map,
violation set, curves) against ``explore.run`` given the same
arguments, across engine layouts, across checkpoint save/resume in
BOTH directions, and — slow-marked — across a multi-chip mesh. The
telemetry tests make the one-host-sync-per-generation claim checkable
from the artifact rather than from this module's word.
"""

import json

import numpy as np
import pytest

import jax

from madsim_tpu import explore
from madsim_tpu.chaos import FaultPlan, GrayFailure, PauseStorm
from madsim_tpu.explore import device
from madsim_tpu.engine import EngineConfig
from madsim_tpu.models import make_raft
from madsim_tpu.parallel import make_mesh

NODES = (0, 1, 2, 3, 4)

CFG = EngineConfig(pool_size=64, loss_p=0.02)
PLAN = FaultPlan((
    PauseStorm(targets=NODES, n=1, t_min_ns=20_000_000,
               t_max_ns=300_000_000, down_min_ns=50_000_000,
               down_max_ns=200_000_000),
    GrayFailure(targets=NODES, n_links=1),
), name="device-explore-test")


def _halt_inv(view):
    # jnp-traceable on the device path, numpy-evaluable on the host
    # path — the SAME predicate object drives both drivers
    return view["halted"]


def _biased_inv(view):
    # a deterministic pure-function-of-final-state "bug": seeds whose
    # trace hash lands in the low eighth are violations. Exercises the
    # violation store, (seed, trace) dedup and replay machinery on both
    # paths without needing a planted model mutant.
    return (view["trace"] & 7) != 0


KW = dict(generations=3, batch=24, root_seed=11, max_steps=600,
          cov_words=16, invariant=_halt_inv)

# the uninterrupted host campaign both checkpoint-interop tests splice
# against — computed once (tier-1 wall is a budgeted resource)
_FULL_CACHE: dict = {}


def _full_host_fp():
    if "fp" not in _FULL_CACHE:
        _FULL_CACHE["fp"] = _fingerprint(
            explore.run(make_raft(), CFG, PLAN, **KW)
        )
    return _FULL_CACHE["fp"]


def _fingerprint(rep):
    return (
        [(e.id, e.generation, e.parent, e.seed, e.plan.name, e.plan.hash(),
          e.trace, e.new_bits, e.violating, e.halt_t) for e in rep.corpus],
        rep.cov_map.tolist(),
        [(e.seed, e.trace) for e in rep.violations],
        rep.curve,
        rep.viol_curve,
    )


class TestDeviceParity:
    def test_device_matches_host_and_layouts(self):
        """One host campaign, one device campaign per layout: all three
        produce the same corpus, coverage map and curves — and the
        gen-0 seed-corpus override rides along on every path."""
        seed_lp = PLAN.literalize(3)
        kw = dict(KW, seed_corpus=(seed_lp,))
        host = explore.run(make_raft(), CFG, PLAN, **kw)
        dev = explore.run_device(make_raft(), CFG, PLAN, **kw)
        dense = explore.run_device(
            make_raft(), CFG, PLAN, layout="dense", **kw
        )
        assert _fingerprint(host) == _fingerprint(dev)
        assert _fingerprint(host) == _fingerprint(dense)
        assert dev.host_syncs == kw["generations"]
        assert host.host_syncs == 0  # the notion is device-driver-only
        # the seed-corpus entry keeps its literal name on both paths
        names = {e.plan.name for e in dev.corpus}
        assert seed_lp.name in names

    def test_violations_dedup_and_replay(self):
        """The violation machinery is bit-identical too: same deduped
        (seed, trace) set, and a device-found violation replays to its
        recorded trace through the ordinary host replay path."""
        kw = dict(KW, invariant=_biased_inv, generations=2)
        host = explore.run(make_raft(), CFG, PLAN, **kw)
        dev = explore.run_device(make_raft(), CFG, PLAN, **kw)
        assert _fingerprint(host) == _fingerprint(dev)
        assert dev.violations, "the biased invariant must flag seeds"
        e = dev.violations[-1]
        r = explore.replay_entry(
            make_raft(), CFG, e, invariant=_biased_inv, max_steps=800,
        )
        assert int(r.traces[0]) == e.trace
        assert int(r.failing_seeds[0]) == e.seed

    def test_checkpoint_interop_host_to_device(self, tmp_path):
        """A host-driver checkpoint resumes on the device driver (and
        the spliced campaign equals the uninterrupted host one)."""
        p = str(tmp_path / "camp.npz")
        explore.run(
            make_raft(), CFG, PLAN,
            **dict(KW, generations=2, checkpoint_path=p),
        )
        resumed = explore.run_device(
            make_raft(), CFG, PLAN,
            **dict(KW, generations=1), resume=p,
        )
        assert _full_host_fp() == _fingerprint(resumed)
        # the wall split / sync count cover only the RESUMED run — the
        # banner must pair them against 1 generation, not all 3
        assert resumed.generations == 3
        assert resumed.host_syncs == 1 and resumed.wall_gens == 1
        assert "1 summary syncs / 1 generations" in resumed.banner()

    def test_checkpoint_interop_device_to_host(self, tmp_path):
        p = str(tmp_path / "camp.npz")
        explore.run_device(
            make_raft(), CFG, PLAN,
            **dict(KW, generations=2, checkpoint_path=p),
        )
        resumed = explore.run(
            make_raft(), CFG, PLAN,
            **dict(KW, generations=1), resume=p,
        )
        assert _full_host_fp() == _fingerprint(resumed)

    def test_telemetry_one_sync_per_generation(self, tmp_path):
        """The artifact proves the claim: every generation record has
        ``host_syncs: 1`` and a dispatch/sync wall split; campaign_end
        totals them."""
        records = []
        rep = explore.run_device(
            make_raft(), CFG, PLAN, telemetry=records.append,
            **dict(KW, generations=2, batch=8),
        )
        gens = [r for r in records if r["event"] == "generation"]
        assert len(gens) == 2
        for r in gens:
            assert r["host_syncs"] == 1
            assert "dispatch_wall_s" in r and "sync_wall_s" in r
        end = records[-1]
        assert end["event"] == "campaign_end"
        assert end["host_syncs"] == 2
        assert rep.host_syncs == 2
        # every record is JSONL-serializable (the artifact format)
        for r in records:
            json.dumps(r)
        assert "host sync" in rep.banner()

    def test_host_driver_banner_reports_wall_split(self):
        rep = explore.run(
            make_raft(), CFG, PLAN, **dict(KW, generations=1, batch=8)
        )
        assert rep.wall_dispatch_s > 0.0
        assert "batched dispatch" in rep.banner()

    def test_requires_traceable_invariant(self):
        with pytest.raises(ValueError, match="traceable"):
            explore.run_device(
                make_raft(), CFG, PLAN,
                **{**KW, "invariant": None},
            )

    def test_viol_store_overflow_raises(self):
        # everything violates and the store cannot hold the batch: the
        # dedup set would silently break, so the campaign must refuse
        with pytest.raises(RuntimeError, match="viol_cap"):
            explore.run_device(
                make_raft(), CFG, PLAN, viol_cap=2,
                **dict(KW, generations=1, batch=8,
                       invariant=lambda v: v["halted"] & False),
            )


# ---------------------------------------------------------------------------
# the report's entries: a view over one host copy of the stores
# ---------------------------------------------------------------------------

# a campaign whose corpus fills (max_corpus=6), so its violations hold
# both entries shared with the corpus and entries the full corpus refused
VIEW_KW = dict(KW, invariant=_biased_inv, generations=2, max_corpus=6)
_VIEW_RUN: dict = {}


def _view_run():
    """One device campaign, and what the eager materialize loop builds
    from its session's final stores."""
    if _VIEW_RUN:
        return _VIEW_RUN
    sessions = []
    report = device._CampaignSession.report

    def spy(self, **kw):
        sessions.append(self)
        return report(self, **kw)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(device._CampaignSession, "report", spy)
        rep = explore.run_device(make_raft(), CFG, PLAN, **VIEW_KW)
    (sess,) = sessions
    host = jax.device_get(sess.carry)
    cn, vn = host["c"], host["v"]
    corpus = [device._store_entry(cn, i, sess._entry_name(cn, i))
              for i in range(int(host["count"]))]
    by_id = {e.id: e for e in corpus}
    violations = [
        by_id.get(int(vn["id"][i]))
        or device._store_entry(vn, i, sess._entry_name(vn, i))
        for i in range(int(host["vcount"]))
    ]
    _VIEW_RUN.update(rep=rep, corpus=corpus, violations=violations)
    return _VIEW_RUN


class TestEntryView:
    @pytest.mark.parametrize("which", ["corpus", "violations"])
    def test_every_index_equals_the_store_row(self, which):
        run = _view_run()
        view, eager = getattr(run["rep"], which), run[which]
        assert isinstance(view, device.EntryView)
        assert len(view) == len(eager) > 0
        for i in range(len(eager)):
            assert view[i] == eager[i]
            assert view[i] is view[i]  # built once, then cached

    @pytest.mark.parametrize("which", ["corpus", "violations"])
    def test_sequence_protocol(self, which):
        run = _view_run()
        view, eager = getattr(run["rep"], which), run[which]
        n = len(eager)
        assert view[-1] is view[n - 1] and view[-n] is view[0]
        assert view[1:4] == eager[1:4] and view[::-2] == eager[::-2]
        assert view[n:] == []
        with pytest.raises(IndexError):
            view[n]
        with pytest.raises(IndexError):
            view[-n - 1]
        assert bool(view) and list(view) == eager
        assert [e.id for e in view] == [e.id for e in eager]
        assert view == eager and eager == view and view == tuple(eager)
        assert view != eager[:-1] and view != eager[::-1]
        assert view.built <= n

    def test_empty_view_is_falsy(self):
        view = device.EntryView({}, 0, {}, None)
        assert not view and len(view) == 0 and list(view) == []
        assert view == [] and view[:] == []

    def test_violating_corpus_entry_is_one_object(self):
        rep = _view_run()["rep"]
        by_id = {e.id: e for e in rep.corpus}
        shared = [v for v in rep.violations if v.id in by_id]
        refused = [v for v in rep.violations if v.id not in by_id]
        assert shared and refused, "the fixture must hold both kinds"
        assert all(v is by_id[v.id] for v in shared)
        assert rep.violations.built <= len(refused)

    def test_device_and_host_fingerprints_equal(self):
        host = explore.run(make_raft(), CFG, PLAN, **VIEW_KW)
        assert _fingerprint(host) == _fingerprint(_view_run()["rep"])

    def test_report_checkpoint_round_trips(self, tmp_path):
        rep = _view_run()["rep"]
        st = explore.CampaignState.from_report(rep)
        assert st.corpus == list(rep.corpus)
        p = str(tmp_path / "camp.npz")
        st.save(p)
        back = explore.load_campaign(p)
        assert back.to_dict() == st.to_dict()
        assert back.corpus == rep.corpus and back.violations == rep.violations

    def test_resumed_entries_are_the_loaded_objects(self, tmp_path):
        p = str(tmp_path / "camp.npz")
        explore.run_device(make_raft(), CFG, PLAN,
                           **dict(VIEW_KW, generations=1, checkpoint_path=p))
        st = explore.load_campaign(p)
        rep = explore.run_device(make_raft(), CFG, PLAN,
                                 **dict(VIEW_KW, generations=1), resume=st)
        assert st.corpus and st.violations
        assert all(rep.corpus[i] is e for i, e in enumerate(st.corpus))
        assert all(rep.violations[i] is e
                   for i, e in enumerate(st.violations))
        assert _fingerprint(rep) == _fingerprint(_view_run()["rep"])


@pytest.mark.slow
@pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU platform"
)
class TestDeviceMesh:
    def test_mesh_campaign_identical(self):
        """Sharding the generation across the 8-device mesh changes no
        bit of the campaign (corpus, coverage, violations), and the
        cross-shard metric fold reports through telemetry."""
        records = []
        host = explore.run(make_raft(), CFG, PLAN, **KW)
        dev = explore.run_device(
            make_raft(), CFG, PLAN, mesh=make_mesh(), metrics=True,
            telemetry=records.append, **KW,
        )
        assert _fingerprint(host) == _fingerprint(dev)
        gens = [r for r in records if r["event"] == "generation"]
        assert all(r["host_syncs"] == 1 for r in gens)
        assert all(len(r["met_total"]) > 0 for r in gens)

    def test_mesh_batch_must_split(self):
        with pytest.raises(ValueError, match="split over"):
            explore.run_device(
                make_raft(), CFG, PLAN, mesh=make_mesh(),
                **dict(KW, batch=12),
            )
