"""madsim_tpu.explore — coverage taps, plan device parity, mutation,
and campaign determinism.

The subsystem's contract is replayability: the whole exploration
campaign is a pure function of its root seed, corpus entries replay to
their recorded trace hashes, and the engine's coverage taps never
perturb the simulation they observe. Each test pins one clause.
"""

import dataclasses

import numpy as np
import pytest

from madsim_tpu import explore
from madsim_tpu.chaos import (
    ClockSkew,
    CrashStorm,
    Duplicate,
    FaultPlan,
    FlappingPartition,
    GrayFailure,
    LiteralPlan,
    Partition,
    PauseStorm,
    stack_plan_rows,
)
from madsim_tpu.check import election_safety, read_your_writes, stale_reads
from madsim_tpu.engine import Build, EngineConfig, search_seeds
from madsim_tpu.engine.rng import PURPOSE_EXPLORE
from madsim_tpu.explore.mutate import HostStream, PlanSpace, mutate_plan
from madsim_tpu.models import make_kvchaos, make_raft
from madsim_tpu.models.raft import OP_ELECT

NODES = (0, 1, 2, 3, 4)

RAFT_CFG = EngineConfig(pool_size=64, loss_p=0.02)
RAFT_PLAN = FaultPlan((
    PauseStorm(targets=NODES, n=1, t_min_ns=20_000_000,
               t_max_ns=300_000_000, down_min_ns=50_000_000,
               down_max_ns=200_000_000),
    GrayFailure(targets=NODES, n_links=1),
), name="raft-explore-test")

MIXED_PLAN = FaultPlan((
    CrashStorm(targets=(1, 2, 3), n=2),
    PauseStorm(targets=(0, 4)),
    Partition(targets=NODES, asymmetric=True, partial_p=0.7),
    FlappingPartition(targets=NODES, n_cycles=2),
    GrayFailure(targets=NODES, n_links=2),
    Duplicate(),
    ClockSkew(targets=(0, 1, 2)),
), name="mixed")


def _raft_wl():
    return make_raft(record=True)


def _elect_inv(h):
    return election_safety(h, elect_op=OP_ELECT)


class TestEngineCoverage:
    def test_cov_off_and_on_identical_traces(self):
        """Coverage is derived state: enabling it changes no value."""
        wl = _raft_wl()
        inv = lambda v: np.ones(v["halted"].shape[0], bool)  # noqa: E731
        r0 = search_seeds(wl, RAFT_CFG, inv, n_seeds=16, max_steps=600)
        r1 = search_seeds(
            wl, RAFT_CFG, inv, n_seeds=16, max_steps=600, cov_words=16
        )
        assert np.array_equal(r0.traces, r1.traces)
        assert r0.cov is None
        assert r1.cov.shape == (16, 16) and r1.cov.dtype == np.uint32
        assert r1.cov.any(), "a raft election run must set coverage bits"

    def test_cov_identical_across_layouts_and_compact(self):
        wl = _raft_wl()
        inv = lambda v: np.ones(v["halted"].shape[0], bool)  # noqa: E731
        kw = dict(n_seeds=16, max_steps=600, cov_words=16)
        base = search_seeds(wl, RAFT_CFG, inv, layout="scatter", **kw)
        dense = search_seeds(wl, RAFT_CFG, inv, layout="dense", **kw)
        comp = search_seeds(wl, RAFT_CFG, inv, compact=True, **kw)
        assert np.array_equal(base.cov, dense.cov)
        assert np.array_equal(base.cov, comp.cov)

    def test_cov_words_must_be_power_of_two(self):
        from madsim_tpu.engine import make_init

        with pytest.raises(ValueError, match="power of two"):
            make_init(_raft_wl(), RAFT_CFG, Build(cov_words=24))

    def test_explicit_seeds_match_range_sweep(self):
        wl = _raft_wl()
        inv = lambda v: np.ones(v["halted"].shape[0], bool)  # noqa: E731
        full = search_seeds(wl, RAFT_CFG, inv, n_seeds=8, max_steps=600)
        some = search_seeds(
            wl, RAFT_CFG, inv, seeds=np.array([2, 5, 7], np.uint64),
            max_steps=600,
        )
        assert np.array_equal(some.traces, full.traces[[2, 5, 7]])


class TestPlanDeviceParity:
    def test_jnp_compile_bit_identical(self):
        """The device (jnp) plan materialization path and the numpy
        path are the same function — bit-identical arrays."""
        seeds = np.arange(257, dtype=np.uint64) * np.uint64(2654435761)
        rows_np = MIXED_PLAN.compile_batch(seeds)
        rows_dev = MIXED_PLAN.compile_batch(seeds, device=True)
        for f in ("time", "kind", "args", "valid"):
            assert np.array_equal(
                np.asarray(getattr(rows_np, f)),
                np.asarray(getattr(rows_dev, f)),
            ), f"device-parity divergence in {f}"

    def test_literal_device_parity(self):
        lp = MIXED_PLAN.literalize(42)
        seeds = np.arange(5, dtype=np.uint64)
        a = lp.compile_batch(seeds)
        b = lp.compile_batch(seeds, device=True)
        for f in ("time", "kind", "args", "valid"):
            assert np.array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            )


class TestFlappingPartition:
    def test_redraws_sides_each_cycle(self):
        spec = FlappingPartition(targets=NODES, n_cycles=2)
        plan = FaultPlan((spec,), name="flap")
        edges = len(NODES) * (len(NODES) - 1) // 2
        assert spec.slots == 2 * 2 * edges
        seeds = np.arange(64, dtype=np.uint64)
        rows = plan.compile_batch(seeds)
        c0 = np.asarray(rows.valid)[:, : 2 * edges]
        c1 = np.asarray(rows.valid)[:, 2 * edges:]
        # both cycles cut something on every seed...
        assert c0.any(axis=1).all() and c1.any(axis=1).all()
        # ...and the cut sides differ between cycles for most seeds
        # (independent subset draws)
        assert (c0 != c1).any(axis=1).sum() > 32
        # cycle 1 strictly follows cycle 0's heal on every seed
        t = np.asarray(rows.time)
        heal0 = t[:, 1]  # slot 1 = first cycle's first unclog (at+dur)
        cut1 = t[:, 2 * edges]
        assert (cut1 > heal0).all()

    def test_validation(self):
        with pytest.raises(ValueError, match="n_cycles"):
            FlappingPartition(targets=NODES, n_cycles=0)
        with pytest.raises(ValueError, match="two target"):
            FlappingPartition(targets=(1,))


class TestPlanHooks:
    def test_templates_align_with_compiled_slots(self):
        tmpl = MIXED_PLAN.slot_templates()
        assert len(tmpl) == MIXED_PLAN.slots
        rows = MIXED_PLAN.compile_batch(np.arange(3, dtype=np.uint64))
        assert [t.kind for t in tmpl] == [int(k) for k in rows.kind[0]]

    def test_literalize_replays_identical_rows(self):
        lp = MIXED_PLAN.literalize(99)
        assert lp.slots == MIXED_PLAN.slots
        a = MIXED_PLAN.compile_batch(np.asarray([99], np.uint64))
        b = lp.compile_batch(np.asarray([99], np.uint64))
        for f in ("time", "kind", "args", "valid"):
            assert np.array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
            )

    def test_serialization_round_trip(self):
        lp = MIXED_PLAN.literalize(7)
        lp2 = LiteralPlan.from_dict(lp.to_dict())
        assert lp2.hash() == lp.hash()
        assert lp2.events == lp.events

    def test_stack_plan_rows_matches_batch_compile(self):
        plans = [MIXED_PLAN.literalize(s) for s in (3, 4, 5)]
        stacked = stack_plan_rows(plans)
        direct = MIXED_PLAN.compile_batch(np.asarray([3, 4, 5], np.uint64))
        for f in ("time", "kind", "args", "valid"):
            assert np.array_equal(
                np.asarray(getattr(stacked, f)),
                np.asarray(getattr(direct, f)),
            )


class TestMutate:
    def test_deterministic_and_fresh(self):
        space = PlanSpace(MIXED_PLAN)
        parent = MIXED_PLAN.literalize(11)
        a = mutate_plan(parent, space, HostStream(1, 2, PURPOSE_EXPLORE))
        b = mutate_plan(parent, space, HostStream(1, 2, PURPOSE_EXPLORE))
        c = mutate_plan(parent, space, HostStream(3, 4, PURPOSE_EXPLORE))
        assert a.hash() == b.hash(), "same stream must breed the same child"
        assert a.hash() != parent.hash(), "a child must differ from parent"
        assert c.hash() != a.hash(), "different streams should diverge"

    def test_slot_count_preserved(self):
        space = PlanSpace(MIXED_PLAN)
        parent = MIXED_PLAN.literalize(11)
        st = HostStream(9, 9, PURPOSE_EXPLORE)
        for _ in range(20):
            child = mutate_plan(parent, space, st, max_ops=3)
            assert child.slots == parent.slots
            parent = child

    def test_degenerate_pair_targets_rejected(self):
        # a pair/slow slot with one distinct target cannot draw "some
        # OTHER target": the host mutator would crash mid-campaign and
        # the device mutator would silently breed b == a — the space is
        # refused up front on both paths instead
        plan = FaultPlan(
            (GrayFailure(targets=(2, 2), n_links=1),), name="degen"
        )
        with pytest.raises(ValueError, match="distinct targets"):
            PlanSpace(plan)


class TestCoverageAccounting:
    def test_admit_sequential_semantics(self):
        g = np.zeros(2, np.uint32)
        batch = np.array(
            [[1, 0], [1, 0], [3, 0], [0, 8]], np.uint32
        )
        new_bits, merged = explore.admit(batch, g)
        # row 0 sets bit0 (new); row 1 sets nothing new; row 2 adds
        # bit1; row 3 adds one bit in word 1
        assert new_bits.tolist() == [1, 0, 1, 1]
        assert merged.tolist() == [3, 8]
        assert explore.popcount(merged) == 3

    def test_merge_coverage_sharded_equals_host(self):
        from madsim_tpu.parallel import make_mesh, merge_coverage

        rng = np.random.default_rng(0)
        bm = rng.integers(0, 2**32, size=(64, 8), dtype=np.uint64).astype(
            np.uint32
        )
        host = explore.merge(bm)
        mesh = make_mesh()
        assert np.array_equal(merge_coverage(bm, mesh), host)
        assert np.array_equal(merge_coverage(bm), host)


class TestCampaign:
    """The determinism clauses of the ISSUE: same root seed => same
    corpus, coverage bitmap, and violation set — across runs and across
    engine lowerings — and stored entries replay their trace hash."""

    KW = dict(
        generations=3, batch=24, root_seed=11, max_steps=800,
        cov_words=16, history_invariant=_elect_inv,
    )

    def _fingerprint(self, rep):
        return (
            [(e.id, e.seed, e.plan.hash(), e.trace, e.new_bits)
             for e in rep.corpus],
            rep.cov_map.tolist(),
            [(e.seed, e.trace) for e in rep.violations],
            rep.curve,
        )

    def test_same_root_identical_campaign(self):
        a = explore.run(_raft_wl(), RAFT_CFG, RAFT_PLAN, **self.KW)
        b = explore.run(_raft_wl(), RAFT_CFG, RAFT_PLAN, **self.KW)
        assert self._fingerprint(a) == self._fingerprint(b)
        assert a.sims == 3 * 24

    def test_compact_and_layouts_identical(self):
        base = explore.run(_raft_wl(), RAFT_CFG, RAFT_PLAN, **self.KW)
        comp = explore.run(
            _raft_wl(), RAFT_CFG, RAFT_PLAN, compact=True, **self.KW
        )
        dense = explore.run(
            _raft_wl(), RAFT_CFG, RAFT_PLAN, layout="dense", **self.KW
        )
        assert self._fingerprint(base) == self._fingerprint(comp)
        assert self._fingerprint(base) == self._fingerprint(dense)

    def test_corpus_entry_replays_trace(self):
        rep = explore.run(_raft_wl(), RAFT_CFG, RAFT_PLAN, **self.KW)
        assert rep.corpus, "campaign admitted nothing"
        # one generation-0 entry and one bred entry, if present
        picks = [rep.corpus[0]]
        bred = [e for e in rep.corpus if e.generation > 0]
        if bred:
            picks.append(bred[-1])
        for e in picks:
            r = explore.replay_entry(
                _raft_wl(), RAFT_CFG, e, history_invariant=_elect_inv,
                max_steps=800,
            )
            assert int(r.traces[0]) == e.trace

    def test_different_root_differs(self):
        a = explore.run(_raft_wl(), RAFT_CFG, RAFT_PLAN, **self.KW)
        kw = dict(self.KW)
        kw["root_seed"] = 12
        b = explore.run(_raft_wl(), RAFT_CFG, RAFT_PLAN, **kw)
        assert self._fingerprint(a) != self._fingerprint(b)


@pytest.mark.slow
class TestCampaignFindsViolations:
    def test_kvchaos_mutant_found_and_replayed(self):
        """The lost-write mutant is found by a tiny campaign, the
        violating entry replays to its stored trace, and the stored
        plan feeds shrink_plan."""
        wl = make_kvchaos(writes=6, record=True, bug=True, chaos=False)
        cfg = EngineConfig(pool_size=160, loss_p=0.05)
        plan = FaultPlan((
            CrashStorm(targets=(1, 2, 3, 4), n=2,
                       down_min_ns=50_000_000, down_max_ns=250_000_000),
        ), name="kv-explore-test")
        box = {}

        def hinv(h):
            box["ok"] = stale_reads(h) & read_your_writes(h)
            return box["ok"]

        rep = explore.run(
            wl, cfg, plan, history_invariant=hinv, generations=3,
            batch=48, root_seed=3, max_steps=3000, cov_words=16,
        )
        assert rep.violations, "mutant not caught by the campaign"
        e = rep.violations[0]
        r = explore.replay_entry(
            wl, cfg, e, history_invariant=hinv, max_steps=3000
        )
        assert int(r.traces[0]) == e.trace
        assert not bool(r.ok[0]), "replay must reproduce the violation"
        from madsim_tpu.chaos import shrink_plan

        res = shrink_plan(
            wl, cfg, e.seed, e.plan, history_invariant=hinv,
            max_steps=3000,
        )
        assert len(res.events) >= 1
        assert res.trace != 0
