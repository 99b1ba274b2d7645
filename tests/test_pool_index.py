"""Readiness-partitioned event pool (ISSUE 13): identity + knob pins.

The tile index is a pure LOWERING: pop via per-tile minima + the one
winning tile, free-slot search via per-tile free counts, summaries
carried as derived-by-construction columns. Everything observable —
traces, pools, histories, latency sketches, overflow counts — must be
bit-identical with the index on or off, across both write lowerings
(element stores / within-tile select chains), under time32, under
chaos + client-army plans, and through checkpoint save/restore (where
the summaries are REBUILT, never read from the file). The knob tests
pin the documented resolution rules (pool_index auto thresholds, the
placement default) so a silent default change fails here, not in a
bench artifact.
"""

import dataclasses

import numpy as np
import pytest

import jax

from madsim_tpu.chaos import CrashStorm, FaultPlan, GrayFailure
from madsim_tpu.engine import (
    Build,
    POOL_INDEX_STATE_FIELDS,
    EngineConfig,
    LatencySpec,
    Workload,
    build_pool_index,
    load_checkpoint,
    make_init,
    make_run,
    make_run_compacted,
    make_run_while,
    pool_tile,
    save_checkpoint,
)
from madsim_tpu.engine.core import _POOL_INDEX_MIN_POOL, make_step
from madsim_tpu.models import make_raft, make_raftlog
from madsim_tpu.models import raftlog as rl_mod

CFG = EngineConfig(pool_size=64, loss_p=0.02, clog_backoff_max_ns=2_000_000_000)
SEEDS = np.arange(48, dtype=np.uint64)
N_STEPS = 260

# raftlog + army + chaos: extended kinds, client rows, history records
# and latency markers all flow through the indexed pop and placement
_ARMY_PLAN = FaultPlan((
    rl_mod.client_army(n_ops=10, t_min_ns=5_000_000, t_max_ns=400_000_000),
    CrashStorm(targets=tuple(range(5)), n=1, t_min_ns=50_000_000,
               t_max_ns=200_000_000, down_min_ns=20_000_000,
               down_max_ns=80_000_000),
    GrayFailure(targets=tuple(range(5)), n_links=1, mult_min=4, mult_max=8,
                t_min_ns=30_000_000, t_max_ns=150_000_000,
                dur_min_ns=50_000_000, dur_max_ns=150_000_000),
))
_LAT = LatencySpec(ops=10, phases=3)


def _fields(st, skip=POOL_INDEX_STATE_FIELDS):
    return {
        f.name: np.asarray(getattr(st, f.name))
        for f in dataclasses.fields(st)
        if f.name not in skip
    }


def _assert_state_equal(a, b, what=""):
    fa, fb = _fields(a), _fields(b)
    for name in fa:
        assert fa[name].shape == fb[name].shape, (what, name)
        assert np.array_equal(fa[name], fb[name]), (
            f"{what}: field {name!r} diverged between indexed and flat"
        )


def _assert_summaries_consistent(st, cfg):
    """The carried summaries equal a from-scratch rebuild (tile_min
    compared only on nonempty tiles — empty minima are stale by
    contract, the invalid-slot rule)."""
    tm, tc = build_pool_index(st.ev_time, st.ev_valid, pool_tile(cfg.pool_size))
    tc, tm = np.asarray(tc), np.asarray(tm)
    assert np.array_equal(tc, np.asarray(st.tile_cnt))
    mask = tc > 0
    assert np.array_equal(tm[mask], np.asarray(st.tile_min)[mask])


def _run_pair(wl, cfg, n_steps, seeds, plan=None, lat=None):
    rows = plan.compile_batch(seeds, wl=wl) if plan is not None else None

    def one(pool_index, **lowering):
        b = Build(
            layout="scatter", pool_index=pool_index, latency=lat,
            plan_slots=plan.slots if plan is not None else 0, **lowering,
        )
        init = make_init(wl, cfg, b)
        st0 = init(seeds, rows) if rows is not None else init(seeds)
        return jax.block_until_ready(
            jax.jit(make_run(wl, cfg, n_steps, b))(st0)
        )

    return one


class TestIndexIdentity:
    def test_army_chaos_indexed_vs_flat_both_write_lowerings(self):
        wl = make_raftlog(record=True, army=True)
        cfg = EngineConfig(pool_size=96, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        one = _run_pair(wl, cfg, N_STEPS, SEEDS, plan=_ARMY_PLAN, lat=_LAT)
        flat = one(False)
        store = one(True, placement="scatter")
        chain = one(True, placement="rank")
        _assert_state_equal(flat, store, "element-store placement")
        _assert_state_equal(flat, chain, "within-tile select chains")
        _assert_summaries_consistent(store, cfg)
        _assert_summaries_consistent(chain, cfg)
        # the scenario actually completed client ops (the markers rode
        # the indexed placement, not a dead path)
        assert int(np.asarray(flat.lat_count).sum()) > 0

    def test_overflow_identity_under_pressure(self):
        # a pool too small for raft's traffic: drops must be counted
        # identically — the free-search rank math and flatnonzero agree
        # exactly at the boundary, not just in the spacious case
        wl = make_raft()
        cfg = EngineConfig(pool_size=16, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        one = _run_pair(wl, cfg, 200, SEEDS)
        flat, idx = one(False), one(True)
        assert int(np.asarray(flat.overflow).sum()) > 0
        _assert_state_equal(flat, idx, "overflow pressure")

    def test_time32_indexed_vs_flat(self):
        wl = make_raft()
        one = _run_pair(wl, CFG, 200, SEEDS)
        flat = one(False, time32=True)
        idx = one(True, time32=True)
        _assert_state_equal(flat, idx, "time32")
        _assert_summaries_consistent(idx, CFG)

    def test_run_while_and_compacted_indexed(self):
        wl = make_raft(record=True)
        cfg = EngineConfig(pool_size=40, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        init_f = make_init(wl, cfg, Build(pool_index=False))
        init_i = make_init(wl, cfg, Build(pool_index=True))
        ref = jax.block_until_ready(jax.jit(make_run_while(
            wl, cfg, 400, Build(layout="scatter", pool_index=False),
        ))(init_f(SEEDS)))
        got = jax.block_until_ready(jax.jit(make_run_while(
            wl, cfg, 400, Build(layout="scatter", pool_index=True),
        ))(init_i(SEEDS)))
        _assert_state_equal(ref, got, "run_while")
        out = make_run_compacted(
            wl, cfg, 400, Build(layout="scatter", pool_index=True), min_size=8,
        )(init_i(SEEDS))
        for name in ("now", "trace", "halted", "overflow", "node_state",
                     "hist_count", "hist_word"):
            assert np.array_equal(
                np.asarray(getattr(ref, name)), getattr(out, name)
            ), f"compacted {name} diverged"


class TestIndexEdgeCases:
    def test_time32_empty_tile_sentinel_decay(self):
        # regression (found in review): under time32 the per-step
        # rebase decays EVERY carried tile_min, including the +inf
        # sentinel of a long-empty tile; an insert burst spilling into
        # that tile after >2.1 sim-seconds used to fold min() against
        # the decayed sentinel, pinning the tile's minimum low and
        # silently popping the wrong event. The insert fold now masks
        # empty tiles back to the sentinel first.
        def handler(ctx):
            em = ctx.emits()
            count = ctx.state[0]
            em.after(100_000_000, 10, 0)  # 100 ms timer chain forever
            for j in range(9):  # at dispatch 25 (sim ~2.5 s), burst-
                # fill tile 0 so placement spills into the empty tile 1
                em.after(150_000_000 + j, 10, 0, when=count == 25)
            return ctx.state.at[0].set(count + 1), em.build()

        wl = Workload(name="sentinel-decay", n_nodes=1, state_width=1,
                      handlers=(handler,), max_emits=10,
                      delay_bound_ns=200_000_000)
        cfg = EngineConfig(pool_size=16, lat_min_ns=1_000_000,
                           lat_max_ns=2_000_000,
                           clog_backoff_max_ns=1_000_000_000)
        seeds = np.arange(4, dtype=np.uint64)
        outs = {}
        for pi in (False, True):
            st = make_init(wl, cfg, Build(time32=True, pool_index=pi))(seeds)
            outs[pi] = jax.block_until_ready(jax.jit(make_run(
                wl, cfg, 60,
                Build(layout="scatter", time32=True, pool_index=pi),
            ))(st))
        _assert_state_equal(outs[False], outs[True], "sentinel decay")

    def test_dense_step_over_indexed_state(self):
        # an indexed state under a dense run, built on purpose from two
        # Builds (an auto init indexes a big CPU pool, the dense run
        # has no index): the off-step must consume the state, match the
        # flat trajectory AND keep the carried summaries exact
        # (index-preserving rebuild), so a later indexed resume can
        # trust them
        wl = make_raft()
        cfg = EngineConfig(pool_size=2048, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        seeds = np.arange(8, dtype=np.uint64)
        st = make_init(wl, cfg)(seeds)  # auto: indexed (CPU, pool 2048)
        assert st.tile_cnt.shape[1] == 2048 // pool_tile(2048)
        dense_out = jax.block_until_ready(jax.jit(make_run(
            wl, cfg, 150, Build(layout="dense"),
        ))(st))
        flat_out = jax.block_until_ready(jax.jit(make_run(
            wl, cfg, 150, Build(layout="scatter", pool_index=False),
        ))(make_init(wl, cfg, Build(pool_index=False))(seeds)))
        _assert_state_equal(flat_out, dense_out, "dense over indexed state")
        _assert_summaries_consistent(dense_out, cfg)


class TestCheckpoint:
    def _run_some(self, wl, cfg, n, state, pool_index):
        return jax.block_until_ready(jax.jit(make_run(
            wl, cfg, n, Build(layout="scatter", pool_index=pool_index),
        ))(state))

    def test_roundtrip_rebuilds_summaries(self, tmp_path):
        wl = make_raft(record=True)
        cfg = EngineConfig(pool_size=40, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        init = make_init(wl, cfg, Build(pool_index=True))
        mid = self._run_some(wl, cfg, 150, init(SEEDS), True)
        p = str(tmp_path / "idx.npz")
        save_checkpoint(p, mid, cfg)
        # the file carries NO summary entries — they are not format
        with np.load(p) as data:
            for f in POOL_INDEX_STATE_FIELDS:
                assert f not in data.files
        back = load_checkpoint(p, wl, cfg, Build(pool_index=True))
        # rebuilt summaries equal a from-scratch build over the loaded
        # pool (count exactly; minima on nonempty tiles)
        tm, tc = build_pool_index(
            back.ev_time, back.ev_valid, pool_tile(cfg.pool_size)
        )
        assert np.array_equal(np.asarray(tc), np.asarray(back.tile_cnt))
        mask = np.asarray(tc) > 0
        assert np.array_equal(
            np.asarray(tm)[mask], np.asarray(back.tile_min)[mask]
        )
        # resuming from the restore equals the uninterrupted run
        full = self._run_some(wl, cfg, 300, init(SEEDS), True)
        resumed = self._run_some(wl, cfg, 150, back, True)
        _assert_state_equal(full, resumed, "checkpoint resume")

    def test_flat_checkpoint_loads_into_indexed_run(self, tmp_path):
        # "old checkpoints load unchanged": a state saved by an
        # index-off run (byte-identical to the pre-index format) feeds
        # an indexed resume, and the trajectory matches the flat one
        wl = make_raft()
        cfg = EngineConfig(pool_size=40, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        init = make_init(wl, cfg, Build(pool_index=False))
        mid = self._run_some(wl, cfg, 150, init(SEEDS), False)
        p = str(tmp_path / "flat.npz")
        save_checkpoint(p, mid, cfg)
        back = load_checkpoint(p, wl, cfg, Build(pool_index=True))
        assert back.tile_cnt.shape == (
            len(SEEDS), cfg.pool_size // pool_tile(cfg.pool_size)
        )
        resumed_idx = self._run_some(wl, cfg, 150, back, True)
        resumed_flat = self._run_some(
            wl, cfg, 150, load_checkpoint(p, wl, cfg, Build(pool_index=False)),
            False,
        )
        _assert_state_equal(resumed_flat, resumed_idx, "cross-format resume")


class TestKnobs:
    def test_pool_tile_divisors(self):
        assert pool_tile(2048) == 64
        assert pool_tile(8192) == 64
        assert pool_tile(96) == 32
        assert pool_tile(40) == 8
        assert pool_tile(72) == 8
        assert pool_tile(7) == 0  # no candidate divides it
        assert pool_tile(64) == 32  # needs >= 2 tiles

    def test_pool_index_auto_rule(self):
        wl = make_raft()

        def resolved(pool, **kw):
            cfg = EngineConfig(pool_size=pool)
            return Build(**kw).resolve(wl, cfg).pool_index

        # CPU backend (the test env): auto on only past the threshold
        assert not resolved(512)
        assert not resolved(_POOL_INDEX_MIN_POOL)
        assert resolved(2048)
        # dense layout never auto-engages, and explicit True rejects it
        assert not resolved(2048, layout="dense")
        with pytest.raises(ValueError, match="dense"):
            resolved(2048, layout="dense", pool_index=True)
        with pytest.raises(ValueError, match="no tile divisor"):
            resolved(2049, pool_index=True)

    def test_default_placement_under_index_is_store(self):
        # the measured CPU default: under the
        # index, placement writes default to element stores whatever
        # the pool size; without it, the PR-8 crossover rule holds
        cfg_small = EngineConfig(pool_size=64)
        wl = make_raft()
        # builds must succeed; the resolution itself is pinned via the
        # error path (placement="bogus" names the resolved set)
        make_step(wl, cfg_small, Build(layout="scatter", pool_index=True))
        with pytest.raises(ValueError, match="unknown placement"):
            make_step(
                wl, cfg_small, Build(layout="scatter", placement="bogus"),
            )

    def test_army_pool_sizing_is_tile_aligned(self):
        wl = make_raftlog(army=True)
        plan = FaultPlan((
            rl_mod.client_army(n_ops=1000),
            CrashStorm(targets=(0,), n=1),
        ))
        size = plan.min_pool_size(wl)
        assert size >= wl.n_nodes + plan.slots + 16
        assert size % 64 == 0 and pool_tile(size) == 64
        raw = plan.min_pool_size(wl, headroom=0, tile_align=False)
        assert raw == wl.n_nodes + plan.slots

    def test_mismatched_state_raises(self):
        wl = make_raft()
        cfg = EngineConfig(pool_size=40, loss_p=0.02,
                           clog_backoff_max_ns=2_000_000_000)
        st = make_init(wl, cfg, Build(pool_index=False))(SEEDS)
        step = make_step(wl, cfg, Build(layout="scatter", pool_index=True))
        with pytest.raises(TypeError, match="pool-index tiles"):
            jax.vmap(step)(st)


@pytest.mark.slow
class TestExploreDevicePin:
    """The explore-device campaign identity pin with the index on: the
    whole device-resident loop (mutation, sweep, admission) runs the
    indexed step and produces the bit-identical campaign."""

    def test_device_campaign_index_on_off(self):
        from madsim_tpu import explore
        from madsim_tpu.chaos import GrayFailure, PauseStorm

        nodes = (0, 1, 2, 3, 4)
        cfg = EngineConfig(pool_size=64, loss_p=0.02)
        plan = FaultPlan((
            PauseStorm(targets=nodes, n=1, t_min_ns=20_000_000,
                       t_max_ns=300_000_000, down_min_ns=50_000_000,
                       down_max_ns=200_000_000),
            GrayFailure(targets=nodes, n_links=1),
        ), name="pool-index-pin")

        def inv(view):
            return view["halted"]

        kw = dict(generations=2, batch=16, root_seed=7, max_steps=500,
                  cov_words=16, invariant=inv)
        off = explore.run_device(make_raft(), cfg, plan, pool_index=False, **kw)
        on = explore.run_device(make_raft(), cfg, plan, pool_index=True, **kw)

        def fp(rep):
            return (
                [(e.id, e.generation, e.parent, e.seed, e.plan.hash(),
                  e.trace, e.new_bits, e.violating) for e in rep.corpus],
                rep.cov_map.tolist(),
                [(e.seed, e.trace) for e in rep.violations],
                rep.curve,
            )

        assert fp(off) == fp(on)
