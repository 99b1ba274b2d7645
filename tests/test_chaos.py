"""madsim_tpu.chaos — nemesis fault plans, both execution modes.

Four layers under test: plan compilation (counter-based, per-seed
deterministic, vectorized), the new engine fault kinds (gray failure,
duplication, clock skew, one-way clog) and their identity defaults,
the search/shrink loop on the planted kvchaos lost-write bug (the
tier-1 smoke the evidence artifact scales up), and dual-mode parity —
the asyncio Nemesis plus the engine-vs-Recorder convergence check.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

import jax

import madsim_tpu as ms
from madsim_tpu.chaos import (
    ClockSkew,
    CrashStorm,
    Duplicate,
    FaultEvent,
    FaultPlan,
    GrayFailure,
    LiteralPlan,
    Nemesis,
    Partition,
    PauseStorm,
    shrink_plan,
)
from madsim_tpu.check import election_safety, read_your_writes, stale_reads
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    search_seeds,
    make_init,
    make_run_while,
)
from madsim_tpu.engine.core import (
    KIND_CLOG,
    KIND_CLOG_1W,
    KIND_DUP_OFF,
    KIND_DUP_ON,
    KIND_KILL,
    KIND_PAUSE,
    KIND_RESTART,
    KIND_RESUME,
    KIND_SKEW,
    KIND_SLOW_LINK,
    KIND_UNSLOW,
    pack_slow_arg,
)
from madsim_tpu.models import make_kvchaos, make_raft

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "examples"))

SEEDS64 = np.arange(64, dtype=np.uint64)


# ------------------------------------------------------------- compilation
class TestPlanCompilation:
    def test_deterministic_and_per_seed_distinct(self):
        plan = FaultPlan((
            CrashStorm(targets=(1, 2, 3), n=2),
            GrayFailure(targets=(0, 1, 2, 3)),
            ClockSkew(targets=(0, 1)),
        ))
        a = plan.compile_batch(SEEDS64)
        b = plan.compile_batch(SEEDS64)
        for f in ("time", "kind", "args", "valid"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        # distinct seeds draw distinct trajectories (overwhelmingly)
        assert len({tuple(map(tuple, a.args[s])) for s in range(64)}) > 32
        # and compile(seed) agrees with the batch row
        evs = plan.compile(7)
        assert [e.t for e in evs] == [int(t) for t, v in
                                      zip(a.time[7], a.valid[7]) if v]

    def test_windows_and_targets_respected(self):
        storm = CrashStorm(
            targets=(2, 5), n=3, t_min_ns=10, t_max_ns=20,
            down_min_ns=100, down_max_ns=200,
        )
        plan = FaultPlan((storm,))
        rows = plan.compile_batch(SEEDS64)
        kills = rows.kind == KIND_KILL
        assert (rows.time[kills] >= 10).all() and (rows.time[kills] < 20).all()
        assert np.isin(rows.args[..., 0][kills], (2, 5)).all()
        restarts = rows.kind == KIND_RESTART
        assert (rows.time[restarts] >= 110).all()
        assert (rows.time[restarts] < 220).all()

    def test_pause_storm_kinds(self):
        rows = FaultPlan((PauseStorm(targets=(0,), n=1),)).compile_batch(
            SEEDS64[:4]
        )
        assert set(rows.kind[rows.valid].tolist()) == {KIND_PAUSE, KIND_RESUME}

    def test_partition_edges_cross_the_cut(self):
        part = Partition(targets=(0, 1, 2, 3, 4))
        rows = FaultPlan((part,)).compile_batch(SEEDS64)
        for s in range(16):
            clogs = [
                (int(rows.args[s, j, 0]), int(rows.args[s, j, 1]))
                for j in range(rows.kind.shape[1])
                if rows.valid[s, j] and rows.kind[s, j] == KIND_CLOG
            ]
            assert clogs, "a nonempty proper cut always has edges"
            # the clogged edges must 2-color the nodes they touch
            side = {}
            for a, b in clogs:
                side.setdefault(a, 0)
                side[b] = 1 - side[a]
            for a, b in clogs:
                assert side[a] != side[b], (s, clogs)

    def test_asymmetric_partition_is_one_way(self):
        part = Partition(targets=(0, 1, 2), asymmetric=True)
        rows = FaultPlan((part,)).compile_batch(SEEDS64)
        assert (rows.kind[rows.valid] != KIND_CLOG).all()
        assert KIND_CLOG_1W in rows.kind[rows.valid]

    def test_plan_threefry_matches_engine_generator(self):
        # chaos/plan.py carries an array-form copy of the cipher; plan
        # draws never enter the trace hash, so textual drift from the
        # engine's generator would otherwise be silent — pin them equal
        from madsim_tpu.chaos.plan import _vthreefry
        from madsim_tpu.engine import np_threefry2x32

        rng = np.random.default_rng(0)
        cases = rng.integers(0, 2**32, size=(64, 4), dtype=np.uint64)
        for k0, k1, x0, x1 in cases:
            a0, a1 = np_threefry2x32(
                np.uint32(k0), np.uint32(k1), np.uint32(x0), np.uint32(x1)
            )
            b0, b1 = _vthreefry(
                np.uint32(k0), np.uint32(k1), np.uint32(x0), np.uint32(x1)
            )
            assert (int(a0), int(a1)) == (int(b0), int(b1))
        # and the vectorized path equals the scalar loop
        v0, v1 = _vthreefry(
            cases[:, 0].astype(np.uint32), cases[:, 1].astype(np.uint32),
            cases[:, 2].astype(np.uint32), cases[:, 3].astype(np.uint32),
        )
        for i, (k0, k1, x0, x1) in enumerate(cases):
            a0, a1 = np_threefry2x32(
                np.uint32(k0), np.uint32(k1), np.uint32(x0), np.uint32(x1)
            )
            assert (int(v0[i]), int(v1[i])) == (int(a0), int(a1))

    def test_window_span_must_fit_uint32(self):
        with pytest.raises(ValueError, match="does not fit uint32"):
            CrashStorm(targets=(1,), t_min_ns=0, t_max_ns=5_000_000_000)

    def test_plan_hash_covers_specs(self):
        p1 = FaultPlan((CrashStorm(targets=(1,), n=1),))
        p2 = FaultPlan((CrashStorm(targets=(1,), n=2),))
        assert p1.hash() != p2.hash()
        assert p1.hash() == FaultPlan((CrashStorm(targets=(1,), n=1),)).hash()

    def test_target_validation_against_workload(self):
        wl = make_raft()
        with pytest.raises(ValueError, match="targets node 9"):
            FaultPlan((CrashStorm(targets=(9,)),)).compile_batch(
                SEEDS64[:2], wl=wl
            )

    def test_literal_plan_mask(self):
        lp = LiteralPlan(
            events=(
                FaultEvent(10, KIND_KILL, 1),
                FaultEvent(20, KIND_RESTART, 1),
            ),
            enabled=(False, True),
        )
        assert [e.kind for e in lp.compile(0)] == [KIND_RESTART]
        rows = lp.compile_batch(SEEDS64[:3])
        assert rows.valid.tolist() == [[False, True]] * 3


# ------------------------------------------------------- engine fault kinds
@pytest.fixture(scope="module")
def kv_plain():
    return make_kvchaos(writes=5, chaos=False)


@pytest.fixture(scope="module")
def kv_cfg():
    return EngineConfig(pool_size=96, loss_p=0.02)


class TestEngineFaultKinds:
    def test_gray_failure_slows_completion(self, kv_plain, kv_cfg):
        seeds = SEEDS64
        init = make_init(kv_plain, kv_cfg)
        run = jax.jit(make_run_while(kv_plain, kv_cfg, 4000))
        base = run(init(seeds))
        gray = FaultPlan((GrayFailure(
            targets=(0, 1, 2, 3, 4, 5), n_links=6,
            t_min_ns=1_000_000, t_max_ns=5_000_000,
            dur_min_ns=400_000_000, dur_max_ns=500_000_000,
            mult_min=32, mult_max=64,
        ),))
        init_g = make_init(kv_plain, kv_cfg, Build(plan_slots=gray.slots))
        slowed = run(init_g(seeds, gray.compile_batch(seeds, wl=kv_plain)))
        assert np.asarray(base.halted).all()
        assert np.asarray(slowed.halted).all()
        assert (
            np.median(np.asarray(slowed.halt_time))
            > 2 * np.median(np.asarray(base.halt_time))
        )

    def test_duplication_multiplies_traffic_and_identity_when_off(
        self, kv_plain, kv_cfg
    ):
        seeds = SEEDS64
        init = make_init(kv_plain, kv_cfg)
        run_d = jax.jit(make_run_while(
            kv_plain, kv_cfg, 4000, Build(dup_rows=True),
        ))
        run = jax.jit(make_run_while(kv_plain, kv_cfg, 4000))
        base = run(init(seeds))
        # dup_rows compiled but no plan: values bit-identical
        same = run_d(init(seeds))
        assert np.array_equal(np.asarray(same.trace), np.asarray(base.trace))
        assert np.array_equal(
            np.asarray(same.node_state), np.asarray(base.node_state)
        )
        dupp = FaultPlan((Duplicate(
            t_min_ns=0, t_max_ns=1,
            dur_min_ns=500_000_000, dur_max_ns=600_000_000,
        ),))
        init_d = make_init(kv_plain, kv_cfg, Build(plan_slots=dupp.slots))
        dup = run_d(init_d(seeds, dupp.compile_batch(seeds, wl=kv_plain)))
        assert np.asarray(dup.halted).all()
        assert (
            int(np.asarray(dup.msg_count).sum())
            > 2 * int(np.asarray(base.msg_count).sum())
        )

    def test_clock_skew_is_observed_by_handlers(self):
        import jax.numpy as jnp

        from madsim_tpu.engine import Workload, user_kind

        def on_init(ctx):
            eb = ctx.emits()
            eb.after(10_000_000, user_kind(1), 0)
            return ctx.state, eb.build()

        def on_probe(ctx):
            # store the observed clock in ms
            new = ctx.state.at[0].set(
                (ctx.now // jnp.int64(1_000_000)).astype(jnp.int32)
            )
            eb = ctx.emits()
            eb.halt()
            return new, eb.build()

        wl = Workload(
            name="skew-probe", n_nodes=1, state_width=1,
            handlers=(on_init, on_probe), max_emits=2,
            delay_bound_ns=20_000_000,
        )
        cfg = EngineConfig(pool_size=8)
        seeds = np.arange(8, dtype=np.uint64)
        skew = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_SKEW, a0=0, a1=500_000_000),
        ))
        run = jax.jit(make_run_while(wl, cfg, 50))
        plain = run(make_init(wl, cfg)(seeds))
        init_s = make_init(wl, cfg, Build(plan_slots=1))
        skewed = run(init_s(seeds, skew.compile_batch(seeds)))
        d = np.asarray(skewed.node_state)[:, 0, 0] - np.asarray(
            plain.node_state
        )[:, 0, 0]
        assert (d == 500).all()
        # skew shifts the handler's VIEW only: the true-time halt clock
        # moves by at most the per-step poll-cost noise the extra plan
        # event introduces (shifted step coordinates), never by the
        # half-second the handlers observed
        dt = np.abs(
            np.asarray(skewed.halt_time) - np.asarray(plain.halt_time)
        )
        assert (dt < 10_000).all()

    def test_one_way_clog_sets_one_direction(self, kv_plain, kv_cfg):
        seeds = SEEDS64[:4]
        lp = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_CLOG_1W, a0=2, a1=3),
        ))
        init_1 = make_init(kv_plain, kv_cfg, Build(plan_slots=1))
        run = jax.jit(make_run_while(kv_plain, kv_cfg, 200))
        out = run(init_1(seeds, lp.compile_batch(seeds)))
        clog = np.asarray(out.clog)
        assert clog[:, 2, 3].all() and not clog[:, 3, 2].any()

    def test_slow_link_args_roundtrip(self):
        packed = pack_slow_arg(3, 17)
        assert (packed & 0xFF) - 1 == 3 and packed >> 8 == 17
        assert (pack_slow_arg(-1, 9) & 0xFF) == 0

    def test_pool_too_small_for_plan_rows(self, kv_plain):
        cfg = EngineConfig(pool_size=8)
        with pytest.raises(ValueError, match="fault-plan rows"):
            make_init(kv_plain, cfg, Build(plan_slots=6))


# -------------------------------------------- search + shrink (planted bug)
@pytest.fixture(scope="module")
def kv_bug():
    return make_kvchaos(writes=5, record=True, bug=True, chaos=False)


@pytest.fixture(scope="module")
def nemesis_plan():
    return FaultPlan((
        CrashStorm(
            targets=(1, 2, 3, 4), n=2,
            t_min_ns=20_000_000, t_max_ns=400_000_000,
            down_min_ns=50_000_000, down_max_ns=300_000_000,
        ),
    ), name="kv-nemesis")


def _kv_hinv(box):
    def inv(h):
        box["ok"] = stale_reads(h) & read_your_writes(h)
        return box["ok"]

    return inv


class TestSearchAndShrink:
    def test_nemesis_search_finds_planted_bug_and_shrinks(
        self, kv_bug, kv_cfg, nemesis_plan
    ):
        """The tier-1 smoke of the whole loop: a plan-driven sweep digs
        out the kvchaos lost-write mutant, ddmin shrinks the fault
        schedule to <= 4 events, and the shrunk (seed, config, plan)
        replays to the identical violation and trace hash."""
        box = {}
        rep = search_seeds(
            kv_bug, kv_cfg, None, n_seeds=256, max_steps=3000,
            history_invariant=_kv_hinv(box), plan=nemesis_plan,
        )
        assert rep.failing_seeds.size > 0, "nemesis must trigger the bug"
        assert rep.overflowed_seeds.size == 0
        assert rep.plan_hash == nemesis_plan.hash()
        assert f"plan_hash={nemesis_plan.hash()}" in rep.banner()

        # some seeds genuinely need the whole storm; at least one of the
        # first few must shrink strictly below the full plan
        results = [
            shrink_plan(
                kv_bug, kv_cfg, int(s), nemesis_plan,
                history_invariant=_kv_hinv({}), max_steps=3000,
            )
            for s in rep.failing_seeds[:3]
        ]
        assert all(len(r.events) <= 4 for r in results)
        res = min(results, key=lambda r: len(r.events))
        assert len(res.events) < res.original_events
        bad = res.seed

        # exact replay: same violating seed, same trace hash
        box2 = {}
        rep2 = search_seeds(
            kv_bug, kv_cfg, None, n_seeds=1, max_steps=3000,
            seed_base=bad, history_invariant=_kv_hinv(box2), plan=res.plan,
        )
        assert rep2.failing_seeds.tolist() == [bad]
        assert int(rep2.traces[0]) == res.trace

    def test_clean_model_is_clean_under_the_same_plan(
        self, kv_cfg, nemesis_plan
    ):
        clean = make_kvchaos(writes=5, record=True, chaos=False)
        box = {}
        rep = search_seeds(
            clean, kv_cfg, None, n_seeds=256, max_steps=3000,
            history_invariant=_kv_hinv(box), plan=nemesis_plan,
        )
        assert rep.failing_seeds.size == 0
        assert rep.unhalted_seeds.size == 0

    def test_shrink_rejects_non_failing_seed(self, kv_bug, kv_cfg, nemesis_plan):
        box = {}
        rep = search_seeds(
            kv_bug, kv_cfg, None, n_seeds=64, max_steps=3000,
            history_invariant=_kv_hinv(box), plan=nemesis_plan,
        )
        passing = sorted(set(range(64)) - set(rep.failing_seeds.tolist()))
        with pytest.raises(ValueError, match="does not fail"):
            shrink_plan(
                kv_bug, kv_cfg, passing[0], nemesis_plan,
                history_invariant=_kv_hinv({}), max_steps=3000,
            )


# ----------------------------------------------------- asyncio mode parity
class TestNemesisAsyncio:
    def test_nemesis_applies_plan_events(self):
        plan = LiteralPlan(events=(
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=1),
            FaultEvent(t=150_000_000, kind=KIND_RESTART, a0=1),
            FaultEvent(t=10_000_000, kind=KIND_SKEW, a0=0, a1=250_000_000),
            FaultEvent(t=20_000_000, kind=KIND_SLOW_LINK, a0=0,
                       a1=pack_slow_arg(1, 8)),
            FaultEvent(t=30_000_000, kind=KIND_DUP_ON),
            FaultEvent(t=170_000_000, kind=KIND_DUP_OFF),
        ))
        rt = ms.Runtime(seed=7)
        n0 = rt.create_node().name("n0").build()
        n1 = rt.create_node().name("n1").build()

        async def main():
            from madsim_tpu.net.netsim import NetSim
            from madsim_tpu.runtime.time_ import SystemTime

            h = ms.Handle.current()
            nem = Nemesis(plan, nodes=[n0, n1])
            wall = []

            async def probe():
                base = h.time.base_unix_ns
                for _ in range(3):
                    await ms.sleep(0.06)
                    wall.append(SystemTime.now().unix_ns - base - ms.now_ns())

            p = n0.spawn(probe())
            applied = await nem.run()
            await p
            netsim = h.simulator(NetSim)
            return applied, wall, netsim

        rt.set_time_limit(2.0)
        applied, wall, netsim = rt.block_on(main())
        # events applied in time order, at their plan times
        times = [t for t, _ in applied]
        assert times == sorted(times)
        assert [e.kind for _, e in applied] == [
            KIND_SKEW, KIND_SLOW_LINK, KIND_DUP_ON, KIND_KILL,
            KIND_RESTART, KIND_DUP_OFF,
        ]
        # skew visible to the node's wall clock
        assert wall == [250_000_000] * 3
        # slow link installed both directions, dup flag back off
        assert netsim.network.slow_mult(n0.id, n1.id) == 8
        assert netsim.network.slow_mult(n1.id, n0.id) == 8
        assert netsim._duplicate is False

    def test_default_mapping_targets_created_nodes(self):
        # plan node i defaults to the i-th CREATED node (ids from 1;
        # id 0 is the unkillable main supervisor node)
        plan = LiteralPlan(events=(
            FaultEvent(t=1_000_000, kind=KIND_KILL, a0=0),
        ))
        rt = ms.Runtime(seed=2)
        n0 = rt.create_node().name("victim").build()

        async def main():
            # the pre-kill NodeInfo: _retire marks it killed and swaps
            # in a fresh incarnation under the same id
            info = ms.Handle.current().executor.nodes[n0.id]
            await Nemesis(plan).run()
            return info

        info = rt.block_on(main())
        assert info.killed

    def test_default_mapping_rejects_out_of_range_target(self):
        plan = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_KILL, a0=3),
        ))
        rt = ms.Runtime(seed=2)
        rt.create_node().build()

        async def main():
            await Nemesis(plan).run()

        with pytest.raises(ValueError, match="nodes="):
            rt.block_on(main())

    def test_nemesis_same_trajectory_as_engine_compile(self):
        plan = FaultPlan((CrashStorm(targets=(0, 1), n=2),))
        rt = ms.Runtime(seed=11)
        rt.create_node().build()
        rt.create_node().build()

        async def main():
            nem = Nemesis(plan, nodes=[1, 2])
            return nem.events()

        events = rt.block_on(main())
        # the asyncio nemesis drives EXACTLY the events the batched
        # engine would pre-seed for the same seed (dual-mode parity)
        assert events == sorted(plan.compile(11), key=lambda e: e.t)

    def test_node_wide_slow_overwrites_like_the_engine(self):
        # engine parity: node-wide slow/unslow OVERWRITES every link
        # touching the node — a node-wide heal also wipes an earlier
        # link-specific multiplier (the (N,N) matrix semantics)
        rt = ms.Runtime(seed=1)
        a = rt.create_node().build()
        b = rt.create_node().build()

        async def main():
            from madsim_tpu.net.netsim import NetSim

            net = ms.Handle.current().simulator(NetSim)
            net.slow_link(a, b, 4)
            net.slow_node(a, 8)
            assert net.network.slow_mult(a.id, b.id) == 8
            net.slow_node(a, 1)
            assert net.network.slow_mult(a.id, b.id) == 1

        rt.block_on(main())

    def test_duplication_duplicates_datagrams(self):
        rt = ms.Runtime(seed=3)
        a = rt.create_node().name("a").ip("10.0.0.1").build()
        b = rt.create_node().name("b").ip("10.0.0.2").build()

        async def main():
            from madsim_tpu.net import Endpoint
            from madsim_tpu.net.netsim import NetSim

            h = ms.Handle.current()
            got = []

            async def server():
                ep = await Endpoint.bind("0.0.0.0:700")
                while True:
                    msg, _ = await ep.recv_from(1)
                    got.append(msg)

            async def client():
                ep = await Endpoint.bind("0.0.0.0:0")
                h.simulator(NetSim).set_duplicate(True)
                await ep.send_to("10.0.0.2:700", 1, "x")
                await ms.sleep(0.5)
                h.simulator(NetSim).set_duplicate(False)
                await ep.send_to("10.0.0.2:700", 1, "y")
                await ms.sleep(0.5)

            b.spawn(server())
            await a.spawn(client())
            return got

        rt.set_time_limit(5.0)
        got = rt.block_on(main())
        assert got.count("x") == 2 and got.count("y") == 1


# ---------------------------------------- dual-mode convergence (satellite)
class TestDualModeConvergence:
    def test_raft_verdicts_converge_across_modes(self, monkeypatch):
        """The same raft protocol, one seed, both execution modes: the
        batched engine's recorded election history and the asyncio
        runtime's Recorder history must produce identical
        election-safety verdicts."""
        import raft_kv
        from madsim_tpu.check import Recorder
        from madsim_tpu.models.raft import OP_ELECT

        seeds = [1, 2, 3]  # consecutive: the engine sweep runs seed_base..+n

        # engine mode: recorded wins through search_seeds
        box = {}

        def inv(h):
            box["ok"] = election_safety(h, elect_op=OP_ELECT)
            return box["ok"]

        search_seeds(
            make_raft(record=True), EngineConfig(pool_size=48, loss_p=0.02),
            None, n_seeds=len(seeds), seed_base=seeds[0], max_steps=600,
            history_invariant=inv,
        )
        # seeds are consecutive from seeds[0]; pick our three
        engine_verdicts = [bool(box["ok"][s - seeds[0]]) for s in seeds]

        # asyncio mode: the raft_kv example cluster with a Recorder spy
        # on election wins
        async def no_save(self):
            return None

        async def no_load(self):
            return None

        monkeypatch.setattr(raft_kv.RaftPeer, "save", no_save)
        monkeypatch.setattr(raft_kv.RaftPeer, "load", no_load)
        orig_note = raft_kv.ClusterMonitor.note_leader
        asyncio_verdicts = []
        for seed in seeds:
            rec = Recorder()

            def spy(self, term, who, rec=rec):
                rec.event(client=who, op=OP_ELECT, key=term, arg=who)
                orig_note(self, term, who)

            monkeypatch.setattr(raft_kv.ClusterMonitor, "note_leader", spy)
            monitor = raft_kv.ClusterMonitor()

            async def main():
                h = ms.Handle.current()
                raft_kv.spawn_cluster(h, monitor)
                await ms.sleep(2.0)

            cfg = ms.Config()
            cfg.net.packet_loss_rate = 0.02
            ms.Runtime(seed=seed, config=cfg).block_on(main())
            assert len(rec) > 0, "the cluster must elect at least once"
            asyncio_verdicts.append(
                bool(election_safety(rec.to_batch(), elect_op=OP_ELECT)[0])
            )

        assert engine_verdicts == asyncio_verdicts == [True] * len(seeds)
