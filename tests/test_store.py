"""Storage fault injection (madsim_tpu disk chaos).

Five layers under test: the engine's two-phase sync discipline
(synced-data survival, unsynced loss, sync-lie windows, torn-write
kills — and the identity contracts: discipline-off verbatim semantics
and always-synced ≡ verbatim bit-identical across layouts/compact),
the chaos ``DiskFault`` spec + fault-window validation (the
CrashStorm-after-halt satellite), the C++-oracle guard for
extended-kind plans, ``FsSim`` power-failure semantics with the
FsSim↔engine convergence check, and the ``recovery_safety`` detector
plus the raftlog storage certificates (soak-scale pieces marked slow —
``tools/store_soak.py`` is the evidence artifact).
"""

import warnings

import numpy as np
import pytest

import jax

import madsim_tpu as ms
from madsim_tpu import fs
from madsim_tpu.chaos import (
    CrashStorm,
    DiskFault,
    FaultEvent,
    FaultPlan,
    FlappingPartition,
    LiteralPlan,
    Nemesis,
)
from madsim_tpu.check import BatchHistory, election_safety, recovery_safety
from madsim_tpu.engine import (
    Build,
    EngineConfig,
    Workload,
    make_init,
    make_run_while,
    search_seeds,
    user_kind,
)
from madsim_tpu.engine.core import (
    KIND_KILL,
    KIND_RESTART,
    KIND_SYNC_LOSS,
    KIND_SYNC_OK,
    KIND_TORN_OFF,
    KIND_TORN_ON,
    MET_SYNC,
    MET_SYNC_LOST,
    MET_TORN,
)
from madsim_tpu.check.history import OK_OK
from madsim_tpu.models import make_raftlog
from madsim_tpu.models.raftlog import (
    OP_COMMIT,
    OP_ELECT,
    OP_RECOVER,
    OP_SYNCED,
)

SEEDS = np.arange(64, dtype=np.uint64)
CFG = EngineConfig(pool_size=16)

WRITE_VALS = (11, 22, 33)


def make_probe(sync_call: bool, durable_sync: bool = True) -> Workload:
    """One node, durable cols (1,2,3): handler 1 writes (11,22,33) in a
    single multi-column dispatch at ~10 ms and optionally fsyncs —
    the minimal surface every discipline rule is visible on."""

    def on_init(ctx):
        eb = ctx.emits()
        eb.after(10_000_000, user_kind(1), 0, when=(ctx.now == 0))
        return ctx.state, eb.build()

    def on_write(ctx):
        new = ctx.state
        for j, v in enumerate(WRITE_VALS):
            new = new.at[1 + j].set(v)
        eb = ctx.emits()
        if sync_call:
            eb.sync()
        return new, eb.build()

    return Workload(
        name=f"store-probe-{int(sync_call)}-{int(durable_sync)}",
        n_nodes=1,
        state_width=4,
        handlers=(on_init, on_write),
        max_emits=2,
        delay_bound_ns=300_000_000,
        durable_cols=(1, 2, 3),
        durable_sync=durable_sync,
    )


KILL = LiteralPlan(events=(FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),))


def run_probe(wl, plan, layout=None, metrics=False, seeds=SEEDS):
    init = make_init(wl, CFG, Build(plan_slots=plan.slots, metrics=metrics))
    run = jax.jit(make_run_while(
        wl, CFG, 60, Build(layout=layout, metrics=metrics),
    ))
    return jax.block_until_ready(
        run(init(seeds, plan.compile_batch(seeds)))
    )


def durable_rows(out):
    return np.asarray(out.node_state)[:, 0, 1:]


# ------------------------------------------------- engine sync discipline
class TestSyncDiscipline:
    def test_synced_write_survives_kill(self):
        out = run_probe(make_probe(sync_call=True), KILL, metrics=True)
        assert (durable_rows(out) == WRITE_VALS).all()
        met = np.asarray(out.met)
        assert (met[:, MET_SYNC] == 1).all()
        assert (met[:, MET_SYNC_LOST] == 0).all()

    def test_unsynced_write_lost_on_kill(self):
        out = run_probe(make_probe(sync_call=False), KILL)
        assert (durable_rows(out) == 0).all(), (
            "an unsynced durable write must not survive a kill"
        )
        # the synced disk image is what the node would recover with
        assert (np.asarray(out.disk)[:, 0, 1:] == 0).all()

    def test_sync_loss_window_makes_sync_lie(self):
        lie = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_SYNC_LOSS, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        out = run_probe(make_probe(sync_call=True), lie, metrics=True)
        assert (durable_rows(out) == 0).all(), "a lying sync must commit nothing"
        met = np.asarray(out.met)
        assert (met[:, MET_SYNC_LOST] == 1).all()
        assert (met[:, MET_SYNC] == 0).all()
        # a closed window commits again: SYNC_OK before the write
        heal = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_SYNC_LOSS, a0=0),
            FaultEvent(t=5_000_000, kind=KIND_SYNC_OK, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        out2 = run_probe(make_probe(sync_call=True), heal)
        assert (durable_rows(out2) == WRITE_VALS).all()

    def test_torn_kill_keeps_prefix_of_last_write(self):
        torn = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_TORN_ON, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        out = run_probe(make_probe(sync_call=False), torn, metrics=True)
        rows = durable_rows(out)
        allowed = {(0, 0, 0)} | {
            WRITE_VALS[: k + 1] + (0,) * (2 - k) for k in range(3)
        }
        got = {tuple(int(x) for x in r) for r in rows}
        assert got <= allowed, f"non-prefix survivors: {got - allowed}"
        # the threefry prefix draw varies over 64 seeds: the tear is a
        # distribution, not a constant
        assert len(got) >= 2
        assert (np.asarray(out.met)[:, MET_TORN] == 1).all()
        # a closed torn window is a clean loss again
        off = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_TORN_ON, a0=0),
            FaultEvent(t=5_000_000, kind=KIND_TORN_OFF, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        assert (durable_rows(run_probe(make_probe(False), off)) == 0).all()

    def test_torn_never_tears_synced_state(self):
        """A tear only loses *uncommitted* bytes: with the write synced
        in its own dispatch, an armed torn kill changes nothing."""
        torn = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_TORN_ON, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        out = run_probe(make_probe(sync_call=True), torn)
        assert (durable_rows(out) == WRITE_VALS).all()

    def test_discipline_off_keeps_verbatim_semantics(self):
        out = run_probe(make_probe(sync_call=False, durable_sync=False), KILL)
        assert (durable_rows(out) == WRITE_VALS).all()
        # discipline off = zero-size columns (the cov_words rule)
        assert np.asarray(out.disk).shape[1] == 0
        assert np.asarray(out.sync_loss).shape[1] == 0

    def test_always_synced_equals_verbatim_bit_identical(self):
        """The oracle-compatibility contract: sync-every-write under the
        discipline is trajectory-identical to verbatim-durable, across
        layouts — disk-faults-off runs pin to current traces."""
        restart = LiteralPlan(events=(
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
            FaultEvent(t=120_000_000, kind=KIND_RESTART, a0=0),
        ))
        ref = run_probe(
            make_probe(sync_call=False, durable_sync=False), restart,
            layout="scatter",
        )
        for layout in ("scatter", "dense"):
            got = run_probe(make_probe(sync_call=True), restart, layout=layout)
            assert np.array_equal(np.asarray(got.trace), np.asarray(ref.trace))
            assert np.array_equal(
                np.asarray(got.node_state), np.asarray(ref.node_state)
            )

    def test_sync_flag_ignored_without_discipline(self):
        # calling eb.sync() on a discipline-off workload is a no-op,
        # not an error — models can share handlers across modes
        out = run_probe(make_probe(sync_call=True, durable_sync=False), KILL)
        assert (durable_rows(out) == WRITE_VALS).all()

    def test_durable_sync_requires_durable_cols(self):
        with pytest.raises(ValueError, match="durable_sync"):
            Workload(
                name="bad", n_nodes=1, state_width=2,
                handlers=(lambda ctx: (ctx.state, ctx.emits().build()),),
                durable_sync=True,
            )


# ------------------------------------------------------- DiskFault spec
class TestDiskFaultSpec:
    def test_compile_deterministic_windows_and_targets(self):
        spec = DiskFault(
            targets=(1, 3), n_torn=2, n_sync_loss=1,
            t_min_ns=10_000, t_max_ns=20_000,
            dur_min_ns=100_000, dur_max_ns=200_000,
        )
        plan = FaultPlan((spec,))
        assert plan.slots == 6
        a = plan.compile_batch(SEEDS)
        b = plan.compile_batch(SEEDS)
        for f in ("time", "kind", "args", "valid"):
            assert np.array_equal(getattr(a, f), getattr(b, f)), f
        on = np.isin(a.kind, (KIND_TORN_ON, KIND_SYNC_LOSS))
        off = np.isin(a.kind, (KIND_TORN_OFF, KIND_SYNC_OK))
        assert (a.time[on] >= 10_000).all() and (a.time[on] < 20_000).all()
        assert (a.time[off] >= 110_000).all() and (a.time[off] < 220_000).all()
        assert np.isin(a.args[..., 0], (1, 3)).all()
        # torn windows first, sync-loss after (the spec-offset rule)
        assert a.kind[0, :4].tolist() == [
            KIND_TORN_ON, KIND_TORN_OFF, KIND_TORN_ON, KIND_TORN_OFF
        ]
        assert a.kind[0, 4:].tolist() == [KIND_SYNC_LOSS, KIND_SYNC_OK]

    def test_slot_templates_match_slots(self):
        spec = DiskFault(targets=(0, 1), n_torn=1, n_sync_loss=2)
        assert len(spec.slot_templates()) == spec.slots
        # mutators retarget by node: the template carries the target set
        assert all(t.targets == (0, 1) for t in spec.slot_templates())

    def test_validation(self):
        with pytest.raises(ValueError, match="at least one target"):
            DiskFault(targets=())
        with pytest.raises(ValueError, match="at least one torn"):
            DiskFault(targets=(0,), n_torn=0, n_sync_loss=0)
        with pytest.raises(ValueError, match="does not fit uint32"):
            DiskFault(targets=(0,), t_min_ns=0, t_max_ns=5_000_000_000)

    def test_kind_names(self):
        from madsim_tpu.chaos import kind_name

        assert kind_name(KIND_SYNC_LOSS) == "sync-loss"
        assert kind_name(KIND_TORN_ON) == "torn-on"
        ev = FaultEvent(t=1_000_000, kind=KIND_TORN_ON, a0=2)
        assert "torn-on n2" in str(ev)

    def test_disk_faults_are_noops_without_discipline(self):
        """The identity-defaults rule: DiskFault windows on a workload
        without the sync discipline change nothing but the dispatched
        chaos events themselves."""
        wl = make_probe(sync_call=False, durable_sync=False)
        plan = FaultPlan((DiskFault(
            targets=(0,), n_torn=1, n_sync_loss=1,
            t_min_ns=1_000, t_max_ns=2_000,
            dur_min_ns=1_000_000, dur_max_ns=2_000_000,
        ),))
        init = make_init(wl, CFG, Build(plan_slots=plan.slots))
        run = jax.jit(make_run_while(wl, CFG, 60))
        out = run(init(SEEDS, plan.compile_batch(SEEDS)))
        assert (durable_rows(out) == WRITE_VALS).all()
        assert np.asarray(out.torn).shape[1] == 0


# ------------------------------- fault-window validation (satellite fix)
class TestWindowValidation:
    def test_late_window_warns(self):
        plan = FaultPlan((CrashStorm(
            targets=(1,), t_min_ns=500_000_000, t_max_ns=600_000_000,
        ),))
        with pytest.warns(UserWarning, match="cannot fire"):
            late = plan.validate_windows(100_000_000)
        assert len(late) == 1
        assert plan.validate_windows(700_000_000, warn=False) == []

    def test_search_seeds_warns_under_time_limit(self):
        wl = make_probe(sync_call=True)
        cfg = EngineConfig(pool_size=16, time_limit_ns=100_000_000)
        plan = FaultPlan((CrashStorm(
            targets=(0,), t_min_ns=200_000_000, t_max_ns=300_000_000,
        ),))
        with pytest.warns(UserWarning, match="cannot fire"):
            search_seeds(
                wl, cfg, lambda v: np.ones(8, bool), n_seeds=8,
                max_steps=60, plan=plan, require_halt=False,
            )

    def test_clamped_windows_fit_the_limit(self):
        plan = FaultPlan((
            CrashStorm(targets=(1,), t_min_ns=200_000_000,
                       t_max_ns=400_000_000),
            DiskFault(targets=(1,), t_min_ns=500_000_000,
                      t_max_ns=900_000_000),
        ))
        clamped = plan.clamped(100_000_000)
        assert clamped.validate_windows(100_000_000, warn=False) == []
        assert clamped.hash() != plan.hash()  # windows are spec identity
        rows = clamped.compile_batch(SEEDS[:8])
        on = np.isin(
            rows.kind, (KIND_KILL, KIND_TORN_ON, KIND_SYNC_LOSS)
        )
        assert (rows.time[on] < 100_000_000).all()


# ------------------------------------------------- oracle guard (satellite)
class TestOracleGuard:
    def test_extended_kind_plan_refused(self):
        from madsim_tpu.engine.oracle import run_oracle

        wl = make_raftlog(durable=True)
        plan = FaultPlan((DiskFault(targets=(0, 1)),))
        with pytest.raises(ValueError, match="two-run/two-layout"):
            run_oracle(wl, CFG, 0, 100, plan=plan)
        lit = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_SYNC_LOSS, a0=0),
        ))
        with pytest.raises(ValueError, match="extended chaos kinds \\[251\\]"):
            run_oracle(wl, CFG, 0, 100, plan=lit)

    def test_base_kind_plan_also_refused(self):
        # the oracle has no plan channel at all: even base-kind plans
        # must error, not silently compare faulted vs unfaulted runs
        from madsim_tpu.engine.oracle import run_oracle

        wl = make_raftlog(durable=True)
        plan = FaultPlan((CrashStorm(targets=(1,)),))
        with pytest.raises(ValueError, match="no fault plan"):
            run_oracle(wl, CFG, 0, 100, plan=plan)


# ----------------------------------------------- FsSim power-fail semantics
def _fs_run(seed, coro_fn, time_limit=60.0):
    rt = ms.Runtime(seed=seed)
    rt.set_time_limit(time_limit)
    return rt.block_on(coro_fn())


class TestFsPowerFail:
    def test_synced_survives_unsynced_lost(self):
        async def main():
            h = ms.Handle.current()
            node = h.create_node().ip("10.0.0.1").build()
            done = ms.SimFuture()
            result = ms.SimFuture()

            async def writer():
                f = await fs.File.create("/wal")
                await f.write_all_at(b"AAAA", 0)
                await f.sync_all()
                await f.write_all_at(b"BBBB", 4)
                done.set_result(None)
                await ms.sleep(100.0)

            node.spawn(writer())
            await done
            h.kill(node)

            async def reader():
                result.set_result(await fs.read("/wal"))

            node.spawn(reader())
            return await result

        assert _fs_run(3, main) == b"AAAA"

    def test_torn_power_fail_keeps_prefix(self):
        outcomes = set()
        for seed in range(8):
            async def main():
                h = ms.Handle.current()
                node = h.create_node().ip("10.0.0.1").build()
                done = ms.SimFuture()
                result = ms.SimFuture()

                async def writer():
                    f = await fs.File.create("/wal")
                    await f.write_all_at(b"AAAA", 0)
                    await f.sync_all()
                    await f.write_all_at(b"BBBB", 4)
                    done.set_result(None)
                    await ms.sleep(100.0)

                node.spawn(writer())
                await done
                fs.FsSim.current().set_torn(node.id)
                h.kill(node)

                async def reader():
                    result.set_result(await fs.read("/wal"))

                node.spawn(reader())
                return await result

            data = _fs_run(seed, main)
            # always the synced bytes plus a PREFIX of the torn write
            assert data[:4] == b"AAAA"
            assert b"BBBB"[: len(data) - 4] == data[4:]
            outcomes.add(data)
        assert len(outcomes) >= 2, f"tear never varied: {outcomes}"

    def test_second_power_fail_keeps_torn_fragment(self):
        """A torn fragment that reached the platter IS on-disk state: a
        second power failure (no intervening sync) must not roll it
        back — the engine commits the prefix into SimState.disk at the
        kill, and FsSim must agree (dual-mode parity)."""
        async def main():
            h = ms.Handle.current()
            node = h.create_node().ip("10.0.0.1").build()
            done = ms.SimFuture()
            result = ms.SimFuture()

            async def writer():
                f = await fs.File.create("/wal")
                await f.write_all_at(b"AAAA", 0)
                await f.sync_all()
                await f.write_all_at(b"BBBB", 4)
                done.set_result(None)
                await ms.sleep(100.0)

            node.spawn(writer())
            await done
            sim = fs.FsSim.current()
            sim.set_torn(node.id)
            h.kill(node)
            after_first = bytes(sim._nodes[node.id]["/wal"].data)
            h.restart(node)
            await ms.sleep(0.05)
            h.kill(node)  # second failure, nothing new written or synced

            async def reader():
                result.set_result(await fs.read("/wal"))

            node.spawn(reader())
            return after_first, await result

        first, second = _fs_run(11, main)
        assert second == first, (
            "a second power failure un-persisted the torn fragment"
        )

    def test_sync_loss_window_lies(self):
        async def main():
            h = ms.Handle.current()
            node = h.create_node().ip("10.0.0.1").build()
            done = ms.SimFuture()
            result = ms.SimFuture()

            async def writer():
                f = await fs.File.create("/wal")
                await f.write_all_at(b"AAAA", 0)
                await f.sync_all()  # honest: commits
                fs.FsSim.current().set_sync_loss(node.id)
                await f.write_all_at(b"BBBB", 4)
                await f.sync_all()  # lies: commits nothing
                done.set_result(None)
                await ms.sleep(100.0)

            node.spawn(writer())
            await done
            h.kill(node)

            async def reader():
                result.set_result(await fs.read("/wal"))

            node.spawn(reader())
            return await result

        assert _fs_run(5, main) == b"AAAA"

    def test_injected_write_errors(self):
        async def main():
            h = ms.Handle.current()
            node = h.create_node().ip("10.0.0.1").build()
            result = ms.SimFuture()

            async def writer():
                f = await fs.File.create("/wal")
                await f.write_all_at(b"ok", 0)
                fs.FsSim.current().set_fail_writes(node.id)
                try:
                    await f.write_all_at(b"boom", 2)
                    result.set_result("no-error")
                except OSError as e:
                    fs.FsSim.current().set_fail_writes(node.id, on=False)
                    await f.write_all_at(b"!!", 2)
                    result.set_result((e.errno, await fs.read("/wal")))

            node.spawn(writer())
            return await result

        errno, data = _fs_run(2, main)
        assert errno == 5 and data == b"ok!!"

    def test_nemesis_drives_disk_faults_into_fssim(self):
        plan = LiteralPlan(events=(
            FaultEvent(t=10_000_000, kind=KIND_SYNC_LOSS, a0=0),
            FaultEvent(t=20_000_000, kind=KIND_TORN_ON, a0=0),
            FaultEvent(t=30_000_000, kind=KIND_SYNC_OK, a0=0),
            FaultEvent(t=40_000_000, kind=KIND_TORN_OFF, a0=0),
        ))

        async def main():
            h = ms.Handle.current()
            node = h.create_node().ip("10.0.0.1").build()
            sim = h.simulator(fs.FsSim)
            nem = Nemesis(plan, nodes=[node])
            states = []

            async def probe():
                # sample BETWEEN the plan times (15/25/35/45 ms)
                await ms.sleep(0.015)
                for _ in range(4):
                    states.append((
                        node.id in sim._sync_loss, node.id in sim._torn
                    ))
                    await ms.sleep(0.01)

            p = node.spawn(probe())
            await nem.run()
            await p
            return states

        rt = ms.Runtime(seed=1)
        rt.set_time_limit(2.0)
        states = rt.block_on(main())
        assert states == [(True, False), (True, True), (False, True),
                          (False, False)]

    def test_nemesis_broadcast_target_hits_every_node(self):
        # the engine's a0=-1 means EVERY node (core.py 251-254); the
        # nemesis must broadcast too, not negative-index the last node
        plan = LiteralPlan(events=(
            FaultEvent(t=10_000_000, kind=KIND_SYNC_LOSS, a0=-1),
            FaultEvent(t=30_000_000, kind=KIND_SYNC_OK, a0=-1),
        ))

        async def main():
            h = ms.Handle.current()
            a = h.create_node().ip("10.0.0.1").build()
            b = h.create_node().ip("10.0.0.2").build()
            sim = h.simulator(fs.FsSim)
            mid = []

            async def probe():
                await ms.sleep(0.02)
                mid.append(set(sim._sync_loss))

            p = a.spawn(probe())
            await Nemesis(plan, nodes=[a, b]).run()
            await p
            return mid[0], set(sim._sync_loss), {a.id, b.id}

        rt = ms.Runtime(seed=4)
        rt.set_time_limit(2.0)
        mid, after, both = rt.block_on(main())
        assert mid == both, "a0=-1 must fault EVERY node's disk"
        assert after == set()

    def test_fssim_engine_convergence(self):
        """The dual-mode storage contract (the TestDualModeConvergence
        shape): the same three scenarios — synced write, unsynced
        write, torn unsynced write — produce the same recovered-state
        CLASSES in both execution modes: synced data survives, an
        unsynced write is lost, a torn write survives as a prefix."""
        # engine side, 64 seeds each
        synced = {
            tuple(map(int, r))
            for r in durable_rows(run_probe(make_probe(True), KILL))
        }
        unsynced = {
            tuple(map(int, r))
            for r in durable_rows(run_probe(make_probe(False), KILL))
        }
        torn_plan = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_TORN_ON, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        torn = {
            tuple(map(int, r))
            for r in durable_rows(run_probe(make_probe(False), torn_plan))
        }
        assert synced == {WRITE_VALS}
        assert unsynced == {(0, 0, 0)}
        prefixes = {WRITE_VALS[:k] + (0,) * (3 - k) for k in range(4)}
        assert torn <= prefixes and len(torn) >= 2

        # FsSim side: the byte-level twin of the same scenarios
        # (test_synced_survives_unsynced_lost and
        # test_torn_power_fail_keeps_prefix above assert the same three
        # classes: survive / lose / prefix) — here we assert the MODES
        # AGREE on the classification for the shared scenario set
        engine_classes = {
            "synced": synced == {WRITE_VALS},
            "unsynced": unsynced == {(0, 0, 0)},
            "torn-is-prefix": torn <= prefixes,
        }
        assert all(engine_classes.values()), engine_classes


# ------------------------------------------------- recovery_safety detector
def _bh(rows):
    """BatchHistory from [(op, key, arg, client, ok), ...] per seed."""
    s = len(rows)
    h = max((len(r) for r in rows), default=1)
    word = np.zeros((s, h, 5), np.int32)
    t = np.zeros((s, h), np.int64)
    count = np.zeros((s,), np.int32)
    for i, r in enumerate(rows):
        count[i] = len(r)
        for j, rec in enumerate(r):
            word[i, j] = rec
            t[i, j] = j
    return BatchHistory(word=word, t=t, count=count,
                        drop=np.zeros((s,), np.int32))


SY, RC = OP_SYNCED, OP_RECOVER


class TestRecoveryDetector:
    def test_clean_and_violating(self):
        h = _bh([
            # synced 2 then recovered 2: clean
            [(SY, 0, 2, 1, OK_OK), (RC, 0, 2, 1, OK_OK)],
            # synced 3, recovered 1: the durable state regressed
            [(SY, 0, 3, 1, OK_OK), (RC, 0, 1, 1, OK_OK)],
            # recovered MORE than synced (caught up another way): clean
            [(SY, 0, 1, 1, OK_OK), (RC, 0, 2, 1, OK_OK)],
        ])
        assert recovery_safety(h, SY, RC).tolist() == [True, False, True]

    def test_floor_is_last_sync_not_max(self):
        # a newer-term truncation legitimately shrinks the synced log:
        # sync 3, sync 2 (truncate), crash, recover 2 — clean
        h = _bh([[
            (SY, 0, 3, 1, OK_OK), (SY, 0, 2, 1, OK_OK), (RC, 0, 2, 1, OK_OK),
        ]])
        assert recovery_safety(h, SY, RC).tolist() == [True]

    def test_per_client_floors(self):
        # node 1's sync is not node 2's floor
        h = _bh([[
            (SY, 0, 5, 1, OK_OK), (RC, 0, 0, 2, OK_OK),
        ], [
            (SY, 0, 5, 1, OK_OK), (SY, 0, 1, 2, OK_OK),
            (RC, 0, 0, 1, OK_OK),
        ]])
        assert recovery_safety(h, SY, RC).tolist() == [True, False]

    def test_vacuous_histories(self):
        h = _bh([[], [(RC, 0, 0, 1, OK_OK)], [(SY, 0, 4, 1, OK_OK)]])
        assert recovery_safety(h, SY, RC).all()


# ------------------------------------------- raftlog storage certificates
RL_NODES = (0, 1, 2, 3, 4)
RL_CFG = EngineConfig(
    pool_size=128, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
)
STORE_PLAN = FaultPlan((
    CrashStorm(
        targets=RL_NODES, n=2, t_min_ns=150_000_000, t_max_ns=500_000_000,
        down_min_ns=100_000_000, down_max_ns=400_000_000,
    ),
    FlappingPartition(
        targets=RL_NODES, n_cycles=2, t_min_ns=50_000_000,
        t_max_ns=400_000_000, dur_min_ns=100_000_000,
        dur_max_ns=300_000_000, up_min_ns=20_000_000, up_max_ns=200_000_000,
    ),
    DiskFault(
        targets=RL_NODES, n_torn=2, t_min_ns=50_000_000,
        t_max_ns=500_000_000,
    ),
), name="store-hunt")


def _store_inv(box):
    def inv(h):
        box["commit"] = election_safety(h, elect_op=OP_COMMIT)
        box["elect"] = election_safety(h, elect_op=OP_ELECT)
        box["recover"] = recovery_safety(
            h, sync_op=OP_SYNCED, recover_op=OP_RECOVER
        )
        return box["commit"] & box["elect"] & box["recover"]

    return inv


class TestRaftlogStorage:
    def test_mutant_validation(self):
        with pytest.raises(ValueError, match="needs durable=True"):
            make_raftlog(bug="nosync")
        with pytest.raises(ValueError, match="unknown raftlog bug"):
            make_raftlog(durable=True, bug="fsync-maybe")
        assert make_raftlog(durable=True, bug="nosync").name == "raftlog-nosync"
        assert make_raftlog(durable=True).name == "raftlog"
        assert make_raftlog(durable=True).durable_sync
        assert not make_raftlog().durable_sync

    @pytest.mark.slow
    def test_correct_sync_placement_clean_under_disk_chaos(self):
        """Crash storms + flapping partitions + torn-write windows:
        fsync-before-reply placement shows zero committed-value loss,
        zero double votes and zero recovery-safety violations (the
        soak runs this at >= 2048 seeds — STORE_r10.txt)."""
        box = {}
        rep = search_seeds(
            make_raftlog(record=True, chaos=False, durable=True),
            RL_CFG, None, n_seeds=256, max_steps=6000,
            history_invariant=_store_inv(box), plan=STORE_PLAN,
            require_halt=False,
        )
        assert rep.failing_seeds.size == 0
        assert rep.overflowed_seeds.size == 0

    @pytest.mark.slow
    def test_missing_sync_mutant_caught(self):
        """The planted acked-before-durable mutant loses committed
        values under the SAME fault space (deterministic: the engine is
        bit-stable, so the uniform sweep's finds are pinned)."""
        box = {}
        rep = search_seeds(
            make_raftlog(record=True, chaos=False, durable=True,
                         bug="nosync"),
            RL_CFG, None, n_seeds=512, max_steps=6000,
            history_invariant=_store_inv(box), plan=STORE_PLAN,
            require_halt=False,
        )
        assert rep.failing_seeds.size > 0
        bad = ~box["commit"] & ~rep.overflowed
        assert bad.any(), "the mutant's signature is committed-value loss"

    @pytest.mark.slow
    def test_lying_disk_positive_control(self):
        """SYNC_LOSS windows on the CORRECT model: the recovery-safety
        detector must see the disk lie (proof the injection works and
        the detector is live)."""
        plan = FaultPlan((
            CrashStorm(
                targets=RL_NODES, n=2, t_min_ns=150_000_000,
                t_max_ns=500_000_000, down_min_ns=100_000_000,
                down_max_ns=400_000_000,
            ),
            DiskFault(
                targets=RL_NODES, n_torn=0, n_sync_loss=3,
                t_min_ns=10_000_000, t_max_ns=400_000_000,
                dur_min_ns=200_000_000, dur_max_ns=600_000_000,
            ),
        ), name="lying-disk")
        box = {}
        rep = search_seeds(
            make_raftlog(record=True, chaos=False, durable=True),
            RL_CFG, None, n_seeds=256, max_steps=6000,
            history_invariant=lambda h: recovery_safety(
                h, sync_op=OP_SYNCED, recover_op=OP_RECOVER
            ),
            plan=plan, require_halt=False,
        )
        assert rep.failing_seeds.size > 0

    def test_explain_narrates_disk_faults(self):
        """obs.explain names the disk-fault events and counts syncs —
        a torn-write repro reads end to end (the forensics satellite)."""
        from madsim_tpu import obs

        wl = make_probe(sync_call=True)
        plan = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_SYNC_LOSS, a0=0),
            FaultEvent(t=5_000_000, kind=KIND_SYNC_OK, a0=0),
            FaultEvent(t=8_000_000, kind=KIND_TORN_ON, a0=0),
            FaultEvent(t=50_000_000, kind=KIND_KILL, a0=0),
        ))
        text = obs.explain(wl, CFG, seed=7, plan=plan, max_steps=60)
        assert "SYNC_LOSS" in text and "TORN_ON" in text
        assert "sync-loss" in text  # the plan pretty-printer names too
        assert "sync=1" in text  # MET_SYNC in the counter row

    def test_perfetto_renders_disk_fault_spans(self):
        from madsim_tpu import obs

        wl = make_probe(sync_call=True)
        plan = LiteralPlan(events=(
            FaultEvent(t=1_000, kind=KIND_SYNC_LOSS, a0=0),
            FaultEvent(t=5_000_000, kind=KIND_SYNC_OK, a0=0),
            FaultEvent(t=8_000_000, kind=KIND_TORN_ON, a0=0),  # unclosed
        ))
        init = make_init(
            wl, CFG, Build(plan_slots=plan.slots, timeline_cap=64),
        )
        run = jax.jit(make_run_while(wl, CFG, 60, Build(timeline_cap=64)))
        out = run(init(SEEDS[:1], plan.compile_batch(SEEDS[:1])))
        doc = obs.to_perfetto(obs.decode_timeline(out, wl, 0), wl)
        chaos = {
            r["name"] for r in doc["traceEvents"]
            if r.get("cat") == "chaos"
        }
        assert "lying fsync n0" in chaos
        assert "torn writes n0" in chaos  # open window runs to the end
