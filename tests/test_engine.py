"""Tests for the batched JAX engine (madsim_tpu.engine).

The determinism invariants mirror the reference's test strategy
(SURVEY.md §4): same seed => identical trace, different seeds =>
different schedules, chaos semantics (kill drops in-flight events,
restart re-runs init, clog delays until unclog), plus the batched-core
specific invariants: batch result == per-seed results (vmap semantics),
jit == eager, and the jnp/numpy threefry mirrors agree bit-for-bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from madsim_tpu.engine import (
    Build,
    KIND_KILL,
    KIND_RESTART,
    KIND_CLOG,
    KIND_UNCLOG,
    EngineConfig,
    Workload,
    make_init,
    make_run,
    make_step,
    np_threefry2x32,
    threefry2x32,
    user_kind,
)
from madsim_tpu.models import (
    make_broadcast,
    make_microbench,
    make_pingpong,
    make_raft,
)


def run_workload(wl, cfg, seeds, n_steps):
    init = make_init(wl, cfg)
    run = jax.jit(make_run(wl, cfg, n_steps))
    return run(init(np.asarray(seeds, np.uint64)))


# ---------------------------------------------------------------------------
# RNG
# ---------------------------------------------------------------------------


class TestThreefry:
    def test_jnp_matches_numpy_mirror(self):
        rng = np.random.RandomState(0)
        for _ in range(50):
            k0, k1, x0, x1 = rng.randint(0, 2**32, size=4, dtype=np.uint32)
            ja, jb = threefry2x32(k0, k1, x0, x1)
            na, nb = np_threefry2x32(k0, k1, x0, x1)
            assert np.uint32(ja) == na
            assert np.uint32(jb) == nb

    def test_known_distinctness(self):
        # different counters / keys give different outputs
        a, _ = threefry2x32(1, 2, 3, 4)
        b, _ = threefry2x32(1, 2, 3, 5)
        c, _ = threefry2x32(1, 3, 3, 4)
        assert int(a) != int(b) != int(c)

    def test_vmaps(self):
        xs = jnp.arange(16, dtype=jnp.uint32)
        outs, _ = jax.vmap(lambda x: threefry2x32(1, 2, x, 0))(xs)
        assert len(set(np.asarray(outs).tolist())) == 16


# ---------------------------------------------------------------------------
# Determinism invariants (the analog of check_determinism,
# reference runtime/mod.rs:165-190)
# ---------------------------------------------------------------------------


class TestDeterminism:
    def test_same_seed_identical_trace(self):
        wl = make_pingpong(rounds=5)
        cfg = EngineConfig(pool_size=64)
        a = run_workload(wl, cfg, np.arange(8), 200)
        b = run_workload(wl, cfg, np.arange(8), 200)
        assert np.array_equal(np.asarray(a.trace), np.asarray(b.trace))
        assert np.array_equal(np.asarray(a.now), np.asarray(b.now))

    def test_different_seeds_different_schedules(self):
        wl = make_pingpong(rounds=5)
        cfg = EngineConfig(pool_size=64)
        out = run_workload(wl, cfg, np.arange(16), 200)
        traces = np.asarray(out.trace)
        assert len(set(traces.tolist())) == 16

    def test_batch_equals_single(self):
        # vmap semantics: each row of a batched run must equal its own
        # single-seed run — seeds are fully independent
        wl = make_pingpong(rounds=3)
        cfg = EngineConfig(pool_size=64)
        batched = run_workload(wl, cfg, np.arange(6), 150)
        for s in range(6):
            single = run_workload(wl, cfg, [s], 150)
            assert int(single.trace[0]) == int(batched.trace[s])
            assert int(single.now[0]) == int(batched.now[s])

    def test_jit_equals_eager(self):
        wl = make_microbench(rounds=50)
        cfg = EngineConfig(pool_size=16)
        init = make_init(wl, cfg)
        st = init(np.arange(4, dtype=np.uint64))
        run = make_run(wl, cfg, 60)
        eager = run(st)
        jitted = jax.jit(run)(st)
        assert np.array_equal(np.asarray(eager.trace), np.asarray(jitted.trace))

    def test_trace_depends_on_config(self):
        wl = make_pingpong(rounds=3)
        a = run_workload(wl, EngineConfig(pool_size=64), [7], 150)
        b = run_workload(
            wl, EngineConfig(pool_size=64, lat_min_ns=100, lat_max_ns=200), [7], 150
        )
        assert int(a.trace[0]) != int(b.trace[0])

    def test_config_hash_stable(self):
        assert EngineConfig().hash() == EngineConfig().hash()
        assert EngineConfig().hash() != EngineConfig(loss_p=0.1).hash()


# ---------------------------------------------------------------------------
# Chaos semantics
# ---------------------------------------------------------------------------


def _two_node_wl(script):
    """Tiny 2-node workload: node 0 runs `script` at init (an EmitBuilder
    program), node 1 counts on_init invocations and received pings."""

    def on_init(ctx):
        eb = ctx.emits()
        is0 = ctx.node == jnp.int32(0)
        script(eb, is0)
        new = jnp.where(
            ctx.node == jnp.int32(1), ctx.state.at[0].set(ctx.state[0] + 1), ctx.state
        )
        return new, eb.build()

    def on_ping(ctx):
        return ctx.state.at[1].set(ctx.state[1] + 1), ctx.emits().build()

    return Workload(
        name="twonode", n_nodes=2, state_width=4, handlers=(on_init, on_ping),
        max_emits=8,
    )


class TestChaos:
    def test_kill_drops_inflight_events(self):
        # ping sent at t=0 (1-10ms latency); node 1 killed at t=0.5ms =>
        # epoch bump drops the delivery (task.rs:255-276 semantics)
        def script(eb, is0):
            eb.send(1, user_kind(1), (), when=is0)
            eb.after(500_000, KIND_KILL, 0, (1,), when=is0)

        wl = _two_node_wl(script)
        cfg = EngineConfig(pool_size=32)
        out = run_workload(wl, cfg, np.arange(8), 50)
        assert not np.asarray(out.alive)[:, 1].any()
        assert (np.asarray(out.node_state)[:, 1, 1] == 0).all()

    def test_restart_reruns_init(self):
        # kill at 0.5ms, restart at 1s: node 1's init runs again on a
        # fresh state row (init-task respawn, task.rs:279-291)
        def script(eb, is0):
            eb.after(500_000, KIND_KILL, 0, (1,), when=is0)
            eb.after(1_000_000_000, KIND_RESTART, 0, (1,), when=is0)
            # ping after restart is delivered to the new incarnation
            eb.after(2_000_000_000, user_kind(1), 0, when=is0)

        def on_init(ctx):
            eb = ctx.emits()
            script(eb, ctx.node == jnp.int32(0))
            new = jnp.where(
                ctx.node == jnp.int32(1),
                ctx.state.at[0].set(ctx.state[0] + 1),
                ctx.state,
            )
            return new, eb.build()

        def on_send_ping(ctx):
            eb = ctx.emits()
            eb.send(1, user_kind(2), ())
            return ctx.state, eb.build()

        def on_ping(ctx):
            return ctx.state.at[1].set(ctx.state[1] + 1), ctx.emits().build()

        wl = Workload(
            name="restart", n_nodes=2, state_width=4,
            handlers=(on_init, on_send_ping, on_ping), max_emits=8,
        )
        cfg = EngineConfig(pool_size=32)
        out = run_workload(wl, cfg, np.arange(8), 100)
        ns = np.asarray(out.node_state)
        assert np.asarray(out.alive)[:, 1].all()
        # state was reset by restart: init counter is 1 again (fresh row,
        # then one on_init), and the post-restart ping arrived
        assert (ns[:, 1, 0] == 1).all()
        assert (ns[:, 1, 1] == 1).all()

    def test_clog_delays_delivery_until_unclog(self):
        # link clogged from t=0; ping sent at t=1ms; unclog at t=5s.
        # The delivery must happen after 5s (clogged messages wait and
        # retry with backoff — net/mod.rs:341-355), not be dropped.
        def script(eb, is0):
            eb.after(0, KIND_CLOG, 0, (0, 1), when=is0)
            eb.send(1, user_kind(1), (), when=is0)
            eb.after(5_000_000_000, KIND_UNCLOG, 0, (0, 1), when=is0)

        wl = _two_node_wl(script)
        cfg = EngineConfig(pool_size=32)
        # one jitted 200-step program (200 un-jitted vmapped dispatches
        # ran op-by-op and took ~80 s — 20% of the whole suite)
        st = run_workload(wl, cfg, np.arange(4), 200)
        ns = np.asarray(st.node_state)
        assert (ns[:, 1, 1] == 1).all(), "clogged message must eventually deliver"
        # and the clock is past the unclog time on every seed
        assert (np.asarray(st.now) >= 5_000_000_000).all()

    def test_loss_drops_messages(self):
        def script(eb, is0):
            for _ in range(6):
                eb.send(1, user_kind(1), (), when=is0)

        wl = _two_node_wl(script)
        out_l = run_workload(
            wl, EngineConfig(pool_size=64, loss_p=0.7), np.arange(64), 30
        )
        got = np.asarray(out_l.node_state)[:, 1, 1]
        assert got.mean() < 4.0, "70% loss should drop most of 6 pings"
        out_0 = run_workload(wl, EngineConfig(pool_size=64), np.arange(64), 30)
        assert (np.asarray(out_0.node_state)[:, 1, 1] == 6).all()

    def test_time_limit_halts(self):
        wl = make_microbench(rounds=10**6)
        cfg = EngineConfig(pool_size=16, time_limit_ns=1_000_000)
        out = run_workload(wl, cfg, np.arange(4), 5000)
        assert np.asarray(out.halted).all()
        assert (np.asarray(out.now) <= 1_100_000).all()

    def test_pool_overflow_counted(self):
        def script(eb, is0):
            for _ in range(8):
                eb.send(1, user_kind(1), (), when=is0)

        wl = _two_node_wl(script)
        cfg = EngineConfig(pool_size=4)  # 2 init events leave 2 free slots
        out = run_workload(wl, cfg, np.arange(4), 30)
        assert (np.asarray(out.overflow) > 0).all()


# ---------------------------------------------------------------------------
# Workloads
# ---------------------------------------------------------------------------


class TestWorkloads:
    def test_pingpong_completes_exact_counts(self):
        wl = make_pingpong(rounds=7)
        out = run_workload(wl, EngineConfig(pool_size=64), np.arange(16), 400)
        assert np.asarray(out.halted).all()
        ns = np.asarray(out.node_state)
        assert (ns[:, 0, 0] == 2).all()  # both clients reported done
        assert (ns[:, 0, 1] == 14).all()  # 2 clients x 7 pings served

    def test_raft_elects_exactly_one_leader(self):
        wl = make_raft()
        cfg = EngineConfig(pool_size=128, loss_p=0.05)
        out = run_workload(wl, cfg, np.arange(128), 500)
        h = np.asarray(out.halted)
        assert h.all(), "every seed should elect a leader within 500 events"
        leaders = (np.asarray(out.node_state)[:, :, 0] == 2).sum(axis=1)
        assert (leaders == 1).all()
        # election latency is at least one timeout (150ms) on every seed
        assert (np.asarray(out.halt_time) >= 150_000_000).all()

    def test_raft_election_times_vary_with_seed(self):
        wl = make_raft()
        out = run_workload(wl, EngineConfig(pool_size=128), np.arange(32), 500)
        times = np.asarray(out.halt_time)
        assert len(set(times.tolist())) > 16

    def test_broadcast_survives_loss_and_partition(self):
        wl = make_broadcast(rounds=3)
        cfg = EngineConfig(pool_size=128, loss_p=0.1)
        out = run_workload(wl, cfg, np.arange(32), 600)
        assert np.asarray(out.halted).all()
        ns = np.asarray(out.node_state)
        assert (ns[:, 1:, 0] == 3).all(), "every peer saw the last round"

    def test_microbench_exact_ticks(self):
        wl = make_microbench(rounds=123)
        out = run_workload(wl, EngineConfig(pool_size=8), np.arange(8), 130)
        assert np.asarray(out.halted).all()
        assert (np.asarray(out.node_state)[:, 0, 0] == 123).all()


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------


class TestStats:
    def test_msg_count_matches_pingpong_math(self):
        # per client: rounds pings + 1 done; server: 2*rounds pongs
        wl = make_pingpong(rounds=4)
        out = run_workload(wl, EngineConfig(pool_size=64), np.arange(8), 300)
        expect = 2 * (4 + 1) + 2 * 4
        assert (np.asarray(out.msg_count) == expect).all()

    def test_sim_seconds_property(self):
        wl = make_microbench(rounds=10)
        out = run_workload(wl, EngineConfig(pool_size=8), np.arange(4), 20)
        secs = np.asarray(out.sim_seconds)
        assert (secs > 0).all()


class TestRunWhileAndCheckpoint:
    def test_run_while_matches_scan_for_halting_workload(self):
        from madsim_tpu.engine import make_run_while

        wl = make_pingpong(rounds=4)
        cfg = EngineConfig(pool_size=64)
        init = make_init(wl, cfg)
        st = init(np.arange(8, dtype=np.uint64))
        scan_out = jax.jit(make_run(wl, cfg, 300))(st)
        while_out = jax.jit(make_run_while(wl, cfg, 300))(st)
        assert np.asarray(while_out.halted).all()
        # halted seeds are frozen, so both paths end in the same state
        assert np.array_equal(
            np.asarray(scan_out.trace), np.asarray(while_out.trace)
        )
        assert np.array_equal(np.asarray(scan_out.now), np.asarray(while_out.now))

    def test_checkpoint_roundtrip_resumes_identically(self, tmp_path):
        from madsim_tpu.engine import load_checkpoint, save_checkpoint

        wl = make_raft()
        cfg = EngineConfig(pool_size=128)
        init = make_init(wl, cfg)
        st = init(np.arange(8, dtype=np.uint64))
        run_half = jax.jit(make_run(wl, cfg, 100))
        mid = run_half(st)
        path = str(tmp_path / "ckpt.npz")
        save_checkpoint(path, mid, cfg)
        resumed = load_checkpoint(path, wl, cfg)
        a = run_half(mid)
        b = run_half(resumed)
        assert np.array_equal(np.asarray(a.trace), np.asarray(b.trace))
        assert np.array_equal(np.asarray(a.now), np.asarray(b.now))

    def test_checkpoint_rejects_other_config(self, tmp_path):
        from madsim_tpu.engine import load_checkpoint, save_checkpoint

        wl = make_microbench(rounds=5)
        cfg = EngineConfig(pool_size=8)
        st = make_init(wl, cfg)(np.arange(2, dtype=np.uint64))
        path = str(tmp_path / "c.npz")
        save_checkpoint(path, st, cfg)
        with pytest.raises(ValueError, match="different EngineConfig"):
            load_checkpoint(path, wl, EngineConfig(pool_size=8, loss_p=0.5))

    def test_checkpoint_rejects_time_representation_mismatch(self, tmp_path):
        """A checkpoint saved under one ev_time representation refuses a
        declared resume under the other (auto-resolution is platform-
        dependent, so this is the cross-platform resume hazard)."""
        from madsim_tpu.engine import load_checkpoint, save_checkpoint
        from madsim_tpu.models import BENCH_SPECS

        # a time32-eligible pair, so that both builds resolve
        factory, kw, _, _ = BENCH_SPECS["raft"]
        wl, cfg = factory(), EngineConfig(**kw)
        st = make_init(wl, cfg)(np.arange(2, dtype=np.uint64))
        path = str(tmp_path / "c.npz")
        save_checkpoint(path, st, cfg)
        saved32 = np.asarray(st.ev_time).dtype == np.int32
        # matching declaration loads fine; the opposite one is rejected
        load_checkpoint(path, wl, cfg, Build(time32=saved32))
        with pytest.raises(ValueError, match="ev_time dtype"):
            load_checkpoint(path, wl, cfg, Build(time32=not saved32))


class TestKvChaos:
    def test_kvchaos_durability_invariant_under_crash(self):
        """Config-5 shape: replicated KV with kill/restart chaos — every
        seed completes and the final committed write is durable on every
        replica at halt (re-sync after restart included)."""
        from madsim_tpu.engine import make_run_while
        from madsim_tpu.models import make_kvchaos

        wl = make_kvchaos(writes=10)
        cfg = EngineConfig(pool_size=160, loss_p=0.05)
        init = make_init(wl, cfg)
        out = jax.jit(make_run_while(wl, cfg, 8000))(
            init(np.arange(64, dtype=np.uint64))
        )
        h = np.asarray(out.halted)
        assert h.all()
        ns = np.asarray(out.node_state)
        assert (ns[:, -1, 0] == 10).all(), "client saw all commits"
        durable = (ns[:, 1:5, 0] >= 10).sum(axis=1)
        # RAM-only replicas: one crash can erase at most one post-ack
        # copy, so >= R-1 always; the rejoin/re-sync path makes full
        # durability the norm (exact on this fixed seed set)
        assert (durable >= 3).all(), "durability floor violated"
        assert (durable == 4).mean() >= 0.9
        assert (np.asarray(out.overflow) == 0).all()

    def test_kvchaos_deterministic(self):
        from madsim_tpu.engine import make_run_while
        from madsim_tpu.models import make_kvchaos

        wl = make_kvchaos(writes=5)
        cfg = EngineConfig(pool_size=160, loss_p=0.05)
        init = make_init(wl, cfg)
        run = jax.jit(make_run_while(wl, cfg, 4000))
        a = run(init(np.arange(8, dtype=np.uint64)))
        b = run(init(np.arange(8, dtype=np.uint64)))
        assert np.array_equal(np.asarray(a.trace), np.asarray(b.trace))


class TestPauseResume:
    def test_pause_holds_events_resume_releases(self):
        """Pause stashes a node's events; resume releases them — the
        batched form of Handle::pause/resume (task.rs:294-314)."""
        from madsim_tpu.engine import KIND_PAUSE, KIND_RESUME

        def script(eb, is0):
            eb.after(0, KIND_PAUSE, 0, (1,), when=is0)
            eb.send(1, user_kind(1), (), when=is0)
            eb.after(5_000_000_000, KIND_RESUME, 0, (1,), when=is0)

        wl = _two_node_wl(script)
        cfg = EngineConfig(pool_size=32)
        out = run_workload(wl, cfg, np.arange(8), 300)
        ns = np.asarray(out.node_state)
        # the ping eventually landed, but only after the 5s resume
        assert (ns[:, 1, 1] == 1).all()
        assert (np.asarray(out.now) >= 5_000_000_000).all()

    def test_kill_clears_pause(self):
        from madsim_tpu.engine import KIND_PAUSE

        def script(eb, is0):
            eb.after(0, KIND_PAUSE, 0, (1,), when=is0)
            eb.after(1_000_000, KIND_KILL, 0, (1,), when=is0)
            eb.after(2_000_000, KIND_RESTART, 0, (1,), when=is0)
            eb.send(1, user_kind(1), (), when=is0)
        # after restart the fresh node is unpaused: a later ping lands

        def on_init(ctx):
            eb = ctx.emits()
            script(eb, ctx.node == jnp.int32(0))
            eb.after(
                3_000_000_000, user_kind(2), 0,
                when=ctx.node == jnp.int32(0),
            )
            return ctx.state, eb.build()

        def on_ping(ctx):
            return ctx.state.at[1].set(ctx.state[1] + 1), ctx.emits().build()

        def on_late(ctx):
            eb = ctx.emits()
            eb.send(1, user_kind(1), ())
            return ctx.state, eb.build()

        wl = Workload(
            name="pausekill", n_nodes=2, state_width=4,
            handlers=(on_init, on_ping, on_late), max_emits=8,
        )
        out = run_workload(wl, EngineConfig(pool_size=32), np.arange(8), 100)
        ns = np.asarray(out.node_state)
        assert np.asarray(out.alive)[:, 1].all()
        assert (ns[:, 1, 1] == 1).all(), "post-restart ping delivered"


def test_zero_handler_workload_traces():
    # a chaos-only workload (no user handlers) must still compile: the
    # user lax.switch is skipped entirely
    wl = Workload(name="empty", n_nodes=2, state_width=2, handlers=())
    out = run_workload(wl, EngineConfig(pool_size=16), np.arange(4), 20)
    assert np.asarray(out.node_state).shape == (4, 2, 2)


def test_restart_restores_initial_rows():
    # restart resets the node to Workload.initial_state(), NOT zeros —
    # the oracle mirrors this via oracle_set_init_state
    init_rows = np.asarray([[7, 3], [9, 5]], np.int32)

    def on_init(ctx):
        eb = ctx.emits()
        # node 0 schedules: bump own state, then kill+restart node 1
        eb.after(1_000, user_kind(1), 1, when=ctx.node == jnp.int32(0))
        eb.after(5_000_000, 0, 0, (1,), when=ctx.node == jnp.int32(0))  # KIND_KILL
        eb.after(9_000_000, 1, 0, (1,), when=ctx.node == jnp.int32(0))  # KIND_RESTART
        return ctx.state, eb.build()

    def on_bump(ctx):
        return ctx.state.at[0].set(ctx.state[0] + 100), ctx.emits().build()

    wl = Workload(
        name="restart-init", n_nodes=2, state_width=2,
        handlers=(on_init, on_bump), max_emits=4, init_state=init_rows,
    )
    out = run_workload(wl, EngineConfig(pool_size=16), np.arange(4), 60)
    ns = np.asarray(out.node_state)
    # node 1 was bumped (7+100 -> wait: node 1 row is [9,5] -> 109),
    # then killed and restarted: back to its initial row [9, 5]
    assert (ns[:, 1, 0] == 9).all()
    assert (ns[:, 1, 1] == 5).all()
    # node 0 untouched: keeps its initial row
    assert (ns[:, 0, 0] == 7).all()


# raft (the flagship) gates every push; the other families' 4-variant
# crosses are compile-heavy and ride the full tier (`-m ""`), with the
# oracle bit-identical tests still covering each family by default
@pytest.mark.parametrize(
    "name",
    ["raft"]
    + [
        pytest.param(n, marks=pytest.mark.slow)
        for n in ["microbench", "pingpong", "broadcast", "kvchaos"]
    ],
)
def test_check_layouts_all_models(name):
    # the library form of the cross-backend check: dense and scatter
    # lowerings must agree (traces + state) for every benchmark workload.
    # Bench configs are time32-eligible, so this crosses the int32
    # offset representation with both layouts too (4 variants)
    from madsim_tpu.engine import EngineConfig, check_layouts, time32_eligible
    from madsim_tpu.models import BENCH_SPECS

    factory, cfg_kwargs, _seeds, _steps = BENCH_SPECS[name]
    wl, cfg = factory(), EngineConfig(**cfg_kwargs)
    assert time32_eligible(wl, cfg), "bench configs must allow int32 times"
    check_layouts(wl, cfg, np.arange(8), 150)


class TestTime32:
    def test_forced_time32_on_ineligible_config_raises(self):
        from madsim_tpu.engine import EngineConfig, make_step
        from madsim_tpu.models import make_raft

        # the default 10 s clog-backoff cap exceeds the int32 horizon
        wl, cfg = make_raft(), EngineConfig(pool_size=48)
        with pytest.raises(ValueError, match="not eligible"):
            make_step(wl, cfg, Build(time32=True))

    def test_undeclared_delay_bound_is_ineligible(self):
        from madsim_tpu.engine import EngineConfig, time32_eligible
        from madsim_tpu.models import make_raft

        wl = make_raft()
        wl = type(wl)(**{**wl.__dict__, "delay_bound_ns": None})
        assert not time32_eligible(
            wl, EngineConfig(clog_backoff_max_ns=10_000_000)
        )

    def test_delay_past_horizon_counts_as_overflow(self):
        # a handler lying about delay_bound_ns must be caught loudly:
        # the emitted timer is clamped and counted into `overflow`
        from madsim_tpu.engine import (
            EngineConfig,
            Workload,
            make_init,
            make_run,
            user_kind,
        )

        def on_init(ctx):
            eb = ctx.emits()
            eb.after(3_000_000_000, user_kind(0), ctx.node)  # 3 s > 2^31 ns
            return ctx.state, eb.build()

        wl = Workload(
            name="liar",
            n_nodes=1,
            state_width=1,
            handlers=(on_init,),
            max_emits=1,
            delay_bound_ns=1_000,  # the lie
        )
        cfg = EngineConfig(pool_size=4, clog_backoff_max_ns=10_000_000)
        out = jax.jit(make_run(wl, cfg, 3, Build(time32=True)))(
            make_init(wl, cfg, Build(time32=True))(
                np.arange(2, dtype=np.uint64))
        )
        assert int(np.asarray(out.overflow).sum()) >= 2

    def test_representation_mismatch_is_loud(self):
        # a state built under one time representation fed to a step
        # built under the other (the checkpoint save/resume hazard)
        # must raise at trace time, not silently misread offsets
        from madsim_tpu.engine import EngineConfig, make_init, make_run
        from madsim_tpu.models import BENCH_SPECS

        factory, kw, _, _ = BENCH_SPECS["raft"]
        wl, cfg = factory(), EngineConfig(**kw)
        state = make_init(wl, cfg, Build(time32=True))(
            np.arange(2, dtype=np.uint64))
        with pytest.raises(TypeError, match="time32"):
            jax.jit(make_run(wl, cfg, 3, Build(time32=False)))(state)


def test_twophase_atomicity_under_chaos():
    # 2PC invariants across seeded chaos schedules: every transaction
    # decided, the final decision applied by every participant, and the
    # commit tally bounded by txns
    from madsim_tpu.models import make_twophase

    wl = make_twophase(txns=5)
    cfg = EngineConfig(pool_size=48, loss_p=0.03)
    out = run_workload(wl, cfg, np.arange(256), 1400)
    ns = np.asarray(out.node_state)
    assert bool(np.asarray(out.halted).all()), "all schedules complete"
    assert int(np.asarray(out.overflow).sum()) == 0
    coord = ns[:, 0]
    assert ((coord[:, 4] + coord[:, 5]) == 5).all(), "every txn decided"
    assert (ns[:, 1:5, 2] == 5).all(), "final decision reached everyone"
    # atomicity: every participant's stored decision VALUE for the final
    # transaction matches the coordinator's (phase 1 = commit)
    coord_committed = (coord[:, 1] == 1).astype(np.int32)
    assert (ns[:, 1:5, 4] == coord_committed[:, None]).all(), (
        "a participant disagrees with the coordinator's final decision"
    )


def test_paxos_agreement_under_chaos():
    """Single-decree paxos safety across 1,024 chaos schedules: every
    seed decides (liveness within the cap), all deciders agree on ONE
    value (agreement), that value is some proposer's (validity), and a
    majority of acceptors hold it at halt (the choosing-quorum witness:
    once chosen, later ballots can only carry the chosen value)."""
    from madsim_tpu.engine import make_run_while
    from madsim_tpu.models import make_paxos
    from madsim_tpu.models.paxos import P_DEC, A_VAL

    a, p = 5, 3
    wl = make_paxos()
    cfg = EngineConfig(pool_size=64, loss_p=0.02)
    out = jax.jit(make_run_while(wl, cfg, 2000))(
        make_init(wl, cfg)(np.arange(1024, dtype=np.uint64))
    )
    h = np.asarray(out.halted)
    assert h.all(), "every schedule must decide within the cap"
    assert int(np.asarray(out.overflow).sum()) == 0
    ns = np.asarray(out.node_state)
    dec = ns[:, a:, P_DEC]
    acc_val = ns[:, :a, A_VAL]
    for s in range(ns.shape[0]):
        d = dec[s][dec[s] != 0]
        assert d.size > 0, f"seed {s}: halted without a decision"
        assert (d == d[0]).all(), f"seed {s}: agreement violated {dec[s]}"
        assert 1 <= d[0] <= p, f"seed {s}: invalid value {d[0]}"
        assert (acc_val[s] == d[0]).sum() >= a // 2 + 1, (
            f"seed {s}: no acceptor-majority witness for {d[0]}"
        )


def test_durable_cols_survive_restart():
    """Workload.durable_cols — the FsSim power-fail analog: RESTART
    restores the initial row for volatile columns only; durable
    columns keep their pre-kill values."""
    from madsim_tpu.engine import EmitBuilder  # noqa: F401 (doc import)
    from madsim_tpu.engine import Workload, make_run, user_kind

    def on_init(ctx):
        eb = ctx.emits()
        # first incarnation: write both columns, then kill+restart self
        first = ctx.state[0] == jnp.int32(0)
        new = ctx.state.at[0].set(7).at[1].set(9)
        eb.after(1_000_000, KIND_KILL, 0, (jnp.int32(0),), when=first)
        eb.after(2_000_000, KIND_RESTART, 0, (jnp.int32(0),), when=first)
        return jnp.where(first, new, ctx.state), eb.build()

    wl = Workload(
        name="durable-probe",
        n_nodes=1,
        state_width=2,
        handlers=(on_init,),
        max_emits=2,
        durable_cols=(0,),
    )
    out = jax.jit(make_run(wl, EngineConfig(pool_size=8), 10))(
        make_init(wl, EngineConfig(pool_size=8))(np.arange(4, dtype=np.uint64))
    )
    ns = np.asarray(out.node_state)
    # post-restart on_init sees state[0]==7 (durable, not 'first'), so
    # it writes nothing: col 0 kept 7, col 1 reset to the initial 0
    assert (ns[:, 0, 0] == 7).all(), "durable column lost on restart"
    assert (ns[:, 0, 1] == 0).all(), "volatile column survived restart"


def test_paxos_durable_acceptor_kills_stay_safe():
    """Classic paxos with stable acceptor storage: the chaos kill hits
    an ACCEPTOR, whose (promised, accepted) columns survive via
    durable_cols — agreement must still hold on every schedule."""
    from madsim_tpu.engine import make_run_while
    from madsim_tpu.models import make_paxos
    from madsim_tpu.models.paxos import A_VAL, P_DEC

    a, p = 5, 3
    wl = make_paxos(durable_acceptors=True)
    cfg = EngineConfig(pool_size=64, loss_p=0.02)
    out = jax.jit(make_run_while(wl, cfg, 2000))(
        make_init(wl, cfg)(np.arange(512, dtype=np.uint64))
    )
    assert np.asarray(out.halted).all()
    assert int(np.asarray(out.overflow).sum()) == 0
    ns = np.asarray(out.node_state)
    dec = ns[:, a:, P_DEC]
    for s in range(ns.shape[0]):
        d = dec[s][dec[s] != 0]
        assert d.size and (d == d[0]).all() and 1 <= d[0] <= p, s
        assert (ns[s, :a, A_VAL] == d[0]).sum() >= a // 2 + 1, s


class TestRaftLog:
    """Raft log replication: safety invariant + lowering equivalence."""

    def _final_states(self, n_seeds=1024, durable=False):
        from madsim_tpu.engine import EngineConfig, make_init, make_run_while
        from madsim_tpu.models import make_raftlog

        wl = make_raftlog(durable=durable)
        cfg = EngineConfig(
            pool_size=64, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
        )
        out = jax.jit(make_run_while(wl, cfg, 4000))(
            make_init(wl, cfg)(np.arange(n_seeds, dtype=np.uint64))
        )
        return jax.block_until_ready(out)

    def _assert_majority_prefix(self, out):
        from madsim_tpu.models.raftlog import COMMIT, LOG0, LOGLEN

        h = np.asarray(out.halted)
        ns = np.asarray(out.node_state)
        assert h.all(), "every seed must finish its writes"
        assert int(np.asarray(out.overflow).sum()) == 0
        W = 4
        for s in range(ns.shape[0]):
            rows = ns[s]
            committers = [i for i in range(5) if rows[i][COMMIT] == W]
            assert committers, f"seed {s}: halted without a full commit"
            # compare entry VALUES (low byte): a legal win-time re-stamp
            # can leave equal values under different term bytes on nodes
            # a delayed ack raced against a re-election
            ref = rows[committers[0]][LOG0:LOG0 + W] & 0xFF
            match = sum(
                1
                for i in range(5)
                if rows[i][LOGLEN] >= W
                and ((rows[i][LOG0:LOG0 + W] & 0xFF) == ref).all()
            )
            assert match >= 3, f"seed {s}: committed log on {match}/5 nodes"

    def test_committed_entries_on_majority(self):
        # the raft safety claim, checked across seeds, elections and the
        # seeded leader kill/restart: at halt, the committed log is
        # present in order with equal values on a majority of nodes
        self._assert_majority_prefix(self._final_states())

    def test_committed_entries_on_majority_durable(self):
        # crash-recovery raft: same invariant with the paper's persistent
        # state (term, votedFor, log) surviving the kill — the restart no
        # longer wipes the log, so safety must hold through genuine
        # recovery rather than reinstall-from-leader
        self._assert_majority_prefix(self._final_states(durable=True))

    @pytest.mark.slow
    def test_check_layouts_raftlog(self):
        from madsim_tpu.engine import EngineConfig, check_layouts, time32_eligible
        from madsim_tpu.models import make_raftlog

        wl = make_raftlog()
        cfg = EngineConfig(
            pool_size=64, loss_p=0.02, clog_backoff_max_ns=2_000_000_000
        )
        assert time32_eligible(wl, cfg)
        check_layouts(wl, cfg, np.arange(8), 500)


@pytest.mark.slow
def test_config_fuzz_layouts_agree():
    """Randomized configs — including overflow-inducing tiny pools,
    total packet loss, degenerate latency ranges and mid-run time
    limits — must keep every lowering combination (dense/scatter x
    int64/int32 when eligible) bit-identical. The drop rule under pool
    overflow is deterministic (rank-based), so even lossy runs agree."""
    from madsim_tpu.engine import EngineConfig, check_layouts
    from madsim_tpu.models import make_broadcast, make_raft

    rng = np.random.RandomState(20260730)
    for case in range(6):
        lat_min = int(rng.randint(1, 5_000_000))
        # case 0 pins the degenerate zero-span latency range (the
        # max(span, 1) clamp in core.py); later cases draw freely
        span = 0 if case == 0 else int(rng.randint(0, 10_000_000))
        cfg = EngineConfig(
            pool_size=int(rng.choice([8, 12, 40, 64])),
            lat_min_ns=lat_min,
            lat_max_ns=lat_min + span,
            loss_p=float(rng.choice([0.0, 0.05, 0.5, 1.0])),
            proc_min_ns=50,
            proc_max_ns=int(rng.choice([50, 100, 1000])),
            clog_backoff_max_ns=2_000_000_000,
            time_limit_ns=int(rng.choice([0, 200_000_000])),
        )
        wl = make_raft() if case % 2 == 0 else make_broadcast()
        check_layouts(wl, cfg, np.arange(6, dtype=np.uint64), 120)


# tier-1 budget (ROADMAP note): 4,096 seeds x 400 steps is this file's
# second-heaviest program; the snapshot model's engine values are
# oracle-pinned tier-1 (test_oracle: snapshot traces bit-identical) and
# the conservation sweep rides test-full / the soaks.
@pytest.mark.slow
def test_snapshot_conservation_under_reordering():
    """Lai-Yang snapshot invariant across 4,096 seeded schedules: the
    recorded cut (balances + channel state) sums EXACTLY to the minted
    total on every seed, despite transfers crossing the cut under
    random message reordering; all seeds terminate via the witness
    count, all nodes end red, and live balances re-conserve at halt."""
    from madsim_tpu.models import make_snapshot
    from madsim_tpu.models.snapshot import BAL, CHANIN, COLOR, RCNT, RECBAL

    n, b0, k = 5, 1000, 6
    wl = make_snapshot(n_nodes=n, balance=b0, n_sends=k)
    cfg = EngineConfig(pool_size=96)
    out = run_workload(wl, cfg, np.arange(4096), 400)
    assert bool(np.asarray(out.halted).all()), "every schedule terminates"
    assert int(np.asarray(out.overflow).sum()) == 0
    ns = np.asarray(out.node_state)
    assert (ns[:, :, COLOR] == 1).all(), "every node turned red"
    cut = ns[:, :, RECBAL].sum(1) + ns[:, :, CHANIN].sum(1)
    assert (cut == n * b0).all(), "consistent-cut conservation violated"
    assert (ns[:, :, BAL].sum(1) == n * b0).all(), "live conservation"
    assert (ns[:, 0, RCNT] == n * k + n * (n - 1)).all()
    # the cut is non-trivial: some schedules must actually capture
    # in-flight money in channel state
    assert (ns[:, :, CHANIN].sum(1) > 0).any()


def test_snapshot_layout_cross():
    from madsim_tpu.engine import check_layouts
    from madsim_tpu.models import make_snapshot

    check_layouts(make_snapshot(), EngineConfig(pool_size=96),
                  np.arange(8), 300)
