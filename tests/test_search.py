"""Chaos-schedule search (engine/search.py): batched invariant sweeps
with per-seed repro — the engine-scale multi-seed runner
(builder.rs:110-148 analog, BASELINE.md config 5)."""

import dataclasses

import numpy as np
import pytest

from madsim_tpu.chaos import CrashStorm, FaultPlan
from madsim_tpu.engine import Build, EngineConfig, make_init, search_seeds
from madsim_tpu.models import make_kvchaos, make_microbench, make_raft
from madsim_tpu.obs import prof


def test_healthy_workload_has_no_violations():
    wl = make_raft()
    cfg = EngineConfig(pool_size=48, loss_p=0.02)
    # invariant: some node won the election (role LEADER == 2)
    report = search_seeds(
        wl, cfg,
        invariant=lambda v: (v["node_state"][:, :, 0] == 2).any(axis=1),
        n_seeds=256, max_steps=600,
    )
    assert report.failing_seeds.size == 0
    assert report.unhalted_seeds.size == 0
    assert "0 violation(s)" in report.banner()


def test_search_finds_planted_violations_deterministically():
    wl = make_kvchaos(writes=5)
    cfg = EngineConfig(pool_size=48, loss_p=0.02)
    # a deliberately-too-strong invariant: every replica APPLIED at
    # least `writes` REPL messages. Replicas are RAM-only, so a chaos
    # kill wipes the victim's apply counter mid-stream and the re-sync
    # only replays the current write — the planted "bug" the search
    # must dig out (schedules whose kill lands early/never pass).
    def all_replicas_current(v):
        ns = v["node_state"]
        return (ns[:, 1:5, 1] >= 5).all(axis=1)

    r1 = search_seeds(wl, cfg, all_replicas_current, n_seeds=512, max_steps=900)
    r2 = search_seeds(wl, cfg, all_replicas_current, n_seeds=512, max_steps=900)
    # deterministic: the same seeds fail every run
    assert np.array_equal(r1.failing_seeds, r2.failing_seeds)
    assert r1.failing_seeds.size > 0, "chaos should break the too-strong invariant"
    assert r1.failing_seeds.size < 512, "most schedules still satisfy it"
    assert f"{r1.failing_seeds.size} violation(s)" in r1.banner()
    assert "config_hash=" + cfg.hash() in r1.banner()


def test_failing_seed_reproduces_in_isolation():
    wl = make_kvchaos(writes=5)
    cfg = EngineConfig(pool_size=48, loss_p=0.02)

    def all_replicas_current(v):
        return (v["node_state"][:, 1:5, 1] >= 5).all(axis=1)

    batch = search_seeds(wl, cfg, all_replicas_current, n_seeds=512, max_steps=900)
    bad = int(batch.failing_seeds[0])
    # rerun the one failing seed alone: same verdict, same trace hash
    solo = search_seeds(
        wl, cfg, all_replicas_current,
        n_seeds=1, max_steps=900, seed_base=bad,
    )
    assert solo.failing_seeds.tolist() == [bad]
    batch_trace = batch.traces[list(batch.seeds).index(bad)]
    assert int(solo.traces[0]) == int(batch_trace)


def test_invariant_shape_is_validated():
    # arg-validation only — the cheapest model body suffices (compiling
    # the raft search program here cost 6 s cold for a ValueError)
    wl = make_microbench(rounds=5)
    cfg = EngineConfig(pool_size=8)
    with pytest.raises(ValueError, match="boolean array"):
        search_seeds(wl, cfg, lambda v: np.bool_(True), n_seeds=8, max_steps=50)


def test_overflowed_seeds_are_flagged_not_reported():
    # a pool too small for the workload drops events: those seeds'
    # verdicts are simulator artifacts, so they're quarantined in
    # overflowed_seeds instead of reported as violations
    wl = make_raft()
    cfg = EngineConfig(pool_size=8, loss_p=0.02)
    report = search_seeds(
        wl, cfg,
        invariant=lambda v: (v["node_state"][:, :, 0] == 2).any(axis=1),
        n_seeds=64, max_steps=600,
    )
    assert report.overflowed_seeds.size > 0
    assert not (set(report.failing_seeds) & set(report.overflowed_seeds))
    assert "overflowed the event pool" in report.banner()


def test_search_reuses_compiled_run():
    from madsim_tpu.engine import search

    wl = make_raft()
    cfg = EngineConfig(pool_size=48, loss_p=0.02)
    inv = lambda v: (v["node_state"][:, :, 0] == 2).any(axis=1)  # noqa: E731
    before = len(search._RUN_CACHE)
    search_seeds(wl, cfg, inv, n_seeds=32, max_steps=200)
    search_seeds(wl, cfg, inv, n_seeds=32, max_steps=200)
    assert len(search._RUN_CACHE) == before + 1


def _cached_init(wl):
    """The init program search_seeds cached for workload ``wl``."""
    from madsim_tpu.engine import search

    (init,) = [e[0] for e in search._RUN_CACHE.values() if e[2] is wl]
    return init


@pytest.mark.parametrize("case", ["raft", "kv_plan", "kv_plan_rows_no_node"])
def test_cached_init_equals_eager_init(case):
    """The compiled init a sweep builds its batch with writes the same
    SimState, field for field and dtype for dtype, as the eager
    ``make_init`` — with no plan, with plan rows, and with pre-army plan
    rows whose ``node`` column is None (another pytree structure)."""
    seeds = np.arange(1, 2**40, 2**34, dtype=np.uint64)[:24]
    inv = lambda v: np.ones(len(seeds), bool)  # noqa: E731
    if case == "raft":
        wl, cfg, rows, kw = make_raft(), EngineConfig(pool_size=48), None, {}
    else:
        wl = make_kvchaos(writes=5, record=True, bug=True)
        cfg = EngineConfig(pool_size=40, loss_p=0.02)
        plan = FaultPlan((CrashStorm(targets=(1, 2, 3, 4), n=2),), name="kv")
        rows = plan.compile_batch(seeds, wl=wl)
        assert rows.node is not None
        if case == "kv_plan":
            kw = dict(plan=plan)
        else:
            rows = dataclasses.replace(rows, node=None)
            kw = dict(plan_rows=rows, dup_rows=plan.uses_dup())
    search_seeds(wl, cfg, inv, seeds=seeds, max_steps=40, **kw)
    init = _cached_init(wl)
    assert isinstance(init, prof.AotProgram) and init.builds == 1
    eager = make_init(
        wl, cfg,
        Build(
            plan_slots=0 if rows is None
                      else rows.time.shape[1],
        ),
    )
    args = (seeds,) if rows is None else (seeds, rows)
    got, want = init(*args), eager(*args)
    assert init.builds == 1  # the search's own build served this call
    for f in dataclasses.fields(want):
        g, w = np.asarray(getattr(got, f.name)), np.asarray(getattr(want, f.name))
        assert g.dtype == w.dtype and g.shape == w.shape, f.name
        np.testing.assert_array_equal(g, w, err_msg=f.name)


def test_cached_init_builds_once_per_batch_shape():
    """Repeated sweeps at one batch size build the init program once, a
    second size builds it once more (the AotProgram counter and the
    profiler's retrace table agree), and the program is not named like
    the compacted runner (``jit_compiled``), which the device trace's
    runner metrics select by name."""
    wl, cfg = make_raft(), EngineConfig(pool_size=48)
    inv = lambda v: v["halted"]  # noqa: E731
    with prof.profiled() as p:
        for base in (0, 100, 200):
            search_seeds(wl, cfg, inv, n_seeds=16, seed_base=base,
                         max_steps=40, compact=True, require_halt=False)
        init = _cached_init(wl)
        assert init.builds == 1
        assert list(p.retraces("engine.search.init").values()) == [1]
        search_seeds(wl, cfg, inv, n_seeds=32, max_steps=40, compact=True,
                     require_halt=False)
        assert init.builds == 2
        assert list(p.retraces("engine.search.init").values()) == [2]
    assert init.name == "engine.search.init"
    for exe in init._exes.values():
        assert exe.as_text().split()[1].rstrip(",") == "jit_init"


@pytest.mark.slow
def test_compact_search_same_verdicts_and_traces():
    # compact=True runs the seed-compaction path: identical per-seed
    # verdicts and trace hashes, narrower view (node_state etc. only)
    wl = make_kvchaos(writes=5)
    cfg = EngineConfig(pool_size=48, loss_p=0.02)

    def all_replicas_current(v):
        return (np.asarray(v["node_state"])[:, 1:5, 1] >= 5).all(axis=1)

    full = search_seeds(wl, cfg, all_replicas_current, n_seeds=256, max_steps=900)
    fast = search_seeds(
        wl, cfg, all_replicas_current, n_seeds=256, max_steps=900, compact=True
    )
    assert np.array_equal(full.failing_seeds, fast.failing_seeds)
    assert np.array_equal(full.traces, fast.traces)
    assert np.array_equal(full.halted, fast.halted)
