"""Device-resident verification (check/device.py + ISSUE 14 wiring).

Four layers under test, mirroring the module stack:

* the **oracle table** — hand-built per-detector fixtures covering the
  rank-matching guard paths (paired invoke / bare response / malformed
  invoke-after — previously exercised only indirectly via soaks),
  asserted against the numpy detectors (the authoritative oracle) AND
  the jnp kernels (the port must match the oracle bit for bit);
* the **engine identity** — `search_seeds(device_check=...)` ==
  `history_invariant` verdicts on recorded models, clean and
  planted-mutant, lockstep and compacted (the layout matrix rides the
  slow tier);
* **prefix-compaction** — the fold is loud and lossless, flagged seeds
  ship verbatim-full histories, and the escalated history fails the
  exact Wing–Gong checker (the PR-1 cross-check);
* the **device history hunt** — `explore.run_device(history_check=)`
  is bit-identical to the host driver and its finds replay there.

Seed counts are lean here; tools/verify_bench.py runs the same pins at
the 65k evidence scale (VERIFY_r09.txt).
"""

import numpy as np
import pytest

import jax

from madsim_tpu.check import BatchHistory, device as dc
from madsim_tpu.check import vectorized as v
from madsim_tpu.check.history import (
    OK_FAIL,
    OK_OK,
    OK_PENDING,
    OP_READ,
    OP_USER,
    OP_WRITE,
)
from madsim_tpu.check.linearize import check_kv
from madsim_tpu.engine import Build, EngineConfig, make_init, search_seeds
from madsim_tpu.engine.compact import make_run_compacted
from madsim_tpu.models import make_kvchaos, make_raft, make_raftlog
from madsim_tpu.models.raft import OP_ELECT
from madsim_tpu.models.raftlog import OP_RECOVER, OP_SYNCED

CFG = EngineConfig(pool_size=40, loss_p=0.02,
                   clog_backoff_max_ns=2_000_000_000)
KV_SCREENS = (dc.stale_reads(), dc.read_your_writes(),
              dc.monotonic_reads())
KV_INV = dc.screens_invariant(KV_SCREENS)


def _hist(*seeds):
    """Synthetic BatchHistory: each seed a list of
    (op, key, arg, client, ok) records in buffer order (t = index)."""
    s = len(seeds)
    h = max((len(rows) for rows in seeds), default=0)
    word = np.zeros((s, h, 5), np.int32)
    t = np.zeros((s, h), np.int64)
    count = np.zeros((s,), np.int32)
    for i, rows in enumerate(seeds):
        count[i] = len(rows)
        for j, rec in enumerate(rows):
            word[i, j] = rec
            t[i, j] = j
    return BatchHistory(word=word, t=t, count=count,
                        drop=np.zeros((s,), np.int32))


def _device(screens, h: BatchHistory) -> np.ndarray:
    ok = jax.jit(
        lambda w, t, c, d: dc.screen_ok(screens, w, t, c, d)
    )(h.word, h.t, h.count, h.drop)
    return np.asarray(ok)


def _both(screen, h):
    """(numpy verdicts, device verdicts) for one screen."""
    return np.asarray(screen.host(h), bool), _device((screen,), h)


# -------------------------------------------------- the oracle table
# Each fixture: (name, screen, history rows, expected verdict). The
# rank-matching guard paths are the point: a response's floor comes
# from its PAIRED invoke (earlier invoke), its OWN slot (no invoke —
# a bare/instantaneous event), or nowhere (rank-matched invoke AFTER
# the response: malformed, under-flag not false-flag).
W, R = OP_WRITE, OP_READ
ORACLE = [
    # paired invoke: write completes while the read is in flight —
    # floor sampled at the INVOKE, so the newer write never false-flags
    ("stale/paired-invoke-in-flight-write", dc.stale_reads(),
     [(W, 0, 1, 0, OK_OK), (R, 0, 0, 1, OK_PENDING),
      (W, 0, 2, 0, OK_OK), (R, 0, 1, 1, OK_OK)],
     True),
    # the same read missing the write completed BEFORE its invoke
    ("stale/paired-invoke-lost-write", dc.stale_reads(),
     [(W, 0, 1, 0, OK_OK), (W, 0, 2, 0, OK_OK),
      (R, 0, 0, 1, OK_PENDING), (R, 0, 1, 1, OK_OK)],
     False),
    # bare response (no invoke record anywhere): floor at its OWN
    # buffer slot — the write before it counts
    ("stale/bare-response-floor-at-own-slot", dc.stale_reads(),
     [(W, 0, 2, 0, OK_OK), (R, 0, 1, 1, OK_OK)],
     False),
    ("stale/bare-response-clean", dc.stale_reads(),
     [(W, 0, 2, 0, OK_OK), (R, 0, 2, 1, OK_OK)],
     True),
    # malformed: the rank-matched invoke sits AFTER the response —
    # no constraint (under-flag, never false-flag)
    ("stale/invoke-after-response-unconstrained", dc.stale_reads(),
     [(W, 0, 2, 0, OK_OK), (R, 0, 0, 1, OK_OK),
      (R, 0, 9, 1, OK_PENDING)],
     True),
    # failed responses never sample the floor
    ("stale/failed-read-unconstrained", dc.stale_reads(),
     [(W, 0, 2, 0, OK_OK), (R, 0, 0, 1, OK_PENDING),
      (R, 0, 0, 1, OK_FAIL)],
     True),
    # read-your-writes scopes the floor to the client's OWN writes
    ("ryw/other-clients-write-ignored", dc.read_your_writes(),
     [(W, 0, 5, 0, OK_OK), (R, 0, 0, 1, OK_PENDING),
      (R, 0, 0, 1, OK_OK)],
     True),
    ("ryw/own-write-enforced", dc.read_your_writes(),
     [(W, 0, 5, 1, OK_OK), (R, 0, 0, 1, OK_PENDING),
      (R, 0, 0, 1, OK_OK)],
     False),
    # invoke-interval-aware monotonic reads: pipelined reads (two open
    # at once) may legally complete out of order
    ("monotonic/pipelined-out-of-order-ok", dc.monotonic_reads(),
     [(R, 0, 0, 0, OK_PENDING), (R, 0, 0, 0, OK_PENDING),
      (R, 0, 2, 0, OK_OK), (R, 0, 1, 0, OK_OK)],
     True),
    # ...but the strict response-order pass flags exactly that
    ("monotonic-strict/flags-pipelined", dc.monotonic_reads_strict(),
     [(R, 0, 0, 0, OK_PENDING), (R, 0, 0, 0, OK_PENDING),
      (R, 0, 2, 0, OK_OK), (R, 0, 1, 0, OK_OK)],
     False),
    # sequential session regression IS flagged by the sound pass
    ("monotonic/sequential-regression", dc.monotonic_reads(),
     [(R, 0, 0, 0, OK_PENDING), (R, 0, 2, 0, OK_OK),
      (R, 0, 0, 0, OK_PENDING), (R, 0, 1, 0, OK_OK)],
     False),
    # election safety: two winners of one term
    ("election/two-winners", dc.election_safety(OP_USER),
     [(OP_USER, 3, 1, 1, OK_OK), (OP_USER, 3, 2, 2, OK_OK)],
     False),
    ("election/re-record-same-winner", dc.election_safety(OP_USER),
     [(OP_USER, 3, 1, 1, OK_OK), (OP_USER, 3, 1, 1, OK_OK),
      (OP_USER, 4, 2, 2, OK_OK)],
     True),
    # recovery safety: floor is the LAST sync, not the running max —
    # a legitimately truncated-then-synced length recovers clean
    ("recovery/truncation-resync-ok",
     dc.recovery_safety(OP_USER + 2, OP_USER + 3),
     [(OP_USER + 2, 0, 5, 1, OK_OK), (OP_USER + 2, 0, 3, 1, OK_OK),
      (OP_USER + 3, 0, 3, 1, OK_OK)],
     True),
    ("recovery/regression-flagged",
     dc.recovery_safety(OP_USER + 2, OP_USER + 3),
     [(OP_USER + 2, 0, 5, 1, OK_OK), (OP_USER + 3, 0, 2, 1, OK_OK)],
     False),
    ("recovery/other-node-sync-ignored",
     dc.recovery_safety(OP_USER + 2, OP_USER + 3),
     [(OP_USER + 2, 0, 5, 2, OK_OK), (OP_USER + 3, 0, 0, 1, OK_OK)],
     True),
]


class TestOracleTable:
    """The per-detector oracle table: numpy == expected (the direct
    unit fixtures the rank-matching guard paths never had) and
    device == numpy (the port pin)."""

    @pytest.mark.parametrize(
        "name,screen,rows,expect", ORACLE, ids=[o[0] for o in ORACLE]
    )
    def test_fixture(self, name, screen, rows, expect):
        h = _hist(rows)
        host, dev = _both(screen, h)
        assert host[0] == expect, f"numpy oracle drifted on {name}"
        assert dev[0] == expect, f"device kernel differs on {name}"

    def test_fuzz_device_equals_numpy_all_detectors(self):
        rng = np.random.default_rng(42)
        s, hd = 128, 24
        word = np.zeros((s, hd, 5), np.int32)
        word[:, :, 0] = rng.integers(1, 4, (s, hd))
        word[:, :, 1] = rng.integers(0, 3, (s, hd))
        word[:, :, 2] = rng.integers(0, 6, (s, hd))
        word[:, :, 3] = rng.integers(0, 3, (s, hd))
        word[:, :, 4] = rng.integers(-1, 2, (s, hd))
        h = BatchHistory(
            word=word,
            t=np.arange(hd, dtype=np.int64)[None].repeat(s, 0),
            count=rng.integers(0, hd + 1, (s,)).astype(np.int32),
            drop=np.zeros((s,), np.int32),
        )
        screens = (
            dc.stale_reads(), dc.read_your_writes(), dc.monotonic_reads(),
            dc.monotonic_reads_strict(), dc.election_safety(3),
            dc.recovery_safety(3, 1),
        )
        for s_ in screens:
            host, dev = _both(s_, h)
            assert np.array_equal(host, dev), s_.kind
            assert not host.all() and host.any(), (
                f"degenerate fuzz for {s_.kind}: nothing to compare"
            )

    def test_overflowed_seed_judged_as_empty(self):
        h = _hist([(W, 0, 2, 0, OK_OK), (R, 0, 0, 1, OK_OK)])
        h.drop[0] = 1
        assert _device((dc.stale_reads(),), h)[0]  # quarantined clean

    def test_verdict_words_roundtrip(self):
        for n in (1, 31, 32, 33, 200):
            ok = (np.arange(n) % 3) != 0
            words = np.asarray(jax.jit(dc.pack_verdicts)(ok))
            assert words.shape == ((n + 31) // 32,)
            assert np.array_equal(dc.unpack_verdicts(words, n), ok)
            assert np.array_equal(dc.pack_verdicts_host(ok), words)

    def test_slo_breaches_matches_numpy(self):
        from madsim_tpu.check.slo import slo_breaches as host_slo
        from madsim_tpu.engine.core import N_LAT_BUCKETS

        rng = np.random.default_rng(1)
        hist = rng.integers(0, 40, (64, 3, N_LAT_BUCKETS)).astype(np.int32)
        hist[rng.random((64, 3)) < 0.3] = 0
        for bound in (10_000, 50_000_000, 10**10):
            dev = np.asarray(
                jax.jit(lambda x, b=bound: dc.slo_breaches(x, b))(hist)
            )
            assert np.array_equal(dev, host_slo(hist, bound))

    def test_screen_spec_validation(self):
        with pytest.raises(ValueError, match="unknown screen kind"):
            dc.HistoryScreen("linearizable_wing_gong")
        with pytest.raises(ValueError, match="non-empty"):
            dc.as_screens(())
        assert dc.as_screens(dc.stale_reads()) == (dc.stale_reads(),)
        # value-hashable: equal specs are one cache key
        assert hash(dc.stale_reads()) == hash(dc.stale_reads())


# ------------------------------------------- engine verdict identity
def _identity_case(wl, n_seeds, **kw):
    host = search_seeds(wl, CFG, None, history_invariant=KV_INV,
                        n_seeds=n_seeds, require_halt=False, **kw)
    dev = search_seeds(wl, CFG, None, device_check=KV_SCREENS,
                       n_seeds=n_seeds, require_halt=False, **kw)
    assert np.array_equal(host.ok, dev.ok)
    assert np.array_equal(host.overflowed, dev.overflowed)
    return host, dev


class TestEngineIdentity:
    # tier-1 budget (ROADMAP note): the host/device/compact lockstep at
    # 512 seeds x 2 bug modes is the heaviest compile in this file and
    # its verdict-identity claim stays tier-1-pinned by
    # test_fuzz_device_equals_numpy_all_detectors (all detectors),
    # test_flagged_history_is_the_escalation_input (mutant caught +
    # exact confirmation) and TestPrefixCompaction (compact verdicts);
    # the full-scale lockstep is VERIFY_r09 cert 1.
    @pytest.mark.slow
    def test_kvchaos_clean_and_mutant_lockstep_and_compact(self):
        for bug in (False, True):
            wl = make_kvchaos(writes=5, record=True, bug=bug)
            host, dev = _identity_case(wl, 512, max_steps=600)
            hostc = search_seeds(
                wl, CFG, None, history_invariant=KV_INV, n_seeds=512,
                max_steps=600, require_halt=False, compact=True,
            )
            devc = search_seeds(
                wl, CFG, None, device_check=KV_SCREENS, n_seeds=512,
                max_steps=600, require_halt=False, compact=True,
            )
            assert np.array_equal(host.ok, hostc.ok)
            assert np.array_equal(host.ok, devc.ok)
            if bug:
                assert len(dev.failing_seeds)  # the mutant is caught
                assert np.array_equal(dev.flagged_idx,
                                      np.nonzero(~host.ok)[0])

    def test_flagged_history_is_the_escalation_input(self):
        wl = make_kvchaos(writes=5, record=True, bug=True)
        dev = search_seeds(wl, CFG, None, device_check=KV_SCREENS,
                           n_seeds=512, max_steps=600,
                           require_halt=False)
        assert len(dev.flagged_idx)
        fh = dev.flagged_history
        assert fh.word.shape[0] == len(dev.flagged_idx)
        # every flagged seed's full history fails exact Wing-Gong KV —
        # the vectorized catch is exact-confirmed (PR-1 discipline)
        for i in range(len(fh)):
            assert not check_kv(fh.ops(i)).ok

    def test_api_validation(self):
        wl_plain = make_kvchaos(writes=5)
        with pytest.raises(ValueError, match="device_check"):
            search_seeds(wl_plain, CFG, None, n_seeds=4,
                         device_check=KV_SCREENS)
        wl = make_kvchaos(writes=5, record=True)
        with pytest.raises(ValueError, match="not both"):
            search_seeds(wl, CFG, None, n_seeds=4,
                         device_check=KV_SCREENS,
                         history_invariant=KV_INV)
        with pytest.raises(ValueError, match="invariant"):
            search_seeds(wl, CFG, None, n_seeds=4)

    @pytest.mark.slow
    def test_layout_matrix_2048_seeds_per_model(self):
        """The acceptance pin: >= 2048 seeds per recorded model, clean
        + planted mutant, scatter/dense/time32 + the compacted runner."""
        cases = [
            (make_kvchaos(writes=5, record=True), KV_SCREENS, KV_INV),
            (make_kvchaos(writes=5, record=True, bug=True),
             KV_SCREENS, KV_INV),
            (make_raft(record=True),
             (dc.election_safety(OP_ELECT),),
             dc.screens_invariant((dc.election_safety(OP_ELECT),))),
            (make_raftlog(record=True, durable=True),
             (dc.election_safety(OP_ELECT),
              dc.recovery_safety(OP_SYNCED, OP_RECOVER)),
             dc.screens_invariant(
                 (dc.election_safety(OP_ELECT),
                  dc.recovery_safety(OP_SYNCED, OP_RECOVER)))),
            (make_raftlog(record=True, durable=True, bug="nosync"),
             (dc.election_safety(OP_ELECT),
              dc.recovery_safety(OP_SYNCED, OP_RECOVER)),
             dc.screens_invariant(
                 (dc.election_safety(OP_ELECT),
                  dc.recovery_safety(OP_SYNCED, OP_RECOVER)))),
        ]
        for wl, screens, inv in cases:
            for lay_kw in (dict(layout="scatter"), dict(layout="dense"),
                           dict(layout="scatter", compact=True)):
                host = search_seeds(
                    wl, CFG, None, history_invariant=inv, n_seeds=2048,
                    max_steps=600, require_halt=False, **lay_kw,
                )
                dev = search_seeds(
                    wl, CFG, None, device_check=screens, n_seeds=2048,
                    max_steps=600, require_halt=False, **lay_kw,
                )
                assert np.array_equal(host.ok, dev.ok), (wl.name, lay_kw)

    @pytest.mark.slow
    def test_time32_representation_verdict_identity(self):
        """The int32-time lowering (what an accelerator runs) feeds the
        same columns to the same kernels: device == numpy under both
        representations, and the representations agree."""
        from madsim_tpu.engine.core import make_run_while

        wl = make_kvchaos(writes=5, record=True, bug=True)
        seeds = np.arange(2048, dtype=np.uint64)
        verdicts = []
        for t32 in (False, True):
            st = jax.jit(make_run_while(wl, CFG, 600, Build(time32=t32)))(
                make_init(wl, CFG, Build(time32=t32))(seeds)
            )
            assert not np.asarray(st.overflow).any()
            dev = _device(KV_SCREENS, BatchHistory.from_state(st))
            host = KV_INV(BatchHistory.from_state(st))
            assert np.array_equal(dev, host), f"time32={t32}"
            verdicts.append(dev)
        assert np.array_equal(verdicts[0], verdicts[1])


# ---------------------------------------------- prefix-compaction
class TestPrefixCompaction:
    def test_fold_keeps_fifo_pending_invokes_only(self):
        # I1 R1 R2 I2: R1 closes I1 (FIFO), R2 is instantaneous (no
        # open invoke), I2 stays pending -> ONLY I2 survives the fold
        h = _hist([
            (W, 0, 1, 0, OK_PENDING), (W, 0, 1, 0, OK_OK),
            (W, 0, 9, 0, OK_OK), (W, 0, 2, 0, OK_PENDING),
        ])
        ok = np.asarray([True])
        w2, t2, c2, fold = jax.jit(dc.fold_verified)(
            h.word, h.t, h.count, h.drop, ok
        )
        assert int(c2[0]) == 1 and int(fold[0]) == 3
        assert tuple(np.asarray(w2)[0, 0]) == (W, 0, 2, 0, OK_PENDING)
        assert int(np.asarray(t2)[0, 0]) == 3  # original clock rides along

    def test_flagged_and_overflowed_seeds_keep_everything(self):
        rows = [(W, 0, 1, 0, OK_PENDING), (W, 0, 1, 0, OK_OK)]
        h = _hist(rows, rows)
        h.drop[1] = 2  # overflowed
        ok = np.asarray([False, True])  # flagged / overflowed-clean
        w2, t2, c2, fold = jax.jit(dc.fold_verified)(
            h.word, h.t, h.count, h.drop, ok
        )
        assert np.array_equal(np.asarray(c2), h.count)
        assert np.array_equal(np.asarray(fold), [0, 0])
        assert np.array_equal(np.asarray(w2), h.word)

    def test_compacted_runner_folds_losslessly(self):
        wl = make_kvchaos(writes=5, record=True, bug=True)
        seeds = np.arange(512, dtype=np.uint64)
        init = make_init(wl, CFG)
        plain = make_run_compacted(wl, CFG, 600)(init(seeds))
        folded = make_run_compacted(wl, CFG, 600,
                                    hist_screen=KV_SCREENS)(init(seeds))
        # loud accounting: nothing vanishes silently
        assert np.array_equal(folded.hist_count + folded.hist_fold,
                              plain.hist_count)
        assert np.array_equal(folded.hist_drop, plain.hist_drop)
        # flagged seeds verbatim-full (the escalation path)
        flag = ~folded.hist_ok
        assert flag.any() and not flag.all()
        assert np.array_equal(folded.hist_word[flag],
                              plain.hist_word[flag])
        assert np.array_equal(folded.hist_t[flag], plain.hist_t[flag])
        # clean seeds fold their responded pairs
        assert (folded.hist_fold[~flag] > 0).any()
        # the verdict equals the numpy detectors on the UNfolded columns
        bh = BatchHistory(word=plain.hist_word, t=plain.hist_t,
                          count=plain.hist_count, drop=plain.hist_drop)
        assert np.array_equal(folded.hist_ok, KV_INV(bh))

    def test_sharded_screened_runner_matches_unsharded(self):
        """The detectors run sharded WITH the sim: each device screens
        and folds its own banked rows inside shard_map, and the
        assembled result equals the unsharded screened runner."""
        from madsim_tpu import parallel

        wl = make_kvchaos(writes=5, record=True, bug=True)
        mesh = parallel.make_mesh()
        n_dev = mesh.devices.size
        seeds = np.arange(16 * n_dev, dtype=np.uint64)
        init = make_init(wl, CFG)
        base = make_run_compacted(wl, CFG, 600,
                                  hist_screen=KV_SCREENS)(init(seeds))
        sh = parallel.shard_run_compacted(
            wl, CFG, 600, mesh, hist_screen=KV_SCREENS,
        )(parallel.shard_state(init(seeds), mesh))
        for f in ("hist_ok", "hist_fold", "hist_count", "hist_word",
                  "hist_t", "trace"):
            assert np.array_equal(getattr(base, f), getattr(sh, f)), f

    def test_hist_screen_requires_history(self):
        with pytest.raises(ValueError, match="history"):
            make_run_compacted(make_kvchaos(writes=5), CFG, 100,
                               hist_screen=KV_SCREENS)


# ------------------------------------------- the device history hunt
class TestDeviceHistoryHunt:
    # tier-1 budget: the host-vs-device campaign bit-identity + replay
    # is VERIFY_r09's headline certificate (and the services soak
    # re-proves it on two more models); tier-1 keeps the API guard
    # below and the device-detector identity pins above.
    @pytest.mark.slow
    def test_run_device_history_hunt_matches_host_and_replays(self):
        from madsim_tpu import explore
        from madsim_tpu.chaos import CrashStorm, FaultPlan
        from madsim_tpu.obs import prof

        wl = make_kvchaos(writes=5, record=True, bug=True)
        plan = FaultPlan((CrashStorm(targets=(1, 2, 3, 4), n=2),),
                         name="hunt")
        kw = dict(generations=2, batch=64, root_seed=7, max_steps=600,
                  cov_words=16)
        host = explore.run(wl, CFG, plan, invariant=None,
                           history_invariant=KV_INV, **kw)
        profiler = prof.ProgramProfiler()
        with prof.profiled(profiler):
            dev = explore.run_device(wl, CFG, plan, invariant=None,
                                     history_check=KV_SCREENS, **kw)
        # bit-identical campaign: corpus, coverage, violations
        assert [
            (e.id, e.seed, e.trace, e.violating, e.plan.hash())
            for e in host.corpus
        ] == [
            (e.id, e.seed, e.trace, e.violating, e.plan.hash())
            for e in dev.corpus
        ]
        assert np.array_equal(host.cov_map, dev.cov_map)
        assert [(e.seed, e.trace) for e in host.violations] == [
            (e.seed, e.trace) for e in dev.violations
        ]
        # the hunt finds the lost-write mutant, device-resident
        assert dev.violations
        # one trace per (key, mode): the screen joined the cached
        # generation program without defeating the cache
        retr = profiler.retraces("explore.device")
        assert retr and all(n == 1 for n in retr.values())
        # the find replays on the HOST driver, trace + verdict identical
        e = dev.violations[0]
        rep = explore.replay_entry(wl, CFG, e, history_invariant=KV_INV,
                                   max_steps=600)
        assert int(rep.traces[0]) == e.trace and not bool(rep.ok[0])

    def test_run_device_requires_some_check(self):
        from madsim_tpu import explore
        from madsim_tpu.chaos import CrashStorm, FaultPlan

        plan = FaultPlan((CrashStorm(targets=(1, 2),),), name="p")
        with pytest.raises(ValueError, match="invariant"):
            explore.run_device(make_kvchaos(writes=5, record=True), CFG,
                               plan, invariant=None)
        with pytest.raises(ValueError, match="history_check"):
            explore.run_device(make_kvchaos(writes=5), CFG, plan,
                               invariant=None,
                               history_check=KV_SCREENS)


# ------------------------------------------------ cov_features hook
class TestCovFeatures:
    # tier-1 budget: bitmap-growth-without-trace-change is re-pinned
    # cheaply by test_obs's hit-count rows and the lint
    # noninterference coverage axes; the spread deltas are EXPLORE_r08.
    @pytest.mark.slow
    def test_commit_spread_changes_bitmaps_not_traces(self):
        inv = lambda view: np.ones(  # noqa: E731
            np.asarray(view["halted"]).shape[0], bool
        )
        base = search_seeds(make_raftlog(record=True), CFG, inv,
                            n_seeds=96, max_steps=400, cov_words=16,
                            require_halt=False)
        hooked = search_seeds(
            make_raftlog(record=True, cov_spread=True), CFG, inv,
            n_seeds=96, max_steps=400, cov_words=16, require_halt=False,
        )
        # coverage is derived state: the hook must not move the sim
        assert np.array_equal(base.traces, hooked.traces)
        assert np.array_equal(base.halted, hooked.halted)
        # ...but it must contribute fresh guidance bits
        extra = (np.bitwise_or.reduce(hooked.cov, axis=0)
                 & ~np.bitwise_or.reduce(base.cov, axis=0))
        assert extra.any()


# -------------------------------------------------- sharded folds
class TestMergeVerdicts:
    def test_merge_verdicts_packs_seed_order(self):
        from madsim_tpu import parallel

        ok = (np.arange(256) % 5) != 0
        words = parallel.merge_verdicts(ok)
        assert np.array_equal(dc.unpack_verdicts(words, 256), ok)
        mesh = parallel.make_mesh()
        if 256 % (mesh.devices.size * 32) == 0:
            sharded = parallel.merge_verdicts(ok, mesh)
            assert np.array_equal(sharded, words)

    def test_merge_verdicts_rejects_misaligned(self):
        from madsim_tpu import parallel

        mesh = parallel.make_mesh()
        if mesh.devices.size > 1:
            with pytest.raises(ValueError, match="word-aligned"):
                parallel.merge_verdicts(np.ones(mesh.devices.size, bool),
                                        mesh)
