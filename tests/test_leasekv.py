"""Lease/watch KV (models/leasekv.py) + check.lease_safety.

Pins, per the round's contract: the detector's oracle table on
synthetic histories (both clauses, the re-grant escape hatch, the
under-flag cases) with the jnp HistoryScreen bit-identical to the
numpy form on every table row; a deterministic grant-after-expiry
scenario where ``bug=True`` is flagged on EVERY seed and the clean
model on none (again numpy == device); dual-mode convergence of the
batched lease machine against the single-seed ``services/etcd.py``
server on the same stalled-keepalive scenario; layout/time32/compact
bit-determinism; and checkpoint save/resume identity. Soak-scale
hunts (device-resident screens, shrink, replay) live in
tools/services_model_soak.py (SERVICES_MODELS_r12.txt)."""

import numpy as np
import pytest

import jax

from madsim_tpu import check
from madsim_tpu.check import device as dc
from madsim_tpu.check.history import OK_FAIL, OK_OK, BatchHistory
from madsim_tpu.engine import (
    EngineConfig,
    load_checkpoint,
    make_init,
    make_run,
    make_run_compacted,
    save_checkpoint,
    search_seeds,
)
from madsim_tpu.engine.verify import check_layouts
from madsim_tpu.models.leasekv import OP_EXPIRE, OP_PUT, OP_WATCH_EVT, make_leasekv

SCREENS = (dc.lease_safety(OP_PUT, OP_EXPIRE),)


def _hist(*seeds):
    """Synthetic BatchHistory: each seed a list of
    (op, key, arg, client, ok, t) records in buffer order."""
    s = len(seeds)
    h = max((len(rows) for rows in seeds), default=0)
    word = np.zeros((s, h, 5), np.int32)
    t = np.zeros((s, h), np.int64)
    count = np.zeros((s,), np.int32)
    for i, rows in enumerate(seeds):
        count[i] = len(rows)
        for j, (op, key, arg, client, ok, ts) in enumerate(rows):
            word[i, j] = (op, key, arg, client, ok)
            t[i, j] = ts
    return BatchHistory(word=word, t=t, count=count,
                        drop=np.zeros((s,), np.int32))


def _both(h):
    """numpy ok-mask and the device HistoryScreen's, asserted equal."""
    host = check.lease_safety(h, OP_PUT, OP_EXPIRE)
    dev = np.asarray(dc.screen_ok(SCREENS, h.word, h.t, h.count, h.drop))
    assert np.array_equal(host, dev), "numpy and jnp detectors disagree"
    return host


# grant / expiry / serve record shorthands (server = client 0 in the
# record convention; key = lease id, lifecycle on OP_EXPIRE)
def _grant(lid, deadline, t=0):
    return (OP_EXPIRE, lid, deadline, 0, OK_OK, t)


def _expire(lid, at_ms, t=0):
    return (OP_EXPIRE, lid, at_ms, 0, OK_FAIL, t)


def _serve(lid, seq, t=0):
    return (OP_PUT, lid, seq, 0, OK_OK, t)


class TestLeaseSafetyOracle:
    """The detector's truth table, host and device forms together."""

    def test_clean_lifecycle_ok(self):
        h = _hist([_grant(1, 500), _serve(1, 1), _expire(1, 500)])
        assert _both(h).tolist() == [True]

    def test_serve_after_expiry_flagged(self):
        h = _hist([_grant(1, 500), _expire(1, 500), _serve(1, 1)])
        assert _both(h).tolist() == [False]

    def test_regrant_between_expiry_and_serve_ok(self):
        # the clean rejoin path: expiry, re-grant, THEN serve
        h = _hist([_grant(1, 500), _expire(1, 500),
                   _grant(1, 900), _serve(1, 2)])
        assert _both(h).tolist() == [True]

    def test_other_leases_expiry_does_not_flag(self):
        # lifecycle records are per lease id: lease 2 dying says
        # nothing about lease 1's serves
        h = _hist([_grant(1, 500), _grant(2, 500),
                   _expire(2, 500), _serve(1, 1)])
        assert _both(h).tolist() == [True]

    def test_early_expiry_flagged(self):
        # clause 2: the server expired the lease before its own clock
        # reached the deadline it granted
        h = _hist([_grant(1, 500), _expire(1, 499)])
        assert _both(h).tolist() == [False]

    def test_skewed_but_honest_expiry_ok(self):
        # expiry strictly after the granted deadline on the server's
        # local clock is the contract — skew never flags by itself
        h = _hist([_grant(1, 500), _expire(1, 777)])
        assert _both(h).tolist() == [True]

    def test_serve_with_no_lifecycle_constrains_nothing(self):
        h = _hist([_serve(1, 1)])
        assert _both(h).tolist() == [True]

    def test_per_seed_verdicts_independent(self):
        h = _hist(
            [_grant(1, 500), _expire(1, 500), _serve(1, 1)],  # clause 1
            [_grant(1, 500), _serve(1, 1), _expire(1, 500)],  # clean
            [_grant(1, 500), _expire(1, 400)],  # clause 2
            [],  # empty history
        )
        assert _both(h).tolist() == [False, True, False, True]


# ---------------------------------------------------------------------------
# the deterministic grant-after-expiry scenario
# ---------------------------------------------------------------------------

# keepalives SLOWER than the TTL (ka 80ms vs ttl 50ms): every lease
# expires between heartbeats, so every keepalive lands on a dead lease.
# The fast put timer (30ms < ttl) keeps the clean model progressing —
# one put is always served inside each fresh grant's window, the
# rejected ones trigger the re-grant path, and the history shows
# expiry -> grant -> serve everywhere. bug=True: the keepalive
# silently resurrects the dead lease, so some put is served with the
# expiry as its latest lifecycle record — every seed flagged.
_SCEN = dict(ttl_ms=50, ka_ms=80, scan_ms=20, put_ms=30,
             chaos=False, record=True)
_CFG = EngineConfig(pool_size=48, loss_p=0.0)
_N_SEEDS = 8
_STEPS = 900

_SHARED: dict = {}


def _scenario(bug):
    key = "bug" if bug else "clean"
    if key not in _SHARED:
        box = {}

        def hinv(h):
            box["h"] = h
            return np.ones(len(h.count), bool)

        rep = search_seeds(
            make_leasekv(bug=bug, **_SCEN), _CFG, None,
            n_seeds=_N_SEEDS, max_steps=_STEPS, history_invariant=hinv,
        )
        _SHARED[key] = (rep, box["h"])
    return _SHARED[key]


class TestMutantScenario:
    def test_clean_model_is_clean(self):
        rep, h = _scenario(bug=False)
        assert rep.ok.all(), rep.failing_seeds
        assert _both(h).all()

    def test_mutant_flagged_on_every_seed(self):
        rep, h = _scenario(bug=True)
        assert rep.halted.all(), "mutant scenario must still halt"
        assert not _both(h).any(), (
            "grant-after-expiry mutant escaped the detector"
        )

    def test_screens_invariant_matches_direct_call(self):
        _, h = _scenario(bug=True)
        inv = dc.screens_invariant(SCREENS)
        assert np.array_equal(np.asarray(inv(h)), _both(h))


# ---------------------------------------------------------------------------
# dual-mode convergence: batched lease machine vs services/etcd.py
# ---------------------------------------------------------------------------


class TestDualModeConvergence:
    """One scenario, two arms: client 1 stalls its keepalives at 2s
    while clients 2/3 keep renewing a 5s-TTL lease. The batched model
    (``ka_stop_ms``) and the single-seed etcd server (``tick()``) must
    reach the same verdict — lease 1 expires, leases 2/3 survive."""

    TTL_S, STALL_S, END_S = 5, 2, 12

    def _host_arm(self):
        import random

        from madsim_tpu.services.etcd import _ServiceInner

        inner = _ServiceInner()
        rng = random.Random(0)
        for lid in (1, 2, 3):
            inner.lease_grant(self.TTL_S, lid, rng)
        expired_at = {}
        for t in range(1, self.END_S + 1):
            for lid in list(inner.leases):
                if not (lid == 1 and t >= self.STALL_S):
                    inner.lease_keep_alive(lid)
            before = set(inner.leases)
            inner.tick()
            for lid in before - set(inner.leases):
                expired_at[lid] = t
        return expired_at, set(inner.leases)

    def _batched_arm(self):
        wl = make_leasekv(
            ttl_ms=self.TTL_S * 1000, ka_ms=1000, scan_ms=1000,
            put_ms=1_000_000, ka_stop_ms=self.STALL_S * 1000,
            chaos=False, record=True,
        )
        box = {}

        def hinv(h):
            box["h"] = h
            return np.ones(len(h.count), bool)

        search_seeds(
            wl, EngineConfig(pool_size=48, loss_p=0.0), None,
            n_seeds=1, max_steps=140, require_halt=False,
            history_invariant=hinv,
        )
        h = box["h"]
        valid = h.valid()[0]
        word = h.word[0]
        life = valid & (word[:, 0] == OP_EXPIRE)
        exp = life & (word[:, 4] == OK_FAIL)
        granted = {int(k) for k in word[life & (word[:, 4] == OK_OK), 1]}
        expired_at = {
            int(k): int(a) // 1000
            for k, a in zip(word[exp, 1], word[exp, 2])
        }
        wevt = valid & (word[:, 0] == OP_WATCH_EVT) & (word[:, 4] == OK_OK)
        return h, granted, expired_at, {int(k) for k in word[wevt, 1]}

    def test_verdicts_converge(self):
        host_expired, host_alive = self._host_arm()
        h, granted, batched_expired, watched = self._batched_arm()
        # identical verdicts: WHICH leases died and which survived
        assert set(host_expired) == set(batched_expired) == {1}
        assert host_alive == granted - set(batched_expired) == {2, 3}
        # the expiry instant agrees up to the two arms' discretization:
        # the host tick expires at remaining<=1 (one second early
        # against the ms deadline) and the batched scan quantizes the
        # deadline up to the next whole-second scan after 1-10ms of
        # network latency on the renewal — a fixed <=2s window, never
        # a drifting one
        for lid, host_t in host_expired.items():
            assert 0 <= batched_expired[lid] - host_t <= 2
        # the watcher saw the delete event for exactly the dead lease
        assert watched == {1}
        # and the batched arm's own history is clean under the detector
        assert _both(h).all()


# ---------------------------------------------------------------------------
# determinism + checkpoint
# ---------------------------------------------------------------------------


class TestDeterminism:
    def test_layouts_time32_bit_identical(self):
        # dense/scatter x time32 lowerings of the recorded model
        check_layouts(
            make_leasekv(record=True), _CFG,
            np.arange(4, dtype=np.uint64), 400,
        )

    def test_compacted_equals_lockstep(self):
        wl = make_leasekv(record=True)
        init = make_init(wl, _CFG)
        seeds = np.arange(8, dtype=np.uint64)
        ref = jax.jit(make_run(wl, _CFG, 900))(init(seeds))
        out = make_run_compacted(wl, _CFG, 900, min_size=4)(init(seeds))
        for f in ("now", "halted", "trace", "node_state",
                  "hist_word", "hist_t", "hist_count", "hist_drop"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(out, f)),
                err_msg=f,
            )

    def test_checkpoint_roundtrip_resumes_identically(self, tmp_path):
        wl = make_leasekv(record=True)
        init = make_init(wl, _CFG)
        st = init(np.arange(4, dtype=np.uint64))
        run_half = jax.jit(make_run(wl, _CFG, 150))
        mid = run_half(st)
        path = str(tmp_path / "leasekv.npz")
        save_checkpoint(path, mid, _CFG)
        resumed = load_checkpoint(path, wl, _CFG)
        a, b = run_half(mid), run_half(resumed)
        for f in ("trace", "now", "node_state", "hist_word", "hist_count"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                err_msg=f,
            )


def test_bug_requires_record():
    with pytest.raises(ValueError, match="record=True"):
        make_leasekv(bug=True)
