"""First-class client retries (ISSUE 20): timeout/backoff RetryPolicy.

The contract under test, clause by clause:

* **Derived-state discipline, off-policy.** ``retry=None`` is the
  pre-retry engine: zero-size ``rt_*`` columns, untouched metric slots,
  and bit-identical traces across the scatter/dense lowerings, the
  time32 representation, the readiness-indexed pool and the compacted
  runner. A policied plan compiles the SAME pool rows as the unpolicied
  one (attempt-0 tokens are plain op ids) — the policy changes the
  engine build, never the compiled plan.
* **Deterministic schedule.** The backoff ladder is a host-side
  constant table; re-send jitter comes from ``(seed, step)`` threefry
  draws on the PURPOSE_RETRY lane — the same seed replays the same
  attempt schedule down to every SimState bit, and retried runs stay
  bit-identical across lowerings.
* **Books.** MET_RETRY counts delivered re-sends, MET_RETRY_GIVEUP
  abandoned ops; under total response starvation the counts are exact:
  ``(max_attempts - 1) * n_ops`` re-sends, ``n_ops`` give-ups, zero
  completed latency samples.
* **Checkpoints.** Format 11 carries the ``rt_*`` columns (armed
  deadlines are core state): a retried run snapshots and resumes
  bit-identically, and mismatched retry axes are refused with the
  designed error in both directions.
* **Attempt-aware checking.** ``check.exactly_once`` and
  ``check.collapse_retries`` agree verdict-for-verdict (and bit-for-bit)
  between the numpy oracles and the jnp device kernels, on hand-built
  oracle tables covering the OK / FAIL / PENDING response shapes and on
  real clean/mutant batches.
* **The planted mutant.** ``shardkv(bug="noidem")`` applies every
  delivered attempt; under a retry policy it is INVISIBLE to the
  final-state shard_coverage checker and caught only by exactly_once —
  found by the guided hunt, ddmin-shrunk under the same policy, and
  replayed to the identical violation + trace.

tools/retry_soak.py runs the same certificates at evidence scale
(RETRY_r14.txt).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from madsim_tpu import check
from madsim_tpu.chaos import (
    FaultPlan,
    GrayFailure,
    Partition,
    RetryPolicy,
    shrink_plan,
)
from madsim_tpu.check import BatchHistory, OK_FAIL, OK_OK, OK_PENDING
from madsim_tpu.check import device as dc
from madsim_tpu.engine import (
    Build,
    MET_RETRY,
    MET_RETRY_GIVEUP,
    N_METRICS,
    RETRY_STATE_FIELDS,
    EngineConfig,
    LatencySpec,
    RetrySpec,
    load_checkpoint,
    make_init,
    make_run,
    make_run_while,
    retry_token,
    retry_token_attempt,
    retry_token_op,
    save_checkpoint,
    search_seeds,
)
from madsim_tpu.engine.compact import make_run_compacted
from madsim_tpu.engine.core import _retry_backoff_tables, time32_eligible
from madsim_tpu.models import kvchaos as KV
from madsim_tpu.models import shardkv as SK

# the pinned retry-amplification shape: 2-replica kvchaos army under a
# gray-failure slow link, 50 ms response deadline
N_OPS = 16
POLICY = RetryPolicy(timeout_ns=50_000_000, max_attempts=3,
                     backoff_base_ns=10_000_000, backoff_mult=2.0,
                     jitter=0.5)
GRAY = GrayFailure(targets=(0, 3), n_links=1, mult_min=6, mult_max=12)
CFG = EngineConfig(pool_size=64, time_limit_ns=450_000_000,
                   clog_backoff_max_ns=2_000_000_000)
SPEC = LatencySpec(ops=N_OPS, phases=3, phase_ns=1 << 27)
STEPS = 1500


def _wl():
    return KV.make_kvchaos(writes=12, n_replicas=2, chaos=False, army=True)


def _plan(retry):
    return FaultPlan(
        (KV.client_army(n_ops=N_OPS, t_min_ns=5_000_000,
                        t_max_ns=280_000_000, n_replicas=2, retry=retry),
         GRAY),
        name="retry-pin",
    )


def _run(wl, plan, seeds, retry, *, layout=None, time32=None,
         pool_index=None, compact=False, steps=STEPS):
    b = Build(
        layout=layout, time32=time32, pool_index=pool_index,
        plan_slots=plan.slots, latency=SPEC, metrics=True, retry=retry,
    )
    st0 = make_init(wl, CFG, b)(seeds, plan.compile_batch(seeds, wl=wl))
    if compact:
        return make_run_compacted(wl, CFG, steps, b, min_size=8)(st0)
    run = jax.jit(make_run_while(wl, CFG, steps, b))
    return jax.block_until_ready(run(st0))


# ------------------------------------------------------------- identity
class TestOffIdentity:
    def test_retry_off_columns_are_zero_size(self):
        wl = _wl()
        plan = _plan(POLICY)
        seeds = np.arange(4, dtype=np.uint64)
        rows = plan.compile_batch(seeds, wl=wl)
        off = make_init(
            wl, CFG, Build(plan_slots=plan.slots, latency=SPEC, metrics=True),
        )(seeds, rows)
        on = make_init(
            wl, CFG,
            Build(
                plan_slots=plan.slots, latency=SPEC, metrics=True,
                retry=plan.retry_spec(),
            ),
        )(seeds, rows)
        for f in RETRY_STATE_FIELDS:
            assert np.asarray(getattr(off, f)).size == 0, f
            assert np.asarray(getattr(on, f)).shape == (4, N_OPS), f
        # the metric row grew the two retry slots for every build — the
        # schema-only change the step goldens digest around
        assert np.asarray(off.met).shape == (4, N_METRICS)
        assert N_METRICS == MET_RETRY_GIVEUP + 1

    def test_policy_changes_no_compiled_row(self):
        """The plan compiles identically with and without the policy:
        attempt-0 tokens ARE plain op ids, so the offered load is the
        same rows and the policy is purely an engine build flag."""
        seeds = np.arange(8, dtype=np.uint64)
        wl = _wl()
        r_on = _plan(POLICY).compile_batch(seeds, wl=wl)
        r_off = _plan(None).compile_batch(seeds, wl=wl)
        for f in ("time", "kind", "args", "valid", "node"):
            assert np.array_equal(np.asarray(getattr(r_on, f)),
                                  np.asarray(getattr(r_off, f))), f

    @pytest.mark.parametrize("axis", ["dense", "time32", "pool_index",
                                      "compact"])
    def test_retry_off_bit_identity_four_axes(self, axis):
        """With no policy the retry machinery compiles away on every
        lowering: trace/clock/books identical to the scatter baseline
        (the step-golden digests in test_stepident.py pin the same
        engine against its PRE-retry values)."""
        wl = _wl()
        plan = _plan(None)
        seeds = np.arange(6, dtype=np.uint64)
        base = _run(wl, plan, seeds, None, layout="scatter")
        kw = {
            "dense": dict(layout="dense"),
            "time32": dict(time32=True),
            "pool_index": dict(pool_index=True),
            "compact": dict(compact=True),
        }[axis]
        if axis == "time32":
            assert time32_eligible(wl, CFG)
        other = _run(wl, plan, seeds, None, **kw)
        for f in ("trace", "now", "step", "halted", "met", "lat_hist"):
            assert np.array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(other, f))), (axis, f)
        assert np.asarray(base.met)[:, MET_RETRY:].sum() == 0

    @pytest.mark.parametrize("axis", ["dense", "time32", "pool_index",
                                      "compact"])
    def test_retry_on_bit_identity_four_axes(self, axis):
        """A retried trajectory is still one trajectory: the re-sent
        attempts land identically on every lowering. (The compacted
        runner banks RESULT_FIELDS only, so the rt_* books are compared
        on the full-state axes.)"""
        wl = _wl()
        plan = _plan(POLICY)
        rt = plan.retry_spec()
        seeds = np.arange(6, dtype=np.uint64)
        base = _run(wl, plan, seeds, rt, layout="scatter")
        assert np.asarray(base.met)[:, MET_RETRY].sum() > 0
        kw = {
            "dense": dict(layout="dense"),
            "time32": dict(time32=True),
            "pool_index": dict(pool_index=True),
            "compact": dict(compact=True),
        }[axis]
        other = _run(wl, plan, seeds, rt, **kw)
        fields = ["trace", "now", "step", "halted", "met", "lat_hist"]
        if axis != "compact":
            fields += list(RETRY_STATE_FIELDS)
        for f in fields:
            assert np.array_equal(np.asarray(getattr(base, f)),
                                  np.asarray(getattr(other, f))), (axis, f)


# ------------------------------------------------------------- schedule
class TestSchedule:
    def test_token_packing_roundtrip(self):
        for op in (0, 7, (1 << 26) - 1):
            for att in (0, 1, 15):
                tok = retry_token(op, att)
                assert retry_token_op(tok) == op
                assert retry_token_attempt(tok) == att
        assert retry_token(9, 0) == 9  # attempt-0 tokens are plain ids

    def test_backoff_table_pin(self):
        """The deterministic ladder: entry a = base * mult**(a-1) before
        delivering attempt a; the jitter table is the ladder scaled by
        the policy's jitter fraction."""
        rt = RetrySpec(kind=16, node=0, op_base=0, n_ops=4,
                       timeout_ns=1, max_attempts=4,
                       backoff_base_ns=10_000_000, backoff_mult=2.0,
                       jitter=0.5)
        boff, bjit = _retry_backoff_tables(rt)
        assert boff == (0, 10_000_000, 20_000_000, 40_000_000, 80_000_000)
        assert bjit == (0, 5_000_000, 10_000_000, 20_000_000, 40_000_000)

    def test_spec_validation(self):
        ok = dict(kind=16, node=0, op_base=0, n_ops=4, timeout_ns=1)
        RetrySpec(**ok)
        with pytest.raises(ValueError, match="max_attempts"):
            RetrySpec(**ok, max_attempts=16)
        with pytest.raises(ValueError, match="token op field"):
            RetrySpec(kind=16, node=0, op_base=(1 << 26) - 2, n_ops=4,
                      timeout_ns=1)
        with pytest.raises(ValueError, match="user kind"):
            RetrySpec(kind=2, node=0, op_base=0, n_ops=4, timeout_ns=1)
        with pytest.raises(ValueError, match="jitter"):
            RetrySpec(**ok, jitter=1.5)

    def test_same_seed_same_attempt_schedule(self):
        """Two independent builds of the same retried run agree on every
        SimState bit — the attempt schedule (deadlines, backoff draws,
        re-send times) is a pure function of the seed."""
        wl = _wl()
        plan = _plan(POLICY)
        rt = plan.retry_spec()
        seeds = np.arange(4, dtype=np.uint64)
        a = _run(wl, plan, seeds, rt)
        b = _run(_wl(), _plan(POLICY), seeds, rt)
        for f in dataclasses.fields(a):
            assert np.array_equal(
                np.asarray(getattr(a, f.name)),
                np.asarray(getattr(b, f.name)),
            ), f.name
        met = np.asarray(a.met)
        assert met[:, MET_RETRY].sum() > 0  # the schedule was exercised

    def test_retry_changes_the_trajectory(self):
        """The policy is core state, not an observability tap: armed
        deadline rows dispatch (delivering or folding as suppressed
        no-ops), so any seed that re-sent has a different trace from
        the fire-and-forget run."""
        wl = _wl()
        seeds = np.arange(4, dtype=np.uint64)
        on = _run(wl, _plan(POLICY), seeds, _plan(POLICY).retry_spec())
        off = _run(wl, _plan(None), seeds, None)
        retried = np.asarray(on.met)[:, MET_RETRY] > 0
        assert retried.any()
        diverged = np.asarray(on.trace) != np.asarray(off.trace)
        assert diverged[retried].all()


# ------------------------------------------------------------- give-ups
class TestGiveup:
    def test_starved_army_gives_up_exactly(self):
        """Client cut off from the primary for the whole horizon: every
        op delivers all max_attempts attempts then abandons — re-send
        and give-up books are exact, nothing completes."""
        wl = KV.make_kvchaos(writes=4, n_replicas=2, chaos=False,
                             army=True)
        pol = RetryPolicy(timeout_ns=20_000_000, max_attempts=3,
                          backoff_base_ns=5_000_000, backoff_mult=2.0)
        plan = FaultPlan(
            (KV.client_army(n_ops=6, t_min_ns=5_000_000,
                            t_max_ns=80_000_000, n_replicas=2, retry=pol),
             Partition(targets=(0, 3), t_min_ns=1, t_max_ns=2,
                       dur_min_ns=900_000_000, dur_max_ns=900_000_001)),
            name="starve",
        )
        cfg = EngineConfig(pool_size=80, time_limit_ns=700_000_000)
        rt = plan.retry_spec()
        seeds = np.arange(8, dtype=np.uint64)
        init = make_init(
            wl, cfg,
            Build(
                plan_slots=plan.slots, latency=LatencySpec(ops=6),
                metrics=True, retry=rt,
            ),
        )
        run = jax.jit(make_run_while(
            wl, cfg, 5000,
            Build(latency=LatencySpec(ops=6), metrics=True, retry=rt),
        ))
        out = jax.block_until_ready(
            run(init(seeds, plan.compile_batch(seeds, wl=wl))))
        met = np.asarray(out.met)
        assert (met[:, MET_RETRY] == (pol.max_attempts - 1) * 6).all()
        assert (met[:, MET_RETRY_GIVEUP] == 6).all()
        assert np.asarray(out.rt_done).sum() == 0
        assert np.asarray(out.lat_hist).sum() == 0
        assert np.asarray(out.halted).all()


# ----------------------------------------------------------- checkpoint
class TestCheckpoint:
    def test_retry_roundtrip_resumes_identically(self, tmp_path):
        wl = _wl()
        plan = _plan(POLICY)
        rt = plan.retry_spec()
        seeds = np.arange(4, dtype=np.uint64)
        kw = dict(latency=SPEC, metrics=True, retry=rt)
        init = make_init(wl, CFG, Build(plan_slots=plan.slots, **kw))
        run = jax.jit(make_run(wl, CFG, 300, Build(**kw)))
        mid = jax.block_until_ready(
            run(init(seeds, plan.compile_batch(seeds, wl=wl))))
        # armed deadlines must actually be in flight at the cut for the
        # roundtrip to prove anything
        assert np.asarray(mid.rt_deadline).max() > 0
        p = str(tmp_path / "retry.npz")
        save_checkpoint(p, mid, CFG)
        resumed = jax.block_until_ready(
            run(load_checkpoint(p, wl, CFG, Build(retry=rt))))
        straight = jax.block_until_ready(run(mid))
        for f in dataclasses.fields(straight):
            assert np.array_equal(
                np.asarray(getattr(straight, f.name)),
                np.asarray(getattr(resumed, f.name)),
            ), f.name

    def test_mismatched_axes_refused_both_directions(self, tmp_path):
        wl = _wl()
        plan = _plan(POLICY)
        rt = plan.retry_spec()
        seeds = np.arange(2, dtype=np.uint64)
        rows = plan.compile_batch(seeds, wl=wl)
        on = make_init(
            wl, CFG, Build(plan_slots=plan.slots, latency=SPEC, retry=rt),
        )(seeds, rows)
        off = make_init(
            wl, CFG, Build(plan_slots=plan.slots, latency=SPEC),
        )(seeds, rows)
        p_on = str(tmp_path / "on.npz")
        p_off = str(tmp_path / "off.npz")
        save_checkpoint(p_on, on, CFG)
        save_checkpoint(p_off, off, CFG)
        with pytest.raises(ValueError, match="no retry policy"):
            load_checkpoint(p_on, wl, CFG)
        with pytest.raises(ValueError, match="retry.n_ops"):
            load_checkpoint(p_off, wl, CFG, Build(retry=rt))
        with pytest.raises(ValueError, match="retry columns"):
            load_checkpoint(
                p_on, wl, CFG, Build(retry=dataclasses.replace(rt, n_ops=8))
            )
        # the matching spec loads cleanly both ways
        assert np.asarray(load_checkpoint(p_on, wl, CFG, Build(retry=rt))
                          .rt_done).shape == (2, N_OPS)
        assert np.asarray(load_checkpoint(p_off, wl, CFG).rt_done).size == 0


# -------------------------------------------------- exactly-once oracle
_AP = 7  # the apply op under test (any user op id works for the oracle)


def _hist(seeds_rows):
    """Hand-built BatchHistory: per-seed lists of (op, key, arg,
    client, ok) rows — the COL_* order of check/history.py."""
    s = len(seeds_rows)
    h = max(len(r) for r in seeds_rows)
    word = np.zeros((s, h, 5), np.int32)
    t = np.zeros((s, h), np.int64)
    for i, rows in enumerate(seeds_rows):
        for j, r in enumerate(rows):
            word[i, j] = r
            t[i, j] = 10 * (j + 1)
    return BatchHistory(
        word=word, t=t,
        count=np.asarray([len(r) for r in seeds_rows], np.int32),
        drop=np.zeros(s, np.int32),
    )


# the oracle table: the three response shapes (OK / FAIL / PENDING)
# against the discriminating columns (client, key=op id)
_ORACLE = [
    # clean: one successful apply per (client, op id)
    ([(_AP, 1, 0, 0, OK_OK), (_AP, 2, 0, 0, OK_OK),
      (_AP, 1, 0, 1, OK_OK)], True),
    # duplicate success, same (client, op id): the violation
    ([(_AP, 1, 0, 0, OK_OK), (_AP, 2, 1, 0, OK_OK),
      (_AP, 1, 1, 0, OK_OK)], False),
    # FAIL response shape: a failed re-apply is not a double apply
    ([(_AP, 1, 0, 0, OK_OK), (_AP, 1, 1, 0, OK_FAIL)], True),
    # PENDING response shape: re-sent invokes are never counted
    ([(_AP, 1, 0, 0, OK_PENDING), (_AP, 1, 1, 0, OK_PENDING),
      (_AP, 1, 1, 0, OK_OK)], True),
    # same op id, different clients: two sessions may both apply
    ([(_AP, 1, 0, 0, OK_OK), (_AP, 1, 0, 1, OK_OK)], True),
    # other ops never counted, even duplicated
    ([(_AP + 1, 1, 0, 0, OK_OK), (_AP + 1, 1, 0, 0, OK_OK)], True),
]


class TestExactlyOnce:
    def test_oracle_table_numpy_equals_device(self):
        h = _hist([rows for rows, _ in _ORACLE])
        want = np.asarray([ok for _, ok in _ORACLE])
        got_np = check.exactly_once(h, _AP)
        assert np.array_equal(got_np, want)
        got_dev = np.asarray(dc.screen_ok(
            (dc.exactly_once(_AP),),
            jnp.asarray(h.word), jnp.asarray(h.t),
            jnp.asarray(h.count), jnp.asarray(h.drop),
        ))
        assert np.array_equal(got_dev, want)
        # the HistoryScreen host oracle is the numpy function itself
        assert np.array_equal(dc.exactly_once(_AP).host(h), got_np)

    def test_empty_history_is_clean(self):
        h = BatchHistory(word=np.zeros((3, 0, 5), np.int32),
                         t=np.zeros((3, 0), np.int64),
                         count=np.zeros(3, np.int32),
                         drop=np.zeros(3, np.int32))
        assert check.exactly_once(h, _AP).all()

    def test_real_batches_clean_and_mutant(self):
        """shardkv army under retries: the clean guard dedups every
        re-delivered attempt; noidem applies them all — and only
        exactly_once sees it (shard_coverage passes both ways)."""
        verdicts = {}
        for bug in (False, "noidem"):
            wl = SK.make_shardkv(record=True, chaos=False, army=True,
                                 bug=bug)
            pol = RetryPolicy(timeout_ns=8_000_000, max_attempts=3,
                              backoff_base_ns=4_000_000,
                              backoff_mult=2.0, jitter=0.25)
            plan = FaultPlan(
                (SK.client_army(n_ops=16, t_min_ns=5_000_000,
                                t_max_ns=280_000_000, retry=pol),
                 GrayFailure(targets=(0, 1), n_links=1, mult_min=8,
                             mult_max=16)),
                name="noidem-pin",
            )
            cfg = EngineConfig(pool_size=96, time_limit_ns=600_000_000)
            rt = plan.retry_spec()
            seeds = np.arange(8, dtype=np.uint64)
            init = make_init(
                wl, cfg,
                Build(
                    plan_slots=plan.slots, latency=LatencySpec(ops=16),
                    retry=rt,
                ),
            )
            run = jax.jit(make_run_while(
                wl, cfg, 3000, Build(latency=LatencySpec(ops=16), retry=rt),
            ))
            out = jax.block_until_ready(
                run(init(seeds, plan.compile_batch(seeds, wl=wl))))
            h = BatchHistory.from_state(out)
            v_np = check.exactly_once(h, SK.OP_ARMY_PUT)
            v_dev = np.asarray(dc.screen_ok(
                (dc.exactly_once(SK.OP_ARMY_PUT),),
                jnp.asarray(out.hist_word), jnp.asarray(out.hist_t),
                jnp.asarray(out.hist_count), jnp.asarray(out.hist_drop),
            ))
            assert np.array_equal(v_np, v_dev), bug
            # the final-state checker is blind to the double-applies
            assert np.asarray(check.shard_coverage(
                h, SK.OP_SHARD_OWN, SK.OP_SHARD_WRITE
            )).all(), bug
            verdicts[bug] = v_np
        assert verdicts[False].all()
        assert not verdicts["noidem"].all()


class TestCollapseRetries:
    def test_collapse_rule_pinned(self):
        """An invoke collapses iff an earlier invoke of its (client,
        op, key) group has no group response between them; collapsed
        rows get COL_OP cleared, nothing else moves."""
        rows = [
            (_AP, 1, 0, 0, OK_PENDING),  # first attempt
            (_AP, 1, 1, 0, OK_PENDING),  # re-send, no response between
            (_AP, 1, 1, 0, OK_OK),       # the response
            (_AP, 1, 2, 0, OK_PENDING),  # fresh invoke AFTER the response
            (_AP, 2, 0, 0, OK_PENDING),  # different key: untouched
        ]
        h = _hist([rows])
        c = check.collapse_retries(h)
        assert c.word[0, :, 0].tolist() == [_AP, 0, _AP, _AP, _AP]
        # only COL_OP of the collapsed row changed
        assert np.array_equal(c.word[..., 1:], np.asarray(h.word)[..., 1:])
        assert np.array_equal(c.t, h.t)
        assert np.array_equal(c.count, h.count)

    def test_numpy_equals_device(self):
        h = _hist([rows for rows, _ in _ORACLE]
                  + [[(_AP, 1, a, 0, OK_PENDING) for a in range(4)]])
        c_np = check.collapse_retries(h)
        c_dev = np.asarray(dc.collapse_retries_cols(
            jnp.asarray(h.word), jnp.asarray(h.count)
        ))
        assert np.array_equal(np.asarray(c_np.word), c_dev)


# ------------------------------------------------------- search wiring
class TestSearchWiring:
    def test_search_seeds_derives_retry_from_plan(self):
        """``search_seeds(plan=...)`` arms the timers from the plan's
        own RetryPolicy with no further wiring — the report's books
        show re-sends."""
        wl = _wl()
        plan = _plan(POLICY)
        ones = lambda v: np.ones(np.asarray(v["halted"]).shape[0], bool)  # noqa: E731
        r = search_seeds(
            wl, CFG, ones, n_seeds=4, max_steps=STEPS, plan=plan,
            latency=SPEC, metrics=True, require_halt=False,
        )
        assert np.asarray(r.met)[:, MET_RETRY].sum() > 0

    def test_two_policied_armies_refused(self):
        a = KV.client_army(n_ops=4, n_replicas=2, retry=POLICY)
        b = KV.client_army(n_ops=4, n_replicas=2, op_base=4,
                           retry=POLICY)
        plan = FaultPlan((a, b), name="double")
        with pytest.raises(ValueError, match="one retried op range"):
            plan.retry_spec()
        assert _plan(None).retry_spec() is None


# ------------------------------------------------- perfetto arrow labels
class TestPerfettoLabels:
    """Regression pin for the (op, attempt) arrow naming (ISSUE 20
    satellite: the Duplicate-class mis-anchors banked in CAUSAL_r13.txt
    are ambiguous re-sends — the label now disambiguates them)."""

    def _events(self, att):
        from madsim_tpu.engine.replay import ReplayEvent

        tok = retry_token(7, att)
        return [
            # the send: a dispatch at node 1 that emitted the message
            ReplayEvent(time_ns=1_000, kind=16, node=1, src=-1,
                        args=(0, 0), pay=()),
            # the delivery: src + emit anchor -> sidecar flow branch
            ReplayEvent(time_ns=5_000, kind=16, node=0, src=1,
                        args=(tok, 0), pay=(), emit_ns=1_000),
        ]

    def test_attempt_labeled_arrow(self):
        from madsim_tpu import obs

        doc = obs.to_perfetto(self._events(att=2))
        flows = [e for e in doc["traceEvents"] if e.get("cat") == "flow"]
        assert flows and all(
            e["name"] == "msg n1->n0 op7 try2" for e in flows
        )

    def test_attempt_zero_label_unchanged(self):
        """Off-policy (and first-attempt) tokens are plain op ids: the
        arrow name is byte-identical to the pre-retry exporter's."""
        from madsim_tpu import obs

        doc = obs.to_perfetto(self._events(att=0))
        flows = [e for e in doc["traceEvents"] if e.get("cat") == "flow"]
        assert flows and all(e["name"] == "msg n1->n0" for e in flows)

    def test_engine_kind_rows_never_decoded(self):
        """A chaos/engine row whose args alias the attempt bits must not
        grow a label — only user-kind deliveries carry op tokens."""
        from madsim_tpu.obs.perfetto import _flow_name
        from madsim_tpu.engine.replay import ReplayEvent

        e = ReplayEvent(time_ns=1, kind=2, node=0, src=1,
                        args=(retry_token(7, 2), 0), pay=())
        assert _flow_name(e) == "msg n1->n0"


# ----------------------------------------------------- soak-scale certs
@pytest.mark.slow
class TestSoakScale:
    def test_noidem_found_shrunk_replayed(self):
        """The acceptance path end-to-end: the noidem mutant is caught
        by the exactly_once hunt, ddmin-shrunk under the same policy,
        and the shrunk literal replays to the identical violation +
        trace (the LiteralPlan carries no policy, so replay passes the
        campaign's spec explicitly)."""
        wl = SK.make_shardkv(record=True, chaos=False, army=True,
                             bug="noidem")
        pol = RetryPolicy(timeout_ns=8_000_000, max_attempts=3,
                          backoff_base_ns=4_000_000, backoff_mult=2.0,
                          jitter=0.25)
        plan = FaultPlan(
            (SK.client_army(n_ops=16, t_min_ns=5_000_000,
                            t_max_ns=280_000_000, retry=pol),
             GrayFailure(targets=(0, 1), n_links=1, mult_min=8,
                         mult_max=16)),
            name="noidem-hunt",
        )
        cfg = EngineConfig(pool_size=96, time_limit_ns=600_000_000)
        rt = plan.retry_spec()

        def hinv(h):
            return check.exactly_once(h, SK.OP_ARMY_PUT)

        r = search_seeds(
            wl, cfg, None, n_seeds=32, max_steps=3000, plan=plan,
            history_invariant=hinv, latency=LatencySpec(ops=16),
            require_halt=False,
        )
        assert len(r.failing_seeds) > 0
        seed = int(r.failing_seeds[0])
        res = shrink_plan(wl, cfg, seed, plan, history_invariant=hinv,
                          max_steps=3000, latency=LatencySpec(ops=16))
        assert len(res.events) <= plan.slots
        rep = search_seeds(
            wl, cfg, None, seeds=np.asarray([seed], np.uint64),
            max_steps=3000, plan=res.plan, history_invariant=hinv,
            latency=LatencySpec(ops=16), require_halt=False, retry=rt,
        )
        assert not bool(np.asarray(rep.ok)[0])
        assert int(np.asarray(rep.traces)[0]) == int(res.trace)

    def test_retry_off_identity_soak_slice(self):
        wl = _wl()
        plan = _plan(None)
        seeds = np.arange(64, dtype=np.uint64)
        base = _run(wl, plan, seeds, None, layout="scatter", steps=3000)
        for kw in (dict(layout="dense"), dict(compact=True)):
            other = _run(wl, plan, seeds, None, steps=3000, **kw)
            for f in ("trace", "now", "halted", "met"):
                assert np.array_equal(np.asarray(getattr(base, f)),
                                      np.asarray(getattr(other, f)))
