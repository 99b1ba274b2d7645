"""madsim_tpu.lint.absint: the interval walker on hand-built jaxprs,
the overflow/lane provers over the engine, both planted mutants, and
the checked absint pragma allowlist."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from madsim_tpu.engine import Build, EngineConfig, Workload
from madsim_tpu.engine.rng import PURPOSE_LANES, lane, lane_of
from madsim_tpu.lint import (
    absint_matrix,
    absint_model_matrix,
    absint_pragma_inventory,
    analyze_intervals,
    check_ranges,
    plant_lane_collision,
    plant_time32_sentinel_decay,
    stale_absint_pragmas,
)
from madsim_tpu.lint.absint import ABSINT_AXES, AVal
from madsim_tpu.lint.rules import lint_source
from madsim_tpu.models import make_raft

CFG = EngineConfig(pool_size=40, loss_p=0.02, clog_backoff_max_ns=2_000_000_000)
H = 60 * 1_000_000_000

# each check_ranges call traces a full step program — share the
# expensive reports across tests (module-scope fixtures)


@pytest.fixture(scope="module")
def rep_int64():
    return check_ranges(
        make_raft(record=True), CFG,
        Build(
            layout="scatter", time32=False, metrics=True, timeline_cap=8,
            cov_words=8,
        ),
        entry="step", horizon_ns=H,
    )


@pytest.fixture(scope="module")
def rep_t32_indexed():
    return check_ranges(
        make_raft(record=True), CFG,
        Build(layout="scatter", time32=True, pool_index=True), entry="step",
        horizon_ns=H,
    )


def _iv(lo, hi, tag=None):
    tags = frozenset({tag}) if tag else frozenset()
    return AVal(lo, hi, tags, None, (lo, hi))


class TestIntervalWalker:
    """The walker on hand-built jaxprs — every construct the engine's
    step/run programs route ranges through."""

    def test_add_mul_propagation(self):
        def f(x, y):
            return x + y, x * y, x - jnp.int64(5)

        closed = jax.make_jaxpr(f)(jnp.int64(0), jnp.int64(0))
        w = analyze_intervals(closed, [_iv(0, 10), _iv(2, 3)])
        assert (w.out[0].lo, w.out[0].hi) == (2, 13)
        assert (w.out[1].lo, w.out[1].hi) == (0, 30)
        assert (w.out[2].lo, w.out[2].hi) == (-5, 5)
        assert not w.findings

    def test_overflow_flagged_only_when_tracked_and_signed(self):
        def f(x):
            return x + jnp.int32(1)

        closed = jax.make_jaxpr(f)(jnp.int32(0))
        top = 2**31 - 1
        # tracked time tag + signed overflow -> finding with the site
        w = analyze_intervals(closed, [_iv(0, top, "time:now")])
        assert len(w.findings) == 1
        assert w.findings[0]["rule"] == "absint-overflow"
        assert w.findings[0]["sources"] == ["time:now"]
        assert w.findings[0]["chain"]
        # same range, untagged -> wraps silently (not a tracked value)
        w2 = analyze_intervals(closed, [_iv(0, top)])
        assert not w2.findings
        assert (w2.out[0].lo, w2.out[0].hi) == (-(2**31), 2**31 - 1)

        # unsigned arithmetic is modular by definition: never flagged
        def g(x):
            return x + jnp.uint32(1)

        closedu = jax.make_jaxpr(g)(jnp.uint32(0))
        w3 = analyze_intervals(
            closedu, [_iv(0, 2**32 - 1, "counter:step")]
        )
        assert not w3.findings

    def test_scan_fixpoint_widens_untagged_carry(self):
        # carry grows every iteration: only widening terminates, and
        # the result must cover the divergence (dtype range)
        def f(c, xs):
            def body(carry, x):
                return carry + x, carry

            return lax.scan(body, c, xs)

        closed = jax.make_jaxpr(f)(jnp.int64(0), jnp.arange(3))
        # no contract on the carry: nothing narrows the divergence
        w = analyze_intervals(closed, [AVal(0, 1), AVal(1, 1)])
        assert w.out[0].hi == 2**63 - 1
        assert not w.findings  # untagged: growth is not a finding

    def test_scan_contract_narrowing_keeps_tagged_carry_bounded(self):
        # the assume-guarantee boundary: a carry with a declared
        # contract re-enters each iteration narrowed to it, so bounded
        # increments never diverge and no overflow is reported
        def f(c, xs):
            def body(carry, x):
                return carry + x, carry

            return lax.scan(body, c, xs)

        closed = jax.make_jaxpr(f)(jnp.int64(0), jnp.arange(3))
        w = analyze_intervals(
            closed, [_iv(0, 1000, "time:now"), AVal(0, 5)]
        )
        assert not w.findings
        # one body application past the contract at most
        assert w.out[0].hi <= 1005

    def test_cond_branch_join(self):
        def f(p, x, y):
            return lax.cond(p, lambda: x + jnp.int64(1), lambda: y)

        closed = jax.make_jaxpr(f)(True, jnp.int64(0), jnp.int64(0))
        w = analyze_intervals(
            closed, [AVal(0, 1), _iv(10, 20), _iv(-5, 0)]
        )
        assert (w.out[0].lo, w.out[0].hi) == (-5, 21)

    def test_while_fixpoint_terminates(self):
        def f(x):
            return lax.while_loop(
                lambda c: c[1] < 8, lambda c: (c[0] + c[1], c[1] + 1),
                (x, jnp.int64(0)),
            )

        closed = jax.make_jaxpr(f)(jnp.int64(0))
        w = analyze_intervals(closed, [_iv(0, 4)])
        assert w.out[0].hi == 2**63 - 1  # widened, terminated

    def test_unknown_prim_conservative_top(self):
        def f(x):
            return jnp.cumprod(x)  # no transfer implemented

        closed = jax.make_jaxpr(f)(jnp.arange(4))
        w = analyze_intervals(closed, [_iv(1, 2, "counter:met")])
        assert (w.out[0].lo, w.out[0].hi) == (-(2**63), 2**63 - 1)
        assert "counter:met" in w.out[0].tags  # tags still flow

    def test_onehot_sum_refinement(self):
        # sum(where(m, x, 0)) is the engine's pick idiom: modeled as a
        # pick (hull with 0) under the default trust, as n*x without it
        def f(m, x):
            return jnp.sum(jnp.where(m, x, 0))

        closed = jax.make_jaxpr(f)(np.zeros(8, bool), np.zeros(8, np.int64))
        ivs = [AVal(0, 1), _iv(5, 100, "time:ev_time")]
        w = analyze_intervals(closed, ivs, onehot_sums=True)
        assert (w.out[0].lo, w.out[0].hi) == (0, 100)
        w2 = analyze_intervals(closed, ivs, onehot_sums=False)
        assert w2.out[0].hi == 800

    def test_meta_unpack_stays_bounded(self):
        # the ev_meta byte decode: full uint32 word -> [0, 255] bytes
        def f(meta):
            return ((meta >> jnp.uint32(8)) & jnp.uint32(0xFF)).astype(
                jnp.int32
            ) - 1

        closed = jax.make_jaxpr(f)(jnp.uint32(0))
        w = analyze_intervals(closed, [AVal(0, 2**32 - 1)])
        assert (w.out[0].lo, w.out[0].hi) == (-1, 254)


class TestOverflowProver:
    def test_int64_step_proves_clean(self, rep_int64):
        assert rep_int64.ok, rep_int64.summary()
        assert rep_int64.checked_ops > 20
        assert rep_int64.n_eqns > 500

    def test_time32_indexed_step_proves_clean_via_pragmas(
        self, rep_t32_indexed
    ):
        # the stale-slot rebases are the acknowledged wrap surface:
        # the proof holds exactly because those three sites carry
        # checked per-site pragmas (core.py), not a blanket exclusion
        assert rep_t32_indexed.ok, rep_t32_indexed.summary()
        files = {p[0] for p in rep_t32_indexed.used_pragmas}
        assert files == {"madsim_tpu/engine/core.py"}
        assert len(rep_t32_indexed.used_pragmas) == 3
        assert len(rep_t32_indexed.allowed) >= 3

    def test_run_entry_scan_path(self):
        rep = check_ranges(
            make_raft(record=True), CFG, Build(layout="scatter", time32=False),
            entry="run", horizon_ns=H, n_steps=3,
        )
        assert rep.ok, rep.summary()

    def test_sentinel_decay_mutant_caught_with_chain(self):
        rep = check_ranges(
            make_raft(record=True), CFG,
            Build(layout="scatter", time32=True, pool_index=True),
            entry="step", horizon_ns=H, mutate=plant_time32_sentinel_decay,
        )
        assert not rep.ok
        hits = [
            f for f in rep.findings
            if f["rule"] == "absint-overflow"
            and any(t.endswith("tile_min") for t in f["sources"])
        ]
        assert hits, rep.findings
        f = hits[0]
        # the chain cites the mutant's own (un-pragma'd) site — the
        # SimState vocabulary names the wrapped column
        assert f["chain"]
        assert f["file"] == "madsim_tpu/lint/absint.py"
        assert f["dtype"] == "int32"

    def test_shared_mutant_controls_catch_both(self):
        # the one control recipe the soak gates share (lint_soak cert
        # 5, absint_soak cert 2): both planted mutants judged caught
        from madsim_tpu.lint import run_mutant_controls

        controls = run_mutant_controls()
        assert [n for n, _r, _c in controls] == [
            "time32-sentinel-decay", "lane-collision",
        ]
        assert all(caught for _n, _r, caught in controls)

    def test_mutant_requires_the_indexed_time32_build(self):
        mut = plant_time32_sentinel_decay
        step = lambda st: st  # noqa: E731 — shape probe only
        from madsim_tpu.engine import make_init

        st = make_init(make_raft(), CFG, Build(pool_index=False))(
            np.zeros(1, np.uint64)
        )
        tmpl = jax.tree.map(lambda a: a[0], st)
        with pytest.raises(ValueError, match="pool_index"):
            mut(step)(tmpl)


class TestLaneProver:
    def test_engine_lanes_resolve_and_disjoint(self, rep_int64):
        assert rep_int64.ok
        # raft prefetches its one user purpose, so the whole step is
        # ONE batched cipher site covering the engine + user lanes
        assert len(rep_int64.lane_sites) == 1
        assert {"poll_cost", "latency", "user"} <= set(rep_int64.lanes)

    def test_dup_axis_lights_the_dup_lane(self):
        rep = check_ranges(
            make_raft(record=True), CFG,
            Build(layout="scatter", time32=False, dup_rows=True), entry="step",
            horizon_ns=H,
        )
        assert rep.ok, rep.summary()
        assert "dup" in rep.lanes

    def test_lane_collision_mutant_caught(self):
        rep = check_ranges(
            make_raft(record=True), CFG, Build(layout="scatter", time32=False),
            entry="step", horizon_ns=H, mutate=plant_lane_collision,
        )
        assert not rep.ok
        hits = [f for f in rep.findings if f["rule"] == "absint-lane"]
        assert hits
        assert len(hits[0]["sites"]) == 2  # both colliding sites cited

    def test_registry_is_sorted_and_disjoint(self):
        prev_end = 0
        for ln in PURPOSE_LANES:
            assert ln.base >= prev_end
            assert ln.end <= 1 << 32
            prev_end = ln.end
        assert lane_of(lane("latency").base + 5).name == "latency"
        assert lane_of(7) is None  # unassigned gap below latency

    def test_huge_purpose_rejected_before_uint32_wrap(self):
        # a purpose >= 2^32 wraps back onto a small lane at draw time
        # (Draw.user casts to uint32) — validation must reject the RAW
        # offset, not the wrapped absolute (which would look in-lane)
        from madsim_tpu.engine.rng import validate_user_purposes

        with pytest.raises(ValueError, match="outside the user lane"):
            validate_user_purposes((1 << 32,))
        with pytest.raises(ValueError, match="outside the user lane"):
            validate_user_purposes(((1 << 32) + 5,))
        with pytest.raises(ValueError):
            validate_user_purposes((-1,))

    def test_clamp_hull_respects_variable_lower_bound(self):
        # clamp with a variable LOWER bound can RAISE x: the sound
        # hull must include the bound's upper corner, else a tracked
        # add downstream could be certified clean while wrapping
        def f(lo, x):
            return lax.clamp(lo, x, jnp.int64(2**62))

        closed = jax.make_jaxpr(f)(jnp.int64(0), jnp.int64(0))
        w = analyze_intervals(
            closed, [_iv(0, 2**61, "time:now"), _iv(0, 10)]
        )
        assert w.out[0].hi == 2**61
        assert w.out[0].lo == 0

    def test_draw_purposes_validated_against_registry(self):
        # an out-of-range user lane used to alias the plan block
        # silently; now the build fails naming the aliased lane
        user_width = lane("user").width
        with pytest.raises(ValueError, match="plan"):
            Workload(
                name="bad", n_nodes=1, state_width=1,
                handlers=(lambda ctx: (ctx.state, None),),
                draw_purposes=(user_width,),
            )
        with pytest.raises(ValueError, match="duplicates"):
            Workload(
                name="bad", n_nodes=1, state_width=1,
                handlers=(lambda ctx: (ctx.state, None),),
                draw_purposes=(3, 3),
            )


class TestPragmaHygiene:
    def test_stale_absint_pragma_reported(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text("x = 1  # lint: allow(absint-overflow)\n")
        inv = absint_pragma_inventory(paths=[f], root=tmp_path)
        assert inv == [("mod.py", 1, "absint-overflow")]
        stale = stale_absint_pragmas(set(), paths=[f], root=tmp_path)
        assert len(stale) == 1 and stale[0]["rule"] == "unused-allow"
        # an exercised pragma is not stale
        assert not stale_absint_pragmas(
            {("mod.py", 1, "absint-overflow")}, paths=[f], root=tmp_path
        )

    def test_ast_linter_leaves_absint_pragmas_to_the_prover(self):
        res = lint_source("x = 1  # lint: allow(absint-overflow)\n")
        assert not res.findings  # not an AST-side unused-allow
        # but a stale AST-rule pragma is still a finding
        res2 = lint_source("x = 1  # lint: allow(np-random)\n")
        assert [f.rule for f in res2.findings] == ["unused-allow"]

    def test_repo_absint_pragmas_live_only_in_the_engine(self):
        # pragma creep guard: today's allowlist is exactly the three
        # stale-slot rebase sites in engine/core.py — growing it is a
        # deliberate act this pin makes visible
        inv = absint_pragma_inventory()
        assert {p[0] for p in inv} == {"madsim_tpu/engine/core.py"}
        assert len(inv) == 3


@pytest.mark.slow
class TestFullMatrix:
    """The full layout-matrix sweep — the ~870s tier-1 budget is
    protected by the smoke above; this is the soak-scale gate."""

    def test_full_step_matrix_proves_clean(self):
        reports = absint_matrix()
        bad = [r.summary() for r in reports if not r.ok]
        assert not bad, bad

    def test_run_entry_matrix_proves_clean(self):
        reports = absint_matrix(
            axes={"all": ABSINT_AXES["all"]},
            layouts=(
                Build(layout="scatter", time32=False),
                Build(layout="scatter", time32=True, pool_index=True),
            ),
            entry="run",
        )
        bad = [r.summary() for r in reports if not r.ok]
        assert not bad, bad

    def test_matrix_names_every_recorded_model(self):
        tags = {m[0] for m in absint_model_matrix()}
        assert {
            "raft/record", "raftlog/durable", "kvchaos/army",
            "paxos/record",
        } <= tags
