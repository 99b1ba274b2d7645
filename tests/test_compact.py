"""Seed-compaction runner vs the plain lockstep loop — bit-identical.

Seeds are independent rows under vmap, so banking halted rows out of the
batch must not change any row's results. These tests assert per-seed
equality of every reported field (except ``step``, the RNG coordinate —
documented divergence: lockstep keeps counting for halted rows, the
compactor stops once a row is banked; halted rows make no draws, so the
difference is unobservable).
"""

import numpy as np
import pytest

import jax

from madsim_tpu.engine import (
    Build,
    EngineConfig,
    make_init,
    make_run_compacted,
    make_run_while,
)
from madsim_tpu.engine.compact import RESULT_FIELDS
from madsim_tpu.models import BENCH_SPECS

COMPARE_FIELDS = tuple(f for f in RESULT_FIELDS if f != "step")


def _run_both(name, n_seeds, max_steps, shrink=2, min_size=8):
    factory, kw, _, _ = BENCH_SPECS[name]
    wl, cfg = factory(), EngineConfig(**kw)
    init = make_init(wl, cfg)
    seeds = np.arange(n_seeds, dtype=np.uint64)
    ref = jax.jit(make_run_while(wl, cfg, max_steps))(init(seeds))
    ref = jax.block_until_ready(ref)
    out = make_run_compacted(
        wl, cfg, max_steps, shrink=shrink, min_size=min_size
    )(init(seeds))
    return ref, out


@pytest.mark.parametrize(
    "name",
    ["raft"]
    + [
        pytest.param(n, marks=pytest.mark.slow)
        for n in ["broadcast", "kvchaos"]
    ],
)
def test_compacted_equals_lockstep(name):
    """Full runs (every seed halts) across three workload families,
    including kill/restart + clog chaos (kvchaos)."""
    ref, out = _run_both(name, n_seeds=64, max_steps=2000)
    assert bool(np.asarray(ref.halted).all()), "test needs a halting run"
    for f in COMPARE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), getattr(out, f), err_msg=f
        )


def test_compacted_equals_lockstep_at_step_cap():
    """Rows still live when max_steps hits are frozen identically."""
    ref, out = _run_both("raft", n_seeds=64, max_steps=9)
    assert not bool(np.asarray(ref.halted).all()), "cap must hit mid-run"
    for f in COMPARE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), getattr(out, f), err_msg=f
        )


def test_degenerate_schedule_is_single_phase():
    """min_size >= n_seeds: one phase, still correct. n_seeds matches
    test_compacted_equals_lockstep[raft] so the lockstep reference is
    the SAME program (persistent-cache hit on a cold run)."""
    ref, out = _run_both("raft", n_seeds=64, max_steps=2000, min_size=64)
    for f in COMPARE_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), getattr(out, f), err_msg=f
        )


def _obs_case():
    """kvchaos's client army under a gray failure with every tap on:
    2-D and 3-D, int64 and uint32 columns are non-empty."""
    from madsim_tpu.chaos import FaultPlan, GrayFailure
    from madsim_tpu.engine import LatencySpec
    from madsim_tpu.models import kvchaos as kv

    wl = kv.make_kvchaos(writes=12, n_replicas=2, chaos=False, army=True)
    plan = FaultPlan((
        kv.client_army(n_ops=16, t_min_ns=5_000_000, t_max_ns=280_000_000,
                       n_replicas=2),
        GrayFailure(targets=(0, 3), n_links=1, mult_min=6, mult_max=12),
    ), name="pack-obs")
    taps = dict(cov_words=8, metrics=True, timeline_cap=16, causal=True,
                latency=LatencySpec(ops=16, phases=3, phase_ns=1 << 27))
    cfg = EngineConfig(pool_size=64, time_limit_ns=450_000_000)
    return wl, cfg, plan, taps, {}


def _screened_case():
    """kvchaos under a crash storm with both history screens: the
    history columns cross trimmed to the longest surviving count."""
    from madsim_tpu.chaos import CrashStorm, FaultPlan
    from madsim_tpu.check import device as dc
    from madsim_tpu.models import make_kvchaos

    wl = make_kvchaos(writes=5, record=True)
    plan = FaultPlan((CrashStorm(targets=(1, 2, 3, 4), n=2),), name="kv")
    cfg = EngineConfig(pool_size=40, loss_p=0.02,
                       clog_backoff_max_ns=2_000_000_000)
    screens = (dc.stale_reads(), dc.read_your_writes())
    return wl, cfg, plan, {}, {"hist_screen": screens}


def _bench_case(name):
    factory, kw, _, _ = BENCH_SPECS[name]
    return factory(), EngineConfig(**kw), None, {}, {}


# (case, seeds, shrink, min_size, banks)
PACK_CASES = {
    "raft_3_banks": (lambda: _bench_case("raft"), 512, 4, 32, 3),
    "kvchaos_screened": (_screened_case, 64, 2, 8, 4),
    "obs_taps": (_obs_case, 32, 2, 8, 3),
    "single_phase": (lambda: _bench_case("raft"), 64, 2, 64, 1),
}


@pytest.mark.parametrize("case", sorted(PACK_CASES))
def test_packed_assemble_equals_bank_scatter(case):
    """The un-permute and pack on the device, then one copy, give
    field for field (dtype, shape, values, writability) what the banks'
    host scatter gives on the same run."""
    build, n_seeds, shrink, min_size, n_banks = PACK_CASES[case]
    wl, cfg, plan, taps, run_kw = build()
    seeds = np.arange(n_seeds, dtype=np.uint64)
    b = Build(
        plan_slots=0 if plan is None else plan.slots,
        dup_rows=plan is not None and plan.uses_dup(), **taps,
    )
    init = make_init(wl, cfg, b)
    st = init(seeds) if plan is None else init(
        seeds, plan.compile_batch(seeds, wl=wl))
    run = make_run_compacted(
        wl, cfg, 2000, b, shrink=shrink, min_size=min_size, **run_kw,
    )
    banked = jax.jit(run.phases)(st)
    assert len(banked) == n_banks
    want = run.assemble_banks(banked)
    got = run.assemble(run.compute(st))
    assert sorted(vars(got)) == sorted(vars(want))
    for f, w in vars(want).items():
        g = getattr(got, f)
        assert (g.dtype, g.shape) == (w.dtype, w.shape), f
        np.testing.assert_array_equal(g, w, err_msg=f)
        assert g.flags.writeable, f
    if case == "obs_taps":
        for f in ("cov", "met", "tl_t", "tl_meta", "tl_args", "lam",
                  "tl_parent", "lat_hist"):
            assert getattr(got, f).size, f
    if case == "kvchaos_screened":
        assert 0 < got.hist_count.max() < got.hist_word.shape[1]
