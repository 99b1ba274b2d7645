"""The build spec (engine.Build): one resolution of the lowering, one
(init, run) cache for every user of the pair, and the build checks."""

import dataclasses

import numpy as np
import pytest

import jax

from madsim_tpu import obs
from madsim_tpu.chaos import FaultEvent, LiteralPlan, shrink_plan
from madsim_tpu.engine import (
    KIND_KILL,
    KIND_RESTART,
    Build,
    EngineConfig,
    LatencySpec,
    RetrySpec,
    check_plan_horizon,
    make_sweep,
    search_seeds,
)
from madsim_tpu.engine import search
from madsim_tpu.models import make_kvchaos, make_raft

WL = make_raft()
MAX_STEPS = 120
SEEDS = np.arange(4, dtype=np.uint64)


def _cfg(pool):
    return EngineConfig(pool_size=pool, clog_backoff_max_ns=2_000_000_000)


# (backend, layout, pool) -> the (layout, placement, pool_index, time32)
# the parent tree's resolvers gave for raft: resolve_layout, the pool
# index rule against the resolved layout, the 512-slot placement
# crossover, resolve_time32
RESOLUTION = [
    ("cpu", None, 40, "scatter", "rank", False, False),
    ("cpu", None, 512, "scatter", "rank", False, False),
    ("cpu", None, 513, "scatter", "scatter", False, False),
    ("cpu", None, 1024, "scatter", "scatter", False, False),
    ("cpu", None, 1025, "scatter", "scatter", False, False),
    ("cpu", None, 2048, "scatter", "scatter", True, False),
    ("cpu", None, 8192, "scatter", "scatter", True, False),
    ("cpu", "dense", 40, "dense", "rank", False, False),
    ("cpu", "dense", 512, "dense", "rank", False, False),
    ("cpu", "dense", 513, "dense", "scatter", False, False),
    ("cpu", "dense", 1024, "dense", "scatter", False, False),
    ("cpu", "dense", 1025, "dense", "scatter", False, False),
    ("cpu", "dense", 2048, "dense", "scatter", False, False),
    ("cpu", "dense", 8192, "dense", "scatter", False, False),
    ("cpu", "scatter", 40, "scatter", "rank", False, False),
    ("cpu", "scatter", 512, "scatter", "rank", False, False),
    ("cpu", "scatter", 513, "scatter", "scatter", False, False),
    ("cpu", "scatter", 1024, "scatter", "scatter", False, False),
    ("cpu", "scatter", 1025, "scatter", "scatter", False, False),
    ("cpu", "scatter", 2048, "scatter", "scatter", True, False),
    ("cpu", "scatter", 8192, "scatter", "scatter", True, False),
    ("tpu", None, 40, "dense", "rank", False, True),
    ("tpu", None, 512, "dense", "rank", False, True),
    ("tpu", None, 513, "dense", "scatter", False, True),
    ("tpu", None, 1024, "dense", "scatter", False, True),
    ("tpu", None, 1025, "dense", "scatter", False, True),
    ("tpu", None, 2048, "dense", "scatter", False, True),
    ("tpu", None, 8192, "dense", "scatter", False, True),
    ("tpu", "dense", 40, "dense", "rank", False, True),
    ("tpu", "dense", 512, "dense", "rank", False, True),
    ("tpu", "dense", 513, "dense", "scatter", False, True),
    ("tpu", "dense", 1024, "dense", "scatter", False, True),
    ("tpu", "dense", 1025, "dense", "scatter", False, True),
    ("tpu", "dense", 2048, "dense", "scatter", False, True),
    ("tpu", "dense", 8192, "dense", "scatter", False, True),
    ("tpu", "scatter", 40, "scatter", "rank", False, True),
    ("tpu", "scatter", 512, "scatter", "rank", False, True),
    ("tpu", "scatter", 513, "scatter", "scatter", False, True),
    ("tpu", "scatter", 1024, "scatter", "scatter", False, True),
    ("tpu", "scatter", 1025, "scatter", "scatter", False, True),
    ("tpu", "scatter", 2048, "scatter", "scatter", False, True),
    ("tpu", "scatter", 8192, "scatter", "scatter", False, True),
]


@pytest.mark.parametrize(
    "backend,layout,pool,want_layout,want_place,want_index,want_t32",
    RESOLUTION,
)
def test_resolution_table(monkeypatch, backend, layout, pool, want_layout,
                          want_place, want_index, want_t32):
    # the resolvers read the backend when they run: stand in for the chip
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    b = Build(layout=layout).resolve(WL, _cfg(pool))
    assert (b.layout, b.placement, b.pool_index, b.time32) == (
        want_layout, want_place, want_index, want_t32
    )


SPECS = {
    "auto": Build(),
    "dense": Build(layout="dense"),
    "indexed_t32": Build(layout="scatter", pool_index=True, time32=True),
    "taps": Build(cov_words=8, cov_hitcount=True, metrics=True,
                  timeline_cap=16, latency=LatencySpec(ops=4), causal=True),
}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_resolve_is_idempotent(name):
    once = SPECS[name].resolve(WL, _cfg(2048))
    twice = once.resolve(WL, _cfg(2048))
    assert twice == once and hash(twice) == hash(once)
    assert None not in (once.layout, once.placement, once.pool_index,
                        once.time32)
    # only the lowering is filled in
    assert dataclasses.replace(
        once, layout=None, placement=None, pool_index=None, time32=None,
    ) == dataclasses.replace(
        SPECS[name], layout=None, placement=None, pool_index=None,
        time32=None,
    )


def test_explicit_and_automatic_spec_share_one_entry():
    wl, cfg = make_raft(), _cfg(48)
    auto = search._compiled_run(wl, cfg, MAX_STEPS, Build())
    explicit = search._compiled_run(wl, cfg, MAX_STEPS, Build(
        layout="scatter", placement="rank", pool_index=False, time32=False,
    ))
    assert explicit is auto
    assert sum(e[2] is wl for e in search._RUN_CACHE.values()) == 1


def _always_fails(view):
    return np.zeros(len(view["halted"]), bool)


_PLAN = LiteralPlan(events=(
    FaultEvent(t=30_000_000, kind=KIND_KILL, a0=1, a1=0),
    FaultEvent(t=90_000_000, kind=KIND_RESTART, a0=1, a1=0),
))


def _via_make_sweep(wl, cfg):
    make_sweep(wl, cfg, MAX_STEPS, Build(metrics=True))


def _via_fleet_metrics(wl, cfg):
    obs.fleet_metrics(wl, cfg, seeds=SEEDS, max_steps=MAX_STEPS)


def _via_explain(wl, cfg):
    obs.explain(wl, cfg, 3, max_steps=MAX_STEPS, timeline_cap=16)


def _via_shrink(wl, cfg):
    shrink_plan(wl, cfg, 3, _PLAN, invariant=_always_fails,
                max_steps=MAX_STEPS)


# each path, and the search_seeds call with the same axes
SHARED = {
    "make_sweep": (_via_make_sweep, dict(metrics=True)),
    "fleet_metrics": (_via_fleet_metrics, dict(metrics=True)),
    "explain": (_via_explain, dict(metrics=True, timeline_cap=16)),
    "shrink_plan": (_via_shrink, dict(plan=_PLAN)),
}


@pytest.mark.parametrize("path", sorted(SHARED))
def test_paths_share_the_search_cache_entry(path):
    via, axes = SHARED[path]
    wl, cfg = make_raft(), _cfg(48)
    via(wl, cfg)
    search_seeds(wl, cfg, _always_fails, seeds=SEEDS, max_steps=MAX_STEPS,
                 require_halt=False, **axes)
    assert sum(e[2] is wl for e in search._RUN_CACHE.values()) == 1


_ARMY = make_kvchaos(writes=12, n_replicas=2, chaos=False, army=True)
# a 3 s response timeout: past the int32 offset horizon (~2.1 s)
_RETRY = RetrySpec(kind=16, node=0, op_base=0, n_ops=4,
                   timeout_ns=3_000_000_000)

INVALID = {
    "cov_words": (WL, 48, dict(cov_words=3), ValueError, "power of two"),
    "hitcount_without_coverage": (
        WL, 48, dict(cov_hitcount=True), ValueError,
        "cov_hitcount=True needs coverage"),
    "timeline_cap": (WL, 48, dict(timeline_cap=-1), ValueError,
                     "timeline_cap=-1 must be >= 0"),
    "latency_type": (WL, 48, dict(latency=8), TypeError,
                     "latency must be a LatencySpec"),
    "retry_type": (WL, 48, dict(retry=3), TypeError,
                   "retry must be a RetrySpec"),
    "retry_without_markers": (WL, 48, dict(retry=_RETRY), ValueError,
                              "latency markers"),
    "layout": (WL, 48, dict(layout="bogus"), ValueError, "unknown layout"),
    "placement": (WL, 48, dict(placement="bogus"), ValueError,
                  "unknown placement"),
    "index_under_dense": (WL, 2048, dict(layout="dense", pool_index=True),
                          ValueError, "scatter-layout lowering"),
    "index_without_tiles": (WL, 2049, dict(pool_index=True), ValueError,
                            "no tile divisor"),
    "time32_ineligible": (WL, None, dict(time32=True), ValueError,
                          "not eligible"),
    "retry_past_horizon": (_ARMY, 96, dict(
        time32=True, latency=LatencySpec(ops=4), retry=_RETRY,
    ), ValueError, "exceeds the int32 offset horizon"),
}


@pytest.mark.parametrize("name", sorted(INVALID))
def test_invalid_combinations_raise(name):
    wl, pool, kw, err, match = INVALID[name]
    # pool None: the default 10 s clog backoff, past the int32 horizon
    cfg = EngineConfig(pool_size=48) if pool is None else _cfg(pool)
    with pytest.raises(err, match=match):
        Build(**kw).resolve(wl, cfg)


@pytest.mark.parametrize("space", [False, True])
def test_plan_horizon_guard(space):
    cfg = _cfg(48)
    t32 = Build(time32=True).resolve(WL, cfg)
    lim = 2**31 - 1 - cfg.proc_max_ns - 1
    check_plan_horizon(t32, cfg, lim, space=space)
    # an int64 build holds any time
    check_plan_horizon(Build(time32=False).resolve(WL, cfg), cfg, 2**40,
                       space=space)
    match = ("plan-space window reaches" if space
             else "fault-plan event at") + f" t={lim + 1} ns"
    with pytest.raises(ValueError, match=match):
        check_plan_horizon(t32, cfg, lim + 1, space=space)
