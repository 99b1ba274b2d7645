"""Dispatch-amortized throughput measurement for the batched engine.

A sub-second measured cell (raft @65,536 seeds runs well under a
second) is dominated by per-dispatch overhead and host-clock noise,
not by the simulation. The fix is structural, not statistical: make
each *dispatch* long enough that the overhead is amortized to nothing,
then take the median over a handful of dispatches.

``make_repeat_program`` builds ONE jitted program that runs ``repeats``
independent seed-batches back-to-back on device (a ``lax.fori_loop``
whose body is the full compacted phase program on a fresh batch of
seeds), returning only scalar reductions (total simulated ns, overflow
count, halted count). One dispatch -> one overhead sample, regardless of
how much simulation rides inside it.

``measure_throughput`` calibrates the single-batch wall, picks
``repeats`` so a dispatch lasts >= ``target_wall_s`` (default 5 s),
and reports the median sim-s/s over
``n_measure`` dispatches with min/max spread.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .compact import make_run_compacted
from .core import Build, EngineConfig, Workload, make_init

__all__ = [
    "make_repeat_program",
    "measure_throughput",
    "measure_latency",
]


def make_repeat_program(
    wl: Workload,
    cfg: EngineConfig,
    max_steps: int,
    n_seeds: int,
    seed_mod: int,
    build: Build = Build(),
    shrink: int = 4,
    min_size: int = 2048,
):
    """Build ``program(seed_base, repeats) -> (sim_ns, overflow, halted)``.

    Runs ``repeats`` batches of ``n_seeds`` seeds (values
    ``(seed_base + r*n_seeds + i) % seed_mod``) through the compacted
    phase program inside one jitted ``fori_loop`` and reduces each to
    scalars: total simulated nanoseconds, total pool-overflow count,
    total halted-row count (== repeats*n_seeds iff every seed halted).

    ``repeats`` is a *runtime* argument (dynamic trip count), so one
    compile serves both the calibration run and the sized run.
    ``seed_mod`` keeps every seed inside the range the config's pool
    size was verified overflow-free for (models.BENCH_SPECS sizing) —
    without it, long measurement sessions would drift seeds millions
    past the verified range; repeated seed values across repeats are
    identical work, which is exactly what a throughput measure wants.
    """
    if seed_mod < n_seeds:
        raise ValueError(f"seed_mod={seed_mod} must be >= n_seeds={n_seeds}")
    init = make_init(wl, cfg, build)
    run = make_run_compacted(
        wl, cfg, max_steps, build,
        shrink=shrink, min_size=min_size, fields=("now", "overflow", "halted"),
    )

    def program(seed_base, repeats):
        seed_base = jnp.asarray(seed_base, jnp.uint64)
        lanes = jnp.arange(n_seeds, dtype=jnp.uint64)

        def body(r, acc):
            sim_ns, ovf, halted = acc
            seeds = (
                seed_base + jnp.uint64(r) * jnp.uint64(n_seeds) + lanes
            ) % jnp.uint64(seed_mod)
            banked = run.phases(init(seeds))
            for b in banked:
                sim_ns = sim_ns + jnp.sum(b["now"]).astype(jnp.int64)
                ovf = ovf + jnp.sum(b["overflow"]).astype(jnp.int64)
                halted = halted + jnp.sum(b["halted"]).astype(jnp.int64)
            return (sim_ns, ovf, halted)

        return lax.fori_loop(
            0, repeats, body,
            (jnp.int64(0), jnp.int64(0), jnp.int64(0)),
        )

    return jax.jit(program)


def _calibrate_and_measure(
    program,
    n_seeds: int,
    target_wall_s: float,
    n_measure: int,
    seed_base: int,
    max_repeats: int,
    cal_repeats: int = 1,
):
    """Shared sizing + timing scaffold for the measure_* entry points.

    Compile, calibrate with one ``cal_repeats``-sized dispatch, pick
    ``repeats`` to reach ``target_wall_s``, then grow it until the
    realized wall does (the calibration dispatch rides the very jitter
    — or cache warm-up — this harness defeats, so a bad sample there
    would mis-size every measured cell; each probe doubles as a warm
    run). Returns ``(repeats, cal_wall, walls, sims, ovf_tot,
    halted_min)`` over ``n_measure`` timed dispatches.
    """
    jax.block_until_ready(program(np.uint64(seed_base), 1))  # compile
    t0 = time.perf_counter()  # lint: allow(wall-clock)
    jax.block_until_ready(program(np.uint64(seed_base), cal_repeats))
    cal_wall = time.perf_counter() - t0  # lint: allow(wall-clock)

    repeats = min(
        max(
            cal_repeats,
            int(np.ceil(target_wall_s / max(cal_wall / cal_repeats, 1e-9))),
        ),
        max_repeats,
    )
    for _ in range(8):
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        jax.block_until_ready(program(np.uint64(seed_base), repeats))
        sized_wall = time.perf_counter() - t0  # lint: allow(wall-clock)
        if sized_wall >= target_wall_s * 0.6 or repeats >= max_repeats:
            break
        per_rep = sized_wall / repeats
        repeats = min(
            max(repeats + 1, int(np.ceil(target_wall_s / max(per_rep, 1e-9)))),
            max_repeats,
        )

    walls, sims, ovf_tot, halted_min = [], [], 0, None
    for m in range(n_measure):
        base = np.uint64(seed_base + (m + 1) * repeats * n_seeds)
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        sim_ns, ovf, halted = jax.block_until_ready(program(base, repeats))
        walls.append(time.perf_counter() - t0)  # lint: allow(wall-clock)
        sims.append(int(sim_ns) / 1e9)
        ovf_tot += int(ovf)
        h = int(halted)
        halted_min = h if halted_min is None else min(halted_min, h)
    return repeats, cal_wall, walls, sims, ovf_tot, halted_min


def measure_throughput(
    wl: Workload,
    cfg: EngineConfig,
    max_steps: int,
    n_seeds: int,
    target_wall_s: float = 5.0,
    n_measure: int = 5,
    seed_base: int = 0,
    seed_mod: int = 131072,
    max_repeats: int = 4096,
    build: Build = Build(),
    shrink: int = 4,
    min_size: int = 2048,
) -> dict:
    """Measure sim-s/s with >= ``target_wall_s``-long dispatches.

    Returns a dict with the median rate over ``n_measure`` timed
    dispatches plus the full per-dispatch walls, the repeat count, and
    correctness counters (overflow must be 0 and halted must equal
    seeds*repeats for the rate to be quotable — callers check).
    ``seed_mod`` must cover only seeds the config's pool size is
    verified overflow-free for (see make_repeat_program, which raises
    if it can't hold one batch).
    """
    program = make_repeat_program(
        wl, cfg, max_steps, n_seeds, seed_mod, build, shrink, min_size
    )
    repeats, cal_wall, walls, sims, ovf_tot, halted_min = _calibrate_and_measure(
        program, n_seeds, target_wall_s, n_measure, seed_base, max_repeats
    )

    # rate per dispatch = its OWN simulated seconds / its wall (seed
    # blocks differ, so sim time varies slightly across dispatches)
    rates = np.asarray(sims) / np.asarray(walls)
    return {
        "n_seeds": n_seeds,
        "repeats": int(repeats),
        "calibration_wall_s": round(cal_wall, 4),
        "dispatch_walls_s": [round(w, 4) for w in walls],
        "sim_s_per_dispatch": [round(s, 3) for s in sims],
        "sim_s_per_s_median": round(float(np.median(rates)), 1),
        "sim_s_per_s_min": round(float(rates.min()), 1),
        "sim_s_per_s_max": round(float(rates.max()), 1),
        "spread_pct": round(
            100.0 * (rates.max() - rates.min()) / max(float(np.median(rates)), 1e-9),
            1,
        ),
        "overflow": ovf_tot,
        "all_halted": halted_min == repeats * n_seeds,
    }


def measure_latency(
    wl: Workload,
    cfg: EngineConfig,
    max_steps: int,
    target_wall_s: float = 3.5,
    n_measure: int = 3,
    seed_base: int = 0,
    seed_mod: int = 131072,
    max_repeats: int = 131072,
    build: Build = Build(),
) -> dict:
    """Wall microseconds per complete single-seed sim, sized dispatches.

    The latency analog of :func:`measure_throughput` for deliberately
    single-seed configs (BASELINE's pingpong): one seed cannot amortize
    dispatch overhead into a throughput quote, so instead ``repeats``
    independent single-seed sims are packed into one multi-second
    dispatch and the quote is median wall-per-sim. Same correctness
    contract: ``overflow`` must be 0 and ``all_halted`` True for the
    number to be quotable — callers check.
    """
    program = make_repeat_program(
        wl, cfg, max_steps, 1, seed_mod, build, min_size=1
    )
    # cal_repeats=32: a single 1-seed run is far too short to time
    repeats, cal_wall, walls, sims, ovf_tot, halted_min = _calibrate_and_measure(
        program, 1, target_wall_s, n_measure, seed_base, max_repeats,
        cal_repeats=32,
    )

    lat_us = np.asarray(walls) / repeats * 1e6
    med = float(np.median(lat_us))
    return {
        "n_seeds": 1,
        "repeats": int(repeats),
        "calibration_wall_s": round(cal_wall, 4),
        "dispatch_walls_s": [round(w, 4) for w in walls],
        "wall_us_per_sim_median": round(med, 2),
        "spread_pct": round(
            100.0 * float(lat_us.max() - lat_us.min()) / max(med, 1e-9), 1
        ),
        "sim_s_per_s": round(float(np.sum(sims) / np.sum(walls)), 2),
        "overflow": ovf_tot,
        "all_halted": halted_min == repeats,
    }

