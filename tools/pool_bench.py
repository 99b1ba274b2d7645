"""Readiness-partitioned pool A/B: same-box interleaved flat-vs-indexed
bench at client-army pool sizes (the ISSUE-13 acceptance evidence).

For each army config (raftlog / kvchaos, ``army=True``, history +
latency taps on) at pool sizes >= 2048 this tool:

1. runs the SAME pre-seeded batch through the flat lowering
   (``pool_index=False`` — exactly the pre-ISSUE-13 program) and the
   indexed one (``pool_index=True``) and asserts every SimState field
   except the derived tile summaries is bit-identical — traces,
   event pools, histories, latency sketches, overflow counts. Final-
   state equality implies identical verdicts for ANY invariant, so
   "violations identical" is covered by construction, not sampled;
2. times both sides INTERLEAVED (A/B/A/B, best-of per round) on one
   box, reporting seed-steps/s and the speedup — the same-box
   methodology BENCH_AB_r06 established (absolute cells on this
   container are throttle-depressed; compare A/B, not cross-round);
3. pins the small-pool guard: pools <= 512 resolve ``pool_index`` off
   by default, so the default program there is byte-identical to the
   previous engine — a 0% regression by construction, asserted from
   the resolution rule itself.

Usage:
    python tools/pool_bench.py            > BENCH_AB_r07.txt   # full
    python tools/pool_bench.py --smoke                         # make check

Exit 0 iff every identity holds (and, in full mode, every measured
speedup clears the 2x acceptance floor).
"""

from __future__ import annotations

import dataclasses
import sys
import time

import _bootstrap  # noqa: F401  (sys.path for tools/)

import numpy as np

import jax
from jax import lax

from madsim_tpu.chaos import CrashStorm, FaultPlan, GrayFailure
from madsim_tpu.engine import (
    POOL_INDEX_STATE_FIELDS,
    Build,
    EngineConfig,
    LatencySpec,
    make_init,
    make_step,
)
from madsim_tpu.models import make_kvchaos, make_raftlog
from madsim_tpu.models import kvchaos as kv_mod
from madsim_tpu.models import raftlog as rl_mod

ACCEPT_SPEEDUP = 2.0  # the ISSUE-13 acceptance floor (full mode only)


def _army_setup(name: str, pool: int):
    """(workload, config, plan, latency) for one army config at one pool."""
    n_ops = max(pool // 2 - 64, 64)
    chaos = (
        CrashStorm(targets=tuple(range(5)), n=1, t_min_ns=50_000_000,
                   t_max_ns=200_000_000, down_min_ns=20_000_000,
                   down_max_ns=80_000_000),
        GrayFailure(targets=tuple(range(5)), n_links=1, mult_min=4,
                    mult_max=8, t_min_ns=30_000_000, t_max_ns=150_000_000,
                    dur_min_ns=50_000_000, dur_max_ns=150_000_000),
    )
    if name == "raftlog":
        wl = make_raftlog(record=True, army=True)
        army = rl_mod.client_army(n_ops=n_ops, t_min_ns=5_000_000,
                                  t_max_ns=3_000_000_000)
    elif name == "kvchaos":
        wl = make_kvchaos(record=True, army=True,
                          hist_capacity=80 + 4 * n_ops)
        army = kv_mod.client_army(n_ops=n_ops, t_min_ns=5_000_000,
                                  t_max_ns=3_000_000_000)
    else:
        raise SystemExit(f"unknown army config {name!r}")
    plan = FaultPlan((army,) + chaos)
    cfg = EngineConfig(pool_size=pool, loss_p=0.02,
                       clog_backoff_max_ns=2_000_000_000)
    return wl, cfg, plan, LatencySpec(ops=n_ops, phases=3)


def _build(wl, cfg, plan, lat, n_steps, pool_index):
    build = Build(
        layout="scatter", pool_index=pool_index, plan_slots=plan.slots,
        latency=lat,
    )
    step = jax.vmap(make_step(wl, cfg, build))

    def run(st):
        final, _ = lax.scan(
            lambda s, _: (step(s), None), st, None, length=n_steps
        )
        return final

    return jax.jit(run), make_init(wl, cfg, build)


def _state_fields(st):
    return {
        f.name: np.asarray(getattr(st, f.name))
        for f in dataclasses.fields(st)
        if f.name not in POOL_INDEX_STATE_FIELDS
    }


def ab_config(name: str, pool: int, n_seeds: int, n_steps: int,
              rounds: int) -> tuple[bool, float]:
    wl, cfg, plan, lat = _army_setup(name, pool)
    seeds = np.arange(n_seeds, dtype=np.uint64)
    rows = plan.compile_batch(seeds, wl=wl)
    run_a, init_a = _build(wl, cfg, plan, lat, n_steps, pool_index=False)
    run_b, init_b = _build(wl, cfg, plan, lat, n_steps, pool_index=True)
    st_a, st_b = init_a(seeds, rows), init_b(seeds, rows)

    # ---- identity (and compile, outside the timed windows) ----
    out_a = jax.block_until_ready(run_a(st_a))
    out_b = jax.block_until_ready(run_b(st_b))
    fa, fb = _state_fields(out_a), _state_fields(out_b)
    diverged = [
        k for k in fa
        if fa[k].shape != fb[k].shape or not np.array_equal(fa[k], fb[k])
    ]
    lat_ops = int(np.asarray(out_a.lat_count).sum())
    hist_drops = int(np.asarray(out_a.hist_drop).sum())
    pool_drops = int(np.asarray(out_a.overflow).sum())
    print(f"  identity: {'OK' if not diverged else f'DIVERGED {diverged}'} "
          f"over {len(fa)} fields (traces, pools, histories, sketches); "
          f"{lat_ops} army ops completed, hist drops {hist_drops}, "
          f"pool drops {pool_drops}")

    # ---- interleaved A/B ----
    walls_a, walls_b = [], []
    for _ in range(rounds):
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        jax.block_until_ready(run_a(st_a))
        walls_a.append(time.perf_counter() - t0)  # lint: allow(wall-clock)
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        jax.block_until_ready(run_b(st_b))
        walls_b.append(time.perf_counter() - t0)  # lint: allow(wall-clock)
    steps = n_seeds * n_steps
    rate_a = steps / min(walls_a)
    rate_b = steps / min(walls_b)
    speedup = rate_b / rate_a
    print(f"  throughput: flat {rate_a:,.0f} seed-steps/s | indexed "
          f"{rate_b:,.0f} seed-steps/s | speedup {speedup:.2f}x "
          f"(interleaved best-of-{rounds}, "
          f"{1e9 * min(walls_a) / steps:.0f} -> "
          f"{1e9 * min(walls_b) / steps:.0f} ns/seed-step)")
    return not diverged, speedup


def main() -> None:
    smoke = "--smoke" in sys.argv[1:]
    plat = jax.devices()[0].platform
    mode = "smoke" if smoke else "full"
    print(f"# pool-bench ({mode}): readiness-partitioned pool A/B, "
          f"platform={plat}")

    # small-pool guard: <= 512 resolves the index OFF by default, so
    # the default program is the pre-ISSUE-13 one — 0% regression by
    # construction (a real check, not an assert: gates must survive -O)
    if Build(layout="scatter").resolve(
        make_raftlog(record=True, army=True), EngineConfig(pool_size=512)
    ).pool_index:
        print("# FAIL: pool_index auto-resolved ON at pool_size=512 — "
              "the small-pool no-regression guarantee is broken")
        sys.exit(1)
    print("# small-pool guard: pool_size<=512 defaults to the flat "
          "lowering (identical program, 0% regression by construction)")

    if smoke:
        cells = [("raftlog", 2048, 48, 200, 1)]
    else:
        cells = [
            ("raftlog", 2048, 192, 250, 3),
            ("raftlog", 8192, 96, 250, 3),
            ("kvchaos", 2048, 192, 250, 3),
            ("kvchaos", 8192, 96, 250, 3),
        ]

    ok = True
    for name, pool, n_seeds, n_steps, rounds in cells:
        print(f"== {name} army=True pool_size={pool} n_seeds={n_seeds} "
              f"n_steps={n_steps} ==")
        ident, speedup = ab_config(name, pool, n_seeds, n_steps, rounds)
        ok &= ident
        if not smoke and speedup < ACCEPT_SPEEDUP:
            print(f"  FAIL: speedup {speedup:.2f}x below the "
                  f"{ACCEPT_SPEEDUP}x acceptance floor")
            ok = False
    print(f"# pool-bench: {'PASS' if ok else 'FAIL'}")
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
