"""Tail-latency soak: open-loop client army, fleet sketches, the
gray-failure p99 blowup, and the guided SLO hunt. The LATENCY evidence
artifact.

Five certificates:

1. **Latency-off identity** — the latency tap + army markers change NO
   trace, across dense/scatter layouts and the compacted runner (the
   derived-state-only rule at soak scale).
2. **Sketch exactness** — the merged fleet sketch equals the histogram
   of the concatenated exact per-op latencies, device quantiles sit
   within one bucket of exact numpy quantiles, and the sharded merge
   (halves summed) equals the whole.
3. **Clean-run tail baseline vs gray-failure blowup** — kvchaos under
   army load alone, then the same load with a GrayFailure window over
   the client<->primary path: the faulted p99 must exceed the clean
   p99 by >= 2x (the tail signal the whole layer exists to see).
4. **SLO hunt: guided finds what uniform misses** — over one
   gray-failure space, the SLO bound is calibrated AT the worst
   provable window-p99 bucket that uniform sampling reaches at the
   full budget, so a breach requires pushing the tail at least two
   ladder buckets (~40%) past uniform's extreme. Uniform finds zero
   by construction (asserted); the latency-coverage-guided campaign
   must find one anyway at equal budget — search reaching tails
   sampling cannot.
5. **Find -> shrink -> replay -> explain** — the hunt's first breach is
   ddmin-shrunk (army slots and fault slots alike), the shrunk literal
   replays to the identical violation + trace, and ``obs.explain``
   narrates the tail percentiles of the breaching seed.

Usage: python tools/latency_soak.py [n_seeds] > LATENCY_r12.txt
Exit 0 iff every certificate holds.
"""

import _bootstrap  # noqa: F401  (repo root on sys.path)

import dataclasses
import sys
import time

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir", "/tmp/jax_test_cache")
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from madsim_tpu import check, explore, obs  # noqa: E402
from madsim_tpu.chaos import FaultPlan, GrayFailure, shrink_plan  # noqa: E402
from madsim_tpu.engine import (  # noqa: E402
    EngineConfig,
    LatencySpec,
    lat_bucket,
    search_seeds,
)
from madsim_tpu.engine.core import N_LAT_BUCKETS  # noqa: E402
from madsim_tpu.models import kvchaos as KV  # noqa: E402
from madsim_tpu.parallel import merge_latency  # noqa: E402

N_OPS = 64
MAX_STEPS = 4000
# two ~268 ms measurement windows over the arrival span: wide enough
# that cert 4's uniform blips (<= 80 ms) can never dominate a window
SPEC = LatencySpec(ops=N_OPS, phases=2, phase_ns=1 << 28)

# each army op is a 3-round session (client -> primary -> client x3):
# the multi-round shape real client calls have, and what makes a
# windowed tail breach require SUSTAINED slowness instead of one blip
WL = KV.make_kvchaos(
    writes=20, n_replicas=2, chaos=False, army=True, army_probes=3
)
ARMY = KV.client_army(
    n_ops=N_OPS, t_min_ns=5_000_000, t_max_ns=500_000_000, n_replicas=2
)
CFG = EngineConfig(pool_size=160, time_limit_ns=700_000_000)
# the client<->primary probe path: node 3 is the client, 0 the primary
GRAY = GrayFailure(
    targets=(0, 3), n_links=1, mult_min=8, mult_max=16,
    t_min_ns=20_000_000, t_max_ns=250_000_000,
    dur_min_ns=250_000_000, dur_max_ns=450_000_000,
)

_ONES = lambda v: np.ones(np.asarray(v["halted"]).shape[0], bool)  # noqa: E731


def main() -> None:
    n_seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 2048
    failures = []
    t_all = time.monotonic()  # lint: allow(wall-clock)
    print(f"# latency soak: platform={jax.devices()[0].platform}, "
          f"n_seeds={n_seeds}")
    army_plan = FaultPlan((ARMY,), name="army-clean")
    gray_plan = FaultPlan((ARMY, GRAY), name="army-gray")

    # ---- certificate 1: latency-off identity ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 1: latency-off identity (layouts + compact) ==")
    s_id = min(n_seeds, 256)
    kw = dict(n_seeds=s_id, max_steps=MAX_STEPS, plan=gray_plan,
              require_halt=False)
    base = search_seeds(WL, CFG, _ONES, layout="scatter", **kw)
    rows = [
        ("scatter+latency", search_seeds(
            WL, CFG, _ONES, layout="scatter", latency=SPEC, **kw)),
        ("dense+latency", search_seeds(
            WL, CFG, _ONES, layout="dense", latency=SPEC, **kw)),
        ("compact+latency", search_seeds(
            WL, CFG, _ONES, compact=True, latency=SPEC, **kw)),
    ]
    ok1 = True
    for name, rep in rows:
        same = np.array_equal(base.traces, rep.traces)
        print(f"  {name}: traces {'identical' if same else 'DIVERGED'}")
        ok1 &= same
    same_sketch = np.array_equal(rows[0][1].lat_hist, rows[1][1].lat_hist)
    same_sketch &= np.array_equal(rows[0][1].lat_hist, rows[2][1].lat_hist)
    print(f"  sketches identical across lowerings: {same_sketch}")
    ok1 &= same_sketch
    if not ok1:
        failures.append("identity")
    print(f"cert1 {'PASS' if ok1 else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 2: sketch exactness at scale ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 2: sketch exactness vs exact per-op latencies ==")
    import jax as _jax

    from madsim_tpu.engine import Build, make_init, make_run_while

    # the acceptance scale: >= 4096 seeds. The sketch side goes through
    # obs.fleet_latency — the device-resident reduction that never
    # transfers a per-seed latency column; the GROUND TRUTH side
    # re-runs the same seeds with the per-op clocks pulled to host
    # (that transfer is the test's oracle, not the product path).
    s_ex = max(n_seeds, 4096) if n_seeds >= 2048 else min(n_seeds, 512)
    fl = obs.fleet_latency(
        WL, CFG, SPEC, n_seeds=s_ex, max_steps=MAX_STEPS, plan=gray_plan,
    )
    seeds = np.arange(s_ex, dtype=np.uint64)
    build = Build(plan_slots=gray_plan.slots, latency=SPEC)
    init = make_init(WL, CFG, build)
    run = _jax.jit(make_run_while(WL, CFG, MAX_STEPS, build))
    out = _jax.block_until_ready(
        run(init(seeds, gray_plan.compile_batch(seeds, wl=WL)))
    )
    inv = np.asarray(out.lat_inv)
    resp = np.asarray(out.lat_resp)
    done = (inv >= 0) & (resp >= 0)
    lats = (resp - inv)[done]
    hist = np.asarray(out.lat_hist)
    merged = fl.hist.sum(axis=0)
    exact_hist = np.bincount(lat_bucket(lats), minlength=N_LAT_BUCKETS)
    ok_merge = np.array_equal(merged, exact_hist)
    halves = merge_latency(hist[: s_ex // 2]) + merge_latency(hist[s_ex // 2:])
    ok_shard = np.array_equal(merge_latency(hist), halves)
    ok_paths = np.array_equal(fl.hist, merge_latency(hist))
    print(f"  {int(done.sum())} completed ops over {s_ex} seeds; "
          f"fleet sketch (device-resident) == exact bucketing: {ok_merge}; "
          f"sharded merge == whole: {ok_shard}; "
          f"fleet_latency == merge of state columns: {ok_paths}")
    ok2 = ok_merge and ok_shard and ok_paths
    for q in (0.5, 0.9, 0.99, 0.999):
        sk = int(obs.hist_quantile_bucket(merged, q))
        ex = int(lat_bucket(float(np.quantile(lats, q))))
        hit = abs(sk - ex) <= 1
        print(f"  p{q*100:g}: sketch bucket {sk}, exact bucket {ex} "
              f"({'within one bucket' if hit else 'OFF'})")
        ok2 &= hit
    if not ok2:
        failures.append("exactness")
    print(f"cert2 {'PASS' if ok2 else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 3: clean baseline vs gray-failure blowup ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 3: clean tail baseline vs GrayFailure blowup ==")
    fl_clean = obs.fleet_latency(
        WL, CFG, SPEC, n_seeds=n_seeds, max_steps=MAX_STEPS, plan=army_plan,
    )
    fl_gray = obs.fleet_latency(
        WL, CFG, SPEC, n_seeds=n_seeds, max_steps=MAX_STEPS, plan=gray_plan,
    )
    print("  -- clean run --")
    print("  " + fl_clean.format().replace("\n", "\n  "))
    print("  -- gray failure over the probe path --")
    print("  " + fl_gray.format().replace("\n", "\n  "))
    p99c, p99g = fl_clean.quantile(0.99), fl_gray.quantile(0.99)
    ratio = p99g / max(p99c, 1)
    print(f"  p99 clean={p99c / 1e6:.2f}ms gray={p99g / 1e6:.2f}ms "
          f"blowup={ratio:.2f}x")
    ok3 = p99c > 0 and ratio >= 2.0
    if not ok3:
        failures.append("blowup")
    print(f"cert3 {'PASS' if ok3 else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 4: guided SLO hunt vs uniform at equal budget ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 4: SLO hunt — guided vs uniform at equal budget ==")
    from madsim_tpu.engine import lat_bucket_hi

    # the hunt space: BLIPS only — slow windows of 50-80 ms, far
    # shorter than a 3-round session under slowness. A blip bounds the
    # sum of slowed rounds structurally (later rounds launch after the
    # heal), so no uniform draw can slow a session end to end; the
    # mutation surface CAN — retiming the unslow event stretches the
    # window across a whole measurement phase (the legal template
    # range), which is the schedule shape the hunt must discover
    hunt_gray = GrayFailure(
        targets=(0, 1, 2, 3), n_links=1, mult_min=4, mult_max=12,
        t_min_ns=20_000_000, t_max_ns=600_000_000,
        dur_min_ns=50_000_000, dur_max_ns=80_000_000,
    )
    space = FaultPlan((ARMY, hunt_gray), name="slo-hunt")
    gens, batch = 8, max(n_seeds // 8, 32)
    budget = gens * batch
    min_ops = 8
    uni = search_seeds(
        WL, CFG, _ONES, plan=space, n_seeds=budget,
        max_steps=MAX_STEPS, require_halt=False, latency=SPEC,
    )
    # calibrate: the worst provable window-p99 bucket uniform reached
    total = uni.lat_hist.sum(axis=-1)  # (S, P)
    qb = obs.hist_quantile_bucket(uni.lat_hist, 0.99)
    qb = np.where(total >= min_ops, qb, -1)
    worst_uni = int(qb.max())
    bound = int(lat_bucket_hi(worst_uni))
    slo = check.slo_bounded(bound, q=0.99, min_ops=min_ops)
    uni_found = int(check.slo_breaches(
        uni.lat_hist, bound, q=0.99, min_ops=min_ops
    ).sum())
    print(f"  uniform worst window-p99 bucket over {budget} sims: "
          f"{worst_uni} (<= {bound / 1e6:.2f}ms)")
    print(f"  SLO: p99 <= {bound / 1e6:.2f}ms per "
          f"{SPEC.phase_ns / 1e6:.0f}ms window, min {min_ops} ops — a "
          f"breach must land >= 2 ladder buckets (~40%) past uniform's "
          f"extreme")
    rep = explore.run(
        WL, CFG, space, invariant=slo, generations=gens, batch=batch,
        root_seed=7, max_steps=MAX_STEPS, cov_words=64, latency=SPEC,
        log=lambda s: print(f"  {s}"),
    )
    print(f"  uniform: {uni_found} breach(es) in {budget} sims "
          f"(0 by construction); guided: {len(rep.violations)} in "
          f"{rep.sims} sims")
    ok4 = uni_found == 0 and len(rep.violations) > 0
    if not ok4:
        failures.append("hunt")
    print(f"cert4 {'PASS' if ok4 else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    # ---- certificate 5: shrink -> replay -> explain ----
    t0 = time.monotonic()  # lint: allow(wall-clock)
    print("== cert 5: ddmin-shrink the breach, replay, explain ==")
    ok5 = True
    if not rep.violations:
        print("  (no breach to shrink — cert 4 already failed)")
        ok5 = False
    else:
        entry = rep.violations[0]
        res = shrink_plan(
            WL, CFG, entry.seed, entry.plan, invariant=slo,
            max_steps=MAX_STEPS, latency=SPEC,
        )
        print("  " + res.banner().replace("\n", "\n  "))
        replay = explore.replay_entry(
            WL, CFG, dataclasses.replace(entry, plan=res.plan),
            invariant=slo, max_steps=MAX_STEPS, latency=SPEC,
        )
        exact = int(replay.traces[0]) == res.trace
        still = bool(~replay.ok[0])
        print(f"  replay: trace {'identical' if exact else 'DIVERGED'}, "
              f"breach {'reproduced' if still else 'LOST'}")
        ok5 = exact and still
        text = obs.explain(
            WL, CFG, entry.seed, plan=res.plan, invariant=slo,
            max_steps=MAX_STEPS, timeline_cap=4096, latency=SPEC,
        )
        has_lat = "--- latency:" in text and "p99<=" in text
        has_verdict = "VIOLATED" in text
        print("  explain excerpt:")
        for line in text.splitlines():
            if line.startswith("---") or "window [" in line or \
                    "slowest" in line:
                print(f"    {line}")
        print(f"  explain narrates percentiles: {has_lat}, "
              f"verdict line: {has_verdict}")
        ok5 = ok5 and has_lat and has_verdict
    if not ok5:
        failures.append("shrink-replay-explain")
    print(f"cert5 {'PASS' if ok5 else 'FAIL'} "
          f"({time.monotonic() - t0:.1f}s)")  # lint: allow(wall-clock)

    print(f"# done in {time.monotonic() - t_all:.0f}s wall")  # lint: allow(wall-clock)
    if failures:
        print(f"LATENCY SOAK FAIL: {failures}")
        sys.exit(1)
    print("LATENCY SOAK PASS")


if __name__ == "__main__":
    main()
