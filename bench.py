"""Headline benchmark: simulated-seconds/sec/chip across the BASELINE configs.

Reports all five BASELINE.md benchmark configs plus raftlog (the raft
log-replication family, beyond-BASELINE) and prints the headline JSON
line (raft, the north-star workload) LAST. Every quoted cell is a
multi-second sized dispatch with reported spread (engine/measure.py) on
BOTH platforms; the deliberately single-seed pingpong config is quoted
as latency (wall us per complete sim), not throughput:

    {"metric": "sim_seconds_per_sec_per_chip", "value": N,
     "unit": "sim_s/s/chip", "vs_baseline": N / 200000,
     "platform": "...", "n_seeds": N, "configs": {...}}

vs_baseline is against the BASELINE.json north-star target of 200,000
simulated-seconds/sec (65,536-seed batch); per-chip normalization keeps
the number comparable across slice sizes.

Every row names the platform, ``device_kind`` and device count JAX
reported. The parent never imports jax and runs each config in a child
process, one at a time, so only one process ever holds the chip. There
is no fallback: with ``JAX_PLATFORMS`` unset, a child that finds no TPU
exits nonzero and so does the bench; a failed config fails the bench.
With ``JAX_PLATFORMS=cpu`` the rows say ``cpu`` and their metric names
carry a ``cpu_`` prefix, so a CPU number never wears a device metric's
name. The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` in this checkout.
"""

import json
import os
import subprocess
import sys
import time

TARGET = 200_000.0  # BASELINE.json north star, sim_s/s

HERE = os.path.dirname(os.path.abspath(__file__))
# children inherit it; jax reads it once, at import
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(HERE, ".jax_cache"))

# name -> (n_seeds, max_steps). Steps are run_while caps; the
# runner exits as soon as every seed halts. On the CPU backend seed
# counts are capped by a time budget (CPU_CELL_TARGET_S).
# The workload factories, engine configs (pool sizes sized to measured
# peak in-flight event counts with zero overflow — raft/broadcast/
# kvchaos 40, microbench/pingpong 32, raftlog 64), seed counts and
# step caps live in
# madsim_tpu.models.BENCH_SPECS, shared with the cross-backend
# determinism artifact (examples/cross_backend_check.py). This mirror
# keeps the parent process jax-free (one process holds the chip):
#   name -> (n_seeds, max_steps)
CONFIGS = {
    "raft": (65536, 600),
    "microbench": (1024, 1100),
    "pingpong": (1, 300),
    "broadcast": (16384, 500),
    "kvchaos": (4096, 900),
    "raftlog": (16384, 4000),
}
# BASELINE.md config 1 specifies the single-seed pingpong on the CPU sim
# runtime — a lone seed cannot amortize accelerator dispatch overhead
CPU_ONLY_CONFIGS = {"pingpong"}
# CPU sizing: seeds are capped by a measured time budget, not a fixed
# count — a tiny calibration batch estimates per-seed cost and the
# child picks the largest power-of-two batch whose single-batch wall is
# ~CPU_CELL_TARGET_S, so every measured dispatch stays multi-second
CPU_CELL_TARGET_S = 3.0
CPU_CALIBRATE_SEEDS = 256


def _run_child(config: str, n_seeds: int, n_steps: int, timeout: float):
    """Run one config in a subprocess; return its JSON row. Raises on
    a failed or silent child: there is no fallback to hide it."""
    env = dict(os.environ, BENCH_CHILD=config, BENCH_SEEDS=str(n_seeds),
               BENCH_STEPS=str(n_steps))
    if config in CPU_ONLY_CONFIGS:
        env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"bench: {config} failed (rc={proc.returncode}): "
            f"{(proc.stderr or '')[-800:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def metric_name(name: str, platform: str) -> str:
    """Device metric names belong to device numbers only."""
    return name if platform == "tpu" else f"{platform}_{name}"


def parent() -> None:
    timeout = float(os.environ.get("BENCH_CONFIG_TIMEOUT", "900"))
    results, failed = {}, []
    for config, (n_seeds, n_steps) in CONFIGS.items():
        res = _run_child(config, n_seeds, n_steps, timeout)
        print(json.dumps(res), flush=True)
        if res.get("error"):
            failed.append(config)
        else:
            results[config] = res
    if failed:
        # before the summary, which needs the raft row
        raise SystemExit(f"bench: configs failed: {failed}")
    print(json.dumps(summary_dict(results)), flush=True)


def summary_dict(results: dict) -> dict:
    """The one parent summary line every bench artifact ends with —
    consumers ``tail -1`` for the headline (raft) value + vs_baseline."""
    head = results["raft"]
    value = float(head["value"])
    return {
        "metric": head["metric"],
        "value": round(value, 2),
        "unit": "sim_s/s/chip",
        "vs_baseline": round(value / TARGET, 4),
        "platform": head["platform"],
        "device_kind": head["device_kind"],
        "device_count": head["device_count"],
        "n_seeds": int(head["n_seeds"]),
        "configs": {
            k: {f: v.get(f) for f in ("metric", "value", "unit", "n_seeds",
                                      "platform", "device_kind",
                                      "device_count", "spread_pct")}
            for k, v in results.items()
        },
    }


# ---------------------------------------------------------------- child


def child(config: str) -> None:
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
           "device_count": len(devs)}
    if not os.environ.get("JAX_PLATFORMS") and dev["platform"] != "tpu":
        raise SystemExit(
            f"bench: no TPU found (JAX gave {dev}); set JAX_PLATFORMS=cpu "
            f"to measure the CPU backend on purpose"
        )

    import numpy as np

    from madsim_tpu.engine import (
        Build,
        EngineConfig,
        make_init,
        make_run_compacted,
        time32_eligible,
    )
    from madsim_tpu.models import BENCH_SPECS

    n_seeds = int(os.environ.get("BENCH_SEEDS", "8192"))
    n_steps = int(os.environ.get("BENCH_STEPS", "600"))
    if config not in BENCH_SPECS:
        raise SystemExit(f"unknown config {config}")
    factory, cfg_kwargs, _spec_seeds, _spec_steps = BENCH_SPECS[config]
    wl, cfg = factory(), EngineConfig(**cfg_kwargs)

    # int32 event times whenever the (workload, config) bounds allow:
    # a value-identical lowering (test-pinned against int64), already
    # the accelerator default, and measured ~8% faster on CPU too —
    # the bench quotes the engine's fastest value-identical program,
    # exactly as it does for layout and compaction
    build = Build(time32=True if time32_eligible(wl, cfg) else None)
    init = make_init(wl, cfg, build)

    # one min_size policy for BOTH platforms, so a config's accelerator
    # and CPU numbers describe the same compaction program
    def _min_size(s: int) -> int:
        return min(2048, max(s // 4, 1))

    from madsim_tpu.engine.measure import measure_throughput

    accel = dev["platform"] != "cpu"
    n_chips = dev["device_count"]
    # seeds wrap inside the range each pool size was verified
    # overflow-free for (models.BENCH_SPECS sizing note): raft over
    # 0..524287, the rest over the sweep's 0..131071
    seed_mod = 524288 if config == "raft" else 131072

    if config == "pingpong":
        # BASELINE config 1 is a deliberately single-seed sim — one seed
        # cannot amortize dispatch overhead, so a throughput quote would
        # measure the transport. Quote it as LATENCY (engine/measure.py
        # measure_latency: repeats independent single-seed sims packed
        # into multi-second dispatches, median wall-per-sim).
        from madsim_tpu.engine.measure import measure_latency

        rec = measure_latency(wl, cfg, n_steps, seed_mod=seed_mod, build=build)
        if rec["overflow"] or not rec["all_halted"]:
            print(
                json.dumps(
                    {
                        "config": config,
                        "error": "pool_overflow"
                        if rec["overflow"]
                        else "not_all_halted",
                        "drops": rec["overflow"],
                    }
                )
            )
            return
        print(
            json.dumps(
                {
                    "config": config,
                    "metric": metric_name("wall_us_per_sim", dev["platform"]),
                    "value": rec["wall_us_per_sim_median"],
                    "unit": "us/sim",
                    **dev,
                    "n_seeds": 1,
                    "repeats_per_dispatch": rec["repeats"],
                    "dispatch_walls_s": rec["dispatch_walls_s"],
                    "spread_pct": rec["spread_pct"],
                    "sim_s_per_s": rec["sim_s_per_s"],
                }
            )
        )
        return

    if not accel and n_seeds > CPU_CALIBRATE_SEEDS:
        # CPU sizing: estimate per-seed cost on a small batch,
        # then pick the largest power-of-two batch whose single-batch
        # wall is ~CPU_CELL_TARGET_S (capped at the spec seed count) —
        # measure_throughput then packs repeats if the batch is shorter
        run = make_run_compacted(
            wl, cfg, n_steps, build,
            min_size=_min_size(CPU_CALIBRATE_SEEDS), fields=("now",),
        )
        jax.block_until_ready(
            run.compute(init(np.arange(CPU_CALIBRATE_SEEDS, dtype=np.uint64)))
        )  # compile outside the timed window
        cal = init(np.arange(CPU_CALIBRATE_SEEDS, dtype=np.uint64))
        t0 = time.perf_counter()  # lint: allow(wall-clock)
        jax.block_until_ready(run.compute(cal))
        per_seed = (time.perf_counter() - t0) / CPU_CALIBRATE_SEEDS  # lint: allow(wall-clock)
        fit = int(CPU_CELL_TARGET_S / max(per_seed, 1e-9))
        sized = CPU_CALIBRATE_SEEDS
        while sized * 2 <= min(fit, n_seeds):
            sized *= 2
        n_seeds = sized

    # Both platforms: sized dispatches (engine/measure.py). Each dispatch
    # packs `repeats` independent seed-batches into one jitted
    # fori_loop >= target_wall_s long; the quoted rate is the median
    # over n_measure dispatches.
    rec = measure_throughput(
        wl, cfg, n_steps, n_seeds,
        target_wall_s=5.0 if accel else 3.5,
        n_measure=5 if accel else 3,
        seed_mod=seed_mod, min_size=_min_size(n_seeds), build=build,
    )
    # the small pool sizes are only valid while nothing overflows; a
    # silent drop would skew the metric. Reported as a JSON error row;
    # the parent prints it and fails the bench.
    if rec["overflow"]:
        print(
            json.dumps(
                {"config": config, "error": "pool_overflow", "drops": rec["overflow"]}
            )
        )
        return
    print(
        json.dumps(
            {
                "config": config,
                "metric": metric_name("sim_seconds_per_sec_per_chip",
                                      dev["platform"]),
                "value": round(rec["sim_s_per_s_median"] / n_chips, 2),
                "unit": "sim_s/s/chip",
                **dev,
                "n_seeds": n_seeds,
                "repeats_per_dispatch": rec["repeats"],
                "dispatch_walls_s": rec["dispatch_walls_s"],
                "spread_pct": rec["spread_pct"],
                "all_halted": rec["all_halted"],
            }
        )
    )


def main() -> None:
    config = os.environ.get("BENCH_CHILD")
    if config:
        child(config)
    else:
        parent()


if __name__ == "__main__":
    main()
