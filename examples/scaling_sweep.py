"""Batch-size scaling sweep: sim-s/s across seeds x the six configs.

Produces SCALING_SWEEP.json: for each of the six benchmark
configs (the five BASELINE ones + raftlog), measure
simulated-seconds/sec at seed counts 1k/4k/16k/65k (256k extra for
raft; a single-seed cell extra for pingpong, BASELINE config 1).

Methodology (engine/measure.py): every cell is timed as >= 5 s-long
jitted dispatches — a ``fori_loop`` of independent seed-batches inside
ONE dispatch — so per-dispatch overhead is amortized below the noise
floor instead of dominating sub-second runs. Cells report the median
over 3 dispatches with min/max spread. A cell is
quotable only if ``overflow == 0`` and ``all_halted`` — check before
quoting. It runs on whatever JAX gives it (``JAX_PLATFORMS=cpu`` for
the CPU backend) and records the platform.

Usage: python examples/scaling_sweep.py [out.json] [--quick]
  --quick: 2 s dispatches, 2 measures (for smoke runs)
"""

from __future__ import annotations

import _bootstrap  # noqa: F401  (repo root on sys.path)

import json
import sys
import time

import jax

from madsim_tpu.engine import EngineConfig
from madsim_tpu.engine.measure import measure_throughput
from madsim_tpu.models import BENCH_SPECS

SEED_COUNTS = [1024, 4096, 16384, 65536]


def main():
    argv = sys.argv[1:]
    quick = "--quick" in argv
    args = [a for a in argv if not a.startswith("--")]
    out_path = args[0] if args else "SCALING_SWEEP.json"
    target_wall = 2.0 if quick else 5.0
    n_measure = 2 if quick else 3

    platform = jax.devices()[0].platform
    print(f"# platform={platform}", file=sys.stderr)

    rows = []
    for name, (mk, cfg_kw, _spec_seeds, max_steps) in BENCH_SPECS.items():
        counts = SEED_COUNTS + ([262144] if name == "raft" else [])
        if name == "pingpong":
            counts = [1] + counts  # BASELINE config 1 is single-seed
        for s in counts:
            t0 = time.monotonic()  # lint: allow(wall-clock)
            rec = measure_throughput(
                mk(), EngineConfig(**cfg_kw), max_steps, s,
                target_wall_s=target_wall, n_measure=n_measure,
                seed_mod=524288 if name == "raft" else 131072,
                min_size=min(2048, max(s // 4, 1)),
            )
            rec = {
                "config": name, "platform": platform, "quick": quick, **rec,
                "cell_wall_s": round(time.monotonic() - t0, 1),  # lint: allow(wall-clock)
            }
            rows.append(rec)
            print(json.dumps(rec), flush=True)

    doc = {"platform": platform, "rows": rows}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {out_path} ({platform})", file=sys.stderr)


if __name__ == "__main__":
    main()
